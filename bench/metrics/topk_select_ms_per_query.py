"""Device self time of the fused rung program's ``rung.select`` stage
(the top-k selection over every column, the survivor count, the column
order restore) per query row answered, in ms: the trace's ops inside
``rung_dispatch`` spans that the program's scope table puts under the
scope (``bench/stages.py``)."""

from bench import stages


def read(win):
    return stages.stage_ms_per_query(win, "rung.select")
