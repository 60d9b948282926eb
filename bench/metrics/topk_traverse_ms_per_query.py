"""Device self time of the fused rung program's ``rung.traverse`` stage
(every segment's trie traversal and the root-plane scatter) per query
row answered, in ms: the trace's ops inside ``rung_dispatch`` spans that
the program's scope table puts under the scope (``bench/stages.py``)."""

from bench import stages


def read(win):
    return stages.stage_ms_per_query(win, "rung.traverse")
