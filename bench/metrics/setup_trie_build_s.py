"""Host seconds of the trie builds before the window (the corpus load's
seals and merges): the program's ``trie_build`` span tally
(``repro.obs.span_totals``) less the window's own ``trie_build`` spans.
Also logs the set-up's split by span (``bench/stages.py``)."""

from bench import stages


def read(win):
    spans = stages.setup_span_seconds(win)
    if not spans or "trie_build" not in spans:
        return None
    return spans["trie_build"][1]
