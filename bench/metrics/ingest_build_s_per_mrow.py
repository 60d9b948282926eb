"""Host seconds of the insert batches that sealed or merged segments
(trie builds in ``core/trie_builder.py``), per million rows
acknowledged: the scheduler's batch spans of the window during which
the index reported a flush or merge."""


def read(win):
    rows = win.extra.get("rows_acked", 0)
    if "seal_merge_s" not in win.extra or not rows:
        return None
    return win.extra["seal_merge_s"] / (rows / 1e6)
