"""Share of the HBM roofline of the top-k dispatches, in %: the least
bytes each dispatch must read (``roofline.dispatch_bytes``: suffix
columns, lanes and trie levels, once per dispatch) times the fused
dispatches of the window, over the chip's peak bandwidth, over the
device time of those dispatches."""


def read(win):
    t = win.dispatch_device_s()
    fused = win.dispatch.get("fused", 0)
    if not t or not fused or win.peaks is None:
        return None
    least_s = fused * win.bytes_per_dispatch / win.peaks["hbm_bytes_per_s"]
    return least_s / t * 100.0
