"""Least bytes a top-k dispatch must read, from the index's shapes, and
the chip's peaks.

Per dispatch (one fused rung program over the whole segment stack) the
algorithm has to read, once, whatever the query tile:

* every column's suffix words: ``row_words`` uint32 words per column,
  where a segment collapsing at depth ``ls`` keeps S = L - ls characters,
  one packed word when b·S <= 32 and b·ceil(S/32) bit-plane words
  otherwise; a delta-buffer column is full length, b·ceil(L/32) words;
* 9 bytes of lanes per column (base offset, global id, liveness);
* every trie level array of every segment.

This is a floor: the program reads these bytes at least once per
dispatch, so bytes / peak bandwidth / device time is a share of the
roofline that a faster program can only raise.
"""

from __future__ import annotations

import json
import pathlib
from typing import Iterable, Sequence, Tuple

LANE_BYTES = 9
WORD_BYTES = 4
PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def suffix_row_words(L: int, b: int, ls: int) -> int:
    S = L - ls
    if b * S <= 32:
        return 1
    return b * -(-S // 32)


def dispatch_bytes(L: int, b: int,
                   segments: Sequence[Tuple[int, int, int]],
                   delta_rows: int) -> int:
    """``segments``: (rows, ls, trie level bytes) per sealed segment."""
    total = 0
    for rows, ls, level_bytes in segments:
        total += rows * (suffix_row_words(L, b, ls) * WORD_BYTES
                         + LANE_BYTES) + level_bytes
    total += delta_rows * (b * -(-L // 32) * WORD_BYTES + LANE_BYTES)
    return total


def peaks_for(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; a device that is not in the
    table is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}")
    return table[device_kind]


def leaf_bytes(leaves: Iterable) -> int:
    return int(sum(int(getattr(x, "nbytes", 0)) for x in leaves))
