"""A run's set-up limit: the process ends when set-up passes it.

Set-up is what ``setup_s`` measures, from process start to the first
request of the window.  Each cell's own file gives its limit,
``setup_limit_s`` (``spec.load_cell`` refuses a cell without one).  A
program that cannot set the cell up in that time, such as one whose
warm-up compiles for tens of minutes, would otherwise hold the chip
until the run's own time limit cuts it; the watchdog ends the run at the
limit instead: one line on standard error that names the cell, the
limit, the seconds elapsed and the set-up stage then running, nothing on
standard output, and exit code ``EXIT_CODE``.

The watchdog is a daemon thread that calls ``os._exit``.  A signal
handler would run only once the main thread came back from native code,
so it would wait for an XLA compile to end; the thread fires in the
middle of one.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Callable, Optional

EXIT_CODE = 4


class SetupLimit:
    """Armed on construction for what is left of ``limit_s`` after
    ``t_start`` (a ``time.perf_counter`` reading at process start);
    ``disarm`` stops it and takes ``setup_s``."""

    def __init__(self, cell: str, limit_s: float, t_start: float):
        self.cell, self.limit_s, self.t_start = cell, float(limit_s), t_start
        self.setup_s: Optional[float] = None
        self._lock = threading.Lock()
        self._disarmed = threading.Event()
        self._stage = "start-up"
        self._t_stage = t_start
        self._detail: Optional[Callable[[], str]] = None
        threading.Thread(target=self._watch, name="bench-setup-limit",
                         daemon=True).start()

    def stage(self, name: str,
              detail: Optional[Callable[[], str]] = None) -> None:
        """Name the set-up stage that starts now; ``detail()`` says how
        far it got, should the limit pass inside it."""
        with self._lock:
            self._stage, self._t_stage = name, time.perf_counter()
            self._detail = detail

    def disarm(self, now: Optional[float] = None) -> float:
        """Stop the watchdog.  The first call takes ``setup_s``, the
        seconds from ``t_start`` to ``now`` (default: this instant);
        later calls return it unchanged."""
        with self._lock:
            if self.setup_s is None:
                t = time.perf_counter() if now is None else now
                self.setup_s = t - self.t_start
            self._disarmed.set()
        return self.setup_s

    def _watch(self) -> None:
        left = self.t_start + self.limit_s - time.perf_counter()
        if self._disarmed.wait(max(0.0, left)):
            return
        with self._lock:
            stage, t_stage, detail = self._stage, self._t_stage, self._detail
        try:
            reached = f"; {detail()}" if detail is not None else ""
        except Exception as e:             # noqa: BLE001 - report, then end
            reached = f"; its progress could not be read: {e!r}"
        with self._lock:
            if self._disarmed.is_set():
                return
            now = time.perf_counter()
            print(f"bench: {self.cell}: set-up passed its limit "
                  f"(setup_limit_s {self.limit_s:g}) after "
                  f"{now - self.t_start:.3f} s, in stage {stage} "
                  f"({now - t_stage:.3f} s into it{reached}); no result",
                  file=sys.stderr, flush=True)
            os._exit(EXIT_CODE)
