"""The pauses of this process's garbage collector, watched and never
changed: a callback in `gc.callbacks` notes when each collection starts and
how long it runs. A collection holds the interpreter's lock, so a server in
this process answers nothing while one runs."""

from __future__ import annotations

import gc
import time


class CollectorPauses:
    def __init__(self):
        self.events: list[tuple[float, int, float]] = []
        self._started = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._started = now
        else:
            self.events.append((self._started, int(info["generation"]),
                                now - self._started))

    def between(self, lo: float, hi: float) -> list[tuple[float, int, float]]:
        """(start, generation, seconds) of the collections that started in
        [lo, hi) of time.perf_counter()."""
        return [e for e in list(self.events) if lo <= e[0] < hi]

    def close(self) -> None:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)
