"""Stall watch: say what a serving process was doing while it answered
nothing.

A thread ticks every 50 ms. When requests are waiting and no dispatch has
been made or completed for `threshold_s` (0.4 s), or when its own tick
comes that late (the process did not run), it takes one report:
every thread's stack, how late its own tick came, and what the process and
the machine did meanwhile. The last two tell the three kinds of stall
apart without a stack:

  - the tick itself came late and the process burnt no CPU in the gap: no
    thread of this process ran. The machine (a paused or starved virtual
    machine: `steal`, or all of `/proc/stat` standing still), or every
    thread blocked in the kernel (`iowait`, memory `pressure`);
  - the tick came late and the process burnt about one core for the gap:
    one call held the interpreter's lock (the stack of the thread that is
    not waiting names it);
  - the ticks came on time: a thread is blocked on something (a lock, the
    device, the store) while the others run; the stacks name it.

Always on in an engine server with a batcher (ISSUE 31: the filtered serve
cell froze for 1-4.7 s in a quarter of its runs and nothing in the program
could say why); the cost is twenty wake-ups a second that read two
integers. Reports: `reports()`, the log at WARNING, the counters
`pio_serve_stalls_total` and `pio_serve_stall_seconds_total`.
"""

from __future__ import annotations

import logging
import sys
import threading
import time
import traceback
from collections import deque
from typing import Callable, Dict, List, Optional

logger = logging.getLogger(__name__)

TICK_S = 0.05
THRESHOLD_S = 0.4
_STACK_CHARS = 24_000
_CPU_FIELDS = ("user", "nice", "system", "idle", "iowait", "irq",
               "softirq", "steal")


def _machine() -> Dict[str, float]:
    """The machine's CPU seconds by state since boot (`/proc/stat`, all
    cores summed) and the memory / io stall totals (`/proc/pressure`,
    seconds some task was stalled); {} where the kernel does not say."""
    out: Dict[str, float] = {}
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()[1:]
        hz = 100.0      # USER_HZ on every Linux this runs on
        for name, v in zip(_CPU_FIELDS, fields):
            out[name] = int(v) / hz
    except (OSError, ValueError):
        pass
    for res in ("memory", "io", "cpu"):
        try:
            with open(f"/proc/pressure/{res}") as f:
                for line in f:
                    if line.startswith("some"):
                        out[f"pressure_{res}"] = int(
                            line.rsplit("total=", 1)[1]) / 1e6
        except (OSError, ValueError, IndexError):
            pass
    return out


def _stacks() -> str:
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    parts = []
    for ident, frame in sys._current_frames().items():
        if ident == me:
            continue
        parts.append(f"-- {names.get(ident, '?')} ({ident})\n"
                     + "".join(traceback.format_stack(frame)[-12:]))
    return "\n".join(parts)[:_STACK_CHARS]


class StallWatch:
    def __init__(self, waiting: Callable[[], int],
                 progress: Callable[[], object], metrics=None,
                 tick_s: float = TICK_S, threshold_s: float = THRESHOLD_S):
        self._waiting, self._progress = waiting, progress
        self.tick_s, self.threshold_s = tick_s, threshold_s
        self._reports: deque = deque(maxlen=16)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.n_stalls = 0
        self.stall_s = 0.0
        #: the latest tick's lateness at its worst since start: how long
        #: this process has gone without running a thread that wanted to
        self.max_tick_late_s = 0.0
        if metrics is not None:
            metrics.counter_func(
                "pio_serve_stalls_total",
                "Times requests waited while no dispatch was made or "
                f"completed for {threshold_s} s (obs/stallwatch.py keeps "
                "the stacks)", lambda: self.n_stalls)
            metrics.counter_func(
                "pio_serve_stall_seconds_total",
                "Seconds inside such stalls, from the last progress "
                "before each to the first after it", lambda: self.stall_s)

    def start(self) -> "StallWatch":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="pio-stall-watch")
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None

    def reports(self) -> List[dict]:
        """The last stalls, oldest first; one still going on has no
        `duration_s` yet."""
        return [dict(r) for r in self._reports]

    def _loop(self):
        last = self._progress()
        since = time.perf_counter()          # the last progress seen
        tick = since
        # what the process and the machine had done by `base_t`, taken anew
        # once a second while there is progress: a report's differences
        # cover the stall and at most that second before it
        base_t, cpu, machine = since, time.process_time(), _machine()
        open_report: Optional[dict] = None
        while not self._stop.wait(self.tick_s):
            now = time.perf_counter()
            late = now - tick - self.tick_s
            tick = now
            self.max_tick_late_s = max(self.max_tick_late_s, late)
            state = self._progress()
            if late >= self.threshold_s and open_report is None:
                # this thread itself did not run: whatever the batcher
                # has done since it woke, the gap is the stall
                report = self._report(now - late, now, late, base_t, cpu,
                                      machine)
                report["duration_s"] = late
                self.stall_s += late
                last, since = state, now
                continue
            if state != last or not self._waiting():
                if open_report is not None:
                    open_report["duration_s"] = now - open_report["since"]
                    self.stall_s += open_report["duration_s"]
                    logger.warning("serving stall over after %.2f s",
                                   open_report["duration_s"])
                    open_report = None
                last, since = state, now
                if now - base_t >= 1.0:
                    base_t, cpu, machine = (now, time.process_time(),
                                            _machine())
                continue
            if open_report is not None:
                open_report["max_tick_late_s"] = max(
                    open_report["max_tick_late_s"], late)
            elif now - since >= self.threshold_s:
                open_report = self._report(since, now, late, base_t, cpu,
                                           machine)

    def _report(self, since, now, late, base_t, cpu, machine) -> dict:
        after = _machine()
        report = {
            "since": since, "at": now, "waiting": self._waiting(),
            "tick_late_s": late, "max_tick_late_s": late,
            "base_age_s": now - base_t,
            "process_cpu_s": time.process_time() - cpu,
            "machine_s": {k: after[k] - machine.get(k, 0.0) for k in after},
            "stacks": _stacks()}
        self.n_stalls += 1
        self._reports.append(report)
        logger.warning(
            "serving stall: %d request(s) waiting, no dispatch for %.2f s; "
            "this tick came %.2f s late, the process burnt %.2f s of CPU "
            "in the last %.2f s, machine %s\n%s", report["waiting"],
            now - since, late, report["process_cpu_s"], now - base_t,
            report["machine_s"], report["stacks"])
        return report
