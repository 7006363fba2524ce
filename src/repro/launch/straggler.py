"""Host-side straggler watchdog for the planner's ``--validate`` run.

A single slow host throttles every synchronous step.  The monitor keeps a
rolling window of per-step wall times; a step is flagged when it exceeds
``factor`` × the window median (p95-style heuristics are too jumpy at small
windows).  ``launch/plan.py`` times a short host-stepped fit loop with it
and puts :meth:`StragglerMonitor.report` in the validation record.
"""
from __future__ import annotations

import time


class StragglerMonitor:
    def __init__(self, window: int = 50, factor: float = 2.0,
                 grace_steps: int = 5):
        self.window = window
        self.factor = factor
        self.grace_steps = grace_steps
        self.times: list[float] = []
        self.flagged: list[tuple[int, float, float]] = []  # (step, t, median)
        self._t0 = None
        self._step = 0

    def start(self):
        self._t0 = time.monotonic()

    def stop(self) -> bool:
        """Record one step; True if this step is flagged as a straggler."""
        dt = time.monotonic() - self._t0
        self._step += 1
        self.times.append(dt)
        hist = self.times[-self.window:]
        med = sorted(hist)[len(hist) // 2]
        is_straggler = (self._step > self.grace_steps
                        and len(hist) >= 10 and dt > self.factor * med)
        if is_straggler:
            self.flagged.append((self._step, dt, med))
        return is_straggler

    def report(self) -> dict:
        if not self.times:
            return {"steps": 0}
        hist = sorted(self.times)
        n = len(hist)
        return {
            "steps": n,
            "median_s": hist[n // 2],
            "p95_s": hist[min(n - 1, int(0.95 * n))],
            "flagged": len(self.flagged),
        }
