"""Host spans at the program's layer boundaries, on the device trace's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: under the profiler it
is a host event on the same clock as the device's operations, so a trace
reduction can put each idle gap of the device under the program phase the
host was in.  Profiler or not, it also keeps totals per name in memory:

    seconds    wall seconds inside it (``time.perf_counter``)
    lowerings  programs JAX lowered while it was the innermost open span
               (each one compiles or loads from the persistent cache)
    compile_s  seconds JAX spent lowering and compiling them

The nesting is per thread.  A span that lowered something also writes its
``lowerings`` and ``compile_s`` onto its trace event.  ``totals()`` returns
a copy of the table; ``NAMES`` lists every span the program opens.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

NAMES = (
    "entry.job",        # one run_production call
    "entry.transfer",   # the host array onto the device
    "entry.seed",       # PRNG key and initial parameters (k-means++, EM)
    "entry.config",     # the engine configuration, with h* from the model
    "engine.dispatch",  # the engine's jitted fit call, returning unready
    "entry.wait",       # the host waiting for the labels
    "entry.readback",   # objective and iterations to the host
    "stop.harvest",     # the stop model's training fits and their (r, h)
    "stop.regression",  # h(r) fitted and selected from the harvest
)

_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"

_totals: dict[str, dict] = {}
_lock = threading.Lock()
_local = threading.local()


class _Open:
    __slots__ = ("lowerings", "compile_s")

    def __init__(self):
        self.lowerings = 0
        self.compile_s = 0.0


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str):
    stack = _stack()
    frame = _Open()
    with jax.profiler.TraceAnnotation(name) as event:
        stack.append(frame)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            seconds = time.perf_counter() - t0
            stack.pop()
            if frame.lowerings or frame.compile_s:
                event.set_metadata(lowerings=frame.lowerings,
                                   compile_s=frame.compile_s)
            with _lock:
                t = _totals.setdefault(name, dict(
                    seconds=0.0, lowerings=0, compile_s=0.0))
                t["seconds"] += seconds
                t["lowerings"] += frame.lowerings
                t["compile_s"] += frame.compile_s


def totals() -> dict[str, dict]:
    with _lock:
        return {name: dict(t) for name, t in _totals.items()}


def _record(event: str, duration: float, **_):
    if event not in (_LOWERING, _COMPILE):
        return
    stack = _stack()
    if not stack:
        return
    frame = stack[-1]
    frame.compile_s += duration
    if event == _LOWERING:
        frame.lowerings += 1


jax.monitoring.register_event_duration_secs_listener(_record)
