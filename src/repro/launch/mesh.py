"""Production mesh builders + latency-hiding XLA flag toggles.

A *function*, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Topology (TPU v5e-class target):
  single-pod: (data=16, model=16)            = 256 chips
  multi-pod:  (pod=2, data=16, model=16)     = 512 chips
The design scales by growing "pod" (pure DP across pods — only gradient
all-reduce crosses the DCN) and "data".

The latency-hiding helpers below wire the async-collective /
latency-hiding-scheduler XLA flags (SNIPPETS.md snippet 1) into launches
as a profiled on/off toggle: ``BENCH_sharded_overlap.json`` records
wall-clock per sweep with and without them.  XLA reads ``XLA_FLAGS`` once
at backend initialisation, so the toggle only works process-wide — set it
in the environment of a fresh process (``overlap_env`` builds one), never
after jax has initialised.
"""
from __future__ import annotations

import os

import jax


# The scheduler/stream flags this jaxlib's XLA still parses.  The full
# SNIPPETS.md set also named --xla_gpu_enable_async_collectives and the
# Triton fusion toggles; async collectives are default-on (the flag was
# removed upstream) and unknown flags make XLA abort at startup, so they
# are deliberately absent here.  libtpu parses all three too (one XLA flag
# parser; checked on a v5e), where they configure nothing.
LATENCY_HIDING_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
    # CPU backend counterpart: the concurrency-optimized thunk scheduler
    # overlaps independent thunks (our prefetched chunk copies) on host
    # platforms, which is what the CI/bench substrate runs on
    "--xla_cpu_enable_concurrency_optimized_scheduler=true",
)


def latency_hiding_xla_flags(base: str | None = None) -> str:
    """``XLA_FLAGS`` value with the latency-hiding set appended to ``base``
    (defaults to the current environment's value); already-present flags
    are not duplicated."""
    flags = (os.environ.get("XLA_FLAGS", "") if base is None else base)
    parts = flags.split()
    for f in LATENCY_HIDING_FLAGS:
        name = f.split("=", 1)[0]
        if not any(p.split("=", 1)[0] == name for p in parts):
            parts.append(f)
    return " ".join(parts)


def overlap_env(env: dict | None = None, enable: bool = True) -> dict:
    """A copy of ``env`` (default ``os.environ``) with the latency-hiding
    flags toggled — the bench/launcher handoff for spawning a fresh process
    per flag configuration (XLA parses the variable exactly once)."""
    out = dict(os.environ if env is None else env)
    if enable:
        out["XLA_FLAGS"] = latency_hiding_xla_flags(out.get("XLA_FLAGS", ""))
    return out


def _auto(n):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_axis: int = 1):
    """Whatever this host has (tests/examples): (data=N/model, model)."""
    n = len(jax.devices())
    if model_axis < 1:
        raise ValueError(f"model_axis must be >= 1; got {model_axis}")
    if n % model_axis:
        raise ValueError(
            f"model_axis={model_axis} does not divide the {n} available "
            f"devices — a ({n // model_axis}, {model_axis}) mesh would "
            f"silently drop {n - (n // model_axis) * model_axis} of them; "
            "pick a model_axis that divides the device count")
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_auto(2))
