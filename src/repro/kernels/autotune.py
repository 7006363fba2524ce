"""Roofline-driven kernel autotuner (ISSUE 9).

The ``TilePolicy`` block sizes in :mod:`repro.kernels.layout` are
hand-picked; this module measures them.  For each registered
:class:`~repro.kernels.dispatch.KernelOp` it sweeps candidate block
shapes (a row-block grid aligned per the backend's ``TilePolicy``,
including the Triton power-of-two rule), times every candidate with the
shared methodology (warmup + ``block_until_ready`` + median-of-k, from
:mod:`repro.kernels.timing`), attaches analytic FLOP/byte counts (the
``analysis.hlo_ir`` Cost walker over the op's compiled ``xla`` reference
at the same shape — backend-independent math), and caches winners in a
versioned JSON keyed by ``(op, backend, device_kind, problem-shape
bucket)``.

Resolution contract (the ops consult :func:`tuned_blocks`):

  · an explicit ``block_n=`` argument always wins — the cache is never
    consulted;
  · no active cache (or no matching entry) → the hand-picked
    ``TilePolicy`` defaults, bit-for-bit unchanged;
  · an active cache entry supplies the blocks, which still pass through
    ``TilePolicy.block_for`` so a cached shape can never violate the
    backend's alignment rules.

Activation is scoped: ``with autotune.tuning(cache): ...`` (what
``EngineConfig(autotune=True)`` does around every fit driver, using
:func:`default_cache`).  The lookup happens at *trace* time, so a config
with ``autotune=True`` traces separately from the untuned one (the flag
is part of the static jit key); swapping caches mid-process requires
``jax.clear_caches()`` to drop traces that baked in the old blocks.

Winner selection is deterministic: candidates are generated in a fixed
order with the default first, timed with one methodology, and the
argmin (first on ties) wins — so the tuned median is by construction
≤ the default's *from the same sweep*, which is what the
``BENCH_roofline.json`` tuned-vs-default ≥ 1.0× claim gates.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch, layout
from repro.kernels.timing import time_callable

SCHEMA_VERSION = 1

# ops this tuner knows how to drive (shape triple: rows × clusters ×
# features)
SUPPORTED_OPS = ("kmeans_assign", "gmm_estep")

# row-block candidate grid; each entry passes through TilePolicy.block_for
# so alignment (incl. the Triton pow2 rule) and the n-cap are enforced
ROW_BLOCK_GRID = (128, 256, 512, 1024, 2048)

DEFAULT_SHAPES: dict[str, tuple[tuple[int, int, int], ...]] = {
    "kmeans_assign": ((16384, 8, 16), (65536, 8, 4)),
    "gmm_estep": ((16384, 8, 16),),
}


class StaleCacheError(ValueError):
    """An on-disk cache written under a different schema version."""


def device_kind() -> str:
    """The host accelerator's device kind, as a cache-key token."""
    return jax.devices()[0].device_kind.replace(" ", "_")


# --------------------------------------------------------------------------
# The versioned winner cache
# --------------------------------------------------------------------------

class AutotuneCache:
    """Winners keyed by ``op|backend|device_kind|n-bucket|k|d``.

    The row count is bucketed through :func:`layout.bucket_for` (the
    serving layer's closed shape ladder, which above the largest bucket
    continues in multiples of it), so one tuned entry serves every
    problem size that pads to the same compile shape; k and d are exact.
    """

    def __init__(self, entries: dict | None = None):
        self.entries: dict[str, dict] = dict(entries or {})

    @staticmethod
    def key(op: str, backend: str, *, n: int, k: int, d: int,
            kind: str | None = None) -> str:
        kind = kind if kind is not None else device_kind()
        return f"{op}|{backend}|{kind}|n{layout.bucket_for(n)}|k{k}|d{d}"

    def put(self, op: str, backend: str, *, n: int, k: int, d: int,
            blocks: dict, **meta) -> str:
        key = self.key(op, backend, n=n, k=k, d=d)
        self.entries[key] = {
            "op": op, "backend": backend, "device_kind": device_kind(),
            "n_bucket": layout.bucket_for(n), "k": k, "d": d,
            "blocks": {name: int(v) for name, v in blocks.items()},
            **meta,
        }
        return key

    def lookup(self, op: str, backend: str, *, n: int, k: int,
               d: int) -> dict | None:
        """The winning blocks dict for this cell, or None (host
        device-kind keyed — a cache tuned on another device kind never
        matches)."""
        e = self.entries.get(self.key(op, backend, n=n, k=k, d=d))
        return dict(e["blocks"]) if e else None

    def to_payload(self) -> dict:
        return {"schema_version": SCHEMA_VERSION,
                "entries": self.entries}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_payload(), f, indent=1, sort_keys=True)
            f.write("\n")
        return path

    @classmethod
    def from_payload(cls, payload: dict, where: str = "<payload>"):
        version = payload.get("schema_version")
        if version != SCHEMA_VERSION:
            raise StaleCacheError(
                f"autotune cache {where} has schema_version={version!r} "
                f"but this build writes {SCHEMA_VERSION} — re-tune "
                "(python -m repro.launch.autotune) instead of trusting "
                "stale winners")
        entries = payload.get("entries")
        if not isinstance(entries, dict):
            raise ValueError(f"autotune cache {where} has no 'entries' "
                             "mapping")
        for key, e in entries.items():
            blocks = e.get("blocks") if isinstance(e, dict) else None
            if not isinstance(blocks, dict) or not all(
                    isinstance(v, int) and v > 0 for v in blocks.values()):
                raise ValueError(
                    f"autotune cache {where} entry {key!r} has malformed "
                    f"blocks {blocks!r} (need a name -> positive-int map)")
        return cls(entries)

    @classmethod
    def load(cls, path: str):
        with open(path) as f:
            return cls.from_payload(json.load(f), where=path)


# --------------------------------------------------------------------------
# Scoped activation + the ops' lookup hook
# --------------------------------------------------------------------------

_STATE = threading.local()
_DEFAULT: dict = {"cache": None, "path": None}


@contextlib.contextmanager
def tuning(cache: AutotuneCache | None):
    """Activate ``cache`` for the ops' block resolution in this thread.

    ``None`` is a no-op scope (defaults everywhere) — the engine facade
    always enters this manager when ``config.autotune`` and lets a
    missing cache degrade silently to the hand-picked policy.
    """
    prev = getattr(_STATE, "cache", None)
    _STATE.cache = cache
    try:
        yield cache
    finally:
        _STATE.cache = prev


def active_cache() -> AutotuneCache | None:
    return getattr(_STATE, "cache", None)


def tuned_blocks(op: str, backend: str, *, n: int, k: int,
                 d: int) -> dict | None:
    """The active cache's blocks for this call site, or None.

    The public ops call this only when no explicit block override was
    passed, so overrides always win and the untuned path never pays a
    lookup.
    """
    cache = active_cache()
    if cache is None:
        return None
    return cache.lookup(op, backend, n=n, k=k, d=d)


def set_default_cache(cache: AutotuneCache | str | None):
    """Install the process default ``EngineConfig(autotune=True)`` uses
    (an :class:`AutotuneCache`, a path to load lazily, or None to clear
    back to the ``REPRO_AUTOTUNE_CACHE`` env lookup)."""
    if isinstance(cache, str):
        _DEFAULT.update(cache=None, path=cache)
    else:
        _DEFAULT.update(cache=cache, path=None)


def default_cache() -> AutotuneCache | None:
    """The process-default cache: ``set_default_cache``'s install wins,
    else the ``REPRO_AUTOTUNE_CACHE`` env path (when it exists), else
    None.  Loads lazily and memoises the loaded object."""
    if _DEFAULT["cache"] is not None:
        return _DEFAULT["cache"]
    path = _DEFAULT["path"] or os.environ.get("REPRO_AUTOTUNE_CACHE")
    if path and os.path.exists(path):
        _DEFAULT["cache"] = AutotuneCache.load(path)
        return _DEFAULT["cache"]
    return None


# --------------------------------------------------------------------------
# Candidate grids
# --------------------------------------------------------------------------

def default_blocks(op: str, backend: str, *, n: int, k: int, d: int) -> dict:
    """The hand-picked blocks the op resolves without any cache — the
    sweep's baseline candidate (kept bit-for-bit in sync with the ops'
    own no-override resolution)."""
    return {"block_n": layout.tile_policy(backend).block_for(n)}


def candidate_blocks(op: str, backend: str, *, n: int, k: int,
                     d: int) -> list[dict]:
    """Deterministic candidate list, default first, duplicates removed.

    Every candidate is passed through the backend's ``TilePolicy``
    alignment (``block_for``), so the grid can never
    propose a block the lowering rejects — including Triton's pow2 rule.
    The ``xla`` reference ignores block shapes entirely, so it gets the
    single default candidate (a sweep there would time one program five
    ways).
    """
    default = default_blocks(op, backend, n=n, k=k, d=d)
    if backend == "xla":
        return [default]
    pol = layout.tile_policy(backend)
    cands, seen = [], set()

    def add(blocks: dict):
        sig = tuple(sorted(blocks.items()))
        if sig not in seen:
            seen.add(sig)
            cands.append(blocks)

    add(default)
    for b in ROW_BLOCK_GRID:
        add({"block_n": pol.block_for(n, b)})
    return cands


# --------------------------------------------------------------------------
# Sweep: operands, timing, analytic counts
# --------------------------------------------------------------------------

def _op_args(op: str, *, n: int, k: int, d: int, seed: int = 0) -> tuple:
    """Deterministic concrete operands for one sweep cell."""
    rng = np.random.default_rng(seed)
    if op == "kmeans_assign":
        return (jnp.asarray(rng.normal(0, 5, (n, d)).astype(np.float32)),
                jnp.asarray(rng.normal(0, 5, (k, d)).astype(np.float32)))
    if op == "gmm_estep":
        return (jnp.asarray(rng.normal(0, 5, (n, d)).astype(np.float32)),
                jnp.asarray(rng.normal(0, 2, (k, d)).astype(np.float32)),
                jnp.asarray((rng.random((k, d)) + 0.5).astype(np.float32)),
                jnp.asarray(np.log(np.full((k,), 1.0 / k,
                                           dtype=np.float32))))
    raise ValueError(f"unknown autotune op {op!r} "
                     f"(supported: {SUPPORTED_OPS})")


def make_op_call(op: str, backend: str, *, n: int, k: int, d: int,
                 seed: int = 0):
    """``blocks → zero-arg thunk`` running the public op at this cell.

    The thunks share one set of operand arrays, so candidate timings
    differ only by block shape.
    """
    args = _op_args(op, n=n, k=k, d=d, seed=seed)
    if op == "kmeans_assign":
        from repro.kernels.kmeans_assign.ops import kmeans_assign as fn
    else:
        from repro.kernels.gmm_estep.ops import gmm_estep as fn

    def factory(blocks: dict):
        return lambda: fn(*args, backend=backend, **blocks)

    return factory


@functools.lru_cache(maxsize=None)
def analytic_cost(op: str, *, n: int, k: int, d: int):
    """FLOPs / HBM bytes of the op's math at this shape, from the Cost
    walker over the compiled ``xla`` reference — backend-independent
    analytic counts (the Pallas lowerings compute the same function)."""
    from repro.analysis.hlo_ir import analyze
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    if op == "kmeans_assign":
        from repro.kernels.kmeans_assign.ops import kmeans_assign
        fn = functools.partial(kmeans_assign, backend="xla")
        args = (f32((n, d)), f32((k, d)))
    else:
        from repro.kernels.gmm_estep.ops import gmm_estep
        fn = functools.partial(gmm_estep, backend="xla")
        args = (f32((n, d)), f32((k, d)), f32((k, d)), f32((k,)))
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return analyze(hlo)


def sweep_op(op: str, backend: str, *, n: int, k: int, d: int,
             reps: int = 5, warmup: int = 1, timer=None,
             call_factory=None, include_cost: bool = True,
             seed: int = 0) -> dict:
    """Time every candidate block shape for one (op, backend, shape) cell.

    Returns ``{"candidates": [{"blocks", "median_s"}, ...], "default",
    "winner", "flops", "bytes"}`` — candidates in deterministic order
    (default first), winner = argmin median (first on ties), so
    ``default.median_s / winner.median_s >= 1.0`` always holds within
    one sweep.  ``call_factory`` / ``timer`` are test hooks (fake ops,
    fake clock).
    """
    cands = candidate_blocks(op, backend, n=n, k=k, d=d)
    factory = call_factory if call_factory is not None \
        else make_op_call(op, backend, n=n, k=k, d=d, seed=seed)
    results = []
    for blocks in cands:
        t = time_callable(factory(blocks), reps=reps, warmup=warmup,
                          timer=timer)
        results.append({"blocks": dict(blocks), "median_s": t})
    winner = min(results, key=lambda r: r["median_s"])
    out = {"op": op, "backend": backend, "n": n, "k": k, "d": d,
           "candidates": results, "default": results[0], "winner": winner}
    if include_cost:
        cost = analytic_cost(op, n=n, k=k, d=d)
        out["flops"] = float(cost.flops)
        out["bytes"] = float(cost.bytes)
    return out


# --------------------------------------------------------------------------
# Roofline peaks (measured on this host, cached per process)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def measure_peaks(kind: str | None = None) -> dict:
    """Achievable peak FLOP/s and HBM bytes/s on this host, via XLA.

    Peak compute = a large f32 matmul; peak bandwidth = a 64 MiB
    streaming add (reads + writes counted).  These are *achievable via
    XLA* peaks, not datasheet numbers — the right ceiling for kernels
    that themselves run through XLA/Pallas.  Median-of-3, cached per
    process.
    """
    kind = kind or device_kind()
    m = 1024
    a = jnp.ones((m, m), jnp.float32)
    b = jnp.ones((m, m), jnp.float32)
    mm = jax.jit(lambda a, b: a @ b)
    t_mm = time_callable(mm, a, b, reps=3, warmup=1)
    v = jnp.ones((64 * 1024 * 1024 // 4,), jnp.float32)
    add = jax.jit(lambda v: v + 1.0)
    t_bw = time_callable(add, v, reps=3, warmup=1)
    return {
        "device_kind": kind,
        "flops_per_s": 2.0 * m ** 3 / max(t_mm, 1e-12),
        "bytes_per_s": 2.0 * v.nbytes / max(t_bw, 1e-12),
        "method": "measured (f32 1024^3 matmul / 64MiB streaming add, "
                  "median-of-3)",
    }


def roofline_point(flops: float, bytes_: float, median_s: float,
                   peaks: dict) -> dict:
    """Achieved FLOP/s, arithmetic intensity, ceiling and the fraction of
    it this cell reaches — one row of the roofline table."""
    intensity = flops / max(bytes_, 1.0)
    achieved = flops / max(median_s, 1e-12)
    ceiling = min(peaks["flops_per_s"], intensity * peaks["bytes_per_s"])
    return {
        "achieved_flops_per_s": achieved,
        "arithmetic_intensity": intensity,
        "roofline_ceiling_flops_per_s": ceiling,
        "ceiling_fraction": achieved / max(ceiling, 1e-12),
        "bound": ("compute" if intensity * peaks["bytes_per_s"]
                  >= peaks["flops_per_s"] else "memory"),
    }


# --------------------------------------------------------------------------
# The end-to-end tuner (what launch/autotune.py drives)
# --------------------------------------------------------------------------

# importing an ops module is what registers its backends — the tuner
# drives ops by name, so it must force that import before asking the
# registry (a cycle-free lazy import: ops.py imports this module too)
_OP_MODULES = {
    "kmeans_assign": "repro.kernels.kmeans_assign.ops",
    "gmm_estep": "repro.kernels.gmm_estep.ops",
}


def _ensure_registered(op_name: str) -> None:
    mod = _OP_MODULES.get(op_name)
    if mod is not None:
        importlib.import_module(mod)


def available_backends(op_name: str) -> tuple[str, ...]:
    """Backends worth sweeping on this host: interpret + xla always,
    tpu/gpu only when the platform actually has the hardware."""
    _ensure_registered(op_name)
    reachable = {"interpret", "xla"}
    jb = jax.default_backend()
    if jb in ("tpu", "gpu"):
        reachable.add(jb)
    return tuple(b for b in dispatch.get_op(op_name).backends()
                 if b in reachable)


def tune(ops=None, backends=None, shapes=None, *, reps: int = 5,
         warmup: int = 1, timer=None, cache: AutotuneCache | None = None,
         call_factory=None, include_cost: bool = True,
         log=None) -> AutotuneCache:
    """Sweep the grid and collect winners into ``cache``.

    Cells already present in ``cache`` are skipped (cache-hit
    short-circuit — no re-timing), so an interrupted tune resumes and a
    merge run only fills holes.  ``shapes`` (``(n, k, d)`` triples)
    applies to every op; per-op defaults otherwise.
    """
    cache = cache if cache is not None else AutotuneCache()
    say = log or (lambda *_: None)
    for op in (ops or SUPPORTED_OPS):
        _ensure_registered(op)
        if op not in dispatch.registered_ops():
            say(f"# {op}: not registered, skipped")
            continue
        op_backends = backends or available_backends(op)
        for backend in op_backends:
            if backend not in dispatch.get_op(op).backends():
                say(f"# {op}/{backend}: backend not registered, skipped")
                continue
            for (n, k, d) in (shapes or DEFAULT_SHAPES[op]):
                if cache.lookup(op, backend, n=n, k=k, d=d) is not None:
                    say(f"# {op}/{backend} n{n} k{k} d{d}: cached, "
                        "skipped")
                    continue
                sw = sweep_op(op, backend, n=n, k=k, d=d, reps=reps,
                              warmup=warmup, timer=timer,
                              call_factory=call_factory,
                              include_cost=include_cost)
                meta = {
                    "median_s": sw["winner"]["median_s"],
                    "default_blocks": sw["default"]["blocks"],
                    "default_median_s": sw["default"]["median_s"],
                    "reps": reps,
                }
                if include_cost:
                    meta.update(flops=sw["flops"], bytes=sw["bytes"])
                cache.put(op, backend, n=n, k=k, d=d,
                          blocks=sw["winner"]["blocks"], **meta)
                say(f"# {op}/{backend} n{n} k{k} d{d}: "
                    f"{sw['winner']['blocks']} "
                    f"({sw['winner']['median_s'] * 1e3:.2f} ms, default "
                    f"{sw['default']['median_s'] * 1e3:.2f} ms, "
                    f"{len(sw['candidates'])} candidates)")
    return cache
