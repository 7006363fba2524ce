"""Unified streaming clustering engine — one driver for k-means and EM.

The monolithic ``kmeans_fit_*`` / ``em_fit_*`` drivers each hand-rolled the
same while_loop + Eq. 7 predicate and required the whole [N, D] array (and a
materialised [N, K] distance/responsibility matrix) resident on one device.
This module folds them behind a small algorithm protocol
(``init / chunk_stats / update / objective``) and adds two scale axes:

  · **streaming assignment** — a ``lax.scan`` over [C, N/C, D] chunks
    accumulates the additive sufficient statistics ((sums, counts, J) for
    k-means; (r_sum, r_x, r_x2, loglik) for EM) so the [N, K] intermediate
    never exists for more than one chunk at a time; N is bounded by HBM
    streaming bandwidth rather than device memory.  The per-sweep result is
    bit-for-bit the same contract the Pallas kernels produce, and composes
    with the ``axis_name`` psum path (shard_map over the data axes): stats
    are accumulated locally, then psum'd once per sweep.

  · **multi-restart via ``vmap``** — R seeds run as one batched program.
    Each restart carries its own early-stop mask; once a restart trips the
    h_i ≤ h* predicate its state is frozen and the (still batched) body
    becomes a no-op for it.  The engine returns the best-objective restart —
    the standard production guard against bad initialisation.

  · **minibatch mode** — ``EngineConfig(mode="minibatch", chunks=C,
    batch_chunks=B)`` makes every iteration sample B of the C chunks
    (without replacement, fresh draw per step) and apply learning-rate
    parameter updates: Sculley-style per-cluster counts for k-means,
    stepwise-EM responsibility mass for GMMs (see
    ``kmeans.minibatch_update_centroids`` / ``em_gmm.minibatch_mstep`` for
    the 1/t schedules and the ``decay`` forgetting factor).  Per-iteration
    data touch drops from N to N·B/C, which is the regime the paper's
    cost argument needs at scales where even one full sweep is expensive.
    The Eq. 7 change rate h is *paired*: the same subsample is evaluated
    at the old and at the new parameters, so the sampling noise cancels in
    the ratio and a full-batch fitted h* = f(r*) transfers to minibatch
    stopping (raw cross-batch differences would floor h at the subsample
    noise, ~1/√batch).  The pairing costs a second distance pass over the
    subsample — 2·B/C of a full sweep's compute, still B/C distinct data.
    ``patience`` > 1 still robustifies against lucky draws, and ``ema``
    optionally smooths h.  The final labels pass is always a full sweep,
    so the result contract is unchanged.

All three axes compose with ``use_kernel=True`` (ISSUE 4): sweeps route
through the backend-dispatched kernel ops (``repro.kernels.dispatch`` —
tpu/gpu Pallas, interpreter elsewhere, or the ``xla`` reference;
``kernel_backend`` pins one).  Multi-restart rides the kernels' restart
grid axis via their ``custom_vmap`` rules, minibatch uses the gather-free
statically-sliced subsample driver, and the sharded drivers run the masked
chunk layout through the same per-chunk kernel calls.

Thresholds from an offline-fitted ``earlystop.LongTailModel`` enter through
``EngineConfig.from_longtail`` so the paper pipeline (fit h(r) once, reuse
h* = f(r*) forever) drives the same engine.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import spans

from . import em_gmm as _em
from . import kmeans as _km

_EPS = 1e-30


class ProvenanceMismatchError(ValueError):
    """A fitted ``LongTailModel`` is being routed into an engine regime that
    does not match the configuration its (r, h) traces were harvested under.

    Raised by ``EngineConfig.from_longtail(..., strict=True)`` — the serving
    registry's admission path — instead of the advisory ``UserWarning`` the
    non-strict research path emits.  ``diff`` maps each mismatched field to
    ``(fitted, production)``."""

    def __init__(self, message: str, diff: dict):
        super().__init__(message)
        self.diff = diff


# --------------------------------------------------------------------------
# Algorithm protocol: init / chunk_stats / update / objective (+ kernels)
# --------------------------------------------------------------------------
# Implementations are stateless singletons; __eq__/__hash__ by type so they
# are stable jit static arguments across engine instances.

class KMeansAlgorithm:
    """Lloyd's k-means.  Params: centroids [K, D].  Stats: (sums, counts, J)."""

    name = "kmeans"
    maximize = False

    def __hash__(self):
        return hash(type(self).__name__)

    def __eq__(self, other):
        return type(other) is type(self)

    def init(self, key, x, k: int, chunks: int = 1):
        return _km.kmeans_plus_plus_init(key, x, k, chunks=chunks)

    def zero_stats(self, params):
        k, d = params.shape
        return (jnp.zeros((k, d), jnp.float32), jnp.zeros((k,), jnp.float32),
                jnp.zeros((), jnp.float32))

    def zero_carry(self, params):
        """Minibatch carry: cumulative per-cluster counts v [K]."""
        return jnp.zeros((params.shape[0],), jnp.float32)

    def minibatch_update(self, params, stats, carry, n_batch, decay):
        del n_batch  # EWA uses decay directly; EM's leg needs the count
        sums, counts, _ = stats
        return _km.minibatch_update_centroids(params, sums, counts, carry,
                                              decay)

    def chunk_stats(self, xc, mask, params):
        labels, sums, counts, j = _km.assign_and_stats(xc, params, mask=mask)
        return labels, (sums, counts, j)

    def kernel_stats(self, x, params, chunks: int, backend=None):
        from repro.kernels.kmeans_assign import ops as _kops
        labels, sums, counts, j = _kops.kmeans_assign_chunked(
            x, params, chunks=chunks, backend=backend)
        return labels, (sums, counts, j)

    def kernel_chunk_stats(self, xc, mask, params, backend=None):
        """One masked chunk through the dispatched kernel op — the fused
        counterpart of ``chunk_stats`` (same contract)."""
        from repro.kernels.kmeans_assign import ops as _kops
        labels, sums, counts, j = _kops.kmeans_assign(
            xc, params, mask=mask, backend=backend)
        return labels, (sums, counts, j)

    def update(self, params, stats, n_total):
        del n_total  # centroid means normalise by per-cluster counts
        sums, counts, _ = stats
        return _km.update_centroids(params, sums, counts)

    def objective(self, stats):
        return stats[2]

    def moved(self, new_params, params):
        return jnp.any(new_params != params)

    # centred compression basis (see _stats_reducer): transmit
    # Σ(x − c_prev) per cluster instead of Σx.  The centred sums are
    # count·(cluster mean − current centroid) — they shrink as the fit
    # converges, and the int8 ring's pmax-shared scale shrinks with them,
    # so quantisation error decays with the residual motion instead of
    # staying pinned at ~1% of the raw moment magnitude.  The transform is
    # linear per shard, so it commutes with the cross-shard sum and inverts
    # exactly from the reduced (counts, centred sums).
    def compress_basis(self, params, stats):
        sums, counts, j = stats
        return (sums - counts[:, None] * params, counts, j)

    def decompress_basis(self, params, stats):
        csums, counts, j = stats
        return (csums + counts[:, None] * params, counts, j)


class EMAlgorithm:
    """Diagonal-covariance GMM via EM.  Params: GMMParams.
    Stats: (r_sum, r_x, r_x2, loglik)."""

    name = "em"
    maximize = True

    def __hash__(self):
        return hash(type(self).__name__)

    def __eq__(self, other):
        return type(other) is type(self)

    def init(self, key, x, k: int, chunks: int = 1):
        del chunks  # uniform draw touches k rows, nothing to stream
        return _em.random_init(key, x, k)

    def zero_stats(self, params):
        k, d = params.means.shape
        return (jnp.zeros((k,), jnp.float32), jnp.zeros((k, d), jnp.float32),
                jnp.zeros((k, d), jnp.float32), jnp.zeros((), jnp.float32))

    def zero_carry(self, params):
        """Minibatch carry: cumulative responsibility mass v [K]."""
        return jnp.zeros((params.means.shape[0],), jnp.float32)

    def minibatch_update(self, params, stats, carry, n_batch, decay):
        r_sum, r_x, r_x2, _ = stats
        return _em.minibatch_mstep(params, r_sum, r_x, r_x2, carry, n_batch,
                                   decay)

    def chunk_stats(self, xc, mask, params):
        labels, loglik, r_sum, r_x, r_x2 = _em.estep_stats(
            xc, params, mask=mask)
        return labels, (r_sum, r_x, r_x2, loglik)

    def kernel_stats(self, x, params, chunks: int, backend=None):
        from repro.kernels.gmm_estep import ops as _gops
        labels, loglik, r_sum, r_x, r_x2 = _gops.gmm_estep_chunked(
            x, params.means, params.var, params.log_w, chunks=chunks,
            backend=backend)
        return labels, (r_sum, r_x, r_x2, loglik)

    def kernel_chunk_stats(self, xc, mask, params, backend=None):
        """One masked chunk through the dispatched kernel op — the fused
        counterpart of ``chunk_stats`` (same contract)."""
        from repro.kernels.gmm_estep import ops as _gops
        labels, loglik, r_sum, r_x, r_x2 = _gops.gmm_estep(
            xc, params.means, params.var, params.log_w, mask=mask,
            backend=backend)
        return labels, (r_sum, r_x, r_x2, loglik)

    def update(self, params, stats, n_total):
        r_sum, r_x, r_x2, _ = stats
        return _em.mstep(params, r_sum, r_x, r_x2, n_total)

    def objective(self, stats):
        return stats[3]

    # centred compression basis (see _stats_reducer and the k-means
    # counterpart).  EM *requires* this: the M-step variance is
    # r_x2/r_sum − mean², a catastrophic cancellation — with means ~9 and
    # var ~1 the raw second moment is ~82 while the variance is 1, so a 1%
    # int8 error on r_x2 is an ~80% error on var and EM diverges.  The
    # first moment is centred on the current means, Σr(x−μ), and the
    # second on the current means and variances, Σr((x−μ)² − σ²): both
    # shrink to the parameter motion as the fit converges, so the int8
    # scale and its rounding shrink with them (centring the second moment
    # on μ alone left it at r·σ², and the variance rounding kept EM's
    # loglik jittering above the stop threshold).  The transforms are
    # linear per shard, commute with the cross-shard sum, and invert
    # exactly with the reduced r_sum (a vector leaf, reduced exact).
    # Still open: a dimension whose variance collapses towards the floor
    # while others in its cluster row move is rounded on the row's scale.
    def compress_basis(self, params, stats):
        r_sum, r_x, r_x2, ll = stats
        a, v = params.means, params.var
        r_xc = r_x - r_sum[:, None] * a
        r_x2c = (r_x2 - 2.0 * a * r_x + (a * a) * r_sum[:, None]
                 - r_sum[:, None] * v)
        return (r_sum, r_xc, r_x2c, ll)

    def decompress_basis(self, params, stats):
        r_sum, r_xc, r_x2c, ll = stats
        a, v = params.means, params.var
        r_x = r_xc + r_sum[:, None] * a
        r_x2 = (r_x2c + r_sum[:, None] * v + 2.0 * a * r_x
                - (a * a) * r_sum[:, None])
        return (r_sum, r_x, r_x2, ll)

    def moved(self, new_params, params):
        # EM has no frozen-partition fixed point at fp granularity; the
        # engine never gates EM on movement (stop_when_frozen=False).
        del new_params, params
        return jnp.asarray(True)


KMEANS = KMeansAlgorithm()
EM = EMAlgorithm()
_ALGORITHMS = {"kmeans": KMEANS, "em": EM}


def get_algorithm(algorithm):
    if isinstance(algorithm, str):
        return _ALGORITHMS[algorithm]
    return algorithm


# --------------------------------------------------------------------------
# Config + results
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static (hashable) engine configuration — one jit cache entry each.

    ``h_star`` here is the *default* threshold; ``fit`` accepts a traced
    override so sweeping thresholds does not retrace.

    ``use_kernel`` routes every sweep (full, chunked, minibatch, restarts,
    sharded) through the backend-dispatched kernel ops;
    ``kernel_backend`` pins a registry backend ("tpu" / "gpu" /
    "interpret" / "xla" or a custom ``register_backend`` name — see
    ``repro.kernels.dispatch``).  ``None``/"auto" resolve to the
    platform's default backend *at construction* (honouring an active
    ``dispatch.force_backend``), so the concrete name is part of this
    static config and jit caches never cross backends.  The
    ``REPRO_FORCE_KERNEL_BACKEND`` env var reroutes every config through
    the kernel path (the CI coverage hook; explicitly pinned backends
    win).

    ``mode="minibatch"`` samples ``batch_chunks`` of the ``chunks`` pieces
    per iteration and applies learning-rate updates with forgetting factor
    ``decay`` (1.0 = pure 1/t annealing; see the module docstring).  The
    chunk draw is seeded from ``seed`` so runs are reproducible; under
    ``axis_name`` every shard draws the same chunk indices from its local
    chunking and the psum'd stats + batch count keep the update and the
    stop decision globally agreed.  The sharded drivers
    (``ClusteringEngine.fit_sharded`` / ``fit_restarts_sharded``) make the
    local chunking a row-slice of the *global* one, so the drawn subsample
    — and hence the whole trajectory — matches the single-device run up to
    fp32 reduction order.

    ``trace=True`` makes every fit driver additionally return a
    per-iteration :class:`Trace` (objective sequence, Eq. 7 change-rate
    sequence — the *paired* rate in minibatch mode — iteration mask and
    the parameter trajectory) recorded inside the ``while_loop`` carry.
    This is the mode-matched training hook: (r, h) harvesting runs under
    the exact production configuration instead of replaying sweeps
    host-side (see ``repro.core.longtail_train``).  The buffers are
    [max_iters]-shaped (params: [max_iters, ...]); sizes are a few KB for
    clustering workloads.

    ``stats_compression="int8_ef"`` routes every per-sweep stats reduction
    in the sharded drivers through the int8 ring all-reduce with error
    feedback (``repro.distribution.compression``) instead of fp32 psum:
    array-valued sufficient statistics (centroid sums, counts, GMM
    moments) move over the wire as int8 chunks (~4× fewer collective
    bytes), the quantisation residual is carried in the fit loop's
    ``while_loop`` state (per restart under vmap), and the scalar
    objective leaves (J / loglik) stay exact fp32 psum — int8's ~8e-3
    relative resolution would destroy the Eq. 7 stop they drive.
    ``stats_axis_size`` is the ring's static size; the sharded drivers
    resolve it from the mesh, so normal use is just
    ``EngineConfig(stats_compression="int8_ef")`` + ``fit_sharded``.  The
    final labels/objective pass always reduces exact, so the result
    contract is unchanged; only the trajectory sees quantisation (parity
    on stop iterations is gated in ``BENCH_sharded_overlap.json``).

    ``prefetch=True`` double-buffers the streaming chunk scan: the scan
    carry holds the chunk being processed while the body issues the
    dynamic-slice load of chunk i+1, so the next chunk's copy has no data
    dependency on the current chunk's matmul and the scheduler can overlap
    them.  Chunk order and accumulation math are unchanged — results are
    bit-identical to the synchronous scan.

    ``autotune=True`` (requires ``use_kernel=True``) resolves kernel
    block shapes from the autotuner's winner cache
    (``repro.kernels.autotune``): every fit driver runs inside an
    ``autotune.tuning(autotune.default_cache())`` scope, so the
    dispatched ops consult the cache keyed by (op, backend, device kind,
    shape bucket).  No cache installed (``set_default_cache`` /
    ``REPRO_AUTOTUNE_CACHE``) → the hand-picked ``TilePolicy`` defaults,
    bit-for-bit.  The flag is part of this static config, so tuned and
    untuned fits never share a trace; swapping caches mid-process needs
    ``jax.clear_caches()``.  Tuned blocks regroup fp32 accumulation but
    compute the same update, so stop iterations match the untuned run
    (gated in CI's autotune-smoke job).
    """
    max_iters: int = 300
    h_star: float = 0.0
    patience: int = 1
    chunks: int = 1                 # C streaming chunks per sweep
    axis_name: Any = None           # psum stats over these mesh axes
    use_kernel: bool = False        # route sweeps through the kernel ops
    use_h_stop: bool = True         # apply the h_i <= h* long-tail predicate
    stop_when_frozen: bool = False  # stop when params stop moving (k-means)
    mode: str = "full"              # "full" | "minibatch"
    batch_chunks: int = 0           # B of C chunks sampled per minibatch step
    decay: float = 1.0              # minibatch count forgetting factor
    seed: int = 0                   # minibatch chunk-sampling PRNG stream
    ema: float = 0.0                # minibatch h smoothing (0 = raw)
    kernel_backend: str | None = None   # registry backend; None = auto
    trace: bool = False             # record a per-iteration Trace
    stats_compression: str = "none"     # "none" | "int8_ef" sweep reductions
    stats_axis_size: int = 0        # ring size; sharded drivers resolve it
    prefetch: bool = False          # double-buffer the streaming chunk scan
    autotune: bool = False          # kernel blocks from the autotune cache

    def __post_init__(self):
        # CI hook: REPRO_FORCE_KERNEL_BACKEND=<backend> reroutes every
        # engine config through the kernel dispatch layer, so the whole
        # engine suite doubles as kernel-path coverage.  An explicitly
        # pinned kernel_backend wins over the env (backend-vs-backend
        # parity tests keep comparing what they name).  It is a CPU test
        # hook: on a TPU it would reroute fits off the compiled kernel.
        forced = os.environ.get("REPRO_FORCE_KERNEL_BACKEND")
        if forced:
            if jax.default_backend() == "tpu":
                raise RuntimeError(
                    "REPRO_FORCE_KERNEL_BACKEND is a CPU test hook; unset "
                    "it on a TPU, where use_kernel resolves to the "
                    "compiled 'tpu' kernel")
            if not self.use_kernel:
                object.__setattr__(self, "use_kernel", True)
            if self.kernel_backend in (None, "auto"):
                object.__setattr__(self, "kernel_backend", forced)
        if self.mode not in ("full", "minibatch"):
            raise ValueError(f"unknown engine mode {self.mode!r}")
        if not 0.0 <= self.ema < 1.0:
            raise ValueError(f"ema must be in [0, 1); got {self.ema}")
        if self.kernel_backend is not None and not self.use_kernel:
            raise ValueError(
                "kernel_backend has no effect with use_kernel=False — "
                "pass use_kernel=True (CLI: --use-kernel) or drop it")
        if self.autotune and not self.use_kernel:
            raise ValueError(
                "autotune=True resolves kernel block shapes, but "
                "use_kernel=False never dispatches a kernel — pass "
                "use_kernel=True (CLI: --use-kernel) or drop it")
        if self.use_kernel and self.kernel_backend in (None, "auto"):
            # resolve eagerly: the concrete backend becomes part of this
            # static (hashable) config, so the jit caches keyed on it can
            # never reuse a trace from another backend (including under a
            # dispatch.force_backend() active right now).  Names the
            # registry does not know fail at the first op dispatch with
            # the available list — custom register_backend() names are
            # legal here.
            from repro.kernels import dispatch as _dispatch
            object.__setattr__(self, "kernel_backend",
                               _dispatch.default_backend())
        if self.mode == "full":
            stray = [f"{name}={value!r}" for name, value, default in (
                ("batch_chunks", self.batch_chunks, 0),
                ("decay", self.decay, 1.0),
                ("seed", self.seed, 0),
                ("ema", self.ema, 0.0)) if value != default]
            if stray:
                raise ValueError(
                    "minibatch-only settings " + ", ".join(stray) +
                    " have no effect in mode='full' — pass mode='minibatch' "
                    "(CLI: --mode minibatch) or drop them")
        if self.mode == "minibatch":
            if self.chunks < 2:
                raise ValueError(
                    "minibatch mode needs chunks >= 2 (the sweep samples "
                    "batch_chunks of them); got chunks="
                    f"{self.chunks}")
            if not 1 <= self.batch_chunks < self.chunks:
                raise ValueError(
                    "minibatch mode needs 1 <= batch_chunks < chunks; got "
                    f"batch_chunks={self.batch_chunks}, chunks={self.chunks}")
            if not 0.0 < self.decay <= 1.0:
                raise ValueError(f"decay must be in (0, 1]; got {self.decay}")
        if self.stats_compression not in ("none", "int8_ef"):
            raise ValueError(
                f"unknown stats_compression {self.stats_compression!r}; "
                "choose 'none' (fp32 psum) or 'int8_ef' (int8 ring "
                "all-reduce with error feedback)")
        if self.stats_axis_size < 0:
            raise ValueError(
                f"stats_axis_size must be >= 0; got {self.stats_axis_size}")
        if self.stats_compression == "none" and self.stats_axis_size:
            raise ValueError(
                f"stats_axis_size={self.stats_axis_size} has no effect with "
                "stats_compression='none' — pass "
                "stats_compression='int8_ef' or drop it")
        if self.stats_compression != "none":
            if self.stop_when_frozen:
                raise ValueError(
                    "stop_when_frozen requires bit-exact parameter fixed "
                    "points, which int8-quantised stats never reach (the "
                    "centroids keep jittering at quantisation granularity "
                    "and the fit only ends at max_iters) — use the Eq. 7 "
                    "h stop with stats_compression='int8_ef'")
            if isinstance(self.axis_name, tuple):
                raise ValueError(
                    "stats_compression rides a single-axis ppermute ring; "
                    f"axis_name={self.axis_name!r} names "
                    f"{len(self.axis_name)} mesh axes — collapse the data "
                    "axes into one or use stats_compression='none'")
            if self.axis_name is not None and self.stats_axis_size < 1:
                raise ValueError(
                    "stats_compression='int8_ef' with an explicit "
                    f"axis_name={self.axis_name!r} needs stats_axis_size "
                    "(the ring's static size); the sharded drivers "
                    "(fit_sharded / fit_restarts_sharded) resolve it from "
                    "the mesh automatically")

    # engine-regime fields a fitted LongTailModel's provenance is compared
    # against in from_longtail (chunks only matters when minibatch draws
    # sample from it — full-mode chunking is a memory layout, not a regime).
    # kernel_backend is not one: tpu, interpret and xla run the same kernel
    # math, so a model fitted with use_kernel on one platform is matched on
    # another, and the serving config resolves the backend where it runs.
    MATCHED_FIELDS = ("mode", "batch_chunks", "decay", "ema", "use_kernel")

    def matched_fingerprint(self) -> dict:
        """The regime this config clusters under, as stampable provenance."""
        d = {f: getattr(self, f) for f in self.MATCHED_FIELDS}
        d["chunks"] = self.chunks
        return d

    @classmethod
    def from_longtail(cls, model, desired_accuracy: float,
                      strict: bool = False, **kw):
        """Route a fitted LongTailModel through the engine: h* = f(r*).

        When the model carries engine-config provenance (it was fitted by
        ``repro.core.longtail_train`` on traces harvested under a concrete
        ``EngineConfig``), the production config built here is compared
        against it.  A regime mismatch fires a loud ``UserWarning`` — a
        transferred h* still *works* (the paired stop keeps the Eq. 7
        scale compatible) but is not mode-matched, which widens the
        achieved-accuracy spread (ROADMAP; ``BENCH_longtail_matched.json``
        quantifies it).  ``strict=True`` upgrades the warning to
        :class:`ProvenanceMismatchError` — the serving registry's admission
        contract, where a silently mis-calibrated threshold must never
        reach production traffic.
        """
        cfg = cls(h_star=float(model.threshold_for(desired_accuracy)), **kw)
        prov = getattr(model, "engine_config", None)
        if prov:
            fields = list(cls.MATCHED_FIELDS)
            if prov.get("mode") == "minibatch" or cfg.mode == "minibatch":
                fields.append("chunks")
            diff = {f: (prov[f], getattr(cfg, f)) for f in fields
                    if f in prov and prov[f] != getattr(cfg, f)}
            if diff:
                detail = ", ".join(f"{f}: fitted={a!r} production={b!r}"
                                   for f, (a, b) in sorted(diff.items()))
                msg = (
                    "LongTailModel was fitted under a different engine "
                    f"configuration than it is now serving ({detail}); "
                    "h* transfers via the paired Eq. 7 stop but is not "
                    "mode-matched — re-fit with "
                    "repro.core.longtail_train.fit_for_config under the "
                    "production EngineConfig to tighten the achieved-"
                    "accuracy spread")
                if strict:
                    raise ProvenanceMismatchError(msg, diff)
                import warnings
                warnings.warn(msg, UserWarning, stacklevel=2)
        return cfg


class Trace(NamedTuple):
    """Per-iteration fit history, recorded on device when ``config.trace``.

    All buffers are [T] = [max_iters]-shaped ([R, T] from the restart
    drivers); ``mask[i] = 1`` marks iterations that actually executed.
    ``h[i]`` is the Eq. 7 change rate of iteration i — the *paired*
    same-subsample rate in minibatch mode — and ``params`` holds the
    parameter state ``objectives[i]`` was measured at, i.e. the state whose
    partition accuracy r_i pairs with h_i (pre-update parameters in full
    mode, where J is evaluated before the update; post-update parameters in
    paired minibatch mode, where the paired J is evaluated after it).
    Index 0 of a full-mode trace carries h = inf (Eq. 7 starts at the
    second sweep); harvesting drops non-finite rows.  A minibatch trace
    with ``use_h_stop=False`` records the pre-update subsample objective
    (no paired pass runs) and h stays inf throughout — there is no Eq. 7
    signal to harvest without the pairing.
    """
    objectives: jnp.ndarray     # [T] J / loglik (per-point subsample value
                                #     in minibatch mode)
    h: jnp.ndarray              # [T] Eq. 7 change rate (paired in minibatch)
    mask: jnp.ndarray           # [T] f32 1 where the iteration executed
    params: Any                 # [T, ...] parameter trajectory


class EngineResult(NamedTuple):
    params: Any                 # centroids [K,D] | GMMParams
    labels: jnp.ndarray         # [N] int32 (local rows under shard_map)
    objective: jnp.ndarray      # [] J / loglik at the final params
    n_iters: jnp.ndarray        # [] int32
    h: jnp.ndarray              # [] last change rate observed
    trace: Any = None           # Trace when config.trace, else None


class RestartResult(NamedTuple):
    best: EngineResult          # the argbest-objective restart
    best_index: jnp.ndarray     # [] int32
    objectives: jnp.ndarray     # [R] final objective per restart
    n_iters: jnp.ndarray        # [R] iterations per restart
    traces: Any = None          # [R, T] Trace when config.trace, else None


class ShardedProgram(NamedTuple):
    """A shard_map'd fit program, its concrete arguments, and the
    mesh-resolved config — built by ``sharded_fit_callable`` /
    ``sharded_restarts_callable`` so callers can run (``fn(*args)``),
    trace (``jax.make_jaxpr(fn)(*args)``) or compile-without-running
    (``jax.jit(fn).lower(*args)``) the exact production graph."""
    fn: Any                     # shard_map'd callable
    args: tuple                 # (xc, mask, params0, h_star) concrete arrays
    config: Any                 # EngineConfig with axis_name/stats_axis_size


# --------------------------------------------------------------------------
# Streaming sweep
# --------------------------------------------------------------------------

# one chunk layout for everything: full sweeps, minibatch draws, ++ init
_chunk_points = _km.chunk_points


def _chunk_stats_fn(alg, config: EngineConfig):
    """The per-chunk masked stats pass: jnp ``chunk_stats`` or the
    dispatched kernel op, per ``config.use_kernel`` / ``kernel_backend``."""
    if config.use_kernel:
        return functools.partial(alg.kernel_chunk_stats,
                                 backend=config.kernel_backend)
    return alg.chunk_stats


def _stats_compressed(config: EngineConfig) -> bool:
    """True when this config actually runs the int8 ring (compression on,
    sharded, more than one shard — a 1-device ring is the identity)."""
    return (config.stats_compression == "int8_ef"
            and config.axis_name is not None
            and config.stats_axis_size > 1)


def _stats_reducer(alg, config: EngineConfig):
    """The per-sweep stats reduction → ``(init_ef, reduce_stats)``.

    ``reduce_stats(stats, ef, params) -> (reduced_stats, new_ef)`` replaces
    the inline psum in the fit-loop bodies.  Uncompressed (or unsharded, or
    single-shard) configs psum exactly and carry an empty ``ef = ()``.

    With ``stats_compression="int8_ef"`` the stats are first rotated into
    the algorithm's *centred* compression basis (``alg.compress_basis`` —
    moments taken around the current parameters, so the transmitted values
    shrink as the fit converges and the pmax-shared int8 scale shrinks with
    them; for EM this is what makes compression viable at all, see
    ``EMAlgorithm.compress_basis``).  Matrix leaves (ndim >= 2: the [K, D]
    moments) then go through ``compress_with_feedback`` +
    ``ring_allreduce_int8`` (sum mode, int8 on the wire with one scale
    per cluster row, Karimireddy-style residual carried to the next
    iteration) while the vector leaves (counts / r_sum, K floats — the
    basis inverts exactly with them) and the scalar leaves (J / loglik)
    stay exact fp32 psum — the scalars drive the Eq. 7 stop, where int8's
    ~8e-3 relative resolution is
    orders of magnitude above production h* thresholds.  The reduced tree
    is rotated back via ``alg.decompress_basis`` (an exact linear
    inversion using the reduced tree itself).

    The ring's output is bit-identical on every shard and ``params`` is
    replicated, so replicated stop decisions stay in lock-step (diverging
    trip counts under shard_map would deadlock the collective).
    """
    if not _stats_compressed(config):
        if config.axis_name is None:
            return (lambda stats_like: ()), (
                lambda stats, ef, params: (stats, ef))

        def reduce_psum(stats, ef, params):
            del params  # uncompressed leg has no error-feedback state
            return jax.tree.map(
                lambda a: jax.lax.psum(a, config.axis_name), stats), ef

        return (lambda stats_like: ()), reduce_psum

    from repro.distribution.compression import (compress_with_feedback,
                                                ring_allreduce_int8,
                                                shared_scale)
    axis, size = config.axis_name, config.stats_axis_size

    def init_ef(stats_like):
        """Zero residual buffers for the compressed (ndim >= 1) leaves."""
        return tuple(jnp.zeros(jnp.shape(a), jnp.float32)
                     for a in jax.tree.leaves(stats_like)
                     if jnp.ndim(a) >= 2)

    def reduce_stats(stats, ef, params):
        stats = alg.compress_basis(params, stats)
        flat, tree = jax.tree.flatten(stats)
        out, new_ef, i = [], [], 0
        for a in flat:
            if jnp.ndim(a) < 2:
                out.append(jax.lax.psum(a, axis))
                continue
            reduced, e = compress_with_feedback(
                a, ef[i],
                lambda g: ring_allreduce_int8(g, axis, size, mean=False),
                scale_fn=lambda g: shared_scale(g, axis, size))
            out.append(reduced)
            new_ef.append(e)
            i += 1
        reduced_stats = jax.tree.unflatten(tree, out)
        return alg.decompress_basis(params, reduced_stats), tuple(new_ef)

    return init_ef, reduce_stats


def stats_wire_bytes(stats_like, axis_size: int,
                     compression: str = "none") -> int:
    """Analytic bytes-on-wire each device sends for ONE stats reduction.

    Mirrors ``_stats_reducer``'s leaf policy: under ``int8_ef`` every
    matrix leaf (ndim >= 2) moves 1 byte/element over the ring plus the
    f32 pmax of its shared scales, one per row; vector and scalar leaves
    (and every leaf under ``none``) move 4 bytes/element.  Both paths carry the same ring factor
    2·(N−1)/N, so it cancels in int8-vs-fp32 ratios but keeps the absolute
    numbers meaningful to a cost model.  ``stats_like`` may be concrete or
    abstract (``jax.eval_shape``) — only shapes are read.
    """
    from repro.distribution.compression import ring_wire_bytes
    total = 0
    for a in jax.tree.leaves(stats_like):
        shape = jnp.shape(a)
        n = 1
        for s in shape:
            n *= int(s)
        if compression == "int8_ef" and len(shape) >= 2:
            total += ring_wire_bytes(n, axis_size)              # int8
            total += ring_wire_bytes(4 * int(shape[0]), axis_size)  # scales
        else:
            total += ring_wire_bytes(4 * n, axis_size)   # fp32 psum
    return total


def _sweep_chunked(alg, config: EngineConfig, xc, mask, params,
                   with_labels: bool, reduce: bool = True):
    """One full pass over a pre-chunked [C, P, D] layout (+ [C, P] mask)
    → (labels [C, P] | None, sufficient stats), stats psum'd over
    ``axis_name`` (``reduce=False`` leaves them shard-local for a caller-
    side reducer — the compressed-stats fit loops).  This is the layout
    the sharded drivers hand each shard (its row-slice of every global
    chunk); labels stay in chunk layout so callers can
    shard/flatten/strip-padding as they need.  With ``use_kernel`` each
    chunk runs through the dispatched kernel op (the mask operand carries
    the padding), so the sharded drivers serve both paths.

    ``config.prefetch`` double-buffers the scan: the carry holds the chunk
    being processed and the body issues the load of chunk i+1, which has no
    data dependency on the current chunk's compute — same chunk order, same
    accumulation, bit-identical stats/labels."""
    chunk_stats = _chunk_stats_fn(alg, config)
    zero = alg.zero_stats(params)

    def compute(acc, xi, mi):
        lab, st = chunk_stats(xi, mi, params)
        acc = jax.tree.map(jnp.add, acc, st)
        return acc, (lab if with_labels else jnp.zeros((), jnp.int32))

    c = xc.shape[0]
    if config.prefetch and c > 1:
        def body(carry, i):
            acc, x_cur, m_cur = carry
            nxt = jnp.minimum(i + 1, c - 1)
            x_nxt = jax.lax.dynamic_index_in_dim(xc, nxt, keepdims=False)
            m_nxt = jax.lax.dynamic_index_in_dim(mask, nxt, keepdims=False)
            acc, lab = compute(acc, x_cur, m_cur)
            return (acc, x_nxt, m_nxt), lab

        (stats, _, _), labs = jax.lax.scan(
            body, (zero, xc[0], mask[0]), jnp.arange(c))
    else:
        def body(acc, inp):
            xi, mi = inp
            return compute(acc, xi, mi)

        stats, labs = jax.lax.scan(body, zero, (xc, mask))
    if reduce and config.axis_name is not None:
        stats = jax.tree.map(
            lambda a: jax.lax.psum(a, config.axis_name), stats)
    return (labs if with_labels else None), stats


def _sweep(alg, config: EngineConfig, x, params, with_labels: bool,
           reduce: bool = True):
    """One full pass over the points → (labels | None, sufficient stats).

    chunks=1 runs the monolithic fused pass; chunks>1 streams via lax.scan
    (pure-JAX path) or via the dispatched ops' chunked entry points (fused
    path, static slices; ``config.kernel_backend`` pins a registry
    backend).  Stats are psum'd over ``axis_name`` once per sweep
    (``reduce=False`` defers to a caller-side reducer).
    """
    if config.use_kernel:
        labels, stats = alg.kernel_stats(x, params, config.chunks,
                                         backend=config.kernel_backend)
        if not with_labels:
            labels = None
    elif config.chunks <= 1:
        ones = jnp.ones((x.shape[0],), jnp.float32)
        labels, stats = alg.chunk_stats(x, ones, params)
        if not with_labels:
            labels = None
    else:
        xc, mask = _chunk_points(x, config.chunks)
        labels, stats = _sweep_chunked(alg, config, xc, mask, params,
                                       with_labels, reduce=reduce)
        if with_labels:
            labels = labels.reshape(-1)[: x.shape[0]]
        return labels, stats
    if reduce and config.axis_name is not None:
        stats = jax.tree.map(
            lambda a: jax.lax.psum(a, config.axis_name), stats)
    return labels, stats


def _minibatch_draw(config: EngineConfig, mask, key):
    """Draw B-of-C chunk *indices* without replacement → idx [B] i32.

    Only indices: the stats pass dynamic-slices each drawn chunk out of the
    resident [C, P, D] layout, so the [B, P, D] gathered copy never
    materialises and the kernel ops see statically-shaped chunks.  The
    paired Eq. 7 evaluation reuses the SAME drawn indices structurally
    (one draw per iteration), rather than leaning on PRNG determinism +
    XLA CSE to dedup a second draw.
    """
    if mask.shape[0] <= config.batch_chunks:
        # chunk_points clamps C to the row count; fail with the engine's
        # message rather than choice()'s opaque replace=False trace error
        raise ValueError(
            f"minibatch mode needs batch_chunks < effective chunks, but "
            f"the data only splits into {mask.shape[0]} chunk(s) "
            f"(batch_chunks={config.batch_chunks}, chunks={config.chunks}); "
            "reduce batch_chunks or use mode='full' at this scale")
    return jax.random.choice(key, mask.shape[0],
                             shape=(config.batch_chunks,), replace=False)


def _minibatch_stats(alg, config: EngineConfig, xc, mask, idx, params,
                     reduce: bool = True):
    """Masked stats over the drawn chunks → (stats, n_batch) — the same
    accumulation as the full sweep, over N·B/C points only, via the shared
    gather-free subsample driver (``kernels.layout.subsampled_stats``).
    ``reduce=False`` leaves the stats shard-local for a caller-side
    reducer; n_batch (a scalar the update and stop divide by) is always
    psum'd exact."""
    from repro.kernels.layout import subsampled_stats
    chunk_stats = _chunk_stats_fn(alg, config)

    def call(xi, mi):
        _, st = chunk_stats(xi, mi, params)
        return st

    stats, n_batch = subsampled_stats(call, alg.zero_stats(params),
                                      xc, mask, idx,
                                      prefetch=config.prefetch)
    if config.axis_name is not None:
        if reduce:
            stats = jax.tree.map(
                lambda a: jax.lax.psum(a, config.axis_name), stats)
        n_batch = jax.lax.psum(n_batch, config.axis_name)
    return stats, n_batch


def _minibatch_sweep(alg, config: EngineConfig, xc, mask, params, key):
    """draw + stats in one call (kept for tests / external callers)."""
    idx = _minibatch_draw(config, mask, key)
    return _minibatch_stats(alg, config, xc, mask, idx, params)


def _global_n(x, config: EngineConfig):
    n = jnp.asarray(x.shape[0], jnp.float32)
    if config.axis_name is not None:
        n = jax.lax.psum(n, config.axis_name)
    return n


# --------------------------------------------------------------------------
# Single-restart driver
# --------------------------------------------------------------------------

class _State(NamedTuple):
    params: Any
    j_curr: jnp.ndarray
    h: jnp.ndarray
    hits: jnp.ndarray
    iteration: jnp.ndarray
    moved: jnp.ndarray
    key: jnp.ndarray            # minibatch chunk-sampling stream
    carry: Any                  # minibatch step-size state (v counts)
    trace: Any                  # Trace buffers when config.trace, else ()
    ef: Any = ()                # int8_ef quantisation residuals, else ()


def _zero_trace(config: EngineConfig, params0):
    """Empty [T]-shaped trace buffers (h starts at inf — 'never measured')."""
    t = config.max_iters
    return Trace(
        objectives=jnp.zeros((t,), jnp.float32),
        h=jnp.full((t,), jnp.inf, jnp.float32),
        mask=jnp.zeros((t,), jnp.float32),
        params=jax.tree.map(
            lambda a: jnp.zeros((t,) + a.shape, jnp.float32), params0))


def _live(config: EngineConfig, iteration, hits, moved):
    """Continue-predicate shared by cond() and the per-restart masks."""
    live = iteration < config.max_iters
    if config.use_h_stop:
        live = jnp.logical_and(
            live, jnp.logical_or(iteration < 2, hits < config.patience))
    if config.stop_when_frozen:
        live = jnp.logical_and(live, moved)
    return live


def _fit_loop(alg, config: EngineConfig, params0, h_star, n_total, sweep,
              mb_data):
    """Shared single-fit driver: while_loop + Eq. 7 stop + final labels pass.

    ``sweep(params, with_labels)`` is the full-pass closure — over flat
    points (``_fit``) or over a pre-chunked shard-local layout
    (``_fit_chunked``); ``mb_data`` is the (xc, mask) chunk layout the
    minibatch draws sample from (None in full mode)."""
    minibatch = config.mode == "minibatch"
    xc, mask = mb_data if minibatch else (None, None)
    init_ef, reduce_stats = _stats_reducer(alg, config)
    init = _State(
        params=params0,
        j_curr=jnp.asarray(jnp.inf, jnp.float32),
        h=jnp.asarray(jnp.inf, jnp.float32),
        hits=jnp.asarray(0, jnp.int32),
        iteration=jnp.asarray(0, jnp.int32),
        moved=jnp.asarray(True),
        key=jax.random.PRNGKey(config.seed),
        carry=alg.zero_carry(params0) if minibatch else (),
        trace=_zero_trace(config, params0) if config.trace else (),
        ef=init_ef(alg.zero_stats(params0)),
    )

    def cond(s: _State):
        return _live(config, s.iteration, s.hits, s.moved)

    def body(s: _State):
        if minibatch:
            key, sub = jax.random.split(s.key)
            idx = _minibatch_draw(config, mask, sub)
            stats, n_batch = _minibatch_stats(alg, config, xc, mask, idx,
                                              s.params, reduce=False)
            stats, ef = reduce_stats(stats, s.ef, s.params)
            j_old = alg.objective(stats) / jnp.maximum(n_batch, 1.0)
            new_params, carry = alg.minibatch_update(
                s.params, stats, s.carry, n_batch, config.decay)
            # paired h (Eq. 7 on the SAME subsample, old vs new params):
            # raw cross-batch differences floor h at the subsampling noise,
            # while the paired ratio's sample noise cancels — so full-batch
            # fitted h* thresholds transfer to minibatch stopping.  Skipped
            # when the h predicate is off (the pairing is a second distance
            # pass; don't pay it for a value nothing reads).
            if config.use_h_stop:
                stats2, _ = _minibatch_stats(alg, config, xc, mask, idx,
                                             new_params, reduce=False)
                stats2, ef = reduce_stats(stats2, ef, s.params)
                j = alg.objective(stats2) / jnp.maximum(n_batch, 1.0)
                h = jnp.abs(j - j_old) / jnp.maximum(jnp.abs(j_old), _EPS)
                h = jnp.where(jnp.isfinite(s.h),
                              config.ema * s.h + (1.0 - config.ema) * h, h)
            else:
                j, h = j_old, s.h
        else:
            _, stats = sweep(s.params, False, reduce=False)
            stats, ef = reduce_stats(stats, s.ef, s.params)
            j = alg.objective(stats)
            new_params = alg.update(s.params, stats, n_total)
            key, carry = s.key, s.carry
            h = jnp.where(
                jnp.isfinite(s.j_curr),
                jnp.abs(j - s.j_curr) / jnp.maximum(jnp.abs(s.j_curr), _EPS),
                jnp.asarray(jnp.inf, jnp.float32))
        hits = jnp.where(h <= h_star, s.hits + 1, 0)
        moved = alg.moved(new_params, s.params)
        if config.trace:
            # record where j was measured: at s.params in full mode (the
            # sweep runs before the update) and at new_params in paired
            # minibatch mode (the second pass runs after it) — either way
            # h_i pairs with the state the iteration's transition arrived
            # at, so the harvested accuracy r_i is read off the same
            # index.  With the h predicate off, minibatch skips the paired
            # pass and j is the pre-update subsample objective — record
            # s.params then, keeping the measured-at invariant.
            paired = minibatch and config.use_h_stop
            i = s.iteration
            tr = Trace(
                objectives=s.trace.objectives.at[i].set(j),
                h=s.trace.h.at[i].set(h),
                mask=s.trace.mask.at[i].set(1.0),
                params=jax.tree.map(
                    lambda buf, p: buf.at[i].set(p), s.trace.params,
                    new_params if paired else s.params))
        else:
            tr = s.trace
        return _State(new_params, j, h, hits, s.iteration + 1, moved,
                      key, carry, tr, ef)

    final = jax.lax.while_loop(cond, body, init)
    # the labels pass is always a full sweep with the exact fp32 psum —
    # minibatch/compression only change how the parameters got there, not
    # the result contract
    labels, stats = sweep(final.params, True)
    return EngineResult(final.params, labels, alg.objective(stats),
                        final.iteration, final.h,
                        final.trace if config.trace else None)


@functools.partial(jax.jit, static_argnames=("alg", "config"))
def _fit(x, params0, h_star, alg, config: EngineConfig):
    x = x.astype(jnp.float32)
    n_total = _global_n(x, config)
    params0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    mb = (_chunk_points(x, config.chunks)
          if config.mode == "minibatch" else None)

    def sweep(params, with_labels, reduce=True):
        return _sweep(alg, config, x, params, with_labels=with_labels,
                      reduce=reduce)

    return _fit_loop(alg, config, params0, h_star, n_total, sweep, mb)


@functools.partial(jax.jit, static_argnames=("alg", "config"))
def _fit_chunked(xc, mask, params0, h_star, alg, config: EngineConfig):
    """``_fit`` on a pre-chunked [C, P, D] (+ [C, P] mask) layout — the
    shard_map entry point.  Under ``axis_name`` every shard holds its
    row-slice of each *global* chunk, so the replicated seeded draw selects
    the same global subsample on every shard and the psum'd stats keep the
    update + paired Eq. 7 stop identical to the single-device trajectory.
    Labels come back in the [C, P] chunk layout (callers flatten and strip
    the mask-0 padding after gathering across shards)."""
    xc = xc.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    n_total = jnp.sum(mask)
    if config.axis_name is not None:
        n_total = jax.lax.psum(n_total, config.axis_name)
    params0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    mb = (xc, mask) if config.mode == "minibatch" else None

    def sweep(params, with_labels, reduce=True):
        return _sweep_chunked(alg, config, xc, mask, params,
                              with_labels=with_labels, reduce=reduce)

    return _fit_loop(alg, config, params0, h_star, n_total, sweep, mb)


@functools.partial(jax.jit, static_argnames=("alg", "config"))
def _step(x, params, alg, config: EngineConfig):
    """One iteration: (new_params, labels, objective) — the traced drivers'
    building block, so host-loop and on-device paths share one sweep."""
    x = x.astype(jnp.float32)
    n_total = _global_n(x, config)
    labels, stats = _sweep(alg, config, x, params, with_labels=True)
    return alg.update(params, stats, n_total), labels, alg.objective(stats)


# --------------------------------------------------------------------------
# Multi-restart driver (vmap + per-restart stop masks)
# --------------------------------------------------------------------------

class _BatchState(NamedTuple):
    params: Any                 # [R, ...]
    j_curr: jnp.ndarray         # [R]
    h: jnp.ndarray              # [R]
    hits: jnp.ndarray           # [R] int32
    n_iters: jnp.ndarray        # [R] int32
    moved: jnp.ndarray          # [R] bool
    active: jnp.ndarray         # [R] bool — restart still iterating
    keys: jnp.ndarray           # [R, 2] per-restart minibatch streams
    carry: Any                  # [R, ...] minibatch step-size state
    trace: Any                  # [R, T] Trace buffers when config.trace
    ef: Any = ()                # [R, ...] int8_ef residuals, else ()


def _zero_trace_restarts(config: EngineConfig, params0, r: int):
    """[R, T]-shaped trace buffers for the vmapped restart fleet."""
    t = config.max_iters
    return Trace(
        objectives=jnp.zeros((r, t), jnp.float32),
        h=jnp.full((r, t), jnp.inf, jnp.float32),
        mask=jnp.zeros((r, t), jnp.float32),
        params=jax.tree.map(
            lambda a: jnp.zeros((r, t) + a.shape[1:], jnp.float32), params0))


def _mask_tree(active, new, old):
    """Per-leaf jnp.where with `active` broadcast over trailing dims."""
    def one(n, o):
        a = active.reshape(active.shape + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)
    return jax.tree.map(one, new, old)


def _restart_loop(alg, config: EngineConfig, params0, h_star, n_total,
                  sweep_stats, sweep_labels, mb_data):
    """Shared multi-restart driver (vmapped body + per-restart stop masks).

    ``sweep_stats(params)`` / ``sweep_labels(params)`` are the vmapped
    full-pass closures (flat or chunked layout); ``mb_data`` is the
    (xc, mask) chunk layout per-restart minibatch draws sample from.
    Under shard_map the psums inside the closures batch over the restart
    axis (vmap-of-psum), so every shard agrees on each restart's stop
    iteration and on the final argbest."""
    r = jax.tree.leaves(params0)[0].shape[0]
    minibatch = config.mode == "minibatch"
    init_ef, reduce_stats = _stats_reducer(alg, config)
    # vmap over the restart axis: the ring/psum inside batches per restart
    # (vmap-of-collective), each restart carrying its own residual buffers
    reduce_v = jax.vmap(reduce_stats)
    if minibatch:
        xc, mask = mb_data
        mb_draw_v = jax.vmap(
            lambda kk: _minibatch_draw(config, mask, kk))
        mb_stats_v = jax.vmap(
            lambda idx, p: _minibatch_stats(alg, config, xc, mask, idx, p,
                                            reduce=False))
        mb_update_v = jax.vmap(
            lambda p, st, cv, nb: alg.minibatch_update(p, st, cv, nb,
                                                       config.decay))
    update_v = jax.vmap(alg.update, in_axes=(0, 0, None))
    objective_v = jax.vmap(alg.objective)
    moved_v = jax.vmap(alg.moved)

    inf = jnp.full((r,), jnp.inf, jnp.float32)
    zeros_i = jnp.zeros((r,), jnp.int32)
    true_b = jnp.ones((r,), bool)
    init = _BatchState(
        params=params0,
        j_curr=inf, h=inf, hits=zeros_i, n_iters=zeros_i,
        moved=true_b, active=_live(config, zeros_i, zeros_i, true_b),
        keys=jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.PRNGKey(config.seed), jnp.arange(r)),
        carry=(jax.vmap(alg.zero_carry)(params0) if minibatch else ()),
        trace=(_zero_trace_restarts(config, params0, r)
               if config.trace else ()),
        ef=jax.vmap(lambda p: init_ef(alg.zero_stats(p)))(params0),
    )

    def cond(s: _BatchState):
        return jnp.any(s.active)

    def body(s: _BatchState):
        # every restart computes; stopped restarts are masked back to their
        # frozen state (the "no-op body" — XLA keeps one batched program)
        if minibatch:
            split = jax.vmap(jax.random.split)(s.keys)      # [R, 2, 2]
            keys, subs = split[:, 0], split[:, 1]
            idx = mb_draw_v(subs)                           # [R, B] indices
            stats, n_batch = mb_stats_v(idx, s.params)
            stats, ef = reduce_v(stats, s.ef, s.params)
            j_old = objective_v(stats) / jnp.maximum(n_batch, 1.0)
            new_params, carry = mb_update_v(s.params, stats, s.carry,
                                            n_batch)
            # paired h on the same per-restart subsample (see _fit)
            if config.use_h_stop:
                stats2, _ = mb_stats_v(idx, new_params)
                stats2, ef = reduce_v(stats2, ef, s.params)
                j = objective_v(stats2) / jnp.maximum(n_batch, 1.0)
                h = (jnp.abs(j - j_old)
                     / jnp.maximum(jnp.abs(j_old), _EPS)).astype(jnp.float32)
                h = jnp.where(jnp.isfinite(s.h),
                              config.ema * s.h + (1.0 - config.ema) * h, h)
            else:
                j, h = j_old, s.h
        else:
            stats, ef = reduce_v(sweep_stats(s.params), s.ef, s.params)
            j = objective_v(stats)
            new_params = update_v(s.params, stats, n_total)
            keys, carry = s.keys, s.carry
            h = jnp.where(
                jnp.isfinite(s.j_curr),
                jnp.abs(j - s.j_curr) / jnp.maximum(jnp.abs(s.j_curr), _EPS),
                jnp.inf).astype(jnp.float32)
        hits = jnp.where(h <= h_star, s.hits + 1, 0)
        moved = moved_v(new_params, s.params)
        a = s.active
        params = _mask_tree(a, new_params, s.params)
        j_curr = jnp.where(a, j, s.j_curr)
        h_out = jnp.where(a, h, s.h)
        hits_out = jnp.where(a, hits, s.hits)
        n_iters = jnp.where(a, s.n_iters + 1, s.n_iters)
        moved_out = jnp.where(a, moved, s.moved)
        active = jnp.logical_and(
            a, _live(config, n_iters, hits_out, moved_out))
        carry_out = _mask_tree(a, carry, s.carry) if minibatch else carry
        # stopped restarts keep their frozen residuals (nothing reads them
        # again, but the masked no-op body must stay a fixed point)
        ef_out = _mask_tree(a, ef, s.ef) if jax.tree.leaves(s.ef) else s.ef
        if config.trace:
            # per-restart scatter at each restart's own iteration counter;
            # stopped restarts are masked back (a write landing at a
            # clamped index is undone by _mask_tree).  Params recorded
            # where j was measured — see _fit_loop.
            rows = jnp.arange(r)
            idx = s.n_iters

            def scat(buf, val):
                return _mask_tree(a, buf.at[rows, idx].set(val), buf)

            tr = Trace(
                objectives=scat(s.trace.objectives, j),
                h=scat(s.trace.h, h),
                mask=scat(s.trace.mask, jnp.ones((r,), jnp.float32)),
                params=jax.tree.map(
                    scat, s.trace.params,
                    new_params if minibatch and config.use_h_stop
                    else s.params))
        else:
            tr = s.trace
        return _BatchState(params, j_curr, h_out, hits_out, n_iters,
                           moved_out, active, keys, carry_out, tr, ef_out)

    final = jax.lax.while_loop(cond, body, init)
    labels, stats = sweep_labels(final.params)
    objectives = objective_v(stats)
    best = (jnp.argmax(objectives) if alg.maximize
            else jnp.argmin(objectives)).astype(jnp.int32)
    best_result = EngineResult(
        params=jax.tree.map(lambda a: a[best], final.params),
        labels=labels[best],
        objective=objectives[best],
        n_iters=final.n_iters[best],
        h=final.h[best],
    )
    return RestartResult(best=best_result, best_index=best,
                         objectives=objectives, n_iters=final.n_iters,
                         traces=final.trace if config.trace else None)


@functools.partial(jax.jit, static_argnames=("alg", "config"))
def _fit_restarts(x, params0, h_star, alg, config: EngineConfig):
    x = x.astype(jnp.float32)
    n_total = _global_n(x, config)
    params0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    sweep_stats = jax.vmap(
        lambda p: _sweep(alg, config, x, p, with_labels=False,
                         reduce=False)[1])
    sweep_labels = jax.vmap(
        lambda p: _sweep(alg, config, x, p, with_labels=True))
    mb = (_chunk_points(x, config.chunks)
          if config.mode == "minibatch" else None)
    return _restart_loop(alg, config, params0, h_star, n_total, sweep_stats,
                         sweep_labels, mb)


@functools.partial(jax.jit, static_argnames=("alg", "config"))
def _fit_restarts_chunked(xc, mask, params0, h_star, alg,
                          config: EngineConfig):
    """``_fit_restarts`` on the pre-chunked shard-local layout (see
    ``_fit_chunked``): vmapped restarts *inside* shard_map, per-restart
    chunk streams and stop masks, stats psum'd per restart.  The best
    restart's labels come back as [C, P] (chunk layout)."""
    xc = xc.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    n_total = jnp.sum(mask)
    if config.axis_name is not None:
        n_total = jax.lax.psum(n_total, config.axis_name)
    params0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    sweep_stats = jax.vmap(
        lambda p: _sweep_chunked(alg, config, xc, mask, p,
                                 with_labels=False, reduce=False)[1])
    sweep_labels = jax.vmap(
        lambda p: _sweep_chunked(alg, config, xc, mask, p,
                                 with_labels=True))
    mb = (xc, mask) if config.mode == "minibatch" else None
    return _restart_loop(alg, config, params0, h_star, n_total, sweep_stats,
                         sweep_labels, mb)


# --------------------------------------------------------------------------
# Public facade
# --------------------------------------------------------------------------

class ClusteringEngine:
    """One engine, two algorithms, three drivers (step / fit / fit_restarts).

    >>> eng = ClusteringEngine("kmeans", EngineConfig(chunks=8, max_iters=100,
    ...                                               stop_when_frozen=True))
    >>> res = eng.fit(x, eng.init(key, x, k=8), h_star=1e-4)
    >>> best = eng.fit_restarts(x, key=key, k=8, restarts=4).best
    >>> mb = ClusteringEngine("kmeans", EngineConfig(
    ...     mode="minibatch", chunks=64, batch_chunks=16, patience=5,
    ...     max_iters=200))                 # touch 25% of the points per step
    >>> res = mb.fit(x, mb.init(key, x, k=8), h_star=1e-3)
    """

    def __init__(self, algorithm="kmeans", config: EngineConfig | None = None):
        self.algorithm = get_algorithm(algorithm)
        self.config = config if config is not None else EngineConfig()

    # -- initialisation ----------------------------------------------------
    def init(self, key, x, k: int):
        """Seed params; k-means++ D² sampling streams over ``config.chunks``
        so init honours the same memory envelope as the sweeps."""
        return self.algorithm.init(key, jnp.asarray(x), k,
                                   chunks=self.config.chunks)

    def init_restarts(self, key, x, k: int, restarts: int):
        """R independent seeds, stacked along a leading restart axis."""
        x = jnp.asarray(x)
        keys = jax.random.split(key, restarts)
        inits = [self.algorithm.init(kk, x, k, chunks=self.config.chunks)
                 for kk in keys]
        return jax.tree.map(lambda *leaves: jnp.stack(leaves), *inits)

    # -- drivers -----------------------------------------------------------
    def _tuning(self):
        """Autotune-cache scope for the drivers: active when
        ``config.autotune``, a no-op otherwise (and when no cache is
        installed — defaults stay bit-for-bit).  Entered around the
        driver *call*, which is where tracing resolves block shapes."""
        if not self.config.autotune:
            return contextlib.nullcontext()
        from repro.kernels import autotune as _autotune
        return _autotune.tuning(_autotune.default_cache())

    def step(self, x, params):
        """One iteration → (new_params, labels, objective)."""
        with self._tuning():
            return _step(jnp.asarray(x), params, self.algorithm, self.config)

    def fit(self, x, params0, h_star=None) -> EngineResult:
        hs = self.config.h_star if h_star is None else h_star
        with self._tuning(), spans.span("engine.dispatch"):
            return _fit(jnp.asarray(x), params0,
                        jnp.asarray(hs, jnp.float32),
                        self.algorithm, self.config)

    def fit_restarts(self, x, params0=None, *, key=None, k=None,
                     restarts=None, h_star=None) -> RestartResult:
        """Batched multi-restart fit; pass stacked ``params0`` or
        (key, k, restarts) to draw them."""
        x = jnp.asarray(x)
        if params0 is None:
            if key is None or k is None or restarts is None:
                raise ValueError(
                    "fit_restarts needs params0 or (key, k, restarts)")
            params0 = self.init_restarts(key, x, k, restarts)
        hs = self.config.h_star if h_star is None else h_star
        with self._tuning(), spans.span("engine.dispatch"):
            return _fit_restarts(x, params0, jnp.asarray(hs, jnp.float32),
                                 self.algorithm, self.config)

    # -- sharded drivers (shard_map over the mesh's data axes) -------------
    def _sharded_setup(self, x, mesh):
        """Chunk globally, shard each chunk's rows, derive the psum config.

        Returns (cfg, xc, mask, xc_spec, mask_spec) with xc [C, P', D] and
        mask [C, P'] placed on the mesh (P' = P padded to the data-axis
        extent; padding rows carry mask 0, so no row is ever truncated).
        """
        from jax.sharding import PartitionSpec as P
        from repro.distribution.sharding import (chunked_points_spec,
                                                 mesh_axes,
                                                 shard_chunked_points)
        dp, _, _ = mesh_axes(mesh)
        if not dp:
            raise ValueError(
                f"mesh axes {mesh.axis_names} contain no data axis (name "
                "one 'data' or 'pod'); the sharded drivers shard the "
                "points over the data axes")
        axis = dp if len(dp) > 1 else dp[0]
        if self.config.stats_compression != "none":
            if len(dp) > 1:
                raise ValueError(
                    "stats_compression rides a single-axis ppermute ring "
                    f"but mesh {mesh.axis_names} has data axes {dp}; "
                    "collapse them into one axis (or use "
                    "stats_compression='none')")
            # the ring needs its static size; a 1-device mesh degrades to
            # the exact path inside _stats_reducer
            cfg = dataclasses.replace(
                self.config, axis_name=axis,
                stats_axis_size=int(mesh.shape[dp[0]]))
        else:
            cfg = dataclasses.replace(self.config, axis_name=axis)
        xc, mask = _chunk_points(jnp.asarray(x, jnp.float32), cfg.chunks)
        xc, mask = shard_chunked_points(xc, mask, mesh)
        xc_spec = chunked_points_spec(mesh)
        return cfg, xc, mask, xc_spec, P(*tuple(xc_spec)[:2])

    @staticmethod
    def _strip_chunk_padding(labels, mask):
        """[C, P] chunk-layout labels → [N] flat labels in input row order
        (the chunk layout is row-major; padding rows have mask 0)."""
        return labels.reshape(-1)[mask.reshape(-1) > 0]

    def sharded_fit_callable(self, x, params0, mesh,
                             h_star=None) -> "ShardedProgram":
        """The shard_map'd fit program and its concrete arguments, WITHOUT
        running it.

        ``prog.fn(*prog.args)`` executes the fit;
        ``jax.make_jaxpr(prog.fn)(*prog.args)`` traces it and
        ``jax.jit(prog.fn).lower(*prog.args)`` compiles it — the static
        graph-contract rules in :mod:`repro.analysis` inspect both forms
        through this hook, so the linter checks the *same* program
        ``fit_sharded`` runs, not a reconstruction.  ``prog.config`` is
        the mesh-resolved :class:`EngineConfig` (``axis_name`` /
        ``stats_axis_size`` filled in); ``prog.args[1]`` is the padding
        mask.
        """
        from jax.sharding import PartitionSpec as P
        cfg, xc, mask, xc_spec, mask_spec = self._sharded_setup(x, mesh)
        params0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
        rep = jax.tree.map(lambda a: P(*(None,) * jnp.ndim(a)), params0)
        hs = self.config.h_star if h_star is None else h_star
        # the trace is computed from psum'd stats, so it is replicated —
        # every shard records the identical history
        tr_spec = (Trace(P(), P(), P(),
                         jax.tree.map(lambda a: P(), params0))
                   if cfg.trace else None)
        fit = jax.shard_map(
            functools.partial(_fit_chunked, alg=self.algorithm, config=cfg),
            mesh=mesh,
            in_specs=(xc_spec, mask_spec, rep, P()),
            out_specs=EngineResult(params=rep, labels=mask_spec,
                                   objective=P(), n_iters=P(), h=P(),
                                   trace=tr_spec),
            check_vma=False)
        return ShardedProgram(
            fit, (xc, mask, params0, jnp.asarray(hs, jnp.float32)), cfg)

    def fit_sharded(self, x, params0, mesh, h_star=None) -> EngineResult:
        """Distributed fit under ``shard_map`` — both engine modes.

        The points are chunked *globally* to [C, P, D] (the engine's one
        chunk layout) and each chunk's rows are sharded over the mesh's
        data axes, so a shard's local chunk c is a row-slice of global
        chunk c.  Per iteration every shard draws the same ``batch_chunks``
        chunk indices (the sampling key is replicated), computes stats over
        its resident slice, and psums once — the subsample, the
        learning-rate update, and the paired Eq. 7 stop are therefore
        identical to the single-device run up to fp32 reduction order.
        Labels cover all N input rows (chunk padding is stripped).
        """
        prog = self.sharded_fit_callable(x, params0, mesh, h_star)
        mask = prog.args[1]
        with self._tuning(), spans.span("engine.dispatch"):
            res = prog.fn(*prog.args)
        return res._replace(labels=self._strip_chunk_padding(res.labels,
                                                             mask))

    def sharded_restarts_callable(self, x, params0=None, mesh=None, *,
                                  key=None, k=None, restarts=None,
                                  h_star=None) -> "ShardedProgram":
        """The shard_map'd multi-restart program + concrete args, without
        running it — the restarts twin of :meth:`sharded_fit_callable`."""
        from jax.sharding import PartitionSpec as P
        if mesh is None:
            raise ValueError("fit_restarts_sharded needs a mesh")
        x = jnp.asarray(x)
        if params0 is None:
            if key is None or k is None or restarts is None:
                raise ValueError(
                    "fit_restarts_sharded needs params0 or (key, k, "
                    "restarts)")
            params0 = self.init_restarts(key, x, k, restarts)
        cfg, xc, mask, xc_spec, mask_spec = self._sharded_setup(x, mesh)
        params0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
        rep = jax.tree.map(lambda a: P(*(None,) * jnp.ndim(a)), params0)
        best_rep = jax.tree.map(lambda a: P(*(None,) * (jnp.ndim(a) - 1)),
                                params0)
        hs = self.config.h_star if h_star is None else h_star
        tr_spec = (Trace(P(), P(), P(),
                         jax.tree.map(lambda a: P(), params0))
                   if cfg.trace else None)
        fit = jax.shard_map(
            functools.partial(_fit_restarts_chunked, alg=self.algorithm,
                              config=cfg),
            mesh=mesh,
            in_specs=(xc_spec, mask_spec, rep, P()),
            out_specs=RestartResult(
                best=EngineResult(params=best_rep, labels=mask_spec,
                                  objective=P(), n_iters=P(), h=P()),
                best_index=P(), objectives=P(None), n_iters=P(None),
                traces=tr_spec),
            check_vma=False)
        return ShardedProgram(
            fit, (xc, mask, params0, jnp.asarray(hs, jnp.float32)), cfg)

    def fit_restarts_sharded(self, x, params0=None, mesh=None, *, key=None,
                             k=None, restarts=None,
                             h_star=None) -> RestartResult:
        """Vmapped multi-restart fit *inside* ``shard_map`` (vmap-of-psum):
        every restart keeps its own replicated chunk-draw stream and stop
        mask, stats are psum'd per restart, and all shards agree on each
        restart's stop iteration and on the final best-objective index.
        Accepts stacked ``params0`` or (key, k, restarts), like
        ``fit_restarts``."""
        prog = self.sharded_restarts_callable(
            x, params0, mesh, key=key, k=k, restarts=restarts, h_star=h_star)
        mask = prog.args[1]
        with self._tuning(), spans.span("engine.dispatch"):
            rr = prog.fn(*prog.args)
        return rr._replace(best=rr.best._replace(
            labels=self._strip_chunk_padding(rr.best.labels, mask)))
