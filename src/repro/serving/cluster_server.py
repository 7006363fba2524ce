"""Clustering-as-a-service: a continuous-batching assignment server.

This is the high-traffic side of the paper's economics (§5.4): models are
fitted rarely (``launch/cluster.py``), then *applied* constantly.  The
server generalises ``serve_loop``'s slot discipline — keep the compiled
shape set closed, refill from a queue — to clustering workloads:

  · :class:`ModelRegistry` admits fitted ``(params, LongTailModel)``
    artifacts (``core.artifacts.ClusterArtifact``), keyed by the
    provenance fingerprint from ``core.longtail_train.config_fingerprint``.
    Admission is *strict*: ``EngineConfig.from_longtail(strict=True)``
    raises :class:`~repro.core.engine.ProvenanceMismatchError` when the
    serving regime does not match the regime the stop-model was fitted
    under — a mis-calibrated h* must never reach production traffic.

  · :class:`ClusterServer` drains a queue of assignment batches (plus
    small incremental minibatch-fit jobs) into fixed padded batch-bucket
    shapes (``kernels.layout.bucket_for``), so XLA compiles one program
    per (model, bucket).  The hot path runs through the backend-dispatched
    assignment ops (``kernels.dispatch``: the platform's kernel backend —
    compiled ``tpu`` on a TPU, ``interpret`` on a CPU — when the artifact
    was fitted with ``use_kernel``, the ``xla`` reference otherwise), with
    the ops' mask operand absorbing the bucket
    padding — padded rows are labelled −1 and dropped before the response
    is split back per request.

Request admission mirrors ``serve_loop.Server.admit_check``: malformed
batches (empty, wrong feature width, larger than the largest bucket,
unknown model, duplicate rid) raise ``ValueError`` before any device work.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.artifacts import ClusterArtifact, fingerprint_key
from repro.core.engine import (EngineConfig, _fit_chunked, get_algorithm)
from repro.core.longtail_train import config_fingerprint
from repro.kernels import layout
from repro.serving.batching import (AssignRequest, FitRequest, ServeMetrics,
                                    pack_batches)


def _serving_kwargs(prov: dict | None, overrides: dict | None) -> dict:
    """EngineConfig kwargs for serving an artifact: its stamped harvest
    regime, with explicit ``overrides`` on top.  Overriding to full mode
    drops the stamped minibatch knobs so the mismatch surfaces as a
    ProvenanceMismatchError (the admission contract), not as
    EngineConfig's stray-knob ValueError."""
    kw: dict = {}
    if prov:
        kw = {f: prov[f] for f in EngineConfig.MATCHED_FIELDS if f in prov}
        if "chunks" in prov:
            kw["chunks"] = prov["chunks"]
    if overrides:
        kw.update(overrides)
    if kw.get("mode", "full") == "full":
        for f, default in (("batch_chunks", 0), ("decay", 1.0),
                           ("seed", 0), ("ema", 0.0)):
            kw[f] = default
    if not kw.get("use_kernel", False):
        kw.pop("kernel_backend", None)
    return kw


@dataclasses.dataclass
class _Entry:
    """One registered model: device params + its compiled programs."""
    key: str
    artifact: ClusterArtifact
    config: EngineConfig
    params: Any                  # device copy, advanced by fit jobs
    assign: Any                  # jit'd (xp, mask, params) → (labels, obj)
    fit: Any                     # jit'd (xc, mask, params, h*) → EngineResult
    backend: str


class ModelRegistry:
    """Fitted artifacts keyed by ``name@fingerprint``; strict admission."""

    def __init__(self, *, devices: int = 1, fit_steps: int = 20,
                 overrides: dict | None = None):
        self.devices = devices
        self.fit_steps = fit_steps
        self.overrides = overrides
        self._entries: dict[str, _Entry] = {}

    def register(self, artifact: ClusterArtifact,
                 overrides: dict | None = None) -> str:
        """Admit an artifact; returns its registry key.

        Raises ``ProvenanceMismatchError`` when the serving configuration
        (stamped regime + overrides) mismatches the regime the artifact's
        stop-model was fitted under — rejected loudly, never registered.
        """
        ov = dict(self.overrides or {})
        ov.update(overrides or {})
        kw = _serving_kwargs(artifact.model.engine_config, ov)
        cfg = EngineConfig.from_longtail(
            artifact.model, artifact.desired_accuracy, strict=True, **kw)
        key = (f"{artifact.name}"
               f"@{fingerprint_key(config_fingerprint(cfg, self.devices))}")
        if key in self._entries:
            raise ValueError(f"model {key!r} already registered")
        alg = get_algorithm(artifact.algorithm)
        backend = cfg.kernel_backend if cfg.use_kernel else "xla"
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                              artifact.params)

        def _assign(xp, mask, p):
            labels, stats = alg.kernel_chunk_stats(xp, mask, p,
                                                   backend=backend)
            return labels, alg.objective(stats)

        fit_cfg = dataclasses.replace(
            cfg, trace=False, max_iters=self.fit_steps)

        def _fit(xc, mask, p, h_star):
            return _fit_chunked(xc, mask, p, h_star, alg=alg, config=fit_cfg)

        self._entries[key] = _Entry(
            key=key, artifact=artifact, config=cfg, params=params,
            assign=jax.jit(_assign), fit=jax.jit(_fit), backend=backend)
        return key

    def __getitem__(self, key: str) -> _Entry:
        try:
            return self._entries[key]
        except KeyError:
            raise ValueError(
                f"unknown model {key!r}; registered: "
                f"{sorted(self._entries)}") from None

    def keys(self):
        return sorted(self._entries)


class ClusterServer:
    """Queue → bucket-padded batches → dispatched assignment ops."""

    def __init__(self, registry: ModelRegistry, *,
                 buckets=layout.DEFAULT_BUCKETS):
        self.registry = registry
        self.buckets = tuple(sorted(buckets))
        self._queue: list = []
        self._pending_rids: set = set()
        self.metrics = ServeMetrics()

    # ---- admission (serve_loop.Server.admit_check's contract) ------------
    def submit(self, req) -> None:
        if not isinstance(req, (AssignRequest, FitRequest)):
            raise TypeError(f"unknown request type {type(req).__name__}")
        entry = self.registry[req.model_key]
        x = np.asarray(req.x, np.float32)
        if x.ndim != 2 or x.shape[0] < 1:
            raise ValueError(
                f"request {req.rid}: batch must be [n >= 1, d]; got shape "
                f"{x.shape}")
        if x.shape[1] != entry.artifact.d:
            raise ValueError(
                f"request {req.rid}: feature width {x.shape[1]} != model "
                f"{req.model_key!r} width {entry.artifact.d}")
        if x.shape[0] > self.buckets[-1]:
            raise ValueError(
                f"request {req.rid}: batch of {x.shape[0]} rows exceeds "
                f"the largest bucket {self.buckets[-1]} — split it")
        if req.rid in self._pending_rids:
            raise ValueError(f"request {req.rid}: rid already pending")
        self._pending_rids.add(req.rid)
        self._queue.append(dataclasses.replace(req, x=x))

    # ---- compile-shape bookkeeping ---------------------------------------
    def warmup(self, model_key: str, buckets=None) -> None:
        """Pre-compile the assign program for each bucket (zero-mask dummy
        batches) so drain latencies measure steady-state serving."""
        entry = self.registry[model_key]
        for b in (buckets or self.buckets):
            xp = jnp.zeros((b, entry.artifact.d), jnp.float32)
            mask = jnp.zeros((b,), jnp.float32)
            jax.block_until_ready(entry.assign(xp, mask, entry.params))

    def compiled_programs(self) -> dict[str, dict[str, int]]:
        """{model key: {assign/fit: jit cache entries}} — the recompile
        probe: assign must stay ≤ the number of distinct buckets served."""
        return {k: {"assign": int(self.registry[k].assign._cache_size()),
                    "fit": int(self.registry[k].fit._cache_size())}
                for k in self.registry.keys()}

    # ---- the serve loop --------------------------------------------------
    def _chunked_bucket(self, x: np.ndarray, config: EngineConfig):
        """Bucket-pad a fit batch and lay it out as the engine's [C, P, D]
        chunked layout; the combined mask zeroes both paddings."""
        bucket = layout.bucket_for(x.shape[0], self.buckets)
        xp, valid = layout.pad_to_bucket(x, bucket)
        xc, m = layout.chunk_points(xp, config.chunks)
        mask = m * valid.reshape(m.shape)
        return xc, mask

    def _serve_assign_group(self, entry: _Entry, group, results) -> None:
        xs = [r.x for r in group]
        total = sum(x.shape[0] for x in xs)
        bucket = layout.bucket_for(total, self.buckets)
        xp, mask = layout.pad_to_bucket(np.concatenate(xs, axis=0), bucket)
        t0 = time.perf_counter()
        labels, _obj = entry.assign(xp, mask, entry.params)
        labels = np.asarray(jax.block_until_ready(labels))
        dt = time.perf_counter() - t0
        self.metrics.record(entry.key, dt, total, len(group))
        off = 0
        for r in group:
            n = r.x.shape[0]
            results[r.rid] = labels[off:off + n].copy()
            off += n

    def _serve_fit(self, entry: _Entry, req: FitRequest, results) -> None:
        xc, mask = self._chunked_bucket(req.x, entry.config)
        t0 = time.perf_counter()
        res = entry.fit(xc, mask, entry.params,
                        jnp.asarray(entry.config.h_star, jnp.float32))
        res = jax.block_until_ready(res)
        dt = time.perf_counter() - t0
        self.metrics.record(f"{entry.key}#fit", dt, req.x.shape[0], 1)
        entry.params = res.params      # the model advances in place
        results[req.rid] = {"objective": float(res.objective),
                            "n_iters": int(res.n_iters)}

    def drain(self) -> dict:
        """Serve everything queued; returns {rid: labels [n] | fit result}.

        Assignment batches are grouped per model and packed (arrival
        order) up to the largest bucket; fit jobs run one at a time —
        they are rare by construction (the paper's whole premise).
        """
        queue, self._queue = self._queue, []
        results: dict = {}
        by_model: dict[str, list] = {}
        for req in queue:
            by_model.setdefault(req.model_key, []).append(req)
        for key in sorted(by_model):
            entry = self.registry[key]
            assigns = [r for r in by_model[key]
                       if isinstance(r, AssignRequest)]
            fits = [r for r in by_model[key] if isinstance(r, FitRequest)]
            for group in pack_batches(assigns, self.buckets[-1]):
                self._serve_assign_group(entry, group, results)
            for req in fits:
                self._serve_fit(entry, req, results)
        self._pending_rids -= set(results)
        return results
