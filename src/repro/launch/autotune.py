"""Kernel-autotuner CLI (ISSUE 9).

Sweep candidate block shapes per (op, backend, problem shape) and write
the winner cache::

    python -m repro.launch.autotune \
        --ops kmeans_assign,gmm_estep --backends interpret,xla \
        --shapes 16384x8x16,65536x8x4 --out autotune_cache.json

Shapes are ``NxKxD`` triples — rows × clusters × features for the
clustering ops, Sq × Skv × head_dim for ``flash_attention`` — applied to
every selected op.  The cache is versioned JSON
(``repro.kernels.autotune.AutotuneCache``); point
``REPRO_AUTOTUNE_CACHE`` (or ``autotune.set_default_cache``) at it and
run the engine with ``EngineConfig(autotune=True)`` to serve the tuned
blocks.  ``--merge`` loads an existing ``--out`` first and only tunes
missing cells (the cache-hit short-circuit skips re-timing).
"""
from __future__ import annotations

import argparse
import sys


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="repro.launch.autotune",
        description="Roofline-driven kernel autotuner: sweep block shapes "
                    "per (op, backend, shape), time with the shared "
                    "methodology, cache winners in versioned JSON.")
    ap.add_argument("--ops", default=None,
                    help="comma-separated op names (default: every "
                         "supported registered op)")
    ap.add_argument("--backends", default=None,
                    help="comma-separated backends (default: interpret + "
                         "xla, plus tpu/gpu when the hardware is present)")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated NxKxD triples (clustering: rows x "
                         "clusters x features; flash_attention: Sq x Skv x "
                         "head_dim); default: per-op suite")
    ap.add_argument("--reps", type=int, default=5,
                    help="timed reps per candidate (median-of-k; default 5)")
    ap.add_argument("--warmup", type=int, default=1,
                    help="untimed warmup calls per candidate (default 1)")
    ap.add_argument("--out", default="autotune_cache.json",
                    help="cache path to write (default autotune_cache.json)")
    ap.add_argument("--merge", action="store_true",
                    help="load --out first and only tune missing cells")
    return ap.parse_args(argv)


def _split(csv):
    return [t.strip() for t in (csv or "").split(",") if t.strip()] or None


def _parse_shapes(csv):
    if not csv:
        return None
    shapes = []
    for tok in csv.split(","):
        parts = tok.strip().lower().split("x")
        if len(parts) != 3:
            raise SystemExit(f"--shapes entry {tok!r} is not an NxKxD "
                             "triple (e.g. 16384x8x16)")
        shapes.append(tuple(int(p) for p in parts))
    return shapes


def main(argv=None) -> int:
    args = _parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()
    import os

    from repro.kernels import autotune

    cache = None
    if args.merge and os.path.exists(args.out):
        cache = autotune.AutotuneCache.load(args.out)
        print(f"# merged {len(cache.entries)} cached cell(s) from "
              f"{args.out}")
    cache = autotune.tune(
        ops=_split(args.ops), backends=_split(args.backends),
        shapes=_parse_shapes(args.shapes), reps=args.reps,
        warmup=args.warmup, cache=cache, log=print)
    cache.save(args.out)
    print(f"# wrote {len(cache.entries)} cell(s) to {args.out} "
          f"(schema v{autotune.SCHEMA_VERSION}, device "
          f"{autotune.device_kind()})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
