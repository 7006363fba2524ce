#!/usr/bin/env python3
"""Run one cell of the clustering benchmark once, on the chip it finds.

    python3 bench/run.py --workload poker-km.whole --seed 7 --seconds 10 \
        --trace 0

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, last, the
numbers compared beside their limits (``checks``), which also end standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.  See ``harness.py`` for what a run does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.spec import Spec

    try:
        spec = Spec(ROOT, BENCH)
        trace_dir = None
        if args.trace:
            trace_dir = ROOT / "bench_out" / "trace" / args.workload
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir)
            trace_dir = str(trace_dir)
        result = harness.run(spec, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START,
                             trace_dir=trace_dir)
    except (harness.Refused, FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
