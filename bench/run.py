#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print one JSON result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the first seconds of the
window. Every run checks the first rounds against the plain reference
(``bench/check.py``) and prints each compared number beside its limit, on
standard error and under ``checks`` in the result line. A run that finds no
TPU, or fewer chips than the cell asks for, exits non-zero and prints no
result. The compile cache is kept in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _seed(text: str) -> int:
    value = int(text)
    if value < 0:
        raise argparse.ArgumentTypeError("--seed must be >= 0")
    return value


def main(argv=None) -> None:
    """Parse the flags, run the cell, print the result line last."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=_seed, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import boot  # noqa: PLC0415

    boot.start(ROOT)
    from bench import harness  # noqa: PLC0415

    result, table = harness.run(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), T_START)
    for name, row in table.items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
