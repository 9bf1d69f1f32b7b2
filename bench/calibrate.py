#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
      [--control-seeds a,b,c] [--faults half_batch,answer] \\
      [--fault-seeds x,y,z] --out <file.jsonl>

For each seed it runs the program's first rounds through the benchmark's
own path (``harness.drive``) and the plain reference, and writes the
compared numbers (``bench/check.py``), with the per-leaf readings of both
sides that they are made from, as one JSON line: ``program`` for the
program as it is, ``control`` for the reference computed with float8
(e4m3) rounding put in the program's place, and one kind per fault of
``bench/faults.py`` planted in the program. A summary line per kind
comes last: the largest reading of ``program`` and the smallest of every
other kind, per number. Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> None:
    """Parse the flags, write one line per reading and the summaries."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import boot  # noqa: PLC0415

    boot.start(ROOT)
    import jax.numpy as jnp  # noqa: PLC0415

    from bench import check, faults, harness, spec  # noqa: PLC0415
    from bench.reference import nn  # noqa: PLC0415

    cell = spec.load_cell(ROOT, args.workload)
    harness._check_devices(cell, require_chip=True)
    harness.compiles()
    exact = harness.reference(cell)
    refs = {}
    lines = []
    args.out.parent.mkdir(parents=True, exist_ok=True)

    def reference(seed):
        if seed not in refs:
            refs[seed] = exact.run(seed, check.ROUNDS)
        return refs[seed]

    def program(seed):
        rounds, run_args, _ = harness.drive(cell, seed, 0.0,
                                            warmup=check.ROUNDS)
        return harness.program_readings(rounds, run_args)

    clock = [time.perf_counter()]

    def record(kind, seed, readings):
        t = time.perf_counter()
        nums = check.numbers(readings, reference(seed))
        line = {"kind": kind, "seed": seed, "numbers": nums,
                "losses": {k: readings[k] for k in
                           ("loss_first", "loss_last", "eval_loss")},
                "ref_losses": {k: reference(seed)[k] for k in
                               ("loss_first", "loss_last", "eval_loss")},
                "ref_s": time.perf_counter() - t,
                "reading_s": t - clock[0],
                "readings": readings, "reference": reference(seed)}
        clock[0] = time.perf_counter()
        lines.append(line)
        with args.out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: v for k, v in line.items()
                          if k not in ("readings", "reference")}),
              flush=True)

    for seed in args.seeds:
        record("program", seed, program(seed))
    control = harness.reference(cell, nn.rounding_to(jnp.float8_e4m3fn))
    for seed in args.control_seeds:
        record("control", seed, control.run(seed, check.ROUNDS))
    for name in filter(None, args.faults.split(",")):
        for seed in args.fault_seeds:
            with faults.FAULTS[name]():
                readings = program(seed)
            record(name, seed, readings)
    summary = {}
    for line in lines:
        pick = max if line["kind"] == "program" else min
        row = summary.setdefault(line["kind"], {})
        for k, v in line["numbers"].items():
            row[k] = v if k not in row else pick(row[k], v)
    with args.out.open("a") as f:
        f.write(json.dumps({"summary": summary}) + "\n")
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
