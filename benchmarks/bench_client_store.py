"""Sharded vs replicated DeviceClientStateStore at population scale.

Measures, on 8 fake host devices, what population sharding buys the
client-state store at N in {10k, 1M} clients (scaffold-sized per-client
state, ~16 floats):

* ``sharded_mem_ratio`` — the headline, gated by ``check_regression``:
  max per-device bytes of the sharded store over the total (replicated)
  footprint. With 8 devices and a divisible population this is exactly
  1/8; padding a non-divisible N can only nudge it by ``padded/N``. A
  regression here means the population axis silently stopped sharding —
  the exact failure mode the padded layout fix closed.
* cohort gather + CAS-scatter wall time, sharded vs replicated — the
  data-movement cost of keeping the population distributed
  (informational; timings are not gated).

The workload runs in a subprocess: device count locks at the first jax
import, and the other benches in ``benchmarks.run`` must keep seeing the
real (single) device. Writes ``BENCH_client_store.json`` for the CI
artifact lane.

  PYTHONPATH=src python -m benchmarks.bench_client_store [--full]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

#: population sizes per the issue contract: a 10k and a 1M-client store
#: (quick only trims the timing repeats — the gated mem ratio must come
#: from the same populations as the committed baseline)
POPULATIONS = (10_000, 1_000_000)
COHORT = 64
STATE_DIM = 16


def _worker() -> None:
    """Subprocess body: build both stores, measure, print one JSON line."""
    import time

    import jax
    import numpy as np

    from repro.core.client_state import make_client_store
    from repro.launch.mesh import make_host_mesh

    assert jax.device_count() == 8, jax.device_count()
    mesh = make_host_mesh()
    quick = os.environ.get("BENCH_QUICK", "1") == "1"
    repeats = 5 if quick else 20
    template = {"c": np.zeros((STATE_DIM,), np.float32)}
    rng = np.random.default_rng(0)
    report = {}

    def bench_ops(store, n):
        ids = np.sort(rng.choice(n, COHORT, replace=False))
        new_states = {"c": np.ones((COHORT, STATE_DIM), np.float32)}
        gather_s = scatter_s = 0.0
        for i in range(repeats + 3):
            t0 = time.perf_counter()
            states, stamps = store.gather(ids)
            jax.block_until_ready(states)
            t1 = time.perf_counter()
            store.scatter(ids, new_states, stamps)
            jax.block_until_ready(store.device_state())
            t2 = time.perf_counter()
            if i >= 3:                      # skip compile/warmup
                gather_s += t1 - t0
                scatter_s += t2 - t1
        return gather_s / repeats * 1e3, scatter_s / repeats * 1e3

    def mem_ratio(store):
        per_dev, total = {}, 0
        for leaf in jax.tree_util.tree_leaves(store.device_state()):
            total += leaf.nbytes
            for s in leaf.addressable_shards:
                per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
        return max(per_dev.values()) / total

    for n in POPULATIONS:
        sharded = make_client_store("device", n, mesh=mesh).ensure(template)
        replicated = make_client_store("device", n).ensure(template)
        g_sh, s_sh = bench_ops(sharded, n)
        g_re, s_re = bench_ops(replicated, n)
        report[f"n{n}"] = {
            "sharded_mem_ratio": mem_ratio(sharded),
            "rows_per_device": sharded.padded_num_clients // 8,
            "gather_sharded_ms": g_sh, "gather_replicated_ms": g_re,
            "scatter_sharded_ms": s_sh, "scatter_replicated_ms": s_re,
        }
    print("BENCHJSON " + json.dumps(report), flush=True)


def run(quick: bool = True):
    """Spawn the 8-device worker, collect the report, emit CSV rows."""
    # 8 forced host devices, pinned to the CPU: the parent already holds the
    # accelerator, and a second process asking for it would fail or hang
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               BENCH_QUICK="1" if quick else "0")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_client_store", "--worker"],
        capture_output=True, text=True, env=env, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"client-store worker failed:\n{out.stderr[-4000:]}")
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("BENCHJSON "))
    report = json.loads(line[len("BENCHJSON "):])
    report["worst_mem_ratio"] = max(v["sharded_mem_ratio"]
                                    for v in report.values())
    rows = []
    for key, res in report.items():
        if not isinstance(res, dict):
            continue
        rows.append({
            "name": f"client_store/{key}",
            "us_per_call": res["gather_sharded_ms"] * 1e3,
            "derived": (f"mem_ratio={res['sharded_mem_ratio']:.4f},"
                        f"gather={res['gather_sharded_ms']:.2f}ms"
                        f"(repl {res['gather_replicated_ms']:.2f}ms),"
                        f"scatter={res['scatter_sharded_ms']:.2f}ms"
                        f"(repl {res['scatter_replicated_ms']:.2f}ms)"),
        })
    with open("BENCH_client_store.json", "w") as f:
        json.dump(report, f, indent=2)
    return rows


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        for row in run(quick="--full" not in sys.argv):
            print(row)
