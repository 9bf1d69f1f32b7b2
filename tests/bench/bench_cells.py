"""Throwaway cells for the benchmark's CPU tests, made from files alone.

``make_tree`` writes a benchmark tree (``BENCHMARK.json`` and the files
under ``bench/``) holding one tiny cell beside copies of the real metric
readers and references, so a test drives the harness exactly as a later
change would drive a cell it adds: by files, with no edit to a file that
is there.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: Smoke sizes of the two configurations (``repro.configs.get_smoke``).
SMOKE_MODELS = {
    "fedlm-100m": {
        "d_model": 256, "num_heads": 4, "num_kv_heads": 2, "head_dim": 64,
        "d_ff": 512, "vocab_size": 512, "repeats": 1,
        "pattern": [{"mixer": "attn", "ffn": "dense", "window": 0}] * 2,
        "qk_norm": True, "norm_eps": 1e-06, "rope_theta": 10000.0,
        "tie_embeddings": True},
    "xlstm-125m": {
        "d_model": 256, "num_heads": 4, "num_kv_heads": 4, "head_dim": 64,
        "d_ff": 0, "vocab_size": 512, "repeats": 1,
        "pattern": [{"mixer": "slstm", "ffn": "none", "window": 0},
                    {"mixer": "mlstm", "ffn": "none", "window": 0}],
        "expansion": 2.0, "conv_width": 4, "norm_eps": 1e-06,
        "tie_embeddings": True},
}

TINY_FLAGS = {
    "algorithm": "fedpa", "clients": 2, "num-clients": 8, "local-steps": 4,
    "burn-in-steps": 2, "steps-per-sample": 1, "burn-in-rounds": 1,
    "rho": 0.01, "server-opt": "sgdm", "server-lr": 0.5,
    "client-opt": "sgdm", "client-lr": 0.01, "batch": 2, "seq-len": 16,
    "compute-dtype": "bfloat16", "prefetch-rounds": 2,
    "prefetch-backend": "process"}


def make_tree(root: Path, arch: str = "fedlm-100m", limits=None,
              **flags) -> str:
    """Write a one-cell benchmark tree under ``root``; returns the cell."""
    cell, config, traffic = "tiny-cell", f"{arch}-smoke", "tiny-mix"
    bench = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics",
                    dirs_exist_ok=True)
    shutil.copy(ROOT / "bench" / "configs" / f"{arch}.ref.py",
                bench / "configs" / f"{config}.ref.py")
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = dict(real, workloads=[{"name": cell, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "CPU test"}],
                configs=[{"name": config, "source": "test",
                          "file": f"bench/configs/{config}.json",
                          "reduced": [], "why": "CPU test"}])
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / f"{config}.json").write_text(json.dumps({
        "name": config, "arch": arch, "smoke": True, "params": 0,
        "model": SMOKE_MODELS[arch]}))
    (bench / "traffic" / f"{traffic}.json").write_text(json.dumps({
        "flags": dict(TINY_FLAGS, **flags),
        "momentum": {"client": 0.9, "server": 0.9},
        "warmup_rounds": 4, "trace_seconds": 1, "cohort_builds": 2}))
    if limits is not None:
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(
            {k: {"limit": v} for k, v in limits.items()}))
    return cell


def real_limits(cell: str) -> dict:
    """{number: limit} of a cell of the benchmark."""
    rows = json.loads((ROOT / "bench" / "limits" / f"{cell}.json")
                      .read_text())
    return {k: row["limit"] for k, row in rows.items()}


def run_tiny(root: Path, cell: str, seed: int = 2**31 + 11) -> dict:
    """One run of a tiny cell on the CPU, past the harness's chip check."""
    import time  # noqa: PLC0415

    from bench import harness  # noqa: PLC0415
    result, _ = harness.run(root, cell, seed, 1.0, False,
                            time.perf_counter(), require_chip=False)
    return result


def check_fault(root: Path, real: str, arch: str, flags: dict, fault):
    """Run a tiny cell with ``real``'s limits and ``fault`` planted (None:
    nothing planted) and assert what ``correct`` must say."""
    from bench import faults  # noqa: PLC0415

    cell = make_tree(root, arch, limits=real_limits(real), **flags)
    if fault is None:
        result = run_tiny(root, cell)
        assert result["correct"] is True, result["checks"]
        return
    with faults.FAULTS[fault]():
        result = run_tiny(root, cell)
    assert result["correct"] is False, result["checks"]
