"""Cells, configurations, traffic mixes and metrics are found by name."""
import json
import subprocess
import sys

import bench_cells
import pytest

from bench import spec

ROOT = bench_cells.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_its_files_by_name(cell):
    c = spec.load_cell(ROOT, cell)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    assert {m["name"] for m in c.end_to_end} == {"round_s", "round_p90_s",
                                                 "setup_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(ROOT, m["name"]))
    ref = spec.reference_model(ROOT, c.config)
    assert callable(ref.init) and callable(ref.loss)
    assert c.limits is not None, f"{cell} has no bench/limits file"
    argv = spec.train_argv(c, 5)
    from repro.launch import train
    args = train.parse_args(argv)
    assert args.arch == c.config["arch"] and args.seed == 5


def test_a_cell_is_added_by_files_alone(tmp_path):
    cell = bench_cells.make_tree(tmp_path)
    c = spec.load_cell(tmp_path, cell)
    assert c.config["smoke"] and c.traffic["flags"]["clients"] == 2
    assert {m["name"] for m in c.per_layer} == {m["name"] for m in
                                                BENCH["per_layer"]}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="unknown workload"):
        spec.load_cell(ROOT, "no-such-cell")


def test_config_mismatch_is_an_error():
    from repro import configs
    cfg_file = json.loads((ROOT / "bench/configs/fedlm-100m.json")
                          .read_text())
    cfg_file["model"]["d_ff"] = 1024
    with pytest.raises(ValueError, match="d_ff"):
        spec.check_config(cfg_file, configs.get_config("fedlm-100m"))


def _run(cwd, env_extra):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    out = _run(ROOT, {})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
