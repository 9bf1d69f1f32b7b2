"""Find a cell's configuration, traffic mix, metrics and limits by name.

Nothing here is specific to one cell: a later change adds a cell by adding
an entry to ``BENCHMARK.json`` and files under ``bench/``, never by editing
this module.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import NamedTuple, Optional


class Cell(NamedTuple):
    """Everything one run of one cell needs, read from files."""

    name: str
    chips: int
    config: dict        # bench/configs/<config>.json
    traffic: dict       # bench/traffic/<traffic>.json
    end_to_end: list    # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list     # BENCHMARK.json per_layer entries this cell reports
    limits: Optional[dict]   # bench/limits/<cell>.json (None: not set yet)
    root: Path


def _applies(metric: dict, cell: str, reported) -> bool:
    """A metric with ``workloads`` applies to those cells; one without, to
    every cell that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(root: Path, name: str) -> Cell:
    """Read ``BENCHMARK.json`` under ``root`` and the files of cell ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    limits_file = root / "bench" / "limits" / f"{name}.json"
    limits = (json.loads(limits_file.read_text())
              if limits_file.exists() else None)
    return Cell(name, w["chips"], config, traffic, e2e, per_layer, limits,
                root)


def load_module(path: Path, name: str):
    """Import a file by path (its name may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, metric: str):
    """The ``read(ctx)`` function of per-layer metric ``metric``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    return load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def reference_model(root: Path, config: dict):
    """The plain reference beside configuration ``config``'s file."""
    path = root / "bench" / "configs" / f"{config['name']}.ref.py"
    return load_module(path, "bench_ref_" + config["name"].replace("-", "_"))


def check_config(config: dict, cfg) -> None:
    """Raise where the program's ModelConfig departs from the file's sizes."""
    for key, want in config["model"].items():
        have = getattr(cfg, key)
        if key in ("pattern", "tail"):
            have = [{"mixer": s.mixer, "ffn": s.ffn, "window": s.window}
                    for s in have]
        if have != want:
            raise ValueError(f"{config['name']}: the program's {key} is "
                             f"{have!r}, the benchmark's file says {want!r}")


def train_argv(cell: Cell, seed: int) -> list:
    """``launch.train`` flags for this cell: the mix's flags plus the model.

    ``--rounds`` is set far beyond any window; the harness ends the run
    itself at a round boundary."""
    argv = ["--arch", cell.config["arch"], "--seed", str(seed),
            "--rounds", str(10**7)]
    if cell.config.get("smoke"):
        argv.append("--smoke")
    for flag, value in cell.traffic["flags"].items():
        argv += [f"--{flag}", str(value)]
    return argv
