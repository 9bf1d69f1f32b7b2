"""The benchmark's fixed arithmetic: flops, peaks, percentiles, sizes."""
import json

import bench_cells  # noqa: F401  (puts the checkout on sys.path)
import jax
import pytest

from bench import spec, yardstick

ROOT = bench_cells.ROOT


@pytest.mark.parametrize("config,clients,tokens,flops", [
    ("fedlm-100m", 5, 20_480, 6 * 100_684_032 * 20_480),
    ("xlstm-125m", 4, 16_384, 6 * 129_642_288 * 16_384),
])
def test_model_flops_per_round(config, clients, tokens, flops):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    assert yardstick.tokens_per_round(clients, 8, 4, 128) == tokens
    assert yardstick.model_flops(cfg["params"], tokens) == flops


def test_peak_table_knows_v5e_and_refuses_unknown_chips():
    row = yardstick.peak("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        yardstick.peak("TPU v4")


def test_p90_interpolates_over_rounds():
    assert yardstick.p90(range(1, 12)) == pytest.approx(10.0)
    assert yardstick.p90([0.5] * 50 + [1.5]) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        yardstick.p90([1.0])


@pytest.mark.parametrize("config", ["fedlm-100m", "xlstm-125m"])
def test_config_files_state_the_programs_sizes(config):
    from repro import configs
    cfg_file = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                          .read_text())
    cfg = configs.get_config(cfg_file["arch"])
    spec.check_config(cfg_file, cfg)
    assert cfg.param_count() == cfg_file["params"]
    ref = spec.reference_model(ROOT, cfg_file)
    shapes = jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0),
                                             cfg_file["model"]))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == cfg_file["params"]
