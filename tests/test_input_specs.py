"""input_specs() shape correctness for every (arch x shape) — the contract
the dry-run lowers against (no device allocation; single-device mesh)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh

from repro import configs
from repro.configs.base import SHAPES
from repro.launch.dryrun import default_fed_config
from repro.launch.specs import input_specs
from repro.sharding import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


@pytest.mark.parametrize("arch", configs.ASSIGNED_ARCHS)
def test_train_specs(arch, mesh):
    cfg = configs.get_config(arch)
    fed = default_fed_config()
    spec = input_specs(cfg, SHAPES["train_4k"], fed, mesh,
                       placement="sequential")
    state, batches = spec["args"]
    C, K, B, S1 = batches["tokens"].shape
    assert C == fed.clients_per_round and K == fed.local_steps
    assert B == 256
    s_text = 4096 - (cfg.frontend_tokens if cfg.frontend else 0)
    assert S1 == s_text + 1
    if cfg.frontend:
        assert batches["frontend"].shape == (C, K, B, cfg.frontend_tokens,
                                             cfg.d_model)
    # server state holds params + opt moments
    n = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    assert n == cfg.param_count()


@pytest.mark.parametrize("arch", ["gemma3-27b", "granite-34b", "xlstm-125m"])
def test_decode_specs(arch, mesh):
    cfg = configs.get_config(arch)
    spec = input_specs(cfg, SHAPES["decode_32k"], default_fed_config(), mesh)
    params, tok, state = spec["args"]
    assert tok.shape == (128,) and tok.dtype == jnp.int32
    # every attention cache is bounded by window or seq_len
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        ks = jax.tree_util.keystr(path)
        if ks.endswith(".k"):
            L = leaf.shape[-3]
            assert L <= 32_768
    assert spec["kind"] == "decode"


def test_parallel_train_batch_split(mesh):
    cfg = configs.get_config("xlstm-125m")
    spec = input_specs(cfg, SHAPES["train_4k"], default_fed_config(), mesh,
                       placement="parallel")
    C, K, B, _ = spec["args"][1]["tokens"].shape
    assert C * B == 256      # clients x local batch = global batch


def test_prefill_specs(mesh):
    cfg = configs.get_config("internvl2-26b")
    spec = input_specs(cfg, SHAPES["prefill_32k"], default_fed_config(), mesh)
    params, batch = spec["args"]
    B, S = batch["tokens"].shape
    assert B == 32 and S == 32_768 - cfg.frontend_tokens
    assert batch["frontend"].shape == (32, 256, cfg.d_model)


def test_device_store_specs_pads_non_divisible_population():
    """Regression: a population that doesn't divide the client-axis extent
    used to fall back to full replication silently; it must now pad N up
    and keep the population axis sharded."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.specs import device_store_specs

    mesh = AbstractMesh((8, 2), ("data", "model"))
    cfg = configs.get_config("xlstm-125m")
    fed = default_fed_config("scaffold")
    store_spec, store_sh, ids_spec, ids_sh = device_store_specs(
        cfg, fed, mesh, "parallel", num_clients=10)
    # 10 clients over extent 8 -> 16 padded rows, still sharded over "data"
    assert store_spec["stamps"].shape == (16,)
    assert store_sh["stamps"].spec == P("data")
    for leaf, sh in zip(
            jax.tree_util.tree_leaves(store_spec["buffers"]),
            jax.tree_util.tree_leaves(store_sh["buffers"])):
        assert leaf.shape[0] == 16
        assert sh.spec[0] == "data"
    assert ids_spec.shape == (8,) and ids_sh.spec == P()
    # a divisible population is unpadded but equally sharded
    spec64, sh64, _, _ = device_store_specs(cfg, fed, mesh, "parallel",
                                            num_clients=64)
    assert spec64["stamps"].shape == (64,)
    assert sh64["stamps"].spec == P("data")


def test_store_population_layout_is_specs_source_of_truth():
    """launch.specs delegates population layout to core.client_state —
    one definition of padding/extent for specs, store, and dry-run."""
    from repro.core.client_state import population_layout
    from repro.launch.specs import store_population_layout

    mesh = AbstractMesh((8, 2), ("data", "model"))
    assert store_population_layout(mesh, 10) == population_layout(mesh, 10)
