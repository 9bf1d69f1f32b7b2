"""Compile for a described TPU v5e chip (none attached): the Pallas kernels
at real widths and the full-width fedlm-100m rounds that ``chip_smoke.py``
runs, so a kernel or a program the chip's compiler refuses fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and xdist workers import every
test file."""
import contextlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.algorithms import get_algorithm
from repro.configs.base import FedConfig
from repro.core.server import init_server_state
from repro.core.sharded_round import make_fed_round
from repro.kernels import fedpa_dp, ops
from repro.models import init_params
from repro.optim import get_optimizer

#: flattened fedlm-100m
D_FEDLM = 100_684_032
#: what one v5e lets a program use: ``bytes_limit`` of its memory_stats()
HBM_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # the TPU library loaded below would otherwise log under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache; and the program runs with 32-bit defaults, while other test
    # modules turn on x64 for the whole process at import
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("lp", [1, 4])
def test_dp_kernels_compile_at_fedlm_width(one_chip, lp):
    vec = _spec(one_chip, (D_FEDLM,))
    hist = _spec(one_chip, (lp, D_FEDLM))
    reduce_ = jax.jit(fedpa_dp.dp_reduce).lower(vec, vec, hist).compile()
    map_ = jax.jit(fedpa_dp.dp_map).lower(
        _spec(one_chip, (lp,)), _spec(one_chip, ()), vec, vec,
        hist).compile()
    for comp in (reduce_, map_):
        assert "tpu_custom_call" in comp.as_text()


def test_swa_decode_compiles_at_gemma3_window(one_chip):
    cfg = configs.get_config("gemma3-27b")
    kv, dh = cfg.num_kv_heads, cfg.head_dim
    window = cfg.pattern[0].window
    assert (kv, cfg.num_heads // kv, dh, window) == (16, 2, 128, 1024)
    B = 8
    cache = _spec(one_chip, (B, window, kv, dh), jnp.bfloat16)
    comp = jax.jit(ops.swa_decode, static_argnames=("window",)).lower(
        _spec(one_chip, (B, cfg.num_heads, dh), jnp.bfloat16), cache, cache,
        _spec(one_chip, (window,), jnp.int32), _spec(one_chip, (), jnp.int32),
        window=window).compile()
    assert "tpu_custom_call" in comp.as_text()


#: chip_smoke.py's cohort: 5 clients x 8 local steps x batch 4 x seq 128
C, K, B, S = 5, 8, 4, 128
_SCAFFOLD = dict(algorithm="scaffold", client_opt="sgd",
                 client_state_placement="device")
#: name -> (clients, compute dtype, FedConfig fields)
ROUNDS = {
    "fedpa": (C, jnp.bfloat16, dict(algorithm="fedpa", burn_in_steps=4,
                                    steps_per_sample=2, client_opt="sgdm")),
    "scaffold_device_store": (C, jnp.bfloat16, _SCAFFOLD),
    # the one-chip side of ``chip_smoke.py --chips 4``'s fp32 comparison
    "scaffold_device_store_fp32": (4, jnp.float32, _SCAFFOLD),
}


@pytest.mark.parametrize("name", list(ROUNDS))
def test_fedlm_round_fits_one_chip(one_chip, name):
    C, dtype, fields = ROUNDS[name]
    cfg = configs.get_config("fedlm-100m")
    fed = FedConfig(clients_per_round=C, local_steps=K, server_opt="sgdm",
                    server_lr=0.5, client_lr=0.05, **fields)
    alg = get_algorithm(fed)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == D_FEDLM
    opt = get_optimizer(fed.server_opt, fed.server_lr, fed.server_momentum)
    state = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda p: init_server_state(p, opt, algorithm=alg),
                       params))
    batches = {"tokens": _spec(one_chip, (C, K, B, S + 1), jnp.int32)}
    round_fn = make_fed_round(cfg, fed, placement="parallel", q_chunk=64,
                              compute_dtype=dtype)
    # chip_smoke runs its fp32 rounds with full-precision matmuls
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        if alg.stateful:
            N = 8
            store = {
                "buffers": jax.tree_util.tree_map(
                    lambda x: _spec(one_chip, (N,) + x.shape, x.dtype),
                    jax.eval_shape(alg.init_client_state, params)),
                "stamps": _spec(one_chip, (N,), jnp.int32),
            }
            lowered = jax.jit(round_fn, donate_argnums=(3,)).lower(
                state, batches, None, store,
                _spec(one_chip, (C,), jnp.int32), None)
        else:
            lowered = jax.jit(round_fn).lower(state, batches, None, None)
        mem = lowered.compile().memory_analysis()
    if alg.stateful:
        # the donated store is updated in place
        assert mem.alias_size_in_bytes >= 8 * 4 * D_FEDLM
    # the compiler's own peak; temp_size_in_bytes counts buffers that are
    # never live together (17.2 GB of temp for a 12.5 GB peak at C=4)
    assert mem.peak_memory_in_bytes < HBM_LIMIT, (name,
                                                  mem.peak_memory_in_bytes)
