"""The plain reference agrees with the program where both are exact.

The reference shares no code with the program, so these checks tie the two
together at smoke size on the CPU: the same weights and rows from the seed,
the same loss in float32, the same FedPA delta.
"""
import bench_cells
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.reference import data, fed, nn

ROOT = bench_cells.ROOT


def _ref(arch):
    config = {"name": arch, "model": bench_cells.SMOKE_MODELS[arch]}
    return spec.reference_model(ROOT, config), config["model"]


@pytest.mark.parametrize("arch", ["fedlm-100m", "xlstm-125m"])
def test_reference_weights_are_the_programs(arch):
    from repro import configs
    from repro.models import init_params
    ref, model = _ref(arch)
    seed = 2**31 + 11
    want = init_params(jax.random.PRNGKey(seed), configs.get_smoke(arch))
    got = ref.init(jax.random.PRNGKey(seed), model)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["fedlm-100m", "xlstm-125m"])
def test_reference_loss_and_grads_match_the_program_in_float32(arch):
    from repro import configs
    from repro.models import lm_loss
    ref, model = _ref(arch)
    cfg = configs.get_smoke(arch)
    params = ref.init(jax.random.PRNGKey(3), model)
    toks = jnp.asarray(data.client_batches(3, 1, 1, 2, 16,
                                           model["vocab_size"], 0)[0])
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(lambda p: lm_loss(
            p, {"tokens": toks}, cfg, compute_dtype=jnp.float32,
            q_chunk=16)[0])(params)
        got, g_got = jax.value_and_grad(
            lambda p: ref.loss(p, toks, model))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-6)


def test_reference_rows_are_the_programs():
    from repro.data import SyntheticLMData
    from repro.data.sampling import ClientSampler
    seed, vocab = 2**31 + 5, 512
    prog = SyntheticLMData(vocab_size=vocab, num_clients=8, seed=seed)
    sampler = ClientSampler(8, 3, seed)
    for r in range(3):
        ids = sampler.sample(r)
        np.testing.assert_array_equal(ids, data.cohort_ids(seed, r, 8, 3))
        np.testing.assert_array_equal(
            prog.round_batches(ids, 4, 2, 16, round_idx=r, host=True),
            data.round_batches(seed, r, 8, 3, 4, 2, 16, vocab))
    np.testing.assert_array_equal(
        np.asarray(prog.client_batches(9, 1, 2, 16)[0]),
        data.eval_batch(seed, 8, 2, 16, vocab))


@pytest.mark.parametrize("ell,rho", [(1, 0.01), (2, 0.01), (3, 0.5),
                                     (4, 0.0)])
def test_closed_form_shrinkage_delta_matches_the_programs_recursion(ell,
                                                                    rho):
    from repro.core.dp_delta import dp_delta
    key = jax.random.PRNGKey(ell)
    x0 = {"a": jax.random.normal(key, (7, 3)),
          "b": jax.random.normal(jax.random.fold_in(key, 1), (5,))}
    samples = [jax.tree_util.tree_map(
        lambda t, i=i: t + 0.3 * jax.random.normal(
            jax.random.fold_in(key, 10 + i), t.shape), x0)
        for i in range(ell)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *samples)
    with jax.default_matmul_precision("highest"):
        want = dp_delta(x0, stacked, rho)
        got = fed.shrinkage_delta(x0, samples, rho)
    for k in x0:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-4, atol=1e-5)


def test_control_rounding_changes_the_loss():
    ref, model = _ref("fedlm-100m")
    params = ref.init(jax.random.PRNGKey(1), model)
    toks = jnp.asarray(data.client_batches(1, 1, 1, 2, 16, 512, 0)[0])
    exact = float(ref.loss(params, toks, model))
    fp8 = float(ref.loss(params, toks, model,
                         nn.rounding_to(jnp.float8_e4m3fn)))
    assert exact != fp8
