"""The client loops carry the bf16 compute copy of the params, written by the
optimizer's update, instead of casting the float32 params at every step.

The results must be bitwise those of casting inside the gradient function:
the copy is ``p.astype(bf16)`` of the same updated ``p``, and the gradient
the same values. Where the params already hold the compute dtype (float32
compute, the sequential placement's bf16 client params) nothing is carried.

Both sides compile with XLA's default precision rules, as on the chip (its
excess precision sums the tied embedding's two bf16 gradients in float32
when the forward casts; the gradient function keeps them apart to match),
but at LLVM optimization level 0: else a multiply-add may or may not be
contracted into one rounding depending on the fusion around it, a
last-bit difference in the momentum that the CPU alone makes."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.algorithms import get_algorithm
from repro.configs.base import FedConfig
from repro.core import tree_math as tm
from repro.core.iasg import compute_copy, local_steps, sample_window, sgd_steps
from repro.models import init_params
from repro.models.steps import lm_grad_fn
from repro.optim import get_optimizer, sgdm

K, B, S = 4, 2, 16
ARCHS = ["fedlm-100m", "xlstm-125m"]
#: a head of its own (``unembed``) in place of the tied embedding
UNTIED = "fedlm-100m-untied"


@functools.lru_cache(maxsize=None)
def _setup(arch, dtype=jnp.bfloat16):
    cfg = configs.get_smoke(arch.removesuffix("-untied"))
    if arch == UNTIED:
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (K, B, S + 1), 0,
                                cfg.vocab_size)
    return params, {"tokens": tokens}, lm_grad_fn(cfg, compute_dtype=dtype,
                                                  q_chunk=8)


def _exact(fn, *args):
    """``fn(*args)``, compiled to round as its operations state."""
    return jax.jit(fn).lower(*args).compile(compiler_options={
        "xla_backend_optimization_level": 0})(*args)


def _casting_inside(grad_fn):
    """``grad_fn`` as it was called before the copy was carried: from the
    float32 params, cast inside the forward pass at every step."""
    return lambda p, batch: grad_fn(p, batch)


def _same(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def _reference_steps(params, opt, batches, grad_fn, sample_dtype=None):
    """The local steps as written before: the gradient function casts, the
    optimizer updates the float32 params; with ``sample_dtype`` also the
    Polyak average of the iterates."""
    def step(carry, batch):
        p, s, acc = carry
        loss, g = grad_fn(p, batch)
        u, s = opt.update(g, s, p)
        p = tm.tmap(lambda pi, ui: pi + ui.astype(pi.dtype), p, u)
        if sample_dtype is not None:
            acc = tm.tmap(lambda a, pi: a + pi.astype(sample_dtype), acc, p)
        return (p, s, acc), loss

    acc0 = None if sample_dtype is None else tm.tzeros_like(params,
                                                            sample_dtype)
    (p, s, acc), losses = jax.lax.scan(step, (params, opt.init(params), acc0),
                                       batches)
    steps = batches["tokens"].shape[0]
    sample = None if acc is None else tm.tscale(1.0 / steps, acc)
    return p, s, sample, losses


@pytest.mark.parametrize("arch", ARCHS + [UNTIED])
def test_loops_bitwise_equal_casting_inside_the_gradient(arch):
    params, batches, grad_fn = _setup(arch)
    opt = sgdm(0.01, 0.9)
    old = _casting_inside(grad_fn)

    new_sgd = _exact(lambda p: sgd_steps(p, opt, opt.init(p), grad_fn,
                                         batches), params)
    ref_sgd = _exact(lambda p: _reference_steps(p, opt, batches, old),
                     params)
    _same((new_sgd[0], new_sgd[1], new_sgd[2]),
          (ref_sgd[0], ref_sgd[1], ref_sgd[3]))

    def window(p):
        return sample_window(p, compute_copy(grad_fn, p), opt.init(p), opt,
                             grad_fn, batches)

    p, cp, s, sample, losses = _exact(window, params)
    ref = _exact(lambda p: _reference_steps(p, opt, batches, old,
                                            jnp.float32), params)
    _same((p, s, sample, losses), ref)
    # the copy carried out of the loop is the cast of the params it ends at
    _same(cp, tm.tcast(p, jnp.bfloat16))


def _client(name, grad_fn, params, batches):
    fields = {"fedavg": {}, "fedprox": {"fedprox_mu": 0.1},
              "scaffold": {"client_opt": "sgd"},
              "fedpa": {"burn_in_steps": 2, "steps_per_sample": 1},
              "fedpa_streaming": {"burn_in_steps": 2, "steps_per_sample": 1,
                                  "streaming_dp": True}}[name]
    fed = FedConfig(algorithm=name.split("_")[0], local_steps=K,
                    client_lr=0.01, **fields)
    alg = get_algorithm(fed)
    client_opt = get_optimizer(fed.client_opt, fed.client_lr,
                               fed.client_momentum)
    update = alg.make_client_update(grad_fn, client_opt)
    extra = ()
    if alg.stateful:
        key = jax.random.PRNGKey(2)
        extra = (tm.tmap(lambda x: 1e-3 * jax.random.normal(key, x.shape),
                         alg.init_client_state(params)),
                 tm.tmap(lambda x: 2e-3 * jnp.ones(x.shape),
                         alg.init_algo_state(params)))
    return _exact(lambda p: update(p, batches, *extra), params)


@pytest.mark.parametrize("name", ["fedavg", "fedprox", "scaffold", "fedpa",
                                  "fedpa_streaming"])
@pytest.mark.parametrize("arch", ARCHS)
def test_client_updates_bitwise_equal_casting_inside(arch, name):
    """The hooks (the proximal term, the control variates, the IASG sum
    and the DP) see the same float32 params and gradients."""
    params, batches, grad_fn = _setup(arch)
    new = _client(name, grad_fn, params, batches)
    old = _client(name, _casting_inside(grad_fn), params, batches)
    _same(new, old)


def test_hooks_keep_the_compute_dtype():
    """A hook that wraps the gradient function (``functools.wraps``) keeps
    its ``compute_dtype``, so the loops still carry the copy through it."""
    params, batches, grad_fn = _setup("fedlm-100m")
    fed = FedConfig(algorithm="fedprox", local_steps=K)
    seen = []

    def spy(p, batch, *cparams):
        seen.append(len(cparams))
        return grad_fn(p, batch, *cparams)

    spy = functools.wraps(grad_fn)(spy)
    update = get_algorithm(fed).make_client_update(
        spy, get_optimizer("sgdm", 0.01, 0.9))
    jax.eval_shape(lambda p: update(p, batches), params)
    assert seen == [1]


def _carry_dtypes(fn, params):
    """The dtypes of the local-step scan's carry in ``fn``'s jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(params)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    eqn = scans[0]
    n = eqn.params["num_consts"]
    return [v.aval.dtype for v in
            eqn.invars[n:n + eqn.params["num_carry"]]]


@pytest.mark.parametrize("case", ["float32_compute", "bf16_params"])
def test_nothing_is_carried_where_params_hold_the_compute_dtype(case):
    params, batches, grad_fn = _setup(
        "fedlm-100m",
        jnp.float32 if case == "float32_compute" else jnp.bfloat16)
    if case == "bf16_params":   # the sequential placement's client params
        params = tm.tcast(params, jnp.bfloat16)
    assert compute_copy(grad_fn, params) is None
    opt = sgdm(0.01, 0.9)
    carried = _carry_dtypes(
        lambda p: sgd_steps(p, opt, opt.init(p), grad_fn, batches), params)
    plain = _carry_dtypes(
        lambda p: sgd_steps(p, opt, opt.init(p), _casting_inside(grad_fn),
                            batches), params)
    assert carried == plain


def test_bf16_compute_carries_one_copy_per_param():
    params, batches, grad_fn = _setup("fedlm-100m")
    opt = sgdm(0.01, 0.9)
    cp = compute_copy(grad_fn, params)
    assert {x.dtype for x in jax.tree_util.tree_leaves(cp)} \
        == {jnp.dtype(jnp.bfloat16)}
    carried = _carry_dtypes(
        lambda p: local_steps(p, compute_copy(grad_fn, p), opt.init(p), opt,
                              grad_fn, batches), params)
    n = len(jax.tree_util.tree_leaves(params))
    assert carried.count(jnp.dtype(jnp.bfloat16)) == n
