"""Forward (train/prefill) vs decode equivalence — the strongest model
correctness property: the chunkwise/scan forward implementations and the
single-token recurrent/cached decode paths must produce identical logits on
the same token stream."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import (decode_step, forward, init_decode_state,
                          init_params, prefill)

# archs chosen to cover every mixer/ffn kind; frontend archs are covered via
# the prefill test path of plain attention (their decoders are identical).
ARCHS = ["xlstm-125m", "recurrentgemma-9b", "gemma3-27b", "qwen3-32b",
         "qwen3-moe-30b-a3b", "llama4-scout-17b-a16e"]

S = 48
B = 2


def _cfg(arch):
    cfg = configs.get_smoke(arch)
    if cfg.moe.enabled:
        # generous capacity so no tokens drop: forward chunks and decode
        # chunks would otherwise drop different tokens (documented behaviour)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_equals_decode_chain(arch):
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    logits_fwd, _ = forward(params, tokens, cfg,
                            compute_dtype=jnp.float32, q_chunk=16,
                            remat="none")
    state = init_decode_state(cfg, B, max_len=S + 8, cache_dtype=jnp.float32)
    step = jax.jit(lambda p, t, s: decode_step(p, t, s, cfg,
                                               compute_dtype=jnp.float32))
    outs = []
    for t in range(S):
        lg, state = step(params, tokens[:, t], state)
        outs.append(lg)
    logits_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_fwd), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("arch", ["gemma3-27b", "qwen3-32b", "xlstm-125m",
                                  "recurrentgemma-9b"])
def test_prefill_then_decode_matches_forward(arch):
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0,
                                cfg.vocab_size)
    # ground truth: forward over S+1 tokens, logits at position S-1 and S
    logits_fwd, _ = forward(params, tokens, cfg, compute_dtype=jnp.float32,
                            q_chunk=16, remat="none")
    lp, state = prefill(params, tokens[:, :S], cfg, max_len=S + 8,
                        compute_dtype=jnp.float32, q_chunk=16,
                        cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(logits_fwd[:, S - 1]),
                               rtol=2e-3, atol=2e-3)
    ld, state = decode_step(params, tokens[:, S], state,
                            cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(logits_fwd[:, S]),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_masks_old_tokens():
    """A swa layer must ignore tokens beyond the window: changing a token
    older than the window leaves later logits unchanged."""
    cfg = _cfg("gemma3-27b")  # pattern = (swa(32), attn) — take swa only
    cfg = dataclasses.replace(cfg, pattern=(cfg.pattern[0],), repeats=1,
                              tail=())
    w = cfg.pattern[0].window
    assert w == 32
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, S), 0,
                                cfg.vocab_size)
    tokens2 = tokens.at[0, 0].set((tokens[0, 0] + 1) % cfg.vocab_size)
    l1, _ = forward(params, tokens, cfg, compute_dtype=jnp.float32,
                    q_chunk=16, remat="none")
    l2, _ = forward(params, tokens2, cfg, compute_dtype=jnp.float32,
                    q_chunk=16, remat="none")
    # positions >= w + something can't see token 0
    np.testing.assert_allclose(np.asarray(l1[:, w + 1:]),
                               np.asarray(l2[:, w + 1:]), atol=1e-5)
    assert float(jnp.abs(l1[:, 1] - l2[:, 1]).max()) > 1e-4


def test_causality():
    """Changing a future token never changes past logits (all mixers)."""
    for arch in ("xlstm-125m", "recurrentgemma-9b", "qwen3-32b"):
        cfg = _cfg(arch)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(4), (1, S), 0,
                                    cfg.vocab_size)
        tokens2 = tokens.at[0, -1].set((tokens[0, -1] + 1) % cfg.vocab_size)
        l1, _ = forward(params, tokens, cfg, compute_dtype=jnp.float32,
                        q_chunk=16, remat="none")
        l2, _ = forward(params, tokens2, cfg, compute_dtype=jnp.float32,
                        q_chunk=16, remat="none")
        np.testing.assert_allclose(np.asarray(l1[:, :-1]),
                                   np.asarray(l2[:, :-1]), atol=1e-5,
                                   err_msg=arch)


def test_mlstm_chunk_size_invariance():
    """The chunkwise mLSTM recurrence must be exact: different chunk sizes
    give identical outputs."""
    from repro.models.xlstm import init_mlstm_params, mlstm_forward
    cfg = configs.get_smoke("xlstm-125m")
    p = init_mlstm_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 64, cfg.d_model))
    y8, _ = mlstm_forward(p, x, cfg, chunk=8)
    y64, _ = mlstm_forward(p, x, cfg, chunk=64)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y64), rtol=1e-4,
                               atol=1e-4)


def _slstm_block():
    from repro.models.xlstm import init_slstm_params
    cfg = configs.get_smoke("xlstm-125m")
    p = init_slstm_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.d_model))
    bf16 = lambda t: t.astype(jnp.bfloat16)
    return cfg, p, x, jax.tree_util.tree_map(bf16, p), bf16(x)


def test_slstm_scan_carries_float32_under_bf16():
    """The sLSTM's c, n, h and m stay float32 through the time scan when
    params and inputs are bf16; the per-step h leaves it in bf16."""
    from repro.models.xlstm import slstm_forward
    cfg, _, _, pb, xb = _slstm_block()
    jaxpr = jax.make_jaxpr(lambda p, x: slstm_forward(p, x, cfg))(pb, xb)
    [eqn] = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    carry = [v.aval for v in eqn.outvars[:eqn.params["num_carry"]]]
    assert [a.dtype for a in carry] == [jnp.float32] * 4, carry
    assert [a.shape for a in carry] == [(2, cfg.d_model)] * 4
    assert eqn.outvars[-1].aval.dtype == jnp.bfloat16


def test_bf16_slstm_block_stays_near_the_float32_one():
    """At seq 128 the bf16 block's relative gap (norm of the difference over
    the norm of the float32 output, at ``highest`` matmuls) reads 0.64%
    with the float32 carry and 1.15% with a bf16 one. The limit, 0.9%,
    sits between the two (near their geometric mean): a carry rounded to
    bf16 fails it, the bf16 inputs, weights and matmuls alone pass."""
    from repro.models.xlstm import slstm_forward
    cfg, p, x, pb, xb = _slstm_block()
    with jax.default_matmul_precision("highest"):
        ref, _ = slstm_forward(p, x, cfg)
    y, _ = jax.jit(lambda p, x: slstm_forward(p, x, cfg))(pb, xb)
    assert y.dtype == jnp.bfloat16
    gap = jnp.linalg.norm(y.astype(jnp.float32) - ref) / jnp.linalg.norm(ref)
    assert float(gap) < 0.009, float(gap)


@pytest.mark.parametrize("mixer,scope", [("slstm", "slstm_scan"),
                                         ("mlstm", "mlstm_chunk")])
def test_recurrence_alone_under_its_scope(mixer, scope):
    """A mixer's named scope marks its scan, forward and transposed, and no
    projection: a device trace reads the recurrence apart from the matmuls
    around it."""
    from repro.models import xlstm
    cfg = configs.get_smoke("xlstm-125m")
    init = getattr(xlstm, f"init_{mixer}_params")
    fwd = getattr(xlstm, f"{mixer}_forward")
    p = init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(fwd(p, x, cfg)[0])))(p)
    marked = lambda e: scope in str(e.source_info.name_stack)  # noqa: E731
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(scans) == 2 and all(map(marked, scans))
    assert dots and not any(map(marked, dots))
