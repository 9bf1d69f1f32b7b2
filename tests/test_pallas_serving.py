"""The Pallas-kernel serving path (``pallas=...``) must produce the same
logits as the pure-jnp decode path it is validated against."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import init_decode_state, init_params
from repro.models.model import decode_step


@pytest.mark.parametrize("arch", ["gemma3-27b", "qwen3-32b"])
def test_pallas_decode_matches_jnp(arch):
    cfg = configs.get_smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 6), 0,
                              cfg.vocab_size)
    s_ref = init_decode_state(cfg, B, max_len=128, cache_dtype=jnp.float32)
    s_pal = init_decode_state(cfg, B, max_len=128, cache_dtype=jnp.float32)
    for t in range(6):
        l_ref, s_ref = decode_step(params, toks[:, t], s_ref, cfg,
                                   compute_dtype=jnp.float32)
        l_pal, s_pal = decode_step(params, toks[:, t], s_pal, cfg,
                                   compute_dtype=jnp.float32,
                                   pallas="interpret")
        np.testing.assert_allclose(np.asarray(l_pal), np.asarray(l_ref),
                                   rtol=2e-3, atol=2e-3)
