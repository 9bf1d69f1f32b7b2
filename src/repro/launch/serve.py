"""Batched serving driver: prefill a batch of synthetic requests, then
decode tokens with the cached state — the decode_32k/long_500k code path at
CPU-friendly scale.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-27b --smoke \
      --batch 4 --prompt-len 96 --gen 32 [--pallas[=interpret]]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, prefill_step, serve_step


def main():
    """CLI: prefill a synthetic batch, then decode ``--gen`` tokens."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-27b", choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--pallas", nargs="?", const="compiled", default=None,
                    choices=("compiled", "interpret"),
                    help="route decode attention through the Pallas "
                         "swa_decode kernel, compiled for the TPU; "
                         "--pallas=interpret runs it in the Pallas "
                         "interpreter (a CPU host has no compiled Pallas)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    data = SyntheticLMData(vocab_size=cfg.vocab_size, num_clients=args.batch,
                           seed=args.seed)

    s_text = args.prompt_len - (cfg.frontend_tokens if cfg.frontend else 0)
    prompts = jnp.stack([
        data.client_batches(i, 1, 1, s_text - 1)[0, 0] for i in range(args.batch)
    ])                                   # (B, s_text)
    frontend = None
    if cfg.frontend:
        frontend = jnp.stack([
            data.frontend_embeddings(i, 1, cfg.frontend_tokens,
                                     cfg.d_model)[0]
            for i in range(args.batch)
        ]).astype(jnp.bfloat16)

    max_len = args.prompt_len + args.gen
    q_chunk = min(32, s_text)
    t0 = time.time()
    pf = jax.jit(lambda p, t: prefill_step(p, t, cfg, max_len,
                                           frontend=frontend,
                                           q_chunk=q_chunk))
    logits, state = pf(params, prompts)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    print(f"prefill {args.batch}x{args.prompt_len}: {time.time()-t0:.2f}s")

    step = jax.jit(lambda p, t, s, r: serve_step(
        p, t, s, cfg, sample=args.sample, rng=r,
        temperature=args.temperature, pallas=args.pallas))
    out = [np.asarray(tok)]
    rng = jax.random.PRNGKey(args.seed + 1)
    t1 = time.time()
    for i in range(args.gen - 1):
        rng, sub = jax.random.split(rng)
        tok, logits, state = step(params, tok, state, sub)
        out.append(np.asarray(tok))
    dt = time.time() - t1
    gen = np.stack(out, axis=1)
    print(f"decoded {args.gen - 1} steps in {dt:.2f}s "
          f"({(args.gen - 1) * args.batch / max(dt, 1e-9):.1f} tok/s)")
    for b in range(min(args.batch, 2)):
        print(f"request {b}: {gen[b].tolist()}")
    assert np.isfinite(np.asarray(logits, np.float32)).all(), "NaN logits"
    print("ok")


if __name__ == "__main__":
    main()
