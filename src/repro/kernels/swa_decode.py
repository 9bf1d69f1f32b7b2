"""Pallas TPU kernel: sliding-window GQA decode attention.

Serves the local layers of gemma3-27b / llama4-scout / recurrentgemma-9b at
``decode_32k`` / ``long_500k``: one query token per request attends to a
ring-buffer KV window. Decode attention is HBM-bandwidth-bound (the whole
window's K/V streams through once per token), so the kernel fuses
QK -> masked online softmax -> PV into a single pass over the window.

Grid: (B, KV, L / TILE_L) with the window dimension innermost — TPU grids
iterate sequentially, so fp32 running (max, sum, out) accumulators live in
VMEM scratch across window tiles (flash-attention decode scheme).
BlockSpecs keep one (TILE_L, dh) K/V tile and the (G, dh) query group in
VMEM; masking is driven by the ring buffer's per-slot token positions, so
the same kernel covers linear (full) and ring (windowed) caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_L = 512
NEG_INF = -2.0e38


def _swa_decode_kernel(pos_ref, window_ref,            # scalar prefetch
                       q_ref, k_ref, v_ref, slot_ref,  # blocks
                       out_ref,                        # output block
                       m_scr, s_scr, acc_scr):         # VMEM scratch
    li = pl.program_id(2)
    n_l = pl.num_programs(2)

    @pl.when(li == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                # (G, dh)
    k = k_ref[0].astype(jnp.float32)                   # (TILE_L, dh)
    v = v_ref[0].astype(jnp.float32)                   # (TILE_L, dh)
    sp = slot_ref[...]                                 # (1, TILE_L) int32
    pos = pos_ref[0]
    window = window_ref[0]

    scores = jnp.dot(q, k.T) / jnp.sqrt(jnp.float32(q.shape[-1]))  # (G, T)
    valid = (sp >= 0) & (sp <= pos)
    valid = valid & ((window <= 0) | (sp > pos - window))
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_scr[...]                                # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                        # (G, T)
    s_scr[...] = s_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(p, v)
    m_scr[...] = m_new

    @pl.when(li == n_l - 1)
    def _finalize():
        out_ref[0, 0] = (acc_scr[...] /
                         jnp.maximum(s_scr[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "tile_l"))
def swa_decode_attention(q, k, v, slot_pos, pos, *, window: int = 0,
                         interpret: bool = False, tile_l: int = TILE_L):
    """q: (B, KV, G, dh); k, v: (B, L, KV, dh); slot_pos: (L,) int32;
    pos: scalar int32 (position of the new token). Returns (B, KV, G, dh).

    ``window=0`` disables the lower position bound (full-cache decode).
    """
    B, KV, G, dh = q.shape
    L = k.shape[1]
    tile_l = min(tile_l, L)
    assert L % tile_l == 0, (L, tile_l)
    n_l = L // tile_l
    ktf = jnp.swapaxes(k, 1, 2).reshape(B * KV, L, dh)
    vtf = jnp.swapaxes(v, 1, 2).reshape(B * KV, L, dh)
    pos_s = jnp.asarray(pos, jnp.int32).reshape(1)
    win_s = jnp.asarray(window, jnp.int32).reshape(1)

    return pl.pallas_call(
        _swa_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, n_l),
            in_specs=[
                pl.BlockSpec((1, 1, G, dh), lambda b, h, l, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, tile_l, dh),
                             lambda b, h, l, *_: (b * KV + h, l, 0)),
                pl.BlockSpec((1, tile_l, dh),
                             lambda b, h, l, *_: (b * KV + h, l, 0)),
                # 2-D (1, L): a 1-D int32 block's Mosaic tiling disagrees
                # with the layout XLA gives the operand
                pl.BlockSpec((1, tile_l), lambda b, h, l, *_: (0, l)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, dh),
                                   lambda b, h, l, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dh), q.dtype),
        interpret=interpret,
    )(pos_s, win_s, q, ktf, vtf, slot_pos.reshape(1, L))
