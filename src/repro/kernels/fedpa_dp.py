"""Pallas TPU kernel for one Sherman-Morrison DP step (the paper's Table-1
hot spot).

One DP step over a d-vector with history {v_k}_{k<t} costs O(t d) flops at
arithmetic intensity ~O(1) flop/byte — purely HBM-bandwidth-bound. A naive
jnp implementation makes ~2t+2 separate passes over HBM (one per dot, one
per axpy). The kernel reshapes the step into two fused passes:

  pass A (reduce): per d-tile, read (u, delta~, V[0:t]) once and emit the
      partial dots <v_k, u> for every k, plus <u, u> and <u, delta~>.
      All Sherman-Morrison scalar coefficients derive from these:
      a_t = <u,u> - sum_k c_k <v_k,u>^2  (since v = u - sum c_k <v_k,u> v_k
      and V rows are Sigma~^{-1}-conjugate by construction).
  pass B (map): per d-tile, read (u, delta~, V[0:t]) once and write
      v_t = u - sum_k w_k v_k   and   delta~' = delta~ - s * v_t.

VMEM tiling: blocks are (l_pad, TILE_D) for the history and (1, TILE_D) for
the vectors, TILE_D a multiple of 128 lanes; l_pad is the static history
capacity (samples per round are single digits, so the whole history column
fits VMEM many times over). Pass A's scalars accumulate in one lane-padded
(1, 128k) row that stays resident across the grid. Both kernels compile for
TPU (``tests/test_tpu_compile.py``); ``interpret=True`` runs them on the CPU
against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_D = 512


def _reduce_kernel(u_ref, delta_ref, v_ref, out_ref):
    """out[0, :lp] += <v_k, u>; out[0, lp] += <u,u>; out[0, lp+1] += <u,delta>.

    The (1, out_w) output block is the same for every d-tile, so it stays
    resident in VMEM and accumulates across the sequential grid. The row is
    assembled with lane masks (no scalar stores into vector memory)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(jnp.float32)              # (1, TILE_D)
    delta = delta_ref[...].astype(jnp.float32)
    V = v_ref[...].astype(jnp.float32)              # (lp, TILE_D)
    lp = V.shape[0]
    dots = jnp.sum(V * u, axis=1, keepdims=True)    # (lp, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    row = jnp.where(lane == lp, jnp.sum(u * u, axis=1, keepdims=True), 0.0)
    row += jnp.where(lane == lp + 1,
                     jnp.sum(u * delta, axis=1, keepdims=True), 0.0)
    for k in range(lp):
        row += jnp.where(lane == k, dots[k:k + 1, :], 0.0)
    out_ref[...] += row


def _map_kernel(w_ref, s_ref, u_ref, delta_ref, v_ref, vout_ref, dout_ref):
    """vout = u - sum_k w[k] V[k];  dout = delta - s * vout."""
    u = u_ref[0, :].astype(jnp.float32)
    delta = delta_ref[0, :].astype(jnp.float32)
    V = v_ref[...].astype(jnp.float32)
    w = w_ref[0, : V.shape[0]].astype(jnp.float32)  # (lp,) (drop lane padding)
    v_new = u - jnp.sum(w[:, None] * V, axis=0)
    s = s_ref[0, 0]
    vout_ref[0, :] = v_new.astype(vout_ref.dtype)
    dout_ref[0, :] = (delta - s * v_new).astype(dout_ref.dtype)


def _pad_to(x, m, axis=-1):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dp_reduce(u, delta, V, *, interpret: bool = False):
    """Fused pass A. u, delta: (d,); V: (lp, d). Returns
    (dots (lp,), uu, ud) accumulated in fp32."""
    lp, d = V.shape
    u2 = _pad_to(u[None, :], TILE_D)
    delta2 = _pad_to(delta[None, :], TILE_D)
    V2 = _pad_to(V, TILE_D)
    dp = u2.shape[1]
    n_tiles = dp // TILE_D
    out_w = ((lp + 2 + 127) // 128) * 128

    totals = pl.pallas_call(
        _reduce_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            pl.BlockSpec((lp, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, out_w), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, out_w), jnp.float32),
        interpret=interpret,
    )(u2, delta2, V2)[0]
    return totals[:lp], totals[lp], totals[lp + 1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dp_map(w, s, u, delta, V, *, interpret: bool = False):
    """Fused pass B. Returns (v_new (d,), delta_new (d,))."""
    lp, d = V.shape
    u2 = _pad_to(u[None, :], TILE_D)
    delta2 = _pad_to(delta[None, :], TILE_D)
    V2 = _pad_to(V, TILE_D)
    dp_ = u2.shape[1]
    n_tiles = dp_ // TILE_D
    w_w = ((lp + 127) // 128) * 128
    w2 = _pad_to(w[None, :].astype(jnp.float32), w_w)
    s2 = jnp.full((1, 1), s, jnp.float32)

    v_new, delta_new = pl.pallas_call(
        _map_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, w_w), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            pl.BlockSpec((lp, TILE_D), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, dp_), u.dtype),
            jax.ShapeDtypeStruct((1, dp_), delta.dtype),
        ],
        interpret=interpret,
    )(w2, s2, u2, delta2, V2)
    return v_new[0, :d], delta_new[0, :d]
