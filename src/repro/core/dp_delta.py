"""Sherman-Morrison dynamic program for FedPA client deltas (Appendix C).

Computes

    Delta_hat_l = Sigma_hat_l^{-1} (x0 - xbar_l)

without ever materializing a d x d matrix, where Sigma_hat_l is the
shrinkage covariance of the l posterior samples (see
``repro.core.shrinkage``). Works on arbitrary parameter pytrees, each leaf in
its own (sharded) layout.

Recurrences implemented (paper eqs. 21-28), with
u_t = x_t - xbar_{t-1}, gamma_t = (t-1) rho / t, v_t = Sigma_tilde_{t-1}^{-1} u_t:

    v_t     = u_t - sum_{k=2}^{t-1} c_k (v_k . u_t) v_k,   c_k = gamma_k / (1 + gamma_k a_k)
    a_t     = u_t . v_t
    Delta~_t = Delta~_{t-1} - [1 + gamma_t (t b_t - a_t) / (1 + gamma_t a_t)] v_t / t,
               b_t = u_t . Delta~_{t-1}
    Delta^_t = Delta~_t / rho_t

The recurrence touches its vectors only through linear combinations and
inner products, so one implementation (``online_dp_update``) serves both
entry points, given the inner product's matrix (``metric``):

  * ``dp_delta``      — samples known up front (stacked trees); used inside
                        the jitted federated round. Every vector of the
                        recurrence lies in the span of the l basis vectors
                        e_1 = x0 - x_1 and w_j = x_j - x_1 (j = 2..l), so it
                        runs on coefficient vectors in R^l under
                        <p, q> = p^T G q, G the basis' l x l Gram matrix.
                        Two passes over the parameters: the l(l+1)/2 dots
                        of G (O(l^2 d) time; on a TPU at l = 2 one read of
                        x0 and the samples, at larger l XLA also writes the
                        w_j once) and one elementwise map writing
                        Delta_hat = sum_j beta_j basis_j; between them an
                        O(l^3) scalar recurrence. No loop carries a
                        full-width tree. With more samples than parameters
                        (l > d) the full-width recurrence runs instead.
  * ``online_dp_*``   — streaming any-time state over full-width trees
                        under plain dots (O(l^2 d) time, O(l d) memory),
                        absorbing each sample as it arrives; mirrored by the
                        Pallas kernel in ``repro.kernels.fedpa_dp``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm


def fedavg_delta(x0, x_final):
    """FedAvg's (biased) client delta: Delta = I (theta_0 - theta_K).

    This is exactly ``dp_delta`` with a single sample (or rho -> 0): FedPA
    with identity covariance — the paper's Section 4 special-case claim,
    asserted in tests/test_dp_delta.py.
    """
    return tm.tsub(x0, x_final)


def dp_delta(x0, samples, rho):
    """Delta_hat_l from stacked posterior samples.

    Runs the recurrence on the basis' l x l Gram matrix, two passes over
    the parameters, while l <= d. With more samples than parameters the
    basis is linearly dependent, its Gram matrix singular and its O(l^3)
    recurrence dearer than the O(l^2 d) full-width one, which then runs.

    Args:
      x0: parameter pytree (the server state broadcast this round).
      samples: pytree with leading sample axis ``l`` on every leaf.
      rho: shrinkage parameter in [0, inf); rho=0 reduces to FedAvg-on-mean.

    Returns Delta_hat_l as a pytree shaped like x0.
    """
    ell = jax.tree_util.tree_leaves(samples)[0].shape[0]
    # DP in >= fp32 (bf16 deltas are re-cast by the caller, see client.py)
    dtype = jnp.promote_types(
        jax.tree_util.tree_leaves(samples)[0].dtype, jnp.float32)
    if ell > tm.tree_size(x0):
        state = _absorb(x0, tm.tcast(samples, dtype), rho, None)
        return online_dp_delta(state, rho)

    def basis(x0_leaf, s_leaf):
        """The basis on one leaf: [x0 - x_1, x_2 - x_1, ..., x_l - x_1],
        each row formed elementwise inside the fusion that reads it."""
        x1 = s_leaf[0].astype(dtype)
        return [x0_leaf.astype(dtype) - x1] + [
            s_leaf[j].astype(dtype) - x1 for j in range(1, ell)]

    def gram(rows):
        """G on one leaf from its l(l+1)/2 dots: sibling reductions, which
        XLA fuses into one pass over x0 and the samples."""
        dots = {(i, j): jnp.sum(rows[i] * rows[j])
                for i in range(ell) for j in range(i, ell)}
        return jnp.stack([jnp.stack([dots[min(i, j), max(i, j)]
                                     for j in range(ell)])
                          for i in range(ell)])

    def combine(coefs, x0_leaf, s_leaf):
        """sum_j coefs[j] basis_j: one elementwise map."""
        rows = basis(x0_leaf, s_leaf)
        out = coefs[0] * rows[0]
        for j in range(1, ell):
            out = out + coefs[j] * rows[j]
        return out

    with jax.named_scope("dp_delta"):
        g = sum(gram(basis(a, s)) for a, s in zip(
            jax.tree_util.tree_leaves(x0), jax.tree_util.tree_leaves(samples)))
        # in the basis x0 is basis_0, x_1 is 0 and x_t is basis_{t-1}
        eye = jnp.eye(ell, dtype=dtype)
        # l x l products in full precision: at its default a TPU rounds f32
        # matmul inputs to bf16
        with jax.default_matmul_precision("highest"):
            state = _absorb(eye[0], eye.at[0].set(0.0), rho, g)
            beta = online_dp_delta(state, rho)
        return tm.tmap(lambda a, s: combine(beta, a, s), x0, samples)


def _absorb(x0, samples, rho, metric) -> DPState:
    """``online_dp_update`` over the leading axis of ``samples``, in a
    ``lax.scan`` (trace size O(1) in l)."""
    leaf = jax.tree_util.tree_leaves(samples)[0]

    def body(state, x_t):
        return online_dp_update(state, x_t, rho, metric), None

    with jax.named_scope("dp_delta"):
        state0 = online_dp_init(x0, leaf.shape[0], dtype=leaf.dtype)
        return jax.lax.scan(body, state0, samples)[0]


# ---------------------------------------------------------------------------
# Streaming / any-time version
# ---------------------------------------------------------------------------

class DPState(NamedTuple):
    """Any-time DP state after ``t`` samples (paper: the O(l d) DP tuple)."""

    t: jnp.ndarray          # i32 scalar, number of samples absorbed
    xbar: object            # running sample mean (tree)
    delta_tilde: object     # Delta~_t (tree)
    v_hist: object          # tree, leading axis ell_max: v_2..v_t in slots 0..t-2
    c_hist: jnp.ndarray     # (ell_max,) combine coefficients c_k
    x0: object              # broadcast server state (tree)

    @property
    def delta(self):
        """Delta_hat_t — best any-time estimate given samples so far."""
        raise AttributeError("use online_dp_delta(state, rho)")


def online_dp_init(x0, ell_max: int, dtype=jnp.float32) -> DPState:
    """Pre-sample state (t=0). ``ell_max`` bounds the history buffers so the
    update is usable as a ``lax.scan`` body with static shapes."""
    zeros = tm.tzeros_like(x0, dtype)
    v_hist = tm.tmap(
        lambda z: jnp.zeros((max(ell_max - 1, 1),) + z.shape, dtype), zeros
    )
    return DPState(
        t=jnp.zeros((), jnp.int32),
        xbar=zeros,
        delta_tilde=zeros,
        v_hist=v_hist,
        c_hist=jnp.zeros((max(ell_max - 1, 1),), dtype),
        x0=tm.tcast(x0, dtype),
    )


def online_dp_update(state: DPState, x_t, rho, metric=None) -> DPState:
    """Absorb one posterior sample. Traceable (lax.cond over the t=1 case).

    ``metric``: None for full-width trees under plain dots; else the vectors
    are coefficient arrays over a basis whose Gram matrix it is, and
    <p, q> = p^T metric q (``dp_delta``)."""
    x_t = tm.tcast(x_t, state.c_hist.dtype)
    t_new = state.t + 1

    def first(st: DPState) -> DPState:
        return st._replace(
            t=t_new, xbar=x_t, delta_tilde=tm.tsub(st.x0, x_t)
        )

    def rest(st: DPState) -> DPState:
        tf = t_new.astype(st.c_hist.dtype)
        u = tm.tsub(x_t, st.xbar)
        # <p, u> = p . mu for every p below
        mu = u if metric is None else metric @ u
        # dots_k = <v_k, u> for the whole history at once, masked to k <= t
        dots = _hist_dots(st.v_hist, mu)
        n_hist = st.c_hist.shape[0]
        mask = jnp.arange(n_hist) < (st.t - 1)
        coefs = jnp.where(mask, st.c_hist * dots, 0.0)
        v = _hist_combine(u, st.v_hist, coefs)
        g = (tf - 1.0) * rho / tf
        a = tm.tvdot(mu, v)
        b = tm.tvdot(mu, st.delta_tilde)
        scale = (1.0 + g * (tf * b - a) / (1.0 + g * a)) / tf
        delta_tilde = tm.taxpy(-scale, v, st.delta_tilde)
        xbar = tm.taxpy(1.0 / tf, u, st.xbar)
        v_hist = tm.tdynamic_update(st.v_hist, v, st.t - 1)
        c_hist = jax.lax.dynamic_update_index_in_dim(
            st.c_hist, g / (1.0 + g * a), st.t - 1, axis=0
        )
        return st._replace(
            t=t_new, xbar=xbar, delta_tilde=delta_tilde, v_hist=v_hist,
            c_hist=c_hist,
        )

    with jax.named_scope("dp_delta"):
        return jax.lax.cond(state.t == 0, first, rest, state)


def online_dp_delta(state: DPState, rho):
    """Delta_hat_t = Delta~_t / rho_t — the any-time estimate.

    With t=0 this returns zeros; with t=1 it returns x0 - x1 == the FedAvg
    delta (the paper's any-time property).
    """
    with jax.named_scope("dp_delta"):
        tf = jnp.maximum(state.t, 1).astype(state.c_hist.dtype)
        r = 1.0 / (1.0 + (tf - 1.0) * rho)
        return tm.tscale(1.0 / r, state.delta_tilde)


def _hist_dots(v_hist, u):
    """dots[k] = <v_hist[k], u> summed across leaves -> (ell_max-1,)."""
    leaves_v = jax.tree_util.tree_leaves(v_hist)
    leaves_u = jax.tree_util.tree_leaves(u)
    dt = jnp.promote_types(leaves_u[0].dtype, jnp.float32)
    acc = 0.0
    for vh, ul in zip(leaves_v, leaves_u):
        acc = acc + jnp.einsum("k...,...->k", vh.astype(dt), ul.astype(dt))
    return acc


def _hist_combine(u, v_hist, coefs):
    """u - sum_k coefs[k] * v_hist[k], leafwise."""
    def leaf(ul, vh):
        c = coefs.reshape((-1,) + (1,) * ul.ndim)
        return ul - jnp.sum(c * vh, axis=0)

    return tm.tmap(leaf, u, v_hist)
