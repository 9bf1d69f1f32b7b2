"""Plain reference of the benchmarked federated rounds.

One round: the cohort's clients run one after another. Each runs K local
steps of SGD with heavy-ball momentum (m = beta m + g, p -= lr m) from the
server's params and a fresh momentum. FedAvg's client returns
delta = p_0 - p_K. FedPA's (Al-Shedivat et al., ICLR 2021, Algorithms 3-4)
runs ``burn_in_steps`` steps, then averages the iterates of each window of
``steps_per_sample`` steps into one posterior sample, and returns the
shrinkage-covariance delta Sigma^-1 (p_0 - mean) with
Sigma = rho_l I + (1 - rho_l) S, rho_l = 1 / (1 + (l - 1) rho), S the
samples' covariance; Sigma^-1 is applied in closed form (Woodbury on the
l x l Gram matrix), not by the paper's recursion. FedPA configurations run
their first ``burn_in_rounds`` rounds as FedAvg. The server takes the
unweighted mean delta as its gradient in one SGD (momentum) step.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.reference import data, nn

tmap = jax.tree_util.tree_map


class Hyper(NamedTuple):
    """The federated job, as a traffic mix states it."""

    algorithm: str
    clients: int
    population: int
    local_steps: int
    burn_in_steps: int
    steps_per_sample: int
    rho: float
    client_opt: str
    client_lr: float
    client_momentum: float
    server_opt: str
    server_lr: float
    server_momentum: float
    burn_in_rounds: int
    batch: int
    seq_len: int


def hyper(traffic: dict) -> Hyper:
    """Read the job from a traffic mix's flags and stated momenta."""
    f, mom = traffic["flags"], traffic["momentum"]
    if f["algorithm"] not in ("fedavg", "fedpa"):
        raise ValueError(f"no reference for algorithm {f['algorithm']!r}")
    for opt in (f["client-opt"], f["server-opt"]):
        if opt not in ("sgd", "sgdm"):
            raise ValueError(f"no reference for optimizer {opt!r}")
    return Hyper(
        algorithm=f["algorithm"], clients=int(f["clients"]),
        population=int(f["num-clients"]), local_steps=int(f["local-steps"]),
        burn_in_steps=int(f["burn-in-steps"]),
        steps_per_sample=int(f["steps-per-sample"]), rho=float(f["rho"]),
        client_opt=f["client-opt"], client_lr=float(f["client-lr"]),
        client_momentum=float(mom["client"]), server_opt=f["server-opt"],
        server_lr=float(f["server-lr"]),
        server_momentum=float(mom["server"]),
        burn_in_rounds=(int(f["burn-in-rounds"])
                        if f["algorithm"] == "fedpa" else 0),
        batch=int(f["batch"]), seq_len=int(f["seq-len"]))


def _vdot(a, b):
    return sum(jnp.vdot(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                              jax.tree_util.tree_leaves(b)))


def shrinkage_delta(x0, samples, rho: float):
    """Sigma^-1 (x0 - mean(samples)) for the shrinkage covariance."""
    ell = len(samples)
    mean = tmap(lambda *xs: sum(xs) / ell, *samples)
    y = tmap(jnp.subtract, x0, mean)
    a = 1.0 / (1.0 + (ell - 1) * rho)
    if ell == 1 or a == 1.0:
        return tmap(lambda t: t / a, y)
    b = (1.0 - a) / (ell - 1)
    u = [tmap(jnp.subtract, s, mean) for s in samples]
    gram = jnp.stack([jnp.stack([_vdot(ui, uj) for uj in u]) for ui in u])
    coef = jnp.linalg.solve(a / b * jnp.eye(ell, dtype=nn.F32) + gram,
                            jnp.stack([_vdot(ui, y) for ui in u]))
    corr = tmap(lambda *xs: sum(c * x for c, x in zip(coef, xs)), *u)
    return tmap(lambda yy, cc: (yy - cc) / a, y, corr)


def _client(step, shrink, zeros, hp: Hyper, sampling: bool, p0, toks):
    """One client's delta and its first and last local-step losses.

    ``step(p, m, acc, tok)`` is one jitted local step and ``shrink`` the
    jitted ``shrinkage_delta``; the loop over steps runs on the host, so
    that each compiled program holds one step."""
    def run(p, m, toks):
        acc, losses = zeros, []
        for tok in toks:
            p, m, acc, loss = step(p, m, acc, tok)
            losses.append(loss)
        return p, m, acc, losses

    if not sampling:
        p, _, _, losses = run(p0, zeros, toks)
        return tmap(jnp.subtract, p0, p), losses[0], losses[-1]
    b, w = hp.burn_in_steps, hp.steps_per_sample
    ell = (hp.local_steps - b) // w
    p, m, losses = p0, zeros, []
    if b:
        p, m, _, losses = run(p, m, toks[:b])
    samples = []
    for j in range(ell):
        p, m, acc, window = run(p, m, toks[b + j * w:b + (j + 1) * w])
        samples.append(tmap(lambda a: a / w, acc))
        losses += window
    return shrink(p0, samples, hp.rho), losses[0], losses[-1]


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} of a float tree (device scalars)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(v)))
            for k, v in flat}


def layer_norms(tree) -> dict:
    """{leaf path: L2 norm} of a float tree, per row of axis 0 (a vector)
    for a leaf that stacks one layer per row (``check.STACKED``)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for k, v in flat:
        key = jax.tree_util.keystr(k)
        axes = (tuple(range(1, v.ndim)) if key.startswith(check.STACKED)
                else None)
        out[key] = jnp.sqrt(jnp.sum(jnp.square(v), axis=axes))
    return out


class Reference:
    """The reference's programs for one model, job and rounding, built once
    and run for any number of seeds."""

    def __init__(self, ref, model: dict, hp: Hyper, rnd=nn.identity):
        self.ref, self.model, self.hp = ref, model, hp
        loss_fn = partial(ref.loss, model=model, rnd=rnd)
        loss_grad = jax.value_and_grad(loss_fn)

        @jax.jit
        def step(p, m, acc, tok):
            loss, g = loss_grad(p, tok)
            m = (tmap(lambda mi, gi: hp.client_momentum * mi + gi, m, g)
                 if hp.client_opt == "sgdm" else g)
            p = tmap(lambda pi, mi: pi - hp.client_lr * mi, p, m)
            return p, m, tmap(jnp.add, acc, p), loss

        self.step = step
        self.shrink = jax.jit(shrinkage_delta, static_argnums=2)
        self.evaluate = jax.jit(loss_fn)
        self.norms = jax.jit(leaf_norms)
        self.change = jax.jit(lambda a, b: leaf_norms(
            tmap(jnp.subtract, a, b)))
        self.layer_norms = jax.jit(layer_norms)
        self.layer_change = jax.jit(lambda a, b: layer_norms(
            tmap(jnp.subtract, a, b)))
        self.init = jax.jit(partial(ref.init, model=model))

        @jax.jit
        def server(p, m, g):
            m = (tmap(lambda mi, gi: hp.server_momentum * mi + gi, m, g)
                 if hp.server_opt == "sgdm" else g)
            return tmap(lambda pi, mi: pi - hp.server_lr * mi, p, m), m

        self.server = server

    def run(self, seed: int, rounds: int) -> dict:
        """The first ``rounds`` rounds from the seed; their losses, each
        round's server gradient per leaf and the params' change per leaf."""
        with jax.default_matmul_precision("highest"):
            return self._run(seed, rounds)

    def _run(self, seed, rounds):
        hp, vocab = self.hp, self.model["vocab_size"]
        params = self.init(jax.random.PRNGKey(seed))
        p0 = params
        m = tmap(jnp.zeros_like, params)
        ev = jnp.asarray(data.eval_batch(seed, hp.population, hp.batch,
                                         hp.seq_len, vocab))
        out = {"loss_first": [], "loss_last": [], "eval_loss": [],
               "grad_norms": [], "grad_layer_norms": []}
        for r in range(rounds):
            sampling = hp.algorithm == "fedpa" and r >= hp.burn_in_rounds
            toks = data.round_batches(seed, r, hp.population, hp.clients,
                                      hp.local_steps, hp.batch, hp.seq_len,
                                      vocab)
            g = tmap(jnp.zeros_like, params)
            firsts, lasts = [], []
            zeros = tmap(jnp.zeros_like, params)
            for c in range(hp.clients):
                delta, first, last = _client(
                    self.step, self.shrink, zeros, hp, sampling, params,
                    jnp.asarray(toks[c]))
                g = tmap(lambda a, d: a + d / hp.clients, g, delta)
                firsts.append(first)
                lasts.append(last)
            params, m = self.server(params, m, g)
            out["loss_first"].append(float(np.mean(jax.device_get(firsts))))
            out["loss_last"].append(float(np.mean(jax.device_get(lasts))))
            out["eval_loss"].append(float(self.evaluate(params, ev)))
            out["grad_norms"].append(_floats(self.norms(g)))
            out["grad_layer_norms"].append(_rows(self.layer_norms(g)))
        out["change_norms"] = _floats(self.change(params, p0))
        out["change_layer_norms"] = _rows(self.layer_change(params, p0))
        return out


def _floats(norms: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def _rows(norms: dict) -> dict:
    out = {}
    for k, v in jax.device_get(norms).items():
        out.update(check.layer_rows(k, v))
    return out
