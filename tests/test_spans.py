"""The program's measurement marks: ``core/spans.py``'s host spans and
counters, the round loop's spans, and the device scopes that the round
program's ops carry in their HLO ``op_name``."""
import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.algorithms import get_algorithm
from repro.configs.base import FedConfig
from repro.core import FedSim, spans
from repro.core.server import init_server_state
from repro.core.sharded_round import make_fed_round
from repro.data import make_federated_lsq
from repro.data.synthetic_lsq import lsq_batches
from repro.models import init_params
from repro.optim import get_optimizer

C, D, STEPS, ROUNDS = 2, 3, 6, 3


def _delta(before: dict, name: str) -> list:
    now = spans.counters().get(name, [0, 0])
    was = before.get(name, [0, 0])
    return [now[0] - was[0], now[1] - was[1]]


def test_span_counts_each_call_and_its_duration():
    before = spans.counters()
    for _ in range(3):
        with spans.span("test_unit"):
            time.sleep(0.001)
    calls, ns = _delta(before, "test_unit")
    assert calls == 3 and ns >= 3e6
    with pytest.raises(ValueError), spans.span("test_unit"):
        raise ValueError
    assert _delta(before, "test_unit")[0] == 4
    # a snapshot, not a view
    snap = spans.counters()
    snap["test_unit"][0] = -1
    assert spans.counters()["test_unit"][0] == 4 + before.get(
        "test_unit", [0, 0])[0]


def _lsq_sim(fed):
    clients, data = make_federated_lsq(C, 30, D, heterogeneity=5.0, seed=0)

    def grad_fn(params, batch):
        def loss(p):
            r = batch["x"] @ p - batch["y"]
            return 0.5 * jnp.mean(r * r)
        return jax.value_and_grad(loss)(params)

    def batch_fn(cid, r, steps):
        X, y = data[cid]
        return lsq_batches(X, y, 10, steps, seed=r * 131 + cid)

    return FedSim(fed=fed, grad_fn=grad_fn, batch_fn=batch_fn, num_clients=C)


BASE = FedConfig(algorithm="fedpa", clients_per_round=C, local_steps=STEPS,
                 burn_in_steps=2, steps_per_sample=2, burn_in_rounds=1,
                 server_opt="sgdm", server_lr=0.5, client_opt="sgd",
                 client_lr=0.01)


@pytest.mark.parametrize("fed,dispatches", [
    (BASE, 1),                                           # one fused program
    (dataclasses.replace(BASE, algorithm="fedavg", burn_in_rounds=0,
                         prefetch_rounds=2, prefetch_backend="thread"), 1),
    (dataclasses.replace(BASE, async_rounds=True, max_staleness=1), 2),
], ids=["fused", "prefetched", "split"])
def test_round_loop_spans_once_per_round(fed, dispatches):
    sim = _lsq_sim(fed)
    state = sim.init(jnp.zeros(D))
    seen = []
    before = spans.counters()
    sim.engine.run(state, sim.cohort, ROUNDS,
                   eval_fn=lambda p: {"norm": jnp.sum(p * p)},
                   on_round=lambda rec, st: seen.append(rec["round"]))
    assert seen == list(range(ROUNDS))
    counts = {n: _delta(before, n) for n in
              ("round", "cohort_get", "dispatch", "eval", "on_round")}
    per_round = {n: c[0] / ROUNDS for n, c in counts.items()}
    assert per_round == {"round": 1, "cohort_get": 1,
                         "dispatch": dispatches, "eval": 1, "on_round": 1}
    assert all(c[1] > 0 for c in counts.values())
    # the phases are inside the round
    assert sum(counts[n][1] for n in counts if n != "round") \
        <= counts["round"][1]


#: Every device scope, and the algorithms whose round program holds it.
SCOPES = {"client_grad": ("fedavg", "fedpa"),
          "transpose(jvp(client_grad))": ("fedavg", "fedpa"),
          "client_opt": ("fedavg", "fedpa"),
          "iasg_average": ("fedpa",),
          "dp_delta": ("fedpa",),
          "aggregate": ("fedavg", "fedpa"),
          "server_update": ("fedavg", "fedpa")}


@pytest.fixture(scope="module")
def op_names():
    """{algorithm: op_names of the lowered smoke round program}."""
    cfg = configs.get_smoke("fedlm-100m")
    out = {}
    for alg in ("fedavg", "fedpa"):
        fed = FedConfig(algorithm=alg, clients_per_round=2, local_steps=4,
                        burn_in_steps=0, steps_per_sample=2,
                        server_opt="sgdm", client_opt="sgdm")
        server_opt = get_optimizer(fed.server_opt, fed.server_lr,
                                   fed.server_momentum)
        state = jax.eval_shape(lambda: init_server_state(
            init_params(jax.random.PRNGKey(0), cfg), server_opt,
            algorithm=get_algorithm(fed)))
        batches = {"tokens": jax.ShapeDtypeStruct((2, 4, 1, 17), jnp.int32)}
        round_fn = make_fed_round(cfg, fed, placement="parallel", q_chunk=16)
        hlo = jax.jit(round_fn).lower(state, batches, None, None).as_text(
            dialect="hlo", debug_info=True)
        out[alg] = set(re.findall(r'op_name="([^"]*)"', hlo))
    return out


@pytest.mark.parametrize("scope", list(SCOPES))
@pytest.mark.parametrize("alg", ["fedavg", "fedpa"])
def test_round_program_carries_its_scopes(op_names, alg, scope):
    component = re.compile(r"(^|[/(;])" + re.escape(scope) + r"([/);]|$)")
    found = any(component.search(n) for n in op_names[alg])
    assert found == (alg in SCOPES[scope])
