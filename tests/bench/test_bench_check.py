"""The compared numbers: layer leaves, and the same norms on both sides."""
import jax
import numpy as np
import pytest

from bench import check, harness
from bench.reference import fed


def _tree(seed):
    rng = np.random.default_rng(seed)
    return {"embed": rng.normal(size=(8, 4)).astype(np.float32),
            "pattern": {"pos_0": {"w": rng.normal(size=(3, 4, 4))
                                  .astype(np.float32),
                                  "norm": rng.normal(size=(3, 4))
                                  .astype(np.float32)}}}


def _host(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): v for k, v in flat}


def test_host_and_reference_layer_norms_agree():
    tree = _tree(0)
    host = harness._layer_norms(_host(tree))
    device = fed._rows(jax.jit(fed.layer_norms)(tree))
    assert sorted(host) == sorted(device)
    assert len(host) == 1 + 3 + 3
    assert "['pattern']['pos_0']['w'][2]" in host
    for k in host:
        assert host[k] == pytest.approx(device[k], rel=1e-6)


def _readings(tree_grads, tree_change):
    grads = [_host(g) for g in tree_grads]
    change = _host(tree_change)
    return {"loss_first": [1.0] * check.ROUNDS,
            "loss_last": [1.0] * check.ROUNDS,
            "eval_loss": [1.0] * check.ROUNDS,
            "grad_norms": [harness._norms(g) for g in grads],
            "change_norms": harness._norms(change),
            "grad_layer_norms": [harness._layer_norms(g) for g in grads],
            "change_layer_norms": harness._layer_norms(change)}


def test_layer_means_see_one_layer_that_the_leaf_means_dilute():
    grads = [_tree(s) for s in range(check.ROUNDS)]
    change = _tree(9)
    ref = _readings(grads, change)
    same = check.numbers(ref, ref)
    assert all(v == 0 for v in same.values())
    bent = jax.tree_util.tree_map(np.copy, change)
    bent["pattern"]["pos_0"]["w"][1] *= 1.1
    got = check.numbers(_readings(grads, bent), ref)
    rows = np.sqrt(np.sum(np.square(change["pattern"]["pos_0"]["w"],
                                    dtype=np.float64), axis=(1, 2)))
    med = np.median(list(ref["change_layer_norms"].values()))
    assert got["change_layer_mean"] == pytest.approx(
        0.1 * rows[1] / max(rows[1], med) / 7, rel=1e-5)
    assert got["change_mean"] > 0
