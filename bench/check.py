"""The comparison that decides ``correct`` for a training cell.

The program's first three rounds and the reference's are reduced to a few
numbers, each the gap between two readings, and each number is held to
the limit in the cell's ``bench/limits/<cell>.json``:

* ``loss_r0_first``: round 0's first local-step loss (the initial weights'
  forward pass), relative gap;
* ``loss``: the largest relative gap over the rounds' first and last
  local-step losses and eval losses;
* ``grad_norm_r<t>``: round t's server gradient (the mean client delta),
  worked out from the server optimizer's state, by the worst leaf;
* ``change_norm``: the params' change over the three rounds, by the worst
  leaf, leaving out leaves whose reference gradient in round 0 is under a
  thousandth of the median leaf's (they move by round-off alone);
* ``loss_mean``, ``grad_mean``, ``change_mean``: the mean of the same
  gaps over the nine losses, over every leaf of the three server
  gradients, and over the moving leaves' changes. One gap can be near
  nought by chance in any precision; the mean over many is steadier from
  seed to seed;
* ``change_layer_mean``: ``change_mean`` taken over layers: a leaf under
  the model's ``pattern`` stacks one layer per row of its first axis, and
  each row counts as a leaf of its own (fedlm-100m: 134 layer leaves
  against 13 leaves), so the mean is steadier still. A cell's limits file
  says which numbers it holds.

By the worst leaf: the largest |norm_program - norm_reference| over
max(norm_reference of the leaf, median leaf norm of the reference).
"""
from __future__ import annotations

import math
import statistics

#: Rounds the reference follows.
ROUNDS = 3
#: Leaves whose reference gradient is under this share of the median leaf's
#: are left out of ``change_norm``.
STILL_LEAF = 1e-3
#: Leaves whose path starts so stack one layer per row of axis 0.
STACKED = "['pattern']"


def _finite(x: float) -> float:
    """A gap that is not a number reads as infinitely wide (max() would
    otherwise pass over a NaN)."""
    return x if math.isfinite(x) else math.inf


def _rel(a: float, b: float) -> float:
    return _finite(abs(a - b) / abs(b))


def leaf_gaps(prog: dict, ref: dict, keep=None) -> list:
    """Each leaf's gap of norms (see module docstring)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    keys = sorted(ref if keep is None else keep)
    med = statistics.median(ref[k] for k in ref)
    return [_finite(abs(prog[k] - ref[k]) / max(ref[k], med)) for k in keys]


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of one run (both dicts as ``fed.run`` returns)."""
    out = {"loss_r0_first": _rel(prog["loss_first"][0], ref["loss_first"][0])}
    out["loss"] = max(_rel(prog[k][r], ref[k][r])
                      for k in ("loss_first", "loss_last", "eval_loss")
                      for r in range(ROUNDS))
    out["loss_mean"] = statistics.mean(
        _rel(prog[k][r], ref[k][r])
        for k in ("loss_first", "loss_last", "eval_loss")
        for r in range(ROUNDS))
    every = []
    for r in range(ROUNDS):
        gaps = leaf_gaps(prog["grad_norms"][r], ref["grad_norms"][r])
        out[f"grad_norm_r{r}"] = max(gaps)
        every += gaps
    out["grad_mean"] = statistics.mean(every)
    g0 = ref["grad_norms"][0]
    med = statistics.median(g0.values())
    moving = [k for k, v in g0.items() if v >= STILL_LEAF * med]
    gaps = leaf_gaps(prog["change_norms"], ref["change_norms"], keep=moving)
    out["change_norm"] = max(gaps)
    out["change_mean"] = statistics.mean(gaps)
    g0 = ref["grad_layer_norms"][0]
    med = statistics.median(g0.values())
    moving = [k for k, v in g0.items() if v >= STILL_LEAF * med]
    out["change_layer_mean"] = statistics.mean(leaf_gaps(
        prog["change_layer_norms"], ref["change_layer_norms"], keep=moving))
    return out


def layer_rows(key: str, norms) -> dict:
    """{leaf path: norm} of one leaf, a stacked leaf's per-row norms (a
    sequence) under ``<path>[<row>]``."""
    if not key.startswith(STACKED):
        return {key: float(norms)}
    return {f"{key}[{i}]": float(n) for i, n in enumerate(norms)}


def judge(nums: dict, limits) -> tuple:
    """``(correct, {name: {"value", "limit"}})``; a number the cell's limits
    do not hold (no entry, or a limit of None) is printed with limit None;
    a run with no limits file is not correct."""
    table = {k: {"value": v,
                 "limit": (limits or {}).get(k, {}).get("limit")}
             for k, v in nums.items()}
    if limits is None:
        return False, table
    ok = all(row["limit"] is None or row["value"] <= row["limit"]
             for row in table.values())
    return ok, table
