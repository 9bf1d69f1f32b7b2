"""Logical-axis sharding rules.

Model code annotates activations with *logical* axis names
(``constrain(x, "batch", None, "tp")``); a context-installed rule table maps
logical names to mesh axes, filtered to the axes the active mesh actually
has. With no active mesh every annotation is a no-op, so the same model code
runs in single-device tests and in the 512-chip dry-run unchanged.

Default mapping (DESIGN.md §3):
  clients/batch -> ("pod", "data")   federated clients = data parallelism
  tp            -> "model"           tensor parallel: heads / ffn / vocab / experts
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

DEFAULT_RULES = {
    "clients": ("pod", "data"),
    "batch": ("pod", "data"),
    "tp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
}

_STATE = threading.local()


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the model code
    places arrays through sharding constraints, not explicit-axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def current_mesh() -> Optional[Mesh]:
    """The mesh installed by the innermost axis_rules (None outside one)."""
    return getattr(_STATE, "mesh", None)


def current_rules() -> dict:
    """The logical-axis rule map currently in effect."""
    return getattr(_STATE, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[dict] = None):
    """Install ``mesh`` (+ optional rule overrides) for model annotations."""
    prev_mesh, prev_rules = current_mesh(), current_rules()
    _STATE.mesh = mesh
    _STATE.rules = {**DEFAULT_RULES, **(rules or {})}
    try:
        yield
    finally:
        _STATE.mesh = prev_mesh
        _STATE.rules = prev_rules


def _resolve(name: Optional[str], mesh: Mesh, rules: dict):
    if name is None:
        return None
    axes = tuple(a for a in rules.get(name, ()) if a in mesh.axis_names)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def logical_spec(names: Tuple[Optional[str], ...], mesh: Optional[Mesh] = None,
                 rules: Optional[dict] = None) -> P:
    """Map logical axis names to a PartitionSpec under the current rules."""
    mesh = mesh or current_mesh()
    rules = rules or current_rules()
    if mesh is None:
        return P()
    return P(*(_resolve(n, mesh, rules) for n in names))


def constrain(x, *names):
    """with_sharding_constraint against the active mesh (no-op without one)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_spec(names, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter shardings (megatron-style tensor parallelism by param name)
# ---------------------------------------------------------------------------

# (regex on the param keypath, logical axes for the *trailing* dims).
# Stacked pattern params carry extra leading axes (repeats) that are padded
# with None automatically, so one rule covers both pattern and tail layers.
_PARAM_RULES = (
    (r"embed$", ("vocab", None)),
    (r"unembed$", (None, "vocab")),
    # attention
    (r"(wq|wk|wv)$", (None, "tp")),
    (r"wo$", ("tp", None)),
    # MoE expert banks (E, d, f) / (E, f, d): expert-parallel over tp
    (r"moe.*w_(gate|up)$", ("experts", None, None)),
    (r"moe.*w_down$", ("experts", None, None)),
    (r"router$", (None, "tp")),
    (r"ws_(gate|up)$", (None, "tp")),
    (r"ws_down$", ("tp", None)),
    # dense ffn / xlstm / rglru projections
    (r"w_(gate|up|mlp_up)$", (None, "tp")),
    (r"(w_down|w_mlp_down)$", ("tp", None)),
    (r"conv$", (None, "tp")),
    (r"w_gates$", (None, None)),
    (r"\bw$", (None, "tp")),        # slstm input gates (d, 4d)
    (r"\br$", ("tp", None, None)),  # slstm recurrent blocks (H, dh, 4dh)
    (r"rg_(a|x)$", (None, None, None)),
)


def _normalize_path(path_str: str) -> str:
    """keystr emits "['pattern']['pos_0']['ffn']['moe']['w_gate']" — turn it
    into "pattern.pos_0.ffn.moe.w_gate" so $-anchored rules match."""
    return re.sub(r"[\[\]'\"]+", ".", path_str).strip(".")


def _axis_extent(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _sanitize(spec: P, shape, mesh: Mesh) -> P:
    """Drop axis assignments whose dimension isn't divisible by the axis
    extent (e.g. 4 sLSTM heads can't shard over model=16 — replicate)."""
    out = []
    for dim, s in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        e = _axis_extent(mesh, s)
        out.append(s if (e > 1 and dim % e == 0 and dim >= e) else None)
    return P(*out)


def _spec_for_path(path_str: str, ndim: int, mesh: Mesh, rules: dict,
                   shape=None) -> P:
    path_str = _normalize_path(path_str)
    for pattern, logical in _PARAM_RULES:
        if re.search(pattern, path_str):
            if len(logical) > ndim:
                logical = logical[-ndim:]
            pad = (None,) * (ndim - len(logical))
            spec = logical_spec(pad + tuple(logical), mesh, rules)
            return _sanitize(spec, shape, mesh) if shape is not None else spec
    return P()  # replicate anything unmatched (norms, biases, scalars)


def fsdp_shardings(params, mesh: Mesh, rules: Optional[dict] = None):
    """ZeRO/FSDP-style shardings: the tensor-parallel spec plus the first
    still-replicated, divisible dimension sharded over the client axes.

    This is what lets FedPA's O(l d) per-client state (posterior samples, DP
    history vectors, optimizer moments, fp32 masters) fit in HBM for the
    >=10B architectures under ``client_placement="sequential"``: every
    parameter-shaped vector shards over data x model = the full pod instead
    of model only. Leaves too small (or not divisible) stay replicated.
    """
    rules = {**DEFAULT_RULES, **(rules or {})}
    client_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    extent = 1
    for a in client_axes:
        extent *= mesh.shape[a]

    def one(path, leaf):
        path_str = jax.tree_util.keystr(path)
        spec = list(_spec_for_path(path_str, leaf.ndim, mesh, rules,
                                   shape=leaf.shape))
        spec += [None] * (leaf.ndim - len(spec))
        for i, (dim, s) in enumerate(zip(leaf.shape, spec)):
            if s is None and dim % extent == 0 and dim >= extent:
                spec[i] = client_axes if len(client_axes) > 1 else client_axes[0]
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, params)


def fsdp_constrain(tree, like_params=None):
    """with_sharding_constraint a parameter-shaped pytree to FSDP shardings
    against the active mesh (no-op without one). ``like_params`` gives the
    path structure when ``tree`` is shaped like the params."""
    mesh = current_mesh()
    if mesh is None:
        return tree
    shardings = fsdp_shardings(like_params if like_params is not None else tree,
                               mesh, current_rules())
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, shardings
    )


def tp_constrain(tree):
    """Constrain a parameter pytree to the pure tensor-parallel shardings
    (replicated over client axes) — forces the FSDP all-gather boundary."""
    mesh = current_mesh()
    if mesh is None:
        return tree
    shardings = param_shardings(tree, mesh, current_rules())
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, shardings
    )


def param_shardings(params, mesh: Mesh, rules: Optional[dict] = None,
                    extra_leading: Tuple[Optional[str], ...] = ()):
    """NamedSharding pytree for a parameter pytree.

    ``extra_leading``: logical names for extra leading axes every leaf
    carries (e.g. ("clients",) for the per-client param copies inside a
    federated round).
    """
    rules = {**DEFAULT_RULES, **(rules or {})}
    lead = tuple(_resolve(n, mesh, rules) for n in extra_leading)

    def one(path, leaf):
        path_str = jax.tree_util.keystr(path)
        nd = leaf.ndim - len(lead)
        spec = _spec_for_path(path_str, nd, mesh, rules,
                              shape=leaf.shape[len(lead):])
        full = P(*(lead + tuple(spec)))
        return NamedSharding(mesh, full)

    return jax.tree_util.tree_map_with_path(one, params)
