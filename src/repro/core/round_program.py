"""The unified compiled round engine: one XLA program per federated round.

An entire generalized federated round (Algorithm 1) — cohort of clients
running their local updates, weighted payload aggregation, server optimizer
step — is staged as a single jittable function, so the simulation path
(``round.FedSim``) and the multi-pod SPMD path (``sharded_round``) pay one
dispatch per round instead of one per client. The round factors into two
separately jittable stages:

  * ``make_cohort_program`` — clients -> aggregated payload (+ losses);
  * ``make_server_program`` — server optimizer step, with an optional
    staleness discount on the aggregate (``core/async_engine.py`` overlaps
    cohort t+1 with server round t using exactly these two stages);

and ``make_round_program`` fuses them back into the single-dispatch
``round_fn`` the synchronous paths jit. Three client placements:

  * ``parallel``  — ``vmap`` over the client axis; on a mesh, pass
    ``spmd_axes`` so per-client state shards one-client-per-data-slice
    (the paper's O(d)-communication pattern made structural).
  * ``sequential`` — ``lax.scan`` over clients, each using the full mesh;
    for memory-bound configs (>=10B archs with FSDP-sharded client state).
  * ``chunked``   — scan-of-vmap: chunks of ``chunk_size`` clients run
    vmapped, chunks run sequentially, so ``clients_per_round`` larger than
    memory allows still compiles (and dispatches) once. Cohorts that don't
    divide evenly are padded with zero-weight duplicate clients.

All round math is resolved through the ``repro.algorithms`` strategy API
(``FedConfig.algorithm`` -> a registered ``FedAlgorithm``): the algorithm
owns the client update, the broadcast extras, the linear payload
accumulator the placements fold into, and the server step. The placements
only decide how the cohort is laid out; they produce the same round math up
to floating-point reduction order (tests/test_round_engine.py).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core import tree_math as tm
from repro.core.client_state import STORES, device_gather, device_scatter
from repro.core.server import ServerState, normalized_weights
from repro.optim import Optimizer, get_optimizer

#: Client placements understood by the engine.
PLACEMENTS = ("parallel", "sequential", "chunked")

#: Client-state placements understood by the engine (the registered store
#: implementations — ``core.client_state.STORES`` is the source of truth).
STATE_PLACEMENTS = tuple(STORES)


def resolve_placement(fed: FedConfig, placement: Optional[str] = None) -> str:
    """Explicit argument wins; otherwise the ``FedConfig`` knob."""
    p = placement or fed.round_placement
    if p not in PLACEMENTS:
        raise ValueError(f"unknown placement {p!r}; known: {PLACEMENTS}")
    return p


def resolve_state_placement(fed: FedConfig,
                            state_placement: Optional[str] = None) -> str:
    """Explicit argument wins; otherwise ``fed.client_state_placement``."""
    p = state_placement or fed.client_state_placement
    if p not in STATE_PLACEMENTS:
        raise ValueError(
            f"unknown client-state placement {p!r}; known: {STATE_PLACEMENTS}")
    return p


def _resolve_chunk(fed: FedConfig, chunk_size: Optional[int],
                   num_clients: int) -> int:
    c = chunk_size if chunk_size is not None else fed.round_chunk_size
    if c <= 0:
        # auto: biggest power-of-two chunk <= 8 that isn't larger than the
        # cohort — small enough to bound peak memory, big enough to amortize.
        c = 1
        while c * 2 <= min(8, num_clients):
            c *= 2
    return min(c, num_clients)


class _CohortCtx(NamedTuple):
    """Everything the placement runners need, resolved once at build time."""
    alg: object
    client_update: Callable
    spmd_axes: Optional[Tuple[str, ...]]
    stateful: bool
    constrain_accum: Optional[Callable]
    fed: FedConfig
    place: str
    chunk_size: Optional[int]
    prepare_params: Optional[Callable]
    server_opt: Optimizer


def _budget_masked(grad_fn: Callable) -> Callable:
    """Wrap ``grad_fn`` with the heterogeneous local-step budget mask.

    A client past its budget runs "idle" steps — gradients masked to zero
    so plain-SGD params freeze (exactness enforced by FedConfig:
    client_opt="sgd" and a gradient-driven algorithm). The per-step 0/1
    budget mask rides in the batch dict as the "_active" leaf, (C, K)
    alongside the data's (C, K, ...) leaves — data/cohort_source.py
    injects it."""
    @functools.wraps(grad_fn)
    def masked_grad_fn(params, batch, *cparams):
        if not isinstance(batch, dict) or "_active" not in batch:
            raise ValueError(
                "min_local_steps > 0 needs dict batches carrying the "
                "'_active' per-step budget mask "
                "(data/cohort_source.py injects it)")
        active = jnp.asarray(batch["_active"], jnp.float32)
        data = {k: v for k, v in batch.items() if k != "_active"}
        loss, grads = grad_fn(params, data, *cparams)
        return loss, tm.tmap(lambda g: g * active.astype(g.dtype), grads)

    return masked_grad_fn


def _client_axes(ctx: _CohortCtx, n_extra: int):
    return (None, 0) + ((0,) if ctx.stateful else ()) + (None,) * n_extra


def _qffl_weights(ctx: _CohortCtx, weights, metrics):
    """q-FFL effective weights: ``w_k * max(loss_first_k, 0)**q``.

    The fairness tilt of q-FFL (Li et al. 2020) — high-loss clients count
    for more in the aggregate; ``_run_cohort`` renormalizes the tilted fold
    by ``sum_k w_k * lam_k`` so the aggregate stays a weighted mean. The
    gate is trace-time: ``fed.qffl_q == 0`` (the default) returns the
    weights untouched, so the default program is bitwise the untilted one.
    ``loss_first`` (the pre-update local loss) is the tilt signal so the
    weight reflects where the client *started* this round, not what its
    local steps already fixed. Zero-weight entries (dropped clients,
    chunk padding) stay zero for any q.
    """
    if not ctx.fed.qffl_q:
        return weights
    lam = jnp.maximum(metrics["loss_first"], 0.0) ** ctx.fed.qffl_q
    return weights * lam.astype(weights.dtype)


def _run_parallel(ctx, params, client_batches, weights, extras, cstates):
    vm = jax.vmap(ctx.client_update, in_axes=_client_axes(ctx, len(extras)),
                  spmd_axis_name=ctx.spmd_axes)
    res = vm(params, client_batches,
             *((cstates,) if ctx.stateful else ()), *extras)
    w = _qffl_weights(ctx, weights, res.metrics)
    with jax.named_scope("aggregate"):
        agg = ctx.alg.reduce_stacked(res.payload, w)
    return agg, res.metrics, res.state_update


def _zero_accum(ctx, params):
    acc = ctx.alg.init_accum(params)
    if ctx.constrain_accum is not None:
        acc = ctx.alg.map_components(
            lambda z: ctx.constrain_accum(z, params), acc)
    return acc


def _run_sequential(ctx, params, client_batches, weights, extras, cstates):
    def body(acc, xs):
        batches, w, cs = xs
        res = ctx.client_update(params, batches,
                                *((cs,) if ctx.stateful else ()), *extras)
        w = _qffl_weights(ctx, w, res.metrics)
        with jax.named_scope("aggregate"):
            acc = ctx.alg.accumulate(acc, res.payload, w)
        return acc, (res.metrics, res.state_update)

    agg, (metrics, new_states) = jax.lax.scan(
        body, _zero_accum(ctx, params),
        (client_batches, weights, cstates if ctx.stateful else ()))
    return agg, metrics, new_states


def _run_chunked(ctx, params, client_batches, weights, extras, cstates,
                 chunk):
    C = weights.shape[0]
    n_chunks = -(-C // chunk)
    pad = n_chunks * chunk - C

    def pad_lead(x):
        return jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)],
                               axis=0)

    if pad:
        # zero-weight duplicates of client 0 square off the last chunk
        client_batches = tm.tmap(pad_lead, client_batches)
        weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)])
        if ctx.stateful:
            cstates = tm.tmap(pad_lead, cstates)

    def to_chunks(x):
        return x.reshape((n_chunks, chunk) + x.shape[1:])

    chunked = tm.tmap(to_chunks, client_batches)
    w_chunks = weights.reshape(n_chunks, chunk)
    cs_chunks = tm.tmap(to_chunks, cstates) if ctx.stateful else ()

    def body(acc, xs):
        batches, w, cs = xs
        vm = jax.vmap(ctx.client_update,
                      in_axes=_client_axes(ctx, len(extras)),
                      spmd_axis_name=ctx.spmd_axes)
        res = vm(params, batches,
                 *((cs,) if ctx.stateful else ()), *extras)
        w = _qffl_weights(ctx, w, res.metrics)
        with jax.named_scope("aggregate"):
            acc = tm.tmap(lambda a, c: a + c.astype(a.dtype),
                          acc, ctx.alg.reduce_stacked(res.payload, w))
        return acc, (res.metrics, res.state_update)

    agg, (metrics, new_states) = jax.lax.scan(
        body, _zero_accum(ctx, params), (chunked, w_chunks, cs_chunks))
    # (n_chunks, chunk) -> (C,) with the padding sliced off
    unpad = lambda x: x.reshape((n_chunks * chunk,) + x.shape[2:])[:C]
    metrics = tm.tmap(unpad, metrics)
    if ctx.stateful:
        new_states = tm.tmap(unpad, new_states)
    return agg, metrics, new_states


def _run_cohort(ctx: _CohortCtx, state: ServerState, client_batches,
                client_weights, client_states, survivor_mask=None):
    """One cohort pass through the resolved placement runner."""
    C = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
    params = (state.params if ctx.prepare_params is None
              else ctx.prepare_params(state.params))
    extras = ctx.alg.broadcast(state, ctx.server_opt)
    if survivor_mask is not None:
        mask = jnp.asarray(survivor_mask, jnp.float32)
        base = (jnp.ones((C,), jnp.float32) if client_weights is None
                else jnp.asarray(client_weights, jnp.float32))
        client_weights = base * mask
    weights = normalized_weights(client_weights, C)

    if ctx.place == "parallel":
        agg, metrics, new_states = _run_parallel(
            ctx, params, client_batches, weights, extras, client_states)
    elif ctx.place == "sequential":
        agg, metrics, new_states = _run_sequential(
            ctx, params, client_batches, weights, extras, client_states)
    else:
        chunk = _resolve_chunk(ctx.fed, ctx.chunk_size, C)
        agg, metrics, new_states = _run_chunked(
            ctx, params, client_batches, weights, extras, client_states,
            chunk)

    if ctx.fed.qffl_q:
        # the placements folded with the q-FFL-tilted weights w_k * lam_k
        # (_qffl_weights); dividing the linear accumulator by
        # z = sum_k w_k * lam_k makes the effective weights
        # (w_k * lam_k) / z — a normalized weighting, same contract as the
        # untilted path. max() guards the all-dropped / all-zero-loss
        # cohort (z = 0 -> zero aggregate, matching the untilted path).
        # Ratio-form aggregates ({num, den} pairs — fedpa_precision,
        # fedlora) cancel z in finish_cohort, so fedlora's encoded-codec
        # map_components skipping the division is still exact.
        lam = jnp.maximum(metrics["loss_first"], 0.0) ** ctx.fed.qffl_q
        z = jnp.sum(weights * lam.astype(weights.dtype))
        agg = ctx.alg.map_components(
            lambda a: a / jnp.maximum(z, 1e-12).astype(a.dtype), agg)

    # cohort-stage epilogue on the summed accumulator, still traced inside
    # the cohort program: fedlora decodes its low-rank accumulator here with
    # the dispatch-time state.round (the async engine may apply the result
    # against a newer server state)
    with jax.named_scope("aggregate"):
        agg = ctx.alg.finish_cohort(state, agg)

    if survivor_mask is None:
        losses = {
            "loss_first": jnp.mean(metrics["loss_first"]),
            "loss_last": jnp.mean(metrics["loss_last"]),
        }
    else:
        # survivor-only means; an all-dropped round reports 0.0 losses
        mask = jnp.asarray(survivor_mask, jnp.float32)
        n = jnp.maximum(jnp.sum(mask), 1.0)
        losses = {
            "loss_first": jnp.sum(metrics["loss_first"] * mask) / n,
            "loss_last": jnp.sum(metrics["loss_last"] * mask) / n,
        }
    return agg, losses, new_states


def make_cohort_program(
    grad_fn: Callable,
    fed: FedConfig,
    *,
    placement: Optional[str] = None,
    chunk_size: Optional[int] = None,
    spmd_axes: Optional[Tuple[str, ...]] = None,
    use_sampling: bool = True,
    client_opt: Optional[Optimizer] = None,
    server_opt: Optional[Optimizer] = None,
    wrap_client: Optional[Callable] = None,
    prepare_params: Optional[Callable] = None,
    constrain_accum: Optional[Callable] = None,
    state_placement: Optional[str] = None,
) -> Callable:
    """Build ``cohort_fn(state, client_batches[, client_weights[, states]])``.

    The client half of a round: cohort of local updates -> aggregated
    payload (the algorithm's linear accumulator; for mean-delta algorithms
    this IS the weighted mean delta). ``client_batches``: pytree whose
    leaves carry a leading client axis C and a second per-client step axis
    K (``fed.local_steps``). ``client_weights`` (optional, shape (C,)) are
    normalized inside the program; None means uniform. Returns
    ``(agg, {"loss_first", "loss_last"})`` with the losses averaged
    (unweighted) over the cohort; ``agg`` feeds ``make_server_program``'s
    server stage, which finalizes it into the pseudo-gradient.

    ``survivor_mask`` (optional trailing argument, shape (C,) float 0/1) is
    the fault-injection path (``data/cohort_source.py``): a client whose
    mask entry is 0 dropped out mid-round, so its weight is zeroed *before*
    normalization — the survivors' weighted partial aggregation renormalizes
    over the survivors only — and its losses are excluded from the cohort
    means. An all-zero mask degrades to a zero aggregate (traced
    ``normalized_weights`` yields zero weights, never NaN), i.e. the server
    sees a zero pseudo-gradient for an all-dropped round. ``None`` (the
    default) traces the exact mask-free program of the fault-free engine,
    so zero-rate fault configs are bitwise-identical to today's rounds.

    For a *stateful* algorithm (``alg.stateful``) the signature depends on
    the client-state placement (``state_placement``, default
    ``fed.client_state_placement``):

    * ``"host"`` — one extra argument and result: ``cohort_fn(state,
      client_batches, client_weights, client_states) -> (agg, losses,
      new_client_states)``. ``client_states`` is the cohort's gathered
      ``ClientStateStore`` slice (leading axis C) and
      ``new_client_states`` the stacked ``ClientResult.state_update`` to
      scatter back — the gather/scatter edges are host-side numpy.
    * ``"device"`` — the gather moves *inside* the program:
      ``cohort_fn(state, client_batches, client_weights, store_state,
      client_ids) -> (agg, losses, new_client_states, stamps)``.
      ``store_state`` is ``DeviceClientStateStore.device_state()`` (the
      full dense ``(N, ...)`` buffers + write stamps) and ``client_ids``
      the traced cohort id vector; the cohort's slice is gathered on
      device and the returned stacked updates + gather-time stamps feed
      ``core.client_state.device_scatter`` (fused into the round by
      ``make_round_program``, or applied later by the async engine) — no
      state traffic ever touches the host.

    Takes the full ``ServerState`` (not just params) because the
    algorithm's broadcast hook may read server-optimizer statistics (MIME's
    frozen momentum) or persistent algorithm state (SCAFFOLD's server
    control variate); only ``state.params`` (+ opt stats) are consumed, so
    the async engine may pass a state that is ``s`` versions stale.
    ``server_opt`` is only consulted by that hook and defaults to the
    ``fed``-configured server optimizer.
    """
    from repro.algorithms import resolve_algorithm  # noqa: PLC0415 — cycle

    alg = resolve_algorithm(fed, use_sampling)
    eff = alg.fed
    client_opt = client_opt or get_optimizer(eff.client_opt, eff.client_lr,
                                             eff.client_momentum)
    server_opt = server_opt or get_optimizer(fed.server_opt, fed.server_lr,
                                             fed.server_momentum)
    if eff.min_local_steps:
        grad_fn = _budget_masked(grad_fn)

    client_update = alg.make_client_update(grad_fn, client_opt)
    if wrap_client is not None:
        client_update = wrap_client(client_update)
    state_place = resolve_state_placement(fed, state_placement)
    ctx = _CohortCtx(
        alg=alg, client_update=client_update, spmd_axes=spmd_axes,
        stateful=alg.stateful, constrain_accum=constrain_accum, fed=fed,
        place=resolve_placement(fed, placement), chunk_size=chunk_size,
        prepare_params=prepare_params, server_opt=server_opt,
    )

    if ctx.stateful and state_place == "device":
        def cohort_fn(state: ServerState, client_batches,
                      client_weights=None, store_state=None,
                      client_ids=None, survivor_mask=None):
            if store_state is None or client_ids is None:
                raise ValueError(
                    f"algorithm {alg.name!r} is stateful with the device "
                    f"store: cohort_fn needs store_state "
                    f"(DeviceClientStateStore.device_state()) and the "
                    f"cohort's client_ids (prepare_ids)")
            cstates, stamps = device_gather(store_state, client_ids)
            agg, losses, new_states = _run_cohort(
                ctx, state, client_batches, client_weights, cstates,
                survivor_mask)
            return agg, losses, new_states, stamps
    elif ctx.stateful:
        def cohort_fn(state: ServerState, client_batches,
                      client_weights=None, client_states=None,
                      survivor_mask=None):
            if client_states is None:
                raise ValueError(
                    f"algorithm {alg.name!r} is stateful: cohort_fn needs "
                    f"the gathered client_states slice "
                    f"(ClientStateStore.gather)")
            return _run_cohort(ctx, state, client_batches, client_weights,
                               client_states, survivor_mask)
    else:
        def cohort_fn(state: ServerState, client_batches,
                      client_weights=None, survivor_mask=None):
            agg, losses, _ = _run_cohort(ctx, state, client_batches,
                                         client_weights, None, survivor_mask)
            return agg, losses

    return cohort_fn


def make_server_program(
    fed: FedConfig,
    *,
    server_opt: Optional[Optimizer] = None,
    use_sampling: bool = True,
    prepare_params: Optional[Callable] = None,
    finalize_params: Optional[Callable] = None,
) -> Callable:
    """Build ``server_fn(state, agg, discount=None) -> new_state``.

    The server half of a round: finalize the cohort aggregate into the
    pseudo-gradient and take one server-optimizer step — both owned by the
    algorithm's ``server_update`` hook. ``discount`` (optional traced
    scalar) is the async engine's ``staleness_discount ** s`` for an
    aggregate computed at params version ``v`` and applied at version
    ``v + s``; ``discount=None`` (or 1.0) is the synchronous update. The
    default hook scales the pseudo-gradient in fp32 and casts back, so a
    discount of exactly 1.0 is a bitwise no-op and the ``staleness=0``
    async path matches the fused sync program; algorithms may discount per
    parameter (``fedpa_precision``). ``use_sampling=False`` builds the
    stage for the burn-in regime's aggregate structure.
    """
    from repro.algorithms import resolve_algorithm  # noqa: PLC0415 — cycle

    alg = resolve_algorithm(fed, use_sampling)
    server_opt = server_opt or get_optimizer(fed.server_opt, fed.server_lr,
                                             fed.server_momentum)

    def server_fn(state: ServerState, agg, discount=None):
        with jax.named_scope("server_update"):
            params = (state.params if prepare_params is None
                      else prepare_params(state.params))
            new_state = alg.server_update(state._replace(params=params), agg,
                                          server_opt, discount)
            if finalize_params is not None:
                new_state = new_state._replace(
                    params=finalize_params(new_state.params))
        return new_state

    return server_fn


def make_round_program(
    grad_fn: Callable,
    fed: FedConfig,
    *,
    placement: Optional[str] = None,
    chunk_size: Optional[int] = None,
    spmd_axes: Optional[Tuple[str, ...]] = None,
    use_sampling: bool = True,
    client_opt: Optional[Optimizer] = None,
    server_opt: Optional[Optimizer] = None,
    wrap_client: Optional[Callable] = None,
    prepare_params: Optional[Callable] = None,
    finalize_params: Optional[Callable] = None,
    constrain_accum: Optional[Callable] = None,
    state_placement: Optional[str] = None,
) -> Callable:
    """Build the fused ``round_fn(state, client_batches[, client_weights])``.

    Composes ``make_cohort_program`` and ``make_server_program`` into the
    single-dispatch synchronous round: cohort of client updates -> weighted
    aggregation -> server step. Returns ``(new_state, {"loss_first",
    "loss_last"})``. For a stateful algorithm with the host store the round
    takes the cohort's gathered ``client_states`` and returns
    ``(new_state, losses, new_client_states)``; with the device store
    (``state_placement="device"``) it takes ``(store_state, client_ids)``
    instead and returns ``(new_state, losses, new_store_state)`` — gather,
    clients, CAS scatter, and server step all in the one jitted program,
    so callers may donate ``store_state``
    (``core.client_state.jit_donating_store``) for an in-place update
    (see ``make_cohort_program``).

    ``use_sampling=False`` builds the burn-in-round variant of the config's
    algorithm (e.g. the FedAvg regime of a FedPA config, Section 5.2) with
    identical signature.

    Sharding hooks (all optional, identity by default) let the multi-pod
    path reuse this exact program structure:

    * ``wrap_client(update) -> update'`` — wrap the per-client update
      (``update`` returns a ``ClientResult``), e.g. to all-gather
      FSDP-sharded params at the compute boundary.
    * ``prepare_params(params)`` — applied to the server params before they
      are handed to clients / the server optimizer. Must be idempotent
      (sharding constraints are): the cohort and server stages each apply
      it, so the fused round runs it twice per round.
    * ``finalize_params(params)`` — applied to the post-update params.
    * ``constrain_accum(zeros, like_params)`` — sharding constraint for the
      sequential/chunked accumulator (applied per param-shaped component).

    The returned function is pure and jit-compatible; callers own the
    ``jax.jit`` (``FedSim`` jits it, the dry-run lowers it un-jitted).
    """
    cohort_fn = make_cohort_program(
        grad_fn, fed, placement=placement, chunk_size=chunk_size,
        spmd_axes=spmd_axes, use_sampling=use_sampling, client_opt=client_opt,
        server_opt=server_opt, wrap_client=wrap_client,
        prepare_params=prepare_params, constrain_accum=constrain_accum,
        state_placement=state_placement,
    )
    server_fn = make_server_program(
        fed, server_opt=server_opt, use_sampling=use_sampling,
        prepare_params=prepare_params, finalize_params=finalize_params,
    )

    from repro.algorithms import resolve_algorithm  # noqa: PLC0415 — cycle

    stateful = resolve_algorithm(fed, use_sampling).stateful
    state_place = resolve_state_placement(fed, state_placement)

    if stateful and state_place == "device":
        def round_fn(state: ServerState, client_batches, client_weights=None,
                     store_state=None, client_ids=None, survivor_mask=None):
            agg, metrics, new_states, stamps = cohort_fn(
                state, client_batches, client_weights, store_state,
                client_ids, survivor_mask)
            # within one program nothing can write between the gather and
            # this scatter, so the CAS always succeeds (drops == 0 by
            # construction; discarded). A survivor mask suppresses the
            # dropped clients' writes: their state must not land.
            new_store, _ = device_scatter(store_state, client_ids,
                                          new_states, stamps,
                                          write_mask=survivor_mask)
            return server_fn(state, agg), metrics, new_store
    elif stateful:
        def round_fn(state: ServerState, client_batches, client_weights=None,
                     client_states=None, survivor_mask=None):
            agg, metrics, new_states = cohort_fn(
                state, client_batches, client_weights, client_states,
                survivor_mask)
            return server_fn(state, agg), metrics, new_states
    else:
        def round_fn(state: ServerState, client_batches, client_weights=None,
                     survivor_mask=None):
            agg, metrics = cohort_fn(state, client_batches, client_weights,
                                     survivor_mask)
            return server_fn(state, agg), metrics

    return round_fn
