"""The staleness-general round engine: ONE loop for sync and async.

The paper's framing — FedAvg as the degenerate case of a generalized
posterior-inference round loop — applies to the loop itself: the
synchronous path is the async pipeline with an in-flight window of one.
``RoundEngine`` owns that single loop: cohort dispatch (up to
``max_staleness + 1`` cohorts in flight), delta application with the
``staleness_discount ** s`` down-weighting, client-state gather /
CAS-scatter routing for both store placements, burn-in regimes, eval
cadence, the prefetcher lifecycle, and history via the shared
``core.history.RoundRecorder``. ``FedSim`` (``core/round.py``),
``launch.train``, the deprecated ``AsyncRoundEngine`` alias
(``core/async_engine.py``), and the engine benchmarks are all thin
frontends over it.

Two program backends hide behind the one loop:

* **fused** (``round_fn`` from ``make_round_program``): the whole round
  — cohort, aggregation, server update — is one jitted XLA dispatch.
  Used when the window is 1 (``max_staleness=0``) and no straggler can
  add lateness to the staleness exponent (``pipeline_only=False``);
  bitwise-identical to the pre-engine synchronous loop.
* **split** (``cohort_fn`` + ``server_fn`` from ``make_cohort_program``
  / ``make_server_program``): cohort compute and server update are
  separate dispatches so cohort ``t+1`` can be in flight before round
  ``t``'s update lands, and so a delta computed at params version ``v``
  and applied at ``v + s`` can be discounted by
  ``staleness_discount ** s`` (straggler lateness rides the same
  exponent). Bitwise-identical to the pre-engine async engine.

The two backends agree to float rounding but are NOT bitwise-identical
in general (XLA fuses the round differently), which is why both exist;
each frontend keeps whichever bitwise contract it always had.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, List, NamedTuple, Optional, Tuple, Union

import jax

from repro.core.client_state import (ClientStateStore, DeviceClientStateStore,
                                     device_scatter, jit_donating_store)
from repro.core.history import RoundRecorder
from repro.core.server import ServerState
from repro.core.spans import span
from repro.data.prefetch import Cohort, close_prefetcher, make_prefetcher

#: build_cohort(round_idx) -> Cohort (see data/prefetch.py)
BuildCohort = Callable[[int], Cohort]


class _InFlight(NamedTuple):
    """One dispatched-but-unapplied cohort in the split-backend pipeline.

    ``version`` is the params version the cohort saw when dispatched;
    ``client_ids`` / ``new_states`` / ``stamps`` carry the per-client
    state write-back (None for stateless regimes): the gather-time write
    stamps let the store drop a stale write from a cohort that overlapped
    an already-applied one on the same client. With the device store the
    three are device arrays (the traced id vector, the cohort program's
    stacked state output, the on-device stamp snapshot) and the write-back
    never touches the host. ``survivors`` / ``extra_staleness`` /
    ``dropped`` are the cohort's fault annotations (``data.cohort_source``):
    the survivors mask was already threaded through the dispatched cohort
    program and gates the state write-back; straggler lateness is added to
    the staleness exponent at apply time.
    """

    agg: object
    metrics: dict
    version: int
    round_idx: int
    is_burn: bool
    client_ids: object = None
    new_states: object = None
    stamps: object = None
    survivors: object = None
    extra_staleness: int = 0
    dropped: int = 0


class _Applied(NamedTuple):
    """What one applied round hands the recorder (either backend)."""

    state: ServerState
    metrics: dict
    is_burn: bool
    staleness: int
    dropped: int
    straggled: int
    state_drops: object   # int, or the device store's CAS drop counter


@dataclasses.dataclass
class RoundEngine:
    """Drives ``num_rounds`` staleness-aware rounds; window=1 ≡ sync.

    Pass raw program builders, not pre-jitted functions — the engine owns
    all jitting (including the device store's donation + pinned
    ``out_shardings``). Backends:

    * split stages: ``cohort_fn(state, batches, weights, survivors) ->
      (agg, metrics)`` + ``server_fn(state, agg, discount) -> state``
      (stateful signatures as in ``make_cohort_program``); required
      whenever ``max_staleness > 0`` or ``pipeline_only=True``.
    * fused round: ``round_fn(state, batches, weights[, store, ids],
      survivors) -> (state, metrics[, new_store])`` from
      ``make_round_program``; required for the single-dispatch window=1
      path and the one-shot ``round()`` API.

    ``burn_*`` variants (optional) are used for the first
    ``burn_in_rounds`` rounds — the burn regime of the config's algorithm
    (e.g. the FedAvg regime of a FedPA config, Section 5.2); the burn
    server stage exists because a burn regime may aggregate in a
    different payload space than the sampling regime (``fedpa_precision``
    burns in as fedavg).

    Stateful algorithms (``stateful=True`` + a ``client_store``): each
    dispatched cohort gathers its clients' persistent state from the store
    and the write-back happens at APPLY time, in round order, tagged with
    the gather-time stamps — so when two in-flight cohorts overlap on a
    client, the one applied second (which gathered before the first wrote)
    is dropped for that client instead of clobbering the fresher state.
    With the host ``ClientStateStore`` the write-back pulls ``new_states``
    to the host; with a ``DeviceClientStateStore`` the gather happens
    *inside* the dispatched program and the write-back is a small jitted
    ``device_scatter`` (store buffers donated, CAS drop count kept as a
    device counter until the end-of-loop history sync).

    ``pipeline_only=True`` forces the split backend even at window=1:
    straggler injection (``fed.straggler_rate > 0``) needs the apply-time
    ``staleness_discount ** extra_staleness`` path that the fused program
    does not trace. ``lift_operand`` (optional) lifts host-built operands
    (the survivors mask, prepared store ids) to global arrays for
    multi-process runs (``launch.train``'s ``replicate_global``).
    """

    cohort_fn: Optional[Callable] = None
    server_fn: Optional[Callable] = None
    max_staleness: int = 0
    staleness_discount: float = 1.0
    burn_cohort_fn: Optional[Callable] = None
    burn_server_fn: Optional[Callable] = None
    burn_in_rounds: int = 0
    prefetch_rounds: int = 0
    prefetch_backend: str = "thread"
    client_store: Optional[Union[ClientStateStore,
                                 DeviceClientStateStore]] = None
    stateful: bool = False
    burn_stateful: bool = False
    #: kept for frontend compat: the uniform history schema now stamps
    #: ``dropped`` / ``straggled`` on every record (0 defaults), so this
    #: no longer gates anything
    record_faults: bool = False
    #: Per-round communicated bytes (``compression.round_bytes`` dicts with
    #: ``bytes_up`` / ``bytes_down``), stamped on every history record;
    #: ``burn_round_bytes`` covers the burn regime's (dense) payloads.
    round_bytes: Optional[dict] = None
    burn_round_bytes: Optional[dict] = None
    round_fn: Optional[Callable] = None
    burn_round_fn: Optional[Callable] = None
    pipeline_only: bool = False
    lift_operand: Optional[Callable] = None

    def __post_init__(self):
        """Validate knobs, normalize the burn-regime flags, jit the
        backends."""
        if self.max_staleness < 0:
            raise ValueError("max_staleness must be >= 0")
        if not 0.0 <= self.staleness_discount <= 1.0:
            raise ValueError("staleness_discount must be in [0, 1]")
        needs_split = (self.max_staleness > 0 or self.pipeline_only
                       or self.round_fn is None)
        if needs_split and (self.cohort_fn is None or self.server_fn is None):
            raise ValueError(
                "RoundEngine needs split stages (cohort_fn + server_fn) "
                "whenever the pipeline can run: max_staleness > 0, "
                "pipeline_only=True, or no fused round_fn was given")
        if self.burn_cohort_fn is None and self.burn_round_fn is None:
            # no dedicated burn stage: burn rounds run the main programs,
            # so they are stateful exactly when the main regime is
            self.burn_stateful = self.stateful
        if (self.stateful or self.burn_stateful) and self.client_store is None:
            raise ValueError(
                "stateful=True requires a client-state store (client_store)")
        self._device_store = isinstance(self.client_store,
                                        DeviceClientStateStore)
        # the split backend's device write-back stage: donate the store so
        # the (N, ...) buffers alias in place instead of doubling
        # per-client state; a population-sharded store additionally pins
        # the scatter's store output to its own placement so the alias is
        # shard-for-shard
        self._scatter = None
        if self._device_store:
            pop_sh = self.client_store.population_sharding
            self._scatter = jit_donating_store(
                device_scatter, 0,
                out_shardings=None if pop_sh is None else (pop_sh, None))
        self._cohort = (jax.jit(self.cohort_fn)
                        if self.cohort_fn is not None else None)
        self._burn = (jax.jit(self.burn_cohort_fn)
                      if self.burn_cohort_fn is not None else self._cohort)
        self._server = (jax.jit(self.server_fn)
                        if self.server_fn is not None else None)
        self._burn_server = (jax.jit(self.burn_server_fn)
                             if self.burn_server_fn is not None
                             else self._server)
        self._fused = self._jit_fused(self.round_fn, self.stateful)
        self._fused_burn = (self._jit_fused(self.burn_round_fn,
                                            self.burn_stateful)
                            if self.burn_round_fn is not None
                            else self._fused)
        #: window=1 with no straggler lateness runs the single-dispatch
        #: fused program — today's sync path, bitwise
        self._use_fused = (self._fused is not None
                           and self.max_staleness == 0
                           and not self.pipeline_only)

    def _jit_fused(self, round_fn, regime_stateful: bool):
        """Jit one fused round; a device-stateful regime donates the store
        argument so the (N, ...) buffers update in place, pinned to the
        store's own population sharding so the alias is shard-for-shard."""
        if round_fn is None:
            return None
        if regime_stateful and self._device_store:
            out_sh = None
            if self.client_store.population_sharding is not None:
                out_sh = (None, None,
                          self.client_store.population_sharding)
            return jit_donating_store(round_fn, 3, out_shardings=out_sh)
        return jax.jit(round_fn)

    def _lift(self, x):
        """Lift a host-built operand to a global array (multi-process)."""
        if x is None or self.lift_operand is None:
            return x
        return self.lift_operand(x)

    # -- split backend: dispatch now, apply (discounted) later ------------
    def _dispatch(self, state: ServerState, cohort: Cohort, t_next: int,
                  version: int) -> _InFlight:
        """Dispatch one cohort program and wrap its outputs as ``_InFlight``.

        Stateful regimes also carry the per-client state write-back: with
        the device store the gather happens inside the dispatched program
        against the store's current device buffers (the returned stamps
        snapshot tags the CAS); with the host store the gather is a host
        numpy slice."""
        is_burn = t_next < self.burn_in_rounds
        fn = self._burn if is_burn else self._cohort
        surv = self._lift(cohort.survivors)
        fault = (surv, cohort.extra_staleness, cohort.dropped)
        if not (self.burn_stateful if is_burn else self.stateful):
            agg, metrics = fn(state, cohort.batches, cohort.weights, surv)
            return _InFlight(agg, metrics, version, t_next, is_burn,
                             None, None, None, *fault)
        if self._device_store:
            ids = self._lift(self.client_store.prepare_ids(cohort.client_ids))
            agg, metrics, new_states, stamps = fn(
                state, cohort.batches, cohort.weights,
                self.client_store.device_state(), ids, surv)
            return _InFlight(agg, metrics, version, t_next, is_burn,
                             ids, new_states, stamps, *fault)
        cstates, stamps = self.client_store.gather(cohort.client_ids)
        agg, metrics, new_states = fn(state, cohort.batches, cohort.weights,
                                      cstates, surv)
        return _InFlight(agg, metrics, version, t_next, is_burn,
                         cohort.client_ids, new_states, stamps, *fault)

    def _apply_pipelined(self, state: ServerState, fl: _InFlight,
                         version: int) -> _Applied:
        """Apply one in-flight cohort: staleness-discounted server update,
        then the apply-order client-state write-back."""
        # a straggling cohort is applied at its slot but discounted as if
        # it were extra_staleness rounds later — the late delta rides the
        # existing staleness_discount**s path
        staleness = version - fl.version + fl.extra_staleness
        server = self._burn_server if fl.is_burn else self._server
        state = server(state, fl.agg, self.staleness_discount ** staleness)
        drops = self._write_back_states(fl)
        return _Applied(state, fl.metrics, fl.is_burn, staleness,
                        int(fl.dropped), int(fl.extra_staleness), drops)

    def _write_back_states(self, fl: _InFlight):
        """Apply-order client-state write-back, tagged with the gather-time
        stamps: a client already updated by an overlapping cohort keeps
        that fresher value (stale write dropped); a dropped client's
        half-finished state must not land. Returns the CAS drop count
        (a device scalar for the device store — no per-round host pull)."""
        if fl.new_states is None:
            return 0
        if self._device_store:
            new_store, drops = self._scatter(
                self.client_store.device_state(), fl.client_ids,
                fl.new_states, fl.stamps, fl.survivors)
            self.client_store.set_device_state(new_store)
            return drops
        return self.client_store.scatter(
            fl.client_ids, fl.new_states, fl.stamps,
            write_mask=fl.survivors)

    # -- fused backend: the whole round is one dispatch --------------------
    def _apply_fused(self, state: ServerState, cohort: Cohort,
                     t: int) -> _Applied:
        """One fused round; stateful algorithms additionally thread the
        cohort's client state through the jitted round — gathered and
        scattered at the host edges for the host store, or passed as the
        store's device buffers (+ the cohort ids) with the gather/CAS
        scatter fused into the program for the device store."""
        is_burn = t < self.burn_in_rounds
        fn = self._fused_burn if is_burn else self._fused
        stateful = self.burn_stateful if is_burn else self.stateful
        surv = self._lift(cohort.survivors)  # None = mask-free program
        drops = 0
        if stateful and self._device_store:
            ids = self._lift(self.client_store.prepare_ids(cohort.client_ids))
            state, metrics, new_store = fn(
                state, cohort.batches, cohort.weights,
                self.client_store.device_state(), ids, surv)
            self.client_store.set_device_state(new_store)
        elif stateful:
            cstates, stamps = self.client_store.gather(cohort.client_ids)
            state, metrics, new_states = fn(
                state, cohort.batches, cohort.weights, cstates, surv)
            # a dropped client's half-finished state must not land
            drops = self.client_store.scatter(cohort.client_ids, new_states,
                                              stamps, write_mask=surv)
        else:
            state, metrics = fn(state, cohort.batches, cohort.weights, surv)
        return _Applied(state, metrics, is_burn, 0,
                        int(cohort.dropped), int(cohort.extra_staleness),
                        drops)

    def round(self, state: ServerState, cohort: Cohort, round_idx: int
              ) -> Tuple[ServerState, dict]:
        """One synchronous round via the fused backend (requires
        ``round_fn``); returns ``(state, record)`` with the record already
        finalized to plain Python — the one-shot twin of ``run``."""
        if self._fused is None:
            raise ValueError(
                "RoundEngine.round needs a fused round_fn (the split "
                "pipeline has no single-round API)")
        recorder = RoundRecorder(round_bytes=self.round_bytes,
                                 burn_round_bytes=self.burn_round_bytes)
        out = self._apply_fused(state, cohort, round_idx)
        recorder.record(round_idx=round_idx, metrics=out.metrics,
                        is_burn=out.is_burn, staleness=out.staleness,
                        dropped=out.dropped, straggled=out.straggled,
                        state_drops=out.state_drops)
        return out.state, recorder.history()[0]

    def run(
        self,
        state: ServerState,
        build_cohort: BuildCohort,
        num_rounds: int,
        *,
        eval_fn: Optional[Callable] = None,
        eval_every: int = 1,
        on_round: Optional[Callable] = None,
    ) -> Tuple[ServerState, List[dict]]:
        """Returns ``(state, history)``; one uniform-schema history entry
        per applied round (``core.history.RoundRecorder``), every value
        JSON-serializable after the single end-of-loop sync. ``eval_fn``
        metrics ride the records of rounds where ``t % eval_every == 0``
        (plus the last round).

        ``on_round(record, state)`` fires after each server update with the
        raw (possibly still-on-device) metrics and the post-update state —
        for live logging/checkpointing. Forcing metrics there re-introduces
        a per-round sync, so log sparingly in throughput-sensitive loops.

        Each round is host span ``repro.round`` (``core.spans``), holding
        ``repro.cohort_get`` (prefetch wait or inline build), ``repro.dispatch``
        (the host's enqueue of a round's programs: once per round fused,
        twice split), ``repro.eval`` and ``repro.on_round``.
        """
        if eval_fn is not None and eval_every < 1:
            raise ValueError(
                f"eval_every must be >= 1 when eval_fn is set, got "
                f"{eval_every} (evaluate every round with eval_every=1, or "
                f"pass eval_fn=None to disable evaluation)")
        recorder = RoundRecorder(round_bytes=self.round_bytes,
                                 burn_round_bytes=self.burn_round_bytes)
        source = (make_prefetcher(self.prefetch_backend, build_cohort, 0,
                                  num_rounds, depth=self.prefetch_rounds)
                  if self.prefetch_rounds > 0 else None)
        get = source.get if source is not None else build_cohort
        fused = self._use_fused
        pending: deque = deque()   # in dispatch (== apply) order
        version = 0                # server updates applied so far
        t_next = 0                 # next round to dispatch
        completed = False
        try:
            for t_apply in range(num_rounds):
                with span("round"):
                    # keep up to max_staleness cohorts in flight beyond the
                    # one being applied; each remembers the params version
                    # it saw. The fused backend (window=1) has nothing in
                    # flight — its "dispatch" is the fused round below.
                    while (t_next < num_rounds
                           and len(pending) <= self.max_staleness):
                        with span("cohort_get"):
                            cohort = get(t_next)
                        if fused:
                            pending.append(cohort)
                        else:
                            with span("dispatch"):
                                pending.append(self._dispatch(
                                    state, cohort, t_next, version))
                        t_next += 1

                    item = pending.popleft()
                    with span("dispatch"):
                        if fused:
                            out = self._apply_fused(state, item, t_apply)
                        else:
                            assert item.round_idx == t_apply, (
                                item.round_idx, t_apply)
                            out = self._apply_pipelined(state, item,
                                                        version)
                    state = out.state
                    version += 1
                    ev = None
                    if eval_fn is not None and (t_apply % eval_every == 0
                                                or t_apply == num_rounds - 1):
                        with span("eval"):
                            ev = eval_fn(state.params)
                    rec = recorder.record(
                        round_idx=t_apply, metrics=out.metrics,
                        is_burn=out.is_burn, staleness=out.staleness,
                        dropped=out.dropped, straggled=out.straggled,
                        state_drops=out.state_drops, eval_metrics=ev)
                    if on_round is not None:
                        with span("on_round"):
                            on_round(rec, state)
            completed = True
        finally:
            if source is not None:
                # a hung prefetch worker stays loud on a clean exit but
                # must not mask an exception unwinding out of the loop
                close_prefetcher(source, unwinding=not completed)

        # one sync at the end instead of one per round — splicing raw
        # device arrays into history broke JSON serialization and hid a
        # sync on first access
        return state, recorder.history()
