"""Federated LM training driver (end-to-end example entry point).

Runs real federated rounds of the selected architecture on whatever devices
exist (a TPU chip, or the CPU at ``--smoke`` size; the same code paths the
dry-run lowers for the production mesh). FedPA vs FedAvg is a flag;
checkpoints + metrics logged. ``chip_smoke.py`` drives ``main`` in-process
at fedlm-100m's full width.

  PYTHONPATH=src python -m repro.launch.train --arch fedlm-100m --smoke \
      --rounds 20 --algorithm fedpa

Multi-host: launch one process per host with ``--coordinator host:port
--num-processes N --process-id k``. The population axis (client-state
store + cohort batches) shards over the global device mesh; each process
builds only its shard's batches (``data/prefetch.py``), the server state
is replicated, and checkpoints split into a process-0 server file plus
per-host store shards. Single-host population sharding (over local
devices) is ``--shard-population``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.algorithms import algorithm_names, get_algorithm, phase_name
from repro.checkpoint import (restore_checkpoint, restore_store_sharded,
                              save_checkpoint, save_store_sharded)
from repro.compression import round_bytes
from repro.configs.base import FedConfig
from repro.core.client_state import make_client_store
from repro.core.engine import RoundEngine
from repro.core.server import init_server_state
from repro.core.sharded_round import make_fed_round, make_fed_round_split
from repro.core.spans import span
from repro.data import SyntheticLMData
from repro.data.cohort_source import CohortSource
from repro.data.prefetch import (globalize_cohort_batches, local_row_range,
                                 replicate_global)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import init_distributed, make_host_mesh
from repro.models import init_params, lm_loss
from repro.optim import get_optimizer


def build_fed(args) -> FedConfig:
    """CLI flags -> the run's ``FedConfig``."""
    return FedConfig(
        algorithm=args.algorithm,
        clients_per_round=args.clients,
        local_steps=args.local_steps,
        burn_in_steps=args.burn_in_steps,
        steps_per_sample=args.steps_per_sample,
        shrinkage_rho=args.rho,
        server_opt=args.server_opt, server_lr=args.server_lr,
        client_opt=args.client_opt, client_lr=args.client_lr,
        burn_in_rounds=args.burn_in_rounds,
        payload_codec=args.payload_codec,
        lora_rank=args.lora_rank,
        quant_bits=args.quant_bits,
        error_feedback=not args.no_error_feedback,
        async_rounds=args.async_rounds,
        max_staleness=args.max_staleness,
        staleness_discount=args.staleness_discount,
        prefetch_rounds=args.prefetch_rounds,
        prefetch_backend=args.prefetch_backend,
        client_state_placement=args.client_state_placement,
        availability=args.availability,
        availability_period=args.availability_period,
        availability_duty=args.availability_duty,
        dropout_rate=args.dropout_rate,
        straggler_rate=args.straggler_rate,
        straggler_max_lateness=args.straggler_max_lateness,
        min_local_steps=args.min_local_steps,
    )


def parse_args(argv=None):
    """CLI flags for the training driver."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fedlm-100m",
                    choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--algorithm", default="fedpa",
                    choices=algorithm_names(),
                    help="registered federated algorithm "
                         f"(repro.algorithms): {', '.join(algorithm_names())}")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--num-clients", type=int, default=64,
                    help="population size")
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--burn-in-steps", type=int, default=4)
    ap.add_argument("--steps-per-sample", type=int, default=2)
    ap.add_argument("--burn-in-rounds", type=int, default=5)
    ap.add_argument("--rho", type=float, default=0.01)
    ap.add_argument("--server-opt", default="sgdm")
    ap.add_argument("--server-lr", type=float, default=0.5)
    ap.add_argument("--client-opt", default="sgdm",
                    help="client optimizer (scaffold requires 'sgd')")
    ap.add_argument("--client-lr", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--compute-dtype", default="bfloat16",
                    choices=("bfloat16", "float32"),
                    help="dtype of the client steps' and the eval's "
                         "activations (params are kept in float32)")
    ap.add_argument("--payload-codec", default="none",
                    help="client payload codec chain (repro.compression): "
                         "none | lowrank | int8 | lowrank+int8; non-'none' "
                         "requires --algorithm fedlora")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="rank of the 'lowrank' codec's per-(round, leaf) "
                         "sketch")
    ap.add_argument("--quant-bits", type=int, default=8, choices=(8, 16),
                    help="bit width of the 'int8' codec's quantizer")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable fedlora's per-client compression-error "
                         "residual (the client-state store stays unused)")
    ap.add_argument("--async-rounds", action="store_true",
                    help="double-buffered rounds: overlap cohort t+1's "
                         "client compute with round t's server update "
                         "(the wide-window path of core/engine.py)")
    ap.add_argument("--max-staleness", type=int, default=1,
                    help="cohorts in flight beyond the one being applied; "
                         "0 matches the sync path numerically")
    ap.add_argument("--staleness-discount", type=float, default=0.9,
                    help="a staleness-s delta is scaled by discount**s")
    ap.add_argument("--prefetch-rounds", type=int, default=2,
                    help="cohort batches stacked ahead by a host thread "
                         "(0 = inline)")
    ap.add_argument("--prefetch-backend", default="process",
                    choices=("process", "thread"),
                    help="cohort prefetcher: forked shared-memory arena "
                         "builder (overlaps GIL-bound decode) or in-process "
                         "thread (data/prefetch.py)")
    ap.add_argument("--availability", default="always",
                    choices=("always", "diurnal"),
                    help="client availability trace; 'diurnal' samples "
                         "cohorts only from currently-up clients "
                         "(data/cohort_source.py)")
    ap.add_argument("--availability-period", type=int, default=24,
                    help="diurnal cycle length in rounds")
    ap.add_argument("--availability-duty", type=float, default=0.5,
                    help="fraction of the cycle each client is up")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-client mid-round dropout probability; "
                         "survivors' partial aggregate is renormalized and "
                         "dropped clients' state writes are masked")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    help="probability a cohort misses its round deadline "
                         "(requires --async-rounds; late deltas are "
                         "discounted by staleness_discount**s)")
    ap.add_argument("--straggler-max-lateness", type=int, default=2,
                    help="max extra rounds of straggler lateness")
    ap.add_argument("--min-local-steps", type=int, default=0,
                    help="heterogeneous per-client step budgets in "
                         "[min, local_steps]; 0 = homogeneous (requires "
                         "--client-opt sgd on a gradient-pure algorithm)")
    ap.add_argument("--client-state-placement", default="host",
                    choices=("host", "device"),
                    help="where stateful algorithms' per-client state "
                         "lives: host numpy store (one device sync per "
                         "stateful round at scatter time) or device "
                         "buffers threaded through the jitted round "
                         "(sync-free; pulled to host only at checkpoints)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 for a multi-host run "
                         "(jax.distributed); every process passes the "
                         "same value")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank in [0, num_processes)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total processes in the multi-host run "
                         "(unset/1 = single-process)")
    ap.add_argument("--shard-population", action="store_true",
                    help="shard the population axis (client-state store + "
                         "cohort batches) over the device mesh; implied "
                         "by a multi-process launch")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log", default=None, help="JSONL metrics path")
    return ap.parse_args(argv)


def make_round_batches(args, cfg, fed, data, s_text):
    """Cohort batch builder ``(round, ids) -> batches`` for the round fns.

    The process prefetcher's forked builder must stay off the jax runtime,
    so its cohorts are assembled as numpy (bf16 is a numpy dtype via
    ml_dtypes; the jitted round casts on transfer)."""
    host_batches = fed.prefetch_backend == "process"

    def round_batches(r, ids):
        toks = data.round_batches(ids, fed.local_steps, args.batch, s_text,
                                  round_idx=r, host=host_batches)
        batches = {"tokens": toks}
        if cfg.frontend:
            fe = np.stack([
                np.stack([
                    data.frontend_embeddings(
                        int(c), args.batch, cfg.frontend_tokens, cfg.d_model,
                        salt=r * 1000 + k, host=True)
                    for k in range(fed.local_steps)
                ]) for c in ids
            ])
            batches["frontend"] = (fe.astype(jnp.bfloat16) if host_batches
                                   else jnp.asarray(fe, jnp.bfloat16))
        return batches

    return round_batches


def make_eval_fn(args, cfg, data, s_text, q_chunk):
    """Jitted held-out eval loss on a batch from an unseen client id."""
    eval_batch = {
        "tokens": data.client_batches(args.num_clients + 1, 1, args.batch,
                                      s_text)[0]
    }
    if cfg.frontend:
        eval_batch["frontend"] = jnp.asarray(
            data.frontend_embeddings(args.num_clients + 1, args.batch,
                                     cfg.frontend_tokens, cfg.d_model),
            jnp.bfloat16)
    return jax.jit(lambda p: lm_loss(p, eval_batch, cfg,
                                     compute_dtype=jnp.dtype(
                                         args.compute_dtype),
                                     q_chunk=q_chunk)[0])


def restore_if_present(args, state, store, ckpt_tree):
    """Resume from ``--ckpt-dir`` when a checkpoint exists.

    Returns ``(state, start_round)``; client state is loaded back into the
    store in place."""
    start_round = 0
    if args.ckpt_dir and os.path.isdir(args.ckpt_dir):
        try:
            restored, start_round, _ = restore_checkpoint(args.ckpt_dir,
                                                          ckpt_tree(state))
            if store is None:
                state = restored
            else:
                state = restored["server"]
                store.load_state_dict(restored["clients"])
            print(f"restored checkpoint at round {start_round}")
        except FileNotFoundError:
            pass
    return state, start_round


def main(argv=None):
    """Parse flags, build the round programs, drive the training loop.

    Returns ``(state, store)``: the final server state and the client-state
    store (None for stateless algorithms)."""
    args = parse_args(argv)
    enable_compile_cache()
    # before ANY jax device use: distributed init must see an
    # uninitialized backend
    distributed = init_distributed(args.coordinator, args.process_id,
                                   args.num_processes)
    shard_pop = args.shard_population or distributed
    if distributed and args.async_rounds:
        raise SystemExit("--async-rounds is single-host only (the async "
                         "engine's apply-order write-back has no "
                         "cross-process story yet); drop the flag")
    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    fed = build_fed(args)
    if shard_pop and fed.prefetch_backend == "process":
        # per-host feeding assembles global jax arrays in the builder; the
        # forked arena child must never touch the jax runtime
        fed = dataclasses.replace(fed, prefetch_backend="thread")
    pop_mesh = make_host_mesh() if shard_pop else None
    is_main = jax.process_index() == 0
    if is_main:
        print(f"arch={cfg.name} params={cfg.param_count():,} "
              f"algorithm={fed.algorithm} rounds={args.rounds}"
              + (f" processes={jax.process_count()}" if distributed else "")
              + (f" population_mesh={tuple(pop_mesh.shape.values())}"
                 if pop_mesh is not None else ""))

    data = SyntheticLMData(vocab_size=cfg.vocab_size,
                           num_clients=args.num_clients, seed=args.seed)
    s_text = args.seq_len - (cfg.frontend_tokens if cfg.frontend else 0)

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    server_opt = get_optimizer(fed.server_opt, fed.server_lr,
                               fed.server_momentum)
    alg = get_algorithm(fed)
    state = init_server_state(params, server_opt, algorithm=alg)
    # stateful algorithms (scaffold/fedep): per-client persistent state,
    # checkpointed alongside the server state. A burn regime may differ in
    # statefulness from the main regime (fedep burns in as stateless
    # fedavg) — same rule as FedSim/RoundEngine.
    burn_stateful = (alg.burn_algorithm().stateful
                     if alg.has_burn_regime and fed.burn_in_rounds
                     else alg.stateful)
    device_store = fed.client_state_placement == "device"
    if shard_pop and not device_store and (alg.stateful or burn_stateful):
        raise SystemExit("population sharding needs the device store for "
                         "stateful algorithms: add "
                         "--client-state-placement device")
    store = (make_client_store(fed.client_state_placement, args.num_clients,
                               mesh=pop_mesh if device_store else None)
             .ensure(alg.init_client_state(params))
             if alg.stateful or burn_stateful else None)
    # a sharded store never ships through the server checkpoint: each
    # host writes its own slice (checkpoint.save_store_sharded)
    sharded_store = store is not None and pop_mesh is not None

    def ckpt_tree(round_state):
        """Checkpoint pytree: bare server state, or {"server", "clients"}.

        ``store.state_dict()`` is the one place device-resident client
        state is pulled to the host."""
        if store is None or sharded_store:
            return round_state
        return {"server": round_state, "clients": store.state_dict()}

    state, start_round = restore_if_present(
        args, state, None if sharded_store else store, ckpt_tree)
    if sharded_store and start_round:
        restore_store_sharded(args.ckpt_dir, store, step=start_round)

    q_chunk = min(64, s_text)

    # faults + sampling + weights live in the cohort source; its draws key
    # off the ABSOLUTE round index, so a checkpoint restart replays the
    # same fault matrix
    round_batches = make_round_batches(args, cfg, fed, data, s_text)
    if pop_mesh is not None:
        # per-host cohort feeding: this process builds batches only for
        # the cohort rows its devices own; the global (C, ...) arrays are
        # assembled shard-locally — no batch bytes cross hosts
        lo, hi = local_row_range(pop_mesh, "data", fed.clients_per_round)
        base_batches = round_batches

        def round_batches(r, ids):  # noqa: F811 — sharded feeding wrapper
            local = base_batches(r, np.asarray(ids)[lo:hi])
            return globalize_cohort_batches(local, pop_mesh, "data",
                                            len(ids), lo)
    source = CohortSource(fed, args.num_clients,
                          lambda ids, r: round_batches(r, ids),
                          seed=args.seed)

    eval_fn = make_eval_fn(args, cfg, data, s_text, q_chunk)

    logf = open(args.log, "a") if args.log and is_main else None

    def emit(rec):
        if not is_main:
            return  # every process computes metrics; one reports
        print(json.dumps(rec), flush=True)
        if logf:
            logf.write(json.dumps(rec) + "\n")
            logf.flush()

    def maybe_checkpoint(round_state, r):
        if args.ckpt_dir and ((r + 1) % args.ckpt_every == 0
                              or r == args.rounds - 1):
            if is_main:
                save_checkpoint(args.ckpt_dir, ckpt_tree(round_state), r + 1,
                                {"arch": cfg.name,
                                 "algorithm": fed.algorithm})
            if sharded_store:
                # every process writes its own store slice
                save_store_sharded(args.ckpt_dir, store, r + 1,
                                   {"arch": cfg.name,
                                    "algorithm": fed.algorithm})

    state = run_rounds(args, cfg, fed, alg, state, store, burn_stateful,
                       start_round, source, eval_fn, emit, maybe_checkpoint,
                       q_chunk, pop_mesh=pop_mesh)
    if logf:
        logf.close()
    return state, store


def run_rounds(args, cfg, fed, alg, state, store, burn_stateful, start_round,
               source, eval_fn, emit, maybe_checkpoint, q_chunk,
               pop_mesh=None):
    """Drive the unified ``RoundEngine``; returns the final state.

    One loop for both modes: synchronous runs are the in-flight window of
    one (single-dispatch fused round — bitwise the historical sync loop);
    ``fed.async_rounds`` widens the window to ``max_staleness + 1`` so
    cohort t+1's client compute overlaps round t's server update, deltas
    discounted by ``staleness_discount**s``. The engine owns all jitting
    (including the device store's donation + pinned shardings); with
    ``pop_mesh`` the host-built operands are lifted to global arrays via
    ``lift_operand`` and the server state is made global up front."""
    if pop_mesh is not None:
        # every jit input must be a global array in a multi-process run;
        # after round one the server state is a round output and stays so
        state = replicate_global(state, pop_mesh)
    has_burn = alg.has_burn_regime and fed.burn_in_rounds > 0
    program = dict(placement="parallel", q_chunk=q_chunk,
                   compute_dtype=jnp.dtype(args.compute_dtype))
    cohort_fn, server_fn = make_fed_round_split(cfg, fed, **program)
    burn_cohort_fn = burn_server_fn = None
    if has_burn:
        burn_cohort_fn, burn_server_fn = make_fed_round_split(
            cfg, fed, use_sampling=False, **program)
    rb = round_bytes(fed, state.params)
    burn_rb = (round_bytes(fed, state.params, use_sampling=False)
               if has_burn else rb)
    engine = RoundEngine(
        cohort_fn=cohort_fn,
        server_fn=server_fn,
        round_fn=make_fed_round(cfg, fed, **program),
        burn_cohort_fn=burn_cohort_fn,
        burn_server_fn=burn_server_fn,
        burn_round_fn=(make_fed_round(cfg, fed, use_sampling=False,
                                      **program)
                       if has_burn else None),
        burn_in_rounds=max(0, fed.burn_in_rounds - start_round),
        max_staleness=fed.max_staleness if fed.async_rounds else 0,
        staleness_discount=fed.staleness_discount,
        # straggler lateness needs the apply-time discount exponent, which
        # only the split pipeline traces
        pipeline_only=fed.straggler_rate > 0,
        prefetch_rounds=fed.prefetch_rounds,
        prefetch_backend=fed.prefetch_backend,
        client_store=store,
        stateful=alg.stateful,
        burn_stateful=burn_stateful,
        record_faults=fed.fault_injection,
        round_bytes=rb,
        burn_round_bytes=burn_rb,
        lift_operand=(None if pop_mesh is None
                      else lambda x: replicate_global(x, pop_mesh)),
    )

    def build_cohort(i):
        # the engine orders by its own 0-based index; the draw (and its
        # faults) stays keyed to the absolute round
        return source.cohort(start_round + i)._replace(round_idx=i)

    last_t = time.time()

    def on_round(rec, round_state):
        # live per-round logging + periodic checkpoints; forcing the
        # metrics here costs one sync per round, but (async) the next
        # cohorts are already dispatched on device
        nonlocal last_t
        r = start_round + rec["round"]
        out = {"round": r,
               "eval_loss": (float(rec["eval"]["eval_loss"])
                             if "eval" in rec else None),
               "client_loss_last": float(rec["metrics"]["loss_last"]),
               "client_loss_first": float(rec["metrics"]["loss_first"]),
               "staleness": rec["staleness"],
               "phase": phase_name(fed, r),
               "sec": round(time.time() - last_t, 2)}
        for k in ("dropped", "straggled", "bytes_up", "bytes_down"):
            out[k] = rec[k]
        emit(out)
        last_t = time.time()
        maybe_checkpoint(round_state, r)

    def evaluate(params):
        loss = eval_fn(params)
        with span("sync"):   # the host waits for the device here
            return {"eval_loss": float(loss)}

    state, _ = engine.run(
        state, build_cohort, args.rounds - start_round,
        eval_fn=evaluate, on_round=on_round)
    return state


if __name__ == "__main__":
    main()
