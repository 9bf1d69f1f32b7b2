"""One run of one cell: set up, measure a window of rounds, check, report.

The window drives the program's own training path:
``launch.train.run_rounds`` -> ``core.engine.RoundEngine.run`` -> the fused
round of ``core.sharded_round.make_fed_round``, with the cell's cohort
prefetcher, an eval every round and ``launch.train``'s per-round metrics
sync. The arguments are built with ``launch.train``'s own helpers, so the
program's defaults are what is measured.

Set-up runs the first rounds through that same call: the reference checks
rounds 0-2 (the FedAvg-regime burn-in round and the first FedPA rounds of
a FedPA mix), and the window opens at the boundary after the mix's
``warmup_rounds``. It closes at the first round boundary at or after
``--seconds``, so it holds whole rounds of the measured regime only.
"""
from __future__ import annotations

import gc
import math
import multiprocessing
import shutil
import statistics
import sys
import time
from pathlib import Path

import jax
import numpy as np

from bench import check, spec, yardstick
from bench import trace as tr
from bench.reference import fed as ref_fed

#: The fused round's jitted module: ``jax.jit`` of the program's ``round_fn``.
ROUND_MODULE = "jit_round_fn"
#: Where the traced run writes its trace, inside the checkout.
TRACE_DIR = ".bench_trace"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class _WindowClosed(Exception):
    """Raised at the round boundary that closes the window."""


class _Compiles:
    """Backend compiles and compile-cache reads, counted process-wide; and
    the persistent cache's hits and misses."""

    def __init__(self):
        self.count = 0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.count += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


_COMPILES = None


def compiles() -> _Compiles:
    """The process's compile counter (listeners cannot be removed, so one
    counter is registered once)."""
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = _Compiles()
    return _COMPILES


def _host(tree) -> dict:
    """{leaf path: host copy} of a device tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def _norms(tree: dict) -> dict:
    """{leaf path: L2 norm}, summed in float64."""
    return {k: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
            for k, v in tree.items()}


def _layer_norms(tree: dict) -> dict:
    """{layer leaf path: L2 norm} (``check.layer_rows``), in float64."""
    out = {}
    for k, v in tree.items():
        sq = np.square(v, dtype=np.float64)
        out.update(check.layer_rows(k, np.sqrt(
            np.sum(sq, axis=tuple(range(1, v.ndim)))
            if k.startswith(check.STACKED) else np.sum(sq))))
    return out


class _Rounds:
    """The callbacks ``run_rounds`` calls at every round boundary."""

    def __init__(self, warmup: int, seconds: float, trace_seconds: float,
                 trace_dir):
        self.warmup, self.seconds = warmup, seconds
        self.trace_seconds, self.trace_dir = trace_seconds, trace_dir
        self.times, self.records = [], []
        self.moments, self.params = [], None   # host snapshots, rounds 0-2
        self.t_open = None
        self.compiles_open = None
        self.tracing = False
        self._round_span = None
        self._boundary_span = None

    def emit(self, rec: dict) -> None:
        """``launch.train``'s per-round record, after its metrics sync."""
        self.times.append(time.perf_counter())
        self.records.append(rec)
        self._close_span("_round_span")
        if self.tracing:
            self._boundary_span = self._open_span("bench.boundary")

    def after_round(self, state, r: int) -> None:
        """``launch.train``'s checkpoint hook: snapshots, window and trace."""
        if r < check.ROUNDS:
            self.moments.append(_host(state.opt_state["m"]))
            if r == check.ROUNDS - 1:
                self.params = _host(state.params)
        now = self.times[-1]
        if r == self.warmup - 1:
            self.t_open = now
            self.compiles_open = compiles().count
            if self.trace_dir is not None:
                jax.profiler.start_trace(str(self.trace_dir))
                self.tracing = True
        elif self.tracing and now - self.t_open >= self.trace_seconds:
            self._stop_trace()
        if self.t_open is not None and r >= self.warmup \
                and now - self.t_open >= self.seconds:
            self._stop_trace()
            raise _WindowClosed
        self._close_span("_boundary_span")
        if self.tracing:
            self._round_span = self._open_span("bench.round")

    def _stop_trace(self):
        if self.tracing:
            self._close_span("_boundary_span")
            jax.profiler.stop_trace()
            self.tracing = False

    def eval_fn(self, fn):
        """Wrap the eval program so its dispatch shows as a span."""
        def traced(p):
            if not self.tracing:
                return fn(p)
            with jax.profiler.TraceAnnotation("bench.eval"):
                return fn(p)
        return traced

    @staticmethod
    def _open_span(name):
        span = jax.profiler.TraceAnnotation(name)
        span.__enter__()
        return span

    def _close_span(self, attr):
        span = getattr(self, attr)
        if span is not None:
            span.__exit__(None, None, None)
            setattr(self, attr, None)

    def window(self):
        """Round wall times in the window and its length (host clock)."""
        i = self.warmup - 1
        times = self.times[i:]
        return np.diff(times).tolist(), times[-1] - times[0]


def _program(cell: spec.Cell, seed: int):
    """Build what ``launch.train.main`` builds for a one-host run."""
    from repro import configs  # noqa: PLC0415
    from repro.algorithms import get_algorithm  # noqa: PLC0415
    from repro.core.client_state import make_client_store  # noqa: PLC0415
    from repro.core.server import init_server_state  # noqa: PLC0415
    from repro.data import SyntheticLMData  # noqa: PLC0415
    from repro.data.cohort_source import CohortSource  # noqa: PLC0415
    from repro.launch import train  # noqa: PLC0415
    from repro.models import init_params  # noqa: PLC0415
    from repro.optim import get_optimizer  # noqa: PLC0415

    args = train.parse_args(spec.train_argv(cell, seed))
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    spec.check_config(cell.config, cfg)
    fed = train.build_fed(args)
    data = SyntheticLMData(vocab_size=cfg.vocab_size,
                           num_clients=args.num_clients, seed=args.seed)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    server_opt = get_optimizer(fed.server_opt, fed.server_lr,
                               fed.server_momentum)
    alg = get_algorithm(fed)
    state = init_server_state(params, server_opt, algorithm=alg)
    burn_stateful = (alg.burn_algorithm().stateful
                     if alg.has_burn_regime and fed.burn_in_rounds
                     else alg.stateful)
    store = (make_client_store(fed.client_state_placement, args.num_clients)
             .ensure(alg.init_client_state(params))
             if alg.stateful or burn_stateful else None)
    q_chunk = _q_chunk(args)
    round_batches = train.make_round_batches(args, cfg, fed, data,
                                             args.seq_len)
    source = CohortSource(fed, args.num_clients,
                          lambda ids, r: round_batches(r, ids),
                          seed=args.seed)
    eval_fn = train.make_eval_fn(args, cfg, data, args.seq_len, q_chunk)
    run_args = (args, cfg, fed, alg, state, store, burn_stateful, 0, source)
    return run_args, eval_fn, q_chunk, train.run_rounds


def _check_devices(cell: spec.Cell, require_chip: bool):
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if cell.chips != 1:
        raise NotImplementedError("only one-chip cells are built")
    return devices[:cell.chips]


def drive(cell: spec.Cell, seed: int, seconds: float, trace_dir=None,
          warmup=None):
    """Run the program until the window closes at a round boundary.

    Returns ``(rounds, run_args, source)``: the boundary record and the
    program's inputs (its initial state among them)."""
    traffic = cell.traffic
    rounds = _Rounds(warmup or traffic["warmup_rounds"], seconds,
                     traffic["trace_seconds"], trace_dir)
    run_args, eval_fn, q_chunk, run_rounds = _program(cell, seed)
    try:
        run_rounds(*run_args, rounds.eval_fn(eval_fn), rounds.emit,
                   rounds.after_round, q_chunk)
        raise RuntimeError("the run ended before its window closed")
    except _WindowClosed:
        pass
    c = compiles()
    print(f"set-up: persistent compile cache {c.hits} hits, {c.misses} "
          f"misses", file=sys.stderr, flush=True)
    if c.count != rounds.compiles_open:
        raise RuntimeError(f"{c.count - rounds.compiles_open} "
                           f"program(s) compiled inside the window")
    _reap_children()
    return rounds, run_args, run_args[-1]


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True):
    """One run; returns ``(result line dict, check table)``."""
    cell = spec.load_cell(root, workload)
    devices = _check_devices(cell, require_chip)
    compiles()
    traffic = cell.traffic
    trace_dir = root / TRACE_DIR / workload if trace else None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    rounds, run_args, source = drive(cell, seed, seconds, trace_dir)
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    program = round_program_memory(run_args, source)
    print(f"memory: peak_bytes_in_use {in_use}; round program "
          + " ".join(f"{k} {v}" for k, v in program.items()),
          file=sys.stderr, flush=True)
    # the allocator's peak leaves out the program's temp, which the
    # compiler's peak of the round program holds
    memory_peak = max(in_use, program["peak_memory_in_bytes"])
    walls, window = rounds.window()
    print(f"window: {len(walls)} rounds in {window!r} s; round walls (s): "
          + " ".join(f"{w:.4f}" for w in walls), file=sys.stderr, flush=True)
    failed = sum(not all(math.isfinite(rec[k]) for k in
                         ("client_loss_first", "client_loss_last",
                          "eval_loss"))
                 for rec in rounds.records[rounds.warmup:])
    builds = (_cohort_builds(source, len(rounds.records) + 8,
                             traffic["cohort_builds"]) if trace else None)
    prog = program_readings(rounds, run_args)
    del run_args, source
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed)
    print(f"reference: {time.perf_counter() - t_ref!r} s", file=sys.stderr,
          flush=True)
    correct, table = check.judge(check.numbers(prog, ref), cell.limits)
    dev = devices[0]
    result = {"correct": correct, "attempted": len(walls), "failed": failed,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(memory_peak)}}
    if trace:
        _per_layer(result, cell, trace_dir, builds)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = _end_to_end(cell, walls, window,
                                        rounds.t_open - t_start)
    result["checks"] = table
    return result, table


def round_program_memory(run_args, source) -> dict:
    """The compiler's memory figures of the window's round program (bytes).

    The program is built and lowered again as ``run_rounds`` builds it,
    from the initial state and a cohort of the same shapes; its executable
    comes from the compile cache. Called after the window."""
    from repro.core.sharded_round import make_fed_round  # noqa: PLC0415

    args, cfg, fed, state = run_args[0], run_args[1], run_args[2], \
        run_args[4]
    dtype = jax.numpy.dtype(args.compute_dtype)
    round_fn = make_fed_round(cfg, fed, placement="parallel",
                              q_chunk=_q_chunk(args), compute_dtype=dtype)
    cohort = source.cohort(0)
    mem = jax.jit(round_fn).lower(state, cohort.batches, cohort.weights,
                                  cohort.survivors).compile() \
        .memory_analysis()
    return {k: int(getattr(mem, k)) for k in
            ("peak_memory_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "temp_size_in_bytes",
             "alias_size_in_bytes")}


def _q_chunk(args) -> int:
    """Attention's query chunk, as ``launch.train.main`` sets it."""
    return min(64, args.seq_len)


def reference(cell: spec.Cell, rnd=None) -> ref_fed.Reference:
    """The cell's reference (``rnd``: a control's rounding)."""
    kw = {} if rnd is None else {"rnd": rnd}
    return ref_fed.Reference(spec.reference_model(cell.root, cell.config),
                             cell.config["model"], ref_fed.hyper(cell.traffic),
                             **kw)


def reference_readings(cell: spec.Cell, seed: int, rnd=None) -> dict:
    """The reference's first rounds from ``seed``."""
    return reference(cell, rnd).run(seed, check.ROUNDS)


def _reap_children(timeout: float = 30.0) -> None:
    """Wait for every child process this run started (the prefetcher)."""
    deadline = time.monotonic() + timeout
    for child in multiprocessing.active_children():
        child.join(max(0.0, deadline - time.monotonic()))
    left = multiprocessing.active_children()
    if left:
        raise RuntimeError(f"child processes still running: {left}")


def _cohort_builds(source, first: int, n: int) -> list:
    """Host seconds of ``n`` inline cohort builds of rounds not yet used."""
    out = []
    for r in range(first, first + n):
        t = time.perf_counter()
        source.cohort(r)
        out.append(time.perf_counter() - t)
    return out


def program_readings(rounds: _Rounds, run_args) -> dict:
    """The program's side of the check, in ``reference.fed.run``'s shape."""
    fed = run_args[2]
    if fed.server_opt != "sgdm":
        raise NotImplementedError("the server gradient is read from sgdm's "
                                  "momentum")
    recs = rounds.records[:check.ROUNDS]
    beta = fed.server_momentum
    grads, prev = [], None
    for m in rounds.moments:
        grads.append(m if prev is None else
                     {k: m[k] - beta * prev[k] for k in m})
        prev = m
    p0 = _host(run_args[4].params)
    change = {k: rounds.params[k] - p0[k] for k in p0}
    return {"loss_first": [r["client_loss_first"] for r in recs],
            "loss_last": [r["client_loss_last"] for r in recs],
            "eval_loss": [r["eval_loss"] for r in recs],
            "grad_norms": [_norms(g) for g in grads],
            "change_norms": _norms(change),
            "change_layer_norms": _layer_norms(change)}


def _end_to_end(cell: spec.Cell, walls, window: float, setup: float) -> dict:
    values = {"round_s": window / len(walls),
              "round_p90_s": yardstick.p90(walls),
              "setup_s": setup}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def _per_layer(result: dict, cell: spec.Cell, trace_dir: Path,
               builds: list) -> None:
    """Reduce the trace; fill the per-layer metrics, busy/window, breakdown."""
    t = tr.load(trace_dir)
    spans = [s for s in t.spans if s[0] == "bench.round"]
    if not spans or not t.ops:
        raise RuntimeError("the trace holds no round or no device operation")
    lo, hi = min(s[1] for s in spans), max(s[2] for s in spans)
    f = cell.traffic["flags"]
    tokens = yardstick.tokens_per_round(int(f["clients"]),
                                        int(f["local-steps"]),
                                        int(f["batch"]), int(f["seq-len"]))
    kind = result["device"]["kind"]
    ctx = {"trace": t, "lo": lo, "hi": hi, "rounds": len(spans),
           "chips": cell.chips, "peak": yardstick.peak(kind),
           "flops_per_round": yardstick.model_flops(cell.config["params"],
                                                    tokens),
           "round_module": ROUND_MODULE, "cohort_build_s": builds}
    for m in cell.per_layer:
        value = spec.metric_reader(cell.root, m["name"])(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    planes = sorted(t.ops)
    busy = [tr.busy_ns(t.ops[p], lo, hi) for p in planes]
    result["device"]["busy_s"] = statistics.mean(busy) / 1e9
    result["device"]["window_s"] = (hi - lo) / 1e9
    by_op = {}
    for p in planes:
        for name, ns in tr.time_by_name(t.ops[p], lo, hi).items():
            by_op[name] = by_op.get(name, 0.0) + ns / len(planes) / 1e9
    gaps = sorted(tr.idle_gaps(t.ops[planes[0]], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    result["breakdown"] = {
        "device_ops": sorted(([n, s] for n, s in by_op.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": [[tr.label(g, t.spans), (g[1] - g[0]) / 1e9]
                      for g in gaps]}
