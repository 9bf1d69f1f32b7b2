#!/usr/bin/env python3
"""Smoke run of the federated training path on a TPU, at full width.

Drives ``repro.launch.train.main`` in this one process (the chip belongs to
one process at a time) at fedlm-100m's published widths, with random
weights from ``--seed``:

* reference: the loss of the initial weights on one batch, compiled for the
  chip, against the same loss in fp32 on the host CPU;
* phase A, FedPA: one burn-in (FedAvg-regime) round, then three FedPA
  rounds. 5 clients x 8 local steps x batch 4 x seq 128 is the largest
  cohort whose FedPA round fits one v5e. The cohorts come from the default
  process prefetcher, and the run saves a checkpoint at its end;
* phase B, SCAFFOLD with the client-state store on the device, donated to
  every round, with the same cohort;
* kernels: the Pallas kernels, compiled for the chip, against
  ``kernels/ref.py`` on the chip (``dp_step`` up to fedlm-100m's width,
  ``swa_decode`` at gemma3-27b's window widths), and the serving decode
  through the compiled kernel against the jnp decode.

``--chips 4`` runs only the population-sharded path: SCAFFOLD with the
device store sharded over four chips (``--shard-population``), 4 clients,
against the same rounds on one chip; once in bf16 at client lr 0.01, once
in fp32 with full-precision matmuls at the default client lr 0.05, where
only round 0 is held to rounding.

Every check is fatal. Only a run whose checks all passed prints its last
line, one JSON object: ``{"ok": true, "device": {...}}``.

  python3 chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import multiprocessing
import shutil
import sys
import time
import warnings
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.data import SyntheticLMData  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import (init_decode_state, init_params, lm_loss,  # noqa: E402
                          serve_step)

#: Scratch space for logs and checkpoints (``runs/`` is git-ignored).
RUN_DIR = ROOT / "runs" / "chip_smoke"
ARCH = "fedlm-100m"
#: fedlm-100m at full width; the cohort shape every phase shares
MODEL_FLAGS = ["--arch", ARCH, "--local-steps", "8", "--batch", "4",
               "--seq-len", "128"]
#: One v5e's largest FedPA cohort: the compiler puts the round's peak at
#: 15.2 GB for 5 clients and refuses 6 (16.1 GB of 15.75 GiB)
CLIENTS = 5
COHORT = f"cohort=(C={CLIENTS}, K=8, B=4, S=128)"
SCAFFOLD_FLAGS = ["--algorithm", "scaffold", "--client-opt", "sgd",
                  "--client-state-placement", "device", "--burn-in-rounds",
                  "0", "--num-clients", "8"]
#: fedpa_dp widths: one that needs padding, and flattened fedlm-100m
DP_WIDTHS = (1300, 100_684_032)
#: requests per swa_decode call at gemma3-27b's sliding-window widths
SWA_BATCH = 8

#: backend compile and cache-read seconds, and cache hits, since the last reset
COMPILE = defaultdict(float)


def _on_duration(event: str, secs: float, **_) -> None:
    if event in ("/jax/core/compile/backend_compile_duration",
                 "/jax/compilation_cache/cache_retrieval_time_sec"):
        COMPILE["compile_s"] += secs


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        COMPILE["cache_hits"] += 1


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def peak_bytes(dev) -> int:
    return dev.memory_stats()["peak_bytes_in_use"]


def run_train(name: str, argv, kind: str):
    """One ``train.main`` run; returns ``(state, store, records)``.

    Fatal: a non-finite loss, a prefetcher that falls back to the thread
    backend or leaves its child behind, and a donated buffer the compiler
    could not use."""
    log = RUN_DIR / f"{name}.jsonl"
    log.unlink(missing_ok=True)
    COMPILE.clear()
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*donated buffers")
        warnings.filterwarnings("error", message="prefetch_backend='process'")
        state, store = train.main(argv + ["--log", str(log)])
    jax.block_until_ready((state, None if store is None
                           else store.device_state()))
    wall = time.perf_counter() - t0
    if multiprocessing.active_children():
        fail(f"{name}: prefetcher child still running after the run")
    recs = [json.loads(ln) for ln in log.read_text().splitlines()]
    losses = [r[k] for r in recs
              for k in ("eval_loss", "client_loss_first", "client_loss_last")]
    if not all(isinstance(v, float) and math.isfinite(v) for v in losses):
        fail(f"{name}: non-finite loss in {recs}")
    # the first round of each regime compiles; the rest are steady
    steady = [r["sec"] for prev, r in zip(recs, recs[1:])
              if r["phase"] == prev["phase"]]
    print(f"{name}: {kind} rounds={len(recs)} wall_s={wall} "
          f"compile_s={COMPILE['compile_s']} "
          f"cache_hits={int(COMPILE['cache_hits'])} "
          f"steady_round_s={steady}", flush=True)
    return state, store, recs


def check_reference(dev, seed: int) -> None:
    """The chip's bf16 loss of the initial weights on one batch against the
    host CPU's fp32 loss of the same weights and batch."""
    cfg = configs.get_config(ARCH)
    data = SyntheticLMData(vocab_size=cfg.vocab_size, num_clients=1,
                           seed=seed)
    batch = {"tokens": data.client_batches(0, 1, 4, 128, host=True)[0]}
    params = jax.jit(lambda: init_params(jax.random.PRNGKey(seed), cfg))()
    chip = float(jax.jit(lambda p, b: lm_loss(p, b, cfg, q_chunk=64)[0])(
        params, batch))
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        host = float(jax.jit(lambda p, b: lm_loss(
            p, b, cfg, compute_dtype=jnp.float32, q_chunk=64)[0])(
                jax.device_put(params, cpu), batch))
    del params
    print(f"reference: loss {kind_of(dev)} bf16={chip} cpu fp32={host} "
          f"abs_diff={abs(chip - host)}", flush=True)
    # bf16 activations against fp32: a few hundredths of a nat at ln(V)~10
    if not (math.isfinite(chip) and abs(chip - host) < 0.05):
        fail(f"initial loss {chip} on the chip vs {host} on the CPU")


def kind_of(dev) -> str:
    return f"[{dev.device_kind}]"


def one_chip(dev, seed: int) -> None:
    """Phases A and B on the first chip."""
    check_reference(dev, seed)
    seed_flags = ["--seed", str(seed)]

    ckpt = RUN_DIR / "ckpt_fedpa"
    shutil.rmtree(ckpt, ignore_errors=True)
    _, _, recs = run_train(
        "phaseA", MODEL_FLAGS + seed_flags + [
            "--algorithm", "fedpa", "--clients", str(CLIENTS),
            "--burn-in-rounds", "1",
            "--rounds", "4", "--ckpt-dir", str(ckpt)],
        f"fedpa {kind_of(dev)} {COHORT}")
    phases = [r["phase"] for r in recs]
    if phases[0] != "fedavg (burn-in)" or set(phases[1:]) != {"fedpa"}:
        fail(f"phase A: expected one burn-in round then fedpa, got {phases}")
    if not (ckpt / "ckpt_00000004.npz").is_file():
        fail(f"phase A: no checkpoint in {ckpt}")
    shutil.rmtree(ckpt)
    gc.collect()
    print(f"phaseA: peak_bytes_in_use {kind_of(dev)} = {peak_bytes(dev)}",
          flush=True)

    _, store, _ = run_train(
        "phaseB", MODEL_FLAGS + seed_flags + SCAFFOLD_FLAGS + [
            "--clients", str(CLIENTS), "--rounds", "3"],
        f"scaffold device store (donated) {kind_of(dev)} {COHORT}")
    store_bytes = sum(x.nbytes for x in
                      jax.tree_util.tree_leaves(store.device_state()))
    print(f"phaseB: store_bytes={store_bytes} "
          f"peak_bytes_in_use {kind_of(dev)} = {peak_bytes(dev)}", flush=True)
    del store
    gc.collect()
    check_kernels(dev, seed)


def _rel(got, want) -> float:
    """||got - want|| / ||want||, on the device."""
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def check_kernels(dev, seed: int, pallas: str = "compiled") -> None:
    """The Pallas kernels against ``kernels/ref.py`` run by XLA on the same
    device with fp32 matmuls at full precision, then the serving decode
    through the kernel against the jnp decode. ``pallas="interpret"`` runs
    the kernels in the Pallas interpreter (a CPU rehearsal)."""
    interpret = pallas == "interpret"
    kind = kind_of(dev)
    highest = jax.default_matmul_precision("highest")
    key = jax.random.PRNGKey(seed)
    errs = {}

    lp, t, rho = 4, 3, 0.7
    dp_ref = jax.jit(ref.dp_step_ref, static_argnames="rho")
    for d in DP_WIDTHS:
        ku, kd, kv, kc = jax.random.split(jax.random.fold_in(key, d), 4)
        u = jax.random.normal(ku, (d,))
        delta = jax.random.normal(kd, (d,))
        V = jax.random.normal(kv, (lp, d))
        # combine coefficients scale as 1 / <u, v> ~ 1 / d in a real run
        c_hist = jnp.abs(jax.random.normal(kc, (lp,))) / d
        got = ops.dp_step(u, delta, V, c_hist, t, rho=rho,
                          interpret=interpret)
        with highest:
            want = dp_ref(u, delta, V, c_hist, t, rho=rho)
        rel = [_rel(g, w) for g, w in zip(got, want)]
        print(f"kernels: dp_step {kind} d={d} lp={lp} rel_err "
              f"v={rel[0]} delta={rel[1]} a={rel[2]} c={rel[3]}", flush=True)
        errs[f"dp_step d={d}"] = (max(rel), 1e-4)
        del u, delta, V, got, want

    cfg = configs.get_config("gemma3-27b")
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W, B = cfg.pattern[0].window, SWA_BATCH
    swa_ref = jax.jit(ref.swa_decode_ref, static_argnames="window")
    # a linear cache (no window, then a window inside it) and the
    # wrapped ring buffer of a sliding-window layer
    for layout, L, window in (("linear", 2 * W, 0), ("linear", 2 * W, 300),
                              ("ring", W, W)):
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, L + window), 3)
        q = jax.random.normal(kq, (B, H, dh), jnp.bfloat16)
        k = jax.random.normal(kk, (B, L, KV, dh), jnp.bfloat16)
        v = jax.random.normal(kv, (B, L, KV, dh), jnp.bfloat16)
        slots = np.arange(L)
        if layout == "linear":
            pos = L - 50
            slot_pos = np.where(slots <= pos, slots, -1)
        else:
            pos = L + L // 2 + 100
            lap = pos // L
            slot_pos = np.where(slots <= pos % L, lap * L + slots,
                                (lap - 1) * L + slots)
        sp = jnp.asarray(slot_pos, jnp.int32)
        got = ops.swa_decode(q, k, v, sp, pos, window=window,
                             interpret=interpret)
        with highest:
            want = swa_ref(q.reshape(B, KV, H // KV, dh), k, v, sp, pos,
                           window=window).reshape(B, H, dh)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        print(f"kernels: swa_decode {kind} {layout} B={B} H={H} KV={KV} "
              f"dh={dh} L={L} window={window} bf16 max_abs_err={err}",
              flush=True)
        # as the interpret-mode tests: bf16 in, bf16 out
        errs[f"swa_decode {layout} window={window}"] = (err, 2e-2)

    scfg = configs.get_smoke("gemma3-27b")
    params = init_params(jax.random.fold_in(key, 1), scfg)
    Bs, steps = 2, 2 * scfg.pattern[0].window + 8    # the ring wraps twice
    toks = jax.random.randint(jax.random.fold_in(key, 2), (Bs, steps), 0,
                              scfg.vocab_size)
    logits = {}
    with highest:
        for mode in (None, pallas):
            step = jax.jit(lambda p, tok, s, mode=mode: serve_step(
                p, tok, s, scfg, compute_dtype=jnp.float32, pallas=mode))
            state = init_decode_state(scfg, Bs, max_len=128,
                                      cache_dtype=jnp.float32)
            out = []
            for i in range(steps):
                _, lg, state = step(params, toks[:, i], state)
                out.append(lg)
            logits[mode] = jnp.stack(out)
    err = float(jnp.max(jnp.abs(logits[pallas] - logits[None]))
                / jnp.max(jnp.abs(logits[None])))
    print(f"kernels: serve_step pallas={pallas} vs jnp {kind} "
          f"gemma3-27b smoke, {steps} decode steps, fp32: "
          f"max_abs_err/max_abs_logit={err}", flush=True)
    errs["serve_step"] = (err, 1e-2)
    bad = {k: v for k, v in errs.items() if not v[0] <= v[1]}
    if bad:
        fail(f"kernels off their reference (error, bound): {bad}")


def _rel_dev(got, want, base=None) -> float:
    """||got - want|| / ||want - base|| over every leaf, in float64."""
    num = den = 0.0
    base = base or [0.0] * len(want)
    for g, w, b in zip(got, want, base):
        w = np.asarray(w, np.float64)
        num += float(np.sum((np.asarray(g, np.float64) - w) ** 2))
        den += float(np.sum((w - np.asarray(b, np.float64)) ** 2))
    return math.sqrt(num / den)


def four_chips(devices, seed: int) -> None:
    """Population-sharded SCAFFOLD on four chips against one chip, twice."""
    flags = MODEL_FLAGS + SCAFFOLD_FLAGS + [
        "--clients", "4", "--rounds", "3", "--seed", str(seed)]
    # bf16, as users train. At the default client lr 0.05 the local steps
    # amplify bf16 rounding (the two programs' matmuls differ in shape)
    # into 9% loss gaps; at 0.01 the comparison is about the placement
    failures = compare_sharded(
        devices, seed, "bf16", flags + ["--client-lr", "0.01"],
        bounds=dict(first=1e-3, loss=2e-2, params=0.1, store=0.1))
    # fp32 activations and full-precision matmuls at the default lr: only
    # the order of sums differs. Round 0 (cohort, aggregation, server
    # step) then agrees to rounding; from round 1 the local steps are
    # unstable at this lr and amplify that rounding as they amplify a
    # one-chip change of matmul precision, so only round 0 is bounded
    with jax.default_matmul_precision("highest"):
        failures += compare_sharded(
            devices, seed, "fp32", flags + ["--compute-dtype", "float32"],
            bounds=dict(first=1e-5, round0=1e-5))
    if failures:
        fail("; ".join(failures))


def compare_sharded(devices, seed: int, tag: str, flags, bounds) -> list:
    """One ``train.main`` run on the first chip, then the same rounds with
    the population sharded over ``devices``. Returns what failed: a store
    not split four ways, a deviation past ``bounds``, other stamps."""
    kind = kind_of(devices[0])
    cfg = configs.get_config(ARCH)
    init = jax.tree_util.tree_leaves(jax.device_get(jax.jit(
        lambda: init_params(jax.random.PRNGKey(seed), cfg))()))
    state, store, ref_recs = run_train(f"ref1chip_{tag}", flags,
                                       f"scaffold on one chip {tag} {kind}")
    ref_params = jax.tree_util.tree_leaves(jax.device_get(state.params))
    ref_store = store.state_dict()
    del state, store
    gc.collect()
    print(f"ref1chip_{tag}: peak_bytes_in_use chip0 {kind} = "
          f"{peak_bytes(devices[0])}", flush=True)

    name = f"sharded4_{tag}"
    state, store, recs = run_train(name, flags + ["--shard-population"],
                                   f"scaffold sharded over 4 chips {tag} "
                                   f"{kind}")
    per_chip = defaultdict(int)
    total = 0
    for leaf in jax.tree_util.tree_leaves(store.device_state()):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_chip[shard.device.id] += shard.data.nbytes
    share = max(per_chip.values()) / total
    params = jax.tree_util.tree_leaves(jax.device_get(state.params))
    got_store = store.state_dict()
    bufs = [jax.tree_util.tree_leaves(s["buffers"])
            for s in (got_store, ref_store)]
    max_abs = [max(float(np.max(np.abs(np.asarray(g, np.float32)
                                       - np.asarray(w, np.float32))))
                   for g, w in zip(got, want))
               for got, want in ((params, ref_params), bufs)]
    # deviation relative to what training changed: the params' update
    # from their initial values, the store's state from zero
    d_params = _rel_dev(params, ref_params, init)
    d_store = _rel_dev(*bufs)
    d_losses = [max(abs(r[k] - q[k]) / abs(q[k])
                    for k in ("eval_loss", "client_loss_first",
                              "client_loss_last"))
                for r, q in zip(recs, ref_recs)]
    d_loss, d_round0 = max(d_losses), d_losses[0]
    # the first local step of round 0: same weights, same batches, no update
    d_first = (abs(recs[0]["client_loss_first"]
                   - ref_recs[0]["client_loss_first"])
               / ref_recs[0]["client_loss_first"])
    same_stamps = np.array_equal(ref_store["stamps"], got_store["stamps"])
    print(f"{name}: deviation from one chip: params max_abs={max_abs[0]} "
          f"rel_to_update={d_params}; store max_abs={max_abs[1]} "
          f"rel={d_store}; losses max_rel={d_loss} "
          f"per_round={d_losses} round0_first_loss_rel={d_first}; "
          f"stamps_equal={same_stamps}", flush=True)
    print(f"{name}: store_bytes total={total} per_chip={dict(per_chip)} "
          f"max_share={share}", flush=True)
    print(f"{name}: peak_bytes_in_use "
          + " ".join(f"chip{d.id}={peak_bytes(d)}" for d in devices),
          flush=True)
    del state, store
    gc.collect()
    failures = []
    if len(per_chip) != 4 or share > 0.26:
        failures.append(f"{name}: store not split over 4 chips: "
                        f"{dict(per_chip)} of {total}")
    # the same rounds: matmul shapes and the cross-chip reduction order
    # make them differ a little, a wrong client far more
    got = dict(first=d_first, round0=d_round0, loss=d_loss, params=d_params,
               store=d_store)
    over = {k: (got[k], bounds[k]) for k in bounds if not got[k] < bounds[k]}
    if over or not same_stamps:
        failures.append(f"{name}: diverged from the one-chip reference "
                        f"(deviation, bound): {over}, stamps_equal="
                        f"{same_stamps}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the population-sharded phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)}")
    print(f"jax {jax.__version__} device_kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    print(f"{ARCH}: params={configs.get_config(ARCH).param_count():,}",
          flush=True)
    RUN_DIR.mkdir(parents=True, exist_ok=True)
    if args.chips == 4:
        four_chips(devices[:4], args.seed)
    else:
        one_chip(dev, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
