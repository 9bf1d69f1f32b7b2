"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against the production mesh and extract the roofline terms.

The programs lowered here are exactly what ``core.engine.RoundEngine``
dispatches at runtime — the fused round (``make_fed_round``) on the
window=1 path — so a config that compiles in the dry-run runs in the
unified loop.

The ``os.environ`` statements below MUST stay ahead of every other import:
jax locks the platform and device count on first initialization, and the
dry-run needs 512 placeholder host devices for ``jax.make_mesh`` to build
the production meshes. Tests override via REPRO_XLA_FLAGS.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --out dryrun.jsonl
"""
import os

# placeholder host devices only: pinned to the CPU so the dry-run never
# claims (or builds its mesh from) a real accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs                                     # noqa: E402
from repro.algorithms import algorithm_names, phase_name      # noqa: E402
from repro.configs.base import SHAPES, FedConfig              # noqa: E402
from repro.core.sharded_round import (default_placement,      # noqa: E402
                                      make_fed_round)
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.launch.specs import (client_axes, input_specs,     # noqa: E402
                                store_population_layout)
from repro.models.steps import prefill_step, serve_step       # noqa: E402
from repro.sharding import axis_rules                         # noqa: E402
from repro.sharding.hlo_cost import analyze as hlo_analyze   # noqa: E402
from repro.sharding.roofline import derive, format_table      # noqa: E402


def default_fed_config(algorithm: str = "fedpa") -> FedConfig:
    """Dry-run federated config: K=8 local steps, l=2 IASG samples."""
    return FedConfig(
        algorithm=algorithm, local_steps=8, burn_in_steps=4,
        steps_per_sample=2, shrinkage_rho=0.1,
        server_opt="sgdm", server_lr=0.5, client_opt="sgd", client_lr=0.01,
    )


def should_skip(cfg, shape) -> str:
    """long_500k needs sub-quadratic decode (DESIGN.md §Arch-applicability)."""
    if shape.name == "long_500k" and not cfg.supports_long_decode:
        return ("skip: pure full-attention arch — long_500k decode cache "
                "is unbounded (documented in DESIGN.md)")
    return ""


def _apply_knobs(cfg, fed, rec, *, delta_dtype, client_state_placement,
                 dropout_rate, moe_chunk, moe_routing, cache_shard,
                 tp_boundary, remat, payload_codec="none", lora_rank=4,
                 quant_bits=8):
    """Fold the perf/fault knob overrides into (cfg, fed), recording every
    non-default on the result record."""
    if delta_dtype != "float32":
        fed = dataclasses.replace(fed, delta_dtype=delta_dtype)
        rec["delta_dtype"] = delta_dtype
    if payload_codec != "none":
        # compressed-payload round (requires a supports_codec algorithm,
        # i.e. --algorithm fedlora; FedConfig validation enforces it)
        fed = dataclasses.replace(fed, payload_codec=payload_codec,
                                  lora_rank=lora_rank, quant_bits=quant_bits)
        rec["payload_codec"] = payload_codec
        rec["lora_rank"] = lora_rank
        rec["quant_bits"] = quant_bits
    if client_state_placement != "host":
        fed = dataclasses.replace(
            fed, client_state_placement=client_state_placement)
        rec["client_state_placement"] = client_state_placement
    if dropout_rate:
        # fault-injecting round variant: threads the (C,) survivor mask
        # through the weighted aggregation (round_program)
        fed = dataclasses.replace(fed, dropout_rate=dropout_rate)
        rec["dropout_rate"] = dropout_rate
    if remat != "full":
        rec["remat"] = remat
    if moe_chunk and cfg.moe.enabled:  # §Perf knob
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, chunk_tokens=moe_chunk))
        rec["moe_chunk"] = moe_chunk
    if moe_routing != "onehot" and cfg.moe.enabled:  # §Perf knob
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, routing=moe_routing))
        rec["moe_routing"] = moe_routing
    if cache_shard != "greedy":
        rec["cache_shard"] = cache_shard
    if tp_boundary:
        cfg = dataclasses.replace(cfg, tp_out_constraint=True)
        rec["tp_boundary"] = True
    return cfg, fed


def _lower_step(cfg, fed, shape, spec, mesh, placement, q_chunk, remat):
    """Lower the shape's step (train round / prefill / decode) against the
    mesh; returns ``(lowered, local_steps)``."""
    if shape.kind == "train":
        caxes = client_axes(mesh)
        round_fn = make_fed_round(
            cfg, fed, placement=placement,
            spmd_axes=(caxes if len(caxes) > 1 else caxes[0])
            if placement == "parallel" else None,
            q_chunk=q_chunk, remat=remat,
        )
        rules = ({"batch": (), "clients": caxes}
                 if placement == "parallel" else None)
        # stateful rounds return (state, metrics, new_client_states) — or
        # (state, metrics, new_store_state) with the device store; either
        # way the third output's sharding sits at args index 3 (keyed off
        # the explicit flag: a fault-injecting stateless round also has
        # extra args, so arity is not a statefulness signal)
        out_sh = ((spec["shardings"][0], None, spec["shardings"][3])
                  if spec["stateful"] else (spec["shardings"][0], None))
        with axis_rules(mesh, rules):
            lowered = jax.jit(
                round_fn,
                in_shardings=spec["shardings"],
                out_shardings=out_sh,
            ).lower(*spec["args"])
        return lowered, fed.local_steps
    if shape.kind == "prefill":
        def step(params, batch):
            return prefill_step(params, batch["tokens"], cfg, shape.seq_len,
                                frontend=batch.get("frontend"),
                                q_chunk=q_chunk)
        with axis_rules(mesh):
            lowered = jax.jit(
                step, in_shardings=spec["shardings"], out_shardings=None
            ).lower(*spec["args"])
        return lowered, 1
    # decode
    def step(params, token, state):
        return serve_step(params, token, state, cfg)
    with axis_rules(mesh):
        lowered = jax.jit(
            step, in_shardings=spec["shardings"],
            out_shardings=(None, None, spec["shardings"][2]),
        ).lower(*spec["args"])
    return lowered, 1


def _save_hlo_text(save_hlo, hlo_text, rec, arch, shape_name, *,
                   cache_shard, moe_chunk, moe_routing, tp_boundary,
                   delta_dtype):
    """Dump compiled HLO text (gzip) under a knob-variant filename."""
    import gzip
    os.makedirs(save_hlo, exist_ok=True)
    variant = ""
    if cache_shard != "greedy":
        variant += f"__cache-{cache_shard}"
    if moe_chunk:
        variant += f"__chunk-{moe_chunk}"
    if moe_routing != "onehot":
        variant += f"__route-{moe_routing}"
    if tp_boundary:
        variant += "__tpb"
    if delta_dtype != "float32":
        variant += "__delta-bf16"
    fn = os.path.join(save_hlo,
                      f"{arch}__{shape_name}__{rec['mesh']}{variant}.hlo.gz")
    with gzip.open(fn, "wt") as f:
        f.write(hlo_text)
    rec["hlo_file"] = fn


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              algorithm: str = "fedpa", placement: str = "auto",
              remat: str = "full", q_chunk: int = 1024,
              fed: FedConfig = None, compile_: bool = True,
              mesh=None, save_hlo: str = None,
              cache_shard: str = "greedy", moe_chunk: int = 0,
              tp_boundary: bool = False, moe_routing: str = "onehot",
              delta_dtype: str = "float32",
              client_state_placement: str = "host",
              num_clients: int = 64,
              dropout_rate: float = 0.0,
              payload_codec: str = "none", lora_rank: int = 4,
              quant_bits: int = 8) -> dict:
    """Lower (and optionally compile) one (arch, shape, mesh) combination;
    returns the record dict (roofline terms, memory, collectives, or the
    skip/error status). ``client_state_placement="device"`` lowers the
    stateful round with the device-resident client-state store —
    ``num_clients`` sizes its population axis."""
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    skip = should_skip(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "algorithm": algorithm}
    if skip:
        rec["status"] = skip
        return rec

    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    fed = fed or default_fed_config(algorithm)
    # same display-name helper as launch.train; the dry-run lowers the
    # sampling-regime round, so label it with the first post-burn-in round
    rec["algorithm"] = phase_name(fed, fed.burn_in_rounds)
    cfg, fed = _apply_knobs(
        cfg, fed, rec, delta_dtype=delta_dtype,
        client_state_placement=client_state_placement,
        dropout_rate=dropout_rate, moe_chunk=moe_chunk,
        moe_routing=moe_routing, cache_shard=cache_shard,
        tp_boundary=tp_boundary, remat=remat,
        payload_codec=payload_codec, lora_rank=lora_rank,
        quant_bits=quant_bits)
    if placement == "auto":
        placement = default_placement(cfg)
    rec["placement"] = placement if shape.kind == "train" else "-"
    rec["chips"] = chips

    if client_state_placement == "device":
        # the store's population layout (launch.specs is the source of
        # truth): sharded over the client axes, padded — a 1M-client
        # scaffold store holds padded_N/extent rows per device
        layout = store_population_layout(mesh, num_clients)
        rec["store_population"] = {
            "num_clients": layout.num_clients,
            "padded_num_clients": layout.padded_num_clients,
            "shard_extent": layout.extent,
            "rows_per_device": layout.padded_num_clients
            // max(layout.extent, 1),
        }
    spec = input_specs(cfg, shape, fed, mesh, placement,
                       cache_shard=cache_shard, num_clients=num_clients)
    if shape.kind == "train":
        # exact per-round wire bytes from the abstract specs (uplink may be
        # compressed; downlink is params + broadcast extras) — no allocation
        from repro.compression import round_bytes  # noqa: PLC0415
        rec["payload_bytes"] = round_bytes(fed, spec["args"][0].params)
    t0 = time.time()
    lowered, local_steps = _lower_step(cfg, fed, shape, spec, mesh,
                                       placement, q_chunk, remat)
    rec["lower_s"] = round(time.time() - t0, 2)
    if not compile_:
        rec["status"] = "lowered"
        return rec

    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    # XLA's cost_analysis counts loop bodies once (no trip scaling) — see
    # EXPERIMENTS.md §Roofline/Methodology. Use the trip-count-aware HLO
    # walker for the real per-device numbers; keep XLA's raw view on record.
    raw_cost = compiled.cost_analysis()
    rec["cost_xla_raw"] = {k: raw_cost[k] for k in ("flops", "bytes accessed")
                           if k in raw_cost}
    hlo_text = compiled.as_text()
    if save_hlo:
        _save_hlo_text(save_hlo, hlo_text, rec, arch, shape_name,
                       cache_shard=cache_shard, moe_chunk=moe_chunk,
                       moe_routing=moe_routing, tp_boundary=tp_boundary,
                       delta_dtype=delta_dtype)
    hlo = hlo_analyze(hlo_text)
    cost = {"flops": hlo["flops"], "bytes accessed": hlo["bytes"]}
    rec["cost"] = cost
    coll = hlo["collectives"]
    rec["collectives"] = coll
    # sequential placement: the round runs clients_per_round clients back to
    # back, each doing local_steps of the full global batch
    eff_steps = local_steps
    if shape.kind == "train" and rec.get("placement") == "sequential":
        eff_steps = local_steps * fed.clients_per_round
    report = derive(arch, shape, cfg, rec["mesh"], chips, cost, coll,
                    local_steps=eff_steps if shape.kind == "train" else 1)
    rec["roofline"] = report.as_row()
    rec["status"] = "ok"
    return rec


def main():
    """CLI: sweep (arch x shape x mesh) combos, print the roofline table."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--algorithm", default="fedpa",
                    choices=algorithm_names(),
                    help="registered federated algorithm "
                         f"(repro.algorithms): {', '.join(algorithm_names())}")
    ap.add_argument("--placement", default="auto",
                    choices=("auto", "parallel", "sequential"))
    ap.add_argument("--remat", default="full",
                    choices=("full", "dots", "none"))
    ap.add_argument("--q-chunk", type=int, default=1024)
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--cache-shard", default="greedy",
                    choices=("greedy", "flash"),
                    help="decode KV-cache sharding strategy (§Perf)")
    ap.add_argument("--moe-chunk", type=int, default=0,
                    help="override MoE chunk_tokens (§Perf)")
    ap.add_argument("--delta-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="FedPA sample/DP-state dtype (§Perf)")
    ap.add_argument("--payload-codec", default="none",
                    help="client payload codec chain (repro.compression): "
                         "none | lowrank | int8 | lowrank+int8; non-'none' "
                         "requires --algorithm fedlora")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="rank of the 'lowrank' codec's sketch")
    ap.add_argument("--quant-bits", type=int, default=8, choices=(8, 16),
                    help="bit width of the 'int8' codec's quantizer")
    ap.add_argument("--client-state-placement", default="host",
                    choices=("host", "device"),
                    help="client-state store for stateful algorithms: "
                         "host numpy or device-resident buffers traced "
                         "through the round (core/client_state.py)")
    ap.add_argument("--num-clients", type=int, default=64,
                    help="population size of the device-resident "
                         "client-state store (device placement only)")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="lower the fault-injecting round variant: a (C,) "
                         "survivor mask threads through the aggregation "
                         "(data/cohort_source.py)")
    ap.add_argument("--moe-routing", default="onehot",
                    choices=("onehot", "sort"),
                    help="MoE dispatch implementation (§Perf)")
    ap.add_argument("--tp-boundary", action="store_true",
                    help="pin TP all-reduces at mixer/ffn outputs (§Perf)")
    ap.add_argument("--save-hlo", default=None,
                    help="dump compiled HLO text (gzip) into this dir")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    archs = configs.ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = lower_one(
                        arch, shape, multi_pod=mp, algorithm=args.algorithm,
                        placement=args.placement, remat=args.remat,
                        q_chunk=args.q_chunk, compile_=not args.no_compile,
                        save_hlo=args.save_hlo, cache_shard=args.cache_shard,
                        moe_chunk=args.moe_chunk,
                        tp_boundary=args.tp_boundary,
                        moe_routing=args.moe_routing,
                        delta_dtype=args.delta_dtype,
                        client_state_placement=args.client_state_placement,
                        num_clients=args.num_clients,
                        dropout_rate=args.dropout_rate,
                        payload_codec=args.payload_codec,
                        lora_rank=args.lora_rank,
                        quant_bits=args.quant_bits,
                    )
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": f"ERROR: {e}",
                           "traceback": traceback.format_exc()}
                records.append(rec)
                status = rec.get("status", "?")
                print(f"[{rec['mesh']}] {arch} x {shape}: {status} "
                      f"(lower {rec.get('lower_s', '-')}s, "
                      f"compile {rec.get('compile_s', '-')}s)", flush=True)
                if rec.get("memory"):
                    per_dev = rec["memory"].get("temp_size_in_bytes", 0)
                    print(f"    temp/device: {per_dev/2**30:.2f} GiB; "
                          f"args: {rec['memory'].get('argument_size_in_bytes',0)/2**30:.2f} GiB; "
                          f"collective bytes: {rec['collectives']['total_bytes']/2**20:.1f} MiB",
                          flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")

    ok = [r.get("roofline") for r in records if r.get("roofline")]
    if ok:
        print("\n" + format_table(ok))
    n_err = sum(1 for r in records if str(r.get("status", "")).startswith("ERROR"))
    print(f"\n{len(records)} combos, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
