"""Subprocess body for test_sharded_round: executes one federated round on
8 fake host devices with a (4 data x 2 model) mesh — real collectives, both
placements, parallel FedPA + sequential FSDP FedPA. Prints MARKER lines the
test asserts on."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import FedConfig
from repro.core.server import init_server_state
from repro.core.sharded_round import make_fed_round
from repro.models import init_params
from repro.optim import get_optimizer
from repro.sharding import (axis_rules, fsdp_shardings, make_mesh,
                            param_shardings)

assert jax.device_count() == 8, jax.device_count()
mesh = make_mesh((4, 2), ("data", "model"))

cfg = configs.get_smoke("fedlm-100m")
fed = FedConfig(algorithm="fedpa", clients_per_round=4, local_steps=4,
                burn_in_steps=2, steps_per_sample=1, shrinkage_rho=0.1,
                server_opt="sgdm", server_lr=0.5,
                client_opt="sgd", client_lr=0.05)

params = init_params(jax.random.PRNGKey(0), cfg)
server_opt = get_optimizer(fed.server_opt, fed.server_lr, fed.server_momentum)

C, K, B, S = 4, fed.local_steps, 2, 32
tokens = jax.random.randint(jax.random.PRNGKey(1), (C, K, B, S + 1), 0,
                            cfg.vocab_size)

# ---------------- parallel placement ----------------
state = init_server_state(params, server_opt)
p_sh = param_shardings(params, mesh)
opt_by_shape = {s.shape: sh for s, sh in zip(
    jax.tree_util.tree_leaves(jax.eval_shape(lambda: params)),
    jax.tree_util.tree_leaves(p_sh))}
opt_sh = jax.tree_util.tree_map(
    lambda l: opt_by_shape.get(l.shape, NamedSharding(mesh, P())),
    state.opt_state)
state_sh = type(state)(p_sh, opt_sh, NamedSharding(mesh, P()))
batch_sh = {"tokens": NamedSharding(mesh, P("data", None, None, None))}

round_fn = make_fed_round(cfg, fed, placement="parallel", spmd_axes="data",
                          q_chunk=16)
with axis_rules(mesh, {"batch": (), "clients": ("data",)}):
    jfn = jax.jit(round_fn, in_shardings=(state_sh, batch_sh),
                  out_shardings=(state_sh, None))
    new_state, metrics = jfn(state, {"tokens": tokens})
ll = float(metrics["loss_last"])
moved = float(sum(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                  for a, b in zip(jax.tree_util.tree_leaves(new_state.params),
                                  jax.tree_util.tree_leaves(state.params))))
print(f"MARKER parallel loss={ll:.4f} finite={np.isfinite(ll)} moved={moved > 0}")

# ---------------- sequential (FSDP) placement ----------------
state = init_server_state(params, server_opt)
f_sh = fsdp_shardings(params, mesh)
opt_by_shape = {s.shape: sh for s, sh in zip(
    jax.tree_util.tree_leaves(jax.eval_shape(lambda: params)),
    jax.tree_util.tree_leaves(f_sh))}
opt_shf = jax.tree_util.tree_map(
    lambda l: opt_by_shape.get(l.shape, NamedSharding(mesh, P())),
    state.opt_state)
state_shf = type(state)(f_sh, opt_shf, NamedSharding(mesh, P()))
batch_shf = {"tokens": NamedSharding(mesh, P(None, None, "data", None))}
tokens_seq = jax.random.randint(jax.random.PRNGKey(2), (2, K, 4, S + 1), 0,
                                cfg.vocab_size)

round_fn_seq = make_fed_round(cfg, fed, placement="sequential", q_chunk=16)
with axis_rules(mesh):
    jfn2 = jax.jit(round_fn_seq, in_shardings=(state_shf, batch_shf),
                   out_shardings=(state_shf, None))
    new_state2, metrics2 = jfn2(state, {"tokens": tokens_seq})
ll2 = float(metrics2["loss_last"])
moved2 = float(sum(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                   for a, b in zip(jax.tree_util.tree_leaves(new_state2.params),
                                   jax.tree_util.tree_leaves(state.params))))
print(f"MARKER sequential loss={ll2:.4f} finite={np.isfinite(ll2)} moved={moved2 > 0}")

# collective check: the compiled parallel round must contain exactly the
# cross-client reductions (all-reduce) and no surprise all-to-alls
txt = jfn.lower(state, {"tokens": tokens}).compile().as_text()
has_ar = "all-reduce" in txt
print(f"MARKER collectives all_reduce={has_ar}")
print("MARKER done")
