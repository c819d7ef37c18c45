"""Fault tolerance end to end — checkpoint, lose half the fleet, resume.

Runs on 8 virtual host devices (set before jax import): trains a tiny
model on an 8-device mesh with async sharded checkpoints, simulates 4
devices going silent, and shows the elastic controller re-mesh + Dora
replan + resharded restore resuming training on the survivors.

    python examples/elastic_recovery.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import dora
from repro.checkpoint import Checkpointer
from repro.configs import reduced_config
from repro.core.cost_model import Workload
from repro.core.device import CATALOG, Topology
from repro.core.graph_builders import GraphSpec, build_lm_graph
from repro.core.qoe import QoESpec
from repro.scenarios import Scenario
from repro.launch.steps import make_train_step
from repro.models.sharding import ShardingRules
from repro.optim import adamw_init
from repro.runtime.elastic import ElasticController, ElasticState


def make_mesh(n):
    return jax.make_mesh((1, n), ("data", "model"), devices=jax.devices()[:n])


def main() -> None:
    cfg = dataclasses.replace(reduced_config("granite_8b"), n_layers=2,
                              d_model=64, d_ff=128, vocab_size=256,
                              n_heads=4, n_kv_heads=2, head_dim=16)
    model, train_step = make_train_step(cfg, remat="none")
    jit_step = jax.jit(train_step)

    def batch(mesh, seed):
        k = jax.random.PRNGKey(seed)
        t = jax.random.randint(k, (8, 17), 0, cfg.vocab_size)
        sh = NamedSharding(mesh, P())
        return {"tokens": jax.device_put(t[:, :-1], sh),
                "labels": jax.device_put(t[:, 1:], sh)}

    def spec_fn(mesh, shapes):
        rules = ShardingRules(cfg, mesh)
        return {"params": rules.param_specs(shapes["params"]),
                "opt": {"m": rules.param_specs(shapes["opt"]["m"]),
                        "v": rules.param_specs(shapes["opt"]["v"]),
                        "count": P()}}

    ckpt = Checkpointer(tempfile.mkdtemp(), async_save=False)
    mesh8 = make_mesh(8)
    print(f"training on {mesh8.devices.size} devices...")
    with jax.set_mesh(mesh8):
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        for step in range(4):
            params, opt, m = jit_step(params, opt, batch(mesh8, step),
                                      jnp.asarray(step))
            print(f"  step {step} loss {float(m['loss']):.4f}")
        ckpt.save(4, {"params": params, "opt": opt}, wait=True)
    print("checkpoint committed at step 4")

    ctrl = ElasticController(make_mesh=make_mesh, spec_fn=spec_fn,
                             ckpt=ckpt, n_devices=8)
    for t in (1.0, 2.0, 3.0, 4.0):
        for d in range(4):
            ctrl.coordinator.beat(d, t)
    failed = ctrl.coordinator.tick(5.0)
    print(f"\nheartbeat detector: devices {failed} FAILED "
          f"(healthy: {ctrl.coordinator.healthy})")

    # Dora replans for the shrunk fleet (planner view of the same event):
    # an ad-hoc Scenario — the facade takes unregistered deployments too.
    devs = [CATALOG["rtx4050"]] * 4
    spec = GraphSpec("m", cfg.n_layers, cfg.d_model, cfg.n_heads,
                     cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size, seq_len=16)
    survivors = Scenario(
        name="home_survivors",
        description="Smart-home fleet after losing 4 of 8 devices",
        topology=lambda: Topology.shared_medium(devs, 600.0),
        model=lambda seq_len: build_lm_graph(spec, seq_len=seq_len),
        workload=Workload(global_batch=8, microbatch_size=1,
                          optimizer_mult=3.0),
        qoe=QoESpec(t_qoe=1.0, lam=10.0), seq_len=16)
    plan = dora.plan(survivors).result
    print(f"Dora replanned for 4 survivors in {plan.total_s:.2f}s: "
          f"{plan.best.n_stages} stages")

    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          {"params": params, "opt": opt})
    state = ctrl.remesh(ElasticState(mesh=mesh8, step=4, params=None,
                                     opt_state=None), shapes)
    print(f"restored step {state.step} onto a "
          f"{state.mesh.devices.size}-device mesh (generation "
          f"{state.generation})")
    with jax.set_mesh(state.mesh):
        p, o, m = jit_step(state.params, state.opt_state,
                           batch(state.mesh, 99), jnp.asarray(5))
    print(f"training resumed: step 5 loss {float(m['loss']):.4f}")


if __name__ == "__main__":
    main()
