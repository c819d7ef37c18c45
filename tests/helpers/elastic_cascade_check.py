"""Subprocess helper: cascading elastic failure — two back-to-back
remesh cycles (8 -> 4 -> 2 devices), each restoring from the latest
checkpoint, with the generation counter strictly monotone and training
resuming after every shrink. Exits nonzero on failure."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.configs import reduced_config
from repro.launch.steps import make_train_step
from repro.models.sharding import ShardingRules
from repro.optim import adamw_init
from repro.runtime.elastic import ElasticController, ElasticState


def make_mesh(n):
    return jax.make_mesh((1, n), ("data", "model"),
                         devices=jax.devices()[:n])


def main():
    cfg = reduced_config("granite_8b")
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                              vocab_size=256, n_heads=4, n_kv_heads=2,
                              head_dim=16)
    model, train_step = make_train_step(cfg, remat="none")
    jit_step = jax.jit(train_step)

    def batch_for(mesh, seed):
        k = jax.random.PRNGKey(seed)
        toks = jax.random.randint(k, (8, 17), 0, cfg.vocab_size)
        sh = NamedSharding(mesh, P())
        return {"tokens": jax.device_put(toks[:, :-1], sh),
                "labels": jax.device_put(toks[:, 1:], sh)}

    def spec_fn(mesh, tree_shapes):
        rules = ShardingRules(cfg, mesh)
        return {"params": rules.param_specs(tree_shapes["params"]),
                "opt": {"m": rules.param_specs(tree_shapes["opt"]["m"]),
                        "v": rules.param_specs(tree_shapes["opt"]["v"]),
                        "count": P()}}

    tmp = tempfile.mkdtemp()
    ckpt = Checkpointer(tmp, async_save=False)

    mesh = make_mesh(8)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        for step in range(3):
            params, opt, m = jit_step(params, opt, batch_for(mesh, step),
                                      jnp.asarray(step))
        ckpt.save(3, {"params": params, "opt": opt}, wait=True)

    ctrl = ElasticController(make_mesh=make_mesh, spec_fn=spec_fn,
                             ckpt=ckpt, n_devices=8)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          {"params": params, "opt": opt})
    state = ElasticState(mesh=mesh, step=3, params=None, opt_state=None)

    # cycle 1: devices 4..7 crash -> remesh to 4, restore step 3
    for t in (1.0, 2.0, 3.0, 4.0):
        for d in range(4):
            ctrl.coordinator.beat(d, t)
    failed = ctrl.coordinator.tick(5.0)
    assert sorted(failed) == [4, 5, 6, 7], failed
    assert ctrl.needs_remesh()
    state = ctrl.remesh(state, shapes)
    assert state.generation == 1 and state.step == 3
    assert state.mesh.devices.size == 4
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    # training continues on the 4-mesh and checkpoints one more step
    with jax.set_mesh(state.mesh):
        p4, o4, m4 = jit_step(state.params, state.opt_state,
                              batch_for(state.mesh, 10), jnp.asarray(3))
        assert np.isfinite(float(m4["loss"]))
        ckpt.save(4, {"params": p4, "opt": o4}, wait=True)
    state = dataclasses.replace(state, step=4, params=p4, opt_state=o4)

    # cycle 2: devices 2..3 crash too -> remesh to 2, restore step 4
    for t in (6.0, 7.0, 8.0, 9.0):
        for d in range(2):
            ctrl.coordinator.beat(d, t)
    failed = ctrl.coordinator.tick(10.0)
    assert sorted(failed) == [2, 3], failed
    assert ctrl.needs_remesh()
    state = ctrl.remesh(state, shapes)
    assert state.generation == 2 and state.step == 4
    assert state.mesh.devices.size == 2
    for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    # the twice-shrunk mesh still trains
    with jax.set_mesh(state.mesh):
        _, _, m2 = jit_step(state.params, state.opt_state,
                            batch_for(state.mesh, 20), jnp.asarray(4))
    assert np.isfinite(float(m2["loss"]))
    print("CASCADE_OK")


if __name__ == "__main__":
    main()
