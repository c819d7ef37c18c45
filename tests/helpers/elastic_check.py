"""Subprocess helper: elastic restart — train on an 8-device mesh,
checkpoint, 'lose' 4 devices, restore onto a 4-device mesh, keep
training. Exits nonzero on failure."""
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

from repro.checkpoint import Checkpointer, latest_step
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

    mesh8 = make_mesh(8)
    with jax.set_mesh(mesh8):
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        for step in range(3):
            params, opt, m = jit_step(params, opt, batch_for(mesh8, step),
                                      jnp.asarray(step))
        loss8 = float(m["loss"])
        ckpt.save(3, {"params": params, "opt": opt}, wait=True)

    ctrl = ElasticController(make_mesh=make_mesh, spec_fn=spec_fn,
                             ckpt=ckpt, n_devices=8)
    # devices 4..7 go silent
    for t in (1.0, 2.0, 3.0, 4.0):
        for d in range(4):
            ctrl.coordinator.beat(d, t)
    failed = ctrl.coordinator.tick(5.0)
    assert sorted(failed) == [4, 5, 6, 7], failed
    assert ctrl.needs_remesh()

    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          {"params": params, "opt": opt})
    state = ctrl.remesh(ElasticState(mesh=mesh8, step=3, params=None,
                                     opt_state=None), shapes)
    assert state.step == 3 and state.generation == 1
    new_mesh = state.mesh
    assert new_mesh.devices.size == 4

    # restored params match bit-for-bit
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    # training resumes on the shrunk mesh
    with jax.set_mesh(new_mesh):
        p2, o2, m2 = jit_step(state.params, state.opt_state,
                              batch_for(new_mesh, 10), jnp.asarray(4))
    assert np.isfinite(float(m2["loss"]))
    print("ELASTIC_OK")


if __name__ == "__main__":
    main()
