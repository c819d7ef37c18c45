"""Subprocess helper: pipeline executor vs sequential reference on a
4-device host mesh. Exits nonzero on mismatch."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plans import ParallelismPlan, Stage
from repro.runtime.pipeline import DoraPipelineExecutor

S, L, D = 4, 8, 16          # stages, layers, width
M, MB = 8, 2                # microbatches, microbatch size


def layer_fn(lp, x):
    return jnp.tanh(x @ lp["w"] + lp["b"])


def main():
    mesh = jax.make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    stacked = {
        "w": jax.random.normal(key, (L, D, D)) * 0.3,
        "b": jnp.zeros((L, D)),
    }
    # uneven plan: 1/3/2/2 layers per stage
    stages = []
    splits = [1, 3, 2, 2]
    lo = 0
    for s, n in enumerate(splits):
        stages.append(Stage(node_ids=list(range(lo, lo + n)), devices=[s],
                            microbatch_split={s: 1.0}))
        lo += n
    plan = ParallelismPlan(stages=stages, microbatch_size=MB,
                           n_microbatches=M)

    ex = DoraPipelineExecutor(plan, L, mesh, layer_fn)
    packed = ex.pack_params(stacked)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))
    with jax.set_mesh(mesh):
        out = ex.forward(packed, x)

    # sequential reference
    ref = x
    for i in range(L):
        ref = layer_fn({"w": stacked["w"][i], "b": stacked["b"][i]}, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    print("PIPELINE_OK")


if __name__ == "__main__":
    main()
