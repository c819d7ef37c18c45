"""Subprocess helper: the DEFER streamed-migration pricing model vs an
executed pipeline iteration on a 4-device host mesh.

A streamed switch overlaps next-plan weight transfer with the current
plan's ongoing execution; the span it can hide behind is a *real*
forward-pass iteration, so the twin measures one with
``DoraPipelineExecutor.forward`` and holds the pricing model to it:

* zero overlap collapses to the synchronous cost (no free lunch),
* the executed span never prices above the synchronous switch,
* the exposed stall shrinks monotonically as the overlap grows and
  bottoms out at the drain.

Exits nonzero on violation."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp

from repro.core.plans import ParallelismPlan, Stage
from repro.runtime.pipeline import DoraPipelineExecutor

S, L, D = 4, 8, 16          # stages, layers, width
M, MB = 8, 2                # microbatches, microbatch size


def layer_fn(lp, x):
    return jnp.tanh(x @ lp["w"] + lp["b"])


def measured_span() -> float:
    """Median-ish executed forward span of a 4-stage pipeline (the
    overlap window a streamed migration runs behind)."""
    mesh = jax.make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    stacked = {
        "w": jax.random.normal(key, (L, D, D)) * 0.3,
        "b": jnp.zeros((L, D)),
    }
    stages = []
    for s in range(S):
        stages.append(Stage(node_ids=[2 * s, 2 * s + 1], devices=[s],
                            microbatch_split={s: 1.0}))
    plan = ParallelismPlan(stages=stages, microbatch_size=MB,
                           n_microbatches=M)
    ex = DoraPipelineExecutor(plan, L, mesh, layer_fn)
    packed = ex.pack_params(stacked)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))
    with jax.set_mesh(mesh):
        jax.block_until_ready(ex.forward(packed, x))     # compile
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            out = ex.forward(packed, x)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    span = measured_span()
    assert span > 0.0

    import repro.dora as dora
    s = dora.serve("hospital_ward")
    cfg = s.adapter.config
    cfg.async_switching = False
    cfg.delta_switching = False
    old = s.current
    new = next(p for p in s.plans if len(p.devices) > 1)

    sync = s.adapter.switch_cost(old, new)
    assert sync > cfg.switch_drain_s, "need a real weight-load time"
    cfg.streamed_migration = True
    zero = s.adapter.switch_cost(old, new, overlap_s=0.0)
    assert abs(zero - sync) < 1e-9, (zero, sync)
    streamed = s.adapter.switch_cost(old, new, overlap_s=span)
    assert streamed <= sync + 1e-9, (streamed, sync)
    costs = [s.adapter.switch_cost(old, new, overlap_s=k * span)
             for k in range(0, 4000, 400)]
    assert all(a >= b - 1e-12 for a, b in zip(costs, costs[1:])), costs
    assert costs[-1] >= cfg.switch_drain_s - 1e-12
    print(f"STREAM_OVERLAP_OK span={span * 1e3:.2f}ms "
          f"sync={sync:.3f}s streamed={streamed:.3f}s")


if __name__ == "__main__":
    main()
