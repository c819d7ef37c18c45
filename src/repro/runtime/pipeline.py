"""Dora-plan-driven pipeline-parallel executor (shard_map).

Bridges the planner and the JAX runtime: a ``ParallelismPlan`` with S
pipeline stages maps onto a mesh axis ``"stage"``; activations move
between stages with ``jax.lax.ppermute`` (the jax-native analogue of the
paper's PiPPy send/recv), microbatches stream GPipe-style via
``lax.scan``. Gradients flow back through the transposed ppermute, so
``jax.grad`` of the pipelined forward gives pipeline-parallel training
without bespoke backward scheduling; per-stage remat keeps memory flat.

Stage imbalance follows the plan: each stage executes ``layers_per_stage``
layers of the stacked parameter tree (padded to the max so the shard_map
body is uniform — idle layers are zero-cost identity slots).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.plans import ParallelismPlan


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """Executable stage layout derived from a Dora plan."""

    n_stages: int
    layers_per_stage: Tuple[int, ...]     # true layer counts (≤ pad)
    pad: int                              # max layers on any stage
    n_microbatches: int

    @classmethod
    def from_plan(cls, plan: ParallelismPlan, n_layers: int) -> "PipelineSpec":
        total_nodes = sum(len(s.node_ids) for s in plan.stages)
        counts = []
        acc = 0
        for s in plan.stages:
            share = round(n_layers * len(s.node_ids) / total_nodes)
            counts.append(max(1, share))
            acc += counts[-1]
        counts[-1] += n_layers - sum(counts)        # fix rounding drift
        counts[-1] = max(1, counts[-1])
        return cls(n_stages=len(plan.stages), layers_per_stage=tuple(counts),
                   pad=max(counts), n_microbatches=plan.n_microbatches)


def _pad_stage_params(stacked: Any, spec: PipelineSpec) -> Any:
    """(L, ...) stacked layer params → (S, pad, ...) host arrays,
    zero-padded."""
    bounds = np.cumsum((0,) + spec.layers_per_stage)

    def fn(x):
        out = np.zeros((spec.n_stages, spec.pad) + x.shape[1:], dtype=x.dtype)
        for s in range(spec.n_stages):
            lo, hi = bounds[s], bounds[s + 1]
            out[s, : hi - lo] = np.asarray(x[lo:hi])
        return out
    return jax.tree.map(fn, stacked)


class DoraPipelineExecutor:
    """GPipe-over-shard_map executor for one decoder-style layer stack.

    ``layer_fn(layer_params, x) -> x`` is a single layer's forward.
    Parameters arrive stacked (L, ...); they are re-packed per stage.
    """

    def __init__(self, plan: ParallelismPlan, n_layers: int, mesh,
                 layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray]):
        if "stage" not in mesh.axis_names:
            raise ValueError("pipeline mesh needs a 'stage' axis")
        self.spec = PipelineSpec.from_plan(plan, n_layers)
        self.mesh = mesh
        self.layer_fn = layer_fn
        n_stage_devices = dict(zip(mesh.axis_names, mesh.devices.shape))["stage"]
        if n_stage_devices != self.spec.n_stages:
            raise ValueError(f"plan has {self.spec.n_stages} stages but mesh "
                             f"'stage' axis is {n_stage_devices}")

    # -- parameter packing ------------------------------------------------------
    def pack_params(self, stacked_params: Any) -> Any:
        """Re-pack per stage and place each stage's block on its own
        device of the 'stage' axis (no device holds another's layers)."""
        return jax.device_put(_pad_stage_params(stacked_params, self.spec),
                              NamedSharding(self.mesh, P("stage")))

    # -- forward -------------------------------------------------------------------
    def forward(self, stage_params: Any, x: jnp.ndarray) -> jnp.ndarray:
        """x: (M, mb, ...) microbatched input (already embedded). Returns
        the pipeline output in the same layout (valid on the last stage,
        broadcast back to all)."""
        spec = self.spec
        S, M = spec.n_stages, spec.n_microbatches
        n_valid = jnp.asarray(spec.layers_per_stage)

        @functools.partial(
            shard_map, mesh=self.mesh,
            in_specs=(P("stage"), P(None)),
            out_specs=P(None),
            check_vma=False)
        def run(params, xs):
            params = jax.tree.map(lambda a: a[0], params)   # local stage block
            stage_id = jax.lax.axis_index("stage")

            def stage_fn(x):
                def body(carry, lp_idx):
                    lp, idx = lp_idx
                    y = self.layer_fn(lp, carry)
                    keep = idx < n_valid[stage_id]          # padded slots = identity
                    return jnp.where(keep, y, carry), None
                idxs = jnp.arange(spec.pad)
                out, _ = jax.lax.scan(body, x, (params, idxs))
                return out

            stage_fn = jax.remat(stage_fn)
            buf = jnp.zeros_like(xs[0])
            outs = jnp.zeros_like(xs)
            perm = [(i, i + 1) for i in range(S - 1)]

            def tick(carry, t):
                buf, outs = carry
                # stage 0 injects microbatch t; others take the permuted input
                inject = jnp.where(t < M, t, M - 1)
                x_in = jnp.where(stage_id == 0, xs[inject], buf)
                y = stage_fn(x_in)
                # collect finished microbatches from the last stage
                done_idx = t - (S - 1)
                take = jnp.logical_and(stage_id == S - 1,
                                       jnp.logical_and(done_idx >= 0, done_idx < M))
                outs = jax.lax.cond(
                    take,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, y, jnp.maximum(done_idx, 0), 0),
                    lambda o: o, outs)
                buf = jax.lax.ppermute(y, "stage", perm)
                return (buf, outs), None

            (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(M + S - 1))
            # broadcast final outputs from the last stage to every stage
            outs = jnp.where(stage_id == S - 1, outs, jnp.zeros_like(outs))
            return jax.lax.psum(outs, "stage")

        return run(stage_params, x)

    def loss(self, stage_params: Any, x: jnp.ndarray,
             loss_fn: Callable[[jnp.ndarray], jnp.ndarray]) -> jnp.ndarray:
        out = self.forward(stage_params, x)
        return loss_fn(out)
