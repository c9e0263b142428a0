"""Pipeline parallelism, TPU-native: GPipe schedule over a `stage` mesh
axis inside one jit program.

The reference's PP substrate is host-side compiled actor-DAGs with NCCL
channels (ref: python/ray/dag/compiled_dag_node.py:757,
experimental/channel/torch_tensor_nccl_channel.py; our host analog lives
in ray_tpu/dag/). The TPU-first design instead keeps the whole pipeline
INSIDE XLA: layers shard over a `stage` mesh axis, activations hop
stage→stage via `lax.ppermute` over ICI neighbors, and a `lax.scan`
drives the microbatch schedule — so the compiler overlaps compute with
the neighbor transfers and the whole train step stays one GSPMD program
(differentiable end to end: ppermute transposes to the reverse shift, so
jax.grad gives the backward pipeline for free).

Schedule: plain GPipe — T = n_micro + S - 1 ticks; stage s processes
microbatch m = t - s when 0 <= m < n_micro. Bubble fraction
(S-1)/(T) shrinks as n_micro grows, the standard trade.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_local(stage_fn: Callable, params_local: Any,
                    micro_x: jax.Array, axis: str) -> jax.Array:
    """Runs on ONE stage's shard inside shard_map.

    params_local: this stage's slice of the stacked stage params
    (leading stage axis removed by sharding). micro_x: [n_micro, ...]
    microbatches, replicated. Returns [n_micro, ...] outputs of the LAST
    stage (zeros elsewhere; caller psums over the stage axis).
    """
    n_stages = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    n_micro = micro_x.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    state0 = jnp.zeros_like(micro_x[0])
    out0 = jnp.zeros_like(micro_x)

    def tick(carry, t):
        state, outputs = carry
        m = t - idx  # microbatch index this stage works on at tick t
        active = (m >= 0) & (m < n_micro)
        m_c = jnp.clip(m, 0, n_micro - 1)
        # stage 0 ingests a fresh microbatch; later stages take the
        # activation that arrived from the previous stage
        x_in = jnp.where(idx == 0, micro_x[jnp.clip(t, 0, n_micro - 1)],
                         state)
        y = stage_fn(params_local, x_in)
        y = jnp.where(active, y, state)
        # the last stage records its finished microbatch
        is_out = active & (idx == n_stages - 1)
        outputs = outputs.at[m_c].add(jnp.where(is_out, y, 0.0))
        # shift activations to the next stage around the ICI ring
        state = jax.lax.ppermute(y, axis, perm)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(
        tick, (state0, out0), jnp.arange(n_micro + n_stages - 1))
    # replicate the result: only the last stage holds nonzero outputs
    return jax.lax.psum(outputs, axis)


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   mesh: Mesh, *, n_micro: int, axis: str = "stage",
                   remat: bool = False) -> jax.Array:
    """Apply `n_stages` sequential stages to `x` with GPipe over `axis`.

    stage_fn(params_one_stage, x) -> y (same shape as x).
    stage_params: pytree whose leaves carry a LEADING stage axis of size
    mesh.shape[axis]. x: [batch, ...]; batch must divide n_micro.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, f"batch {b} % n_micro {n_micro} != 0"
    micro_x = x.reshape((n_micro, b // n_micro) + x.shape[1:])

    fn = stage_fn
    if remat:
        fn = jax.checkpoint(fn)

    local = functools.partial(_pipeline_local, fn, axis=axis)

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    sharded = jax.shard_map(
        lambda p, mx: local(jax.tree.map(lambda l: l[0], p), mx),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False)
    out = sharded(stage_params, micro_x)
    return out.reshape((b,) + out.shape[2:])


def stack_stage_params(per_stage: list[Any]) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage axis."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage)
