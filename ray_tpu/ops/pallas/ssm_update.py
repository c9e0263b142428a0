"""The one-token Mamba-2 state update in one pass (Pallas/TPU).

`ops/ssm.ssm_step` for layer `li` of the STACKED recurrent state as it
lies (`models/granite_hybrid.init_cache`: ``[layers, b, heads, p, n]``
float32):

    H = exp(dt a) H + (dt x) (x) B        y = H C + D x

XLA will not fuse the contraction with C into the in-place write of H:
one fusion reads the old state and writes the new, a second reads the
OLD state again, recomputes the new and contracts it, 1.5 times the
bytes the update requires. Here each block of the state is read once,
written once to the same place (the stack is aliased to the first
output, so the caller's `dynamic_index_in_dim` and
`dynamic_update_slice` go) and contracted while it is in VMEM.

Grid (row, block of heads); a block is ``[1, 1, hb, p, n]``, contiguous
in memory. The layer index is a scalar-prefetch operand, read by the
index maps alone. The arithmetic is `ssm_step`'s, float32 and in its
order: the decay's exp, dt x, the outer product and the sum over n
here, so that the caller's scope holds little beside the call; the
small operands come in the shapes a block wants them in (a head's
scalars on sublanes, B and C as rows), which costs a reshape of a few
KB each. All of it hides under the block's copies, and only just: a
block that is merely copied takes 97% as long, and ``+ D x`` inside
(a second turn of the sum from sublanes to lanes before the add) took
a seventh more, so that one addition of ``[b, h, p]`` is left to XLA
(PERF.md, PR 39).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention as _flash
from ray_tpu.ops.pallas.flash_attention import _LANES

F32 = jnp.float32
_SUBLANES = 8
# A block's bytes at most: in and out, each double-buffered, are four of
# them in VMEM (8 MiB of the 16 a kernel may take on a v5e unasked).
# From 0.5 MiB up the time a layer does not move with the block (16, 32
# and 64 heads of 64 x 128 within 1%; 8 heads 17% slower by its 9,216
# grid steps a round: PERF.md, PR 39)
_BLOCK_BYTES = 2 * 2 ** 20


def heads_per_block(heads: int, p: int, n: int, dtype) -> int | None:
    """Heads in one block of the kernel for a state of ``heads x p x n``
    in `dtype`, or None for a shape the kernel does not take: the
    largest divisor of `heads` that is whole in sublanes (the small
    operands hold a head a sublane), or all of them, within
    `_BLOCK_BYTES`."""
    if jnp.dtype(dtype) != F32 or p % _SUBLANES or n % _LANES:
        return None
    fit = [hb for hb in range(1, heads + 1)
           if heads % hb == 0 and (hb % _SUBLANES == 0 or hb == heads)
           and hb * p * n * 4 <= _BLOCK_BYTES]
    return max(fit, default=None)


def _kernel(li_ref, h_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, y_ref,
            *, hb: int, hs: int):
    hj = pl.program_id(1)
    b, c = b_ref[0], c_ref[0]                         # [1, n]

    def heads(j, carry):
        # `hs` heads at a time bound what the body holds beside the
        # blocks: the whole block as one value is as fast and takes
        # three blocks more of VMEM
        at = pl.multiple_of(j * hs, hs)
        here = pl.ds(at, hs)
        of_all = pl.ds(pl.multiple_of(hj * hb, hs) + at, hs)
        x, dt = x_ref[0, here], dt_ref[0, here]       # [hs, p], [hs, 1]
        decay = jnp.exp(dt * a_ref[of_all])
        new = (h_ref[0, 0, here] * decay[:, :, None]
               + (dt * x)[:, :, None] * b[None])
        o_ref[0, 0, here] = new
        y_ref[0, here] = jnp.sum(new * c[None], axis=-1)
        return carry

    jax.lax.fori_loop(0, hb // hs, heads, 0)


def ssm_update(states: jax.Array, li, x: jax.Array, dt: jax.Array,
               a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array, *,
               heads_block: int) -> tuple[jax.Array, jax.Array]:
    """One token for every row of layer `li`. states: the stack
    ``[layers, b, h, p, n]`` float32, updated in place where the caller
    donates it; x: [b, h, p]; dt: [b, h] (after softplus); a: [h]
    (negative); b, c: [b, n]; d: [h]; `heads_block` from
    `heads_per_block`. Returns (y [b, h, p] float32, the stack with
    layer `li` renewed and every other layer as it was)."""
    _, bsz, h, p, n = states.shape
    hb = heads_block
    assert h % hb == 0 and states.dtype == F32, (states.shape, hb)
    hs = _SUBLANES if hb % _SUBLANES == 0 else hb
    x, dt, a, b, c, d = (t.astype(F32) for t in (x, dt, a, b, c, d))

    state_spec = pl.BlockSpec((1, 1, hb, p, n),
                              lambda bi, hj, li: (li[0], bi, hj, 0, 0))
    per_head = pl.BlockSpec((h, 1), lambda bi, hj, li: (0, 0))
    row = pl.BlockSpec((1, 1, n), lambda bi, hj, li: (bi, 0, 0))
    y_spec = pl.BlockSpec((1, hb, p), lambda bi, hj, li: (bi, hj, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, h // hb),
        in_specs=[state_spec, y_spec,
                  pl.BlockSpec((1, hb, 1), lambda bi, hj, li: (bi, hj, 0)),
                  per_head, row, row],
        out_specs=[state_spec, y_spec],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, hb=hb, hs=hs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct((bsz, h, p), F32)],
        # operand 0 is the layer index: the stack is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_flash._interpret(),
    )
    states, y = call(jnp.reshape(li, (1,)).astype(jnp.int32), states, x,
                     dt[..., None], a[:, None], b[:, None], c[:, None])
    return y + d[:, None] * x, states
