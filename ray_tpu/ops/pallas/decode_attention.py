"""Decode attention over the live blocks of each row (Pallas/TPU).

One new token per row against layer `li` of the STACKED KV cache as it
lies (`models/llama.init_kv_cache`: K ``[layers, b, kv_heads, hd, len]``,
V ``[layers, b, kv_heads, len, hd]``). A row's positions that count are
``[start[row], length[row]]``; in a serving engine that is a few hundred
of several thousand, and a slot that holds no request has none. The XLA
path (`ops/attention.cached_attention`) reads the whole layer and masks
afterwards, at the memory roofline for bytes that are mostly masked;
this kernel reads, for each row, only the blocks of `block_len`
positions that overlap its range, and nothing for a row whose range is
empty.

Grid (row, block), the block axis innermost so the online-softmax state
stays in VMEM scratch across a row's blocks. The layer index and the
rows' `start` and `length` are scalar-prefetch operands: the index maps
pick layer `li` and clamp the block index into the row's range, so a
grid step outside it names the block already in VMEM (no copy) and
computes nothing (`pl.when`); a row with an empty range names the block
the row before it left there, and computes on none. All kv heads of a row are one block; the kv-head
group's query heads are rows of one product per kv head, so no GQA
repeat of K or V exists. Scores, running max and sum in float32,
probabilities cast to the cache's dtype before the product with V: the
precisions of the XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention as _flash
from ray_tpu.ops.pallas.flash_attention import _LANES, NEG_INF


def _row_blocks(start, length, block_len: int, num_blocks: int):
    """(first block, number of blocks) that overlap positions
    [start, length] of one row; scalars, computed where they are used
    (the index maps and the kernel body) so that no operation outside
    the kernel exists for them in the caller's layer loop."""
    lo = jnp.clip(jax.lax.div(start, block_len), 0, num_blocks - 1)
    hi = jnp.minimum(jax.lax.div(jnp.maximum(length, 0), block_len),
                     num_blocks - 1)
    return lo, jnp.where(length < start, 0, hi - lo + 1)


def _named_block(bi, j, start_ref, len_ref, block_len: int,
                 num_blocks: int):
    """(row, block) of K and V that grid step (bi, j) names. Past a
    row's last block that block again, and for a row with an empty range
    the last block of the nearest row before it that has one:
    consecutive steps that name one block copy it once, so neither costs
    a copy. (Only empty rows ahead of the first live one name a block
    nobody reads: row 0's, once.)"""
    r = jax.lax.while_loop(
        lambda r: (r > 0) & (len_ref[r] < start_ref[r]),
        lambda r: r - 1, bi)
    lo, n = _row_blocks(start_ref[r], len_ref[r], block_len, num_blocks)
    return r, lo + jnp.minimum(jnp.where(r == bi, j, num_blocks),
                               jnp.maximum(n - 1, 0))


def _kernel(li_ref, start_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scratch, l_scratch, acc_scratch, *, scale: float,
            block_len: int, num_blocks: int, v_positions_minor: bool):
    bi = pl.program_id(0)
    j = pl.program_id(1)
    start, length = start_ref[bi], len_ref[bi]
    lo, n = _row_blocks(start, length, block_len, num_blocks)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    @pl.when(j < n)
    def _block():
        q = q_ref[0]                                  # [nkv, group, hd]
        k = k_ref[0, 0]                               # [nkv, hd, block]
        v = v_ref[0, 0]            # [nkv, block, hd], or [nkv, hd, block]
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [nkv, group, block]
        pos = (lo + j) * block_len + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        s = jnp.where((pos >= start) & (pos <= length), s, NEG_INF)
        m_prev = m_scratch[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[:, :, 0:1] = (alpha * l_scratch[:, :, 0:1]
                                + jnp.sum(p, axis=-1, keepdims=True))
        m_scratch[:, :, 0:1] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((2,), (2 if v_positions_minor else 1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # [nkv, group, hd]
        acc_scratch[:] = acc_scratch[:] * alpha + pv

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_scratch[:, :, 0:1]
        # a row with no live block: zeros, not 0 / 0
        o_ref[0] = (acc_scratch[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     li, start: jax.Array, length: jax.Array, *,
                     scale: float, block_len: int,
                     v_positions_minor: bool = False) -> jax.Array:
    """q ``[b, kv_heads, group, hd]``, one token per row; k_cache, v_cache
    the stacked caches (with `v_positions_minor`, V given in K's order,
    ``[layers, b, kv_heads, hd, len]``); `li` the layer; row r attends
    to positions ``start[r] <= p <= length[r]`` (none where ``length[r]
    < start[r]``: that row's output is zeros and nothing is copied for
    it). `block_len` divides the cache's depth.
    Returns ``[b, kv_heads, group, hd]`` in q's dtype."""
    b, nkv, group, hd = q.shape
    max_len = k_cache.shape[4]
    assert max_len % block_len == 0, (max_len, block_len)
    num_blocks = max_len // block_len

    def named(bi, j, start_ref, len_ref):
        return _named_block(bi, j, start_ref, len_ref, block_len, num_blocks)

    def k_index(bi, j, li, start_ref, len_ref):
        r, blk = named(bi, j, start_ref, len_ref)
        return li[0], r, 0, 0, blk

    def v_index(bi, j, li, start_ref, len_ref):
        r, blk = named(bi, j, start_ref, len_ref)
        return li[0], r, 0, blk, 0

    # K's order; V's too where the caller holds V with positions minor
    k_spec = pl.BlockSpec((1, 1, nkv, hd, block_len), k_index)
    v_spec = k_spec if v_positions_minor else pl.BlockSpec(
        (1, 1, nkv, block_len, hd), v_index)
    q_spec = pl.BlockSpec((1, nkv, group, hd),
                          lambda bi, j, *_: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_blocks),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((nkv, group, _LANES), jnp.float32),
            pltpu.VMEM((nkv, group, _LANES), jnp.float32),
            pltpu.VMEM((nkv, group, hd), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_len=block_len,
                          num_blocks=num_blocks,
                          v_positions_minor=v_positions_minor),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_flash._interpret(),
    )
    return call(jnp.reshape(li, (1,)).astype(jnp.int32),
                start.astype(jnp.int32), length.astype(jnp.int32),
                q, k_cache, v_cache)
