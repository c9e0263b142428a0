"""Decode attention over the live blocks of a latent cache (Pallas/TPU).

The ABSORBED form of latent attention (models/kimi_k2.py) for one new
token a row against layer `li` of the STACKED latent rows as they lie,
``[layers, b, len, row]``: a position is ONE row ``[c_kv | k_rope |
fill]`` that all heads share, key and value at once (the value is the
row's first `kv_rank` numbers). A row's positions that count are
``[start[row], length[row]]``. The XLA form (`dots3_note._absorbed` over
the whole layer) reads every row of every slot and masks afterwards;
this kernel reads, for each row, only the blocks of `block_len`
positions that overlap its range, and nothing for a row whose range is
empty: ops/pallas/decode_attention.py's walk (`_row_blocks`,
`_named_block`, imported), over one array where that file has K and V.

It is a kernel beside that one and not a case of it because the shapes
differ in kind: there K lies positions-minor and V positions-major, a
block holds several kv heads and the heads of a group are a product's
few rows; here one array serves as both, positions major, one "kv head"
whose group is ALL the heads (64 rows of one product against a block of
rows, the second product against the same block's first `kv_rank`
columns), so a block is read once for both products.

Grid (row, block), the block axis innermost so that the running softmax
stays in VMEM scratch across a row's blocks. Scores, running max and sum
in float32, probabilities cast to the cache's dtype before the product
with the latents: the precisions of the XLA form. The output is the
softmax-weighted latent ``[b, heads, kv_rank]``; the value's
up-projection is the caller's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention as _flash
from ray_tpu.ops.pallas.decode_attention import _named_block, _row_blocks
from ray_tpu.ops.pallas.flash_attention import _LANES, NEG_INF


def block_len(max_len: int, row: int, dtype) -> int | None:
    """Positions in one block of rows for a cache `max_len` deep: the
    largest power of two from 128 that divides the depth and keeps a
    block within 2 MiB (1,024 rows of 640 bf16 numbers: a live row's
    range of thousands of positions ends inside a block at either side,
    and a grid step that computes nothing still costs one), or None
    where 128 does not divide the depth."""
    if max_len % 128:
        return None
    block = 128
    while (max_len % (2 * block) == 0
           and 2 * block * row * jnp.dtype(dtype).itemsize <= 2 ** 21):
        block *= 2
    return block


def _kernel(li_ref, start_ref, len_ref, q_ref, rows_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale: float, kv_rank: int, block_len: int,
            num_blocks: int):
    bi, j = pl.program_id(0), pl.program_id(1)
    start, length = start_ref[bi], len_ref[bi]
    lo, n = _row_blocks(start, length, block_len, num_blocks)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j < n)
    def _block():
        rows = rows_ref[0, 0]                             # [block, row]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [heads, block]
        pos = (lo + j) * block_len + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where((pos >= start) & (pos <= length), s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = alpha * l_scr[:, 0:1] + p.sum(-1, keepdims=True)
        m_scr[:, 0:1] = m_new
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :kv_rank],
            preferred_element_type=jnp.float32)           # [heads, rank]

    @pl.when(j == num_blocks - 1)
    def _done():
        l = l_scr[:, 0:1]
        # a row with no live block: zeros, not 0 / 0
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def latent_decode_attention(q: jax.Array, rows: jax.Array, li,
                            start: jax.Array, length: jax.Array, *,
                            kv_rank: int, scale: float,
                            block_len: int) -> jax.Array:
    """q ``[b, heads, row]``: each head's absorbed query ``[q_nope W_k |
    q_rope | zeros]``, one token a row; rows the stacked latent rows
    ``[layers, b, len, row]``, of which layer `li` is attended; row r
    attends to positions ``start[r] <= p <= length[r]`` (none where
    ``length[r] < start[r]``: zeros, and nothing is copied for it).
    `block_len` divides the cache's depth. Returns ``[b, heads,
    kv_rank]`` in q's dtype: softmax over those positions of q . row *
    scale, times the rows' first `kv_rank` numbers."""
    b, heads, row = q.shape
    max_len = rows.shape[2]
    assert rows.shape[3] == row and max_len % block_len == 0, (
        q.shape, rows.shape, block_len)
    num_blocks = max_len // block_len

    def rows_at(bi, j, li_ref, start_ref, len_ref):
        r, blk = _named_block(bi, j, start_ref, len_ref, block_len,
                              num_blocks)
        return li_ref[0], r, blk, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_blocks),
        in_specs=[pl.BlockSpec((1, heads, row), lambda bi, j, *_: (bi, 0, 0)),
                  pl.BlockSpec((1, 1, block_len, row), rows_at)],
        out_specs=pl.BlockSpec((1, heads, kv_rank),
                               lambda bi, j, *_: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((heads, _LANES), jnp.float32),
                        pltpu.VMEM((heads, _LANES), jnp.float32),
                        pltpu.VMEM((heads, kv_rank), jnp.float32)],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, scale=scale, kv_rank=kv_rank,
                          block_len=block_len, num_blocks=num_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_flash._interpret(),
    )
    return call(jnp.reshape(li, (1,)).astype(jnp.int32),
                start.astype(jnp.int32), length.astype(jnp.int32), q, rows)
