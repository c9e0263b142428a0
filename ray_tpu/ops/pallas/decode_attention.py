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

Handed the step's new key and value (`new_kv`, PR 48) the kernel is also
the step's cache write. Row r's new row stands at position `length[r]`
whatever the cache holds there: it is scored beside the row's last block
as one more column of the running softmax (the block's own column at
that position is masked), and it is left written there. The block that
holds `length[r]` is the row's last, in VMEM anyway, so the write is cut
from it: the tile of 128 positions of K around the position (positions
are K's lanes: ``[kv_heads, hd, 128]``) and the tile of 16 of V
(``[kv_heads, 16, hd]``), each with the new row laid in, go from VMEM
scratch to layer `li` of the stacked caches by one async copy each, a
row. The stacks are two more outputs, left in HBM (`pl.ANY`) and
aliased to the inputs (`input_output_aliases`), so every other byte of
them is never touched and the caller's layer loop carries them through
the call with no copy. The copies start in the grid step of the row's
last block and are waited for in the row's last grid step, under the
next blocks' reads. Reading blocks of a buffer while writing tiles of it
is safe here because a tile is written only after the block that holds
it has been read for the last time in this call: the pipeline fetches a
row's blocks in order, that block is the last of them and is in VMEM
when its tiles are cut, and no later grid step fetches it (a row with an
empty range after it names it again, which copies nothing:
`_named_block`; the rows after that are other rows of the buffer). A row
with an empty range writes nothing. (An output `BlockSpec` of the tiles,
its index map reading `length`, writes them back as well and was 11 to
18 us a layer slower on the chip: four index maps a grid step where two
were, and the tiles' hand-back for rows that hold nothing.) The sixteen
`dynamic_update_slice`s a layer that wrote these rows before cost a
decode round as much as reading every live position (0.89 ms of 7.0;
PERF.md, PR 48).

A window layer's RING (`ring`, PR 51; models/laguna.py) is the same
call over a cache one block deep in which position p lies in row ``p
mod block_len``: row j then holds the newest position congruent to j
that is not past `length`, it counts where that position is not before
`start` (every row, once the request is as deep as the ring), and the
new row stands, and is written, at ``length mod block_len`` and not at
the range's end. Nothing else differs: a row's one block is read, the
tiles around the new row are cut from it and written back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention as _flash
from ray_tpu.ops.pallas.flash_attention import _LANES, NEG_INF

# positions in the tile of V that a row's write takes with it: what a
# sublane tile of a 16-bit cache holds (two of a float32 cache's)
_V_TILE = 16


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


def _kernel(li_ref, start_ref, len_ref, q_ref, k_ref, v_ref, *refs,
            scale: float, block_len: int, num_blocks: int,
            v_positions_minor: bool, writes: bool, ring: bool):
    if writes:
        (kn_ref, vn_ref, o_ref, ko_ref, vo_ref, m_scratch, l_scratch,
         acc_scratch, kt_scratch, vt_scratch, sems) = refs
    else:
        o_ref, m_scratch, l_scratch, acc_scratch = refs
    bi = pl.program_id(0)
    j = pl.program_id(1)
    start, length = start_ref[bi], len_ref[bi]
    lo, n = _row_blocks(start, length, block_len, num_blocks)
    if writes:
        # where the new row stands: a depth past the cache's end is its
        # last position, as the XLA path's `dynamic_update_slice` clamps it
        at = (jax.lax.rem(jnp.maximum(length, 0), block_len) if ring
              else jnp.minimum(length, block_len * num_blocks - 1))

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
        # with the new row in hand, its position is that row's and what
        # the cache holds there does not count
        if ring:
            # how far behind `length` the position lies that row `pos`
            # of the ring holds; 0 is the new row's own
            behind = jax.lax.rem(length - pos + block_len, block_len)
            seen = (length - behind >= start) & (
                behind != 0 if writes else True)
        else:
            seen = (pos >= start) & (pos < at if writes else pos <= length)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scratch[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        if writes:
            # the new row: one more column, beside the row's last block
            kn = kn_ref[0].astype(jnp.float32)[:, None, :]   # [nkv, 1, hd]
            s_new = jnp.where(
                j == n - 1,
                jnp.sum(q.astype(jnp.float32) * kn, axis=-1,
                        keepdims=True) * scale, NEG_INF)  # [nkv, group, 1]
            m_new = jnp.maximum(m_new, s_new)
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
        if writes:
            p_new = jnp.exp(s_new - m_new)
            l_scratch[:, :, 0:1] += p_new
            acc_scratch[:] += (p_new.astype(v.dtype).astype(jnp.float32)
                               * vn_ref[0].astype(jnp.float32)[:, None, :])

    if writes:
        # the tiles around the new row's position, in the row's last
        # block and in the cache
        last = (lo + n - 1) * block_len
        here = at - last
        k_at = pl.multiple_of((here // _LANES) * _LANES, _LANES)
        v_at = pl.multiple_of((here // _V_TILE) * _V_TILE, _V_TILE)
        copies = (
            pltpu.make_async_copy(
                kt_scratch, ko_ref.at[li_ref[0], bi, :, :, pl.ds(
                    pl.multiple_of(last + k_at, _LANES), _LANES)],
                sems.at[0]),
            pltpu.make_async_copy(
                vt_scratch, vo_ref.at[li_ref[0], bi, :, pl.ds(
                    pl.multiple_of(last + v_at, _V_TILE), _V_TILE), :],
                sems.at[1]))

        @pl.when(j == n - 1)
        def _write():
            # cut from the block in VMEM (the row's last: it holds that
            # position), with the new row laid in
            tile = k_ref[0, 0, :, :, pl.ds(k_at, _LANES)]  # [nkv, hd, 128]
            lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 2)
            kt_scratch[:] = jnp.where(lane == here - k_at,
                                      _columns(kn_ref[0]), tile)
            tile = v_ref[0, 0, :, pl.ds(v_at, _V_TILE), :]  # [nkv, 16, hd]
            sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            vt_scratch[:] = jnp.where(sub == here - v_at,
                                      vn_ref[0][:, None, :], tile)
            for copy in copies:
                copy.start()

        @pl.when((j == num_blocks - 1) & (n > 0))
        def _written():
            for copy in copies:
                copy.wait()

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_scratch[:, :, 0:1]
        # a row with no live block: zeros, not 0 / 0
        o_ref[0] = (acc_scratch[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def _columns(rows):
    """[nkv, hd] -> [nkv, hd, 1]: a key as K holds it, hd down the
    sublanes."""
    return jnp.swapaxes(rows[:, None, :], 1, 2)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     li, start: jax.Array, length: jax.Array, *,
                     scale: float, block_len: int,
                     v_positions_minor: bool = False,
                     new_kv: tuple[jax.Array, jax.Array] | None = None,
                     ring: bool = False):
    """q ``[b, kv_heads, group, hd]``, one token per row; k_cache, v_cache
    the stacked caches (with `v_positions_minor`, V given in K's order,
    ``[layers, b, kv_heads, hd, len]``); `li` the layer; row r attends
    to positions ``start[r] <= p <= length[r]`` (none where ``length[r]
    < start[r]``: that row's output is zeros and nothing is copied for
    it). `block_len` divides the cache's depth.
    Returns ``[b, kv_heads, group, hd]`` in q's dtype.

    With `new_kv`, the step's key (roped) and value, ``[b, kv_heads,
    hd]`` each: row r attends to them AT ``length[r]``, whatever the
    cache holds there, and they are left written there in layer `li`
    (V as declared only; a depth past the cache's end is taken as its
    last position, as `dynamic_update_slice` clamps it). Returns (out,
    k_cache, v_cache), the caches aliased to the inputs: in place where
    the caller donates them, every other position as it was.

    With `ring` the cache is a ring one block deep (``block_len`` its
    depth): row r attends to the newest `block_len` positions up to
    ``length[r]`` that are not before ``start[r]``, each in row ``p mod
    block_len``, and the new row stands at ``length[r] mod block_len``."""
    b, nkv, group, hd = q.shape
    max_len = k_cache.shape[4]
    assert max_len % block_len == 0, (max_len, block_len)
    num_blocks = max_len // block_len
    assert not ring or num_blocks == 1, (max_len, block_len)
    writes = new_kv is not None

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
    in_specs, out_specs = [q_spec, k_spec, v_spec], q_spec
    out_shape = jax.ShapeDtypeStruct((b, nkv, group, hd), q.dtype)
    operands, scratch, aliases = (q, k_cache, v_cache), [], {}
    if writes:
        assert not v_positions_minor and block_len % _LANES == 0
        assert tuple(a.shape for a in new_kv) == ((b, nkv, hd),) * 2
        new_spec = pl.BlockSpec((1, nkv, hd), lambda bi, j, *_: (bi, 0, 0))
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        in_specs += [new_spec, new_spec]
        out_specs = [q_spec, hbm, hbm]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                     jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)]
        operands += tuple(a.astype(k_cache.dtype) for a in new_kv)
        scratch = [pltpu.VMEM((nkv, hd, _LANES), k_cache.dtype),
                   pltpu.VMEM((nkv, _V_TILE, hd), v_cache.dtype),
                   pltpu.SemaphoreType.DMA((2,))]
        # operands 0-2 are the prefetched scalars, 3 is q
        aliases = {4: 1, 5: 2}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((nkv, group, _LANES), jnp.float32),
            pltpu.VMEM((nkv, group, _LANES), jnp.float32),
            pltpu.VMEM((nkv, group, hd), jnp.float32),
        ] + scratch,
    )
    call = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_len=block_len,
                          num_blocks=num_blocks,
                          v_positions_minor=v_positions_minor,
                          writes=writes, ring=ring),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_flash._interpret(),
    )
    return call(jnp.reshape(li, (1,)).astype(jnp.int32),
                start.astype(jnp.int32), length.astype(jnp.int32),
                *operands)
