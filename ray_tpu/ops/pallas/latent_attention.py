"""Masked flash attention of a prefill chunk over latent rows (Pallas/TPU).

The EXPANDED form of latent attention (models/dots3_note.py) for many
queries against cached rows ``[c_kv | k_rope | fill]`` that all heads
share, under a mask that is an INPUT: a learned selection's in a full
layer, window, padding and causality in a sliding one. What XLA makes of
the plain form (`dots3_note._attend_block`) sends every block's scores,
float32 ``[heads, queries, keys]``, to memory after the score product
and reads them back for the softmax pass and again for the value
product. Here a tile of scores lives and dies in VMEM.

Grid (row, group of `heads` heads, key tile), the key axis innermost, so
the running softmax of a group's heads stays in VMEM scratch across the
keys. A grid step holds one key tile of rows and of the mask for all of
its heads (neither is read once a head), expands the tile's keys and
values per head from the latent part (two products with that head's
slices of `w_kvb_k` / `w_kvb_v`, rounded to the rows' dtype as the plain
form's einsums round them), and folds them into each query tile's
state. The layer of the stacked cache and two small tables are
scalar-prefetch operands: `live` says which (query tile, key tile)
pairs hold a position the mask lets through, `named` which key tile a
grid step fetches. A pair with none is not computed (`pl.when`), and a
key tile with none for any query names the tile before it, which is in
VMEM already: nothing is copied for what causality, padding, the
window or the depth still unwritten leave empty.

Scores, running max, sum and accumulator in float32; probabilities cast
to the rows' dtype before the value product; a query with nothing let
through gives zeros: the precisions and the edge cases of the plain
form. No backward, and nothing shared with ops/pallas/flash_attention.py
but its rule for interpret mode: that file is the train step's.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention as _flash

_LANES = 128
NEG = -1e30          # the running max starts here, as the plain form's
# what a grid step may hold, of the core's 128 MiB: two buffers of each
# block, the scratch and the score tile's temporaries, about 47 MiB for
# 8 heads x 1,024 queries against a tile of 1,024 keys of 640 numbers
# and 68 for a ring and a chunk in one tile of 1,664 keys of 1,152
_VMEM_BYTES = 96 * 2 ** 20


class Tiles(NamedTuple):
    heads: int       # a grid step's
    q: int           # queries a tile
    k: int           # keys a tile


def _first_dividing(n: int, sizes) -> int | None:
    return next((t for t in sizes if n % t == 0), None)


def tiles(heads: int, nope: int, rope: int, v: int, kv_rank: int,
          queries: int, keys: int) -> Tiles | None:
    """The tiles for these shapes, or None where they are not whole in
    any: the caller then keeps the plain form. Eight heads a step,
    queries in tiles of 512 or less, keys in tiles of 1,024 or, up to
    2,048 of them (a ring and a chunk), in one: a tile's keys are the
    rows of the expansion's products, and under 512 of them the array
    waits for its weights (my chip run, PR 33: 1,664 keys in tiles of 128
    took twice as long as in one). The parts of a row and of a head in
    whole lanes, but for the rope's 64, which ends its array."""
    if nope % 64 or rope % 64 or v % _LANES or kv_rank % _LANES:
        return None
    t = (_first_dividing(heads, (8, 4, 2, 1)),
         _first_dividing(queries, (512, 256, 128)),
         keys if keys <= 2048 and keys % _LANES == 0
         else _first_dividing(keys, (1024, 512, 256, 128)))
    return None if None in t else Tiles(*t)


def tile_tables(mask: jax.Array, t: Tiles):
    """(live [b, query tiles, key tiles] int32: the tile holds a position
    the mask lets through; named [b, key tiles] int32: the key tile to
    hold at that grid step: itself where any query tile is live, else the
    nearest live one before it, else the first live one, so that
    consecutive steps that compute nothing copy nothing)."""
    b, s, n = mask.shape
    nq, nk = s // t.q, n // t.k
    live = mask.reshape(b, nq, t.q, nk, t.k).any(axis=(2, 4))
    any_q = live.any(axis=1)                                  # [b, nk]
    at = jnp.where(any_q, jnp.arange(nk), -1)
    before = jax.lax.cummax(at, axis=1)
    named = jnp.where(before >= 0, before, jnp.argmax(any_q, axis=1)[:, None])
    return live.astype(jnp.int32), named.astype(jnp.int32)


def _kernel(li_ref, live_ref, named_ref, q_ref, rows_ref, wk_ref, wv_ref,
            mask_ref, o_ref, m_scr, l_scr, acc_scr, bias_scr, *,
            scale: float, kv_rank: int, rope: int, t: Tiles):
    bi, j, nk = pl.program_id(0), pl.program_id(2), pl.num_programs(2)
    nq = q_ref.shape[2] // t.q
    flag = lambda qi: live_ref[(bi * nq + qi) * nk + j]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(functools.reduce(jnp.maximum, [flag(qi) for qi in range(nq)])
             > 0)
    def _tile():
        # 0 where the mask lets through, -inf where not: added to the
        # scores it gives exp(-inf - m) = 0 whatever the finite m, with
        # no second select, and m never leaves [NEG, inf)
        bias_scr[...] = jnp.where(mask_ref[0].astype(jnp.int32) != 0, 0.0,
                                  -jnp.inf).astype(jnp.float32)
        c = rows_ref[0, 0, :, :kv_rank]                       # [tk, rank]
        k_rope = rows_ref[0, 0, :, kv_rank:kv_rank + rope]    # [tk, rope]

        def head(h, _):
            k_nope = jnp.dot(c, wk_ref[h], preferred_element_type=jnp.float32
                             ).astype(c.dtype)                # [tk, nope]
            v = jnp.dot(c, wv_ref[h], preferred_element_type=jnp.float32
                        ).astype(c.dtype)                     # [tk, v]
            k = jnp.concatenate([k_nope, k_rope], axis=1)     # [tk, d]
            for qi in range(nq):
                @pl.when(flag(qi) > 0)
                def _fold():
                    at = pl.ds(qi * t.q, t.q)
                    s = jax.lax.dot_general(
                        q_ref[0, h, at, :], k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32
                        ) * scale + bias_scr[at, :]           # [tq, tk]
                    m_prev = m_scr[h, at, 0:1]
                    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
                    p = jnp.exp(s - m_new)
                    alpha = jnp.exp(m_prev - m_new)
                    l_scr[h, at, 0:1] = (alpha * l_scr[h, at, 0:1]
                                         + p.sum(-1, keepdims=True))
                    m_scr[h, at, 0:1] = m_new
                    acc_scr[h, at, :] = acc_scr[h, at, :] * alpha + jnp.dot(
                        p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
            return _

        jax.lax.fori_loop(0, t.heads, head, 0)

    @pl.when(j == nk - 1)
    def _done():
        # a query with nothing let through: 0 / 1e-30, zeros
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :, 0:1], 1e-30)
                    ).astype(o_ref.dtype)


def latent_attention(q: jax.Array, rows: jax.Array, li, w_k: jax.Array,
                     w_v: jax.Array, mask: jax.Array, *, kv_rank: int,
                     rope: int, t: Tiles,
                     scale: float | None = None) -> jax.Array:
    """q ``[b, H, s, nope + rope]`` (heads first, rope part last and
    rotated); rows the STACKED latent rows ``[layers, b, n, row]`` with
    ``row >= kv_rank + rope``, of which layer `li` is attended; w_k
    ``[kv_rank, H, nope]`` and w_v ``[kv_rank, H, v]``, the up-projections
    as the model holds them; mask ``[b, s, n]`` bool. `t` from `tiles`.
    Returns ``[b, H, s, v]`` in q's dtype: softmax over the positions the
    mask lets through of q . [c W_k | k_rope] * scale (1 / sqrt(nope +
    rope) where none is given), times c W_v; zeros for a query it lets
    nothing through for."""
    b, heads, s, d = q.shape
    n, row = rows.shape[2], rows.shape[3]
    nope, v = w_k.shape[2], w_v.shape[2]
    assert d == nope + rope and s % t.q == 0 and n % t.k == 0 and (
        heads % t.heads == 0), (q.shape, rows.shape, t)
    nk = n // t.k
    live, named = tile_tables(mask, t)
    dt = q.dtype

    def group(bi, g, j, *_):         # q and the result: one copy a group
        return bi, g, 0, 0

    def weights(bi, g, j, *_):
        return g, 0, 0

    def rows_at(bi, g, j, li_ref, live_ref, named_ref):
        return li_ref[0], bi, named_ref[bi * nk + j], 0

    def mask_at(bi, g, j, li_ref, live_ref, named_ref):
        return bi, 0, named_ref[bi * nk + j]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, heads // t.heads, nk),
        in_specs=[
            pl.BlockSpec((1, t.heads, s, d), group),
            pl.BlockSpec((1, 1, t.k, row), rows_at),
            pl.BlockSpec((t.heads, kv_rank, nope), weights),
            pl.BlockSpec((t.heads, kv_rank, v), weights),
            pl.BlockSpec((1, s, t.k), mask_at),
        ],
        out_specs=pl.BlockSpec((1, t.heads, s, v), group),
        scratch_shapes=[
            pltpu.VMEM((t.heads, s, _LANES), jnp.float32),
            pltpu.VMEM((t.heads, s, _LANES), jnp.float32),
            pltpu.VMEM((t.heads, s, v), jnp.float32),
            pltpu.VMEM((s, t.k), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, scale=scale or 1.0 / math.sqrt(d),
                          kv_rank=kv_rank, rope=rope, t=t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, s, v), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_flash._interpret(),
    )
    # a head's slice of the up-projections as one leading index
    return call(jnp.reshape(li, (1,)).astype(jnp.int32), live.reshape(-1),
                named.reshape(-1), q, rows,
                w_k.astype(dt).transpose(1, 0, 2),
                w_v.astype(dt).transpose(1, 0, 2), mask.astype(jnp.int8))
