"""Attention of a prefill chunk against a cache as it lies (Pallas/TPU):
grouped-query heads, a group of one among them.

`s` new tokens of a row against layer `li` of a STACKED cache (K
``[layers, b, kv_heads, hd, keys]``, V ``[layers, b, kv_heads, keys,
hd]``: `models/llama.init_kv_cache`'s orders). What XLA makes of the
plain form (`ops/attention.cached_attention`) holds a layer's scores
whole, float32 ``[kv_heads, queries, group, keys]``: 4.6 GB for 1,024
queries of 48 heads against 23,552 keys. Here a tile of scores lives and
dies in VMEM.

Whom a query attends to is decided from what the input says about each
key COLUMN, not from a mask that is an input: column c of a row is seen
by the queries that stand at positions ``seen[row, 0, c]`` to ``seen[row,
1, c]``, both ends counted (first > last: by none), and query i of the
chunk stands at ``q_pos0[row] + i``. A column that holds position p of a
cache by position is seen from p on, to p + window - 1 under a sliding
window (`seen_by_position`: models/laguna.py's full layers, and its
sliding ones over the ring as the chunk found it, then the chunk); a
column of the byte model's leaf is seen to its window's end if it is a
window's row and from its window's end on if it is a summary
(`models/evabyte._seen_by`). One kernel so serves them all, and the
tiles it walks follow from the same two numbers a column.

Grid (row, kv head, query tile, key tile), the key axis innermost, so
the running softmax of a kv head's `group` query heads stays in VMEM
scratch across the keys: the group's heads are rows of ONE product a
tile (``[group * tq, hd] x [hd, tk]``), so no GQA repeat of K or V
exists and a group of 6 or 9 fills the array as well as a power of two
would. The layer, the rows' first query positions and two small tables
are scalar-prefetch operands: `live` says which (query tile, key tile)
pairs hold a pair that counts, `named` which key tile a grid step
fetches. A pair with none is not computed (`pl.when`) and names the tile
before it, which is in VMEM already: nothing is copied for what
causality, a window, the left padding or the depth still unwritten leave
empty (`tile_tables`; `live_tiles` is the same rule on the host, for the
counters: exact, a tile is live iff one of its columns is seen by one of
its queries).

Scores, running max, sum and accumulator in float32; probabilities cast
to V's dtype before the value product; a query with nothing to attend
to (left padding) gives zeros: the precisions and the edge cases of the
XLA path. With `parts` the call returns what it summed and not the
quotient (float32 values, each query's running max and sum), so that a
softmax over keys that lie in two arrays is two calls and a merge and no
copy of either. No backward.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention as _flash

_LANES = 128
NEG = -1e30          # the running max starts here
NO_WINDOW = 2 ** 30  # farther than any position lies from another
# rows of one product a grid step: the group's heads x the query tile.
# 3,072 rows of 512 keys are 6 MiB of float32 scores and as much again
# of probabilities beside the tiles
_MAX_ROWS = 3072
_VMEM_BYTES = 64 * 2 ** 20
# the sizes a tile may take, largest first (tests give smaller ones)
_Q_TILES = (512, 256, 128)
_K_TILES = (512, 256, 128)
class Tiles(NamedTuple):
    q: int           # queries a tile
    k: int           # keys a tile


def tiles(group: int, head_dim: int, queries: int, keys: int) -> Tiles | None:
    """The tiles for these shapes, or None where they are not whole in
    any (the caller then keeps the XLA form): the largest query tile
    that keeps a product's rows under `_MAX_ROWS`, keys in tiles of 512."""
    if head_dim % _LANES and not _flash._interpret():
        return None
    tq = next((t for t in _Q_TILES
               if queries % t == 0 and group * t <= _MAX_ROWS), None)
    tk = next((t for t in _K_TILES if keys % t == 0), None)
    return None if tq is None or tk is None else Tiles(tq, tk)


def seen_by_position(xp, k_pos, start, window: int | None = None):
    """`seen` [..., 2, keys] of columns that hold the positions k_pos
    [..., keys] of rows whose first real position is start [...]: a key
    counts for the queries from its own position on, `window` of them
    (all, with none), and one before `start` for none. `xp` is numpy or
    jax.numpy."""
    first = xp.where(k_pos >= xp.asarray(start)[..., None], k_pos, NO_WINDOW)
    last = k_pos + (NO_WINDOW if window is None else int(window) - 1)
    return xp.stack([first, last], -2).astype(xp.int32)


def live_tiles(xp, seen, q_pos0, queries: int, t: Tiles):
    """[..., query tiles, key tiles] bool: the tile holds a pair of query
    and key that counts. seen [..., 2, keys], q_pos0 [...]; `xp` is numpy
    or jax.numpy. A column counts for a query tile iff the positions it
    is seen from overlap the tile's."""
    nq = queries // t.q
    first, last = (seen[..., i, :].reshape(seen.shape[:-2] + (1, -1, t.k))
                   for i in (0, 1))
    q_lo = (xp.asarray(q_pos0)[..., None]
            + xp.arange(nq) * t.q)[..., None, None]    # a tile's first query
    return ((first <= last) & (first <= q_lo + t.q - 1)
            & (last >= q_lo)).any(-1)


def tile_tables(seen: jax.Array, q_pos0: jax.Array, queries: int, t: Tiles):
    """(live [b, query tiles, key tiles] int32; named, same shape: the
    key tile to hold at that grid step: itself where live, else the
    nearest live one before it in the query tile's walk, else the first
    live one, so that steps that compute nothing copy nothing)."""
    live = live_tiles(jnp, seen, q_pos0, queries, t)
    nk = live.shape[-1]
    at = jnp.where(live, jnp.arange(nk), -1)
    before = jax.lax.cummax(at, axis=at.ndim - 1)
    named = jnp.where(before >= 0, before,
                      jnp.argmax(live, axis=-1)[..., None])
    return live.astype(jnp.int32), named.astype(jnp.int32)


def keys_visited(seen: np.ndarray, q_pos0: int, queries: int,
                 t: Tiles) -> np.ndarray:
    """[keys]: for each key column of one row, the queries whose scores
    against it the kernel computes: the live tiles' (host side, for the
    engine's counters)."""
    live = live_tiles(np, seen, q_pos0, queries, t)     # [nq, nk]
    return t.q * np.repeat(live.sum(0), t.k)


def _kernel(li_ref, q0_ref, live_ref, named_ref, q_ref, k_ref, v_ref,
            seen_ref, o_ref, *rest, scale: float, t: Tiles, group: int):
    stat_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    bi, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq, nk = pl.num_programs(2), pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live_ref[(bi * nq + qi) * nk + j] > 0)
    def _tile():
        hd = q_ref.shape[-1]
        q = q_ref[0, 0].reshape(group * t.q, hd)
        s = jnp.dot(q, k_ref[0, 0, 0], preferred_element_type=jnp.float32
                    ) * scale                                  # [g*tq, tk]
        q_pos = q0_ref[bi] + qi * t.q + jax.lax.broadcasted_iota(
            jnp.int32, (t.q, t.k), 0)
        seen = ((seen_ref[0, 0:1] <= q_pos)                    # [1, tk]
                & (q_pos <= seen_ref[0, 1:2]))
        # -inf where the pair does not count: exp(-inf - m) = 0 whatever
        # the finite m, and m never leaves [NEG, inf)
        s = (s.reshape(group, t.q, t.k)
             + jnp.where(seen, 0.0, -jnp.inf)[None]).reshape(
                 group * t.q, t.k)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = alpha * l_scr[:, 0:1] + p.sum(-1, keepdims=True)
        m_scr[:, 0:1] = m_new
        v = v_ref[0, 0, 0]                                     # [tk, hd]
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _done():
        if stat_ref is None:
            # a query with nothing to attend to: 0 / 1e-30, zeros
            o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[:, 0:1], 1e-30)
                           ).reshape(o_ref.shape[2:]).astype(o_ref.dtype)
            return
        o_ref[0, 0] = acc_scr[...].reshape(o_ref.shape[2:])
        # a query's max and sum as rows of lanes, not as 4-byte columns
        # padded to 128 lanes: the scratch's first column, turned
        for i, scr in enumerate((m_scr, l_scr)):
            stat_ref[0, 0, 0, i:i + 1, :] = jnp.broadcast_to(
                scr[:, 0:1], scr.shape).T[0:1, :]


def gqa_chunk_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        li, seen: jax.Array, q_pos0: jax.Array, *,
                        scale: float, t: Tiles, tables=None,
                        parts: bool = False):
    """q ``[b, kv_heads, group, s, hd]`` (rotated), row r's queries at
    positions ``q_pos0[r] + [0, s)``; k_cache ``[layers, b, kv_heads,
    hd, n]`` and v_cache ``[layers, b, kv_heads, n, hd]``, of which layer
    `li` is attended; seen ``[b, 2, n]`` int32, the first and the last
    position each column is seen from. `t` from `tiles`; `tables`, what
    `tile_tables` gives for (seen, q_pos0), where the caller has them
    already (one set serves every layer). Returns ``[b, kv_heads, group,
    s, hd]`` in q's dtype: softmax over the keys a query sees of q . k *
    scale, times v; zeros for a query with none. With `parts`, (values
    in float32 not yet divided, running max, sum: the last two ``[b,
    kv_heads, group, s]``), a max of `NEG` and zeros for a query with
    none."""
    b, nkv, group, s, hd = q.shape
    n = k_cache.shape[4]
    assert s % t.q == 0 and n % t.k == 0, (q.shape, k_cache.shape, t)
    assert v_cache.shape[3:] == (n, hd) and seen.shape == (b, 2, n)
    nq, nk = s // t.q, n // t.k
    q_pos0 = jnp.broadcast_to(q_pos0, (b,)).astype(jnp.int32)
    seen = seen.astype(jnp.int32)
    live, named = tables or tile_tables(seen, q_pos0, s, t)

    def at(bi, qi, j, named_ref):
        return named_ref[(bi * nq + qi) * nk + j]

    def q_at(bi, h, qi, j, *_):
        return bi, h, 0, qi, 0

    def k_at(bi, h, qi, j, li_ref, q0, live_ref, named_ref):
        return li_ref[0], bi, h, 0, at(bi, qi, j, named_ref)

    def v_at(bi, h, qi, j, li_ref, q0, live_ref, named_ref):
        return li_ref[0], bi, h, at(bi, qi, j, named_ref), 0

    def seen_at(bi, h, qi, j, li_ref, q0, live_ref, named_ref):
        return bi, 0, at(bi, qi, j, named_ref)

    rows = group * t.q
    out_block = pl.BlockSpec((1, 1, group, t.q, hd), q_at)
    out_specs, out_shape = out_block, jax.ShapeDtypeStruct(q.shape, q.dtype)
    if parts:
        out_specs = [out_block, pl.BlockSpec(
            (1, 1, 1, 2, rows), lambda bi, h, qi, j, *_: (bi, h, qi, 0, 0))]
        out_shape = [jax.ShapeDtypeStruct(q.shape, jnp.float32),
                     jax.ShapeDtypeStruct((b, nkv, nq, 2, rows), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, group, t.q, hd), q_at),
            pl.BlockSpec((1, 1, 1, hd, t.k), k_at),
            pl.BlockSpec((1, 1, 1, t.k, hd), v_at),
            pl.BlockSpec((1, 2, t.k), seen_at),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, scale=scale, t=t, group=group),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_flash._interpret(),
    )
    out = call(jnp.reshape(li, (1,)).astype(jnp.int32), q_pos0,
               live.reshape(-1), named.reshape(-1), q, k_cache, v_cache,
               seen)
    if not parts:
        return out
    acc, stats = out
    m, l = (stats[:, :, :, i].reshape(b, nkv, nq, group, t.q).transpose(
        0, 1, 3, 2, 4).reshape(b, nkv, group, s) for i in (0, 1))
    return acc, m, l
