"""Grouped-query attention of a prefill chunk against a deep cache
(Pallas/TPU).

`s` new tokens of a row against layer `li` of a STACKED cache as it lies
(K ``[layers, b, kv_heads, hd, keys]``, V ``[layers, b, kv_heads, keys,
hd]``: `models/llama.init_kv_cache`'s orders), the chunk's own rows
among the keys. What XLA makes of the plain form
(`ops/attention.cached_attention`) holds a layer's scores whole, float32
``[kv_heads, queries, group, keys]``: 4.6 GB for 1,024 queries of 48
heads against 23,552 keys. Here a tile of scores lives and dies in VMEM.

Whom a query attends to is decided from POSITIONS, not from a mask that
is an input: key column c of the cache holds position ``k_pos[row, c]``
(the column's own index in a cache laid out by position; a ring's
columns followed by the chunk's say their own), query i of the chunk
stands at ``q_pos0 + i``, and a key counts iff ``start[row] <= k <= q``
and ``q - k < window``. One kernel so serves a full layer (no window,
keys to the cache's depth) and a sliding one (the ring as the chunk
found it, then the chunk).

Grid (row, kv head, query tile, key tile), the key axis innermost, so
the running softmax of a kv head's `group` query heads stays in VMEM
scratch across the keys: the group's heads are rows of ONE product a
tile (``[group * tq, hd] x [hd, tk]``), so no GQA repeat of K or V
exists and a group of 6 or 9 fills the array as well as a power of two
would. The layer, the chunk's first position, the rows' `start` and two
small tables are scalar-prefetch operands: `live` says which (query
tile, key tile) pairs hold a pair that counts, `named` which key tile a
grid step fetches. A pair with none is not computed (`pl.when`) and
names the tile before it, which is in VMEM already: nothing is copied
for what causality, the window, the left padding or the depth still
unwritten leave empty (`tile_tables`, computed from each tile's least
and greatest position; `live_tiles` is the same rule on the host, for
the counters).

Scores, running max, sum and accumulator in float32; probabilities cast
to V's dtype before the value product; a query with nothing to attend
to (left padding) gives zeros: the precisions and the edge cases of the
XLA path. No backward.
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


def live_tiles(xp, k_pos, start, q_pos0, queries: int, t: Tiles,
               window: int):
    """[..., query tiles, key tiles] bool: the tile holds a pair of query
    and key that counts. k_pos [..., keys], start [...]; `xp` is numpy
    or jax.numpy. From each key tile's least position not before `start`
    and its greatest: exact where a tile's positions are a run of
    consecutive ones (a cache by position, a full ring, a chunk)."""
    nq = queries // t.q
    kp = k_pos.reshape(k_pos.shape[:-1] + (-1, t.k))
    first = xp.asarray(start)[..., None]
    seen = kp >= first[..., None]
    k_min = xp.where(seen, kp, NO_WINDOW).min(-1)[..., None, :]
    k_max = xp.where(seen, kp, -NO_WINDOW).max(-1)[..., None, :]
    q0 = q_pos0 + xp.arange(nq) * t.q                  # a tile's first query
    q_lo = xp.maximum(q0, first)[..., :, None]
    q_hi = (q0 + t.q - 1)[:, None]
    return (q_hi >= q_lo) & (k_min <= q_hi) & (k_max > q_lo - window)


def tile_tables(k_pos: jax.Array, start: jax.Array, q_pos0, queries: int,
                t: Tiles, window: int):
    """(live [b, query tiles, key tiles] int32; named, same shape: the
    key tile to hold at that grid step: itself where live, else the
    nearest live one before it in the query tile's walk, else the first
    live one, so that steps that compute nothing copy nothing)."""
    live = live_tiles(jnp, k_pos, start, q_pos0, queries, t, window)
    nk = live.shape[-1]
    at = jnp.where(live, jnp.arange(nk), -1)
    before = jax.lax.cummax(at, axis=at.ndim - 1)
    named = jnp.where(before >= 0, before,
                      jnp.argmax(live, axis=-1)[..., None])
    return live.astype(jnp.int32), named.astype(jnp.int32)


def keys_visited(k_pos: np.ndarray, start: int, q_pos0: int, queries: int,
                 t: Tiles, window: int) -> int:
    """Pairs (query, key) whose scores the kernel computes for one row:
    the live tiles' (host side, for the engine's counters)."""
    return t.q * t.k * int(live_tiles(np, k_pos, start, q_pos0, queries, t,
                                      window).sum())


def _kernel(li_ref, q0_ref, start_ref, live_ref, named_ref, q_ref, k_ref,
            v_ref, kp_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float,
            window: int, t: Tiles, group: int):
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
        q_pos = q0_ref[0] + qi * t.q + jax.lax.broadcasted_iota(
            jnp.int32, (t.q, t.k), 0)
        k_pos = kp_ref[0]                                      # [1, tk]
        seen = ((k_pos <= q_pos) & (k_pos >= start_ref[bi])
                & (q_pos - k_pos < window))
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
        # a query with nothing to attend to: 0 / 1e-30, zeros
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[:, 0:1], 1e-30)
                       ).reshape(o_ref.shape[2:]).astype(o_ref.dtype)


def gqa_chunk_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        li, k_pos: jax.Array, start: jax.Array, q_pos0, *,
                        scale: float, t: Tiles,
                        window: int | None = None) -> jax.Array:
    """q ``[b, kv_heads, group, s, hd]`` (rotated), the chunk's queries at
    positions ``q_pos0 + [0, s)``; k_cache ``[layers, b, kv_heads, hd,
    n]`` and v_cache ``[layers, b, kv_heads, n, hd]``, of which layer
    `li` is attended and which hold the chunk's own rows already; k_pos
    ``[b, n]`` int32, the position each column holds; start ``[b]``, the
    rows' first real positions. `t` from `tiles`. Returns ``[b, kv_heads,
    group, s, hd]`` in q's dtype: softmax over the keys with ``start <= k
    <= q`` and ``q - k < window`` of q . k * scale, times v; zeros for a
    query with none."""
    b, nkv, group, s, hd = q.shape
    n = k_cache.shape[4]
    assert s % t.q == 0 and n % t.k == 0, (q.shape, k_cache.shape, t)
    assert v_cache.shape[3:] == (n, hd) and k_pos.shape == (b, n)
    nq, nk = s // t.q, n // t.k
    window = NO_WINDOW if window is None else int(window)
    k_pos = k_pos.astype(jnp.int32)
    start = start.astype(jnp.int32)
    live, named = tile_tables(k_pos, start, q_pos0, s, t, window)

    def at(bi, qi, j, named_ref):
        return named_ref[(bi * nq + qi) * nk + j]

    def q_at(bi, h, qi, j, *_):
        return bi, h, 0, qi, 0

    def k_at(bi, h, qi, j, li_ref, q0, st, live_ref, named_ref):
        return li_ref[0], bi, h, 0, at(bi, qi, j, named_ref)

    def v_at(bi, h, qi, j, li_ref, q0, st, live_ref, named_ref):
        return li_ref[0], bi, h, at(bi, qi, j, named_ref), 0

    def pos_at(bi, h, qi, j, li_ref, q0, st, live_ref, named_ref):
        return bi, 0, at(bi, qi, j, named_ref)

    rows = group * t.q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, nkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, group, t.q, hd), q_at),
            pl.BlockSpec((1, 1, 1, hd, t.k), k_at),
            pl.BlockSpec((1, 1, 1, t.k, hd), v_at),
            pl.BlockSpec((1, 1, t.k), pos_at),
        ],
        out_specs=pl.BlockSpec((1, 1, group, t.q, hd), q_at),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, t=t,
                          group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_flash._interpret(),
    )
    return call(jnp.reshape(li, (1,)).astype(jnp.int32),
                jnp.reshape(q_pos0, (1,)).astype(jnp.int32), start,
                live.reshape(-1), named.reshape(-1), q, k_cache, v_cache,
                k_pos[:, None, :])
