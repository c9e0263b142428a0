"""Dropless routed-expert layer for one holder of experts: the `noaux_tc`
router over all routed experts (`route_sigmoid_topk`) and the part of
the layer that the holder of some of them computes (`held_experts_ffn`).

No capacity and no dropped token: the token-expert pairs are grouped by
expert and one grouped-matmul kernel (ops/pallas/held_experts.py) walks
the tiles that exist, each against its expert's matrices as they stream
in. The models that route (`models/dots3_note.py`, and
`models/kimi_k2.py` and `models/laguna.py` through its `_ffn`) hold a
shard of the experts on one chip; the exchange between holders under a
mesh with an `expert` axis is not here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas.held_experts import held_experts


def route_sigmoid_topk(x: jax.Array, router: jax.Array, bias: jax.Array,
                       top_k: int, *, normalize: bool = True,
                       scaling: float = 1.0):
    """The `noaux_tc` router over ALL routed experts, in float32: scores
    ``s = sigmoid(x W_r)`` [T, E], the `top_k` experts of largest
    ``s + bias`` (the bias moves the choice and nothing else), and their
    weights ``s_e`` normalised over the chosen ones times `scaling`.
    Returns (scores [T, E], chosen [T, k] int32, weights [T, k])."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / weights.sum(-1, keepdims=True)
    return scores, chosen.astype(jnp.int32), weights * scaling


def held_experts_ffn(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     first: int = 0, *, valid: jax.Array | None = None):
    """The part of a routed expert layer that THIS holder of experts
    computes: ``sum_j weights[t, j] * SwiGLU_e(x[t])`` over the chosen
    experts e = chosen[t, j] that lie in [first, first + held), `held`
    the leading axis of the stacked weights (w_gate, w_up ``[held, d,
    f]``, w_down ``[held, f, d]``). What the other experts would add is
    some other holder's (under a mesh with an `expert` axis this function
    is what each shard runs; the exchange between shards is not here).

    No capacity and no dropped token. The token-expert pairs are laid
    out by expert, each expert's group padded to whole tiles of 16 rows
    (a decode step's T) or 128 (a chunk's), and ONE kernel
    (ops/pallas/held_experts.py) walks the tiles THAT EXIST: it reads
    each tile's expert where the stacks lie, the next tile's matrices in
    flight while this one's multiply, gathers the tile's rows of x and
    adds its results, weighted in float32, into their tokens' rows of y
    on the chip's own memory. An expert no token chose is never read,
    one that many chose takes as many tiles as it needs, and nothing of
    T x k rows of d is written, gathered or summed; only the layout's
    integers are sized for the worst case (every pair on one held
    expert). x: [T, d]; chosen, weights: [T, k]; valid: [T] bool or
    None, rows that are no token (padding, a slot that holds no request)
    and reach no expert. Returns (y [T, d] float32, pairs computed, held
    experts hit, tiles walked)."""
    T, d = x.shape
    k = chosen.shape[1]
    held = w_gate.shape[0]
    bm = 16 if T <= 64 else 128
    pairs = T * k
    n_slots = -(-pairs // bm) * bm + held * bm

    local = chosen.reshape(pairs) - first
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & jnp.repeat(valid, k)
    local = jnp.where(mine, local, held)
    onehot = (local[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    counts = onehot.sum(0)                                    # [held]
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
    ends = jnp.cumsum(-(-counts // bm) * bm)
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    slot = jnp.where(mine, offsets[jnp.minimum(local, held - 1)] + rank,
                     n_slots)                                 # [pairs]
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    # a padding row is no token: the kernel stops at the tile's count
    slot_token = jnp.zeros((n_slots,), jnp.int32).at[slot].set(
        token, mode="drop")
    slot_weight = jnp.zeros((n_slots,), jnp.float32).at[slot].set(
        weights.reshape(pairs).astype(jnp.float32), mode="drop")
    tile_start = jnp.arange(n_slots // bm) * bm
    # compared with every end: the default's binary search is a `while`
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, tile_start, side="right", method="compare_all"), held - 1)
    # a group fills its tiles from the first row on: a tile's tokens are
    # its first `tile_rows` rows, none past the tiles that exist
    tile_rows = jnp.clip(counts[tile_expert] - (
        tile_start - offsets[tile_expert]), 0, bm)
    tiles = ends[-1] // bm
    y = held_experts(x, w_gate, w_up, w_down, tile_expert, tile_rows, tiles,
                     slot_token, slot_weight)
    return y, mine.sum().astype(jnp.int32), (counts > 0).sum().astype(
        jnp.int32), tiles
