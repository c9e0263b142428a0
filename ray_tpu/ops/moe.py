"""Mixture-of-Experts layer: top-k router + capacity-bounded dispatch +
grouped expert FFN (GShard/Switch formulation).

The reference framework has NO expert parallelism (SURVEY.md §2.4 —
verified absent); this is TPU-native core-op territory. Design follows
the GShard/Mesh-TF einsum recipe rather than a scatter/gather kernel:

* routing produces a dispatch one-hot [tokens, E, C] and combine weights;
* expert inputs form via one einsum, the expert FFN is a single grouped
  matmul ("ecd,edh->ech") over a leading expert dim, outputs combine via
  another einsum;
* under GSPMD the expert dim carries the `expert` mesh axis, so XLA
  lowers the dispatch/combine einsums to all_to_all over ICI and the
  grouped matmul to per-device expert shards — no hand-written
  collectives, static shapes throughout (capacity bounds make it
  jit-compatible; overflow tokens are dropped, the standard trade).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    # aux load-balancing loss weight (Switch Transformer eq. 4)
    aux_loss_weight: float = 0.01


def init_moe_params(key: jax.Array, dim: int, hidden_dim: int,
                    cfg: MoEConfig, dtype=jnp.float32) -> dict:
    """Router + per-expert SwiGLU FFN weights (stacked on a leading
    expert axis, the EP analog of the stacked-layers scan trick)."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    e, d, h = cfg.num_experts, dim, hidden_dim

    def dense(rng, shape, fan_in):
        return (jax.random.normal(rng, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    return {
        "router": dense(k1, (d, e), d),
        "w_gate": dense(k2, (e, d, h), d),
        "w_up": dense(k3, (e, d, h), d),
        "w_down": dense(k4, (e, h, d), h),
    }


def moe_logical_axes() -> dict:
    return {
        "router": ("embed", "expert_logits"),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def _route(router_logits: jax.Array, cfg: MoEConfig, capacity: int):
    """router_logits [T, E] -> (dispatch [T, E, C] bool-ish f32,
    combine [T, E, C] f32, aux_loss scalar).

    Top-k routing with per-expert capacity: the c-th token routed to an
    expert takes slot c; tokens beyond capacity are dropped (their
    combine weight is 0 and the residual path carries them).
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    # top-k expert choices per token
    top_probs, top_idx = jax.lax.top_k(probs, cfg.top_k)     # [T, k]
    # renormalize chosen gates so they sum to 1 (Mixtral convention)
    top_probs = top_probs / jnp.maximum(
        top_probs.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss: mean prob per expert x fraction routed
    onehot_topk = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [T,k,E]
    routed_frac = onehot_topk.sum(axis=(0, 1)) / (T * cfg.top_k)
    mean_prob = probs.mean(axis=0)
    aux_loss = E * jnp.sum(routed_frac * mean_prob)

    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    # position of each (token, choice) within its expert's queue:
    # cumulative count of earlier assignments to the same expert
    for k in range(cfg.top_k):
        onehot = onehot_topk[:, k, :]                          # [T, E]
        if k == 0:
            prior = jnp.zeros((T, E), jnp.float32)
        else:
            prior = onehot_topk[:, :k, :].sum(axis=1)
        # earlier tokens' assignments (all k slots) + this token's
        # earlier-k assignments
        pos_within = (jnp.cumsum(onehot_topk.sum(axis=1), axis=0)
                      - onehot_topk.sum(axis=1)) + prior       # [T, E]
        slot = (pos_within * onehot).sum(-1).astype(jnp.int32)  # [T]
        keep = (pos_within * onehot).sum(-1) < capacity
        slot_oh = jax.nn.one_hot(jnp.where(keep, slot, capacity),
                                 capacity + 1,
                                 dtype=jnp.float32)[:, :capacity]  # [T, C]
        d_k = onehot[:, :, None] * slot_oh[:, None, :]          # [T, E, C]
        dispatch = dispatch + d_k
        combine = combine + d_k * top_probs[:, k][:, None, None]
    return dispatch, combine, aux_loss


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig,
            activation=jax.nn.silu) -> tuple[jax.Array, jax.Array]:
    """x: [b, s, d] -> (out [b, s, d], aux_loss scalar).

    Static-shape capacity dispatch; the grouped matmuls keep a leading
    [E] dim that GSPMD shards over the `expert` mesh axis. Routing is
    per batch row ("group" in GShard terms) so the one-hot dispatch
    tensor is [b, s, E, C] with C ~ s/E — bounded, not O((b*s)^2/E).
    """
    b, s, d = x.shape
    E = cfg.num_experts
    capacity = max(1, int(cfg.capacity_factor * cfg.top_k * s / E))
    router_logits = jnp.einsum(
        "gsd,de->gse", x.astype(jnp.float32),
        params["router"].astype(jnp.float32))
    dispatch, combine, aux_loss = jax.vmap(
        lambda lg: _route(lg, cfg, capacity))(router_logits)
    aux_loss = aux_loss.mean()

    dt = x.dtype
    # dispatch: [g, s, E, C] x [g, s, d] -> expert inputs [E, g, C, d]
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch.astype(dt), x)
    # grouped SwiGLU FFN over the leading expert dim
    gate = activation(jnp.einsum(
        "egcd,edh->egch", expert_in, params["w_gate"].astype(dt)))
    up = jnp.einsum("egcd,edh->egch", expert_in, params["w_up"].astype(dt))
    expert_out = jnp.einsum(
        "egch,ehd->egcd", gate * up, params["w_down"].astype(dt))
    # combine: [g, s, E, C] x [E, g, C, d] -> [g, s, d]
    out = jnp.einsum("gsec,egcd->gsd", combine.astype(dt), expert_out)
    return out, aux_loss * cfg.aux_loss_weight


# --------------------------------------------------------------------------
# Dropless expert layer for one shard of the experts (serving)
# --------------------------------------------------------------------------
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
                     first: int = 0, *, valid: jax.Array | None = None,
                     block_rows: int | None = None):
    """The part of a routed expert layer that THIS holder of experts
    computes: ``sum_j weights[t, j] * SwiGLU_e(x[t])`` over the chosen
    experts e = chosen[t, j] that lie in [first, first + held), `held`
    the leading axis of the stacked weights (w_gate, w_up ``[held, d,
    f]``, w_down ``[held, f, d]``). What the other experts would add is
    some other holder's (under a mesh with an `expert` axis this function
    is what each shard runs; the exchange between shards is not here).

    No capacity and no dropped token. The token-expert pairs are laid
    out by expert, each expert's group padded to whole tiles of
    `block_rows` rows, and a loop over the tiles THAT EXIST (its trip
    count is decided on the device) multiplies each by its expert's
    matrices and adds its rows, weighted in float32, into their tokens'
    rows of y: an expert no token chose is never read, one that many
    chose takes as many tiles as it needs, and nothing of T x k rows of
    d is written, gathered or summed; only the layout's integers are
    sized for the worst case (every pair on one held expert). x: [T, d];
    chosen, weights: [T, k]; valid: [T] bool or None, rows that are no
    token (padding, a slot that holds no request) and reach no expert.
    Returns (y [T, d] float32, pairs computed, held experts hit, tiles
    walked)."""
    T, d = x.shape
    k = chosen.shape[1]
    held = w_gate.shape[0]
    bm = block_rows or (16 if T <= 64 else 128)
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
    # a padding row is no token: its index lies past y and its add is
    # dropped
    slot_token = jnp.full((n_slots,), T, jnp.int32).at[slot].set(
        token, mode="drop")
    slot_weight = jnp.zeros((n_slots,), jnp.float32).at[slot].set(
        weights.reshape(pairs).astype(jnp.float32), mode="drop")
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(n_slots // bm) * bm, side="right"), held - 1)
    tiles = ends[-1] // bm
    # the scatter pays for a padding row what it pays for a token's (0.2
    # us of 7,168 float32; my chip run, PR 47): a tile's rows are added
    # 16 at a time, as far as its tokens reach
    sub = math.gcd(bm, 16)

    def tile(i, y):
        e = tile_expert[i]
        rows = jax.lax.dynamic_slice_in_dim(slot_token, i * bm, bm)
        g = jax.lax.dynamic_slice_in_dim(slot_weight, i * bm, bm)
        xt = jnp.take(x, rows, axis=0, mode="clip")
        pick = lambda w: jax.lax.dynamic_index_in_dim(
            w, e, 0, keepdims=False).astype(x.dtype)
        h = jax.nn.silu(xt @ pick(w_gate)) * (xt @ pick(w_up))
        out = (h @ pick(w_down)).astype(jnp.float32) * g[:, None]

        def add(j, y):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, j * sub, sub)
            return y.at[cut(rows)].add(cut(out), mode="drop")

        if bm == sub:  # a decode step's tile: one add, no loop around it
            return add(0, y)
        return jax.lax.fori_loop(0, -(-(rows < T).sum() // sub), add, y)

    # zeros that depend on x: as a bare constant the compiler merges the
    # layers' buffers into one broadcast that carries no scope's name
    # (84 MB a layer a chunk, a quarter of the cell's unscoped device
    # time; my chip run, PR 32)
    y = jax.lax.fori_loop(0, tiles, tile, jnp.broadcast_to(
        (x[:1, :1] * jnp.zeros((), x.dtype)).astype(jnp.float32), (T, d)))
    return y, mine.sum().astype(jnp.int32), (counts > 0).sum().astype(
        jnp.int32), tiles
