"""EvaByte: a byte-level decoder whose attention keeps exact keys and
values for the current WINDOW of `window_size` positions only, and for
everything before it one summary key and one summary value for each
CHUNK of `chunk_size` positions (EVA: Zheng et al., "Efficient Attention
via Control Variates", ICLR 2023; the EvaByte release, HKU NLP and
SambaNova, 2025-01). The block is llama's (RMSNorm, RoPE on rotated
halves, SwiGLU) with MHA, the norm's weight held as an offset from one,
a float32 residual stream, and `n_pred_heads` output heads of which head
i is the distribution of byte t + 1 + i; the served stream samples head 0.

Per head h the layer adds two learned vectors phi_h, mu_h. With t a
request's own position from 0, w(t) = t // window_size, chunk j the
positions [c j, c j + c):

  a_{j,s} = softmax over s in chunk j of (phi_h . k_s - |k_s|^2 / 2)
  v~_j    = sum_s a_{j,s} v_s          k~_j = (1/c) sum_s k_s + mu_h
  query t attends exactly to E_t = {s : w(s) = w(t), s <= t} and through
  (k~_j, v~_j) to C_t = {j : chunk j lies in a window before w(t)}: one
  float32 softmax over both.

The cache, a row and layer, is ONE position axis of `window_size +
summaries(max_len)` in each of K ``[layers, b, heads, hd, n]`` and V
``[layers, b, heads, n, hd]`` (llama's orders, so that the decode kernel
the models share reads it as it lies):

  index W - 1 - (t mod W)   the exact key of position t (the window's
                            rows fill DOWNWARD from W - 1)
  index W + j               the summary of chunk j (UPWARD from W)

so what query t attends to is one contiguous range, [W - 1 - t mod W,
W + (W / c) w(t) - 1]: `ops/pallas/decode_attention.py` takes it as a
row's [start, length]. No row moves at a window's end: the range's lower
end jumps back to W - 1 and its upper end rises by W / c, a comparison.
A cache made for a shallower `max_len` has fewer summaries and the same
origin, so the engine's `insert_row` grafts it as it lies. Nothing of it
is as deep as the context: `CACHE_LEN_AXIS` is empty, every leaf is
grafted whole, and the engine keeps no prefix store for this model.

A decode step writes k_t, v_t; a row whose t is the last position of
its chunk then folds the chunk, once: the summary is computed from the
chunk's rows in the window and written at W + j, final from that step
on and visible only once the window has ended. A row in mid-chunk reads
and writes nothing for it: the slot of an open chunk, which no query's
range holds, keeps what it had. Then every row attends. A prefill chunk
reads the window's rows as it found them, attends its queries to those
of their own window, to the chunk's own keys of their own window and to
the summaries of earlier windows INCLUDING one that ends inside the
chunk, and then lays its rows over the window's. Positions before
cache["start"] are left padding: no bytes, in no window, chunk or
summary.

On a TPU, for shapes whole in its tiles, a chunk's attention is two
calls a layer of ops/pallas/gqa_chunk_attention.py, which keeps a tile
of scores in VMEM and walks only the tiles that hold a pair that counts:
one over the leaf AS FOUND, read where it lies in the stack (the
window's rows, each seen to its window's end; the summaries of chunks
that ended before the call, each seen from its window's end on), one
over the call's OWN rows followed by the summaries it has just made
(`_seen_by` says from where to where each column is seen; `_chunk_plan`
makes the tables once a call, for every layer). Each returns its part
of the one softmax (values, running max, sum) and `_merged` divides.
Elsewhere (every CPU run) the same two parts are plain jax.numpy over
the window and the chunk whole and over every summary row. Which it is
follows from the platform and the shapes: nothing selects it.

Scopes beside llama's `attn_qkv`, `attn_out`, `mlp`, `embed`, `lm_head`:
`eva_window_attn` (a decode step's one kernel over the whole range, the
window's rows written; of a chunk the kernel call over its own rows and
the summaries it made, and the write of the window's rows; in the plain
form the scores and values over E), `eva_chunk_attn` (of a chunk the
kernel call over the leaf as found and the merge of the two parts; in
the plain form the scores and values over C and the merge),
`eva_summarise` (k~, v~ computed, and of a chunk written, the columns
of the chunks it holds bytes of and no other; in a decode step the loop
over the rows that close a chunk, their k~, v~ computed and written).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ray_tpu.ops import attention as _attention
from ray_tpu.ops.attention import decode_attention, decode_block_len
from ray_tpu.ops.pallas import gqa_chunk_attention as _chunk
from ray_tpu.ops.rope import apply_rope, rope_frequencies

NEG = -1e30
TENSOR_PARALLEL = False
# std of phi and mu, float32: they move a summary and never dominate it
EVA_VECTOR_STD = 0.02


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    hidden_dim: int = 11008
    max_seq_len: int = 32768
    window_size: int = 2048
    chunk_size: int = 16
    n_pred_heads: int = 8
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.dim % self.n_heads or self.window_size % self.chunk_size:
            raise ValueError("heads must divide dim and chunk_size "
                             "window_size")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def chunks_per_window(self) -> int:
        return self.window_size // self.chunk_size

    def summaries(self, max_len: int | None = None) -> int:
        """Summary rows of a cache for `max_len` positions: whole
        windows' worth, so that a chunk's index never leaves the leaf."""
        return (-(-(max_len or self.max_seq_len) // self.window_size)
                * self.chunks_per_window)


def from_published(config: dict, **overrides) -> EvaByteConfig:
    """The program's config from the published keys."""
    if config.get("attention_class", "eva") != "eva" or (
            config["num_key_value_heads"] != config["num_attention_heads"]):
        raise ValueError("EVA attention over one key head a query head")
    kw = dict(vocab_size=int(config["vocab_size"]),
              dim=int(config["hidden_size"]),
              n_layers=int(config["num_hidden_layers"]),
              n_heads=int(config["num_attention_heads"]),
              hidden_dim=int(config["intermediate_size"]),
              max_seq_len=int(config["max_position_embeddings"]),
              window_size=int(config["window_size"]),
              chunk_size=int(config["chunk_size"]),
              n_pred_heads=int(config["num_pred_heads"]),
              rope_theta=float(config["rope_theta"]),
              norm_eps=float(config["rms_norm_eps"]))
    kw.update(overrides)
    return EvaByteConfig(**kw)


# ------------------------------------------------------------------- params
def init_params(cfg: EvaByteConfig, key: jax.Array) -> dict:
    """Every matrix N(0, 1/fan_in) in the parameter dtype, the norms'
    offsets 0, phi and mu N(0, EVA_VECTOR_STD^2) in float32; per-layer
    weights stacked on a leading [n_layers] axis."""
    pd = cfg.param_dtype
    d, f, L, H, hd = (cfg.dim, cfg.hidden_dim, cfg.n_layers, cfg.n_heads,
                      cfg.head_dim)
    k = iter(jax.random.split(key, 11))

    def dense(shape, fan_in):
        return (jax.random.normal(next(k), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(pd)

    def vector():
        return jax.random.normal(next(k), (L, H, hd),
                                 jnp.float32) * EVA_VECTOR_STD

    return {
        "embed": dense((cfg.vocab_size, d), d),
        "layers": {
            "wq": dense((L, d, d), d), "wk": dense((L, d, d), d),
            "wv": dense((L, d, d), d), "wo": dense((L, d, d), d),
            "phi": vector(), "mu": vector(),
            "w_gate": dense((L, d, f), d), "w_up": dense((L, d, f), d),
            "w_down": dense((L, f, d), f),
            "attn_norm": jnp.zeros((L, d), pd),
            "mlp_norm": jnp.zeros((L, d), pd)},
        "final_norm": jnp.zeros((d,), pd),
        "lm_head": dense((d, cfg.n_pred_heads * cfg.vocab_size), d),
    }


def param_logical_axes(cfg: EvaByteConfig) -> dict:
    return {"embed": ("vocab", "embed"),
            "layers": {"wq": ("layers", "embed", "heads"),
                       "wk": ("layers", "embed", "kv_heads"),
                       "wv": ("layers", "embed", "kv_heads"),
                       "wo": ("layers", "heads", "embed"),
                       "phi": ("layers", None, None),
                       "mu": ("layers", None, None),
                       "w_gate": ("layers", "embed", "mlp"),
                       "w_up": ("layers", "embed", "mlp"),
                       "w_down": ("layers", "mlp", "embed"),
                       "attn_norm": ("layers", None),
                       "mlp_norm": ("layers", None)},
            "final_norm": (None,), "lm_head": ("embed", "vocab")}


# -------------------------------------------------------------------- cache
# No leaf is as deep as the context: both are grafted whole.
CACHE_LEN_AXIS: dict = {}


def _kind_bytes(cfg: EvaByteConfig, leaf) -> dict:
    """A leaf's bytes by kind: the window's rows and the summaries share
    its position axis."""
    n = leaf.shape[-1] * leaf.shape[-2] // cfg.head_dim
    per = leaf.size * leaf.dtype.itemsize // n
    return {"window": per * cfg.window_size,
            "summary": per * (n - cfg.window_size)}


# what `LLMEngine.stats()["cache_bytes"]` files each leaf under
CACHE_KIND = {"k": _kind_bytes, "v": _kind_bytes}


def init_cache(cfg: EvaByteConfig, batch: int,
               max_len: int | None = None) -> dict:
    """An empty cache (the module's docstring has the layout): its depth
    follows `max_len` only by the summaries, one a chunk."""
    n = cfg.window_size + cfg.summaries(max_len)
    lead = (cfg.n_layers, batch, cfg.n_heads)
    return {"k": jnp.zeros(lead + (cfg.head_dim, n), cfg.dtype),
            "v": jnp.zeros(lead + (n, cfg.head_dim), cfg.dtype),
            "length": jnp.zeros((), jnp.int32),
            "start": jnp.zeros((batch,), jnp.int32)}


def cache_logical_axes(cfg: EvaByteConfig) -> dict:
    return {"k": ("layers", "batch", "kv_heads", "head_dim", None),
            "v": ("layers", "batch", "kv_heads", None, "head_dim"),
            "length": (), "start": ("batch",)}


def _read_block(cfg: EvaByteConfig) -> int | None:
    return decode_block_len(
        cfg.n_heads, cfg.head_dim, cfg.window_size + cfg.summaries(),
        cfg.dtype, jax.sharding.get_abstract_mesh())


def decode_read_block(cfg: EvaByteConfig, mesh) -> int | None:
    """None: the engine counts `decode_kv_positions_read` in positions of
    the context, which this cache does not have; what a step reads of
    the window's rows and of the summaries is `decode_counters`'."""
    return None


def decode_counters(cfg: EvaByteConfig, spans: list, rows: int) -> dict:
    """What one decode step of `rows` rows does for live rows at `spans`
    [(start, the position the step writes)], summed over the layers:
    `_live`, the window's rows and the summaries its queries attend to;
    `_read`, those the step is asked to read: the kernel's blocks that
    overlap a row's range, or both parts whole for every row where
    nothing bounds the read. `windows_folded`: rows whose query is the
    first of a window, so that the window before it has just become its
    summaries. `chunks_folded`: rows whose written position is the last
    of its chunk, the rows the step folds (one in `chunk_size` of the
    live rows stepped, over a long run)."""
    W, cpw, L = cfg.window_size, cfg.chunks_per_window, cfg.n_layers
    c = cfg.chunk_size
    t = np.asarray([last - start for start, last in spans], np.int64)
    win_live, sum_live = t % W + 1, cpw * (t // W)
    block = _read_block(cfg)
    if block:
        win_read = W - (W - 1 - t % W) // block * block
        sum_read = -(-sum_live // block) * block
    else:
        win_read, sum_read = rows * W, rows * cfg.summaries()
    return {"decode_window_positions_live": L * int(win_live.sum()),
            "decode_summaries_live": L * int(sum_live.sum()),
            "decode_window_positions_read": L * int(np.sum(win_read)),
            "decode_summaries_read": L * int(np.sum(sum_read)),
            "windows_folded": int(((t > 0) & (t % W == 0)).sum()),
            "chunks_folded": int((t % c == c - 1).sum())}


def _groups(cfg: EvaByteConfig, s: int) -> int:
    """The chunks a call of `s` positions can lie in, wherever it
    starts."""
    return (s + cfg.chunk_size - 2) // cfg.chunk_size + 1


def _chunk_tiles(cfg: EvaByteConfig, s: int, n: int):
    """The chunk kernel's tiles for a call of `s` queries: (those against
    a leaf of `n` columns as found; those against the call's own rows
    followed by the summaries it makes; the columns those summaries are
    padded to, whole key tiles), or None where the plain form stays: off
    a TPU, and for shapes not whole in any tile."""
    if not _attention._on_tpu():
        return None
    found = _chunk.tiles(1, cfg.head_dim, s, n)
    own = _chunk.tiles(1, cfg.head_dim, s, s)
    if found is None or own is None:
        return None
    return found, own, -(-_groups(cfg, s) // own.k) * own.k


def _seen_by(xp, cfg: EvaByteConfig, t0, s: int, S: int, made: int):
    """From which own positions to which each key column of a call's
    two kernel calls is seen, as ops/pallas/gqa_chunk_attention.py takes
    it (first > last: by none). `t0` [...] is the own position of the
    call's first query (negative: left padding), `s` its queries, `S`
    the leaf's summary columns, `made` the columns of the summaries the
    call makes (chunk ``first // c + g`` in column g). Returns (the leaf
    as found [..., 2, W + S]: a row of the first query's window from
    before the call to that window's end, the summary of a chunk that
    ended before the call from its window's end on; the call's own [...,
    2, s + made]: a byte's key from itself to its window's end, a
    summary the call makes of bytes it holds from its window's end on).
    `xp` is numpy or jax.numpy."""
    W, c, cpw = cfg.window_size, cfg.chunk_size, cfg.chunks_per_window
    never = ever = _chunk.NO_WINDOW
    t0 = xp.asarray(t0)[..., None]
    first = xp.maximum(t0, 0)
    j0 = first // c

    def cols(seen_from, to):
        return xp.stack(xp.broadcast_arrays(seen_from, xp.asarray(to)), -2)

    res = W - 1 - xp.arange(W)           # index i holds residue W - 1 - i
    w0 = W * (first // W)
    j = xp.arange(S)
    p = t0 + xp.arange(s)
    jm = j0 + xp.arange(made)
    found = xp.concatenate([
        cols(xp.where(res < first % W, w0 + res, never), w0 + W - 1),
        cols(xp.where(j < j0, W * (j // cpw + 1), never), ever)], -1)
    own = xp.concatenate([
        cols(xp.where(p >= 0, p, never), W * (p // W + 1) - 1),
        cols(xp.where(c * jm < t0 + s, W * (jm // cpw + 1), never), ever)],
        -1)
    return found, own


def prefill_counters(cfg: EvaByteConfig, start: int, pos: int, chunk: int,
                     depth: int) -> dict:
    """What the attention of one prefill call does: `chunk` queries at
    positions [pos, pos + chunk) of a row whose first byte lies at
    `start`, in a cache made for `depth` positions. Pairs of query and
    key summed over the layers: `_visible`, those a query attends to (its
    window's rows up to itself, the summaries of the windows before),
    and `_visited`, those whose scores are computed: under the kernel
    the live tiles' (`_chunk_tiles`, by the rule the device's tables
    follow), filed by what the key column holds, a window's row or a
    summary; in the plain form the window as the call found it and the
    call's own keys, and every summary row, for every query."""
    W, cpw, L = cfg.window_size, cfg.chunks_per_window, cfg.n_layers
    S = cfg.summaries(depth)
    t = np.arange(max(pos, start), pos + chunk, dtype=np.int64) - start
    tl = _chunk_tiles(cfg, chunk, W + S)
    if tl is None:
        window, summaries = chunk * (W + chunk), chunk * S
    else:
        found, own = _seen_by(np, cfg, pos - start, chunk, S, tl[2])
        found = _chunk.keys_visited(found, pos - start, chunk, tl[0])
        own = _chunk.keys_visited(own, pos - start, chunk, tl[1])
        window = int(found[:W].sum() + own[:chunk].sum())
        summaries = int(found[W:].sum() + own[chunk:].sum())
    return {
        "prefill_window_keys_visible": L * int((t % W + 1).sum()),
        "prefill_window_keys_visited": L * window,
        "prefill_summaries_visible": L * int((cpw * (t // W)).sum()),
        "prefill_summaries_visited": L * summaries,
        "windows_folded": int(((t > 0) & (t % W == 0)).sum())}


# --------------------------------------------------------------------- step
def _norm(x, g, eps: float, dt):
    """RMSNorm of the float32 stream, the weight an offset from one."""
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (y * (1.0 + g.astype(jnp.float32))).astype(dt)


def _summarise(layer, k, v, ok, c: int):
    """k, v [b, g, c, H, hd], g chunks of c positions of which `ok` [b,
    g, c] are bytes the chunk has -> (k~, v~) each [b, g, H, hd], in the
    cache's dtype. float32 from the keys as the cache holds them."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    has = ok[..., None]
    score = (jnp.einsum("hd,bgchd->bgch", layer["phi"], k32)
             - 0.5 * (k32 * k32).sum(-1))
    score = jnp.where(has, score, NEG)
    e = jnp.exp(score - score.max(2, keepdims=True)) * has
    a = e / jnp.maximum(e.sum(2, keepdims=True), 1e-30)
    v_sum = jnp.einsum("bgch,bgchd->bghd", a, v32)
    k_sum = (k32 * has[..., None]).sum(2) / c + layer["mu"]
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


def _decode_attend(cfg, layer, li, q, kk, vv, kc, vc, t, live):
    """One new position a row against layer `li`: q, kk, vv [b, 1, H,
    hd], `t` [b] each row's own position, `live` [b] whether the row
    holds a request. Returns (attn [b, 1, H * hd], kc, vc)."""
    b, _, H, hd = q.shape
    W, c, cpw = cfg.window_size, cfg.chunk_size, cfg.chunks_per_window
    t = jnp.maximum(t, 0)
    ring = W - 1 - t % W
    with jax.named_scope("eva_window_attn"):
        # one small write in place a row, the row cut out before it is
        # transposed: ops/attention._write_rows says why
        for r in range(b):
            kc = jax.lax.dynamic_update_slice(
                kc, kk[r:r + 1].transpose(0, 2, 3, 1)[None],
                (li, r, 0, 0, ring[r]))
            vc = jax.lax.dynamic_update_slice(
                vc, vv[r:r + 1].transpose(0, 2, 1, 3)[None],
                (li, r, 0, ring[r], 0))
    with jax.named_scope("eva_summarise"):
        # a row whose written position is the last of its chunk folds the
        # chunk, once: its c rows of the window, the step's own among
        # them and the latest first, become k~, v~ at index W + j. The
        # loop takes those rows alone: a row in mid-chunk, or one that
        # holds no request, reads and writes nothing here
        j = t // c
        base = W - c - (j % cpw) * c
        closes = live & (t % c == c - 1)
        first = jnp.argsort(~closes, stable=True)     # the closing rows
        whole = jnp.ones((1, 1, c), bool)
        # the rows cut out in the stack's own order: left to choose, the
        # compiler lays the whole stack out for the transposes below, and
        # copies it a step (tests/test_chip_compile.py)
        as_it_lies = Layout((0, 1, 2))

        def fold(i, stacks):
            kc, vc = stacks
            r = first[i]
            kch = with_layout_constraint(jax.lax.dynamic_slice(
                kc, (li, r, 0, 0, base[r]), (1, 1, H, hd, c))[0, 0],
                as_it_lies)                              # [H, hd, c]
            vch = with_layout_constraint(jax.lax.dynamic_slice(
                vc, (li, r, 0, base[r], 0), (1, 1, H, c, hd))[0, 0],
                as_it_lies)                              # [H, c, hd]
            k_sum, v_sum = _summarise(
                layer, kch.transpose(2, 0, 1)[None, None],
                vch.transpose(1, 0, 2)[None, None], whole, c)
            return (jax.lax.dynamic_update_slice(
                        kc, k_sum[..., None], (li, r, 0, 0, W + j[r])),
                    jax.lax.dynamic_update_slice(
                        vc, v_sum[:, :, :, None], (li, r, 0, W + j[r], 0)))

        kc, vc = jax.lax.fori_loop(0, closes.sum(), fold, (kc, vc))
    with jax.named_scope("eva_window_attn"):
        # the window's rows and the summaries before it: one range; a
        # row that holds no request gets an empty one
        lo = jnp.where(live, ring, 1)
        hi = jnp.where(live, W + cpw * (t // W) - 1, 0)
        n = vc.shape[3]
        block = decode_block_len(H, hd, n, vc.dtype,
                                 jax.sharding.get_abstract_mesh())
        if block is not None:
            attn = decode_attention(q.reshape(b, H, 1, hd), kc, vc, li,
                                    lo, hi, scale=hd ** -0.5,
                                    block_len=block)
            return attn.reshape(b, 1, H * hd), kc, vc
        k_l = jax.lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
        z = jnp.einsum("bhd,bhdn->bhn", q[:, 0], k_l,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        idx = jnp.arange(n)[None, :]
        seen = (idx >= lo[:, None]) & (idx <= hi[:, None])
        p = jax.nn.softmax(jnp.where(seen[:, None], z, NEG), axis=-1)
        attn = jnp.einsum("bhn,bhnd->bhd", p.astype(v_l.dtype), v_l)
    return attn.reshape(b, 1, H * hd), kc, vc


def _part(z, ok, v_of):
    """One part of the softmax: scores z [b, H, q, k] of which `ok` [b,
    1, q, k] count -> (values [b, H, q, hd], running max, sum) for the
    merge; `v_of(p)` multiplies the weights into the part's values."""
    z = jnp.where(ok, z, NEG)
    m = z.max(-1)
    p = jnp.exp(z - m[..., None]) * ok
    return v_of(p), m, p.sum(-1)


def _rows_at(a, first, n: int):
    """a [b, len, ...] -> rows [first[r], first[r] + n) of each batch
    row."""
    return jax.vmap(lambda x, i: jax.lax.dynamic_slice_in_dim(x, i, n, 0))(
        a, first)


def _laid_at(new, first, n: int):
    """new [b, g, ...] laid at rows [first[r], first[r] + g) of zeros
    [b, n, ...] (first + g may pass n: the rest is dropped)."""
    g = new.shape[1]
    buf = jnp.zeros((new.shape[0], n + g) + new.shape[2:], new.dtype)
    return jax.vmap(lambda x, y, i: jax.lax.dynamic_update_slice_in_dim(
        x, y, i, 0))(buf, new, first)[:, :n]


def _chunk_plan(cfg: EvaByteConfig, t, n: int):
    """What the chunk kernel's two calls a layer take, the same for every
    layer of a call: (tiles, the leaf's columns as found, their tables,
    the call's own columns, their tables), or None where the plain form
    stays. `t` [b, s] as `_chunk_attend` takes it, `n` the leaf's
    columns."""
    s = t.shape[1]
    tl = _chunk_tiles(cfg, s, n)
    if tl is None:
        return None
    found, own = _seen_by(jnp, cfg, t[:, 0], s, n - cfg.window_size, tl[2])
    return (tl, found, _chunk.tile_tables(found, t[:, 0], s, tl[0]),
            own, _chunk.tile_tables(own, t[:, 0], s, tl[1]))


def _merged(part1, part2):
    """Two parts of one softmax, each (values not yet divided [..., q,
    hd], running max [..., q], sum [..., q]) -> the quotient, zeros for
    a query with nothing in either."""
    (acc1, m1, l1), (acc2, m2, l2) = part1, part2
    m = jnp.maximum(m1, m2)
    a1, a2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    total = l1 * a1 + l2 * a2
    return (acc1 * a1[..., None] + acc2 * a2[..., None]) / jnp.where(
        total == 0, 1.0, total)[..., None]


def _chunk_attend(cfg, layer, li, q, kk, vv, kc, vc, t, plan=None):
    """`s` new positions of a batch in lock-step against layer `li`: q,
    kk, vv [b, s, H, hd], `t` [b, s] each position's own place in its
    request (negative: left padding, no byte); `plan` from `_chunk_plan`.
    Returns (attn [b, s, H * hd], kc, vc). The layer is read before
    anything of it is written, and of it only the window's rows and the
    summaries the call makes are written, each once, so that the stack
    is updated where it lies."""
    b, s, H, hd = q.shape
    W, c, cpw = cfg.window_size, cfg.chunk_size, cfg.chunks_per_window
    n = kc.shape[4]
    S = n - W
    dt, scale = kc.dtype, hd ** -0.5
    real = t >= 0
    win = jnp.maximum(t, 0) // W                       # [b, s]
    t0 = t[:, 0]
    first = jnp.maximum(t0, 0)      # the call's first byte, were it one
    f32 = dict(preferred_element_type=jnp.float32)
    ring_k = jax.lax.dynamic_slice(kc, (li, 0, 0, 0, 0), (1, b, H, hd, W))[0]
    ring_v = jax.lax.dynamic_slice(vc, (li, 0, 0, 0, 0), (1, b, H, W, hd))[0]
    at = jnp.arange(W)               # index i holds residue W - 1 - i
    by_head = lambda x: x.transpose(0, 2, 1, 3)      # [b, s, H, hd] <->

    with jax.named_scope("eva_summarise"):
        # every chunk the call's bytes lie in, whole: G groups of c from
        # chunk `j0`; of the first, what lies before the call is in the
        # window: the c positions before the call's first, picked out as
        # found by a product with their one-hot rows (exact)
        G = _groups(cfg, s)
        before = W - 1 - (t0[:, None] - c + jnp.arange(c)[None, :]) % W
        pick = (before[:, :, None] == at[None, None, :]).astype(dt)
        prev_k = jnp.einsum("bew,bhdw->behd", pick, ring_k)
        prev_v = jnp.einsum("bew,bhwd->behd", pick, ring_v)
        pad = jnp.zeros((b, G * c, H, hd), dt)
        j0 = first // c
        e0 = c * j0 - t0 + c          # ext row of the first group's start
        grp = lambda prev, new: _rows_at(
            jnp.concatenate([prev, new, pad], 1), e0, G * c).reshape(
                b, G, c, H, hd)
        place = (c * (j0[:, None, None] + jnp.arange(G)[None, :, None])
                 + jnp.arange(c)[None, None, :])
        ok = place < (t0 + s)[:, None, None]
        k_sum, v_sum = _summarise(layer, grp(prev_k, kk), grp(prev_v, vv),
                                  ok, c)
        hit = ok.any(-1)           # [b, G]: the call holds bytes of it

    if plan is not None:
        (found_t, own_t, made), found, found_tables, own, own_tables = plan
        q_h = by_head(q)[:, :, None]                  # [b, H, 1, s, hd]
        with jax.named_scope("eva_chunk_attn"):
            # the leaf as the call found it, where it lies in the stack:
            # the window's rows and the summaries of the chunks before
            part1 = _chunk.gqa_chunk_attention(
                q_h, kc, vc, li, found, t0, scale=scale, t=found_t,
                tables=found_tables, parts=True)
        with jax.named_scope("eva_window_attn"):
            # the call's own keys, then the summaries it has just made
            none = jnp.zeros((b, made - G, H, hd), dt)
            k_own = jnp.concatenate([kk, k_sum, none], 1).transpose(
                0, 2, 3, 1)                            # [b, H, hd, s + made]
            v_own = by_head(jnp.concatenate([vv, v_sum, none], 1))
            part2 = _chunk.gqa_chunk_attention(
                q_h, k_own[None], v_own[None], 0, own, t0, scale=scale,
                t=own_t, tables=own_tables, parts=True)
        with jax.named_scope("eva_chunk_attn"):
            attn = by_head(_merged(part1, part2)[:, :, 0])
    else:
        with jax.named_scope("eva_window_attn"):
            # the window as the call found it: a byte of the first
            # query's window where its residue lies before the call's
            # first byte
            held = (W - 1 - at)[None, :] < (first % W)[:, None]
            ring_ok = (real & (win == (first // W)[:, None]))[:, :, None] \
                & held[:, None, :]
            own_ok = (real[:, :, None] & real[:, None, :]
                      & (win[:, :, None] == win[:, None, :])
                      & (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]))
            z = jnp.concatenate([
                jnp.einsum("bqhd,bhdk->bhqk", q, ring_k, **f32),
                jnp.einsum("bqhd,bkhd->bhqk", q, kk, **f32)], -1) * scale
            part1 = _part(
                z, jnp.concatenate([ring_ok, own_ok], -1)[:, None],
                lambda p: jnp.einsum("bhqk,bhkd->bhqd",
                                     p[..., :W].astype(dt), ring_v, **f32)
                + jnp.einsum("bhqk,bkhd->bhqd", p[..., W:].astype(dt), vv,
                             **f32))
        with jax.named_scope("eva_chunk_attn"):
            # the summaries of the windows before each query's own, one
            # that ended inside this call among them
            laid = _laid_at(hit, j0, S)                 # [b, S]
            sum_k = jnp.where(
                laid[:, None, None, :],
                _laid_at(k_sum, j0, S).transpose(0, 2, 3, 1),
                jax.lax.dynamic_slice(kc, (li, 0, 0, 0, W),
                                      (1, b, H, hd, S))[0])
            sum_v = jnp.where(
                laid[:, None, :, None],
                _laid_at(v_sum, j0, S).transpose(0, 2, 1, 3),
                jax.lax.dynamic_slice(vc, (li, 0, 0, W, 0),
                                      (1, b, H, S, hd))[0])
            ok2 = real[:, :, None] & (jnp.arange(S)[None, None, :]
                                      < (cpw * win)[:, :, None])
            part2 = _part(
                jnp.einsum("bqhd,bhdj->bhqj", q, sum_k, **f32) * scale,
                ok2[:, None],
                lambda p: jnp.einsum("bhqj,bhjd->bhqd", p.astype(dt), sum_v,
                                     **f32))
            attn = by_head(_merged(part1, part2))

    with jax.named_scope("eva_window_attn"):
        # the call's last W rows over the window's: index i takes the
        # call's row (W - 1 - i - tail0) mod W, where it has one; the
        # rows are turned round while positions are their major axis
        n_tail = min(s, W)
        tail0 = t0 + (s - n_tail)
        row_of = (W - 1 - at[None, :] - tail0[:, None]) % W
        put = (row_of < n_tail) & (tail0[:, None] + row_of >= 0)

        def laid(new):    # [b, s, H, hd] -> [b, W, H, hd] by window index
            turned = jnp.concatenate(
                [jnp.zeros((b, W - n_tail, H, hd), dt),
                 new[:, s - n_tail:][:, ::-1]], 1)
            return jax.vmap(lambda x, by: jnp.roll(x, by, 0))(
                turned, -tail0 % W)

        # the layer's two writes: the window's rows, whole
        kc = jax.lax.dynamic_update_slice(kc, jnp.where(
            put[:, None, None, :], laid(kk).transpose(0, 2, 3, 1),
            ring_k)[None], (li, 0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, jnp.where(
            put[:, None, :, None], laid(vv).transpose(0, 2, 1, 3),
            ring_v)[None], (li, 0, 0, 0, 0))
    with jax.named_scope("eva_summarise"):
        # and the summaries made, a row's `cols` columns from the first
        # chunk it holds bytes of (held back where they would pass the
        # leaf's end: a column then takes the group that falls on it);
        # a column whose chunk the call holds no byte of keeps what it had
        cols = min(G, S)
        col0 = jnp.minimum(j0, S - cols)                      # [b]
        g_of = (col0 - j0)[:, None] + jnp.arange(cols)[None, :]
        takes = (g_of >= 0) & jnp.take_along_axis(
            hit, jnp.maximum(g_of, 0), 1)                     # [b, cols]
        pick_g = lambda x: jnp.take_along_axis(
            x, jnp.maximum(g_of, 0)[:, :, None, None], 1)
        new_k = pick_g(k_sum).transpose(0, 2, 3, 1)       # [b, H, hd, cols]
        new_v = pick_g(v_sum).transpose(0, 2, 1, 3)       # [b, H, cols, hd]
        # cut out and put back in the stack's own order, as the decode
        # step's fold does and for its reason
        lies = lambda x: with_layout_constraint(x, Layout((0, 1, 2)))
        for r in range(b):
            k_at, v_at = (li, r, 0, 0, W + col0[r]), (li, r, 0, W + col0[r], 0)
            kc = jax.lax.dynamic_update_slice(kc, lies(jnp.where(
                takes[r][None, None, :], new_k[r], lies(jax.lax.dynamic_slice(
                    kc, k_at, (1, 1, H, hd, cols))[0, 0])))[None, None], k_at)
            vc = jax.lax.dynamic_update_slice(vc, lies(jnp.where(
                takes[r][None, :, None], new_v[r], lies(jax.lax.dynamic_slice(
                    vc, v_at, (1, 1, H, cols, hd))[0, 0])))[None, None], v_at)
    return attn.astype(dt).reshape(b, s, H * hd), kc, vc


def _hidden(params: dict, cache: dict, tokens: jax.Array,
            cfg: EvaByteConfig) -> tuple[jax.Array, dict]:
    """`tokens` [b, s] appended to the cache -> (the float32 stream
    behind the last layer [b, s, d], the updated cache)."""
    b, s = tokens.shape
    dt, H, hd = cfg.dtype, cfg.n_heads, cfg.head_dim
    cache_len = cache["length"]
    per_row = jnp.ndim(cache_len) == 1
    if s > 1 and per_row:
        raise ValueError("a chunk advances the batch in lock-step: "
                         "cache['length'] must be a scalar")
    start = cache.get("start")
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    at = (cache_len[:, None] if per_row else cache_len) \
        + jnp.arange(s)[None, :]
    t = jnp.broadcast_to(at, (b, s)) - start[:, None]
    rel = jnp.maximum(t, 0)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    cos, sin = rope_frequencies(hd, cfg.max_seq_len, cfg.rope_theta)
    f32 = dict(preferred_element_type=jnp.float32)
    plan = None if per_row else _chunk_plan(cfg, t, cache["k"].shape[4])

    def block(li, carry):
        x, kc, vc = carry
        layer = jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, li, 0, keepdims=False),
            params["layers"])
        with jax.named_scope("attn_qkv"):
            h = _norm(x, layer["attn_norm"], cfg.norm_eps, dt)
            q, kk, vv = ((h @ layer[name].astype(dt)).reshape(b, s, H, hd)
                         for name in ("wq", "wk", "wv"))
            q = apply_rope(q, cos, sin, rel)
            kk = apply_rope(kk, cos, sin, rel)
        if per_row:
            attn, kc, vc = _decode_attend(cfg, layer, li, q, kk, vv, kc, vc,
                                          t[:, 0], cache_len >= 0)
        else:
            attn, kc, vc = _chunk_attend(cfg, layer, li, q, kk, vv, kc, vc,
                                         t, plan)
        with jax.named_scope("attn_out"):
            x = x + jnp.dot(attn, layer["wo"].astype(dt), **f32)
        with jax.named_scope("mlp"):
            h = _norm(x, layer["mlp_norm"], cfg.norm_eps, dt)
            x = x + jnp.dot(jax.nn.silu(h @ layer["w_gate"].astype(dt))
                            * (h @ layer["w_up"].astype(dt)),
                            layer["w_down"].astype(dt), **f32)
        return x, kc, vc

    x, kc, vc = jax.lax.fori_loop(0, cfg.n_layers, block,
                                  (x, cache["k"], cache["v"]))
    return x, {"k": kc, "v": vc, "length": cache_len + s, "start": start}


def _logits(params: dict, x, cfg: EvaByteConfig, all_heads: bool):
    """Float32 logits of the stream's rows x [..., d]: of head 0, the
    next byte, [..., vocab]; with `all_heads` [..., n_pred_heads,
    vocab]."""
    with jax.named_scope("lm_head"):
        h = _norm(x, params["final_norm"], cfg.norm_eps, cfg.dtype)
        logits = jnp.dot(h, params["lm_head"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
    logits = logits.reshape(x.shape[:-1] + (cfg.n_pred_heads,
                                            cfg.vocab_size))
    return logits if all_heads else logits[..., 0, :]


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                cfg: EvaByteConfig, all_heads: bool = False
                ) -> tuple[jax.Array, dict]:
    """Append `tokens` [b, s] to the cache, return the logits for the
    last position and the updated cache: `llama.decode_step`'s contract,
    the logits those of head 0 [b, vocab], the byte the served stream
    samples (with `all_heads` every head's, [b, n_pred_heads, vocab]).
    s = 1 with a per-row cache["length"] is the engine's decode step (a
    row with length < 0 holds no request and reads nothing); a scalar
    length advances the batch in lock-step by a chunk of any s."""
    x, cache = _hidden(params, cache, tokens, cfg)
    return _logits(params, x[:, -1], cfg, all_heads), cache


def forward(params: dict, tokens: jax.Array, cfg: EvaByteConfig,
            all_heads: bool = False) -> jax.Array:
    """Logits at every position of `tokens` [b, s], no cache kept: one
    call of the chunk's path over the whole sequence. [b, s, vocab] of
    head 0, or [b, s, n_pred_heads, vocab]."""
    b, s = tokens.shape
    x, _ = _hidden(params, init_cache(cfg, b, max_len=s), tokens, cfg)
    return _logits(params, x, cfg, all_heads)
