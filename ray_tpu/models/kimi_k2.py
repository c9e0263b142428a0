"""Decoder of the `kimi_k2` family (DeepSeek-V3's layer under another
name): DENSE latent attention under YaRN, and routed experts beside a
shared one.

Every layer:  h = x + Attn(rmsnorm(x));  y = h + FFN(rmsnorm(h)).

Attention is the latent form of models/dots3_note.py, whose parts this
file imports (`_queries`, `_latent_rows`, `_attend_block`, `_absorbed`,
the chunk kernel's choice and call, `_write_rows`, `_ffn`): a position
is cached as ONE row ``[c_kv | k_rope]`` that all heads share (576
numbers, filled to 640). What differs is whom a query attends to, and
that decides the work: EVERY position of its row from `start` to
itself. No indexer, no selection, no window, no gate. So a prefill chunk
runs the expanded form through ops/pallas/latent_attention.py under a
causal-and-padding mask (on a TPU; the plain blocks elsewhere), and a
decode step runs the absorbed form over every live row of every live
slot: on a TPU through ops/pallas/latent_decode_attention.py, which
reads the blocks of the stacked cache that overlap a row's ``[start,
length]`` and no others; elsewhere `_absorbed` over the whole layer
under a mask.

The rotated 64 numbers turn at YaRN's frequencies (`ops/rope.
yarn_inv_freq`: the published context is 64 times the 4,096 the
frequencies were trained at) and the scores are scaled by
``mscale(factor, mscale_all_dim)**2 / sqrt(192)``.

Layers below `first_k_dense` end in a dense SwiGLU MLP, the others in
`ops/moe.py`'s dropless expert layer: the router scores all
`n_routed_experts`, this holder computes the part of the `experts_held`
experts from `experts_first` on, times `routed_scaling`, and the shared
expert is added whole.

Same function set as models/llama.py, so serve/llm.py's engine runs it.
Every cache leaf has a position axis as deep as the cache
(`CACHE_LEN_AXIS`), so the engine's prefix store may hold its rows.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import dots3_note as _mla
from ray_tpu.models.dots3_note import ROUTER_BIAS_STD, AttnSizes
from ray_tpu.ops import attention as _attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas import latent_decode_attention as _ldec
from ray_tpu.ops.rope import yarn_mscale

F32 = jnp.float32
# one latent row serves every head: a tensor axis over the heads would
# have to replicate the cache, and that layout is not written
TENSOR_PARALLEL = False


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163840
    dim: int = 7168
    n_layers: int = 61
    first_k_dense: int = 1
    hidden_dim: int = 18432            # the dense layers' MLP
    moe_hidden_dim: int = 2048         # one expert, and the shared one
    n_routed_experts: int = 384        # the router's width
    experts_first: int = 0             # the experts this holder computes
    experts_held: int | None = None    # None: all of them
    experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling: float = 2.827
    n_heads: int = 64
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    q_rank: int = 1536
    kv_rank: int = 512
    rope_theta: float = 5e4
    rope_factor: float = 64.0          # 1: plain RoPE, no temperature
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # what dots3_note's parts ask of a config and this family lacks
    lora_rescale: ClassVar[bool] = False

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if not 0 <= self.experts_first <= (
                self.n_routed_experts - self.experts_held):
            raise ValueError("held experts lie outside the routed ones")

    @property
    def attn(self) -> AttnSizes:
        yarn = None if self.rope_factor <= 1 else (
            self.rope_factor, self.rope_original_len, self.rope_beta_fast,
            self.rope_beta_slow)
        return AttnSizes(
            self.n_heads, self.qk_nope_dim, self.qk_rope_dim,
            self.v_head_dim, self.q_rank, self.kv_rank, self.rope_theta,
            yarn, yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    @property
    def n_moe_layers(self) -> int:
        return max(0, self.n_layers - self.first_k_dense)

    def num_params(self) -> int:
        shapes = jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))
        return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def from_published(config: dict, **overrides) -> KimiK2Config:
    """The config from the keys of a published `config.json`. A file
    that describes ONE holder of a layer's experts gives the experts it
    holds as `n_routed_experts`, the first of them as `experts_first`,
    and the router's width, the published `n_routed_experts`, as
    `router_experts` (the published file has neither of the two)."""
    scaling = config.get("rope_scaling") or {}
    if (config.get("scoring_func", "sigmoid") != "sigmoid"
            or config.get("topk_method", "noaux_tc") != "noaux_tc"
            or int(config.get("n_group", 1)) != 1
            or int(config.get("topk_group", 1)) != 1
            or config.get("attention_bias", False)
            or int(config.get("moe_layer_freq", 1)) != 1
            or int(config.get("n_shared_experts", 1)) != 1
            or int(config.get("num_nextn_predict_layers", 0)) != 0
            or config.get("tie_word_embeddings", False)
            or scaling.get("type", "yarn") != "yarn"
            or scaling.get("mscale", 1) != scaling.get("mscale_all_dim", 1)):
        raise ValueError("only sigmoid noaux_tc routing over one group, one "
                         "shared expert in every layer past the dense ones, "
                         "no bias, no next-token module, an untied head and "
                         "YaRN with mscale = mscale_all_dim are implemented "
                         "for this family")
    if int(config["num_key_value_heads"]) != int(config["num_attention_heads"]):
        raise ValueError("latent attention has one key row for all heads: "
                         "num_key_value_heads must equal the heads")
    kw = dict(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        first_k_dense=int(config["first_k_dense_replace"]),
        hidden_dim=int(config["intermediate_size"]),
        moe_hidden_dim=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config.get("router_experts",
                                        config["n_routed_experts"])),
        experts_held=int(config["n_routed_experts"]),
        experts_first=int(config.get("experts_first", 0)),
        experts_per_tok=int(config["num_experts_per_tok"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        n_heads=int(config["num_attention_heads"]),
        qk_nope_dim=int(config["qk_nope_head_dim"]),
        qk_rope_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(scaling.get("factor", 1.0)),
        rope_original_len=int(scaling.get(
            "original_max_position_embeddings",
            config.get("max_position_embeddings", 4096))),
        rope_beta_fast=float(scaling.get("beta_fast", 32)),
        rope_beta_slow=float(scaling.get("beta_slow", 1)),
        rope_mscale_all_dim=float(scaling.get("mscale_all_dim", 1)),
        norm_eps=float(config["rms_norm_eps"]))
    kw.update(overrides)
    return KimiK2Config(**kw)


# ------------------------------------------------------------------- params
def _layer_shapes(cfg: KimiK2Config, li: int) -> dict:
    """name -> (shape, fan_in | "one" | "bias") of layer `li`; the names
    are dots3_note's, whose parts read them."""
    a, d = cfg.attn, cfg.dim
    out = {"norm": ((d,), "one"),
           "w_qa": ((d, a.q_rank), d), "q_norm": ((a.q_rank,), "one"),
           "w_qb": ((a.heads * (a.nope + a.rope), a.q_rank), a.q_rank),
           "w_kva": ((d, a.kv_rank + a.rope), d), "kv_norm": ((a.kv_rank,), "one"),
           "w_kvb_k": ((a.kv_rank, a.heads, a.nope), a.kv_rank),
           "w_kvb_v": ((a.kv_rank, a.heads, a.v), a.kv_rank),
           "w_o": ((a.heads * a.v, d), a.heads * a.v),
           "mlp_norm": ((d,), "one")}
    if li < cfg.first_k_dense:
        f = cfg.hidden_dim
        out.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                    "w_down": ((f, d), f)})
    else:
        f, e = cfg.moe_hidden_dim, cfg.experts_held
        out.update({"router": ((d, cfg.n_routed_experts), d),
                    "router_bias": ((cfg.n_routed_experts,), "bias"),
                    "we_gate": ((e, d, f), d), "we_up": ((e, d, f), d),
                    "we_down": ((e, f, d), f),
                    "ws_gate": ((d, f), d), "ws_up": ((d, f), d),
                    "ws_down": ((f, d), f)})
    return out


def init_params(cfg: KimiK2Config, key: jax.Array) -> dict:
    """Matrices N(0, 1/fan_in), norms one, the router's selection bias
    N(0, ROUTER_BIAS_STD**2) in float32 (dots3_note's: small and not
    zero, it changes which experts are chosen and never their weights)."""
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 3 + 16 * cfg.n_layers))

    def make(shape, how):
        if how == "one":
            return jnp.ones(shape, pd)
        if how == "bias":
            return ROUTER_BIAS_STD * jax.random.normal(next(keys), shape, F32)
        return (jax.random.normal(next(keys), shape, F32)
                / math.sqrt(how)).astype(pd)

    return {"embed": make((cfg.vocab_size, cfg.dim), cfg.dim),
            "final_norm": jnp.ones((cfg.dim,), pd),
            "lm_head": make((cfg.dim, cfg.vocab_size), cfg.dim),
            "layers": [{name: make(shape, how) for name, (shape, how)
                        in _layer_shapes(cfg, li).items()}
                       for li in range(cfg.n_layers)]}


def param_logical_axes(cfg: KimiK2Config) -> dict:
    """Everything replicated (TENSOR_PARALLEL is False): leaves are tuples
    of None, one for each axis."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a: (None,) * a.ndim, shapes)


# ---------------------------------------------------------------- the parts
def _out(cfg, layer, attn):
    """attn [b, s, H, v] -> through w_o: [b, s, d]."""
    b, s = attn.shape[:2]
    with jax.named_scope("attn_out"):
        return attn.reshape(b, s, -1) @ layer["w_o"].astype(cfg.dtype)


def forward(params: dict, tokens: jax.Array, cfg: KimiK2Config,
            collect: bool = False):
    """tokens: [b, s] int32 -> logits [b, s, vocab] (f32): the whole
    sequence, no cache kept, attention in the expanded form over all s
    keys at once (so s is bounded by memory: the serving path is
    `decode_step`). With `collect`, also what was chosen: {"chosen":
    [expert layers] of [b, s, k], "router_scores": of [b, s, E]}."""
    b, s = tokens.shape
    a, dt = cfg.attn, cfg.dtype
    pos = jnp.arange(s)[None, :].repeat(b, 0)
    causal = pos[:, :, None] >= pos[:, None, :]
    x = _mla._embed(cfg, params, tokens)
    seen: dict = {"chosen": [], "router_scores": []}
    for layer in params["layers"]:
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        _, q_nope, q_rope = _mla._queries(cfg, a, layer, h, pos)
        with jax.named_scope("mla_kv"):
            rows = _mla._latent_rows(cfg, a, layer, h, pos)
        with jax.named_scope("mla_prefill_attn"):
            q = _mla._heads_first(q_nope, q_rope)
            attn = _mla._attend_done(_mla._attend_block(
                _mla._attend_init(q, a), a, layer, q, rows, causal, dt), dt)
        x = x + _out(cfg, layer, attn)
        x, _, aux = _mla._ffn(cfg, layer, x, None, collect)
        for k_, v_ in aux.items():
            seen[k_].append(v_)
    logits = _mla._logits(cfg, params, x)
    return (logits, seen) if collect else logits


# ----------------------------------------------------------------- decoding
# the one leaf with rows is as deep as the cache: the engine cuts and
# grafts it by position, and may keep it in its prefix store
CACHE_LEN_AXIS = {"latent": 2}
CACHE_KIND = {"latent": "latent"}
STEP_AUX = _mla.STEP_AUX


def init_cache(cfg: KimiK2Config, batch: int,
               max_len: int | None = None) -> dict:
    """An empty cache: the latent rows ``[layers, b, len, row]`` of every
    layer; "aux", the last step's device-side counters."""
    max_len = max_len or cfg.max_seq_len
    return {"latent": jnp.zeros((cfg.n_layers, batch, max_len, cfg.attn.row),
                                cfg.dtype),
            "aux": jnp.zeros((len(STEP_AUX),), jnp.int32),
            "length": jnp.zeros((), jnp.int32),
            "start": jnp.zeros((batch,), jnp.int32)}


def cache_logical_axes(cfg: KimiK2Config) -> dict:
    return {"latent": ("layers", "batch", None, None),
            "aux": (None,), "length": (), "start": ("batch",)}


def _read_block(cfg: KimiK2Config, depth: int) -> int | None:
    """Positions in a block of the decode kernel's reads of a cache
    `depth` deep, or None where a step keeps the XLA form and reads a
    layer whole: chosen, as `ops/attention.cached_attention` chooses its
    decode kernel, from the platform and the shapes alone."""
    if not _attention._on_tpu():
        return None
    return _ldec.block_len(depth, cfg.attn.row, cfg.dtype)


def decode_read_block(cfg: KimiK2Config, mesh) -> int | None:
    return _read_block(cfg, cfg.max_seq_len)


def decode_counters(cfg: KimiK2Config, spans: list, rows: int) -> dict:
    """What one decode step of `rows` rows does for live rows at `spans`
    [(start, the position the step writes)], over all layers: the latent
    positions its queries attend to, and the positions of the blocks its
    attention is asked to read: the blocks that overlap those ranges, or
    every layer whole where nothing bounds the read."""
    block = _read_block(cfg, cfg.max_seq_len)
    read = rows * cfg.max_seq_len if not block else block * sum(
        last // block - start // block + 1 for start, last in spans)
    return {"decode_latent_positions_live":
            cfg.n_layers * sum(last - start + 1 for start, last in spans),
            "decode_latent_positions_read": cfg.n_layers * read}


def prefill_counters(cfg: KimiK2Config, start: int, pos: int, chunk: int,
                     depth: int) -> dict:
    """What the attention of one prefill chunk does: `chunk` queries at
    positions [pos, pos + chunk) of a row whose first real token lies at
    `start`, against a cache `depth` deep; key positions summed over the
    chunk's queries and all layers: `visible`, those a query attends to
    (every one from `start` to itself), and `visited`, those whose
    scores are computed: under the kernel the tiles that causality and
    padding leave live, in the plain form every block from the first
    real position to the last written."""
    depths = np.arange(max(pos, start), pos + chunk) - start + 1
    visited = _mla._dense_keys_visited(cfg.attn, start, pos, chunk, depth)
    return {"prefill_latent_keys_visited": cfg.n_layers * visited,
            "prefill_latent_keys_visible": cfg.n_layers * int(depths.sum())}


def _decode_attend(cfg, a, layer, li, q_nope, q_rope, latent, start, last):
    """One query a row, the ABSORBED form, against positions [start,
    last] of layer `li` of the stacked rows: [b, 1, H, v]."""
    dt = cfg.dtype
    depth = latent.shape[2]
    block = _read_block(cfg, depth)
    if block is None:
        k_pos = jnp.arange(depth)[None, :]
        mask = (k_pos >= start[:, None]) & (k_pos <= last[:, None])
        return _mla._absorbed(a, layer, q_nope, q_rope, latent[li], mask,
                              dt)[:, None]
    o_lat = _ldec.latent_decode_attention(
        _mla._absorbed_query(a, layer, q_nope, q_rope, dt), latent, li, start,
        last, kv_rank=a.kv_rank, scale=a.scale, block_len=block)
    return jnp.einsum("bhc,chv->bhv", o_lat,
                      layer["w_kvb_v"].astype(dt))[:, None]


def _chunk_attend(cfg, a, layer, li, q_nope, q_rope, latent, mask, first,
                  stop):
    """A chunk's queries, the EXPANDED form, against layer `li` of the
    stacked rows under `mask` [b, s, depth]: the kernel where the shapes
    are whole in its tiles, else plain blocks of keys `first` to `stop`."""
    dt = cfg.dtype
    s, depth = mask.shape[1:]
    q = _mla._heads_first(q_nope, q_rope)
    t = _mla._chunk_tiles(a, s, depth)
    if t is not None:
        return _mla._attend_kernel(a, layer, q, latent, li, mask, t)
    blk = min(depth, 1024)

    def attend(j, state):
        # a last block that was moved back to fit repeats positions of
        # the block before it: they are not this block's
        at = jnp.minimum(j * blk, depth - blk)
        rows = jax.lax.dynamic_slice_in_dim(latent[li], at, blk, axis=1)
        own = (at + jnp.arange(blk)) >= j * blk
        return _mla._attend_block(
            state, a, layer, q, rows,
            jax.lax.dynamic_slice_in_dim(mask, at, blk, axis=2)
            & own[None, None, :], dt)

    return _mla._attend_done(jax.lax.fori_loop(
        first // blk, (stop - 1) // blk + 1, attend,
        _mla._attend_init(q, a)), dt)


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                cfg: KimiK2Config, collect: bool = False):
    """Append `tokens` [b, s] to the cache, return logits for the last
    position [b, vocab] and the updated cache: `llama.decode_step`'s
    contract. s = 1 is a decode step, with a scalar or per-row
    cache["length"] (a row with length < 0 holds no request: it reaches
    no expert, its attention reads nothing and its result is not read);
    larger s is a prefill chunk of the batch in lock-step (a scalar
    length), which finds in the cache the rows the chunks before it
    left. Positions before cache["start"] are left padding: RoPE counts
    from `start`, and a padded position is attended to by nobody and
    reaches no expert. cache["aux"] comes back as (token-expert pairs
    computed on held experts, held experts with at least one token,
    tiles of rows the experts' loop walked), summed over the expert
    layers. With `collect` a third value is returned: what each expert
    layer chose (`forward`'s dict)."""
    b, s = tokens.shape
    cache_len = cache["length"]
    if s > 1 and jnp.ndim(cache_len):
        raise ValueError("a chunk advances the batch in lock-step: "
                         "cache['length'] must be a scalar")
    start = cache.get("start")
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    abs_pos = jnp.broadcast_to(jnp.maximum(
        (cache_len[:, None] if jnp.ndim(cache_len) else cache_len)
        + jnp.arange(s)[None, :], 0), (b, s))
    rel = jnp.maximum(abs_pos - start[:, None], 0)
    valid = abs_pos >= start[:, None]
    if jnp.ndim(cache_len):
        valid = valid & (cache_len >= 0)[:, None]
    a, latent = cfg.attn, cache["latent"]
    depth = latent.shape[2]
    if s == 1:
        # the position the step writes, or one before `start` for a row
        # that holds no request: an empty range
        last = jnp.where(jnp.broadcast_to(cache_len, (b,)) >= 0,
                         abs_pos[:, 0], start - 1)
    else:
        k_pos = jnp.arange(depth)[None, None, :]
        mask = ((k_pos <= abs_pos[..., None]) & (k_pos >= start[:, None, None])
                & valid[..., None])
        first, stop = jnp.min(start), cache_len + s
    seen: dict = {"chosen": [], "router_scores": []}
    counts = jnp.zeros((len(STEP_AUX),), jnp.int32)
    x = _mla._embed(cfg, params, tokens)
    for li, layer in enumerate(params["layers"]):
        with jax.named_scope("mla_q"):
            h = rms_norm(x, layer["norm"], cfg.norm_eps)
        _, q_nope, q_rope = _mla._queries(cfg, a, layer, h, rel)
        with jax.named_scope("mla_kv"):
            latent = _mla._write_rows(
                latent, li, _mla._latent_rows(cfg, a, layer, h, rel),
                jnp.maximum(cache_len, 0))
        if s == 1:
            with jax.named_scope("mla_decode_attn"):
                attn = _decode_attend(cfg, a, layer, li, q_nope[:, 0],
                                      q_rope[:, 0], latent, start, last)
        else:
            with jax.named_scope("mla_prefill_attn"):
                attn = _chunk_attend(cfg, a, layer, li, q_nope, q_rope,
                                     latent, mask, first, stop)
        x = x + _out(cfg, layer, attn)
        x, moe, aux = _mla._ffn(cfg, layer, x, valid, collect)
        if moe is not None:
            counts = counts + jnp.stack(moe)
        for k_, v_ in aux.items():
            seen[k_].append(v_)
    new_cache = {"latent": latent, "aux": counts, "length": cache_len + s,
                 "start": start}
    logits = _mla._logits(cfg, params, x[:, -1])
    return (logits, new_cache, seen) if collect else (logits, new_cache)
