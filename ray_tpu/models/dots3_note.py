"""Decoder of the `dots3_note` family: latent attention (MLA) of two
kinds in one stack, a learned sparse selection on the full layers, and
routed experts beside a shared one.

Every layer:  h = x + Attn(rmsnorm(x));  y = h + FFN(rmsnorm(h)).

Attention is DeepSeek-V2's latent form. A position is cached as ONE row
shared by all heads, ``[c_kv | k_rope]``: the normalised low-rank latent
and the rotated position key (576 numbers in a full layer, 1,088 in a
sliding one, each filled with zeros to whole lanes of 128: 640 and
1,152). A head's key and value are linear in the latent, so decode
runs in the ABSORBED form: the key's up-projection is folded into the
query and the value's into the output, and scores and values are taken
against the cached rows themselves. A prefill chunk and `forward` run the
EXPANDED form (keys and values made per head from a block of rows),
which costs a third of the operations when many queries share the rows.
Both kinds of layer are built from the same parts (`_queries`,
`_latent_rows`, `_attend_block`, `_absorbed`, `_gate_out`), parametrised
by `AttnSizes`; they differ in what they cache and whom they attend to.

* "full_attention": 128 heads; an indexer (DeepSeek-V3.2's) scores every
  earlier position, ``I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s))``,
  and the layer attends to the `index_topk` positions of largest score
  only (to all of them while there are no more). The index keys are
  cached beside the latent rows. A decode step picks the rows with
  `top_k` and GATHERS them; a chunk finds each query's k-th largest
  score by bisection on the scores' bits and masks.
* "sliding_attention": 64 heads, a wider latent, its own rope_theta, and
  a window of `sliding_window` positions that counts the query's own.
  Its cache is a RING of `ring_len` rows whatever the sequence length:
  position p lies in row p mod ring_len.

Layers below `first_k_dense` end in a dense SwiGLU MLP, the others in
`ops/moe.py`'s dropless expert layer: the router scores all
`n_routed_experts`, this holder computes the part of the
`experts_held` experts from `experts_first` on (all of them by
default), and the shared expert is added.

Same function set as models/llama.py, so serve/llm.py's engine runs it:
`init_params`, `param_logical_axes`, `forward`, `init_cache`,
`cache_logical_axes`, `CACHE_LEN_AXIS`, `decode_step`, and what the
engine asks a module about its decode step: `CACHE_KIND`,
`decode_read_block`, `decode_counters`, `prefill_counters`, `STEP_AUX`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import attention as _attention
from ray_tpu.ops.moe import held_experts_ffn, route_sigmoid_topk
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas.latent_attention import latent_attention, tiles
from ray_tpu.ops.rope import apply_rope, yarn_inv_freq

F32 = jnp.float32
KINDS = ("full_attention", "sliding_attention")
NEG = -1e30
# one latent row serves every head: a tensor axis over the heads would
# have to replicate the cache, and that layout is not written
TENSOR_PARALLEL = False


class AttnSizes(NamedTuple):
    heads: int
    nope: int      # a head's key/query part without position
    rope: int      # the rotated part, one key for all heads
    v: int
    q_rank: int
    kv_rank: int
    theta: float
    # YaRN over the rotated part, (factor, original context, beta_fast,
    # beta_slow), and the temperature its family squares into the
    # softmax scale (models/kimi_k2.py); plain RoPE where None
    yarn: tuple | None = None
    mscale: float = 1.0

    @property
    def scale(self) -> float:
        """What a score is multiplied by before the softmax."""
        return self.mscale ** 2 / math.sqrt(self.nope + self.rope)

    @property
    def row(self) -> int:
        """A cached row [c_kv | k_rope | zeros], filled up to whole lanes
        of 128: with 576 numbers minor the compiler keeps the cache with
        positions minor instead and re-lays the whole stack out around
        every step's write (1.8 GB twice; sandbox compile, PR 32). The
        tiled layout would pad the row to the same size anyway."""
        return -(-(self.kv_rank + self.rope) // 128) * 128


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    dim: int = 5120
    layer_types: tuple = ("full_attention", "full_attention",
                          "sliding_attention", "sliding_attention",
                          "sliding_attention")
    first_k_dense: int = 1
    hidden_dim: int = 13824            # the dense layers' MLP
    moe_hidden_dim: int = 1536         # one expert, and the shared one
    n_routed_experts: int = 256        # the router's width
    experts_first: int = 0             # the experts this holder computes
    experts_held: int | None = None    # None: all of them
    experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling: float = 1.0
    n_heads: int = 128
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    q_rank: int = 1024
    kv_rank: int = 512
    rope_theta: float = 8e7
    swa_n_heads: int = 64
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_q_rank: int = 1024
    swa_kv_rank: int = 1024
    swa_rope_theta: float = 5e4
    sliding_window: int = 513
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    lora_rescale: bool = True          # apply_mla_qkv_lora_rescale
    ring_multiple: int = 128           # the ring is whole tiles of positions
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(KINDS)
        if unknown or not self.layer_types:
            raise ValueError(f"layer types {sorted(unknown)}: only "
                             f"{KINDS} are implemented")
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if not 0 <= self.experts_first <= (
                self.n_routed_experts - self.experts_held):
            raise ValueError("held experts lie outside the routed ones")
        if self.index_head_dim < self.qk_rope_dim:
            raise ValueError("the indexer rotates the first qk_rope_dim "
                             "of its head")

    def attn(self, kind: str) -> AttnSizes:
        if kind == "full_attention":
            return AttnSizes(self.n_heads, self.qk_nope_dim, self.qk_rope_dim,
                             self.v_head_dim, self.q_rank, self.kv_rank,
                             self.rope_theta)
        return AttnSizes(self.swa_n_heads, self.swa_qk_nope_dim,
                         self.swa_qk_rope_dim, self.swa_v_head_dim,
                         self.swa_q_rank, self.swa_kv_rank,
                         self.swa_rope_theta)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    @property
    def n_moe_layers(self) -> int:
        return max(0, self.n_layers - self.first_k_dense)

    @property
    def ring_len(self) -> int:
        """Rows of a sliding layer's ring: the window, up to a whole
        number of `ring_multiple` (the compiler tiles the position axis)."""
        return -(-self.sliding_window // self.ring_multiple) * self.ring_multiple

    def num_params(self) -> int:
        shapes = jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))
        return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def from_published(config: dict, **overrides) -> Dots3NoteConfig:
    """The config from the keys of a published `config.json`. A file
    that describes ONE holder of a layer's experts gives the experts it
    holds as `n_routed_experts`, the first of them as `experts_first`,
    and the router's width, the published `n_routed_experts`, as
    `router_experts` (the published file has neither of the two)."""
    if (config.get("attention_gate_type", "headwise") != "headwise"
            or config.get("swa_attention_gate_type", "headwise") != "headwise"
            or config.get("scoring_func", "sigmoid") != "sigmoid"
            or config.get("topk_method", "noaux_tc") != "noaux_tc"
            or config.get("rope_scaling") is not None
            or config.get("attention_bias", False)
            or int(config.get("moe_layer_freq", 1)) != 1
            or int(config.get("n_shared_experts", 1)) != 1
            or config.get("tie_word_embeddings", False)):
        raise ValueError("only head-wise gates, sigmoid noaux_tc routing, "
                         "one shared expert in every layer past the dense "
                         "ones, plain RoPE and an untied head are "
                         "implemented for this family")
    if (int(config["num_key_value_heads"]) != int(config["num_attention_heads"])
            or int(config["swa_num_key_value_heads"])
            != int(config["swa_num_attention_heads"])):
        raise ValueError("latent attention has one key row for all heads: "
                         "num_key_value_heads must equal the heads")
    kw = dict(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        layer_types=tuple(config["layer_types"]),
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
        swa_n_heads=int(config["swa_num_attention_heads"]),
        swa_qk_nope_dim=int(config["swa_qk_nope_head_dim"]),
        swa_qk_rope_dim=int(config["swa_qk_rope_head_dim"]),
        swa_v_head_dim=int(config["swa_v_head_dim"]),
        swa_q_rank=int(config["swa_q_lora_rank"]),
        swa_kv_rank=int(config["swa_kv_lora_rank"]),
        swa_rope_theta=float(config["swa_rope_theta"]),
        sliding_window=int(config["sliding_window_size"]),
        index_n_heads=int(config["index_n_heads"]),
        index_head_dim=int(config["index_head_dim"]),
        index_topk=int(config["index_topk"]),
        lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]),
        norm_eps=float(config["rms_norm_eps"]))
    if len(kw["layer_types"]) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    kw.update(overrides)
    return Dots3NoteConfig(**kw)


# ------------------------------------------------------------------- params
def _layer_shapes(cfg: Dots3NoteConfig, li: int) -> dict:
    """name -> (shape, fan_in | "one" | "zero" | "bias") of layer `li`."""
    kind = cfg.layer_types[li]
    a, d = cfg.attn(kind), cfg.dim
    out = {"norm": ((d,), "one"),
           "w_qa": ((d, a.q_rank), d), "q_norm": ((a.q_rank,), "one"),
           "w_qb": ((a.heads * (a.nope + a.rope), a.q_rank), a.q_rank),
           "w_kva": ((d, a.kv_rank + a.rope), d), "kv_norm": ((a.kv_rank,), "one"),
           "w_kvb_k": ((a.kv_rank, a.heads, a.nope), a.kv_rank),
           "w_kvb_v": ((a.kv_rank, a.heads, a.v), a.kv_rank),
           "w_gate_attn": ((d, a.heads), d),
           "w_o": ((a.heads * a.v, d), a.heads * a.v),
           "mlp_norm": ((d,), "one")}
    if kind == "full_attention":
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        out.update({"wi_q": ((hi * di, a.q_rank), a.q_rank),
                    "wi_k": ((d, di), d),
                    "wi_k_norm": ((di,), "one"),
                    "wi_k_bias": ((di,), "zero"),
                    "wi_w": ((d, hi), d)})
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


# the router's selection bias: a normal draw of this spread, against
# scores whose own spread is 0.2, so that it moves a choice at the margin
ROUTER_BIAS_STD = 0.02


def init_params(cfg: Dots3NoteConfig, key: jax.Array) -> dict:
    """Matrices N(0, 1/fan_in), norms one, the indexer's LayerNorm bias
    zero, the router's selection bias N(0, ROUTER_BIAS_STD**2) in
    float32 (small and not zero: it changes which experts are chosen and
    never their weights)."""
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 3 + 24 * cfg.n_layers))

    def make(shape, how):
        if how == "one":
            return jnp.ones(shape, pd)
        if how == "zero":
            return jnp.zeros(shape, pd)
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


def param_logical_axes(cfg: Dots3NoteConfig) -> dict:
    """Everything replicated (TENSOR_PARALLEL is False): leaves are tuples
    of None, one for each axis."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a: (None,) * a.ndim, shapes)


# ---------------------------------------------------------------- the parts
def _rope(x, positions, a: AttnSizes):
    """Rotated halves over the last axis, at `a`'s frequencies; x: [b,
    s, heads, dim], positions: [b, s]."""
    half = x.shape[-1] // 2
    if a.yarn is None:
        inv = 1.0 / (a.theta ** (jnp.arange(half, dtype=F32) / half))
    else:
        inv = jnp.asarray(yarn_inv_freq(2 * half, a.theta, *a.yarn))
    ang = positions.astype(F32)[..., None] * inv
    return apply_rope(x, jnp.cos(ang), jnp.sin(ang))


def _queries(cfg, a: AttnSizes, layer, h, rel):
    """The low-rank query: (c_q [b, s, r_q], q_nope [b, s, H, nope],
    q_rope [b, s, H, rope], rotated)."""
    b, s, _ = h.shape
    dt = cfg.dtype
    with jax.named_scope("mla_q"):
        c_q = rms_norm(h @ layer["w_qa"].astype(dt), layer["q_norm"],
                       cfg.norm_eps)
        if cfg.lora_rescale:
            c_q = c_q * jnp.asarray(math.sqrt(cfg.dim / a.q_rank), dt)
        # w_qb (and the indexer's wi_q) lie [out, in], as the checkpoint
        # stores them: held [in, out] the compiler transposes all 50 MB
        # of each on every step (sandbox compile, PR 32)
        q = jnp.einsum("bsr,nr->bsn", c_q, layer["w_qb"].astype(dt)
                       ).reshape(b, s, a.heads, -1)
        q_nope, q_rope = q[..., :a.nope], q[..., a.nope:]
        return c_q, q_nope, _rope(q_rope, rel, a)


def _latent_rows(cfg, a: AttnSizes, layer, h, rel):
    """What a position leaves in the cache: [c_kv | k_rope], [b, s, row]."""
    dt = cfg.dtype
    kv = h @ layer["w_kva"].astype(dt)
    c_kv = rms_norm(kv[..., :a.kv_rank], layer["kv_norm"], cfg.norm_eps)
    if cfg.lora_rescale:
        c_kv = c_kv * jnp.asarray(math.sqrt(cfg.dim / a.kv_rank), dt)
    k_rope = _rope(kv[..., None, a.kv_rank:], rel, a)[:, :, 0]
    fill = jnp.zeros(kv.shape[:-1] + (a.row - kv.shape[-1],), dt)
    return jnp.concatenate([c_kv, k_rope, fill], axis=-1)


def _index_parts(cfg, a: AttnSizes, layer, h, c_q, rel):
    """The indexer's (queries [b, s, HI, dI], keys [b, s, dI], head
    weights [b, s, HI] float32)."""
    b, s, _ = h.shape
    dt, hi, di, dr = cfg.dtype, cfg.index_n_heads, cfg.index_head_dim, a.rope
    q = jnp.einsum("bsr,nr->bsn", c_q, layer["wi_q"].astype(dt)
                   ).reshape(b, s, hi, di)
    q = jnp.concatenate([_rope(q[..., :dr], rel, a), q[..., dr:]], -1)
    k = (h @ layer["wi_k"].astype(dt)).astype(F32)
    k = (k - k.mean(-1, keepdims=True)) * jax.lax.rsqrt(
        k.var(-1, keepdims=True) + cfg.norm_eps)
    k = (k * layer["wi_k_norm"].astype(F32)
         + layer["wi_k_bias"].astype(F32)).astype(dt)
    k = jnp.concatenate([_rope(k[:, :, None, :dr], rel, a)[:, :, 0],
                         k[..., dr:]], -1)
    w = (h @ layer["wi_w"].astype(dt)).astype(F32) / math.sqrt(hi * di)
    return q, k, w


def _index_scores(q, w, keys):
    """I(t, s) for queries [b, s, HI, dI] with weights [b, s, HI] against
    keys [b, n, dI]: [b, s, n] float32."""
    dots = jnp.einsum("bqhd,bkd->bqhk", q, keys, preferred_element_type=F32)
    return (jax.nn.relu(dots) * w[..., None]).sum(2)


def _kth_largest_mask(scores, valid, k: int):
    """valid & (score >= the k-th largest valid score of its row), over
    the last axis: the exact top-k as a mask (every valid position where
    there are no more than k), by bisection on the scores' bits: 32
    passes of a comparison and a count, no sort."""
    if scores.shape[-1] <= k:
        return valid
    bits = jax.lax.bitcast_convert_type(scores.astype(F32), jnp.int32)
    # float order as unsigned order; an invalid position sorts lowest
    key = jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31))
    key = jnp.where(valid, jax.lax.bitcast_convert_type(key, jnp.uint32),
                    jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (key >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    return valid & (key >= thr[..., None])


def _filled(like, shape, value: float = 0.0):
    """A float32 array of `value` that depends on `like`'s data. As a bare
    constant the compiler hoists and merges such buffers into broadcasts
    that carry no scope's name, and the trace files their time under
    nobody's (a fifth of the cell's unscoped device time; my chip run,
    PR 32)."""
    return jnp.broadcast_to(
        like.reshape(-1)[:1].astype(F32) * 0.0 + value, shape)


def _heads_first(q_nope, q_rope):
    """[q_nope | q_rope] as [b, H, s, nope + rope]: the order the blocks'
    score product takes it in, made once (left to the compiler it is a
    copy with no scope's name, inside every chunk)."""
    return jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)


def _attend_block(state, a: AttnSizes, layer, q, rows, mask, dt):
    """One block of keys in the EXPANDED form, folded into the running
    softmax `state` = (m, l, acc). q: `_heads_first`; rows: [b, n, row]
    latent rows; mask: [b, q, n]."""
    m, l, acc = state
    c, kr = rows[..., :a.kv_rank], rows[..., a.kv_rank:a.kv_rank + a.rope]
    k_nope = jnp.einsum("bkc,chn->bkhn", c, layer["w_kvb_k"].astype(dt))
    v = jnp.einsum("bkc,chv->bkhv", c, layer["w_kvb_v"].astype(dt))
    # one product over [nope | rope], the shared rope key repeated for
    # every head: as two products the rope's, 64 deep, took longer than
    # the nope's and each wrote the block's scores in float32 (my chip
    # run, PR 32)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, :, None], k_nope.shape[:3] + (a.rope,))], axis=-1)
    s = jnp.einsum("bhqd,bkhd->bhqk", q, k, preferred_element_type=F32
                   ) * a.scale
    s = jnp.where(mask[:, None], s, NEG)
    m_new = jnp.maximum(m, s.max(-1))
    p = jnp.where(mask[:, None], jnp.exp(s - m_new[..., None]), 0.0)
    scale = jnp.exp(m - m_new)
    l = l * scale + p.sum(-1)
    acc = acc * scale[..., None] + jnp.einsum(
        "bhqk,bkhv->bhqv", p.astype(dt), v, preferred_element_type=F32)
    return m_new, l, acc


def _attend_init(q, a: AttnSizes):
    b, _, s, _ = q.shape
    return (_filled(q, (b, a.heads, s), NEG), _filled(q, (b, a.heads, s)),
            _filled(q, (b, a.heads, s, a.v)))


def _attend_done(state, dt):
    _, l, acc = state
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(dt)          # [b, s, H, v]


def _chunk_tiles(a: AttnSizes, s: int, n: int):
    """The kernel's tiles for a chunk of `s` queries against `n` keys, or
    None where the chunk keeps the plain form (`_attend_block`): chosen,
    as `ops/attention.cached_attention` chooses its decode kernel, from
    the platform and the shapes alone. On a TPU, more than one query, and
    heads, chunk and keys whole in the kernel's tiles."""
    if s == 1 or not _attention._on_tpu():
        return None
    return tiles(a.heads, a.nope, a.rope, a.v, a.kv_rank, s, n)


def _attend_kernel(a: AttnSizes, layer, q, rows, li, mask, t):
    """ops/pallas/latent_attention.py over layer `li` of the stacked
    `rows` [layers, b, n, row]: what folding `_attend_block` over the
    keys and `_attend_done` give, [b, s, H, v], with no block's scores
    in memory."""
    return latent_attention(
        q, rows, li, layer["w_kvb_k"], layer["w_kvb_v"], mask,
        kv_rank=a.kv_rank, rope=a.rope, t=t, scale=a.scale
        ).transpose(0, 2, 1, 3)


def _absorbed_query(a: AttnSizes, layer, q_nope, q_rope, dt):
    """A query as it meets a cached row: the key's up-projection folded
    into q_nope, [q_nope W_k | q_rope | zeros], [b, H, row]."""
    q_abs = jnp.einsum("bhn,chn->bhc", q_nope, layer["w_kvb_k"].astype(dt))
    fill = jnp.zeros(q_abs.shape[:-1] + (a.row - a.kv_rank - a.rope,), dt)
    return jnp.concatenate([q_abs, q_rope, fill], axis=-1)


def _absorbed(a: AttnSizes, layer, q_nope, q_rope, rows, mask, dt):
    """The ABSORBED form for one query a row: q_nope [b, H, nope],
    q_rope [b, H, rope] against rows [b, n, row] under mask [b, n];
    returns [b, H, v]."""
    q_cat = _absorbed_query(a, layer, q_nope, q_rope, dt)
    s = jnp.einsum("bhr,bkr->bhk", q_cat, rows,
                   preferred_element_type=F32) * a.scale
    s = jnp.where(mask[:, None], s, NEG)
    p = jnp.where(mask[:, None], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).astype(dt)
    o_lat = jnp.einsum("bhk,bkc->bhc", p, rows[..., :a.kv_rank])
    return jnp.einsum("bhc,chv->bhv", o_lat, layer["w_kvb_v"].astype(dt))


def _gate_out(cfg, layer, h, attn):
    """attn [b, s, H, v] -> gated, through w_o: [b, s, d]."""
    b, s = attn.shape[:2]
    dt = cfg.dtype
    with jax.named_scope("attn_gate_out"):
        g = jax.nn.sigmoid((h @ layer["w_gate_attn"].astype(dt)).astype(F32))
        gated = (attn.astype(F32) * g[..., None]).astype(dt)
        return gated.reshape(b, s, -1) @ layer["w_o"].astype(dt)


def _ffn(cfg: Dots3NoteConfig, layer, x, valid, collect: bool):
    """x [b, s, d] -> (x + FFN(rmsnorm(x)), (pairs, hit, tiles), aux)."""
    b, s, d = x.shape
    dt = cfg.dtype
    swiglu = lambda h, g, u, dn: (jax.nn.silu(h @ layer[g].astype(dt))
                                  * (h @ layer[u].astype(dt))
                                  ) @ layer[dn].astype(dt)
    if "router" not in layer:
        with jax.named_scope("mlp"):
            h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            return x + swiglu(h, "w_gate", "w_up", "w_down"), None, {}
    with jax.named_scope("moe_router"):
        h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps).reshape(b * s, d)
        scores, chosen, weights = route_sigmoid_topk(
            h, layer["router"], layer["router_bias"], cfg.experts_per_tok,
            normalize=cfg.norm_topk_prob, scaling=cfg.routed_scaling)
    with jax.named_scope("moe_experts"):
        y, *moe = held_experts_ffn(
            h, chosen, weights, layer["we_gate"], layer["we_up"],
            layer["we_down"], cfg.experts_first,
            valid=None if valid is None else valid.reshape(b * s))
    with jax.named_scope("moe_shared"):
        y = y + swiglu(h, "ws_gate", "ws_up", "ws_down").astype(F32)
    aux = {"router_scores": scores.reshape(b, s, -1),
           "chosen": chosen.reshape(b, s, -1)} if collect else {}
    return x + y.reshape(b, s, d).astype(dt), moe, aux


def _embed(cfg, params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)


def _logits(cfg, params, x):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (x @ params["lm_head"].astype(cfg.dtype)).astype(F32)


def forward(params: dict, tokens: jax.Array, cfg: Dots3NoteConfig,
            collect: bool = False):
    """tokens: [b, s] int32 -> logits [b, s, vocab] (f32): the whole
    sequence, no cache kept, attention in the expanded form over all s
    keys at once (so s is bounded by memory: the serving path is
    `decode_step`). With `collect`, also what was selected: a dict
    {"selected": [full layers] of [b, s, s] bool, "index_scores": the
    same of float32, "chosen": [expert layers] of [b, s, k],
    "router_scores": of [b, s, E]}."""
    b, s = tokens.shape
    dt = cfg.dtype
    pos = jnp.arange(s)[None, :].repeat(b, 0)
    causal = (pos[:, :, None] >= pos[:, None, :])
    x = _embed(cfg, params, tokens)
    seen: dict = {"selected": [], "index_scores": [], "chosen": [],
                  "router_scores": []}
    for li, layer in enumerate(params["layers"]):
        kind = cfg.layer_types[li]
        a = cfg.attn(kind)
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        c_q, q_nope, q_rope = _queries(cfg, a, layer, h, pos)
        with jax.named_scope("mla_kv"):
            rows = _latent_rows(cfg, a, layer, h, pos)
        if kind == "full_attention":
            with jax.named_scope("index_score"):
                qi, ki, wi = _index_parts(cfg, a, layer, h, c_q, pos)
                scores = jnp.where(causal, _index_scores(qi, wi, ki), -jnp.inf)
            with jax.named_scope("index_topk"):
                mask = _kth_largest_mask(scores, causal, cfg.index_topk)
            seen["selected"].append(mask)
            seen["index_scores"].append(scores)
            scope = "sparse_attn"
        else:
            mask = causal & (pos[:, :, None] - pos[:, None, :]
                             < cfg.sliding_window)
            scope = "window_attn"
        with jax.named_scope(scope):
            q = _heads_first(q_nope, q_rope)
            attn = _attend_done(_attend_block(
                _attend_init(q, a), a, layer, q, rows, mask, dt), dt)
        x = x + _gate_out(cfg, layer, h, attn)
        x, _, aux = _ffn(cfg, layer, x, None, collect)
        for k_, v_ in aux.items():
            seen[k_].append(v_)
    logits = _logits(cfg, params, x)
    return (logits, seen) if collect else logits


# ----------------------------------------------------------------- decoding
# Axis of the cache positions, for the leaves that are as deep as the
# cache. "window" has a position axis too (2), but it is a ring of
# `ring_len` rows whatever the depth, valid at the one position it was
# written up to: to whoever cuts or grafts positions (serve/llm.py) it
# is recurrent, like a state, and is grafted whole.
CACHE_LEN_AXIS = {"latent": 2, "index": 2}
# what `LLMEngine.stats()["cache_bytes"]` files each leaf under
CACHE_KIND = {"latent": "latent", "index": "index", "window": "window"}
# the step's counters decided on the device, in the order of cache["aux"]:
# field of the engine's emit span -> counter of stats()
STEP_AUX = {"expert_rows": "moe_expert_rows",
            "experts_hit": "moe_experts_hit",
            "expert_tiles": "moe_expert_tiles"}


def init_cache(cfg: Dots3NoteConfig, batch: int,
               max_len: int | None = None) -> dict:
    """An empty cache: of the full layers the latent rows ``[layers, b,
    len, kv_rank + rope]`` and the index keys ``[layers, b, len,
    index_head_dim]``; of the sliding layers the ring ``[layers, b,
    ring_len, swa_kv_rank + rope]``, whose depth does not follow
    `max_len`; "aux", the last step's device-side counters."""
    max_len = max_len or cfg.max_seq_len
    nf, ns = cfg.count(KINDS[0]), cfg.count(KINDS[1])
    return {
        "latent": jnp.zeros((nf, batch, max_len, cfg.attn(KINDS[0]).row),
                            cfg.dtype),
        "index": jnp.zeros((nf, batch, max_len, cfg.index_head_dim),
                           cfg.dtype),
        "window": jnp.zeros((ns, batch, cfg.ring_len, cfg.attn(KINDS[1]).row),
                            cfg.dtype),
        "aux": jnp.zeros((len(STEP_AUX),), jnp.int32),
        "length": jnp.zeros((), jnp.int32),
        "start": jnp.zeros((batch,), jnp.int32),
    }


def cache_logical_axes(cfg: Dots3NoteConfig) -> dict:
    return {"latent": ("layers", "batch", None, None),
            "index": ("layers", "batch", None, None),
            "window": ("layers", "batch", None, None),
            "aux": (None,), "length": (), "start": ("batch",)}


def decode_read_block(cfg: Dots3NoteConfig, mesh) -> int | None:
    """Positions in a block of the decode step's cache reads, or None
    where a step reads a layer's whole depth: the indexer scores every
    position of every row (256 B each) and masks."""
    return None


def decode_counters(cfg: Dots3NoteConfig, spans: list, rows: int) -> dict:
    """What one decode step of `rows` rows does for live rows at `spans`
    [(start, the position the step writes)], by layer kind: positions
    the indexers score, latent rows the full layers gather and attend
    to, ring rows the sliding layers attend to. (Nothing here counts the
    rows that hold no request.)"""
    depth = [last - start + 1 for start, last in spans]
    nf, ns = cfg.count(KINDS[0]), cfg.count(KINDS[1])
    return {
        "decode_index_positions_scored": nf * sum(depth),
        "decode_latent_positions_attended":
            nf * sum(min(n, cfg.index_topk) for n in depth),
        "decode_window_positions_attended":
            ns * sum(min(n, cfg.sliding_window) for n in depth)}


def _tiles_visited(t, start: int, pos: int, chunk: int, k_pos, reach: int):
    """Pairs (query, key) in the kernel's tiles `t` that hold one with
    start <= key <= query < key + reach, for `chunk` queries from `pos`
    against keys at positions `k_pos`: the tiles that padding,
    causality and the window leave live."""
    pairs = 0
    for t0 in range(pos, pos + chunk, t.q):
        t1 = t0 + t.q - 1
        seen = ((k_pos >= max(start, 0)) & (k_pos <= t1) & (t1 >= start)
                & (k_pos > max(t0, start) - reach))
        pairs += t.q * t.k * int(seen.reshape(-1, t.k).any(1).sum())
    return pairs


def _dense_keys_visited(a: AttnSizes, start: int, pos: int, chunk: int,
                        depth: int) -> int:
    """Key positions whose scores one layer computes for `chunk` queries
    from `pos` of a row that starts at `start`, against a cache `depth`
    deep with nothing but causality and padding to empty a tile: under
    the kernel its live tiles, in the plain form every block from the
    first real position to the last written."""
    t = _chunk_tiles(a, chunk, depth)
    if t is None:
        blk = min(depth, 1024)
        return chunk * blk * ((pos + chunk - 1) // blk + 1 - start // blk)
    return _tiles_visited(t, start, pos, chunk, np.arange(depth),
                          depth + chunk)


def prefill_counters(cfg: Dots3NoteConfig, start: int, pos: int, chunk: int,
                     depth: int) -> dict:
    """What the attention of one prefill chunk does: `chunk` queries at
    positions [pos, pos + chunk) of a row whose first real token lies at
    `start`, against a cache `depth` deep. By layer kind, key positions
    summed over the chunk's queries: `visible`, those a query attends to
    (the selected ones, the window's), and `visited`, those whose scores
    are computed: under the kernel the live tiles (the selection could
    empty one more, which this does not know), in the plain form every
    block from the first real position to the last written, and the
    ring and the chunk whole. visible / visited says how much of the
    computed scores count."""
    # how deep each real query of the chunk lies in its row
    depths = np.arange(max(pos, start), pos + chunk) - start + 1
    ring = cfg.ring_len
    full = _dense_keys_visited(cfg.attn(KINDS[0]), start, pos, chunk, depth)
    t = _chunk_tiles(cfg.attn(KINDS[1]), chunk, ring + chunk)
    if t is None:
        window = chunk * (ring + chunk)
    else:
        # the keys as `_sliding_layer` lays them out: the ring as the
        # chunk found it, then the chunk
        k_pos = np.concatenate([
            pos - 1 - (pos - 1 - np.arange(ring)) % ring,
            pos + np.arange(chunk)])
        window = _tiles_visited(t, start, pos, chunk, k_pos,
                                cfg.sliding_window)
    nf, ns = cfg.count(KINDS[0]), cfg.count(KINDS[1])
    return {
        "prefill_latent_keys_visited": nf * full,
        "prefill_latent_keys_visible":
            nf * int(np.minimum(depths, cfg.index_topk).sum()),
        "prefill_window_keys_visited": ns * window,
        "prefill_window_keys_visible":
            ns * int(np.minimum(depths, cfg.sliding_window).sum())}


def _write_rows(stack, li: int, new, cache_len, at=None):
    """new [b, s, w] into layer `li` of `stack` [layers, b, len, w] at
    positions `at` (default `cache_len`): one block for a scalar, one
    small in-place write a row for per-row depths (as
    ops/attention._write_rows writes K and V)."""
    at = cache_len if at is None else at
    if jnp.ndim(at) == 0:
        return jax.lax.dynamic_update_slice(stack, new[None], (li, 0, at, 0))
    for r in range(new.shape[0]):
        stack = jax.lax.dynamic_update_slice(
            stack, new[None, r:r + 1], (li, r, at[r], 0))
    return stack


def _full_layer(cfg, a, layer, li, h, c_q, q_nope, q_rope, rel, latent,
                index, cache_len, abs_pos, start, collect):
    """A full layer against its cache; returns (attn [b, s, H, v],
    latent, index, aux)."""
    b, s = abs_pos.shape
    dt = cfg.dtype
    depth = latent.shape[2]
    with jax.named_scope("mla_kv"):
        latent = _write_rows(latent, li, _latent_rows(cfg, a, layer, h, rel),
                             jnp.maximum(cache_len, 0))
    with jax.named_scope("index_score"):
        qi, ki, wi = _index_parts(cfg, a, layer, h, c_q, rel)
        index = _write_rows(index, li, ki, jnp.maximum(cache_len, 0))
    k_pos = jnp.arange(depth)
    if s == 1:
        # a decode step: score every position, pick, gather, absorbed
        with jax.named_scope("index_score"):
            visible = ((k_pos[None, :] <= abs_pos) &
                       (k_pos[None, :] >= start[:, None]))          # [b, n]
            scores = jnp.where(visible, _index_scores(
                qi, wi, index[li])[:, 0], -jnp.inf)
        with jax.named_scope("index_topk"):
            top, picked = jax.lax.top_k(scores, min(cfg.index_topk, depth))
        with jax.named_scope("sparse_attn"):
            # straight out of the stack: cut first, `latent[li]` is a
            # copy of the whole layer, 1 GB and 3.3 ms a step for 32 x
            # 24,576 (my chip run, PR 32)
            rows = latent.at[li, jnp.arange(b)[:, None], picked].get(
                mode="promise_in_bounds")
            attn = _absorbed(a, layer, q_nope[:, 0], q_rope[:, 0], rows,
                             top > -jnp.inf, dt)[:, None]
        aux = {}
        if collect:
            hit = jnp.zeros((b, depth), bool).at[
                jnp.arange(b)[:, None], picked].set(top > -jnp.inf)
            aux = {"selected": hit[:, None], "index_scores": scores[:, None]}
        return attn, latent, index, aux
    # a chunk (the batch in lock-step): blocks of keys, up to the last
    # position written and from the first real one; expanded form
    blk = min(depth, 1024)
    first = jnp.min(start) // blk
    stop = (cache_len + s - 1) // blk + 1
    q_visible = abs_pos >= start[:, None]                           # [b, s]

    def cut(stack, j):
        """Block j of layer li: (rows, where they start, which of them
        are this block's: a last block that was moved back to fit repeats
        positions of the block before it)."""
        at = jnp.minimum(j * blk, depth - blk)
        rows = jax.lax.dynamic_slice_in_dim(stack[li], at, blk, axis=1)
        return rows, at, (at + jnp.arange(blk)) >= j * blk

    with jax.named_scope("index_score"):
        def score(j, scores):
            keys, at, own = cut(index, j)
            pos = (at + jnp.arange(blk))[None, None, :]
            visible = ((pos <= abs_pos[..., None])
                       & (pos >= start[:, None, None]))
            new = jnp.where(visible, _index_scores(qi, wi, keys), -jnp.inf)
            old = jax.lax.dynamic_slice_in_dim(scores, at, blk, axis=2)
            return jax.lax.dynamic_update_slice_in_dim(
                scores, jnp.where(own, new, old), at, 2)

        scores = jax.lax.fori_loop(
            first, stop, score, _filled(wi, (b, s, depth), -jnp.inf))
    with jax.named_scope("index_topk"):
        selected = _kth_largest_mask(scores, scores > -jnp.inf,
                                     cfg.index_topk)
    with jax.named_scope("sparse_attn"):
        q = _heads_first(q_nope, q_rope)
        t = _chunk_tiles(a, s, depth)
        if t is not None:
            # nothing is selected past the last position written or
            # before the first real one: those tiles are never fetched
            attn = _attend_kernel(a, layer, q, latent, li, selected, t)
        else:
            def attend(j, state):
                rows, at, own = cut(latent, j)
                mask = (jax.lax.dynamic_slice_in_dim(selected, at, blk, axis=2)
                        & own[None, None, :])
                return _attend_block(state, a, layer, q, rows, mask, dt)

            attn = _attend_done(jax.lax.fori_loop(
                first, stop, attend, _attend_init(q, a)), dt)
        attn = attn * q_visible[..., None, None].astype(dt)
    aux = {"selected": selected, "index_scores": scores} if collect else {}
    return attn, latent, index, aux


def _sliding_layer(cfg, a, layer, li, h, q_nope, q_rope, rel, window,
                   cache_len, abs_pos, start):
    """A sliding layer against its ring; returns (attn, window)."""
    b, s = abs_pos.shape
    dt = cfg.dtype
    ring = window.shape[2]
    with jax.named_scope("mla_kv"):
        new = _latent_rows(cfg, a, layer, h, rel)
    slot = jnp.arange(ring)
    if s == 1:
        with jax.named_scope("mla_kv"):
            at = jnp.maximum(cache_len, 0) % ring
            window = _write_rows(window, li, new, cache_len, at)
        with jax.named_scope("window_attn"):
            # row j holds the newest position congruent to j
            pos = abs_pos - (abs_pos - slot[None, :]) % ring          # [b, n]
            mask = ((abs_pos - pos < cfg.sliding_window)
                    & (pos >= start[:, None]))
            attn = _absorbed(a, layer, q_nope[:, 0], q_rope[:, 0],
                             window[li], mask, dt)[:, None]
        return attn, window
    # a chunk: the ring as the chunk found it, then the chunk itself
    with jax.named_scope("window_attn"):
        last = cache_len - 1
        old_pos = last - (last - slot) % ring                         # [n]
        k_pos = jnp.concatenate([jnp.broadcast_to(old_pos, (b, ring)),
                                 abs_pos], axis=1)                    # [b, n+s]
        dist = abs_pos[:, :, None] - k_pos[:, None, :]
        mask = ((dist >= 0) & (dist < cfg.sliding_window)
                & (k_pos >= jnp.maximum(start, 0)[:, None])[:, None, :]
                & (abs_pos >= start[:, None])[:, :, None])
        rows = jnp.concatenate([window[li], new], axis=1)
        q = _heads_first(q_nope, q_rope)
        t = _chunk_tiles(a, s, ring + s)
        if t is not None:
            attn = _attend_kernel(a, layer, q, rows[None], 0, mask, t)
        else:
            attn = _attend_done(_attend_block(
                _attend_init(q, a), a, layer, q, rows, mask, dt), dt)
    with jax.named_scope("mla_kv"):
        # the chunk's last ring_len rows, each to its own slot
        keep = min(s, ring)
        at = (cache_len + s - keep + jnp.arange(keep)) % ring
        window = jax.lax.dynamic_update_slice(
            window, window[li].at[:, at].set(new[:, s - keep:])[None],
            (li, 0, 0, 0))
    return attn, window


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                cfg: Dots3NoteConfig, collect: bool = False):
    """Append `tokens` [b, s] to the cache, return logits for the last
    position [b, vocab] and the updated cache: `llama.decode_step`'s
    contract. s = 1 is a decode step, with a scalar or per-row
    cache["length"] (a row with length < 0 holds no request: it reaches
    no expert and its result is not read); larger s is a prefill chunk
    of the batch in lock-step (a scalar length), which finds in the
    cache what the chunks before it left: latent rows, index keys, and
    the ring at the position it was written up to. Positions before
    cache["start"] are left padding: RoPE and the window count from
    `start`, and a padded position reaches neither the selection nor an
    expert. cache["aux"] comes back as (token-expert pairs computed on
    held experts, held experts with at least one token, tiles of rows
    the experts' loop walked), summed over the expert layers. With
    `collect` a third value is returned: what each full layer selected
    and each expert layer chose (`forward`'s dict, positions as the
    cache's)."""
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
    latent, index, window = cache["latent"], cache["index"], cache["window"]
    seen: dict = {"selected": [], "index_scores": [], "chosen": [],
                  "router_scores": []}
    counts = jnp.zeros((len(STEP_AUX),), jnp.int32)
    x = _embed(cfg, params, tokens)
    seen_kind = dict.fromkeys(KINDS, 0)
    for li, layer in enumerate(params["layers"]):
        kind = cfg.layer_types[li]
        ki = seen_kind[kind]
        seen_kind[kind] += 1
        a = cfg.attn(kind)
        with jax.named_scope("mla_q"):
            h = rms_norm(x, layer["norm"], cfg.norm_eps)
        c_q, q_nope, q_rope = _queries(cfg, a, layer, h, rel)
        if kind == "full_attention":
            attn, latent, index, aux = _full_layer(
                cfg, a, layer, ki, h, c_q, q_nope, q_rope, rel, latent,
                index, cache_len, abs_pos, start, collect)
        else:
            attn, window = _sliding_layer(
                cfg, a, layer, ki, h, q_nope, q_rope, rel, window,
                cache_len, abs_pos, start)
            aux = {}
        x = x + _gate_out(cfg, layer, h, attn)
        x, moe, aux_ffn = _ffn(cfg, layer, x, valid, collect)
        if moe is not None:
            counts = counts + jnp.stack(moe)
        for k_, v_ in {**aux, **aux_ffn}.items():
            seen[k_].append(v_)
    new_cache = {"latent": latent, "index": index, "window": window,
                 "aux": counts, "length": cache_len + s, "start": start}
    logits = _logits(cfg, params, x[:, -1])
    return (logits, new_cache, seen) if collect else (logits, new_cache)
