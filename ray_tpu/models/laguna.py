"""Decoder of the `laguna` family: grouped-query attention of TWO kinds
in one model, each with its own count of query heads, under a per-head
output gate; a dense MLP in the leading layer and many small routed
experts beside a shared one in the others.

Every layer:  h = x + Gate(Attn(rmsnorm(x)));  y = h + FFN(rmsnorm(h)).

Both kinds project `n_kv_heads` keys and values of `head_dim` and differ
in everything around them:

* a `full_attention` layer has `heads_full` query heads (48: groups of
  6), turns the FIRST HALF of each head under YaRN (`ops/rope.
  yarn_inv_freq` over the 64 rotated numbers, cos and sin times the
  config's `attention_factor`) and attends to every position from the
  row's `start` to the query's own. It caches K and V as deep as the
  cache, in `models/llama.py`'s orders (``k [layers, b, kv, hd, len]``,
  ``v [layers, b, kv, len, hd]``);
* a `sliding_attention` layer has `heads_sliding` query heads (72:
  groups of 9), turns the whole head plainly (theta 10,000) and attends
  to the `sliding_window` newest positions, its own among them. It
  caches a RING of `sliding_window` rows of K and V (``window_k [layers,
  b, kv, hd, ring]``, ``window_v [layers, b, kv, ring, hd]``), position p
  in row ``p mod ring``, whatever the cache's depth.

One number a head, ``sigmoid(h W_g)``, multiplies the attention's output
before `w_o` (dots3_note's `_gate_out`, imported with its expert layer
`_ffn`, embedding and head: a change to those parts is a change to three
models).

On a TPU, for shapes whole in its tiles, a prefill chunk's attention of
either kind is ops/pallas/gqa_chunk_attention.py (a full layer: the
chunk written to the cache, then the live tiles of the layer; a sliding
one: the ring as the chunk found it, then the chunk), and a decode
step's is ops/pallas/decode_attention.py: over the full stack through
`ops/attention.cached_attention`, which also writes the new row, and
over the ring in its `ring` mode, where the new row stands at ``length
mod ring``. The ring's write is the kernel's too: the tile around the
new row is cut from the one block the kernel holds anyway, as the full
layers' is, and no `dynamic_update_slice` a row (32 a layer) stands
ahead of the call. Elsewhere (every CPU run) both are masked XLA forms
that read a layer whole. Which it is follows from the platform and the
shapes: nothing selects it.

Layers in `mlp_only_layers` end in a dense SwiGLU MLP, the others in
`ops/moe.py`'s dropless expert layer: the router scores all
`n_routed_experts` (sigmoid, the `experts_per_tok` of largest score plus
selection bias, weights normalised and times `routed_scaling`), this
holder computes the part of the `experts_held` experts from
`experts_first` on, and the shared expert is added whole.

Same function set as models/llama.py, so serve/llm.py's engine runs it.
K and V of the full layers are in `CACHE_LEN_AXIS`; the rings are not,
so the engine grafts them whole and keeps no prefix of this model.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import dots3_note as _parts
from ray_tpu.models.dots3_note import ROUTER_BIAS_STD
from ray_tpu.ops import attention as _attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas import gqa_chunk_attention as _chunk
from ray_tpu.ops.rope import apply_partial_rope, yarn_inv_freq

F32 = jnp.float32
KINDS = ("full_attention", "sliding_attention")
# every chip of the deployment holds all heads of a layer and attends
# for its own requests; a tensor axis over the kv heads is not written
TENSOR_PARALLEL = False


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    dim: int = 3072
    n_layers: int = 48
    layer_types: tuple | None = None   # None: full, then three sliding
    heads_full: int = 48
    heads_sliding: int = 72
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512          # counts the query's own position
    mlp_only_layers: tuple = (0,)
    hidden_dim: int = 12288            # the dense layers' MLP
    moe_hidden_dim: int = 1024         # one routed expert
    shared_hidden_dim: int = 1024      # the shared expert
    n_routed_experts: int = 256        # the router's width
    experts_first: int = 0             # the experts this holder computes
    experts_held: int | None = None    # None: all of them
    experts_per_tok: int = 10
    norm_topk_prob: bool = True
    routed_scaling: float = 2.5
    # full layers: YaRN over the rotated part of a head
    rope_theta: float = 5e5
    rope_partial: float = 0.5
    rope_factor: float = 128.0         # 1: plain RoPE
    rope_original_len: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.4852030263919618
    # sliding layers: plain
    swa_rope_theta: float = 1e4
    swa_rope_partial: float = 1.0
    norm_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(
            self.layer_types or (KINDS[0] if i % 4 == 0 else KINDS[1]
                                 for i in range(self.n_layers))))
        object.__setattr__(self, "mlp_only_layers",
                           tuple(self.mlp_only_layers))
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if len(self.layer_types) != self.n_layers or (
                set(self.layer_types) - set(KINDS)):
            raise ValueError("layer_types names a kind of each layer")
        if not 0 <= self.experts_first <= (
                self.n_routed_experts - self.experts_held):
            raise ValueError("held experts lie outside the routed ones")
        if self.heads_full % self.n_kv_heads or (
                self.heads_sliding % self.n_kv_heads):
            raise ValueError("query heads come in whole groups a kv head")

    def heads(self, li: int) -> int:
        return (self.heads_full if self.layer_types[li] == KINDS[0]
                else self.heads_sliding)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def ring_len(self) -> int:
        return self.sliding_window

    @property
    def moe_layers(self) -> tuple:
        return tuple(li for li in range(self.n_layers)
                     if li not in self.mlp_only_layers)

    def num_params(self) -> int:
        shapes = jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))
        return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def from_published(config: dict, **overrides) -> LagunaConfig:
    """The config from the keys of a published `config.json`. A file
    that describes ONE holder of a layer's experts gives the experts it
    holds as `num_experts`, the first of them as `experts_first`, and
    the router's width, the published `num_experts`, as `router_experts`
    (the published file has neither of the two)."""
    types = tuple(config["layer_types"])
    heads = tuple(int(h) for h in config["num_attention_heads_per_layer"])
    n = int(config["num_hidden_layers"])
    by_kind = {kind: {h for t, h in zip(types, heads) if t == kind}
               for kind in KINDS}
    dense = tuple(i for i, t in enumerate(config["mlp_layer_types"])
                  if t == "dense")
    if (len(types) != n or len(heads) != n
            or any(len(v) > 1 for v in by_kind.values())
            or config.get("gating", "per-head") != "per-head"
            or set(config.get("gating_types", ["per_head"])) != {"per_head"}
            or dense != tuple(config.get("mlp_only_layers", dense))
            or int(config.get("decoder_sparse_step", 1)) != 1
            or config.get("attention_bias", False)
            or config.get("tie_word_embeddings", False)
            or config.get("moe_apply_router_weight_on_input", False)
            or config.get("moe_router_logit_softcapping", 0)):
        raise ValueError("only one head count a kind of layer, the per-head "
                         "gate in every layer, router weights on the "
                         "outputs, no logit cap, no bias and an untied head "
                         "are implemented for this family")
    full = config["rope_parameters"][KINDS[0]]
    swa = config["rope_parameters"][KINDS[1]]
    if swa.get("rope_type", "default") != "default" or (
            full.get("rope_type") not in ("yarn", "default")):
        raise ValueError("full layers turn under YaRN or plainly, sliding "
                         "layers plainly")
    yarn = full.get("rope_type") == "yarn"
    kw = dict(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=n, layer_types=types,
        heads_full=int(next(iter(by_kind[KINDS[0]]),
                            config["num_attention_heads"])),
        heads_sliding=int(next(iter(by_kind[KINDS[1]]),
                               config["num_attention_heads"])),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        sliding_window=int(config["sliding_window"]),
        mlp_only_layers=dense,
        hidden_dim=int(config["intermediate_size"]),
        moe_hidden_dim=int(config["moe_intermediate_size"]),
        shared_hidden_dim=int(config["shared_expert_intermediate_size"]),
        n_routed_experts=int(config.get("router_experts",
                                        config["num_experts"])),
        experts_held=int(config["num_experts"]),
        experts_first=int(config.get("experts_first", 0)),
        experts_per_tok=int(config["num_experts_per_tok"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling=float(config["moe_routed_scaling_factor"]),
        rope_theta=float(full["rope_theta"]),
        rope_partial=float(full.get("partial_rotary_factor", 1.0)),
        rope_factor=float(full["factor"]) if yarn else 1.0,
        rope_original_len=int(full.get(
            "original_max_position_embeddings",
            config.get("max_position_embeddings", 8192))),
        rope_beta_fast=float(full.get("beta_fast", 32)),
        rope_beta_slow=float(full.get("beta_slow", 1)),
        rope_attention_factor=float(full.get("attention_factor", 1.0))
        if yarn else 1.0,
        swa_rope_theta=float(swa["rope_theta"]),
        swa_rope_partial=float(swa.get("partial_rotary_factor", 1.0)),
        norm_eps=float(config["rms_norm_eps"]))
    kw.update(overrides)
    return LagunaConfig(**kw)



# ------------------------------------------------------------------- params
def _layer_shapes(cfg: LagunaConfig, li: int) -> dict:
    """name -> (shape, fan_in | "one" | "bias") of layer `li`; the FFN's
    names are dots3_note's, whose `_ffn` reads them."""
    d, hd = cfg.dim, cfg.head_dim
    h, kv = cfg.heads(li) * hd, cfg.n_kv_heads * hd
    out = {"norm": ((d,), "one"),
           "wq": ((d, h), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
           "w_gate_attn": ((d, cfg.heads(li)), d), "w_o": ((h, d), h),
           "mlp_norm": ((d,), "one")}
    if li in cfg.mlp_only_layers:
        f = cfg.hidden_dim
        out.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                    "w_down": ((f, d), f)})
    else:
        f, fs, e = cfg.moe_hidden_dim, cfg.shared_hidden_dim, cfg.experts_held
        out.update({"router": ((d, cfg.n_routed_experts), d),
                    "router_bias": ((cfg.n_routed_experts,), "bias"),
                    "we_gate": ((e, d, f), d), "we_up": ((e, d, f), d),
                    "we_down": ((e, f, d), f),
                    "ws_gate": ((d, fs), d), "ws_up": ((d, fs), d),
                    "ws_down": ((fs, d), fs)})
    return out


def init_params(cfg: LagunaConfig, key: jax.Array) -> dict:
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


def param_logical_axes(cfg: LagunaConfig) -> dict:
    """Everything replicated (TENSOR_PARALLEL is False): leaves are tuples
    of None, one for each axis."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a: (None,) * a.ndim, shapes)


# ---------------------------------------------------------------- the parts
def rope_of(cfg: LagunaConfig, kind: str) -> tuple:
    """(inverse frequencies [rotated // 2] float32, the factor on cos
    and sin) of a layer of `kind`."""
    if kind == KINDS[0]:
        rot = int(cfg.head_dim * cfg.rope_partial)
        if cfg.rope_factor > 1:
            return yarn_inv_freq(
                rot, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_len,
                cfg.rope_beta_fast, cfg.rope_beta_slow), \
                cfg.rope_attention_factor
        theta = cfg.rope_theta
    else:
        rot, theta = int(cfg.head_dim * cfg.swa_rope_partial), \
            cfg.swa_rope_theta
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    return inv.astype(np.float32), 1.0


def _qkv(cfg, layer, li: int, x, rel):
    """(h, q [b, s, H, hd], k, v [b, s, kv, hd]): the layer's normed
    input and its projections, q and k turned as the layer's kind turns
    them at positions `rel`."""
    b, s, _ = x.shape
    dt, hd = cfg.dtype, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        # q and k exist as projected before they are cut into heads for
        # the rotary turn: left free, the compiler makes the product give
        # them heads-major and copies wq and wk transposed for that on
        # every layer of every step (56 MB a sliding layer; models/llama.
        # py `_decode_block`, PR 45)
        q, k = jax.lax.optimization_barrier(
            (h @ layer["wq"].astype(dt), h @ layer["wk"].astype(dt)))
        v = (h @ layer["wv"].astype(dt)).reshape(b, s, cfg.n_kv_heads, hd)
        inv, factor = rope_of(cfg, cfg.layer_types[li])
        return (h, apply_partial_rope(q.reshape(b, s, cfg.heads(li), hd),
                                      rel, inv, factor),
                apply_partial_rope(k.reshape(b, s, cfg.n_kv_heads, hd),
                                   rel, inv, factor), v)


def _attend_plain(q, k, v, mask, scale: float):
    """q [b, s, H, hd] against K [b, kv, hd, n] and V [b, kv, n, hd]
    under mask [b, s, n], the XLA form: the group's heads are rows of
    one product a kv head; scores and softmax in float32. [b, s, H, hd];
    zeros for a query the mask lets nothing through for."""
    b, s, H, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(b, s, nkv, H // nkv, hd).transpose(0, 2, 1, 3, 4)
    logits = jnp.einsum("bnqgd,bndk->bnqgk", qg, k,
                        preferred_element_type=F32) * scale
    logits = jnp.where(mask[:, None, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bnqgk,bnkd->bqngd", probs, v).reshape(b, s, H, hd)
    return jnp.where(mask.any(-1)[:, :, None, None], out,
                     jnp.zeros((), out.dtype))


def _chunk_tiles(group: int, hd: int, s: int, n: int):
    """The chunk kernel's tiles for these shapes, or None where the plain
    form stays: off a TPU, and for shapes not whole in any tile."""
    if not _attention._on_tpu():
        return None
    return _chunk.tiles(group, hd, s, n)


def _by_group(q, nkv: int):
    """[b, s, H, hd] -> the kernel's [b, kv, group, s, hd]."""
    b, s, H, hd = q.shape
    return q.reshape(b, s, nkv, H // nkv, hd).transpose(0, 2, 3, 1, 4)


def _by_head(o):
    """The kernel's [b, kv, group, s, hd] -> [b, s, H, hd]."""
    b, nkv, g, s, hd = o.shape
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, nkv * g, hd)


def _ring_block(cfg: LagunaConfig, per_row: bool) -> int | None:
    """The ring's depth where a decode step runs the decode kernel over
    it (one block, the kernel's `ring` mode), or None where it keeps the
    XLA form: chosen, as `ops/attention.cached_attention` chooses, from
    the platform and the shapes alone."""
    if (not _attention._on_tpu() or not per_row or cfg.head_dim % 128
            or cfg.ring_len % 128):
        return None
    return cfg.ring_len


def _full_layer(cfg, ki: int, q, kk, vv, k_cache, v_cache, cache_len,
                abs_pos, start):
    """A full layer against layer `ki` of its stacks; returns (attn
    [b, s, H, hd], k_cache, v_cache)."""
    b, s, H, hd = q.shape
    nkv, depth = cfg.n_kv_heads, v_cache.shape[3]
    scale = hd ** -0.5
    t = _chunk_tiles(H // nkv, hd, s, depth) if s > 1 else None
    with jax.named_scope("full_attn"):
        if t is None:
            attn, k_cache, v_cache = _attention.cached_attention(
                q, kk, vv, k_cache, v_cache, ki, cache_len, abs_pos, start,
                scale=scale)
            return attn.reshape(b, s, H, hd), k_cache, v_cache
        k_cache, v_cache = _attention._write_rows(k_cache, v_cache, kk, vv,
                                                  ki, cache_len)
        k_pos = jnp.broadcast_to(jnp.arange(depth, dtype=jnp.int32),
                                 (b, depth))
        out = _chunk.gqa_chunk_attention(
            _by_group(q, nkv), k_cache, v_cache, ki,
            _chunk.seen_by_position(jnp, k_pos, start), cache_len,
            scale=scale, t=t)
        return _by_head(out), k_cache, v_cache


def _ring_decode(cfg, ki: int, q, kk, vv, ring_k, ring_v, cache_len, start):
    """One query a row against layer `ki` of the rings, the step's row
    written at ``length mod ring``."""
    b, _, H, hd = q.shape
    nkv, ring = cfg.n_kv_heads, ring_k.shape[4]
    scale = hd ** -0.5
    if _ring_block(cfg, jnp.ndim(cache_len) == 1):
        out, ring_k, ring_v = _attention.decode_attention(
            q.reshape(b, nkv, H // nkv, hd), ring_k, ring_v, ki, start,
            cache_len, scale=scale, block_len=ring,
            new_kv=(kk[:, 0], vv[:, 0]), ring=True)
        return out.reshape(b, 1, H, hd), ring_k, ring_v
    ring_k, ring_v = _attention._write_rows(
        ring_k, ring_v, kk, vv, ki, jnp.maximum(cache_len, 0) % ring)
    depth = jnp.broadcast_to(cache_len, (b,))[:, None]
    # row j holds the newest position congruent to j
    pos = depth - (depth - jnp.arange(ring)[None, :]) % ring      # [b, n]
    mask = ((pos >= start[:, None]) & (depth >= 0))[:, None, :]
    return (_attend_plain(q, ring_k[ki], ring_v[ki], mask, scale),
            ring_k, ring_v)


def _sliding_layer(cfg, ki: int, q, kk, vv, ring_k, ring_v, cache_len,
                   abs_pos, start):
    """A sliding layer against layer `ki` of its rings; returns (attn
    [b, s, H, hd], ring_k, ring_v)."""
    b, s, H, hd = q.shape
    nkv, ring = cfg.n_kv_heads, ring_k.shape[4]
    scale = hd ** -0.5
    with jax.named_scope("window_attn"):
        if s == 1:
            return _ring_decode(cfg, ki, q, kk, vv, ring_k, ring_v,
                                cache_len, start)
        # a chunk: the ring as the chunk found it, then the chunk itself
        k_new = kk.transpose(0, 2, 3, 1)                     # [b, kv, hd, s]
        v_new = vv.transpose(0, 2, 1, 3)                     # [b, kv, s, hd]
        last = cache_len - 1
        old_pos = last - (last - jnp.arange(ring)) % ring             # [n]
        k_pos = jnp.concatenate([jnp.broadcast_to(old_pos, (b, ring)),
                                 abs_pos], axis=1).astype(jnp.int32)
        k_all = jnp.concatenate([ring_k[ki], k_new], axis=3)
        v_all = jnp.concatenate([ring_v[ki], v_new], axis=2)
        t = _chunk_tiles(H // nkv, hd, s, ring + s)
        if t is not None:
            attn = _by_head(_chunk.gqa_chunk_attention(
                _by_group(q, nkv), k_all[None], v_all[None], 0,
                _chunk.seen_by_position(jnp, k_pos, start,
                                        cfg.sliding_window),
                cache_len, scale=scale, t=t))
        else:
            dist = abs_pos[:, :, None] - k_pos[:, None, :]
            mask = ((dist >= 0) & (dist < cfg.sliding_window)
                    & (k_pos >= start[:, None])[:, None, :])
            attn = _attend_plain(q, k_all, v_all, mask, scale)
        # the chunk's last `ring` rows, each to its own slot
        keep = min(s, ring)
        if keep == ring:
            # a whole turn: the rows in order, turned to where they lie
            shift = (cache_len + s) % ring
            k_l = jnp.roll(k_new[..., s - ring:], shift, axis=3)
            v_l = jnp.roll(v_new[:, :, s - ring:], shift, axis=2)
        else:
            at = (cache_len + s - keep + jnp.arange(keep)) % ring
            k_l = ring_k[ki].at[..., at].set(k_new[..., s - keep:])
            v_l = ring_v[ki].at[:, :, at].set(v_new[:, :, s - keep:])
        ring_k = jax.lax.dynamic_update_slice(ring_k, k_l[None],
                                              (ki, 0, 0, 0, 0))
        ring_v = jax.lax.dynamic_update_slice(ring_v, v_l[None],
                                              (ki, 0, 0, 0, 0))
    return attn, ring_k, ring_v


def forward(params: dict, tokens: jax.Array, cfg: LagunaConfig,
            collect: bool = False):
    """tokens: [b, s] int32 -> logits [b, s, vocab] (f32): the whole
    sequence, no cache kept, scores over all s keys at once (so s is
    bounded by memory: the serving path is `decode_step`). With
    `collect`, also what was chosen: {"chosen": [expert layers] of
    [b, s, k], "router_scores": of [b, s, E]}."""
    b, s = tokens.shape
    pos = jnp.arange(s)[None, :].repeat(b, 0)
    dist = pos[:, :, None] - pos[:, None, :]
    masks = {KINDS[0]: dist >= 0,
             KINDS[1]: (dist >= 0) & (dist < cfg.sliding_window)}
    x = _parts._embed(cfg, params, tokens)
    seen: dict = {"chosen": [], "router_scores": []}
    for li, layer in enumerate(params["layers"]):
        kind = cfg.layer_types[li]
        h, q, kk, vv = _qkv(cfg, layer, li, x, pos)
        with jax.named_scope("full_attn" if kind == KINDS[0]
                             else "window_attn"):
            attn = _attend_plain(q, kk.transpose(0, 2, 3, 1),
                                 vv.transpose(0, 2, 1, 3), masks[kind],
                                 cfg.head_dim ** -0.5)
        x = x + _parts._gate_out(cfg, layer, h, attn)
        x, _, aux = _parts._ffn(cfg, layer, x, None, collect)
        for k_, v_ in aux.items():
            seen[k_].append(v_)
    logits = _parts._logits(cfg, params, x)
    return (logits, seen) if collect else logits


# ----------------------------------------------------------------- decoding
# K and V of the full layers have a position axis as deep as the cache.
# The rings have one too, but of `ring_len` rows whatever the depth,
# valid at the one position they were written up to: to whoever cuts or
# grafts positions (serve/llm.py) they are recurrent, like a state, and
# are grafted whole.
CACHE_LEN_AXIS = {"k": 4, "v": 3}
# what `LLMEngine.stats()["cache_bytes"]` files each leaf under
CACHE_KIND = {"k": "kv", "v": "kv", "window_k": "window",
              "window_v": "window"}
STEP_AUX = _parts.STEP_AUX


def init_cache(cfg: LagunaConfig, batch: int,
               max_len: int | None = None) -> dict:
    """An empty cache: of the full layers K ``[layers, b, kv, hd, len]``
    and V ``[layers, b, kv, len, hd]``; of the sliding layers the rings
    ``[layers, b, kv, hd, ring]`` / ``[layers, b, kv, ring, hd]``, whose
    depth does not follow `max_len`; "aux", the last step's device-side
    counters."""
    max_len = max_len or cfg.max_seq_len
    hd, ring = cfg.head_dim, cfg.ring_len
    full = (cfg.count(KINDS[0]), batch, cfg.n_kv_heads)
    swa = (cfg.count(KINDS[1]), batch, cfg.n_kv_heads)
    return {"k": jnp.zeros(full + (hd, max_len), cfg.dtype),
            "v": jnp.zeros(full + (max_len, hd), cfg.dtype),
            "window_k": jnp.zeros(swa + (hd, ring), cfg.dtype),
            "window_v": jnp.zeros(swa + (ring, hd), cfg.dtype),
            "aux": jnp.zeros((len(STEP_AUX),), jnp.int32),
            "length": jnp.zeros((), jnp.int32),
            "start": jnp.zeros((batch,), jnp.int32)}


def cache_logical_axes(cfg: LagunaConfig) -> dict:
    k = ("layers", "batch", "kv_heads", "head_dim", None)
    v = ("layers", "batch", "kv_heads", None, "head_dim")
    return {"k": k, "v": v, "window_k": k, "window_v": v,
            "aux": (None,), "length": (), "start": ("batch",)}


def decode_read_block(cfg: LagunaConfig, mesh) -> int | None:
    """Positions in a block of a decode step's reads of the full layers'
    K and V, or None where a step reads them whole (the rings are read
    whole either way, once a live row: `decode_counters`)."""
    return _attention.decode_block_len(cfg.n_kv_heads, cfg.head_dim,
                                       cfg.max_seq_len, cfg.dtype, mesh)


def decode_counters(cfg: LagunaConfig, spans: list, rows: int) -> dict:
    """What one decode step of `rows` rows does for live rows at `spans`
    [(start, the position the step writes)], by layer kind: the
    positions its queries attend to (a full layer: the row's whole
    range; a sliding one: its newest `sliding_window`), and the
    positions its attention is asked to read: of the full layers the
    blocks that overlap the ranges, of the rings every row of every live
    slot, or every layer whole for every row where no kernel bounds the
    read."""
    depth = [last - start + 1 for start, last in spans]
    nf, ns = cfg.count(KINDS[0]), cfg.count(KINDS[1])
    block = decode_read_block(cfg, jax.sharding.get_abstract_mesh())
    full = rows * cfg.max_seq_len if not block else block * sum(
        last // block - start // block + 1 for start, last in spans)
    ring = cfg.ring_len * (len(spans) if _ring_block(cfg, True) else rows)
    return {"decode_full_positions_attended": nf * sum(depth),
            "decode_window_positions_attended":
                ns * sum(min(n, cfg.sliding_window) for n in depth),
            "decode_full_positions_read": nf * full,
            "decode_window_positions_read": ns * ring}


def prefill_counters(cfg: LagunaConfig, start: int, pos: int, chunk: int,
                     depth: int) -> dict:
    """What the attention of one prefill chunk does: `chunk` queries at
    positions [pos, pos + chunk) of a row whose first real token lies at
    `start`, against a cache `depth` deep. By layer kind, key positions
    summed over the chunk's queries: `visible`, those a query attends to
    (every one from `start` to itself; the window's), and `visited`,
    those whose scores are computed: under the kernel the live tiles, in
    the plain form the layer whole, and the ring and the chunk whole."""
    depths = np.arange(max(pos, start), pos + chunk) - start + 1
    ring, nkv = cfg.ring_len, cfg.n_kv_heads
    t = _chunk_tiles(cfg.heads_full // nkv, cfg.head_dim, chunk, depth)
    full = chunk * depth if t is None else int(_chunk.keys_visited(
        _chunk.seen_by_position(np, np.arange(depth), start), pos, chunk,
        t).sum())
    t = _chunk_tiles(cfg.heads_sliding // nkv, cfg.head_dim, chunk,
                     ring + chunk)
    if t is None:
        window = chunk * (ring + chunk)
    else:
        # the keys as `_sliding_layer` lays them out
        k_pos = np.concatenate([
            pos - 1 - (pos - 1 - np.arange(ring)) % ring,
            pos + np.arange(chunk)])
        window = int(_chunk.keys_visited(
            _chunk.seen_by_position(np, k_pos, start, cfg.sliding_window),
            pos, chunk, t).sum())
    nf, ns = cfg.count(KINDS[0]), cfg.count(KINDS[1])
    return {"prefill_full_keys_visited": nf * full,
            "prefill_full_keys_visible": nf * int(depths.sum()),
            "prefill_window_keys_visited": ns * window,
            "prefill_window_keys_visible":
                ns * int(np.minimum(depths, cfg.sliding_window).sum())}


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                cfg: LagunaConfig, collect: bool = False):
    """Append `tokens` [b, s] to the cache, return logits for the last
    position [b, vocab] and the updated cache: `llama.decode_step`'s
    contract. s = 1 is a decode step, with a scalar or per-row
    cache["length"] (a row with length < 0 holds no request: it reaches
    no expert, its attention reads nothing where a kernel runs and its
    result is not read); larger s is a prefill chunk of the batch in
    lock-step (a scalar length), which finds in the cache what the
    chunks before it left: K and V of the full layers, and the rings at
    the position they were written up to. Positions before
    cache["start"] are left padding: RoPE counts from `start`, and a
    padded position is attended to by nobody and reaches no expert.
    cache["aux"] comes back as (token-expert pairs computed on held
    experts, held experts with at least one token, tiles of rows the
    experts' loop walked), summed over the expert layers. With `collect`
    a third value is returned: what each expert layer chose (`forward`'s
    dict)."""
    b, s = tokens.shape
    cache_len = cache["length"]
    if s > 1 and jnp.ndim(cache_len):
        raise ValueError("a chunk advances the batch in lock-step: "
                         "cache['length'] must be a scalar")
    start = cache.get("start")
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    abs_pos = jnp.broadcast_to(
        (cache_len[:, None] if jnp.ndim(cache_len) else cache_len)
        + jnp.arange(s)[None, :], (b, s))
    rel = jnp.maximum(abs_pos - start[:, None], 0)
    valid = abs_pos >= start[:, None]
    if jnp.ndim(cache_len):
        valid = valid & (cache_len >= 0)[:, None]
    k_cache, v_cache = cache["k"], cache["v"]
    ring_k, ring_v = cache["window_k"], cache["window_v"]
    seen: dict = {"chosen": [], "router_scores": []}
    counts = jnp.zeros((len(STEP_AUX),), jnp.int32)
    x = _parts._embed(cfg, params, tokens)
    seen_kind = dict.fromkeys(KINDS, 0)
    for li, layer in enumerate(params["layers"]):
        kind = cfg.layer_types[li]
        ki = seen_kind[kind]
        seen_kind[kind] += 1
        h, q, kk, vv = _qkv(cfg, layer, li, x, rel)
        if kind == KINDS[0]:
            attn, k_cache, v_cache = _full_layer(
                cfg, ki, q, kk, vv, k_cache, v_cache, cache_len, abs_pos,
                start)
        else:
            attn, ring_k, ring_v = _sliding_layer(
                cfg, ki, q, kk, vv, ring_k, ring_v, cache_len, abs_pos,
                start)
        x = x + _parts._gate_out(cfg, layer, h, attn)
        x, moe, aux = _parts._ffn(cfg, layer, x, valid, collect)
        if moe is not None:
            counts = counts + jnp.stack(moe)
        for k_, v_ in aux.items():
            seen[k_].append(v_)
    new_cache = {"k": k_cache, "v": v_cache, "window_k": ring_k,
                 "window_v": ring_v, "aux": counts,
                 "length": cache_len + s, "start": start}
    logits = _parts._logits(cfg, params, x[:, -1])
    return (logits, new_cache, seen) if collect else (logits, new_cache)
