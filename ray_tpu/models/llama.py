"""Llama-family transformer, TPU-first.

The reference framework (LydiaXwQ/ray) carries no model code of its own —
LLMs arrive via torch/DeepSpeed examples (ref:
release/air_examples/dolly_v2_lightning_fsdp_finetuning/,
doc/source/train/examples/deepspeed Llama-2 fine-tune). For the TPU build
the model layer is first-class because GSPMD sharding, remat, and kernel
choice must be co-designed with the parallelism layer (SURVEY.md §2.4).

Design (idiomatic JAX, nothing torch-shaped):

* Params are a plain pytree of ``jnp`` arrays; per-layer weights are
  **stacked on a leading "layers" axis** and the block stack is a single
  ``lax.scan`` — one trace/compile of one block regardless of depth.
* Every parameter has a tuple of *logical axis names*
  (``param_logical_axes``); ``ray_tpu.parallel.mesh.shard_params`` maps
  them to mesh axes, so DP/FSDP/TP/SP are just different rule tables.
* Compute in bf16, params f32 (configurable), softmax/norm/rope in f32.
* ``jax.checkpoint`` around each block (policy: save nothing but dots'
  inputs) trades FLOPs for HBM — the standard TPU recipe.
* Attention dispatches to the Pallas flash kernel on TPU, XLA elsewhere
  (``ops/ring_attention.py`` holds the sequence-parallel forms; no model
  config selects them yet).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

# decode_read_block is this module's (models.module_for): K and V of
# n_kv_heads x head_dim in every attention layer
from ray_tpu.ops.attention import (cached_attention,  # noqa: F401
                                   decode_read_block, dot_product_attention)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # activation-saving policy under remat:
    #   "dots"    — dots_with_no_batch_dims_saveable (matmul outputs)
    #   "nothing" — save only the block carry and recompute the whole
    #               block in the backward pass (minimum memory; the only
    #               one that fits the benchmark's published widths)
    remat_policy: str = "dots"
    # attention impl: "auto" (flash on TPU at s >= 1024, else xla) |
    # "xla" | "flash"
    attn_impl: str = "auto"
    # LoRA: scale numerator for the low-rank path (scale = alpha / rank,
    # rank inferred from the adapter's shape; see models/lora.py)
    lora_alpha: float = 16.0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self, include_embed: bool = True) -> int:
        d, h = self.dim, self.hidden_dim
        kv_dim = self.n_kv_heads * self.head_dim
        per_layer = (d * d + 2 * d * kv_dim + d * d) + 3 * d * h + 2 * d
        total = self.n_layers * per_layer + d
        if include_embed:
            total += self.vocab_size * d
            if not self.tie_embeddings:
                total += d * self.vocab_size
        return total


# ----------------------------------------------------------------- presets
PRESETS: dict[str, dict] = {
    # debug-size model for tests / CI (CPU-mesh friendly)
    "debug": dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, hidden_dim=128, max_seq_len=128),
    "1b": dict(vocab_size=32000, dim=2048, n_layers=16, n_heads=16,
               n_kv_heads=8, hidden_dim=5632, max_seq_len=2048),
}


def config_for(name: str, **overrides) -> LlamaConfig:
    if name not in PRESETS:
        raise ValueError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    kw = dict(PRESETS[name])
    kw.update(overrides)
    return LlamaConfig(**kw)


# ------------------------------------------------------------------- params
def init_params(cfg: LlamaConfig, key: jax.Array) -> dict:
    """Initialize a param pytree. Per-layer weights carry a leading
    [n_layers] axis so the block stack scans."""
    pd = cfg.param_dtype
    d, h, L = cfg.dim, cfg.hidden_dim, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    k = iter(jax.random.split(key, 16))

    def dense(rng, shape, fan_in):
        return (jax.random.normal(rng, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(pd)

    layers: dict = {
        "wq": dense(next(k), (L, d, nh * hd), d),
        "wk": dense(next(k), (L, d, nkv * hd), d),
        "wv": dense(next(k), (L, d, nkv * hd), d),
        "wo": dense(next(k), (L, nh * hd, d), nh * hd),
        "attn_norm": jnp.ones((L, d), pd),
        "mlp_norm": jnp.ones((L, d), pd),
        "w_gate": dense(next(k), (L, d, h), d),
        "w_up": dense(next(k), (L, d, h), d),
        "w_down": dense(next(k), (L, h, d), h),
    }
    params = {
        "embed": dense(next(k), (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (d, cfg.vocab_size), d)
    return params


def param_logical_axes(cfg: LlamaConfig) -> dict:
    """Same tree structure as init_params, leaves = logical-axis tuples
    consumed by parallel.mesh.shard_params."""
    layer_axes: dict = {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "attn_norm": ("layers", None),
        "mlp_norm": ("layers", None),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layer_axes,
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ------------------------------------------------------------------ forward
def _proj(cfg: LlamaConfig, layer: dict, name: str, h):
    """Frozen matmul + optional LoRA low-rank path (shared by the
    training block and the KV-cache decode block so adapters behave
    identically at train and serve time). The [d, out] delta is never
    materialized."""
    dt = cfg.dtype
    out = h @ layer[name].astype(dt)
    a = layer.get(name + "_a")
    if a is not None:
        scale = cfg.lora_alpha / a.shape[-1]
        with jax.named_scope("lora"):
            out = out + ((h @ a.astype(dt)) @ layer[name + "_b"].astype(dt)
                         ) * jnp.asarray(scale, dt)
    return out


def _block(cfg: LlamaConfig, x, layer, cos, sin, positions):
    """One transformer block. x: [b, s, d] (cfg.dtype) -> x.

    When the layer dict carries LoRA adapters ("<w>_a"/"<w>_b", stacked
    like the base weights — see models/lora.py), the low-rank path
    ``h @ A @ B * (alpha/r)`` is added next to the frozen matmul; the
    full-rank delta is never materialized.
    """
    b, s, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    dt = cfg.dtype

    def proj(name, h):
        return _proj(cfg, layer, name, h)

    # The named scopes here and in _decode_block are HLO metadata only:
    # they name each device operation's part of the block in a profiler
    # trace (benchmarks/trace_spans.py sums device time by them) and
    # change no operation, fusion or buffer.
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = proj("wq", h).reshape(b, s, nh, hd)
        kk = proj("wk", h).reshape(b, s, nkv, hd)
        vv = proj("wv", h).reshape(b, s, nkv, hd)
        q = apply_rope(q, cos, sin, positions)
        kk = apply_rope(kk, cos, sin, positions)
    with jax.named_scope("attn"):
        attn = dot_product_attention(
            q, kk, vv, causal=True, impl=cfg.attn_impl
        ).reshape(b, s, nh * hd)
    with jax.named_scope("attn_out"):
        x = x + proj("wo", attn)

    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(proj("w_gate", h))
        up = proj("w_up", h)
        x = x + proj("w_down", gate * up)
    return x


def backbone(params: dict, tokens: jax.Array, cfg: LlamaConfig,
             positions: jax.Array | None = None) -> jax.Array:
    """tokens: [b, s] int32 -> final hidden states [b, s, d] (cfg.dtype).

    The layer stack is one lax.scan over stacked weights; each step is
    optionally rematerialized.
    """
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    scanned_layers = params["layers"]
    if "lora" in params:
        # adapters are stacked on the same leading [n_layers] axis, so
        # they ride the same scan as the base weights (models/lora.py)
        scanned_layers = {**scanned_layers, **params["lora"]["layers"]}

    def step(x, layer):
        return _block(cfg, x, layer, cos, sin, positions), None

    if cfg.remat:
        if cfg.remat_policy == "nothing":
            policy = None   # save only the block carry; recompute all
        elif cfg.remat_policy == "dots":
            policy = \
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r}")
        step = jax.checkpoint(step, policy=policy)
    x, _ = jax.lax.scan(step, x, scanned_layers)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def _head_matrix(params: dict, cfg: LlamaConfig) -> jax.Array:
    return (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)


def forward(params: dict, tokens: jax.Array, cfg: LlamaConfig,
            positions: jax.Array | None = None) -> jax.Array:
    """tokens: [b, s] int32 -> logits [b, s, vocab] (f32)."""
    x = backbone(params, tokens, cfg, positions)
    with jax.named_scope("lm_head"):
        return (x @ _head_matrix(params, cfg)).astype(jnp.float32)


def loss_fn(params: dict, batch: dict, cfg: LlamaConfig):
    """batch: {"tokens": [b, s], "targets": [b, s]} -> (loss, aux).

    Uses the fused lm-head + cross entropy (ops/cross_entropy.py) so the
    [b*s, vocab] f32 logits tensor is never materialized.
    """
    from ray_tpu.ops.cross_entropy import fused_lm_head_cross_entropy

    x = backbone(params, batch["tokens"], cfg)
    with jax.named_scope("ce"):   # the head's matmul is fused into it
        ce_loss, n_tok = fused_lm_head_cross_entropy(
            x, _head_matrix(params, cfg), batch["targets"])
    return ce_loss, {"loss": ce_loss, "tokens": n_tok}


# ----------------------------------------------------------------- decoding
# Axis of the cache positions in cache["k"] and cache["v"]: whoever cuts
# or grafts a stretch of positions (serve/llm.py) reads it from here.
KV_LEN_AXIS = {"k": 4, "v": 3}


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int | None = None
                  ) -> dict:
    """An empty cache, stacked over layers, in the axis order attention
    reads: K transposed, ``[layers, b, kv_heads, hd, len]``, and V
    ``[layers, b, kv_heads, len, hd]``, so that per kv head the scores are
    ``q @ K`` and the output ``probs @ V`` with both operands as they lie
    (any other order costs a layout copy of each layer, or of the whole
    stack, per step: PERF.md, PR 25). A position is a lane of K and a
    sublane row of V: a prefill chunk and the XLA decode path write a
    row's new positions with one small `dynamic_update_slice` each, and
    the decode kernel writes its one new position as the tiles around
    it, 128 positions of K and 16 of V, from the block it has in VMEM
    (`ops/attention.cached_attention`; PERF.md, PR 48)."""
    max_len = max_len or cfg.max_seq_len
    lead = (cfg.n_layers, batch, cfg.n_kv_heads)
    return {
        "k": jnp.zeros(lead + (cfg.head_dim, max_len), cfg.dtype),
        "v": jnp.zeros(lead + (max_len, cfg.head_dim), cfg.dtype),
        "length": jnp.zeros((), jnp.int32),
        # per-row first REAL slot: left-padded batched serving writes pad
        # tokens into cache slots [0, start); they are masked out and rope
        # positions are start-relative (vLLM-style batched decode)
        "start": jnp.zeros((batch,), jnp.int32),
    }


def kv_cache_logical_axes() -> dict:
    return {"k": ("layers", "batch", "kv_heads", "head_dim", None),
            "v": ("layers", "batch", "kv_heads", None, "head_dim"),
            "length": (), "start": ("batch",)}


# The names serve/llm.py's engine reads of whichever model module serves
# its config (models.REQUIRED; module_for says what each means).
TENSOR_PARALLEL = True
CACHE_LEN_AXIS = KV_LEN_AXIS
init_cache = init_kv_cache


def cache_logical_axes(cfg: LlamaConfig) -> dict:
    return kv_cache_logical_axes()


def _qkv(cfg: LlamaConfig, layer: dict, x):
    """A block's `attn_qkv` products over x ``[..., d]``: q and k as
    projected, ``[..., heads * hd]``, and v cut into its heads,
    ``[..., kv_heads, hd]``."""
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = _proj(cfg, layer, "wq", h)
        kk = _proj(cfg, layer, "wk", h)
        vv = _proj(cfg, layer, "wv", h)
        vv = vv.reshape(vv.shape[:-1] + (cfg.n_kv_heads, cfg.head_dim))
        # q and k exist as projected, [..., heads * hd], before they are
        # cut into heads for the rope. Left free, the compiler makes the
        # product give them heads-major, takes wq and wk transposed for
        # that, and so slices each out of its stack and copies it into
        # the other layout on every layer of every step (12 MB a layer,
        # a tenth of the chat cell's decode round: PERF.md, PR 45). Held
        # here, the products read the stacks where they lie, as wv's, wo's
        # and the MLP's do. Not v: with it held too the chunk's V stack is
        # re-laid around the layer loop (tests/test_chip_compile.py).
        q, kk = jax.lax.optimization_barrier((q, kk))
    return q, kk, vv


def _out_mlp(cfg: LlamaConfig, layer: dict, x, attn):
    """The rest of a block behind its attention: `attn_out` and `mlp`."""
    with jax.named_scope("attn_out"):
        x = x + _proj(cfg, layer, "wo", attn)
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        x = x + _proj(cfg, layer, "w_down",
                      jax.nn.silu(_proj(cfg, layer, "w_gate", h))
                      * _proj(cfg, layer, "w_up", h))
    return x


def _decode_block(cfg: LlamaConfig, x, layer, li, k_cache, v_cache, cos, sin,
                  positions, cache_len, start, abs_positions):
    """Single-step (or chunked prefill) block `li` against the KV cache.

    x: [b, s, d]; k_cache/v_cache: the STACKED cache (`init_kv_cache`),
    carried through the layer loop. The block writes its s new rows at
    positions [cache_len[row], cache_len[row] + s) of layer `li` in place
    and reads that layer once, for attention; nothing else of the cache
    is read, written or copied. `positions` are rope positions
    (start-relative for left-padded rows); `abs_positions` [b, s] are the
    cache slots the new rows land in, used for masking; `start` [b] (or
    None) hides the left-pad slots of each row.
    """
    b, s, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q, kk, vv = _qkv(cfg, layer, x)
    attn, k_cache, v_cache = cached_attention(
        q.reshape(b, s, nh, hd), kk.reshape(b, s, nkv, hd), vv, k_cache,
        v_cache, li, cache_len, abs_positions, start, scale=hd ** -0.5,
        rope=(cos, sin, positions))
    return _out_mlp(cfg, layer, x, attn), k_cache, v_cache


def _cache_positions(cache: dict, b: int, s: int):
    """Where s new tokens a row land in `cache`: (cache_len, a scalar or
    [b]; the slots [b, s]; `start` [b] or None; the rope positions
    [b, s], relative to each row's first real token)."""
    cache_len = cache["length"]
    if jnp.ndim(cache_len) == 0:
        abs_positions = cache_len + jnp.arange(s)[None, :].repeat(b, 0)
    else:
        abs_positions = cache_len[:, None] + jnp.arange(s)[None, :]
    start = cache.get("start")
    if start is None:
        return cache_len, abs_positions, None, abs_positions
    return (cache_len, abs_positions, start,
            jnp.maximum(abs_positions - start[:, None], 0))


def _serve_layers(params: dict) -> dict:
    """The stacked layers a serve-time loop indexes: with adapters, they
    are stacked on the same [n_layers] axis and taken per layer exactly
    like the base weights (the _proj low-rank branch fires per layer;
    models/lora.py)."""
    layers = params["layers"]
    if "lora" in params:
        layers = {**layers, **params["lora"]["layers"]}
    return layers


def _layer(layers: dict, li):
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, li, 0, keepdims=False),
        layers)


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                cfg: LlamaConfig) -> tuple[jax.Array, dict]:
    """Append `tokens` [b, s] to the cache, return logits for the last
    position [b, vocab] and the updated cache. jit-able with static s
    (s=1 for autoregressive decode; larger s = chunked prefill).

    cache["length"] may be a scalar (whole batch in lock-step, the
    left-padded batched path) or shape [b] (per-row depths: the
    continuous-batching slot path, where each row is an independent
    request and writes at its own cache offset).

    The stacked cache rides the layer loop as carried state: per step
    every cache byte is read at most once, by attention, and only the new
    rows are written. With the cache donated (as the engine does) the
    writes land in the caller's buffers and no copy of a stack is made."""
    b, s = tokens.shape
    dt = cfg.dtype
    cache_len, abs_positions, start, positions = _cache_positions(cache, b, s)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    layers = _serve_layers(params)

    def step(li, carry):
        x, kc, vc = carry
        layer = _layer(layers, li)
        return _decode_block(cfg, x, layer, li, kc, vc, cos, sin,
                             positions, cache_len, start, abs_positions)

    x, k_new, v_new = jax.lax.fori_loop(
        0, cfg.n_layers, step, (x, cache["k"], cache["v"]))
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = (x[:, -1] @ _head_matrix(params, cfg)).astype(jnp.float32)
    new_cache = {"k": k_new, "v": v_new, "length": cache_len + s}
    if start is not None:
        new_cache["start"] = start
    return logits, new_cache


def mixed_step(params: dict, small: dict, chunk_tokens: jax.Array,
               cache: dict, tokens: jax.Array, cfg: LlamaConfig
               ) -> tuple[jax.Array, dict, jax.Array, dict]:
    """A prefill chunk and a decode step as ONE pass over the weights
    (models.OPTIONAL): `chunk_tokens` [1, s] appended to `small`, one
    request's prefill cache, as `decode_step(params, small,
    chunk_tokens, cfg)` appends them, and `tokens` [b, 1] to `cache`, the
    slots' cache with per-row depths, as `decode_step(params, cache,
    tokens, cfg)` does. Returns (the chunk's last position's logits [1,
    vocab], small, the rows' logits [b, vocab], cache).

    One layer loop carries both caches. The chunk's s rows and the
    slots' b rows lie together, [s + b, d], through the loop: every
    product of a layer (`attn_qkv`, `attn_out`, `mlp`) and the head's
    reads its matrix once for all of them, where two programs read it
    twice (a decode step's products over b rows cost what reading their
    matrices costs: PERF.md, PR 57). They are cut apart only for
    attention: the chunk's against its own cache, the rows' each against
    its slot (on a TPU the decode kernel, which also writes the row). A
    row computes what `decode_step` computes for it: the same
    arithmetic, precisions and rope positions.

    The phases are named here, since one program holds both: the
    products and the chunk's attention are `prefill`'s, the slots'
    attention (rope, kernel, write) is `decode`'s, under the block's
    part names, so a profiler trace files every operation as it files
    the two programs' (benchmarks/trace_spans.scope_of)."""
    s, b = chunk_tokens.shape[1], tokens.shape[0]
    hd, nh = cfg.head_dim, cfg.n_heads
    chunk_at = _cache_positions(small, 1, s)
    rows_at = _cache_positions(cache, b, 1)
    with jax.named_scope("prefill"), jax.named_scope("embed"):
        x = jnp.take(params["embed"], jnp.concatenate(
            [chunk_tokens[0], tokens[:, 0]]), axis=0).astype(cfg.dtype)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    layers = _serve_layers(params)

    def attend(q, kk, vv, rows, kc, vc, li, at):
        """`cached_attention` of `rows` rows' new tokens, q, k and v
        as `_qkv` gives them, against layer `li` of one cache."""
        cache_len, abs_positions, start, positions = at
        heads = lambda a: a.reshape(rows, a.shape[0] // rows, -1, hd)
        attn, kc, vc = cached_attention(
            heads(q), heads(kk), heads(vv), kc, vc, li, cache_len,
            abs_positions, start, scale=hd ** -0.5,
            rope=(cos, sin, positions))
        return attn.reshape(-1, nh * hd), kc, vc

    def step(li, carry):
        x, sk, sv, kc, vc = carry
        with jax.named_scope("prefill"):
            layer = _layer(layers, li)
            q, kk, vv = _qkv(cfg, layer, x)
            # the chunk's V stack held as it lies: left to choose, the
            # compiler lays it positions-minor for v cut out of the
            # s + b rows' product and copies the whole stack into and
            # out of the layer loop, 176 MB each way at the long-prompt
            # cell's bucket (tests/test_chip_compile.py)
            sv = with_layout_constraint(sv, Layout((0, 1, 2, 3, 4)))
            attn_chunk, sk, sv = attend(q[:s], kk[:s], vv[:s], 1, sk, sv,
                                        li, chunk_at)
        with jax.named_scope("decode"):
            attn_rows, kc, vc = attend(q[s:], kk[s:], vv[s:], b, kc, vc,
                                       li, rows_at)
        with jax.named_scope("prefill"):
            x = _out_mlp(cfg, layer, x,
                         jnp.concatenate([attn_chunk, attn_rows]))
        return x, sk, sv, kc, vc

    x, sk, sv, kc, vc = jax.lax.fori_loop(
        0, cfg.n_layers, step,
        (x, small["k"], small["v"], cache["k"], cache["v"]))
    with jax.named_scope("prefill"), jax.named_scope("lm_head"):
        # the chunk's last row and the b rows: one read of the head
        x = rms_norm(x[s - 1:], params["final_norm"], cfg.norm_eps)
        logits = (x @ _head_matrix(params, cfg)).astype(jnp.float32)
    small = {**small, "k": sk, "v": sv, "length": chunk_at[0] + s}
    cache = {**cache, "k": kc, "v": vc, "length": rows_at[0] + 1}
    return logits[:1], small, logits[1:], cache
