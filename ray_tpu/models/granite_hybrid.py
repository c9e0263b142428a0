"""Hybrid decoder of Mamba-2 and attention layers (the `granitemoehybrid`
family without experts): most layers mix tokens through a state-space
recurrence, one in ten through grouped-query attention without position
embeddings, and every layer ends in the same SwiGLU MLP.

Every layer:  x = x + r * mixer(rmsnorm(x));  x = x + r * mlp(rmsnorm(x))
with r the residual multiplier. The embedding's output is scaled by the
embedding multiplier, the logits divided by the logits scaling, and the
attention scores multiplied by the attention multiplier (not 1/sqrt(hd)).
The Mamba-2 mixer is ops/ssm.py's recurrence between an input projection
to [z | x B C | dt], a causal depthwise convolution over [x B C], and a
gated RMSNorm before the output projection. The three parts of the input
projection are held apart (in_z, in_xbc, in_dt): fused, its 8512 columns
are no multiple of the chip's 128 lanes and the compiler copies the whole
stack, 1.25 GB, on every step.

Same function set as models/llama.py, so serve/llm.py's engine runs
either: `init_params`, `param_logical_axes`, `forward`, `init_cache`,
`cache_logical_axes`, `CACHE_LEN_AXIS`, `decode_step`, and of what the
engine asks a module only where it has it, `decode_counters`. Per-layer weights
are stacked BY KIND ("mamba", "attention") and the layer loop runs over
the periods of `layer_types`, inside a period over its runs of one kind:
one compiled block per run, whatever the depth. A request's cache is the
attention layers' K and V (a position axis, as llama's) beside each
Mamba layer's recurrent state and convolution tail (no position axis:
they are what the whole prefix left behind, whatever its length).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as _attention
# decode_read_block is this module's (models.module_for): K and V of
# n_kv_heads x head_dim in every attention layer
from ray_tpu.ops.attention import (cached_attention,  # noqa: F401
                                   decode_read_block, xla_attention)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas import ssm_update as _kernel
from ray_tpu.ops.ssm import causal_conv, ssd_scan, ssm_step

F32 = jnp.float32
KINDS = ("mamba", "attention")
# one group of B and C is shared by all heads: sharding the heads over a
# tensor axis would have to replicate or split it, and neither is written
TENSOR_PARALLEL = False


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    layer_types: tuple = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    hidden_dim: int = 8192           # shared_intermediate_size
    n_heads: int = 32
    n_kv_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32   # the recurrent state, between steps

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.d_inner != self.mamba_expand * self.dim:
            raise ValueError(
                f"mamba heads {self.mamba_n_heads} x {self.mamba_d_head} "
                f"are not mamba_expand {self.mamba_expand} x {self.dim}")
        if self.mamba_n_groups != 1 or self.mamba_proj_bias \
                or not self.mamba_conv_bias:
            raise ValueError("only one B/C group, no projection bias and a "
                             "convolution bias are implemented")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("attention heads do not divide")
        if not self.layer_types:
            raise ValueError("layer_types is empty")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    @property
    def period(self) -> tuple:
        """The shortest pattern that `layer_types` is a whole number of
        repeats of: read from the list, never guessed. A list that
        repeats nothing is one period of its own length."""
        lt = self.layer_types
        return next(lt[:n] for n in range(1, len(lt) + 1)
                    if len(lt) % n == 0 and lt == lt[:n] * (len(lt) // n))

    @property
    def runs(self) -> tuple:
        """The period as runs of one kind: ((kind, first, count), ...),
        `first` counting that kind's layers inside the period."""
        out, seen = [], dict.fromkeys(KINDS, 0)
        for kind in self.period:
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(r) for r in out)

    def num_params(self) -> int:
        shapes = jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))
        return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def from_published(config: dict, **overrides) -> GraniteHybridConfig:
    """The config from the keys of a published `config.json`."""
    if config.get("num_local_experts", 0) or \
            config.get("position_embedding_type", "nope") != "nope":
        raise ValueError("experts and position embeddings are not "
                         "implemented for this family")
    kw = dict(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        layer_types=tuple(config["layer_types"]),
        hidden_dim=int(config["shared_intermediate_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        **{k: config[k] for k in (
            "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
            "mamba_expand", "mamba_n_groups", "mamba_chunk_size",
            "mamba_conv_bias", "mamba_proj_bias")},
        **{k: float(config[k]) for k in (
            "embedding_multiplier", "residual_multiplier",
            "attention_multiplier", "logits_scaling")})
    if len(kw["layer_types"]) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    kw.update(overrides)
    return GraniteHybridConfig(**kw)


# ------------------------------------------------------------------- params
def _shapes(cfg: GraniteHybridConfig) -> dict:
    """kind -> {name: (shape of one layer, fan_in or None for a vector)}."""
    d, f, di = cfg.dim, cfg.hidden_dim, cfg.d_inner
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    mlp = {"mlp_norm": ((d,), None), "w_in": ((d, 2 * f), d),
           "w_out": ((f, d), f)}
    return {
        "mamba": {"norm": ((d,), None),
                  "in_z": ((d, di), d), "in_xbc": ((d, cfg.conv_dim), d),
                  "in_dt": ((d, cfg.mamba_n_heads), d),
                  "conv_w": ((cfg.mamba_d_conv, cfg.conv_dim),
                             cfg.mamba_d_conv),
                  "conv_b": ((cfg.conv_dim,), None),
                  "gate_norm": ((di,), None),
                  "out_proj": ((di, d), di), **mlp},
        "attention": {"norm": ((d,), None),
                      "wq": ((d, nh * hd), d), "wk": ((d, nkv * hd), d),
                      "wv": ((d, nkv * hd), d), "wo": ((nh * hd, d), nh * hd),
                      **mlp}}


def init_params(cfg: GraniteHybridConfig, key: jax.Array) -> dict:
    """Matrices N(0, 1/fan_in), norms one, the convolution's bias zero.
    The per-head scalars follow the family's published initialisation and
    stay float32: A_log = log U(1, 16), dt_bias the inverse softplus of a
    log-uniform step in [0.001, 0.1], D = 1."""
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 32))

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                / math.sqrt(fan_in)).astype(pd)

    params: dict = {"embed": dense((cfg.vocab_size, cfg.dim), cfg.dim),
                    "final_norm": jnp.ones((cfg.dim,), pd)}
    for kind, shapes in _shapes(cfg).items():
        n = cfg.count(kind)
        params[kind] = {
            name: (dense((n,) + shape, fan_in) if fan_in else
                   (jnp.zeros if name == "conv_b" else jnp.ones)(
                       (n,) + shape, pd))
            for name, (shape, fan_in) in shapes.items()}
    m, h = cfg.count("mamba"), cfg.mamba_n_heads
    step = jnp.exp(jax.random.uniform(next(keys), (m, h), F32,
                                      math.log(1e-3), math.log(1e-1)))
    params["mamba"].update(
        A_log=jnp.log(jax.random.uniform(next(keys), (m, h), F32, 1.0, 16.0)),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        D=jnp.ones((m, h), F32))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense((cfg.dim, cfg.vocab_size), cfg.dim)
    return params


def param_logical_axes(cfg: GraniteHybridConfig) -> dict:
    """Everything replicated (TENSOR_PARALLEL is False): leaves are tuples
    of None, one for each axis."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a: (None,) * a.ndim, shapes)


# ------------------------------------------------------------------- mixers
def _state_update(states, li, x, dt, a, b, c, d):
    """One token for layer `li` of the stacked state [layers, b, heads,
    p, n]: (y [b, heads, p] float32, the stack). One recurrence, two
    schedules: on a TPU, for a state the kernel takes, one call that
    reads each block once, writes it in place and emits y
    (ops/pallas/ssm_update.py); everywhere else the plain form on the
    layer cut from the stack, which the compiler makes two passes of."""
    hb = _kernel.heads_per_block(*states.shape[2:], states.dtype) \
        if _attention._on_tpu() else None
    if hb:
        return _kernel.ssm_update(states, li, x, dt, a, b, c, d,
                                  heads_block=hb)
    y, state = ssm_step(
        jax.lax.dynamic_index_in_dim(states, li, 0, keepdims=False),
        x, dt, a, b, c, d)
    return y, jax.lax.dynamic_update_slice(states, state[None],
                                           (li, 0, 0, 0, 0))


def _mamba_mixer(cfg: GraniteHybridConfig, x, layer, states, li, tail,
                 valid):
    """x: [b, s, d]; states: the stacked state [layers, b, heads, p, n],
    of which this is layer `li`; tail: [b, d_conv - 1, conv_dim]; valid:
    [b, s] bool or None. Positions that are not valid (left padding)
    reach neither the convolution's window nor the state: the input is
    zeroed before the projection, which has no bias, and again after the
    convolution's activation, whose bias would otherwise write
    silu(bias) into x, B and C. The layer's state is cut from its stack
    and written back under the scope of the operation that uses it: the
    compiler fuses the update into the write, and the fusion carries
    the write's name (one token on a TPU takes neither cut nor write:
    `_state_update`). Returns (out [b, s, d], states, tail)."""
    b, s, _ = x.shape
    dt_, di, n = cfg.dtype, cfg.d_inner, cfg.mamba_d_state
    nh, p = cfg.mamba_n_heads, cfg.mamba_d_head
    with jax.named_scope("ssm_in_proj"):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if valid is not None:
            h = h * valid[..., None].astype(dt_)
        z = h @ layer["in_z"].astype(dt_)
        xbc = h @ layer["in_xbc"].astype(dt_)
        dt = h @ layer["in_dt"].astype(dt_)
    with jax.named_scope("ssm_conv"):
        xbc, tail = causal_conv(xbc, tail, layer["conv_w"], layer["conv_b"])
        xbc = jax.nn.silu(xbc.astype(F32))
        if valid is not None:
            xbc = xbc * valid[..., None]
        xs, bmat, cmat = jnp.split(xbc, [di, di + n], axis=-1)
        dt = jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])
        a = -jnp.exp(layer["A_log"])
    if s == 1:
        with jax.named_scope("ssm_update"):
            y, states = _state_update(states, li, xs.reshape(b, nh, p),
                                      dt[:, 0], a, bmat[:, 0], cmat[:, 0],
                                      layer["D"])
    else:
        with jax.named_scope("ssm_scan"):
            y, state = ssd_scan(
                jax.lax.dynamic_index_in_dim(states, li, 0, keepdims=False),
                xs.reshape(b, s, nh, p), dt, a, bmat, cmat, layer["D"],
                cfg.mamba_chunk_size)
            states = jax.lax.dynamic_update_slice(states, state[None],
                                                  (li, 0, 0, 0, 0))
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(b, s, di) * jax.nn.silu(z.astype(F32))
        y = rms_norm(y, layer["gate_norm"], cfg.norm_eps).astype(dt_)
    with jax.named_scope("ssm_out"):
        return y @ layer["out_proj"].astype(dt_), states, tail


def _qkv(cfg: GraniteHybridConfig, x, layer):
    b, s, _ = x.shape
    dt_ = cfg.dtype
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        return ((h @ layer["wq"].astype(dt_)).reshape(b, s, cfg.n_heads, -1),
                (h @ layer["wk"].astype(dt_)).reshape(b, s, cfg.n_kv_heads, -1),
                (h @ layer["wv"].astype(dt_)).reshape(b, s, cfg.n_kv_heads, -1))


def _mlp(cfg: GraniteHybridConfig, x, layer):
    dt_ = cfg.dtype
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        gate, up = jnp.split(h @ layer["w_in"].astype(dt_), 2, axis=-1)
        return x + cfg.residual_multiplier * (
            (jax.nn.silu(gate) * up) @ layer["w_out"].astype(dt_))


def _layers(cfg: GraniteHybridConfig, params: dict, carry, blocks: dict):
    """The layer stack: a loop over the periods of `layer_types`, and in
    each a loop over every run of one kind. blocks[kind](carry, layer,
    li) -> carry, with `li` the layer's index among its own kind."""
    per_period = {kind: sum(1 for t in cfg.period if t == kind)
                  for kind in KINDS}

    def period(pi, carry):
        for kind, first, count in cfg.runs:
            def one(j, carry, kind=kind, first=first):
                li = pi * per_period[kind] + first + j
                layer = jax.tree.map(
                    lambda w: jax.lax.dynamic_index_in_dim(
                        w, li, 0, keepdims=False), params[kind])
                return blocks[kind](carry, layer, li)

            carry = (one(0, carry) if count == 1 else
                     jax.lax.fori_loop(0, count, one, carry))
        return carry

    return jax.lax.fori_loop(0, cfg.n_layers // len(cfg.period), period,
                             carry)


def _embed(cfg: GraniteHybridConfig, params: dict, tokens):
    with jax.named_scope("embed"):
        return (jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
                * jnp.asarray(cfg.embedding_multiplier, cfg.dtype))


def _logits(cfg: GraniteHybridConfig, params: dict, x):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(cfg.dtype)
        return (x @ head).astype(F32) / cfg.logits_scaling


def forward(params: dict, tokens: jax.Array, cfg: GraniteHybridConfig
            ) -> jax.Array:
    """tokens: [b, s] int32 -> logits [b, s, vocab] (f32): the whole
    sequence from an empty state, no cache kept."""
    b, s = tokens.shape
    r = cfg.residual_multiplier

    def mamba(x, layer, li):
        # a stack of one layer: the empty prefix's state
        states = jnp.zeros((1, b, cfg.mamba_n_heads, cfg.mamba_d_head,
                            cfg.mamba_d_state), F32)
        tail = jnp.zeros((b, cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype)
        out, _, _ = _mamba_mixer(cfg, x, layer, states, 0, tail, None)
        return _mlp(cfg, x + r * out, layer)

    def attention(x, layer, li):
        q, k, v = _qkv(cfg, x, layer)
        with jax.named_scope("attn"):
            attn = xla_attention(q, k, v, causal=True,
                                 scale=cfg.attention_multiplier)
        with jax.named_scope("attn_out"):
            out = attn.reshape(b, s, -1) @ layer["wo"].astype(cfg.dtype)
        return _mlp(cfg, x + r * out, layer)

    x = _layers(cfg, params, _embed(cfg, params, tokens),
                {"mamba": mamba, "attention": attention})
    return _logits(cfg, params, x)


# ----------------------------------------------------------------- decoding
# Axis of the cache positions, for the leaves that have one: whoever cuts
# or grafts a stretch of positions (serve/llm.py) reads it from here. A
# leaf of `init_cache` that is not named here (and is no bookkeeping:
# "length", "start") is recurrent: it belongs to the whole prefix.
CACHE_LEN_AXIS = {"k": 4, "v": 3}


def init_cache(cfg: GraniteHybridConfig, batch: int,
               max_len: int | None = None) -> dict:
    """An empty cache. K and V of the attention layers as llama's
    (`init_kv_cache`: K transposed, stacked over those layers); of the
    Mamba layers the recurrent state ``[layers, b, heads, p, n]`` in
    `state_dtype` and the convolution's tail ``[layers, b, d_conv - 1,
    conv_dim]``, the last inputs it has seen. Zero is the state of an
    empty prefix."""
    max_len = max_len or cfg.max_seq_len
    lead = (cfg.count("attention"), batch, cfg.n_kv_heads)
    m = cfg.count("mamba")
    return {
        "k": jnp.zeros(lead + (cfg.head_dim, max_len), cfg.dtype),
        "v": jnp.zeros(lead + (max_len, cfg.head_dim), cfg.dtype),
        "state": jnp.zeros((m, batch, cfg.mamba_n_heads, cfg.mamba_d_head,
                            cfg.mamba_d_state), cfg.state_dtype),
        "conv": jnp.zeros((m, batch, cfg.mamba_d_conv - 1, cfg.conv_dim),
                          cfg.dtype),
        "length": jnp.zeros((), jnp.int32),
        "start": jnp.zeros((batch,), jnp.int32),
    }


def cache_logical_axes(cfg: GraniteHybridConfig) -> dict:
    return {"k": ("layers", "batch", None, None, None),
            "v": ("layers", "batch", None, None, None),
            "state": ("layers", "batch", None, None, None),
            "conv": ("layers", "batch", None, None),
            "length": (), "start": ("batch",)}


def decode_counters(cfg: GraniteHybridConfig, spans: list, rows: int) -> dict:
    """What one decode step of `rows` rows does for the live rows at
    `spans` (one entry a live row), in states of one row of one Mamba
    layer (`mamba_n_heads x mamba_d_head x mamba_d_state` numbers, read
    and written once each): those the step had to update, and those it
    did update: every row's, whatever the row holds, in the kernel as
    in the plain form. Their ratio is the share of the state traffic
    that is required work."""
    m = cfg.count("mamba")
    return {"decode_state_rows_live": m * len(spans),
            "decode_state_rows_updated": m * rows}


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                cfg: GraniteHybridConfig) -> tuple[jax.Array, dict]:
    """Append `tokens` [b, s] to the cache, return logits for the last
    position [b, vocab] and the updated cache: `llama.decode_step`'s
    contract (s = 1 a decode step with a scalar or per-row
    cache["length"]; larger s a prefill chunk, which picks up the state
    and the convolution tail the chunk before it left). The stacked
    leaves ride the layer loop as carried state: a layer's K, V, state
    and tail are read once and the new rows, the new state and the new
    tail written in place; with the cache donated no copy of a stack is
    made. Positions before cache["start"] (left padding) leave the
    recurrent state untouched."""
    b, s = tokens.shape
    cache_len = cache["length"]
    if jnp.ndim(cache_len) == 0:
        abs_positions = cache_len + jnp.arange(s)[None, :].repeat(b, 0)
    else:
        abs_positions = cache_len[:, None] + jnp.arange(s)[None, :]
    start = cache.get("start")
    # a decode step's token is never padding
    valid = (abs_positions >= start[:, None]
             if s > 1 and start is not None else None)
    r = cfg.residual_multiplier

    # the layer's tail is cut from and written back to its stack under
    # the scope of the operation that uses it, as the mixer does with
    # the state
    def mamba(carry, layer, li):
        x, kc, vc, states, tails = carry
        with jax.named_scope("ssm_conv"):
            tail = jax.lax.dynamic_index_in_dim(tails, li, 0, keepdims=False)
        out, states, tail = _mamba_mixer(cfg, x, layer, states, li, tail,
                                         valid)
        with jax.named_scope("ssm_conv"):
            tails = jax.lax.dynamic_update_slice(
                tails, tail[None], (li, 0, 0, 0))
        return _mlp(cfg, x + r * out, layer), kc, vc, states, tails

    def attention(carry, layer, li):
        x, kc, vc, states, tails = carry
        q, kk, vv = _qkv(cfg, x, layer)
        attn, kc, vc = cached_attention(
            q, kk, vv, kc, vc, li, cache_len, abs_positions, start,
            scale=cfg.attention_multiplier)
        with jax.named_scope("attn_out"):
            out = attn @ layer["wo"].astype(cfg.dtype)
        return _mlp(cfg, x + r * out, layer), kc, vc, states, tails

    x, k_new, v_new, states, tails = _layers(
        cfg, params, (_embed(cfg, params, tokens), cache["k"], cache["v"],
                      cache["state"], cache["conv"]),
        {"mamba": mamba, "attention": attention})
    new_cache = {"k": k_new, "v": v_new, "state": states, "conv": tails,
                 "length": cache_len + s}
    if start is not None:
        new_cache["start"] = start
    return _logits(cfg, params, x[:, -1]), new_cache
