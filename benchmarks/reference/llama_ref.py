"""Plain reference of the dense GQA decoder that InternLM2 and Mistral
share: RMSNorm, rotary embeddings (split-half), grouped-query causal
attention, SwiGLU, untied head. Float32 throughout, matmuls at
"highest" precision, no cache, no kernel, no remat, and no import from
ray_tpu.

Parameters are read in the layout the program holds them (stacked on a
leading layer axis: wq [L, d, h*hd], wk/wv [L, d, kv*hd], wo, w_gate,
w_up [L, d, f], w_down [L, f, d], attn_norm, mlp_norm [L, d]; embed
[V, d], final_norm [d], lm_head [d, V]) so that the system's own weights
can be handed to it as they are. Layers run under one lax.scan, which
keeps the compiled program small and lets a sharded stack stay sharded.

`hp` is a dict of what the mathematics needs: n_heads, n_kv_heads,
rope_theta, norm_eps, and lora_alpha where adapters are given.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [b, s, heads, hd]; position i is row i. Split-half pairing."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(h, layer, name, lora_scale):
    out = h @ layer[name].astype(F32)
    if name + "_a" in layer:
        out = out + (h @ layer[name + "_a"].astype(F32)) @ \
            layer[name + "_b"].astype(F32) * lora_scale
    return out


def _layer(x, layer, hp, lora_scale):
    b, s, d = x.shape
    nh, nkv = hp["n_heads"], hp["n_kv_heads"]
    hd = d // nh
    h = _rms_norm(x, layer["attn_norm"].astype(F32), hp["norm_eps"])
    q = _rope(_proj(h, layer, "wq", lora_scale).reshape(b, s, nh, hd),
              hp["rope_theta"])
    k = _rope(_proj(h, layer, "wk", lora_scale).reshape(b, s, nkv, hd),
              hp["rope_theta"])
    v = _proj(h, layer, "wv", lora_scale).reshape(b, s, nkv, hd)
    # query head i reads kv head i // (nh // nkv)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + _proj(attn.reshape(b, s, nh * hd), layer, "wo", lora_scale)
    h = _rms_norm(x, layer["mlp_norm"].astype(F32), hp["norm_eps"])
    gate = jax.nn.silu(_proj(h, layer, "w_gate", lora_scale))
    return x + _proj(gate * _proj(h, layer, "w_up", lora_scale), layer,
                     "w_down", lora_scale)


def hidden(params: dict, tokens, hp: dict, lora: dict | None = None):
    """tokens [b, s] -> final normed hidden states [b, s, d], float32."""
    with jax.default_matmul_precision("highest"):
        layers = dict(params["layers"])
        lora_scale = 0.0
        if lora is not None:
            layers.update(lora["layers"])
            rank = next(v.shape[-1] for k, v in lora["layers"].items()
                        if k.endswith("_a"))
            lora_scale = hp["lora_alpha"] / rank
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

        def step(x, layer):
            return _layer(x, layer, hp, lora_scale), None

        x, _ = jax.lax.scan(step, x, layers)
        return _rms_norm(x, params["final_norm"].astype(F32),
                         hp["norm_eps"])


def logits_at(params: dict, tokens, hp: dict, rows):
    """Logits [len(rows), vocab] of sequence 0 at positions `rows`."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, hp)[0]
        return jnp.take(x, rows, axis=0) @ params["lm_head"].astype(F32)


def loss(params: dict, lora: dict, batch: dict, hp: dict):
    """Mean next-token cross entropy over every position of
    batch["tokens"] against batch["targets"]."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, batch["tokens"], hp, lora)
        logits = x @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(
            logp, batch["targets"][..., None], axis=-1)[..., 0]
        return -jnp.mean(picked)


def loss_and_adapter_grads(params: dict, lora: dict, batch: dict,
                           hp: dict):
    return jax.value_and_grad(
        lambda lo: loss(params, lo, batch, hp))(lora)
