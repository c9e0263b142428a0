"""Plain reference of the granitemoehybrid decoder without experts
(Granite 4.0-H): Mamba-2 and attention layers in the order `layer_types`
gives, each followed by the same SwiGLU MLP, tied or untied head.
Float32 throughout, matmuls at "highest" precision, no cache, no
chunking, no kernel, and no import from ray_tpu. The recurrence is a
lax.scan over tokens, the convolution an explicit sum of d_conv shifted
products, attention a full masked softmax.

Parameters are read in the layout the program holds them, stacked BY
KIND on a leading axis: params["mamba"] (norm, the input projection's three
parts in_z [M, d, d_inner], in_xbc [M, d, conv_dim] in the order [x | B |
C] and in_dt [M, d, heads], conv_w [M, d_conv,
conv_dim] with the last row on the current token, conv_b, dt_bias,
A_log, D, gate_norm, out_proj, mlp_norm, w_in [M, d, 2f] in the order
[gate | up], w_out) and params["attention"] (norm, wq, wk, wv, wo,
mlp_norm, w_in, w_out); embed [V, d], final_norm [d], and lm_head [d, V]
where the head is not tied. One layer's leaves are cut out and upcast at
a time, by one jitted function per kind, so a model of billions of
bfloat16 parameters is never held in float32.

`hp` is a dict of what the mathematics needs: layer_types, n_heads,
n_kv_heads, mamba_n_heads, mamba_d_state, norm_eps, and the four
multipliers (embedding, residual, attention, logits_scaling).

Departures from the published code (transformers'
modeling_granitemoehybrid.py, torch path): the time step is not clamped
(`time_step_limit` is (0, inf) in the published config, which clamps
nothing); there is no attention mask or padding, the sequence starts at
an empty state; dropout, the router and the experts (none in this
configuration) are absent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mlp(x, layer, hp):
    h = _rms_norm(x, layer["mlp_norm"], hp["norm_eps"]) @ layer["w_in"]
    gate, up = jnp.split(h, 2, axis=-1)
    return x + hp["residual"] * ((jax.nn.silu(gate) * up) @ layer["w_out"])


def _hp_key(hp: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hp.items()))


@functools.lru_cache(maxsize=None)
def _layer_fns(hp_key: tuple):
    hp = dict(hp_key)

    def cut(stack, i):
        return jax.tree.map(lambda w: w[i].astype(F32), stack)

    def mamba_layer(x, stack, i, state_row):
        """x: [s, d] of one sequence. Returns the layer's output and the
        recurrent state H after the token at `state_row`."""
        with jax.default_matmul_precision("highest"):
            layer = cut(stack, i)
            s = x.shape[0]
            nh, n = hp["mamba_n_heads"], hp["mamba_d_state"]
            di = layer["out_proj"].shape[0]
            p = di // nh
            h = _rms_norm(x, layer["norm"], hp["norm_eps"])
            z, xbc, dt = (h @ layer[k] for k in ("in_z", "in_xbc", "in_dt"))
            k = layer["conv_w"].shape[0]
            padded = jnp.concatenate(
                [jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
            conv = layer["conv_b"] + sum(
                padded[i:i + s] * layer["conv_w"][i] for i in range(k))
            xs, bmat, cmat = jnp.split(jax.nn.silu(conv), [di, di + n],
                                       axis=-1)
            xs = xs.reshape(s, nh, p)
            dt = jax.nn.softplus(dt + layer["dt_bias"])        # [s, heads]
            a = -jnp.exp(layer["A_log"])

            def token(carry, inp):
                h, kept = carry
                x_t, dt_t, b_t, c_t, t = inp
                h = (jnp.exp(dt_t * a)[:, None, None] * h
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
                kept = jnp.where(t == state_row, h, kept)
                return (h, kept), h @ c_t + layer["D"][:, None] * x_t

            zero = jnp.zeros((nh, p, n), F32)
            (_, kept), y = jax.lax.scan(
                token, (zero, zero), (xs, dt, bmat, cmat, jnp.arange(s)))
            y = _rms_norm(y.reshape(s, di) * jax.nn.silu(z),
                          layer["gate_norm"], hp["norm_eps"])
            x = x + hp["residual"] * (y @ layer["out_proj"])
            return _mlp(x, layer, hp), kept

    def attention_layer(x, stack, i):
        with jax.default_matmul_precision("highest"):
            layer = cut(stack, i)
            s = x.shape[0]
            nh, nkv = hp["n_heads"], hp["n_kv_heads"]
            h = _rms_norm(x, layer["norm"], hp["norm_eps"])
            q = (h @ layer["wq"]).reshape(s, nh, -1)
            k = (h @ layer["wk"]).reshape(s, nkv, -1)
            v = (h @ layer["wv"]).reshape(s, nkv, -1)
            # query head i reads kv head i // (nh // nkv)
            k = jnp.repeat(k, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) * hp["attention"]
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None],
                               scores, -jnp.inf)
            attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
            x = x + hp["residual"] * (attn.reshape(s, -1) @ layer["wo"])
            return _mlp(x, layer, hp)

    return {"mamba": jax.jit(mamba_layer), "attention": jax.jit(
        attention_layer)}


def hidden(params: dict, tokens, hp: dict, state_row: int = 0):
    """tokens [s] of one sequence -> (final normed hidden states [s, d],
    every Mamba layer's recurrent state after the token at `state_row`,
    [mamba layers, heads, p, n])."""
    fns = _layer_fns(_hp_key(hp))
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32) \
        * hp["embedding"]
    seen = {"mamba": 0, "attention": 0}
    states = []
    for kind in hp["layer_types"]:
        if kind == "mamba":
            x, kept = fns[kind](x, params[kind], seen[kind], state_row)
            states.append(kept)
        else:
            x = fns[kind](x, params[kind], seen[kind])
        seen[kind] += 1
    return (_rms_norm(x, params["final_norm"].astype(F32), hp["norm_eps"]),
            jnp.stack(states))


def logits_and_states(params: dict, tokens, hp: dict, rows, state_row: int):
    """Logits [len(rows), vocab] of sequence 0 of tokens [b, s] at
    positions `rows`, and the recurrent states after the token at
    `state_row` (what a cache holds once that token has been consumed)."""
    x, states = hidden(params, tokens[0], hp, state_row)
    x = jnp.take(x, rows, axis=0)
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        return (x @ head.astype(F32)) / hp["logits_scaling"], states


def logits_at(params: dict, tokens, hp: dict, rows):
    return logits_and_states(params, tokens, hp, rows, 0)[0]
