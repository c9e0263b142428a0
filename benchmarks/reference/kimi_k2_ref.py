"""Plain reference of the `kimi_k2` decoder (DeepSeek-V3's layer): the
layer equations of benchmarks/configs/Kimi-K2.6.json in jax.numpy,
float32, matmuls at "highest" precision, the whole sequence at once: no
cache, no chunk, no absorbed form, no kernel, nothing imported from the
program. It reads the program's parameter tree (params["layers"][i]
[name]) and the hyper-parameters `hp` (benchmarks/latent_moe_model.
reference_hp).

One choice in this model is discrete: the experts a token is sent to
(the `experts_per_tok` of largest biased score), taken here by
`jax.lax.top_k` over the float32 scores. A caller may FORCE it: `chosen`
(one [S, k] int matrix an expert layer) replaces the reference's own
choice and nothing else, so that a program that chose otherwise at a
margin can be held to the arithmetic that follows its choice.

Memory is bounded by blocks: attention over `head_block` heads at a
time, the experts one at a time, the dense MLP over `mlp_block` of its
hidden numbers at a time (each matrix cast to float32 when its turn
comes), so that 8,448 positions fit beside an engine that fills the
chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def yarn(hp: dict):
    """(inverse frequencies [rope // 2], the softmax scale's factor
    m**2): YaRN as DeepSeek-V3's modeling code has it. Plain RoPE and 1
    where `hp["rope_scaling"]` is None."""
    dim, theta, sc = hp["rope"], hp["rope_theta"], hp["rope_scaling"]
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if sc is None:
        return plain, 1.0
    turns = lambda r: (dim * math.log(sc["original_max_position_embeddings"]
                                      / (r * 2 * math.pi))
                       / (2 * math.log(theta)))
    low = max(math.floor(turns(sc["beta_fast"])), 0)
    high = min(math.ceil(turns(sc["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    return plain / sc["factor"] * ramp + plain * (1.0 - ramp), m * m


def _rope(x, pos, inv):
    """Rotated halves over the last axis; x [S, ..., dim], pos [S]."""
    half = x.shape[-1] // 2
    ang = (pos.astype(F32)[:, None] * inv).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(lp, h, hp, pos, head_block):
    """One layer's attention, every position from 0 to the query's own:
    [S, d]."""
    S = h.shape[0]
    H, dn, dr, dv = hp["heads"], hp["nope"], hp["rope"], hp["v"]
    rkv, eps = hp["kv_rank"], hp["norm_eps"]
    f = lambda name: lp[name].astype(F32)
    inv, m2 = yarn(hp)
    c_q = _rms(h @ f("w_qa"), f("q_norm"), eps)
    kv = h @ f("w_kva")
    c_kv = _rms(kv[:, :rkv], f("kv_norm"), eps)
    q = (c_q @ f("w_qb").T).reshape(S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv)
    k_rope = _rope(kv[:, rkv:], pos, inv)
    causal = pos[:, None] >= pos[None, :]
    w_k, w_v = f("w_kvb_k"), f("w_kvb_v")                   # [rkv, H, dn|dv]

    def heads(j):
        cut = lambda a, ax: jax.lax.dynamic_slice_in_dim(
            a, j * head_block, head_block, ax)
        k_nope = jnp.einsum("kc,chn->khn", c_kv, cut(w_k, 1))
        v = jnp.einsum("kc,chv->khv", c_kv, cut(w_v, 1))
        s = (jnp.einsum("qhn,khn->hqk", cut(q_nope, 1), k_nope)
             + jnp.einsum("qhr,kr->hqk", cut(q_rope, 1), k_rope)
             ) * (m2 / math.sqrt(dn + dr))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v)

    out = jax.lax.map(heads, jnp.arange(H // head_block))   # [n, S, hb, dv]
    out = out.transpose(1, 0, 2, 3).reshape(S, H * dv)
    return out @ f("w_o")


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _dense_mlp(lp, h, block):
    """SwiGLU over `block` hidden numbers at a time."""
    n = lp["w_gate"].shape[1] // block

    def part(j, y):
        cut = lambda name, ax: jax.lax.dynamic_slice_in_dim(
            lp[name], j * block, block, ax).astype(F32)
        return y + _swiglu(h, cut("w_gate", 1), cut("w_up", 1),
                           cut("w_down", 0))

    return jax.lax.fori_loop(0, n, part, jnp.zeros_like(h))


def _ffn(lp, h, hp, chosen, mlp_block):
    """(FFN output [S, d], router scores or None, the experts chosen)."""
    f = lambda name: lp[name].astype(F32)
    if "router" not in lp:
        width = lp["w_gate"].shape[1]
        block = mlp_block if width % mlp_block == 0 else width
        return _dense_mlp(lp, h, block), None, None
    S = h.shape[0]
    scores = jax.nn.sigmoid(h @ f("router"))                       # [S, E]
    if chosen is None:
        _, chosen = jax.lax.top_k(scores + f("router_bias"),
                                  hp["experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if hp["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * hp["routed_scaling"]
    # the weight each routed expert has for each token, then only the
    # held ones' columns: what the absent experts would add is left out
    gates = jnp.zeros_like(scores).at[jnp.arange(S)[:, None], chosen].add(w)
    first = hp["experts_first"]

    def expert(e, y):
        pick = lambda name: jax.lax.dynamic_index_in_dim(
            lp[name], e, 0, keepdims=False).astype(F32)
        g = jax.lax.dynamic_index_in_dim(gates, first + e, 1, keepdims=False)
        return y + g[:, None] * _swiglu(h, pick("we_gate"), pick("we_up"),
                                        pick("we_down"))

    y = jax.lax.fori_loop(0, lp["we_gate"].shape[0], expert,
                          jnp.zeros_like(h))
    y = y + _swiglu(h, f("ws_gate"), f("ws_up"), f("ws_down"))
    return y, scores, chosen


def logits_and_choices(params: dict, tokens, hp: dict, rows, chosen=None,
                       head_block: int = 2, mlp_block: int = 2048):
    """tokens [1, S] -> (logits [len(rows), vocab] at positions `rows`,
    {"chosen": [expert layers] of [S, k], "router_scores": of [S, E]
    (the sigmoid scores, without the selection bias)}). `chosen`, where
    given, is a list of the same form that replaces the reference's own
    choices."""
    with jax.default_matmul_precision("highest"):
        toks = tokens[0]
        pos = jnp.arange(toks.shape[0])
        eps = hp["norm_eps"]
        x = jnp.take(params["embed"], toks, axis=0).astype(F32)
        out: dict = {"chosen": [], "router_scores": []}
        for lp in params["layers"]:
            h = _rms(x, lp["norm"].astype(F32), eps)
            x = x + _attention(lp, h, hp, pos, head_block)
            h = _rms(x, lp["mlp_norm"].astype(F32), eps)
            forced = None
            if "router" in lp and chosen is not None:
                forced = chosen[len(out["chosen"])]
            y, scores, took = _ffn(lp, h, hp, forced, mlp_block)
            if scores is not None:
                out["chosen"].append(took)
                out["router_scores"].append(scores)
            x = x + y
        x = _rms(x[rows], params["final_norm"].astype(F32), eps)
        return x @ params["lm_head"].astype(F32), out

