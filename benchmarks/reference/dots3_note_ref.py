"""Plain reference of the `dots3_note` decoder: the layer equations of
benchmarks/configs/dots3-note-prev.json in jax.numpy, float32, matmuls
at "highest" precision, the whole sequence at once: no cache, no chunk,
no ring, no absorbed form, no kernel, nothing imported from the program.
It reads the program's parameter tree (params["layers"][i][name]) and the
hyper-parameters `hp` (benchmarks/sparse_moe_model.reference_hp).

Two choices in this model are discrete: the positions a full layer
attends to (the `index_topk` of largest index score) and the experts a
token is sent to (the `experts_per_tok` of largest biased score). Both
are taken here by `jax.lax.top_k` over the float32 scores. A caller may
FORCE either: `selected` (one [S, S] bool matrix a full layer) and
`chosen` (one [S, k] int matrix an expert layer) replace the reference's
own choice and nothing else, so that a program that chose otherwise at a
margin can be held to the arithmetic that follows its choice.

Memory is bounded by blocks: attention over `head_block` heads at a
time, the indexer over `head_block` of its heads, the experts one at a
time (each cast to float32 when its turn comes).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotated halves over the last axis; x [S, ..., dim], pos [S]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _sizes(hp: dict, kind: str) -> dict:
    return hp["full"] if kind == "full_attention" else hp["sliding"]


def _attention(lp, h, hp, kind, pos, selected, head_block):
    """One layer's attention; returns (out [S, d], index scores or None,
    the mask of attended positions)."""
    z = _sizes(hp, kind)
    S, d = h.shape
    H, dn, dr, dv = z["heads"], z["nope"], z["rope"], z["v"]
    rq, rkv, eps = z["q_rank"], z["kv_rank"], hp["norm_eps"]
    f = lambda name: lp[name].astype(F32)
    c_q = _rms(h @ f("w_qa"), f("q_norm"), eps)
    kv = h @ f("w_kva")
    c_kv = _rms(kv[:, :rkv], f("kv_norm"), eps)
    if hp["lora_rescale"]:
        c_q = c_q * math.sqrt(d / rq)
        c_kv = c_kv * math.sqrt(d / rkv)
    q = (c_q @ f("w_qb").T).reshape(S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, z["theta"])
    k_rope = _rope(kv[:, rkv:], pos, z["theta"])
    causal = pos[:, None] >= pos[None, :]
    scores_i = None
    if kind == "full_attention":
        HI, DI = hp["index_heads"], hp["index_head_dim"]
        qi = (c_q @ f("wi_q").T).reshape(S, HI, DI)
        qi = jnp.concatenate([_rope(qi[..., :dr], pos, z["theta"]),
                              qi[..., dr:]], -1)
        ki = h @ f("wi_k")
        ki = ((ki - ki.mean(-1, keepdims=True))
              * jax.lax.rsqrt(ki.var(-1, keepdims=True) + eps)
              * f("wi_k_norm") + f("wi_k_bias"))
        ki = jnp.concatenate([_rope(ki[:, :dr], pos, z["theta"]),
                              ki[:, dr:]], -1)
        wi = (h @ f("wi_w")) / math.sqrt(HI * DI)

        def add(j, acc):
            qb = jax.lax.dynamic_slice_in_dim(qi, j * head_block, head_block,
                                              1)
            wb = jax.lax.dynamic_slice_in_dim(wi, j * head_block, head_block,
                                              1)
            dots = jnp.einsum("qhd,kd->qhk", qb, ki)
            return acc + (jax.nn.relu(dots) * wb[..., None]).sum(1)

        scores_i = jax.lax.fori_loop(0, HI // head_block, add,
                                     jnp.zeros((S, S), F32))
        scores_i = jnp.where(causal, scores_i, -jnp.inf)
        if selected is None:
            k = min(hp["index_topk"], S)
            top, idx = jax.lax.top_k(scores_i, k)
            selected = jnp.zeros((S, S), bool).at[
                jnp.arange(S)[:, None], idx].set(top > -jnp.inf)
        mask = selected & causal
    else:
        mask = causal & (pos[:, None] - pos[None, :] < hp["sliding_window"])
    g = jax.nn.sigmoid(h @ f("w_gate_attn"))                       # [S, H]
    w_k, w_v = f("w_kvb_k"), f("w_kvb_v")                   # [rkv, H, dn|dv]

    def heads(j):
        cut = lambda a, ax: jax.lax.dynamic_slice_in_dim(
            a, j * head_block, head_block, ax)
        k_nope = jnp.einsum("kc,chn->khn", c_kv, cut(w_k, 1))
        v = jnp.einsum("kc,chv->khv", c_kv, cut(w_v, 1))
        s = (jnp.einsum("qhn,khn->hqk", cut(q_nope, 1), k_nope)
             + jnp.einsum("qhr,kr->hqk", cut(q_rope, 1), k_rope)
             ) / math.sqrt(dn + dr)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v) * cut(g, 1)[..., None]

    out = jax.lax.map(heads, jnp.arange(H // head_block))   # [n, S, hb, dv]
    out = out.transpose(1, 0, 2, 3).reshape(S, H * dv)
    return out @ f("w_o"), scores_i, mask


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _ffn(lp, h, hp, chosen):
    """(FFN output [S, d], router scores or None, the experts chosen)."""
    f = lambda name: lp[name].astype(F32)
    if "router" not in lp:
        return _swiglu(h, f("w_gate"), f("w_up"), f("w_down")), None, None
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


def logits_and_choices(params: dict, tokens, hp: dict, rows,
                       selected=None, chosen=None, head_block: int = 2):
    """tokens [1, S] -> (logits [len(rows), vocab] at positions `rows`,
    {"selected": [full layers] of [S, S] bool, "index_scores": the same
    of float32 (-inf above the diagonal), "chosen": [expert layers] of
    [S, k], "router_scores": of [S, E] (the sigmoid scores, without the
    selection bias)}). `selected` and `chosen`, where given, are lists
    of the same forms that replace the reference's own choices."""
    with jax.default_matmul_precision("highest"):
        toks = tokens[0]
        S = toks.shape[0]
        pos = jnp.arange(S)
        eps = hp["norm_eps"]
        x = jnp.take(params["embed"], toks, axis=0).astype(F32)
        out: dict = {"selected": [], "index_scores": [], "chosen": [],
                     "router_scores": []}
        for li, lp in enumerate(params["layers"]):
            kind = hp["layer_types"][li]
            full = kind == "full_attention"
            forced = None
            if full and selected is not None:
                forced = selected[len(out["selected"])]
            h = _rms(x, lp["norm"].astype(F32), eps)
            attn, scores_i, mask = _attention(lp, h, hp, kind, pos, forced,
                                              head_block)
            if full:
                out["selected"].append(mask)
                out["index_scores"].append(scores_i)
            x = x + attn
            h = _rms(x, lp["mlp_norm"].astype(F32), eps)
            forced = None
            if "router" in lp and chosen is not None:
                forced = chosen[len(out["chosen"])]
            y, scores_r, took = _ffn(lp, h, hp, forced)
            if scores_r is not None:
                out["chosen"].append(took)
                out["router_scores"].append(scores_r)
            x = x + y
        x = _rms(x[rows], params["final_norm"].astype(F32), eps)
        return x @ params["lm_head"].astype(F32), out


def logits_at(params: dict, tokens, hp: dict, rows, **kw):
    """Logits [len(rows), vocab] of the full forward at positions `rows`,
    as the other references give them."""
    return logits_and_choices(params, tokens, hp, rows, **kw)[0]
