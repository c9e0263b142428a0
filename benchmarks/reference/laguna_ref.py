"""Plain reference of the `laguna` decoder: the layer equations of
benchmarks/configs/Laguna-S-2.1.json in jax.numpy, float32, matmuls at
"highest" precision, the whole sequence at once: no cache, no ring, no
chunk, no kernel, nothing imported from the program. It reads the
program's parameter tree (params["layers"][i][name]) and the
hyper-parameters `hp` (benchmarks/gqa_moe_model.reference_hp).

Departures from the published description (the config gives numbers
only; each is in the configuration file's `assumed`, none confirmed
against the public implementation): the per-head gate is a sigmoid of a
linear map of the layer's normed input, one number a head, on the
attention's output before the output projection; the router scores by
sigmoid and chooses by score plus a learned selection bias, the weights
the unbiased scores normalised over the chosen and times the routed
scaling factor; no query-key norm; the shared expert is added whole;
YaRN as the transformers library computes it over the ROTATED numbers of
a head (the first half in a full layer), `attention_factor` on cos and
sin, rotated halves; `sliding_window` counts the query's own position.
The experts this holder does not hold add nothing (`experts_first`, the
stacked weights' leading axis), the vocabulary is the slice the weights
have.

One choice in this model is discrete: the experts a token is sent to,
taken here by `jax.lax.top_k` over the float32 scores plus bias. A
caller may FORCE it: `chosen` (one [S, k] int matrix an expert layer)
replaces the reference's own choice and nothing else, so that a program
that chose otherwise at a margin can be held to the arithmetic that
follows its choice.

Memory is bounded by blocks: attention one query head at a time (a
head's scores over 6,272 positions are 0.16 GB in float32), the experts
one at a time, the dense MLP over `mlp_block` of its hidden numbers at a
time (each matrix cast to float32 when its turn comes), so that it fits
beside an engine that fills the chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
SLIDING = "sliding_attention"   # the kind that sees a window; any other sees all


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rope_of(rp: dict, head_dim: int):
    """(inverse frequencies [rotated // 2], the factor on cos and sin)
    from one entry of the config's `rope_parameters`: plain RoPE over the
    first `partial_rotary_factor` of a head, or YaRN over those numbers
    as the transformers library computes it."""
    dim = int(head_dim * rp.get("partial_rotary_factor", 1.0))
    theta = rp["rope_theta"]
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if rp.get("rope_type", "default") != "yarn":
        return plain, 1.0
    turns = lambda r: (dim * math.log(rp["original_max_position_embeddings"]
                                      / (r * 2 * math.pi))
                       / (2 * math.log(theta)))
    low = max(math.floor(turns(rp["beta_fast"])), 0)
    high = min(math.ceil(turns(rp["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    factor = rp.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(rp["factor"]) + 1.0
    return plain / rp["factor"] * ramp + plain * (1.0 - ramp), float(factor)


def _rope(x, pos, inv, factor):
    """The first 2 * len(inv) numbers of the last axis turned (rotated
    halves inside that part), the rest as they are; x [S, heads, hd]."""
    half = inv.shape[0]
    ang = (pos.astype(F32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], -1)


def _attention(lp, h, hp, kind: str, pos):
    """One layer's gated attention: [S, d]."""
    S = h.shape[0]
    nkv, hd = hp["kv_heads"], hp["head_dim"]
    f = lambda name: lp[name].astype(F32)
    H = lp["wq"].shape[1] // hd
    inv, factor = rope_of(hp["rope_parameters"][kind], hd)
    q = _rope((h @ f("wq")).reshape(S, H, hd), pos, inv, factor)
    k = _rope((h @ f("wk")).reshape(S, nkv, hd), pos, inv, factor)
    v = (h @ f("wv")).reshape(S, nkv, hd)
    dist = pos[:, None] - pos[None, :]
    seen = dist >= 0
    if kind == SLIDING:
        seen = seen & (dist < hp["sliding_window"])
    group = H // nkv

    def head(j):
        pick = lambda a, i: jax.lax.dynamic_index_in_dim(a, i, 1, False)
        s = pick(q, j) @ pick(k, j // group).T / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ pick(v, j // group)                          # [S, hd]

    out = jax.lax.map(head, jnp.arange(H)).transpose(1, 0, 2)   # [S, H, hd]
    gate = jax.nn.sigmoid(h @ f("w_gate_attn"))                 # [S, H]
    return (out * gate[..., None]).reshape(S, H * hd) @ f("w_o")


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
                       mlp_block: int = 2048):
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
        for lp, kind in zip(params["layers"], hp["layer_types"]):
            h = _rms(x, lp["norm"].astype(F32), eps)
            x = x + _attention(lp, h, hp, kind, pos)
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
