"""Plain reference of the EvaByte decoder: the layer equations of
benchmarks/configs/EvaByte.json in jax.numpy, float32, matmuls at
"highest" precision, the whole sequence at once: no cache, no chunks of
queries, no ring, no kernel, nothing imported from the program. It reads
the program's parameter tree (stacked on a leading layer axis:
params["layers"][name][layer]) one layer at a time, each cast to float32
when its turn comes, and the hyper-parameters `hp`
(benchmarks/eva_model.reference_hp).

A query at position t attends exactly to the positions of its own
window of `window` that are not after it, and through one summary key
and one summary value to every chunk of `chunk` positions that lies in
a window before its own; one softmax runs over both. Windows and chunks
are aligned blocks counted from position 0.

Memory is bounded by blocks of heads (`head_block` at a time: the
scores of one head over 10,368 positions are 457 MB in float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30


def _rms(x, g, eps):
    """RMSNorm whose weight is held as an offset from one."""
    return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * (1.0 + g))


def _rope(x, theta):
    """Rotated halves over the whole head; x [S, H, hd], row i is
    position i."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def summaries(k, v, phi, mu, chunk: int):
    """k, v [S, H, hd] (S a multiple of `chunk`), phi, mu [H, hd] ->
    the chunks' summary keys and values, each [S / chunk, H, hd]."""
    S, H, hd = k.shape
    kc = k.reshape(S // chunk, chunk, H, hd)
    vc = v.reshape(S // chunk, chunk, H, hd)
    a = jax.nn.softmax(jnp.einsum("hd,jshd->jsh", phi, kc)
                       - 0.5 * (kc * kc).sum(-1), axis=1)
    return kc.mean(1) + mu, jnp.einsum("jsh,jshd->jhd", a, vc)


def _attention(q, k, v, k_sum, v_sum, window: int, chunk: int):
    """One block of heads: q, k, v [S, h, hd], summaries [S / chunk, h,
    hd] -> [S, h, hd]."""
    S, _, hd = q.shape
    pos = jnp.arange(S)
    win = pos // window
    exact = (win[:, None] == win[None, :]) & (pos[:, None] >= pos[None, :])
    # chunk j lies in window (chunk * j) // window
    before = ((jnp.arange(k_sum.shape[0]) * chunk) // window)[None, :] \
        < win[:, None]
    z = jnp.concatenate([jnp.einsum("thd,shd->hts", q, k),
                         jnp.einsum("thd,jhd->htj", q, k_sum)], -1)
    z = jnp.where(jnp.concatenate([exact, before], -1)[None],
                  z * hd ** -0.5, NEG)
    p = jax.nn.softmax(z, axis=-1)
    return (jnp.einsum("hts,shd->thd", p[..., :S], v)
            + jnp.einsum("htj,jhd->thd", p[..., S:], v_sum))


def _layer(x, lp, hp, head_block: int):
    """x [S, d] -> (x, the layer's summary keys and values)."""
    S, d = x.shape
    H, eps = hp["heads"], hp["norm_eps"]
    hd = d // H
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope((h @ lp["wq"]).reshape(S, H, hd), hp["rope_theta"])
    k = _rope((h @ lp["wk"]).reshape(S, H, hd), hp["rope_theta"])
    v = (h @ lp["wv"]).reshape(S, H, hd)
    k_sum, v_sum = summaries(k, v, lp["phi"], lp["mu"], hp["chunk"])

    def block(args):
        return _attention(*args, hp["window"], hp["chunk"])

    # [S, H, hd] -> blocks of heads [H / hb, S, hb, hd], one at a time
    split = lambda a: a.reshape(a.shape[0], H // head_block, head_block,
                                hd).swapaxes(0, 1)
    attn = jax.lax.map(block, tuple(map(split, (q, k, v, k_sum, v_sum))))
    x = x + attn.swapaxes(0, 1).reshape(S, d) @ lp["wo"]
    h = _rms(x, lp["mlp_norm"], eps)
    x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    return x, (k_sum, v_sum)


def logits_and_summaries(params, tokens, hp: dict, rows=None,
                         head_block: int = 1):
    """tokens [S] int (S a multiple of `chunk`; what lies behind the last
    position that counts is masked by causality) -> (logits [rows,
    pred_heads, vocab] at positions `rows` (default all), layer 0's
    (summary keys, summary values), each [S / chunk, H, hd])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        first = None
        for li in range(params["layers"]["wq"].shape[0]):
            lp = {name: w[li].astype(F32)
                  for name, w in params["layers"].items()}
            x, sums = _layer(x, lp, hp, head_block)
            first = sums if first is None else first
        if rows is not None:
            x = x[rows]
        x = _rms(x, params["final_norm"].astype(F32), hp["norm_eps"])
        logits = x @ params["lm_head"].astype(F32)
    return logits.reshape(x.shape[0], hp["pred_heads"], -1), first
