"""Decompose train-step time: attention kernel vs dense matmuls vs CE.

Each leg runs in its own child process, so each starts with an empty
HBM; the parent never imports jax. Usage:
  python tools/mfu_decompose.py            # driver: runs all legs
  python tools/mfu_decompose.py <leg>      # child: one leg
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import PEAK_FLOPS  # noqa: E402


def _peak() -> float:
    """bf16 peak of the device the leg ran on; unknown kind = KeyError."""
    import jax

    return PEAK_FLOPS[jax.devices()[0].device_kind]


B = int(os.environ.get("MFU_B", 8))
S = int(os.environ.get("MFU_S", 2048))
D = int(os.environ.get("MFU_D", 1024))
H = int(os.environ.get("MFU_H", 16))
KV = int(os.environ.get("MFU_KV", H))
HID = int(os.environ.get("MFU_HID", 2816))
L = int(os.environ.get("MFU_L", 24))
V = int(os.environ.get("MFU_V", 32000))
BLOCK_Q = int(os.environ.get("MFU_BLOCK_Q", 512))
BLOCK_K = int(os.environ.get("MFU_BLOCK_K", 512))


def _time(f, *args, steps=20):
    """Time value_and_grad(f) per call: a lax.scan chains `steps`
    iterations inside ONE jit (iteration i+1 consumes a grad from i so
    nothing pipelines away), and the sync is a host readback of the
    summed losses.
    """
    import jax
    import jax.numpy as jnp

    vg = jax.value_and_grad(f, argnums=tuple(range(len(args))))

    def many(*args):
        def body(carry, _):
            l, grads = vg(carry, *args[1:])
            return carry + 0 * grads[0].astype(carry.dtype), l
        _, ls = jax.lax.scan(body, args[0], None, length=steps)
        return ls.astype(jnp.float32).sum()

    m = jax.jit(many)
    float(m(*args))  # compile + warmup
    t0 = time.perf_counter()
    float(m(*args))
    return (time.perf_counter() - t0) / steps


def leg_attn_flash():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import dot_product_attention

    hd = D // H
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd), jnp.bfloat16)

    def f(q, k, v):
        from ray_tpu.ops.pallas.flash_attention import flash_attention
        return flash_attention(
            q, k, v, True, None, BLOCK_Q, BLOCK_K).astype(
                jnp.float32).sum()

    dt = _time(f, q, k, v)
    # causal attention flops (fwd 2 matmuls + bwd 4): per layer-call
    # fwd = 2 * 2 * B*H*S*S*hd * 0.5 (causal), bwd = 2x fwd
    flops = 3 * (4 * B * H * S * S * hd * 0.5)
    return {"leg": "attn_flash_fwdbwd", "ms": dt * 1e3,
            "mfu": flops / dt / _peak(),
            "total_ms_in_step": dt * 1e3 * L}


def leg_attn_xla():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import dot_product_attention

    hd = D // H
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd), jnp.bfloat16)

    def f(q, k, v):
        return dot_product_attention(
            q, k, v, causal=True, impl="xla").astype(jnp.float32).sum()

    dt = _time(f, q, k, v)
    flops = 3 * (4 * B * H * S * S * hd * 0.5)
    return {"leg": "attn_xla_fwdbwd", "ms": dt * 1e3,
            "mfu": flops / dt / _peak(),
            "total_ms_in_step": dt * 1e3 * L}


def leg_mlp():
    """One transformer block's dense matmuls (qkvo + mlp), fwd+bwd."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, S, D), jnp.bfloat16)
    wq = jax.random.normal(key, (D, D), jnp.bfloat16)
    wo = jax.random.normal(key, (D, D), jnp.bfloat16)
    wkv = jax.random.normal(key, (D, 2 * D), jnp.bfloat16)
    w1 = jax.random.normal(key, (D, HID), jnp.bfloat16)
    w3 = jax.random.normal(key, (D, HID), jnp.bfloat16)
    w2 = jax.random.normal(key, (HID, D), jnp.bfloat16)

    def f(x, wq, wkv, wo, w1, w2, w3):
        a = x @ wq
        kv = x @ wkv
        o = (a + kv[..., :D]) @ wo
        h = jax.nn.silu(x @ w1) * (x @ w3)
        return (o + h @ w2).astype(jnp.float32).sum()

    dt = _time(f, x, wq, wkv, wo, w1, w2, w3)
    n_mm_flops = 2 * B * S * (D * D + D * 2 * D + D * D + 3 * D * HID)
    flops = 3 * n_mm_flops
    return {"leg": "block_matmuls_fwdbwd", "ms": dt * 1e3,
            "mfu": flops / dt / _peak(),
            "total_ms_in_step": dt * 1e3 * L}


def leg_ce():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.cross_entropy import fused_lm_head_cross_entropy

    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (D, V), jnp.float32)
    t = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)

    def f(x, w):
        loss, n = fused_lm_head_cross_entropy(x, w, t)
        return loss

    dt = _time(f, x, w)
    flops = 3 * (2 * B * S * D * V)
    return {"leg": "fused_ce_fwdbwd", "ms": dt * 1e3,
            "mfu": flops / dt / _peak(), "total_ms_in_step": dt * 1e3}


def leg_attn_jaxflash():
    """jax.experimental.pallas.ops.tpu.flash_attention, for comparison
    with our kernel (layout: [b, h, s, d])."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)

    hd = D // H
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, hd), jnp.bfloat16)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    dt = _time(f, q, k, v)
    flops = 3 * (4 * B * H * S * S * hd * 0.5)
    return {"leg": "attn_jaxflash_fwdbwd", "ms": dt * 1e3,
            "mfu": flops / dt / _peak(),
            "total_ms_in_step": dt * 1e3 * L}


LEGS = {f.__name__[4:]: f for f in
        (leg_attn_flash, leg_attn_xla, leg_attn_jaxflash, leg_mlp, leg_ce)}


def main():
    if len(sys.argv) > 1:
        print(json.dumps(LEGS[sys.argv[1]]()), flush=True)
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo:" + env.get("PYTHONPATH", "")
    for name in LEGS:
        try:
            r = subprocess.run([sys.executable, os.path.abspath(__file__),
                                name], capture_output=True, text=True,
                               timeout=900, env=env)
        except subprocess.TimeoutExpired:
            print(json.dumps({"leg": name, "error": "timeout"}), flush=True)
            continue
        line = [l for l in r.stdout.splitlines() if l.startswith("{")]
        if line:
            print(line[-1], flush=True)
        else:
            print(json.dumps({"leg": name,
                              "error": r.stderr[-400:]}), flush=True)


if __name__ == "__main__":
    main()
