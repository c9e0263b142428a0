"""What the attention of one prefill chunk of the byte model costs at the
EvaByte cell's sizes, on whatever device jax finds, by the chunk
kernel's tiles, and how far the kernel (ops/pallas/gqa_chunk_attention.py
as `models/evabyte._chunk_attend` calls it, twice a layer) lies from the
plain form it stands in for:

  python3 tools/evabyte_chunk_probe.py [--tiles 512x512,1024x512]
      [--phases 1000,1536,22000] [--depth 24576] [--repeat 5]

For each own position of the chunk's first query (--phases: 1,000 lies
mid-window, at 1,536 a window ends inside the chunk, 22,000 is deep in
the bucket with both), eight layers of `_chunk_attend` over a stack of
noise, the stacks donated: the plain form, then the kernel with each of
--tiles (queries x keys a tile; keys are the largest of {that, 512, 256,
128} a call's columns are whole in; default: every pair of 256, 512,
1,024). One JSON line a case: milliseconds a layer of the whole scope
(attention, the summaries made, the layer's write) and of the two kernel
calls and their merge alone (the median of --repeat, each ended by
block_until_ready), the pairs visited over the pairs visible
(`prefill_counters`), and the largest absolute difference to the plain
form's output. Nothing here is the benchmark's: it sizes the kernel's
tiles (PERF.md, PR 59).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default=",".join(
        f"{q}x{k}" for q in (256, 512, 1024) for k in (256, 512, 1024)))
    ap.add_argument("--phases", default="1000,1536,22000")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--depth", type=int, default=24576)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import evabyte as m
    from ray_tpu.ops.pallas import gqa_chunk_attention as gqa

    cfg = m.EvaByteConfig(n_layers=args.layers)
    L, H, hd, W, s = cfg.n_layers, cfg.n_heads, cfg.head_dim, \
        cfg.window_size, args.chunk
    n = W + cfg.summaries(args.depth)
    dt = cfg.dtype
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "leaf_columns": n}), flush=True)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 7)
    q, kk, vv = (jax.random.normal(k, (1, s, H, hd)).astype(dt)
                 for k in ks[:3])
    layer = {"phi": jax.random.normal(ks[5], (H, hd)) * m.EVA_VECTOR_STD,
             "mu": jax.random.normal(ks[6], (H, hd)) * m.EVA_VECTOR_STD}

    def stacks():
        return (jax.random.normal(ks[3], (L, 1, H, hd, n)).astype(dt),
                jax.random.normal(ks[4], (L, 1, H, n, hd)).astype(dt))

    @functools.partial(jax.jit, static_argnames=("kernel",),
                       donate_argnums=(0, 1))
    def layers(kc, vc, t0, kernel):
        t = t0 + jnp.arange(s)[None, :]
        plan = m._chunk_plan(cfg, t, n) if kernel else None

        def body(li, carry):
            acc, kc, vc = carry
            attn, kc, vc = m._chunk_attend(cfg, layer, li, q, kk, vv, kc,
                                           vc, t, plan)
            return acc + attn.astype(jnp.float32), kc, vc

        return jax.lax.fori_loop(
            0, L, body, (jnp.zeros((1, s, H * hd), jnp.float32), kc, vc))

    @jax.jit
    def kernels(kc, vc, t0):
        t = t0 + jnp.arange(s)[None, :]
        (found_t, own_t, made), found, found_tab, own, own_tab = \
            m._chunk_plan(cfg, t, n)
        q_h = q.transpose(0, 2, 1, 3)[:, :, None]
        k_own = jnp.zeros((1, 1, H, hd, s + made), dt)
        v_own = jnp.zeros((1, 1, H, s + made, hd), dt)

        def body(li, acc):
            one = gqa.gqa_chunk_attention(
                q_h, kc, vc, li, found, t[:, 0], scale=hd ** -0.5,
                t=found_t, tables=found_tab, parts=True)
            two = gqa.gqa_chunk_attention(
                q_h, k_own, v_own, 0, own, t[:, 0], scale=hd ** -0.5,
                t=own_t, tables=own_tab, parts=True)
            return acc + m._merged(one, two)

        return jax.lax.fori_loop(
            0, L, body, jnp.zeros((1, H, 1, s, hd), jnp.float32))

    def timed(fn, fresh):
        ms, out = [], None
        for i in range(args.repeat + 1):
            a = fresh()
            jax.block_until_ready(a)
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*a))
            if i:                                    # the first compiles
                ms.append(1e3 * (time.perf_counter() - t0))
        return out, statistics.median(ms) / L

    held = stacks()
    for phase in map(int, args.phases.split(",")):
        t0 = jnp.full((1, 1), phase, jnp.int32)
        (want, _, _), ms = timed(
            lambda kc, vc: layers(kc, vc, t0, kernel=False), stacks)
        print(json.dumps({"phase": phase, "form": "plain",
                          "ms_per_layer": round(ms, 4)}), flush=True)
        for name in args.tiles.split(","):
            tq, tk = map(int, name.split("x"))
            gqa._Q_TILES = (tq,)
            gqa._K_TILES = tuple(
                k for k in (1024, 512, 256, 128) if k <= tk) or (tk,)
            m._attention._on_tpu = lambda: True
            jax.clear_caches()
            try:
                (got, _, _), ms = timed(
                    lambda kc, vc: layers(kc, vc, t0, kernel=True), stacks)
                _, ms_kernels = timed(lambda: kernels(*held, t0),
                                      lambda: ())
            except Exception as e:      # a tile the chip does not hold
                print(json.dumps({"phase": phase, "tiles": name,
                                  "error": repr(e)[-300:]}), flush=True)
                continue
            c = m.prefill_counters(cfg, 0, phase, s, args.depth)
            tl = m._chunk_tiles(cfg, s, n)
            print(json.dumps({
                "phase": phase, "form": "kernel", "tiles": name,
                "found": list(tl[0]), "own": list(tl[1]), "made": tl[2],
                "ms_per_layer": round(ms, 4),
                "ms_per_layer_kernels": round(ms_kernels, 4),
                "visited_per_visible": round(
                    (c["prefill_window_keys_visited"]
                     + c["prefill_summaries_visited"])
                    / (c["prefill_window_keys_visible"]
                       + c["prefill_summaries_visible"]), 3),
                "max_abs_diff": float(jnp.abs(got - want).max()),
                "max_abs": float(jnp.abs(want).max())}), flush=True)


if __name__ == "__main__":
    main()
