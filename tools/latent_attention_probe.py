"""What a prefill chunk's latent attention costs at the dots3-note-prev
cell's sizes, on whatever device jax finds, and how far the kernel
(ops/pallas/latent_attention.py) lies from the plain form it stands in
for (models/dots3_note._attend_block over blocks of 1,024 keys):

  python3 tools/latent_attention_probe.py [--tiles 8x512x512,8x256x512]
      [--positions 0,7168,19456] [--repeat 5]

For each layer kind and each position of a chunk of 1,024 queries in a
bucket 20,480 deep: a mask of that position's shape (full: causal, each
query selecting 2,048 of its visible positions at random; sliding: the
window of 513 over the ring as the chunk found it and the chunk), random
rows and weights from --seed; the plain form as `_full_layer` /
`_sliding_layer` run it, then the kernel in each of --tiles (heads x
queries x keys a tile; the first is what `tiles` gives). One JSON line a
case: milliseconds a call (the median of --repeat, each ended by
block_until_ready), milliseconds a block of 1,024 keys visited, and the
largest absolute difference to the plain form's output. Nothing here is
the benchmark's: it sizes the kernel's tiles (PERF.md, PR 33).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="")
    ap.add_argument("--positions", default="0,7168,19456")
    ap.add_argument("--kinds", default="full_attention,sliding_attention")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--depth", type=int, default=20480)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import dots3_note as m
    from ray_tpu.ops.pallas import latent_attention as la

    cfg = m.Dots3NoteConfig()
    dt = cfg.dtype
    s, depth = args.chunk, args.depth
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))          # compiles
        ms = []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ms.append(1e3 * (time.perf_counter() - t0))
        return out, statistics.median(ms)

    for kind in args.kinds.split(","):
        a = cfg.attn(kind)
        full = kind == m.KINDS[0]
        n = depth if full else cfg.ring_len + s
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 5)
        q = jax.random.normal(ks[0], (1, a.heads, s, a.nope + a.rope)
                              ).astype(dt)
        rows = jax.random.normal(ks[1], (1, 1, n, a.row)).astype(dt)
        layer = {"w_kvb_k": (jax.random.normal(
                     ks[2], (a.kv_rank, a.heads, a.nope))
                     / np.sqrt(a.kv_rank)).astype(dt),
                 "w_kvb_v": (jax.random.normal(
                     ks[3], (a.kv_rank, a.heads, a.v))
                     / np.sqrt(a.kv_rank)).astype(dt)}
        default = la.tiles(a.heads, a.nope, a.rope, a.v, a.kv_rank, s, n)
        variants = [default] + [
            la.Tiles(*map(int, t.split("x")))
            for t in args.tiles.split(",") if t]

        @jax.jit
        def plain(q, rows, mask, first, stop):
            blk = min(n, 1024) if full else n

            def attend(j, state):
                at = jnp.minimum(j * blk, n - blk)
                own = (at + jnp.arange(blk)) >= j * blk
                return m._attend_block(
                    state, a, layer, q,
                    jax.lax.dynamic_slice_in_dim(rows[0], at, blk, axis=1),
                    jax.lax.dynamic_slice_in_dim(mask, at, blk, axis=2)
                    & own, dt)

            return m._attend_done(jax.lax.fori_loop(
                first, stop, attend, m._attend_init(q, a)), dt)

        kernels = {t: jax.jit(lambda q, rows, mask, t=t: m._attend_kernel(
            a, layer, q, rows, 0, mask, t)) for t in variants}
        for pos in map(int, args.positions.split(",")):
            q_pos = pos + jnp.arange(s)
            if full:
                k_pos = jnp.arange(n)
                visible = k_pos[None, :] <= q_pos[:, None]
                draw = jnp.where(visible, jax.random.uniform(ks[4], (s, n)),
                                 -1.0)
                kth = jax.lax.top_k(draw, min(cfg.index_topk, n))[0][:, -1:]
                mask = (visible & (draw >= kth))[None]
                first, stop = 0, (pos + s - 1) // 1024 + 1
            else:
                ring = cfg.ring_len
                old = pos - 1 - (pos - 1 - jnp.arange(ring)) % ring
                k_pos = jnp.concatenate([old, q_pos])
                dist = q_pos[:, None] - k_pos[None, :]
                mask = ((dist >= 0) & (dist < cfg.sliding_window)
                        & (k_pos >= 0)[None, :])[None]
                first, stop = 0, 1
            blocks = (stop - first) if full else n / 1024
            want, ms = timed(plain, q, rows, mask, first, stop)
            want = want.astype(jnp.float32)
            print(json.dumps({
                "kind": kind, "pos": pos, "form": "plain",
                "ms": round(ms, 3), "ms_per_block": round(ms / blocks, 3),
                "selected": int(mask.sum())}), flush=True)
            for t, fn in kernels.items():
                try:
                    got, ms = timed(fn, q, rows, mask)
                except Exception as e:  # a tile VMEM does not hold
                    print(json.dumps({"kind": kind, "pos": pos,
                                      "tiles": list(t),
                                      "error": repr(e)[-300:]}), flush=True)
                    continue
                live = int(la.tile_tables(mask, t)[0].sum())
                print(json.dumps({
                    "kind": kind, "pos": pos, "form": "kernel",
                    "tiles": list(t), "ms": round(ms, 3),
                    "ms_per_block": round(ms / blocks, 3),
                    "live_tiles": live,
                    "visited_blocks": live * t.q * t.k / (s * 1024),
                    "max_abs_diff": float(jnp.abs(
                        got.astype(jnp.float32) - want).max()),
                    "max_abs": float(jnp.abs(want).max())}), flush=True)


if __name__ == "__main__":
    main()
