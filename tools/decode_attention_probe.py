"""What a decode step's attention and its cache writes cost a layer at
the chat cell's sizes (InternLM2-1.8B: 8 slots x 4,096 positions, 8 kv
heads of 128, 24 layers, bf16), on whatever device jax finds, in the two
forms `ops/attention.cached_attention` has for them:

  python3 tools/decode_attention_probe.py [--live 4,8] [--rows 8]
      [--layers 24] [--max-len 4096] [--repeat 7] [--seed 0]
      [--ring] [--depths 100,1500]

`writes+kernel`: sixteen `dynamic_update_slice`s a layer (one of K and
one of V a slot) and then the decode kernel, which is what every decode
step ran before PR 48 and what a head under a lane row still runs;
`kernel-writes`: the kernel handed the new rows, which it attends to and
leaves written in the block it holds (ops/pallas/decode_attention.py).
One program a form: every layer of the donated stacks once, in a loop
whose counter is the layer index, as the decode step's layer loop does
(a copy of a stack around the call, had an alias not held, would show
here as milliseconds a layer). With each of --live rows of --rows
holding a request at a depth drawn from 100-1,500 (--seed), the others
empty as the engine marks them (depth -1). One JSON line a form:
microseconds a round of all layers (the median of --repeat, each ended
by block_until_ready) and a layer. The line `loop` is the same program
with no cache in it: what the loop, its small operations and the
dispatch cost, to be taken off the others. The line `agree` compares the
two forms after one round from the same stacks: the largest absolute
difference of the outputs over the live rows, and whether the caches are
equal bit for bit over the live rows and, in the empty rows, equal to
what went in. With --ring the stacks are a window layer's rings,
--max-len rows deep (models/laguna.py: `--ring --rows 32 --live 32
--layers 3 --max-len 512 --group 9 --depths 600,20000` is the Laguna
cell's): one block a row in the kernel's `ring` mode, the new row at
``length mod max_len``. Nothing here is the benchmark's (PERF.md, PRs 48
and 51).
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
    ap.add_argument("--live", default="4,8")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=4096)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--group", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--repeat", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ring", action="store_true")
    ap.add_argument("--depths", default="100,1500")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import attention

    dt = jnp.bfloat16
    layers, b, nkv, group, hd, max_len = (args.layers, args.rows,
                                          args.kv_heads, args.group,
                                          args.head_dim, args.max_len)
    dev = jax.devices()[0]
    block = max_len if args.ring else attention.decode_block_len(
        nkv, hd, max_len, dt, jax.sharding.get_abstract_mesh())
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "k": [layers, b, nkv, hd, max_len],
                      "block_len": block}), flush=True)
    if block is None:
        sys.exit("no decode kernel for this shape on this device")
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    k0 = jax.random.normal(ks[0], (layers, b, nkv, hd, max_len), dt)
    v0 = jax.random.normal(ks[1], (layers, b, nkv, max_len, hd), dt)
    q = jax.random.normal(ks[2], (b, nkv, group, hd), dt)
    kk = jax.random.normal(ks[3], (b, 1, nkv, hd), dt)
    vv = jax.random.normal(ks[4], (b, 1, nkv, hd), dt)
    start = jnp.zeros((b,), jnp.int32)
    scale = hd ** -0.5

    def kernel(k, v, li, q, length, new_kv):
        return attention.decode_attention(
            q, k, v, li, start, length, scale=scale, block_len=block,
            new_kv=new_kv, ring=args.ring)

    def writes_then_kernel(k, v, li, q, kk, vv, length):
        at = jnp.maximum(length, 0) % max_len if args.ring else length
        k, v = attention._write_rows(k, v, kk, vv, li, at)
        return kernel(k, v, li, q, length, None), k, v

    def kernel_writes(k, v, li, q, kk, vv, length):
        return kernel(k, v, li, q, length, (kk[:, 0], vv[:, 0]))

    def loop_only(k, v, li, q, kk, vv, length):
        return q * scale + (kk[:, 0] * vv[:, 0])[:, :, None], k, v

    def every_layer(form):
        def run(k, v, length):
            def one(li, carry):
                k, v, out = carry
                # a layer's output reaches the next layer's operands, so
                # none is dropped
                mix = 1e-3 * out.astype(dt)
                o, k, v = form(k, v, li, q + mix, kk + mix[:, None, :, 0],
                               vv + mix[:, None, :, 1], length)
                return k, v, o
            return jax.lax.fori_loop(0, layers, one,
                                     (k, v, jnp.zeros_like(q)))
        return jax.jit(run, donate_argnums=(0, 1))

    def timed(fn, k, v, length):
        k, v, out = jax.block_until_ready(fn(k, v, length))
        us = []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            k, v, out = jax.block_until_ready(fn(k, v, length))
            us.append(1e6 * (time.perf_counter() - t0))
        del k, v
        return statistics.median(us)

    forms = {"loop": every_layer(loop_only),
             "writes+kernel": every_layer(writes_then_kernel),
             "kernel-writes": every_layer(kernel_writes)}
    rng = np.random.default_rng(args.seed)
    for live in (int(n) for n in args.live.split(",") if n):
        depth = np.full((b,), -1, np.int32)
        at = rng.permutation(b)[:live]
        lo, hi = (int(n) for n in args.depths.split(","))
        depth[at] = rng.integers(lo, hi + 1, size=live)
        length = jnp.asarray(depth)
        holds = jnp.asarray(depth >= 0)
        case = {"live": live, "depths": depth.tolist()}

        k1, v1, o1 = forms["writes+kernel"](jnp.array(k0), jnp.array(v0),
                                            length)
        k2, v2, o2 = forms["kernel-writes"](jnp.array(k0), jnp.array(v0),
                                            length)

        def same(a, b, rows):
            return bool(jnp.all(jnp.where(
                rows[None, :, None, None, None], a == b, True)))

        print(json.dumps({
            "form": "agree", **case,
            "out_max_abs_diff": float(jnp.max(jnp.abs(
                (o1 - o2).astype(jnp.float32))[holds])),
            "out_max_abs": float(jnp.max(jnp.abs(
                o1.astype(jnp.float32))[holds])),
            "caches_equal_live_rows": same(k1, k2, holds) and same(
                v1, v2, holds),
            "empty_rows_as_they_went_in": same(k0, k2, ~holds) and same(
                v0, v2, ~holds)}), flush=True)
        del k1, v1, k2, v2
        for name, fn in forms.items():
            us = timed(fn, jnp.array(k0), jnp.array(v0), length)
            print(json.dumps({"form": name, **case,
                              "us_round": round(us, 1),
                              "us_layer": round(us / layers, 2)}),
                  flush=True)


if __name__ == "__main__":
    main()
