"""What the hybrid model's one-token state update costs at the
granite-4.0-h-micro cell's sizes, on whatever device jax finds, and how
far the kernel (ops/pallas/ssm_update.py) lies from the plain form it
stands in for (ops/ssm.ssm_step on the layer cut from the stack):

  python3 tools/ssm_update_probe.py [--blocks 8,16,32,64] [--rows 32]
      [--layers 36] [--heads 64] [--p 64] [--n 128] [--repeat 5]

One program a form: every layer of a donated stack updated once, in a
loop whose counter is the layer index, as the decode step's layer loop
does (a copy of the stack around the call, had the alias not held,
would show here as 3 ms a layer). Random state and inputs from --seed.
One JSON line a form: milliseconds a round of all layers (the median of
--repeat, each ended by block_until_ready) and a layer, the bytes of
state read and written over that time, and for the kernel, in each of
--blocks heads a block (the first line is what `heads_per_block`
gives), the largest absolute difference of y and of the first layer's
state to the plain form's. The line `loop` is the same program with no
state in it: what the loop, its small operations and the dispatch cost,
to be taken off the others. Nothing here is the benchmark's: it sizes
the kernel's block (PERF.md, PR 39).
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
    ap.add_argument("--blocks", default="8,16,32,64")
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--p", type=int, default=64)
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.pallas import ssm_update as su
    from ray_tpu.ops.ssm import ssm_step

    f32 = jnp.float32
    layers, rows, h, p, n = (args.layers, args.rows, args.heads, args.p,
                             args.n)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "state": [layers, rows, h, p, n]}), flush=True)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 7)
    stack = jax.random.normal(ks[0], (layers, rows, h, p, n), f32)
    small = (jax.random.normal(ks[1], (rows, h, p), f32),
             jax.nn.softplus(jax.random.normal(ks[2], (rows, h), f32)),
             -jnp.exp(jax.random.uniform(ks[3], (layers, h), f32, 0.0, 2.77)),
             jax.random.normal(ks[4], (rows, n), f32),
             jax.random.normal(ks[5], (rows, n), f32),
             jax.random.normal(ks[6], (layers, h), f32))

    def plain(states, li, *ops):
        y, state = ssm_step(jax.lax.dynamic_index_in_dim(
            states, li, 0, keepdims=False), *ops)
        return y, jax.lax.dynamic_update_slice(states, state[None],
                                               (li, 0, 0, 0, 0))

    def loop_only(states, li, x, dt, a, b, c, d):
        return d[:, None] * x * dt[..., None] + jnp.exp(a)[:, None], states

    def every_layer(form):
        def run(states, x, dt, a, b, c, d):
            def one(li, carry):
                states, y = carry
                # a layer's y reaches the next layer's x, so none is dropped
                return form(states, li, x + 1e-3 * y, dt, a[li], b, c,
                            d[li])[::-1]
            return jax.lax.fori_loop(0, layers, one,
                                     (states, jnp.zeros_like(x)))
        return jax.jit(run, donate_argnums=(0,))

    def timed(form):
        fn = every_layer(form)
        states, y = jax.block_until_ready(fn(jnp.array(stack), *small))
        first = np.asarray(states[0, 0]), np.asarray(y)
        ms = []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            states, y = jax.block_until_ready(fn(states, *small))
            ms.append(1e3 * (time.perf_counter() - t0))
        return first, statistics.median(ms)

    def line(name, ms, **more):
        print(json.dumps({
            "form": name, "ms_round": round(ms, 3),
            "ms_layer": round(ms / layers, 4),
            "state_gb_per_s": round(2 * stack.size * 4 / ms / 1e6, 1),
            **more}), flush=True)

    _, ms = timed(loop_only)
    line("loop", ms)
    (want_state, want_y), ms = timed(plain)
    line("plain", ms)
    chosen = su.heads_per_block(h, p, n, f32)
    blocks = [chosen] + [int(b) for b in args.blocks.split(",")
                         if b and int(b) != chosen]
    for hb in blocks:
        if not hb:
            print(json.dumps({"form": "kernel", "heads_block": hb,
                              "error": "a shape the kernel does not take"}),
                  flush=True)
            continue
        try:
            (state, y), ms = timed(
                lambda *a, hb=hb: su.ssm_update(*a, heads_block=hb))
        except Exception as e:  # a block VMEM does not hold
            print(json.dumps({"form": "kernel", "heads_block": hb,
                              "error": repr(e)[-300:]}), flush=True)
            continue
        line("kernel", ms, heads_block=hb, chosen=hb == chosen,
             block_mib=hb * p * n * 4 / 2 ** 20,
             y_max_abs_diff=float(np.abs(y - want_y).max()),
             y_max_abs=float(np.abs(want_y).max()),
             state_max_abs_diff=float(np.abs(state - want_state).max()))


if __name__ == "__main__":
    main()
