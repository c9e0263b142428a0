"""What one holder's routed experts cost at the three expert cells'
sizes (Kimi-K2.6: 12 of 384 experts, d 7,168, f 2,048, top-8;
dots3-note-prev: 32 of 256, d 5,120, f 1,536, top-8; Laguna-S-2.1: 64 of
256, d 3,072, f 1,024, top-10; a chunk of 1,024 tokens and a decode step
of 32 rows), on whatever device jax finds:

  python3 tools/held_experts_probe.py [--models kimi,dots3,laguna]
      [--tokens 1024,32] [--other path/to/another/moe.py[,...]]
      [--layers 4] [--repeat 5] [--seed 0]

One program a form, model, T and routing: `ops/moe.held_experts_ffn`
called --layers times on bfloat16 x, each call's y reaching the next
call's x so that none is dropped, as a step's expert layers follow one
another. Two routings: `routed`, drawn by `route_sigmoid_topk` over the
FULL router width from a random router (about T x k x held / width
pairs are this holder's), and `worst`, every pair on one held expert
(T x k rows in one group; a token there chooses its expert k times,
which no router does and the function still has to sum). One
JSON line each: milliseconds a round of all calls (the median of
--repeat, each ended by block_until_ready) and a call with the `loop`
line's round taken off (the same program with no experts in it: the
chain's additions and the dispatch), the pairs held, the experts hit,
the tiles and rows walked (from the routing, on the host; `tiles` is
the function's own third counter where it returns one), and the
largest absolute difference of y to the first form's. --other times
further copies of the module (the parent commit's, a candidate's)
beside the tree's at all three sizes: PR 52 set the kernel
(ops/pallas/held_experts.py) against PR 51's XLA loop that way.
Nothing here is the benchmark's and no cell runs it: it sizes the
experts' walk (PERF.md, PRs 47 and 52).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# held, router width, d, f, top-k:
# benchmarks/configs/{Kimi-K2.6,dots3-note-prev,Laguna-S-2.1}.json
MODELS = {"kimi": (12, 384, 7168, 2048, 8), "dots3": (32, 256, 5120, 1536, 8),
          "laguna": (64, 256, 3072, 1024, 10)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="kimi,dots3,laguna")
    ap.add_argument("--tokens", default="1024,32")
    ap.add_argument("--other", default="")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    forms = {"tree": moe}
    for path in filter(None, args.other.split(",")):
        name = os.path.splitext(os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(name, path)
        # registered first: the module's dataclass looks itself up by name
        forms[name] = sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(forms[name])

    bf16, f32 = jnp.bfloat16, jnp.float32
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "forms": list(forms), "layers": args.layers}),
          flush=True)

    def timed(fn, *ops):
        out = jax.block_until_ready(fn(*ops))
        ms = []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*ops))
            ms.append(1e3 * (time.perf_counter() - t0))
        return out, statistics.median(ms)

    def chain(layer):
        def run(x, chosen, weights, *w):
            y, counts = jnp.zeros(x.shape, f32), ()
            for _ in range(args.layers):
                y, *counts = layer(x + (1e-3 * y).astype(x.dtype), chosen,
                                   weights, *w)
            return y, counts
        return jax.jit(run)

    for model in filter(None, args.models.split(",")):
        held, width, d, f, top_k = MODELS[model]
        first = width // 3
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        dense = lambda key, shape: (jax.random.normal(key, shape, f32)
                                    / np.sqrt(shape[-2])).astype(bf16)
        w = (dense(ks[0], (held, d, f)), dense(ks[1], (held, d, f)),
             dense(ks[2], (held, f, d)))
        router = jax.random.normal(ks[3], (d, width), f32) / np.sqrt(d)
        for T in (int(t) for t in args.tokens.split(",")):
            bm = 16 if T <= 64 else 128
            x = jax.random.normal(ks[4], (T, d), f32).astype(bf16)
            _, chosen, weights = moe.route_sigmoid_topk(
                x, router, jnp.zeros((width,)), top_k)
            routings = {
                "routed": (chosen, weights),
                "worst": (jnp.full((T, top_k), first, jnp.int32),
                          jnp.full((T, top_k), 1.0 / top_k, f32))}
            _, loop_ms = timed(chain(lambda x, *_: (x.astype(f32),)), x,
                               chosen, weights, *w)
            print(json.dumps({"model": model, "T": T, "form": "loop",
                              "ms_round": round(loop_ms, 3)}), flush=True)
            for routing, (ch, wt) in routings.items():
                local = np.asarray(ch).reshape(-1) - first
                n = np.bincount(local[(local >= 0) & (local < held)],
                                minlength=held)
                tiles = int((-(-n // bm)).sum())
                want = None
                for name, mod in forms.items():
                    (y, counts), ms = timed(
                        chain(lambda *a, mod=mod: mod.held_experts_ffn(
                            *a, first)), x, ch, wt, *w)
                    y = np.asarray(y)
                    want = y if want is None else want
                    print(json.dumps({
                        "model": model, "T": T, "routing": routing,
                        "form": name, "ms_round": round(ms, 3),
                        "ms_call": round((ms - loop_ms) / args.layers, 4),
                        "pairs": int(n.sum()), "experts_hit": int((n > 0).sum()),
                        "tiles": tiles, "rows_walked": tiles * bm,
                        "counters": [int(c) for c in counts],
                        "y_max_abs_diff": float(np.abs(y - want).max()),
                        "y_max_abs": float(np.abs(want).max())}), flush=True)


if __name__ == "__main__":
    main()
