"""What the train step's flash attention costs at the train cells'
shapes, on whatever device jax finds, tile by tile, and how far the
compiled kernels lie from `xla_attention`:

  python3 tools/flash_attention_probe.py [--shapes 4x2048x16x8x128,...]
      [--tiles 256x256,512x512,1024x1024@128,...] [--repeat 20]
      [--seed 0] [--parity none|chosen|all]
      [--other path/to/another/flash_attention.py]

One JSON line a (shape, tile): milliseconds of the forward
(`_flash_forward`), of the backward (`_flash_backward`, with delta) and
of both through `jax.value_and_grad` (forward once), each the mean of
--repeat calls queued back to back and ended by one block_until_ready.
The operands are handed over as the model's projections make them and
take them, ``[b, s, heads * head_dim]``, and cut into heads inside the
timed program, so a layout the kernel's caller has to make (a transpose
to ``[b, h, s, d]``) is in the time and one it need not is not. A tile
`QxK@S` is run with strips of S on the diagonal. Beside them the
share of the chip's bf16 peak that the REQUIRED products reach (the
causal half once: two products forward, four backward, as
benchmarks/attention_ops.py counts them); and `parity`, the largest
error of out, dq, dk, dv against `xla_attention` in bf16 over the
largest reference value (`chip_smoke._kernel_parity`, its tolerances;
--parity: for the chosen tile and --other, for every tile, or none).
The first tile of a shape is the one the caller chooses (blocks left
None). --other times a second copy of the module (the parent commit's)
under the same clock at the tile it can take. Nothing here is the
benchmark's: it sizes `flash_attention.default_blocks` (PERF.md, PR 41).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK = {"TPU v5 lite": 197e12}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="4x2048x16x8x128,4x2048x16x4x128")
    ap.add_argument("--tiles", default="256x256,512x512,1024x1024")
    ap.add_argument("--repeat", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--other", default=None)
    ap.add_argument("--parity", choices=("none", "chosen", "all"),
                    default="chosen")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import chip_smoke
    from ray_tpu.ops.pallas import flash_attention as fa

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    peak = PEAK.get(dev.device_kind)
    impls = {"this": fa}
    diag_sub = fa._DIAG_SUB
    if args.other:
        spec = importlib.util.spec_from_file_location("other_flash",
                                                      args.other)
        impls["other"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(impls["other"])

    def mean_ms(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.repeat):
            out = fn(*a)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / args.repeat

    for shape in args.shapes.split(","):
        b, s, h, hk, d = (int(x) for x in shape.split("x"))
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q, g = (jax.random.normal(k, (b, s, h * d), jnp.bfloat16)
                for k in ks[:2])
        k, v = (jax.random.normal(k, (b, s, hk * d), jnp.bfloat16)
                for k in ks[2:])
        product = 2 * b * h * (s * s / 2) * d      # one, the causal half

        def cut(*xs):       # [b, s, heads * d] -> [b, s, heads, d]
            return [x.reshape(b, s, -1, d) for x in xs]

        def flat(*xs):
            return [x.reshape(b, s, -1) for x in xs]

        tiles = [None] + [t for t in args.tiles.split(",") if t]
        for name, mod in impls.items():
            for tile in tiles if name == "this" else ["512x512"]:
                sub = None
                if tile:
                    tile, _, sub = tile.partition("@")
                    tile = tuple(int(x) for x in tile.split("x"))
                bq, bk = tile or (None, None)
                if name == "this":
                    mod._DIAG_SUB = int(sub) if sub else diag_sub
                line = {"shape": shape, "impl": name,
                        "tile": tile or list(fa.default_blocks(s, s)),
                        "chosen": tile is None}
                if name == "this":
                    line["strips"] = mod._strips(s, s, *line["tile"])
                try:
                    def fwd(q, k, v):
                        out, lse = mod._flash_forward(
                            *cut(q, k, v), causal=True, scale=None,
                            block_q=bq, block_k=bk)
                        return flat(out)[0], lse
                    fwd = jax.jit(fwd)
                    out, lse = fwd(q, k, v)
                    bwd = jax.jit(lambda q, k, v, o, lse, g: flat(
                        *mod._flash_backward(
                            *cut(q, k, v, o), lse, *cut(g), causal=True,
                            scale=None, block_q=bq, block_k=bk)))
                    both = jax.jit(jax.value_and_grad(
                        lambda q, k, v: (flat(mod.flash_attention(
                            *cut(q, k, v), True, None, bq, bk))[0]
                            .astype(jnp.float32) * g).sum(),
                        argnums=(0, 1, 2)))
                    ms = {"fwd": mean_ms(fwd, q, k, v),
                          "bwd": mean_ms(bwd, q, k, v, out, lse, g),
                          "both": mean_ms(both, q, k, v)}
                    line["ms"] = {n: round(x, 4) for n, x in ms.items()}
                    if peak:
                        line["required_share_of_peak"] = {
                            n: round(100 * c * product / peak / (x / 1e3), 2)
                            for (n, x), c in zip(ms.items(), (2, 4, 6))}
                    if args.parity == "all" or (
                            args.parity == "chosen"
                            and (line["chosen"] or name == "other")):
                        par = chip_smoke._kernel_parity(
                            b, s, h, hk, d, args.seed,
                            blocks=tile if name == "this" else (512, 512),
                            flash_attention=mod.flash_attention)
                        line["parity"] = {**par["rel_err"],
                                          "within": par["within"]}
                    if name == "this":
                        line["backward_path"] = fa.backward_path(
                            s, d, h // hk, q.dtype)
                except Exception as e:  # a tile VMEM does not hold
                    line["error"] = repr(e)[-400:]
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
