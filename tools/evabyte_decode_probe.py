"""What one layer of the byte model's decode step costs at the EvaByte
cell's shapes (16 rows, 32 heads of 128, a position axis of 2,048 window
rows + 2,048 summaries, bf16), on whatever device jax finds, for the
`_decode_attend` of this tree and of every other copy of
`models/evabyte.py` named:

  python3 tools/evabyte_decode_probe.py [--other <path/to/evabyte.py>]...
      [--layers 4] [--steps 64] [--repeat 5] [--depth 9000] [--seed 0]
      [--phases spread,same]

`_decode_attend` is the ring write, the fold of a closed chunk into its
summary (scope `eva_summarise`) and the decode kernel over each row's
one range. One program a copy: every layer of the donated stacks once,
in a loop whose counter is the layer index, as the step's layer loop
has it (a copy of a stack around a branch or a loop would show here as
milliseconds a layer). A round is --steps consecutive steps, each
writing the next position of every row, ended by one
block_until_ready; one JSON line a copy and a phasing: milliseconds a
step of all layers and a layer (the median of --repeat rounds).
`spread`: the 16 rows stand at 16 different phases of their chunks
from --depth on, so one row closes a chunk on every step, as the cell's
slots do on average; `same`: every row at one phase, all 16 close on
one step in 16. The line `loop` is the program with no cache in it. The
line `agree` compares each other copy with this tree's after one round
from the same stacks: the largest difference of the last step's
outputs, and whether the summaries of every chunk the round closed are
equal bit for bit, with the largest difference of k~ and of v~ there
beside their largest entries (an open chunk's slot, which no query
reads, may differ). The every-step fold of before PR 53 is timed from the parent's
file (`git show <parent>:ray_tpu/models/evabyte.py`), not kept as a
switch. Nothing here is the benchmark's (PERF.md, PR 53).
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


def _module_at(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod           # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--other", action="append", default=[])
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--depth", type=int, default=9000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phases", default="spread,same")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import evabyte

    copies = {"tree": evabyte}
    for i, path in enumerate(args.other):
        copies[path] = _module_at(path, f"evabyte_other_{i}")
    cfg = evabyte.EvaByteConfig(n_layers=args.layers)
    L, b, H, hd = args.layers, args.rows, cfg.n_heads, cfg.head_dim
    W, c = cfg.window_size, cfg.chunk_size
    n = W + cfg.summaries()
    dt = cfg.dtype
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "k": [L, b, H, hd, n],
                      "block_len": evabyte._read_block(cfg)}), flush=True)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 7)
    k0 = jax.random.normal(ks[0], (L, b, H, hd, n), dt)
    v0 = jax.random.normal(ks[1], (L, b, H, n, hd), dt)
    q = jax.random.normal(ks[2], (b, 1, H, hd), dt)
    kk = jax.random.normal(ks[3], (b, 1, H, hd), dt)
    vv = jax.random.normal(ks[4], (b, 1, H, hd), dt)
    layers = {name: jax.random.normal(key, (L, H, hd), jnp.float32)
              * evabyte.EVA_VECTOR_STD
              for name, key in (("phi", ks[5]), ("mu", ks[6]))}
    live = jnp.ones((b,), bool)

    def attend(mod):
        def form(k, v, li, q, kk, vv, t):
            layer = {name: w[li] for name, w in layers.items()}
            out, k, v = mod._decode_attend(cfg, layer, li, q, kk, vv, k, v,
                                           t, live)
            return out.reshape(b, 1, H, hd), k, v
        return form

    def loop_only(k, v, li, q, kk, vv, t):
        return q * hd ** -0.5 + kk * vv, k, v

    def every_layer(form):
        def run(k, v, t):
            def one(li, carry):
                k, v, out = carry
                # a layer's output reaches the next layer's operands, so
                # none is dropped
                mix = 1e-3 * out.astype(dt)
                o, k, v = form(k, v, li, q + mix, kk + mix, vv + mix, t)
                return k, v, o
            return jax.lax.fori_loop(0, L, one, (k, v, jnp.zeros_like(q)))
        return jax.jit(run, donate_argnums=(0, 1))

    def a_round(fn, k, v, t0):
        for s in range(args.steps):
            k, v, out = fn(k, v, jnp.asarray(t0 + s, jnp.int32))
        return jax.block_until_ready((k, v, out))

    def timed(fn, t0):
        k, v, _ = a_round(fn, jnp.array(k0), jnp.array(v0), t0)
        ms = []
        for _ in range(args.repeat):
            at = time.perf_counter()
            k, v, _ = a_round(fn, k, v, t0)
            ms.append(1e3 * (time.perf_counter() - at))
        del k, v
        return statistics.median(ms) / args.steps

    @jax.jit
    def compare(k1, v1, k2, v2, closed):
        """Of the closed chunks' slots [b, n]: equal bit for bit, the
        largest difference of (k~, v~) and their largest entries; and
        whether the window's rows are equal."""
        at_k, at_v = (closed[None, :, None, None, :],
                      closed[None, :, None, :, None])
        f32 = lambda x: x.astype(jnp.float32)
        top = lambda x, at: jnp.max(jnp.where(at, jnp.abs(x), 0))
        return {
            "closed_summaries_equal": jnp.all(jnp.where(at_k, k1 == k2, True))
            & jnp.all(jnp.where(at_v, v1 == v2, True)),
            "closed_summaries_max_abs_diff": jnp.stack(
                [top(f32(k1) - f32(k2), at_k), top(f32(v1) - f32(v2), at_v)]),
            "closed_summaries_max_abs": jnp.stack(
                [top(f32(k2), at_k), top(f32(v2), at_v)]),
            "window_rows_equal": jnp.all(k1[..., :W] == k2[..., :W])
            & jnp.all(v1[:, :, :, :W] == v2[:, :, :, :W])}

    forms = {"loop": every_layer(loop_only)}
    forms.update({name: every_layer(attend(mod))
                  for name, mod in copies.items()})
    base = args.depth // c * c
    for phases in (p for p in args.phases.split(",") if p):
        t0 = base + (np.arange(b) % c if phases == "spread"
                     else np.zeros((b,), np.int64))
        case = {"phases": phases, "t_first": t0.tolist()}
        # the slots of the chunks the round closed, a row
        t = t0[:, None] + np.arange(args.steps)[None, :]
        closed = np.zeros((b, n), bool)
        for r, s in zip(*np.nonzero(t % c == c - 1)):
            closed[r, W + t[r, s] // c] = True
        ours = a_round(forms["tree"], jnp.array(k0), jnp.array(v0), t0)
        for name in args.other:
            k2, v2, o2 = a_round(forms[name], jnp.array(k0), jnp.array(v0),
                                 t0)
            print(json.dumps({
                "form": "agree", "other": name, **case,
                "chunks_closed": int(closed.sum()),
                "out_max_abs_diff": float(jnp.max(jnp.abs(
                    (ours[2] - o2).astype(jnp.float32)))),
                "out_max_abs": float(jnp.max(jnp.abs(
                    ours[2].astype(jnp.float32)))),
                **{key: np.asarray(x).tolist() for key, x in compare(
                    ours[0], ours[1], k2, v2, jnp.asarray(closed)).items()}}),
                flush=True)
            del k2, v2, o2
        del ours
        for name, fn in forms.items():
            ms = timed(fn, t0)
            print(json.dumps({"form": name, **case,
                              "ms_step": round(ms, 4),
                              "ms_layer": round(ms / L, 4)}), flush=True)


if __name__ == "__main__":
    main()
