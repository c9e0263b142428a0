"""On-chip MFU sweep over (preset, batch, remat policy) configs.

One CHILD PROCESS per config, so each measurement starts with an empty
HBM and an OOM in one config cannot poison the next; the parent never
imports jax, so only the running child holds the chip (same discipline
as bench.py).

Round-4 matrix (PERF.md decomposition):
  * head_dim geometry — 410m (16x64) vs 410m-hd128 (8x128, same params):
    hd64 half-fills the MXU's 128-wide contraction; hd128 is the
    Llama-7B geometry and the biggest modeled attention lever.
  * remat policy — "dots" (saves matmul outputs, ~8.5GB at b8, OOMs b16)
    vs "nothing" (saves only block carries, unlocks b16/b24).

Usage: python tools/mfu_sweep.py            # run the matrix
       python tools/mfu_sweep.py --one preset batch policy  # child mode
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SEQ = 2048
STEPS = 15

CONFIGS = [
    # (preset, batch, remat_policy, attn_impl, block_q, block_k)
    ("410m", 8, "dots", "flash", 512, 512),   # round-3 champion (21.4k)
    ("410m", 8, "nothing", "flash", 512, 512),  # recompute A/B, equal b
    ("410m", 16, "nothing", "flash", 512, 512),  # headroom "dots" OOMs on
    ("410m", 24, "nothing", "flash", 512, 512),
    # flash tile retune at the champion geometry: the
    # kernel runs 13.4% MFU at hd64 — wider K blocks lengthen the MXU
    # contraction per softmax rescale; smaller Q blocks cut the f32
    # acc/scratch footprint so the wider K fits VMEM
    ("410m", 8, "dots", "flash", 512, 1024),
    ("410m", 8, "dots", "flash", 256, 1024),
    ("410m", 8, "dots", "flash", 256, 2048),
    ("410m", 8, "dots", "flash", 1024, 512),
    # MXU-aligned head_dim, flash and plain XLA attention side by side
    # (tests/test_chip_compile.py holds the d=128 kernels to the chip's
    # compiler). Not yet run on the chip.
    ("410m-hd128", 8, "dots", "xla", 512, 512),
    ("410m-hd128", 16, "nothing", "xla", 512, 512),
    ("410m-hd128", 24, "nothing", "xla", 512, 512),
    ("410m-hd128", 8, "dots", "flash", 512, 512),
    ("410m-hd128", 16, "nothing", "flash", 512, 512),
]


def measure(preset: str, batch: int, policy: str,
            attn_impl: str = "flash", block_q: int = 512,
            block_k: int = 512) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.parallel.spmd import build_train_step, shard_batch

    cfg = llama.config_for(preset, max_seq_len=SEQ, remat=True,
                           remat_policy=policy, attn_impl=attn_impl,
                           attn_block_q=block_q, attn_block_k=block_k)
    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    step, state = build_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), optax.adamw(3e-4), params,
        llama.param_logical_axes(cfg), mesh)
    del params
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, SEQ), 0,
                                cfg.vocab_size)
    data = shard_batch({"tokens": tokens,
                        "targets": jnp.roll(tokens, -1, 1)}, mesh)
    state, aux = step(state, data)
    float(aux["loss"])  # sync
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, aux = step(state, data)
    float(aux["loss"])
    dt = time.perf_counter() - t0
    tok_s = batch * SEQ * STEPS / dt
    from bench import PEAK_FLOPS  # keyed by device_kind; unknown = error

    mfu = (tok_s * cfg.flops_per_token()
           / PEAK_FLOPS[jax.devices()[0].device_kind])
    return {"tok_s": round(tok_s, 1), "mfu": round(mfu, 4)}


def main():
    budget = float(os.environ.get("RAYT_SWEEP_TIMEOUT_S", "900"))
    results = []
    for preset, batch, policy, attn, bq, bk in CONFIGS:
        label = {"preset": preset, "batch": batch, "policy": policy,
                 "attn": attn, "block_q": bq, "block_k": bk}
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one",
                 preset, str(batch), policy, attn, str(bq), str(bk)],
                capture_output=True, text=True, timeout=budget)
        except subprocess.TimeoutExpired:
            print(json.dumps({"cfg": label, "error": "timeout"}),
                  flush=True)
            continue
        line = next((ln for ln in reversed(r.stdout.splitlines())
                     if ln.startswith("{")), None)
        if r.returncode != 0 or line is None:
            print(json.dumps({"cfg": label,
                              "error": r.stderr[-300:]}), flush=True)
            continue
        row = {"cfg": label, **json.loads(line)}
        results.append(row)
        print(json.dumps(row), flush=True)
    if results:
        best = max(results, key=lambda r: r["mfu"])
        print(json.dumps({"best": best}), flush=True)


if __name__ == "__main__":
    if len(sys.argv) >= 5 and sys.argv[1] == "--one":
        print(json.dumps(measure(
            sys.argv[2], int(sys.argv[3]), sys.argv[4],
            sys.argv[5] if len(sys.argv) > 5 else "flash",
            int(sys.argv[6]) if len(sys.argv) > 6 else 512,
            int(sys.argv[7]) if len(sys.argv) > 7 else 512)), flush=True)
    else:
        main()
