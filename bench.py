"""Headline benchmark: Llama train-step throughput on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device": {"platform", "kind", "count"}}.

The north-star metric (BASELINE.json) is Llama fine-tune tokens/sec/chip
at >=35% MFU on TPU; `vs_baseline` here is achieved-MFU / 0.35 so >=1.0
means the target is met.

One measurement, in one child process; this parent never imports jax, so
the child is the only process that asks for the chip. There is no CPU
leg and no replay of an earlier number: with no TPU the child fails, this
script exits non-zero and prints no rate. ROADMAP S0 replaces the single
cell with a table of cells.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# bf16 peak FLOP/s per chip, keyed by jax's `device_kind` (Google Cloud
# TPU documentation, per-generation system architecture pages). A device
# that is not in the table is an error, not a default.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# best single-v5e config from the round-3 sweep: 410m params fills the
# MXU better than 160m while params+adamw+activations fit HBM
PRESET, BATCH, SEQ, STEPS = "410m", 8, 2048, 20
CHILD_TIMEOUT_S = 900


def _measure() -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.parallel.spmd import build_train_step, shard_batch

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: platform is {dev.platform!r}, not 'tpu'; "
                         "no rate is reported from anything else")
    if dev.device_kind not in PEAK_FLOPS:
        raise SystemExit(f"bench: no peak FLOP/s on record for device_kind "
                         f"{dev.device_kind!r}; add it to PEAK_FLOPS with "
                         "its source")

    cfg = llama.config_for(PRESET, max_seq_len=SEQ, attn_impl="flash")
    mesh = build_mesh({"data": 1}, [dev])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    step, state = build_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), optax.adamw(3e-4), params,
        llama.param_logical_axes(cfg), mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0,
                                cfg.vocab_size)
    data = shard_batch({"tokens": tokens,
                        "targets": jnp.roll(tokens, -1, 1)}, mesh)

    state, aux = step(state, data)  # compile + warm-up
    jax.block_until_ready(aux["loss"])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, aux = step(state, data)
    jax.block_until_ready(aux["loss"])
    dt = time.perf_counter() - t0

    tok_s = BATCH * SEQ * STEPS / dt
    mfu = tok_s * cfg.flops_per_token() / PEAK_FLOPS[dev.device_kind]
    return {
        "metric": f"llama_{PRESET}_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }


def main() -> int:
    from ray_tpu._internal.spawn import COMPILE_CACHE_ENV, compile_cache_dir

    env = dict(os.environ)
    env.setdefault(COMPILE_CACHE_ENV, compile_cache_dir(HERE))
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            stdout=subprocess.PIPE, text=True, timeout=CHILD_TIMEOUT_S,
            env=env, cwd=HERE)
    except subprocess.TimeoutExpired:
        print(f"bench: measurement timed out after {CHILD_TIMEOUT_S}s",
              file=sys.stderr)
        return 1
    if r.returncode != 0:
        print(f"bench: measurement failed (rc={r.returncode}); "
              "no result", file=sys.stderr)
        return r.returncode
    print(r.stdout.strip().splitlines()[-1])
    return 0


if __name__ == "__main__":
    if sys.argv[1:] == ["--child"]:
        print(json.dumps(_measure()), flush=True)
    else:
        sys.exit(main())
