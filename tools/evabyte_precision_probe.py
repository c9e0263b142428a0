"""What the EvaByte cell's comparison with the plain reference reads at
the stated precision, at the precision just below it, and with the
chunk grid one byte off, at the configuration's full size, on whatever
device jax finds:

  python3 tools/evabyte_precision_probe.py [--seeds 2] [--prompts 6000,10000]

For each seed: weights from the seed, random prompts of the given
lengths with 256 teacher-forced random bytes each, through
benchmarks/eva_model.program_steps and compare (the cell's own check:
bucket-12288 prefill in chunks, left-padded, grafted into an engine's 16
slots, then cached decode steps of the batch) with (a) the configuration
as stated, (b) the program's matrices rounded to float8_e4m3's 3
mantissa bits, (c) the program given each prompt behind ONE more byte,
so that every window and chunk of its cache lies one byte off the
reference's grid; the reference keeps the exact weights and the prompts
as they are throughout. The chip holds one copy of the weights: the
program's steps run on the rounded copy, which is then dropped, and the
weights are made again from the seed for the reference. One JSON line a
case and sample, then the limits of the cell the case fails. The cell's
tolerances (benchmarks/configs/EvaByte.json) are set between (a) and the
other two. The continuations are random, so `token_margin_logits` and
`token_margin_program` read here what a stream unrelated to the model
reads, in every case: `arithmetic_within_limits` judges by the other
two.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--prompts", default="6000,10000")
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmarks", "configs", "EvaByte.json"))
    ap.add_argument("--check-len", type=int, default=12288)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--decode-tokens", type=int, default=256)
    ap.add_argument("--max-seq-len", type=int, default=32768)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import eva_model as helper

    with open(args.config) as f:
        config = json.load(f)
    cfg = helper.program_config(config, "serve",
                                max_seq_len=args.max_seq_len)
    hp = helper.reference_hp(config)
    lengths = [int(n) for n in args.prompts.split(",")]
    keys = ("logits_rel_rms", "logits_rel_rms_head0", "summary_rel_rms",
            "summary_key_rel_rms", "token_margin_logits",
            "tokens_not_argmax", "token_margin_program",
            "tokens_not_program_argmax", "finite")

    def steps(params, samples):
        """The cell's program steps in the slots of an engine of the
        cell's sizes, dropped with its hold on `params`."""
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(cfg, tp=1, max_batch=args.slots,
                        prompt_buckets=(args.check_len,),
                        prefill_chunk=args.chunk, prefix_cache_entries=0,
                        params=params)
        try:
            return helper.program_steps(
                eng, params, samples, args.check_len, args.decode_tokens,
                helper.take_slots(eng))
        finally:
            del eng
            gc.collect()

    for seed in range(args.seeds):
        rng = np.random.default_rng([seed, 7])
        samples = [{"tokens": rng.integers(1, cfg.vocab_size, n).tolist(),
                    "generated": rng.integers(
                        1, cfg.vocab_size, args.decode_tokens).tolist()}
                   for n in lengths]
        params = helper.jitted_init(cfg, seed)
        cases = {"stated": steps(params, samples),
                 "grid_one_byte_off": steps(params, [
                     {**s, "tokens": [1] + s["tokens"]} for s in samples])}
        # reduce_precision, not astype(float8).astype(bf16): the chip's
        # compiler is allowed excess precision and drops that round trip
        # (tools/hybrid_precision_probe.py, PR 28)
        coarse = jax.jit(lambda p: jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, 8, 3)
            if a.dtype == jnp.bfloat16 and a.ndim > 1 else a, p),
            donate_argnums=(0,))(params)
        del params
        cases["fp8_mantissa"] = steps(coarse, samples)
        del coarse
        params = helper.jitted_init(cfg, seed)
        for name, progs in cases.items():
            checks = helper.compare(params, hp, samples, progs)
            for c in checks:
                print(json.dumps({"seed": seed, "case": name,
                                  "prompt_len": c["prompt_len"],
                                  **{k: c[k] for k in keys}}), flush=True)
            failed = sorted({k for c in checks for k in helper.LIMITS
                             if not c[k] <= config["tolerances"][k]})
            # the stream is random here: its two margins say nothing of
            # the case, the arithmetic's two limits do
            print(json.dumps({
                "seed": seed, "case": name, "failed_limits": failed,
                "arithmetic_within_limits": not any(
                    k.endswith("rel_rms") for k in failed)}), flush=True)
        del params
    print(json.dumps({"device": str(jax.devices()[0])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
