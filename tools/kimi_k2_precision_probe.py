"""What the Kimi-K2.6 cell's comparison with the plain reference reads
at the stated precision, at the precision just below it, and with YaRN
left out, at the configuration's full size, on whatever device jax
finds:

  python3 tools/kimi_k2_precision_probe.py [--seeds 2] [--prompts 4000,8000]

For each seed: weights from the seed, random prompts of the given
lengths with 64 teacher-forced random tokens each, through
benchmarks/latent_moe_model.program_steps and compare (the cell's own
check: bucket-8192 prefill in chunks, left-padded, grafted into an
engine's 32 slots, then cached decode steps of the batch) with (a) the
configuration as stated, (b) the program's matrices rounded to
float8_e4m3's 3 mantissa bits, (c) the program built without
`rope_scaling`: plain RoPE at theta 50,000 and no temperature on the
scores, at depths of twice the 4,096 positions the frequencies were
trained at. The reference keeps the exact weights and the file's YaRN
throughout. The chip holds one copy of the weights: the program's steps
run on the rounded copy, which is then dropped, and the weights are made
again from the seed for the reference. One JSON line a case and sample,
and one that says whether the cell would call the case `correct`. The
cell's tolerances (benchmarks/configs/Kimi-K2.6.json) are set between
(a) and (b), and (c) has to fail one of them, or the limits hold nothing
of the positions. The continuations are random, so `token_margin_logits`
and `token_margin_program` read here what a stream unrelated to the
model reads.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--prompts", default="4000,8000")
    ap.add_argument("--config", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "Kimi-K2.6.json"))
    ap.add_argument("--check-len", type=int, default=8192)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=64)
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import latent_moe_model as helper

    with open(args.config) as f:
        config = json.load(f)
    depth = args.check_len + 128
    cfg = helper.program_config(config, "serve", max_seq_len=depth)
    plain = helper.program_config({**config, "rope_scaling": None}, "serve",
                                  max_seq_len=depth)
    hp = helper.reference_hp(config)
    lengths = [int(n) for n in args.prompts.split(",")]
    # reduce_precision, not astype(float8).astype(bf16): the chip's
    # compiler is allowed excess precision and drops that round trip
    # (tools/hybrid_precision_probe.py, PR 28)
    fp8 = lambda a: jax.lax.reduce_precision(a, 8, 3)
    keys = ("logits_rel_rms_forced", "router_margin", "router_swap_share",
            "logits_rel_rms", "logit_std", "token_margin_logits",
            "tokens_not_argmax", "token_margin_program",
            "tokens_not_program_argmax", "finite")
    total = -(-(max(lengths) + args.decode_tokens) // 128) * 128

    def steps(step_cfg, params, samples):
        """An engine of the cell's sizes but for its depth: the check's
        steps take its slots, chunk, `insert_row` and `retire`. Dropped
        with its hold on `params`, cycles and all."""
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(step_cfg, tp=1, max_batch=args.slots,
                        prompt_buckets=(args.check_len // 2, args.check_len),
                        prefill_chunk=args.chunk, prefix_cache_entries=0,
                        params=params)
        try:
            return helper.program_steps(
                eng, params, samples, args.check_len, args.decode_tokens,
                total, helper.take_slots(eng))
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
        cases = {"stated": steps(cfg, params, samples),
                 "plain_rope": steps(plain, params, samples)}
        coarse = jax.jit(lambda p: jax.tree.map(
            lambda a: fp8(a) if a.ndim > 1 else a, p),
            donate_argnums=(0,))(params)
        del params
        cases["fp8_mantissa"] = steps(cfg, coarse, samples)
        del coarse
        params = helper.jitted_init(cfg, seed)
        for name, progs in cases.items():
            checks = helper.compare(cfg, params, hp, samples, progs, total)
            for c in checks:
                print(json.dumps({"seed": seed, "case": name,
                                  "prompt_len": c["prompt_len"],
                                  **{k: c[k] for k in keys}}), flush=True)
            print(json.dumps({"seed": seed, "case": name, "correct":
                              helper.correct({"checks": checks},
                                             config["tolerances"])}),
                  flush=True)
        del params
    print(json.dumps({"device": str(jax.devices()[0])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
