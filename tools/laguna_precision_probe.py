"""What the Laguna-S-2.1 cell's comparison with the plain reference reads
at the stated precision, at the precision just below it, and with each
of the model's mechanisms broken in turn, at the configuration's full
size, on whatever device jax finds:

  python3 tools/laguna_precision_probe.py [--seeds 1] [--prompts 2000,6000]
                                          [--cases stated,fp8_mantissa,...]

For each seed: weights from the seed, random prompts of the given
lengths with 64 teacher-forced random tokens each, through
benchmarks/gqa_moe_model.program_steps and compare (the cell's own
check: bucket-6144 prefill in chunks, left-padded, grafted into an
engine's 32 slots, then cached decode steps of the batch) with

  stated               the configuration as stated
  fp8_mantissa         the program's matrices rounded to float8_e4m3's 3
                       mantissa bits
  plain_rope           the full layers turned plainly (theta 500,000, no
                       YaRN, no attention_factor)
  no_attention_factor  YaRN's frequencies, cos and sin not multiplied
  whole_head_rope      the full layers turn all 128 numbers of a head
  no_gate              the per-head gate left out
  ring_half_turn       the rings grafted into the slots half a turn off
  sliding_48           the sliding layers given the first 48 of their 72
                       heads' worth of Wq, gate and Wo

The reference keeps the exact weights and the file's layer throughout.
The chip holds one copy of the weights: the rounded copy is made last,
in place, and the weights are made again from the seed for the
reference. One JSON line a case and sample, and one that says whether
the cell would call the case `correct`. The cell's tolerances
(benchmarks/configs/Laguna-S-2.1.json) are set between `stated` and
`fp8_mantissa`, and every other case has to fail one of them, or the
limits hold nothing of that mechanism. The continuations are random, so
`token_margin_logits` and `token_margin_program` read here what a stream
unrelated to the model reads.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CASES = ("stated", "plain_rope", "no_attention_factor", "whole_head_rope",
         "no_gate", "ring_half_turn", "sliding_48", "fp8_mantissa")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--prompts", default="2000,6000")
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--config", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "Laguna-S-2.1.json"))
    ap.add_argument("--check-len", type=int, default=6144)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=64)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import gqa_moe_model as helper
    from ray_tpu.models import laguna

    with open(args.config) as f:
        config = json.load(f)
    wanted = [c for c in args.cases.split(",") if c]
    assert set(wanted) <= set(CASES), wanted
    depth = args.check_len + 128
    cfg = helper.program_config(config, "serve", max_seq_len=depth)
    hp = helper.reference_hp(config)
    lengths = [int(n) for n in args.prompts.split(",")]
    # reduce_precision, not astype(float8).astype(bf16): the chip's
    # compiler is allowed excess precision and drops that round trip
    # (tools/hybrid_precision_probe.py, PR 28)
    fp8 = lambda a: jax.lax.reduce_precision(a, 8, 3)
    keys = ("logits_rel_rms_forced", "logits_rel_rms_forced_step",
            "router_margin", "router_swap_share",
            "logits_rel_rms", "logit_std", "token_margin_logits",
            "tokens_not_argmax", "token_margin_program",
            "tokens_not_program_argmax", "finite")
    total = -(-(max(lengths) + args.decode_tokens) // 128) * 128
    gated = laguna._parts._gate_out

    def ungated(cfg_, layer, h, attn):
        b, s = attn.shape[:2]
        return attn.reshape(b, s, -1) @ layer["w_o"].astype(cfg_.dtype)

    def fewer_heads(params, heads: int):
        """The sliding layers' Wq, gate and Wo cut to `heads` heads."""
        cut = heads * cfg.head_dim
        layers = [
            {**lp, "wq": lp["wq"][:, :cut],
             "w_gate_attn": lp["w_gate_attn"][:, :heads],
             "w_o": lp["w_o"][:cut]}
            if kind == laguna.KINDS[1] else lp
            for lp, kind in zip(params["layers"], cfg.layer_types)]
        return {**params, "layers": layers}

    def steps(step_cfg, params, samples, half_turn: bool = False):
        """An engine of the cell's sizes but for its depth: the check's
        steps take its slots, chunk, `insert_row` and `retire`. Dropped
        with its hold on `params`, cycles and all."""
        from ray_tpu.serve.llm import LLMEngine

        helper._collect_steps.cache_clear()
        eng = LLMEngine(step_cfg, tp=1, max_batch=args.slots,
                        prompt_buckets=(args.check_len // 2, args.check_len),
                        prefill_chunk=args.chunk, prefix_cache_entries=0,
                        params=params)
        if half_turn:
            row, turn = eng._row, step_cfg.ring_len // 2
            eng._row = lambda small: {
                **row(small),
                "window_k": jnp.roll(small["window_k"], turn, axis=4),
                "window_v": jnp.roll(small["window_v"], turn, axis=3)}
        try:
            return helper.program_steps(
                eng, params, samples, args.check_len, args.decode_tokens,
                total, helper.take_slots(eng))
        finally:
            del eng
            gc.collect()

    plain = dict(config["rope_parameters"]["full_attention"],
                 rope_type="default")
    broken = {
        "stated": lambda p, s: steps(cfg, p, s),
        "plain_rope": lambda p, s: steps(helper.program_config(
            {**config, "rope_parameters": {**config["rope_parameters"],
                                           "full_attention": plain}},
            "serve", max_seq_len=depth), p, s),
        "no_attention_factor": lambda p, s: steps(dataclasses.replace(
            cfg, rope_attention_factor=1.0), p, s),
        "whole_head_rope": lambda p, s: steps(dataclasses.replace(
            cfg, rope_partial=1.0), p, s),
        "ring_half_turn": lambda p, s: steps(cfg, p, s, half_turn=True),
        "sliding_48": lambda p, s: steps(
            dataclasses.replace(cfg, heads_sliding=cfg.heads_full),
            fewer_heads(p, cfg.heads_full), s),
    }

    for seed in range(args.seeds):
        rng = np.random.default_rng([seed, 7])
        samples = [{"tokens": rng.integers(1, cfg.vocab_size, n).tolist(),
                    "generated": rng.integers(
                        1, cfg.vocab_size, args.decode_tokens).tolist()}
                   for n in lengths]
        params = helper.jitted_init(cfg, seed)
        cases = {}
        for name in wanted:
            if name in broken:
                cases[name] = broken[name](params, samples)
            elif name == "no_gate":
                laguna._parts._gate_out = ungated
                try:
                    cases[name] = steps(cfg, params, samples)
                finally:
                    laguna._parts._gate_out = gated
        if "fp8_mantissa" in wanted:
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
