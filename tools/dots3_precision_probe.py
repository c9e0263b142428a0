"""What the dots3-note-prev cell's comparison with the plain reference
reads at the stated precision and at the precisions just below it, at
the configuration's full size, on whatever device jax finds:

  python3 tools/dots3_precision_probe.py [--seeds 2] [--prompts 3000,6000]
  python3 tools/dots3_precision_probe.py --faults [--seeds 1]

For each seed: weights from the seed, random prompts of the given
lengths with 64 teacher-forced random tokens each, through
benchmarks/sparse_moe_model.program_steps and compare (the cell's own
check: bucket-8192 prefill in chunks, left-padded, grafted into the
engine's 32 slots, then cached decode steps of the batch) with (a) the
configuration as stated, (b) the program's matrices rounded to
float8_e4m3's 3 mantissa bits, (c) to multiples of 1/8; the reference
keeps the exact weights throughout. The chip holds one copy of the
weights: the program's steps run on the rounded copy, which is then
dropped, and the weights are made again from the seed for the
reference. One JSON line a case. The cell's tolerances
(benchmarks/configs/dots3-note-prev.json) are set between (a) and (b).
The continuations are random, so `token_margin_logits` and
`token_margin_program` read here what a stream unrelated to the model
reads.

With --faults the continuations are what an LLMEngine of the cell's
sizes streams for the prompts, all live at once, and the check reads
them (a) as streamed, (b) with the two rows' streams swapped, and from
an engine whose `insert_row` grafts the window's ring (c) one row and
(d) half a turn off; the check's own steps stay sound throughout. The
last line of each case says whether the cell would call it `correct`.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def faults(seed, eng, params, prompts, args, report):
    """Streams of one engine, sound and with a fault planted in its
    graft, each held by the cell's check."""
    import asyncio

    import jax.numpy as jnp

    def streams():
        async def one(p):
            return [t async for t in eng.generate(
                p, max_new_tokens=args.decode_tokens)]

        async def run():
            # twice over: several rows live at once, of either length
            return await asyncio.gather(*[one(p) for p in prompts + prompts])
        return asyncio.run(run())[:len(prompts)]

    sound = streams()
    pair = lambda gens: [{"tokens": p, "generated": g}
                         for p, g in zip(prompts, gens)]
    report(seed, "as_streamed", eng, params, pair(sound))
    report(seed, "rows_swapped", eng, params, pair(sound[::-1]))
    graft = eng._insert_row
    for name, by in (("ring_one_row_off", 1),
                     ("ring_half_a_turn_off", eng.cfg.ring_len // 2)):
        eng._insert_row = lambda cache, row, *a: graft(cache, {
            **row, "window": jnp.roll(row["window"], by, axis=2)}, *a)
        got = streams()
        eng._insert_row = graft
        print(json.dumps({"seed": seed, "case": name, "tokens_changed": [
            sum(a != b for a, b in zip(g, s)) for g, s in zip(got, sound)]}),
            flush=True)
        report(seed, name, eng, params, pair(got))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--prompts", default="3000,6000")
    ap.add_argument("--config", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "dots3-note-prev.json"))
    ap.add_argument("--check-len", type=int, default=8192)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=64)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import sparse_moe_model as helper

    with open(args.config) as f:
        config = json.load(f)
    cfg = helper.program_config(config, "serve",
                                max_seq_len=args.check_len + 128)
    hp = helper.reference_hp(config)
    lengths = [int(n) for n in args.prompts.split(",")]
    # reduce_precision, not astype(float8).astype(bf16): the chip's
    # compiler is allowed excess precision and drops that round trip
    # (tools/hybrid_precision_probe.py, PR 28)
    lower = {
        "fp8_mantissa": lambda a: jax.lax.reduce_precision(a, 8, 3),
        "eighths": lambda a: (jnp.round(a.astype(jnp.float32) * 8) / 8
                              ).astype(a.dtype)}
    keys = ("logits_rel_rms_forced", "index_score_rel_rms",
            "selection_margin", "selection_swap_share", "router_margin",
            "router_swap_share", "logits_rel_rms", "token_margin_logits",
            "tokens_not_argmax", "token_margin_program",
            "tokens_not_program_argmax", "finite")
    total = -(-(max(lengths) + args.decode_tokens) // 128) * 128

    def engine(params):
        """An engine of the cell's sizes but for its depth: the check's
        steps take its slots, chunk, `insert_row` and `retire`."""
        from ray_tpu.serve.llm import LLMEngine

        return LLMEngine(cfg, tp=1, max_batch=args.slots,
                         prompt_buckets=(args.check_len // 2, args.check_len),
                         prefill_chunk=args.chunk, prefix_cache_entries=0,
                         params=params)

    def report(seed, name, eng, params, samples, progs=None):
        progs = progs or helper.program_steps(
            eng, params, samples, args.check_len, args.decode_tokens, total)
        checks = helper.compare(cfg, params, hp, samples, progs, total)
        for c in checks:
            print(json.dumps({"seed": seed, "case": name,
                              "prompt_len": c["prompt_len"],
                              **{k: c[k] for k in keys}}), flush=True)
        print(json.dumps({"seed": seed, "case": name, "correct":
                          helper.correct({"checks": checks},
                                         config["tolerances"])}), flush=True)

    for seed in range(args.seeds):
        rng = np.random.default_rng([seed, 7])
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                   for n in lengths]
        params = helper.jitted_init(cfg, seed)
        if args.faults:
            faults(seed, engine(params), params, prompts, args, report)
            del params
            continue
        samples = [{"tokens": p, "generated": rng.integers(
            1, cfg.vocab_size, args.decode_tokens).tolist()}
            for p in prompts]

        def steps(p):
            eng = engine(p)   # dropped with its hold on `p`, cycles and all
            try:
                return helper.program_steps(eng, p, samples, args.check_len,
                                            args.decode_tokens, total)
            finally:
                del eng
                gc.collect()

        cases = {"stated": steps(params)}
        for name, how in lower.items():
            coarse = jax.jit(lambda p: jax.tree.map(
                lambda a: how(a) if a.ndim > 1 else a, p),
                donate_argnums=(0,))(params)
            del params
            cases[name] = steps(coarse)
            del coarse
            params = helper.jitted_init(cfg, seed)
        for name, progs in cases.items():
            report(seed, name, None, params, samples, progs)
        del params
    print(json.dumps({"device": str(jax.devices()[0])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
