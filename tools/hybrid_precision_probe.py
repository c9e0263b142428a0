"""What the hybrid serve cell's comparison with the plain reference reads
at the stated precision and at the precisions just below it, at the
configuration's full size, on whatever device jax finds:

  python3 tools/hybrid_precision_probe.py [--seeds 3] [--config <file>]

For each seed: weights from the seed, prompts of the cell's four lengths
with 64 teacher-forced tokens each (the program's own greedy tokens, as
the cell's samples have them, and random ones), through
BenchHybridService.reference_check itself (prefill left-padded to 1024,
then cached decode; the logits' and the recurrent state's error) with (a) the configuration as stated, (b) the
recurrent state held in bfloat16, (c) matrices rounded to float8_e4m3's 3 mantissa bits,
(d) matrices rounded to multiples of 1/8; the reference keeps the exact
weights throughout. One JSON line a seed. The cell's tolerances
(benchmarks/configs/<name>.json) are set between (a) and (c).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--weight-seeds", type=int, default=None,
                    help="seeds that also run the rounded-weight cases "
                         "(all by default)")
    ap.add_argument("--config", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "granite-4.0-h-micro.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import hybrid_model
    from benchmarks.hybrid_deployment import BenchHybridService
    from benchmarks.reference import granite_hybrid_ref

    with open(args.config) as f:
        config = json.load(f)
    cfg = hybrid_model.program_config(config, "serve", max_seq_len=4096)
    dev = jax.devices()[0]
    exact_ref = granite_hybrid_ref.logits_and_states
    lower = {
        # float8_e4m3's 3 mantissa bits with bfloat16's exponent range (what
        # a scale per tensor would give: unscaled, weights of std 0.01-0.02
        # fall under e4m3's smallest normal, 0.0156, and the logits read
        # 1.23). reduce_precision, not astype(float8).astype(bf16): the
        # chip's compiler is allowed excess precision and drops that round
        # trip (it read bit for bit what the stated precision reads; my
        # chip runs, PR 28)
        "weights_fp8": lambda w: jax.lax.reduce_precision(
            w, exponent_bits=8, mantissa_bits=3),
        "weights_eighths": lambda w: (jnp.round(w * 8) / 8).astype(w.dtype)}

    def check(cfg_, params, samples):
        stand_in = types.SimpleNamespace(
            config=config, engine=types.SimpleNamespace(cfg=cfg_,
                                                        params=params))
        out = BenchHybridService.reference_check(stand_in, samples, 1024, 64)
        return {"distinct_generated": [len(set(s_["generated"]))
                                       for s_ in samples],
                "logits_rel_rms": [c["logits_rel_rms"] for c in out],
                "state_rel_rms": [c["state_rel_rms"] for c in out],
                "state_rel_rms_by_layer": [c["state_rel_rms_by_layer"]
                                           for c in out]}, \
            all(c["finite"] for c in out)

    def greedy(cfg_, params, prompt, n_new):
        from ray_tpu.models import granite_hybrid

        step = jax.jit(lambda p, c, t: granite_hybrid.decode_step(
            p, c, t, cfg_), donate_argnums=(1,))
        cache = granite_hybrid.init_cache(cfg_, 1, max_len=1024 + n_new)
        cache["start"] = jnp.asarray([1024 - len(prompt)], jnp.int32)
        toks = np.zeros((1, 1024), np.int32)
        toks[0, 1024 - len(prompt):] = prompt
        out = []
        for _ in range(n_new):
            logits, cache = step(params, cache, jnp.asarray(toks))
            out.append(int(jnp.argmax(logits[0])))
            toks = np.asarray([[out[-1]]], np.int32)
        return out

    for seed in range(args.seeds):
        rng = np.random.default_rng([seed, 28])
        vocab = int(config["vocab_size"])
        params = hybrid_model.jitted_init(cfg, seed)
        # half the samples as the cell makes them (the program's own
        # greedy tokens, which with a tied head and random weights repeat
        # the prompt's last token), half with random continuations
        samples = []
        for n in (795, 293, 136, 50):
            prompt = rng.integers(1, vocab, size=n).tolist()
            samples.append({"tokens": prompt,
                            "generated": greedy(cfg, params, prompt, 64)})
            samples.append({"tokens": prompt, "generated": rng.integers(
                1, vocab, size=64).tolist()})
        refs = {}

        def remember(p, toks, hp, rows, state_row):   # the exact weights'
            key = (int(toks.shape[1]), int(rows[0]), int(state_row),
                   int(np.asarray(toks).sum()))
            if key not in refs:
                refs[key] = exact_ref(p, toks, hp, rows, state_row)
            return refs[key]

        granite_hybrid_ref.logits_and_states = remember
        line = {"seed": seed, "device": dev.device_kind,
                "platform": dev.platform}
        line["as_stated"], ok = check(cfg, params, samples)
        line["state_bf16"], ok2 = check(dataclasses.replace(
            cfg, state_dtype=jnp.bfloat16), params, samples)
        line["finite"] = ok and ok2
        if args.weight_seeds is not None and seed >= args.weight_seeds:
            lower_now = {}
        else:
            lower_now = lower
        for name, fn in lower_now.items():
            # rounded in place of the exact weights (both do not fit),
            # which the next case makes again from the seed
            if params is None:
                params = hybrid_model.jitted_init(cfg, seed)
            rounded = jax.jit(lambda p: jax.tree.map(
                lambda w: fn(w) if w.ndim > 2 else w, p),
                donate_argnums=(0,))(params)
            params = None
            line[name], _ = check(cfg, rounded, samples)
            del rounded
        granite_hybrid_ref.logits_and_states = exact_ref
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
