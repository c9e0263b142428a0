"""What the benchmark needs from a configuration file of the `laguna`
family (grouped-query attention of two kinds, full-depth K/V and a ring,
under a per-head gate; many small routed experts): the program's
config, the weights from a seed in one jitted
program, the plain reference's hyper-parameters, and the comparison that
decides `correct`. A configuration file names this module under its
"model" key; benchmarks/model_cell.py and model_deployment.py import
what it names and bind no model themselves.

Nothing of the program is imported at module level: a program from
before the model existed must be able to import this file and be told,
at once, that it cannot run the cell (`PROGRAM_MODULE`).
"""

from __future__ import annotations

import functools

from benchmarks import gqa_moe_ops, model
from benchmarks.eva_model import take_slots
from benchmarks.sparse_moe_model import _margin, _placements
from benchmarks.yardsticks import Yardsticks, per_shapes

PROGRAM_MODULE = "ray_tpu.models.laguna"
# the limits `correct` holds every check to, beside `finite`
LIMITS = ("logits_rel_rms_forced", "logits_rel_rms_forced_step",
          "router_margin", "logits_rel_rms", "token_margin_logits",
          "token_margin_program")

# what benchmarks/readers/model.py reads for this model: grouped-query
# attention of two kinds, each counted apart (another count of heads)
_ATTN = ["full_attn", "window_attn"]
YARDSTICKS = Yardsticks(
    flops_per_token=per_shapes(gqa_moe_ops.flops_per_token),
    attn_scopes={"decode": _ATTN, "prefill": _ATTN},
    decode_attended=(("decode_full_positions_attended",),
                     ("decode_window_positions_attended",)),
    decode_attn_work=gqa_moe_ops.decode_attn_work,
    prefill_visible=(("prefill_full_keys_visible",),
                     ("prefill_window_keys_visible",)),
    prefill_attn_flops=gqa_moe_ops.attn_flops,
    cache_read=(["decode_full_positions_read",
                 "decode_window_positions_read"],
                ["decode_full_positions_attended",
                 "decode_window_positions_attended"]))


def program_config(config: dict, role: str, **overrides):
    """The program's config from the published keys (and `experts_first`
    / `router_experts`, the share of the experts this deployment holds);
    `held_as[role]` gives the dtypes."""
    import jax.numpy as jnp

    from ray_tpu.models import laguna

    held = config["held_as"][role]
    return laguna.from_published(
        config, param_dtype=jnp.dtype(held["param_dtype"]),
        dtype=jnp.dtype(held["compute_dtype"]), **overrides)


def reference_hp(config: dict) -> dict:
    return {"kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "layer_types": list(config["layer_types"]),
            "rope_parameters": config["rope_parameters"],
            "sliding_window": int(config["sliding_window"]),
            "experts_per_tok": int(config["num_experts_per_tok"]),
            "norm_topk_prob": bool(config["norm_topk_prob"]),
            "routed_scaling": float(config["moe_routed_scaling_factor"]),
            "experts_first": int(config.get("experts_first", 0)),
            "norm_eps": float(config["rms_norm_eps"])}


def jitted_init(cfg, seed: int):
    """The model's own `init_params` (every matrix N(0, 1/fan_in), norms
    one, the router's selection bias N(0, 0.02**2): the configuration
    file's `departures` describe it) as one program on the device."""
    import jax

    from ray_tpu.models import laguna

    return jax.jit(lambda key: laguna.init_params(cfg, key))(
        jax.random.PRNGKey(model.fold_seed(seed)))


def correct(obs: dict, tol: dict) -> bool:
    checks = obs["checks"]
    return bool(checks) and all(
        c["finite"] and all(c[name] <= tol[name] for name in LIMITS)
        for c in checks)


@functools.lru_cache(maxsize=2)
def _collect_steps(cfg) -> tuple:
    """The model's step with `collect`, jitted for a chunk of one row
    and for a decode step of the slots."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import laguna

    def decode(p, cache, toks):
        depth = cache["length"]
        logits, cache, seen = laguna.decode_step(p, cache, toks, cfg,
                                                  collect=True)
        # a row that holds no request stays so, as in the engine's step
        cache["length"] = jnp.where(depth < 0, depth, cache["length"])
        return logits, cache, seen["chosen"]

    def chunk(p, cache, toks):
        logits, cache, seen = laguna.decode_step(p, cache, toks, cfg,
                                                  collect=True)
        return logits, cache, seen["chosen"]

    return (jax.jit(chunk, donate_argnums=(1,)),
            jax.jit(decode, donate_argnums=(1,)))


def program_steps(eng, params, samples: list, check_len: int,
                  decode_tokens: int, total: int, cache: dict) -> list:
    """The timed path's own steps for the samples, with `collect`, at
    the engine's own sizes. Each prompt is left-padded to `check_len`
    and prefilled alone in chunks of the engine's `prefill_chunk`, in a
    cache as deep as the bucket (the all-padding chunks skipped, as the
    engine skips them). Its row is grafted by the engine's own
    `insert_row` into `cache`, the engine's `max_batch` slots with
    per-row depths in which no other row holds a request. The rows join
    one step after another (`_placements`), copies of the samples among
    them so that several rows are live at depths of their own, and each
    is teacher-forced through `decode_tokens` - 1 decode steps of the
    whole batch and then retired. For each sample: {"logits" [k, vocab],
    "chosen": [expert layers] of [total, k_experts] by the sequence's
    own positions, "rows": the n + k - 1 positions the steps saw}.
    `cache` is used up."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import laguna

    cfg, slots = eng.cfg, eng.max_batch
    chunk = eng.prefill_chunk or check_len
    chunk_fn, decode_fn = _collect_steps(cfg)

    def keep(rec, chosen, row, first, count):
        """Cache rows [first, first + count) of the batch's `row` -> the
        sequence's own."""
        lo = max(first, rec["start"])
        for i, layer in enumerate(chosen):
            rec["chosen"][i][lo - rec["start"]:first + count - rec["start"]] \
                = np.asarray(layer[row])[lo - first:]

    recs, grafts = [], []
    for s in samples:
        prompt, gen = list(s["tokens"]), list(s["generated"])
        n, k = len(prompt), min(decode_tokens, len(gen))
        start = check_len - n
        rec = {"logits": [], "rows": n + k - 1, "start": start, "k": k,
               "gen": gen,
               "chosen": [np.zeros((total, cfg.experts_per_tok), np.int32)
                          for _ in cfg.moe_layers]}
        small = laguna.init_cache(cfg, 1, max_len=check_len)
        small["start"] = jnp.asarray([start], jnp.int32)
        pos = (start // chunk) * chunk
        small["length"] = jnp.int32(pos)
        padded = np.zeros((1, check_len), np.int32)
        padded[0, start:] = prompt
        while pos < check_len:
            step = min(chunk, check_len - pos)
            logits, small, chosen = chunk_fn(
                params, small, jnp.asarray(padded[:, pos:pos + step]))
            keep(rec, chosen, 0, pos, step)
            pos += step
        rec["logits"].append(np.asarray(logits[0], np.float32))
        recs.append(rec)
        grafts.append(eng._row(small))
        del small

    place = _placements(slots, len(samples))
    steps = max(rec["k"] for rec in recs) - 1
    live = {}          # slot -> steps its row has made
    for t in range(steps + len(place) - 1):
        if t < len(place) and recs[place[t][0]]["k"] > 1:
            j, slot = place[t]
            cache = eng._insert_row(
                cache, grafts[j], jnp.int32(slot), jnp.int32(check_len),
                jnp.int32(recs[j]["start"]))
            live[slot] = 0
        if not live:
            continue
        toks = np.zeros((slots, 1), np.int32)
        for j, slot in place:
            if slot in live:
                toks[slot, 0] = recs[j]["gen"][live[slot]]
        logits, cache, chosen = decode_fn(params, cache, jnp.asarray(toks))
        gone = np.zeros((slots,), bool)
        for i, (j, slot) in enumerate(place):
            if slot not in live:
                continue
            if i < len(samples):             # the sample itself, no copy
                keep(recs[j], chosen, slot, check_len + live[slot], 1)
                recs[j]["logits"].append(
                    np.asarray(logits[slot], np.float32))
            live[slot] += 1
            if live[slot] >= recs[j]["k"] - 1:
                gone[slot] = True
                del live[slot]
        if gone.any():
            cache["length"] = eng._retire(cache["length"], gone)
    return [{"logits": np.stack(rec["logits"]), "chosen": rec["chosen"],
             "rows": rec["rows"]} for rec in recs]


def compare(cfg, params, hp: dict, samples: list, progs: list,
            total: int) -> list:
    """Each sample's `program_steps` against the plain reference's full
    forward over prompt + generated, run twice: on its own choice of
    experts, and FORCED to the program's.

    logits_rel_rms_forced   program against the forced reference: the
                            arithmetic, given the choices (the tight one)
    logits_rel_rms_forced_step  the same of each of the check's steps
                            alone, the largest: a fault that grows with
                            the steps (a ring whose rows lie where the
                            next writes do not expect them loses one
                            window key a step) shows at the last steps
                            before it shows in the mean
    router_margin           how far under the reference's 10th biased
                            score a member lies that only the program
                            chose (sparse_moe_model._margin), against
                            the forced reference's scores
    logits_rel_rms          program against the free-running reference
    token_margin_logits     every streamed token's distance under the
                            free-running reference's best logit
    token_margin_program    and, of the first k, under the best of the
                            program's own logits at that step: what ties
                            the stream the window timed to the steps the
                            other limits hold
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import laguna_ref

    ref_fn = jax.jit(lambda p, toks, rows, cho:
                     laguna_ref.logits_and_choices(
                         p, toks, hp, rows, chosen=cho))
    rel = lambda a, b: float(np.sqrt(((a - b) ** 2).mean())
                             / np.sqrt((b ** 2).mean()))
    rel_steps = lambda a, b: float((np.sqrt(((a - b) ** 2).mean(-1))
                                    / np.sqrt((b ** 2).mean(-1))).max())
    out = []
    for s, prog in zip(samples, progs):
        prompt, gen = list(s["tokens"]), list(s["generated"])
        n, g = len(prompt), len(gen)
        seen_rows, logits = prog["rows"], prog["logits"]
        k = len(logits)
        toks = np.zeros((1, total), np.int32)
        toks[0, :n + g] = prompt + gen
        rows = jnp.asarray(np.arange(n - 1, n + g - 1, dtype=np.int32))
        free, own = ref_fn(params, jnp.asarray(toks), rows, None)
        free = np.asarray(free, np.float32)
        got = np.asarray(gen)
        margin = free.max(-1) - free[np.arange(g), got]
        own_margin = logits.max(-1) - logits[np.arange(k), got[:k]]
        # forced to the program's choices where its steps made any
        rest = np.arange(total)[:, None] >= seen_rows
        forced, theirs = ref_fn(
            params, jnp.asarray(toks), rows,
            [jnp.asarray(np.where(rest, np.asarray(o), c))
             for o, c in zip(own["chosen"], prog["chosen"])])
        del own
        forced = np.asarray(forced, np.float32)
        exp_margin, exp_swaps = 0.0, 0.0
        for i, mine in enumerate(prog["chosen"]):
            biased = (np.asarray(theirs["router_scores"][i], np.float32)
                      + np.asarray(params["layers"][cfg.moe_layers[i]]
                                   ["router_bias"], np.float32))[:seen_rows]
            took = np.zeros(biased.shape, bool)
            np.put_along_axis(took, mine[:seen_rows], True, axis=1)
            worst, share = _margin(biased, took, cfg.experts_per_tok)
            exp_margin, exp_swaps = max(exp_margin, worst), max(exp_swaps,
                                                                share)
        out.append({
            "prompt_len": n, "generated": g,
            "logits_rel_rms_forced": rel(logits, forced[:k]),
            "logits_rel_rms_forced_step": rel_steps(logits, forced[:k]),
            "logits_rel_rms": rel(logits, free[:k]),
            "router_margin": exp_margin,
            "router_swap_share": exp_swaps,
            "logits_max_abs_err": float(np.abs(logits - forced[:k]).max()),
            "logit_std": float(free.std()),
            "token_margin_logits": float(margin.max()),
            "tokens_not_argmax": int((free.argmax(-1) != got).sum()),
            "token_margin_program": float(own_margin.max()),
            "tokens_not_program_argmax": int((own_margin > 0).sum()),
            "finite": bool(np.isfinite(logits).all()
                           and np.isfinite(forced).all()
                           and np.isfinite(free).all())})
    return out


def engine_counters(eng) -> dict:
    """What `LLMEngine.stats()` has counted since the process began
    (warm-up, lead-in, window and drain): every counter of the model's
    hooks, and the ratios the cell is sized by."""
    stats = eng.stats()
    out = {k: v for k, v in stats.items()
           if isinstance(v, int) and k.startswith((
               "batches", "prefill", "decode_", "moe_", "generated"))}
    out["cache_bytes"] = stats["cache_bytes"]
    ratio = lambda a, b: stats[a] / stats[b] if stats.get(b) else None
    out["decode_rounds_per_chunk"] = ratio("batches", "prefill_chunks")
    out["decode_full_read_over_attended"] = ratio(
        "decode_full_positions_read", "decode_full_positions_attended")
    out["prefill_full_visited_over_visible"] = ratio(
        "prefill_full_keys_visited", "prefill_full_keys_visible")
    out["prefill_window_visited_over_visible"] = ratio(
        "prefill_window_keys_visited", "prefill_window_keys_visible")
    out["experts_hit_per_decode_step"] = ratio("moe_experts_hit", "batches")
    return out


def reference_check(service, samples: list, check_len: int,
                    decode_tokens: int) -> list:
    """Hold finished greedy requests against the plain reference, with
    the replica's own parameters: `program_steps` at the engine's own
    chunk and in its own slots, taken once the engine is idle (a second
    set of 6.6 GB does not fit beside them) and dropped before the
    reference runs, then `compare`. The program's choice of experts
    comes out of the same model function that serves, as an auxiliary
    output."""
    eng = service.engine
    counters = engine_counters(eng)
    total = -(-max(len(s["tokens"]) + len(s["generated"])
                   for s in samples) // 128) * 128
    progs = program_steps(eng, eng.params, samples, check_len,
                          decode_tokens, total, take_slots(eng))
    checks = compare(eng.cfg, eng.params, reference_hp(service.config),
                     samples, progs, total)
    # what the engine counted since the process began (warm-up and
    # lead-in too) rides on the first check; the window's own difference
    # is serve_cell.stats_in_window's, on the info line
    checks[0]["engine_since_start"] = counters
    return checks
