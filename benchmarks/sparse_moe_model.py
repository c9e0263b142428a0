"""What the benchmark needs from a configuration file of the
`dots3_note` family (latent attention, a sparse selection, routed
experts): the program's config, the weights from a seed in one jitted
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

from benchmarks import model, sparse_moe_ops
from benchmarks.yardsticks import Yardsticks, per_shapes

PROGRAM_MODULE = "ray_tpu.models.dots3_note"
# the limits `correct` holds every check to, beside `finite`
LIMITS = ("logits_rel_rms_forced", "index_score_rel_rms", "selection_margin",
          "router_margin", "logits_rel_rms", "token_margin_logits",
          "token_margin_program")

# what benchmarks/readers/model.py reads for this model: the whole
# step's operations. Its attention's rooflines are its own entries
# (readers/sparse_moe.py: the indexer and the chosen rows, each apart)
_ATTN = ["sparse_attn", "window_attn"]
YARDSTICKS = Yardsticks(
    flops_per_token=per_shapes(sparse_moe_ops.flops_per_token),
    attn_scopes={"decode": _ATTN, "prefill": _ATTN})


def program_config(config: dict, role: str, **overrides):
    """The program's config from the published keys (and `experts_first`
    / `experts_held`, the share of the experts this deployment holds);
    `held_as[role]` gives the dtypes."""
    import jax.numpy as jnp

    from ray_tpu.models import dots3_note

    held = config["held_as"][role]
    if "ring_multiple" in config:        # a twin's ring may be shorter
        overrides.setdefault("ring_multiple", int(config["ring_multiple"]))
    return dots3_note.from_published(
        config, param_dtype=jnp.dtype(held["param_dtype"]),
        dtype=jnp.dtype(held["compute_dtype"]), **overrides)


def reference_hp(config: dict) -> dict:
    side = lambda p: {
        "heads": int(config[p + "num_attention_heads"]),
        "nope": int(config[p + "qk_nope_head_dim"]),
        "rope": int(config[p + "qk_rope_head_dim"]),
        "v": int(config[p + "v_head_dim"]),
        "q_rank": int(config[p + "q_lora_rank"]),
        "kv_rank": int(config[p + "kv_lora_rank"]),
        "theta": float(config[p + "rope_theta"])}
    return {"layer_types": tuple(config["layer_types"]),
            "full": side(""), "sliding": side("swa_"),
            "sliding_window": int(config["sliding_window_size"]),
            "index_heads": int(config["index_n_heads"]),
            "index_head_dim": int(config["index_head_dim"]),
            "index_topk": int(config["index_topk"]),
            "lora_rescale": bool(config["apply_mla_qkv_lora_rescale"]),
            "experts_per_tok": int(config["num_experts_per_tok"]),
            "norm_topk_prob": bool(config["norm_topk_prob"]),
            "routed_scaling": float(config["routed_scaling_factor"]),
            "experts_first": int(config.get("experts_first", 0)),
            "norm_eps": float(config["rms_norm_eps"])}


def jitted_init(cfg, seed: int):
    """The model's own `init_params` (every matrix N(0, 1/fan_in), norms
    one, the router's selection bias N(0, 0.02**2): the configuration
    file's `departures` describe it) as one program on the device."""
    import jax

    from ray_tpu.models import dots3_note

    return jax.jit(lambda key: dots3_note.init_params(cfg, key))(
        jax.random.PRNGKey(model.fold_seed(seed)))


def correct(obs: dict, tol: dict) -> bool:
    checks = obs["checks"]
    return bool(checks) and all(
        c["finite"] and all(c[name] <= tol[name] for name in LIMITS)
        for c in checks)


def _margin(scores, picked, k: int) -> tuple:
    """For rows of `scores` [rows, n] (-inf where a position cannot be
    chosen) and the program's choice `picked` [rows, n] bool: how far
    below the reference's k-th largest score the lowest member lies that
    the program chose and the reference did not, in units of the row's
    spread (0 where the sets agree), the largest over the rows; and the
    share of chosen members that are such swaps."""
    import numpy as np

    worst, swaps, members = 0.0, 0, 0
    for row, took in zip(scores, picked):
        ok = np.isfinite(row)
        members += int(took.sum())
        if ok.sum() <= k:
            swaps += int((took & ~ok).sum())
            continue
        kth = np.partition(row[ok], -k)[-k]
        out = took & (row < kth)
        if out.any():
            swaps += int(out.sum())
            worst = max(worst, float((kth - row[out].min())
                                     / max(row[ok].std(), 1e-30)))
    return worst, swaps / max(members, 1)


def _placements(slots: int, samples: int) -> list:
    """Where the check's rows lie among the `slots`: [(sample, slot)],
    the samples themselves first and then copies of them, up to 8 rows
    spread over the slots. Row i joins at step i, so that every live row
    has a depth of its own, as the engine's rows do."""
    live = max(samples, min(8, slots) // samples * samples)
    stride = slots // live
    return [(i % samples, i * stride + stride // 2) for i in range(live)]


@functools.lru_cache(maxsize=2)
def _collect_steps(cfg) -> tuple:
    """The model's step with `collect`, jitted for a chunk of one row
    and for a decode step of the slots."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import dots3_note

    def decode(p, cache, toks):
        depth = cache["length"]
        logits, cache, seen = dots3_note.decode_step(p, cache, toks, cfg,
                                                     collect=True)
        # a row that holds no request stays so, as in the engine's step
        cache["length"] = jnp.where(depth < 0, depth, cache["length"])
        return logits, cache, seen

    return (jax.jit(lambda p, cache, toks: dots3_note.decode_step(
        p, cache, toks, cfg, collect=True), donate_argnums=(1,)),
        jax.jit(decode, donate_argnums=(1,)))


def program_steps(eng, params, samples: list, check_len: int,
                  decode_tokens: int, total: int) -> list:
    """The timed path's own steps for the samples, with `collect`, at
    the engine's own sizes. Each prompt is left-padded to `check_len`
    and prefilled alone in chunks of the engine's `prefill_chunk`, in a
    cache as deep as the bucket (the all-padding chunks skipped, as the
    engine skips them). Its row is grafted by the engine's own
    `insert_row` into a cache of the engine's `max_batch` slots with
    per-row depths, in which no other row holds a request (the engine's
    `retire`). The rows join one step after another (`_placements`),
    copies of the samples among them so that several rows are live, and
    each is teacher-forced through `decode_tokens` - 1 decode steps of
    the whole batch and then retired. For each sample: {"logits" [k,
    vocab], "selected": [full layers] of [total, total] bool,
    "index_scores": of [rows, total] float32, "chosen": [expert layers]
    of [total, k_experts]}, rows and columns by the sequence's own
    positions (position p of the cache is p - start), filled for the
    `rows` = n + k - 1 positions the steps saw."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import dots3_note

    cfg, slots = eng.cfg, eng.max_batch
    chunk = eng.prefill_chunk or check_len
    n_full = cfg.count("full_attention")
    chunk_fn, decode_fn = _collect_steps(cfg)

    def keep(rec, seen, row, first, count):
        """Cache rows [first, first + count) of the batch's `row` -> the
        sequence's own."""
        start = rec["start"]
        lo = max(first, start)
        r0, r1 = lo - start, first + count - start
        for name, n_layers, cols in (("selected", n_full, total),
                                     ("index_scores", n_full, total),
                                     ("chosen", cfg.n_moe_layers, None)):
            for i in range(n_layers):
                mine = np.asarray(seen[name][i][row])[lo - first:]
                if cols is None:
                    rec[name][i][r0:r1] = mine
                else:
                    mine = mine[:, start:start + cols]
                    rec[name][i][r0:r1, :mine.shape[1]] = mine

    recs, grafts = [], []
    for s in samples:
        prompt, gen = list(s["tokens"]), list(s["generated"])
        n, k = len(prompt), min(decode_tokens, len(gen))
        rows, start = n + k - 1, check_len - n
        rec = {"logits": [], "rows": rows, "start": start, "k": k, "gen": gen,
               "selected": [np.zeros((total, total), bool)
                            for _ in range(n_full)],
               "index_scores": [np.full((rows, total), -np.inf, np.float32)
                                for _ in range(n_full)],
               "chosen": [np.zeros((total, cfg.experts_per_tok), np.int32)
                          for _ in range(cfg.n_moe_layers)]}
        small = dots3_note.init_cache(cfg, 1, max_len=check_len)
        small["start"] = jnp.asarray([start], jnp.int32)
        pos = (start // chunk) * chunk
        small["length"] = jnp.int32(pos)
        padded = np.zeros((1, check_len), np.int32)
        padded[0, start:] = prompt
        while pos < check_len:
            step = min(chunk, check_len - pos)
            logits, small, seen = chunk_fn(
                params, small, jnp.asarray(padded[:, pos:pos + step]))
            keep(rec, seen, 0, pos, step)
            pos += step
        rec["logits"].append(np.asarray(logits[0], np.float32))
        recs.append(rec)
        grafts.append(eng._row(small))
        del small

    place = _placements(slots, len(samples))
    steps = max(rec["k"] for rec in recs) - 1
    cache = dots3_note.init_cache(
        cfg, slots, max_len=-(-(check_len + steps) // 128) * 128)
    cache["length"] = jnp.full((slots,), -1, jnp.int32)
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
        logits, cache, seen = decode_fn(params, cache, jnp.asarray(toks))
        gone = np.zeros((slots,), bool)
        for i, (j, slot) in enumerate(place):
            if slot not in live:
                continue
            if i < len(samples):             # the sample itself, no copy
                keep(recs[j], seen, slot, check_len + live[slot], 1)
                recs[j]["logits"].append(
                    np.asarray(logits[slot], np.float32))
            live[slot] += 1
            if live[slot] >= recs[j]["k"] - 1:
                gone[slot] = True
                del live[slot]
        if gone.any():
            cache["length"] = eng._retire(cache["length"], gone)
    return [{"logits": np.stack(rec["logits"]), "selected": rec["selected"],
             "index_scores": rec["index_scores"], "chosen": rec["chosen"],
             "rows": rec["rows"]} for rec in recs]


def compare(cfg, params, hp: dict, samples: list, progs: list,
            total: int) -> list:
    """Each sample's `program_steps` against the plain reference's full
    forward over prompt + generated, run twice: on its own choices, and
    FORCED to the program's.

    logits_rel_rms_forced   program against the forced reference: the
                            arithmetic, given the choices (the tight one)
    index_score_rel_rms     the program's index scores against the forced
                            reference's (same inputs to every layer),
                            the larger of the full layers'
    selection_margin        `_margin` of the program's attended sets
    router_margin           and of its chosen experts, on the biased
                            scores; both against the forced reference
    logits_rel_rms          program against the free-running reference
    token_margin_logits     every streamed token's distance under the
                            free-running reference's best logit
    token_margin_program    and, of the first k, under the best of the
                            program's own logits at that step: what ties
                            the stream the window timed to the steps the
                            other limits hold (both bf16, the same
                            choices but for a tie: the tight one of the
                            two)
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import dots3_note_ref

    k_sel, k_exp = cfg.index_topk, cfg.experts_per_tok
    # one head at a time: the reference's temporaries are 1.8 GB at 6,656
    # positions so (2.5 GB at two), beside an engine that fills the chip
    ref_fn = jax.jit(lambda p, toks, rows, sel, cho:
                     dots3_note_ref.logits_and_choices(
                         p, toks, hp, rows, selected=sel, chosen=cho,
                         head_block=1))
    rel = lambda a, b: float(np.sqrt(((a - b) ** 2).mean())
                             / np.sqrt((b ** 2).mean()))
    out = []
    for s, prog in zip(samples, progs):
        prompt, gen = list(s["tokens"]), list(s["generated"])
        n, g = len(prompt), len(gen)
        seen_rows, logits = prog["rows"], prog["logits"]
        k = len(logits)
        toks = np.zeros((1, total), np.int32)
        toks[0, :n + g] = prompt + gen
        rows = jnp.asarray(np.arange(n - 1, n + g - 1, dtype=np.int32))
        free, own = ref_fn(params, jnp.asarray(toks), rows, None, None)
        free = np.asarray(free, np.float32)
        own = {key: own[key] for key in ("selected", "chosen")}
        got = np.asarray(gen)
        margin = free.max(-1) - free[np.arange(g), got]
        own_margin = logits.max(-1) - logits[np.arange(k), got[:k]]
        # forced to the program's choices where its steps made any
        rest = np.arange(total)[:, None] >= seen_rows
        forced, theirs = ref_fn(
            params, jnp.asarray(toks), rows,
            [jnp.asarray(np.where(rest, np.asarray(o), m))
             for o, m in zip(own["selected"], prog["selected"])],
            [jnp.asarray(np.where(rest, np.asarray(o), c))
             for o, c in zip(own["chosen"], prog["chosen"])])
        del own
        forced = np.asarray(forced, np.float32)

        score_err, sel_margin, sel_swaps = 0.0, 0.0, 0.0
        for i, mine in enumerate(prog["index_scores"]):
            ref_scores = np.asarray(theirs["index_scores"][i],
                                    np.float32)[:seen_rows]
            both = np.isfinite(ref_scores) & np.isfinite(mine)
            score_err = max(score_err, rel(mine[both], ref_scores[both]))
            worst, share = _margin(ref_scores,
                                   prog["selected"][i][:seen_rows], k_sel)
            sel_margin, sel_swaps = max(sel_margin, worst), max(sel_swaps,
                                                                share)
        exp_margin, exp_swaps = 0.0, 0.0
        for i, mine in enumerate(prog["chosen"]):
            biased = (np.asarray(theirs["router_scores"][i], np.float32)
                      + np.asarray(params["layers"][cfg.first_k_dense + i]
                                   ["router_bias"], np.float32))[:seen_rows]
            took = np.zeros(biased.shape, bool)
            np.put_along_axis(took, mine[:seen_rows], True, axis=1)
            worst, share = _margin(biased, took, k_exp)
            exp_margin, exp_swaps = max(exp_margin, worst), max(exp_swaps,
                                                                share)
        out.append({
            "prompt_len": n, "generated": g,
            "logits_rel_rms_forced": rel(logits, forced[:k]),
            "logits_rel_rms": rel(logits, free[:k]),
            "index_score_rel_rms": score_err,
            "selection_margin": sel_margin,
            "selection_swap_share": sel_swaps,
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


def reference_check(service, samples: list, check_len: int,
                    decode_tokens: int) -> list:
    """Hold finished greedy requests against the plain reference, with
    the replica's own parameters: `program_steps` at the engine's own
    chunk and slots, then `compare`. The program's choices come out of
    the same jitted functions that serve, as an auxiliary output."""
    eng = service.engine
    total = -(-max(len(s["tokens"]) + len(s["generated"])
                   for s in samples) // 128) * 128
    progs = program_steps(eng, eng.params, samples, check_len,
                          decode_tokens, total)
    return compare(eng.cfg, eng.params, reference_hp(service.config),
                   samples, progs, total)
