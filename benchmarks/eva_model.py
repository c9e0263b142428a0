"""What the benchmark needs from a configuration file of the EvaByte
family (a window of exact keys, chunk summaries before it, several byte
heads): the program's config, the weights from a seed in one jitted
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
import time

from benchmarks import eva_ops, model
from benchmarks.sparse_moe_model import _placements
from benchmarks.yardsticks import Yardsticks, per_shapes

PROGRAM_MODULE = "ray_tpu.models.evabyte"
# the limits `correct` holds every check to, beside `finite`
LIMITS = ("logits_rel_rms", "summary_rel_rms", "token_margin_logits",
          "token_margin_program")

# what benchmarks/readers/model.py reads for this model: a window of
# exact keys and one summary a chunk before it, in one pair of scopes
_ATTN = ["eva_window_attn", "eva_chunk_attn"]
YARDSTICKS = Yardsticks(
    flops_per_token=per_shapes(eva_ops.flops_per_token),
    attn_scopes={"decode": _ATTN, "prefill": _ATTN},
    decode_attended=(("decode_window_positions_live",
                      "decode_summaries_live"),),
    decode_attn_work=eva_ops.decode_attn_work,
    prefill_visible=(("prefill_window_keys_visible",
                      "prefill_summaries_visible"),),
    prefill_attn_flops=eva_ops.attn_flops,
    cache_read=(["decode_window_positions_read", "decode_summaries_read"],
                ["decode_window_positions_live", "decode_summaries_live"]))


def program_config(config: dict, role: str, **overrides):
    """The program's config from the published keys; `held_as[role]`
    gives the dtypes."""
    import jax.numpy as jnp

    from ray_tpu.models import evabyte

    held = config["held_as"][role]
    return evabyte.from_published(
        config, param_dtype=jnp.dtype(held["param_dtype"]),
        dtype=jnp.dtype(held["compute_dtype"]), **overrides)


def reference_hp(config: dict) -> dict:
    return {"heads": int(config["num_attention_heads"]),
            "window": int(config["window_size"]),
            "chunk": int(config["chunk_size"]),
            "pred_heads": int(config["num_pred_heads"]),
            "rope_theta": float(config["rope_theta"]),
            "norm_eps": float(config["rms_norm_eps"])}


def jitted_init(cfg, seed: int):
    """The model's own `init_params` (every matrix N(0, 1/fan_in), the
    norms' offsets 0, phi and mu N(0, 0.02**2) in float32: the
    configuration file's `departures` describe it) as one program on the
    device."""
    import jax

    from ray_tpu.models import evabyte

    return jax.jit(lambda key: evabyte.init_params(cfg, key))(
        jax.random.PRNGKey(model.fold_seed(seed)))


def correct(obs: dict, tol: dict) -> bool:
    checks = obs["checks"]
    return bool(checks) and all(
        c["finite"] and all(c[name] <= tol[name] for name in LIMITS)
        for c in checks)


@functools.lru_cache(maxsize=2)
def _steps(cfg) -> tuple:
    """The model's step with every output head, jitted for a chunk of
    one row and for a decode step of the slots."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import evabyte

    def decode(p, cache, toks):
        depth = cache["length"]
        logits, cache = evabyte.decode_step(p, cache, toks, cfg,
                                            all_heads=True)
        # a row that holds no request stays so, as in the engine's step
        cache["length"] = jnp.where(depth < 0, depth, cache["length"])
        return logits, cache

    return (jax.jit(lambda p, cache, toks: evabyte.decode_step(
        p, cache, toks, cfg, all_heads=True), donate_argnums=(1,)),
        jax.jit(decode, donate_argnums=(1,)))


def take_slots(eng, wait_s: float = 240.0) -> dict:
    """The engine's own slots, once it holds no request: a second set
    does not fit beside them (8.6 GB each at the cell's size). The
    engine is left without a cache, as before its first request, and
    builds one anew should a request still come."""
    import numpy as np

    deadline = time.monotonic() + wait_s
    while True:
        with eng._mutex:
            idle = (not any(s is not None for s in eng._slots)
                    and not eng._pending_prefills and eng._inflight is None
                    and (eng._queue is None or eng._queue.empty()))
            if idle:
                eng._ensure_decode_cache()
                cache, eng._decode_cache = eng._decode_cache, None
                eng._row_live = [False] * eng.max_batch
                break
        if time.monotonic() > deadline:
            raise RuntimeError("the engine still holds requests: its "
                               "slots cannot be taken for the check")
        time.sleep(0.2)
    cache["length"] = eng._retire(cache["length"],
                                  np.ones((eng.max_batch,), bool))
    return cache


def program_steps(eng, params, samples: list, check_len: int,
                  decode_tokens: int, cache: dict) -> list:
    """The timed path's own steps for the samples, every output head
    kept, at the engine's own sizes. Each prompt is left-padded to
    `check_len` and prefilled alone in chunks of the engine's
    `prefill_chunk`, in a cache made for the bucket (the all-padding
    chunks skipped, as the engine skips them). Its row is grafted by the
    engine's own `insert_row` into `cache`, the engine's `max_batch`
    slots with per-row depths in which no other row holds a request. The
    rows join one step after another (`_placements`), copies of the
    samples among them so that several rows are live at depths of their
    own, and each is teacher-forced through `decode_tokens` - 1 decode
    steps of the whole batch and then retired. For each sample:
    {"logits" [k, heads, vocab], "k_sum", "v_sum" [chunks, H, hd]: layer
    0's summaries of the whole chunks the steps saw, out of the slot}.
    `cache` is used up."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import evabyte

    cfg, slots = eng.cfg, eng.max_batch
    chunk = eng.prefill_chunk or check_len
    W, c = cfg.window_size, cfg.chunk_size
    chunk_fn, decode_fn = _steps(cfg)
    recs, grafts = [], []
    for s in samples:
        prompt, gen = list(s["tokens"]), list(s["generated"])
        n, k = len(prompt), min(decode_tokens, len(gen))
        start = check_len - n
        small = evabyte.init_cache(cfg, 1, max_len=check_len)
        small["start"] = jnp.asarray([start], jnp.int32)
        pos = (start // chunk) * chunk
        small["length"] = jnp.int32(pos)
        padded = np.zeros((1, check_len), np.int32)
        padded[0, start:] = prompt
        while pos < check_len:
            step = min(chunk, check_len - pos)
            logits, small = chunk_fn(
                params, small, jnp.asarray(padded[:, pos:pos + step]))
            pos += step
        recs.append({"logits": [np.asarray(logits[0], np.float32)],
                     "start": start, "k": k, "gen": gen,
                     "chunks": (n + k - 1) // c})
        grafts.append(eng._row(small))
        del small

    def summaries(rec, k_row, v_row):
        """Layer 0's summaries of the whole chunks the steps saw, out of
        a row's leaves [H, hd, n] and [H, n, hd]."""
        m = rec["chunks"]
        rec["k_sum"] = np.asarray(k_row[:, :, W:W + m],
                                  np.float32).transpose(2, 0, 1)
        rec["v_sum"] = np.asarray(v_row[:, W:W + m],
                                  np.float32).transpose(1, 0, 2)

    for rec, row in zip(recs, grafts):
        if rec["k"] <= 1:        # never grafted: the prefill's own row
            summaries(rec, row["k"][0, 0], row["v"][0, 0])
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
        logits, cache = decode_fn(params, cache, jnp.asarray(toks))
        gone = np.zeros((slots,), bool)
        for i, (j, slot) in enumerate(place):
            if slot not in live:
                continue
            if i < len(samples):             # the sample itself, no copy
                recs[j]["logits"].append(
                    np.asarray(logits[slot], np.float32))
            live[slot] += 1
            if live[slot] >= recs[j]["k"] - 1:
                gone[slot] = True
                del live[slot]
                if i < len(samples):
                    # now: a step writes into a row that holds no
                    # request too (its own position 0), as the engine's
                    summaries(recs[j], cache["k"][0, slot],
                              cache["v"][0, slot])
        if gone.any():
            cache["length"] = eng._retire(cache["length"], gone)
    return [{"logits": np.stack(rec["logits"]), "k_sum": rec["k_sum"],
             "v_sum": rec["v_sum"]} for rec in recs]


def compare(params, hp: dict, samples: list, progs: list) -> list:
    """Each sample's `program_steps` against the plain reference's full
    forward over prompt + generated.

    logits_rel_rms        all the output heads' logits, the bucket's
                          prefill and the cached decode steps, against
                          the reference's at the same positions
    summary_rel_rms       layer 0's cached summary keys and values, out
                          of the slot, against the reference's (the
                          larger of the two): what the logits would not
                          show, summaries held coarser or cut a byte off
    token_margin_logits   every streamed token's distance under the
                          reference's best logit of head 0
    token_margin_program  and, of the first k, under the best of the
                          program's own head-0 logits at that step: what
                          ties the stream the window timed to the steps
                          the other limits hold
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import evabyte_ref

    # one head at a time: a head's scores over 11,008 positions are
    # 0.5 GB in float32, beside the weights and one layer in float32
    ref_fn = jax.jit(lambda p, toks, rows: evabyte_ref.logits_and_summaries(
        p, toks, hp, rows, head_block=1))
    rel = lambda a, b: float(np.sqrt(((a - b) ** 2).mean())
                             / np.sqrt((b ** 2).mean()))
    out = []
    for s, prog in zip(samples, progs):
        prompt, gen = list(s["tokens"]), list(s["generated"])
        n, g = len(prompt), len(gen)
        total = -(-(n + g) // 128) * 128
        toks = np.zeros((total,), np.int32)
        toks[:n + g] = prompt + gen
        rows = jnp.asarray(np.arange(n - 1, n + g - 1, dtype=np.int32))
        ref, (k_sum, v_sum) = ref_fn(params, jnp.asarray(toks), rows)
        ref = np.asarray(ref, np.float32)               # [g, heads, vocab]
        logits = prog["logits"]
        k, m = len(logits), len(prog["k_sum"])
        got = np.asarray(gen)
        margin = ref[:, 0].max(-1) - ref[np.arange(g), 0, got]
        own = logits[:, 0].max(-1) - logits[np.arange(k), 0, got[:k]]
        k_sum = np.asarray(k_sum[:m], np.float32)
        v_sum = np.asarray(v_sum[:m], np.float32)
        out.append({
            "prompt_len": n, "generated": g, "chunks": m,
            "logits_rel_rms": rel(logits, ref[:k]),
            "logits_rel_rms_head0": rel(logits[:, 0], ref[:k, 0]),
            "summary_rel_rms": max(rel(prog["k_sum"], k_sum),
                                   rel(prog["v_sum"], v_sum)),
            "summary_key_rel_rms": rel(prog["k_sum"], k_sum),
            "logits_max_abs_err": float(np.abs(logits - ref[:k]).max()),
            "logit_std": float(ref.std()),
            "token_margin_logits": float(margin.max()),
            "tokens_not_argmax": int((ref[:, 0].argmax(-1) != got).sum()),
            "token_margin_program": float(own.max()),
            "tokens_not_program_argmax": int((own > 0).sum()),
            "finite": bool(np.isfinite(logits).all()
                           and np.isfinite(ref).all()
                           and np.isfinite(prog["k_sum"]).all()
                           and np.isfinite(prog["v_sum"]).all())})
    return out


def reference_check(service, samples: list, check_len: int,
                    decode_tokens: int) -> list:
    """Hold finished greedy requests against the plain reference, with
    the replica's own parameters: `program_steps` at the engine's own
    chunk and in its own slots, taken once the engine is idle and
    dropped before the reference runs, then `compare`."""
    eng = service.engine
    progs = program_steps(eng, eng.params, samples, check_len,
                          decode_tokens, take_slots(eng))
    return compare(eng.params, reference_hp(service.config), samples, progs)
