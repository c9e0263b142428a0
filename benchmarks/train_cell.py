"""A train cell: JaxTrainer(lora_finetune_loop) on the configuration's
published sizes, measured from inside the one worker that holds the
chips.

The recipe runs a fixed number of steps and offers two hooks, both used
here from the benchmark's own files: `init_params_fn` (the base weights,
made in one jitted program on the mesh) and `batch_fn` (called at the
top of every step: it stamps the host clock, makes the batch in one
jitted program, starts and stops the profiler, and ends the run when the
window has passed by raising `_WindowClosed`, which the wrapper
catches). After the last step, outside set-up and window, the program's
loss and adapter gradients are held against the plain reference.
"""

from __future__ import annotations

import gc
import os
import time

from benchmarks.manifest import Cell


class _WindowClosed(Exception):
    pass


def _reference_check(cfg, lcfg, base, mesh, config: dict, tr: dict,
                     seed: int) -> dict:
    """Loss and adapter gradients of one fixed batch: the program's
    loss_fn (bf16 compute, flash attention, remat: as the step runs it)
    against the plain reference in float32, both on `mesh` with the same
    base weights and the same seeded adapters (B is not zero here, or
    every gradient of A would be)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama, lora
    from ray_tpu.parallel.mesh import shard_params

    from benchmarks import model
    from benchmarks.reference import llama_ref

    chk = tr["check"]
    b, s = int(chk["batch"]), int(chk["seq_len"])
    key = jax.random.PRNGKey(model.fold_seed(seed) ^ 0x5EED)
    k_tok, k_a, k_b = jax.random.split(key, 3)
    shardings = shard_params(None, lora.lora_logical_axes(cfg, lcfg), mesh)

    def make_adapters(ka, kb):
        a = lora.init_lora_params(cfg, lcfg, ka)["layers"]
        keys = jax.random.split(kb, len(a))
        return {"layers": {
            n: (w if n.endswith("_a") else
                jax.random.normal(k, w.shape, jnp.float32).astype(w.dtype)
                * 0.02) for k, (n, w) in zip(keys, sorted(a.items()))}}

    adapters = jax.jit(make_adapters, out_shardings=shardings)(k_a, k_b)
    rep = NamedSharding(mesh, P())
    toks = jax.device_put(jax.random.randint(k_tok, (b, s), 0,
                                             cfg.vocab_size), rep)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    hp = model.reference_hp(config, lora_alpha=cfg.lora_alpha)

    def program(base, adapters, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.value_and_grad(lambda lo: llama.loss_fn(
                {**base, "lora": lo}, batch, cfg)[0])(adapters)

    def reference(base, adapters, batch):
        return llama_ref.loss_and_adapter_grads(base, adapters, batch, hp)

    out_sh = (rep, shardings)
    p_loss, p_grads = jax.jit(program, out_shardings=out_sh)(
        base, adapters, batch)
    r_loss, r_grads = jax.jit(reference, out_shardings=out_sh)(
        base, adapters, batch)
    p_loss, r_loss = float(p_loss), float(r_loss)
    rel = {}
    for name in sorted(r_grads["layers"]):
        r = np.asarray(r_grads["layers"][name], np.float32)
        p = np.asarray(p_grads["layers"][name], np.float32)
        rel[name] = float(np.sqrt(((p - r) ** 2).mean())
                          / np.sqrt((r ** 2).mean()))
    return {"batch": [b, s], "program_loss": p_loss,
            "reference_loss": r_loss,
            "loss_rel": abs(p_loss - r_loss) / abs(r_loss),
            "grad_rel_rms": rel, "grad_rel_rms_max": max(rel.values()),
            "finite": bool(np.isfinite([p_loss, r_loss]).all()
                           and np.isfinite(list(rel.values())).all())}


def bench_lora_loop(config: dict):
    """Runs inside the TrainWorker. `config` is lora_finetune_loop's own,
    plus "bench": {"config", "traffic", "seed", "seconds", "trace",
    "work"}."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import lora
    from ray_tpu.parallel.mesh import shard_params
    from ray_tpu.train.recipes import lora_finetune_loop

    from benchmarks import model, trace_reduce

    bench = config["bench"]
    tr, seed = bench["traffic"], bench["seed"]
    t_enter = time.perf_counter()
    model.register_preset(bench["config"], "train")
    mesh = train.get_context().get_mesh()
    jax.devices()
    out: dict = {"setup": {"worker_backend_s":
                           time.perf_counter() - t_enter}}

    seen: dict = {}

    def make_base(cfg):
        from ray_tpu.models import llama

        base = model.jitted_init(cfg, seed, shard_params(
            None, llama.param_logical_axes(cfg), mesh))
        jax.block_until_ready(base)
        return base

    def init_params_fn(cfg):
        t = time.perf_counter()
        seen["cfg"] = cfg
        base = make_base(cfg)
        out["setup"]["weights_s"] = time.perf_counter() - t
        return base

    bsz, seq = config["batch_size"], config["seq_len"]
    vocab = int(bench["config"]["vocab_size"])
    base_key = jax.random.PRNGKey(model.fold_seed(seed))

    @jax.jit
    def make_batch(step):
        toks = jax.random.randint(jax.random.fold_in(base_key, step),
                                  (bsz, seq), 0, vocab)
        return {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}

    warm = int(tr["warmup_steps"])
    stamps: list = []      # host clock at the top of every step
    tracing = {"on": False, "from": None, "to": None}
    trace_dir = os.path.join(bench["work"], "trace")

    def batch_fn(step, rank):
        now = time.perf_counter()
        stamps.append(now)
        n = len(stamps) - 1          # steps finished so far
        if n == warm:
            out["window_start_wall"] = time.time()
        if n >= warm and now - stamps[warm] >= bench["seconds"]:
            raise _WindowClosed
        if bench["trace"]:
            at = warm + int(tr["trace_after_steps"])
            if n == at:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing.update(on=True)
                tracing["from"] = now
            elif n == at + int(tr["trace_steps"]):
                # stopping the profiler takes many seconds: the traced
                # run ends here, and its per-layer metrics are read from
                # the steps before the profiler started
                jax.profiler.stop_trace()
                tracing.update(on=False, to=time.perf_counter())
                out["untraced_stamps"] = at + 1
                raise _WindowClosed
        return make_batch(step)

    loop_config = {k: v for k, v in config.items() if k != "bench"}
    loop_config.update(init_params_fn=init_params_fn, batch_fn=batch_fn,
                       steps=10 ** 9)
    try:
        lora_finetune_loop(loop_config)
    except _WindowClosed:
        pass
    finally:
        if tracing["on"]:
            jax.profiler.stop_trace()
    out["stamps"] = stamps
    out["warmup_steps"] = warm
    out["device"] = model.device_report()
    out["memory_peak_bytes"] = model.memory_peak_bytes()
    # the step donated the base it was given, and the loop's state went
    # with its frame: the same weights are made again from the seed
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    t = time.perf_counter()
    cfg = seen["cfg"]
    lcfg = lora.LoraConfig(rank=config["lora_rank"], alpha=cfg.lora_alpha,
                           targets=tuple(config["lora_targets"]))
    out["check"] = _reference_check(cfg, lcfg, make_base(cfg), mesh,
                                    bench["config"], tr, seed)
    out["after_window"] = {"reference_check_s": time.perf_counter() - t,
                           "live_bytes_left_by_the_loop": live}
    if bench["trace"] and tracing["to"] is not None:
        out["trace"] = trace_reduce.reduce_dir(trace_dir)
        out["traced"] = {"traced_wall_s": tracing["to"] - tracing["from"],
                         "steps": int(tr["trace_steps"])}
    train.report({"bench": out})


def run(cell: Cell, seed: int, seconds: float, trace: bool, work: str,
        t_process: float) -> dict:
    t_process_wall = time.time() - (time.perf_counter() - t_process)
    from ray_tpu import state_api
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmarks import model
    from benchmarks.serve_cell import start_cluster

    tr = cell.traffic
    setup = {"imports_s": time.perf_counter() - t_process}
    t = time.perf_counter()
    rt = start_cluster(cell.chips)
    setup["cluster_s"] = time.perf_counter() - t

    job = tr["job"]
    config = {
        "preset": cell.config["name"], "seq_len": job["seq_len"],
        "batch_size": job["batch_size"], "lora_rank": job["lora_rank"],
        "lora_targets": job["lora_targets"],
        "report_every": job["report_every"],
        "grad_accum": job.get("grad_accum", 1),
        "seed": model.fold_seed(seed),
        "model_overrides": {"max_seq_len": job["seq_len"],
                            **job.get("model_overrides", {})},
        "bench": {"config": cell.config, "traffic": tr, "seed": seed,
                  "seconds": seconds, "trace": trace, "work": work}}
    name = "bench-" + cell.name
    t_fit = time.perf_counter()
    result = JaxTrainer(
        bench_lora_loop, train_loop_config=config,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"CPU": 1.0, "TPU": float(cell.chips)},
            mesh=job.get("mesh")),
        run_config=RunConfig(name=name,
                             storage_path=os.path.join(work, "train")),
    ).fit()
    fit_s = time.perf_counter() - t_fit
    if result.error is not None:
        raise RuntimeError(f"the train run failed: {result.error!r}")
    out = (result.metrics or {}).get("bench")
    if out is None:
        raise RuntimeError("the train worker reported nothing")

    runs = state_api.list_train_runs(experiment=name)
    steps = sorted(state_api.list_train_steps(
        run_id=runs[0]["run_id"], limit=0), key=lambda s: s["step"]) \
        if runs else []
    rt.shutdown()

    stamps, warm = out["stamps"], out["warmup_steps"]
    win = stamps[warm:out.get("untraced_stamps")]
    setup.update(out["setup"])
    setup["fit_s"] = fit_s
    setup["first_steps_s"] = stamps[warm] - stamps[0]
    return {"device": out["device"],
            "setup": setup, "traffic": tr, "config": cell.config,
            "chips": cell.chips, "job": job,
            # both processes are on one host: the wall clock joins them
            "setup_s": out["window_start_wall"] - t_process_wall,
            "window_stamps": win, "steps": steps, "warmup_steps": warm,
            "check": out["check"], "after_window": out["after_window"],
            "trace": out.get("trace"),
            "traced": out.get("traced"),
            "memory_peak_bytes": out["memory_peak_bytes"]}


# ------------------------------------------------------------------------
def train_tokens_per_s(obs: dict) -> float:
    """batch x sequence x whole steps finished in the window, over the
    time those steps took: first step's start to last step's end, all
    chips together. Taken from the host clock at the top of each step,
    so everything between two steps (report, checkpoint, telemetry) is
    in it."""
    win = obs["window_stamps"]
    if len(win) < 3:
        raise RuntimeError("fewer than two whole steps in the window")
    job = obs["job"]
    return (job["batch_size"] * job["seq_len"] * (len(win) - 1)
            / (win[-1] - win[0]))


def window_steps(obs: dict) -> list:
    """The recipe's own step records for the steps of the window."""
    n = len(obs["window_stamps"]) - 1
    first = obs["warmup_steps"] + 1          # records count from 1
    return [s for s in obs["steps"] if first <= s["step"] < first + n]


def attempted_failed(obs: dict) -> tuple:
    import math

    n = len(obs["window_stamps"]) - 1
    losses = [s.get("loss") for s in window_steps(obs)]
    bad = sum(1 for l in losses if l is None or not math.isfinite(l))
    return n, bad + max(0, n - len(losses))


def correct(obs: dict, tol: dict) -> bool:
    """`grad_rel_rms` gives a tolerance for each adapted projection: the
    gradients of wv and wo do not pass through a softmax's Jacobian and
    are held more tightly than those of wq and wk."""
    c = obs["check"]
    return bool(c["finite"] and c["loss_rel"] <= tol["loss_rel"] and all(
        v <= tol["grad_rel_rms"][name.rsplit("_", 1)[0]]
        for name, v in c["grad_rel_rms"].items()))


def info(obs: dict) -> dict:
    win = obs["window_stamps"]
    steps = window_steps(obs)
    dts = [b - a for a, b in zip(win, win[1:])]
    return {"setup": obs["setup"], "after_window": obs["after_window"],
            "device": obs["device"],
            "check": obs["check"], "steps_in_window": len(win) - 1,
            "step_records_in_window": len(steps),
            "step_wall_s": {"min": min(dts), "max": max(dts),
                            "median": sorted(dts)[len(dts) // 2]},
            "first_loss": steps[0].get("loss") if steps else None,
            "last_loss": steps[-1].get("loss") if steps else None}
