"""Reusable train-loop recipes for JaxTrainer.

The reference ships fine-tuning as free-standing torch/DeepSpeed example
scripts (ref: doc/source/train/examples/deepspeed/,
release/air_examples/dolly_v2_lightning_fsdp_finetuning/); here the
canonical loops are library code so tests, benches, and users share one
implementation.
"""

from __future__ import annotations

from typing import Any, Callable, Optional


def corpus_pretrain_loop(config: dict):
    """Pre-train from a sharded tokenized corpus via session ingest
    (train/ingest.py). The model is a deliberately tiny embedding net —
    this recipe is the canonical wiring of the INGEST contract: the
    corpus cursor is saved inside every checkpoint and restored on
    (re)start, so a run killed mid-epoch resumes consuming exactly the
    tokens an uninterrupted run would have.

    config keys:
      vocab_size, dim      — toy model size (default 128 / 8)
      lr, steps            — SGD rate / max train steps (corpus may end
                             earlier; the loop stops at either)
      checkpoint_every     — steps between checkpointed reports (def. 5)
      use_mesh             — shard batches onto the ScalingConfig mesh
      trace_dir            — debug/test hook: persist the consumed token
                             ids per step (trace_dir/rank{r}/step_*.npy);
                             re-executed steps overwrite, so the dir
                             always holds the EFFECTIVE consumed stream
      crash_at_step        — fault-injection hook: hard-exit the worker
                             before that step, once per marker file
    """
    import os
    import shutil

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.train.checkpoint import Checkpoint

    ctx = train.get_context()
    rank = ctx.get_world_rank()
    mesh = ctx.get_mesh() if config.get("use_mesh") else None

    vocab = config.get("vocab_size", 128)
    dim = config.get("dim", 8)
    lr = config.get("lr", 1e-2)
    steps = config.get("steps", 20)
    ckpt_every = config.get("checkpoint_every", 5)

    start_step = 0
    ingest_state = None
    w = jax.random.normal(jax.random.PRNGKey(0), (vocab, dim)) * 0.02
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        restored = Checkpoint(
            os.path.join(ckpt.path, f"rank_{rank}")).to_dict()
        w = jnp.asarray(restored["w"])
        start_step = int(restored["step"])
        ingest_state = restored["ingest"]

    it = ctx.get_ingest(mesh=mesh, state=ingest_state)

    @jax.jit
    def sgd_step(w, tokens):
        def loss_fn(w):
            emb = w[tokens]  # (B, T, dim) gather
            return jnp.mean(jnp.square(emb - jnp.mean(emb)))

        loss, grad = jax.value_and_grad(loss_fn)(w)
        return w - lr * grad, loss

    # per-step waterfall (train/telemetry): ingest stamps data_wait/h2d,
    # the compute phase block-until-readies so step_s is honest, report
    # stamps ckpt_block — the four stages tile step wall by construction.
    # wrap_jit adds compile/retrace accounting on the train step.
    rec = ctx.recorder
    if rec is not None:
        sgd_step = rec.wrap_jit(sgd_step, "sgd_step")

    trace_dir = config.get("trace_dir")
    if trace_dir:
        os.makedirs(os.path.join(trace_dir, f"rank{rank}"), exist_ok=True)
    crash_at = config.get("crash_at_step")

    loss = None
    try:
        for step in range(start_step, steps):
            if crash_at is not None and step == crash_at:
                marker = os.path.join(ctx.experiment_path,
                                      f".crashed-rank_{rank}")
                if not os.path.exists(marker):
                    open(marker, "w").close()
                    os._exit(1)  # simulate a hard worker kill mid-epoch
            try:
                batch = next(it)  # ingest stamps data_wait (+h2d if mesh)
            except StopIteration:
                break  # corpus exhausted before `steps`
            if rec is not None:
                with rec.phase("h2d"):
                    tokens = jnp.asarray(batch["tokens"])
            else:
                tokens = jnp.asarray(batch["tokens"])
            if trace_dir:
                np.save(os.path.join(trace_dir, f"rank{rank}",
                                     f"step_{step:05d}.npy"),
                        np.asarray(batch["tokens"]))
            if rec is not None:
                with rec.phase("step"):
                    w, loss = sgd_step(w, tokens)
                    jax.block_until_ready(loss)
            else:
                w, loss = sgd_step(w, tokens)
            if (step + 1) % ckpt_every == 0 or step == steps - 1:
                c = Checkpoint.from_dict({
                    "w": np.asarray(w), "step": step + 1,
                    "ingest": it.state_dict()})
                train.report(
                    {"loss": float(loss), "step": step + 1,
                     "tokens": int(batch["tokens"].size),
                     "ingest_stall_s": it.stats.stall_s,
                     "ingest_load_s": it.stats.load_s},
                    checkpoint=c)
                shutil.rmtree(c.path, ignore_errors=True)  # report copied
            if rec is not None:
                rec.end_step(step + 1, tokens=int(batch["tokens"].size),
                             loss=float(loss))
    finally:
        it.close()  # a failed step must not leak the prefetch thread
    return float(loss) if loss is not None else None


def build_lora_step(config: dict, mesh):
    """The LoRA fine-tune step as `lora_finetune_loop` runs it, for the
    keys of its config that shape the program (preset, model_overrides,
    lora_*, lr, grad_accum, seed, init_params_fn): frozen base params +
    fresh adapters placed on `mesh`, and a jitted step that trains ONLY
    the adapters (build_train_step(trainable_keys=("lora",)) — the
    backward computes no base-weight gradients and the optimizer holds
    moments only for A/B). Returns (step, state, cfg).

    The programs it asks XLA for (the adapters' and the optimizer
    state's initialisation, and the weights' where they are its own
    random ones) are named `build_lora_step` in the process's log
    (_internal/profiler.ProcessLog); a caller's `init_params_fn` runs
    under the caller's label: its programs are the caller's to name."""
    import jax
    import optax

    from ray_tpu._internal.profiler import process_log
    from ray_tpu.models import llama, lora
    from ray_tpu.parallel.spmd import build_train_step

    overrides = dict(config.get("model_overrides") or {})
    overrides.setdefault("lora_alpha", config.get("lora_alpha", 16.0))
    cfg = llama.config_for(config.get("preset", "debug"), **overrides)
    lcfg = lora.LoraConfig(
        # cfg.lora_alpha is the single source of truth for the scale (the
        # forward and merge_lora both read it); mirror it here for repr
        rank=config.get("lora_rank", 8),
        alpha=cfg.lora_alpha,
        targets=tuple(config.get("lora_targets", lora.DEFAULT_TARGETS)))

    log = process_log()
    with log.labelled("build_lora_step") as outer:
        key = jax.random.PRNGKey(config.get("seed", 0))
        init_fn: Optional[Callable[[Any], Any]] = config.get(
            "init_params_fn")
        if init_fn is None:
            base = llama.init_params(cfg, key)
        else:
            with log.labelled(outer):
                base = init_fn(cfg)
        adapters = lora.init_lora_params(cfg, lcfg,
                                         jax.random.fold_in(key, 1))
        params = {**base, "lora": adapters}
        axes = {**llama.param_logical_axes(cfg),
                "lora": lora.lora_logical_axes(cfg, lcfg)}

        loss = lambda p, b: llama.loss_fn(p, b, cfg)
        step, state = build_train_step(
            loss, optax.adamw(config.get("lr", 1e-3)), params, axes, mesh,
            grad_accum=config.get("grad_accum", 1),
            trainable_keys=("lora",))
    return step, state, cfg


def lora_finetune_loop(config: dict):
    """LoRA fine-tune a Llama-family model.

    Runs inside each TrainWorker: builds the mesh from ScalingConfig,
    initializes (or loads) frozen base params + LoRA adapters, and trains
    ONLY the adapters (see `build_lora_step`).

    config keys:
      preset        — llama preset name (default "debug")
      model_overrides — dict merged into the preset config
      lora_rank / lora_alpha / lora_targets
      lr, steps, batch_size, seq_len, grad_accum
      report_every  — steps between train.report calls (default 10)
      batch_fn      — optional callable (step, rank) -> {"tokens","targets"}
                      (defaults to synthetic LM data)
      init_params_fn — optional callable (cfg) -> base params (defaults to
                      random init; real runs pass a checkpoint loader)
    """
    import os
    import pickle
    import tempfile

    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu._internal.profiler import process_log, span_type
    from ray_tpu.parallel.spmd import shard_batch
    from ray_tpu.train.checkpoint import Checkpoint, save_pytree

    report_span = span_type()
    ctx = train.get_context()
    mesh = ctx.get_mesh()
    step, state, cfg = build_lora_step(config, mesh)

    rank = ctx.get_world_rank()
    start_step = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        # failure-policy restart: reload adapters + optimizer moments +
        # step so a resumed run continues EXACTLY where it stopped —
        # resetting adamw moments would silently change training
        # dynamics after every restart. Moments cover only the adapters,
        # so the artifact stays small and serving-loadable.
        from ray_tpu.train.checkpoint import load_pytree

        ckpt_dir = ckpt.subdir(f"rank_{rank}").path
        restored = load_pytree(ckpt_dir)
        loaded = jax.tree.map(jnp.asarray, restored["lora"])
        state["params"]["lora"] = jax.tree.map(
            lambda x, cur: jax.device_put(x.astype(cur.dtype), cur.sharding),
            loaded, state["params"]["lora"])
        opt_path = os.path.join(ckpt_dir, "opt_state.pkl")
        if os.path.exists(opt_path):
            # pickled host copy (not save_pytree): pickle preserves the
            # optax NamedTuple structure exactly, so tree.map against the
            # live opt_state restores sharded without re-registration
            with open(opt_path, "rb") as f:
                opt_host = pickle.load(f)
            state["opt_state"] = jax.tree.map(
                lambda h, cur: jax.device_put(
                    jnp.asarray(h, cur.dtype), cur.sharding),
                opt_host, state["opt_state"])
        start_step = int(restored["step"])

    bsz = config.get("batch_size", 8)
    seq = config.get("seq_len", min(128, cfg.max_seq_len))
    batch_fn = config.get("batch_fn")

    def synthetic(i, rank):
        k = jax.random.PRNGKey(1000 * rank + i)
        toks = jax.random.randint(k, (bsz, seq), 0, cfg.vocab_size)
        return {"tokens": toks,
                "targets": jnp.roll(toks, -1, axis=1)}

    make_batch = batch_fn or synthetic
    report_every = config.get("report_every", 10)
    steps = config.get("steps", 50)

    # the batch's programs (a jitted generator's, on the first call) by
    # name in the process's log, like the step's
    site = process_log().labelled
    # same waterfall as corpus_pretrain_loop (h2d = shard_batch, step =
    # block-until-ready update, ckpt_block stamped inside report)
    rec = ctx.recorder
    if rec is not None:
        step = rec.wrap_jit(step, "lora_step")

    last_loss = first_loss = None
    for i in range(start_step, steps):
        if rec is not None:
            with rec.phase("h2d"), site("make_batch"):
                batch = shard_batch(make_batch(i, rank), mesh)
            with rec.phase("step"):
                state, aux = step(state, batch)
                jax.block_until_ready(aux["loss"])
        else:
            with site("make_batch"):
                batch = shard_batch(make_batch(i, rank), mesh)
            state, aux = step(state, batch)
        if (i + 1) % report_every == 0 or i == steps - 1:
            last_loss = float(aux["loss"])
            if first_loss is None:
                first_loss = last_loss
            # one host span over everything a report blocks the loop for:
            # the adapter and moment copies to the host, then the report
            with report_span("rayt.train.report", step=i + 1), \
                    tempfile.TemporaryDirectory() as d:
                # adapters-only checkpoint: the LoRA artifact is the
                # deliverable (base stays wherever it was loaded from);
                # optimizer moments ride along so restarts resume the
                # exact trajectory
                save_pytree({"lora": state["params"]["lora"],
                             "step": i + 1}, d)
                with open(os.path.join(d, "opt_state.pkl"), "wb") as f:
                    pickle.dump(jax.device_get(state["opt_state"]), f)
                train.report({"loss": last_loss, "first_loss": first_loss,
                              "step": i + 1},
                             checkpoint=Checkpoint(d))
        if rec is not None:
            rec.end_step(i + 1, loss=float(aux["loss"]))
    return last_loss
