"""SPMD training step builder: mesh + logical axes + optax → one jitted step.

This is the TPU-native replacement for the reference's DDP wiring (ref:
train/torch/config.py:66 `_setup_torch_process_group` + torch DDP/FSDP
delegation): instead of wrapping a module in a process group, we annotate
shardings and let GSPMD insert the collectives — gradient allreduce over
the `data` axis, parameter all-gather/reduce-scatter over `fsdp`, TP
partials over `tensor` — all riding ICI.

Usage:
    mesh = MeshConfig(data=2, fsdp=2, tensor=2).build()
    step, state = build_train_step(loss_fn, optimizer, params, axes, mesh)
    state, metrics = step(state, batch)     # compiled, donated
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import DEFAULT_RULES, shard_params, spec_for


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dim sharded over data×fsdp (DP)."""
    return NamedSharding(mesh, spec_for(("batch",), None, mesh))


def shard_batch(batch: Any, mesh: Mesh) -> Any:
    sh = batch_sharding(mesh)

    def put(x):
        x = jnp.asarray(x)
        return jax.device_put(x, sh if x.ndim else NamedSharding(mesh, P()))
    return jax.tree.map(put, batch)


def build_train_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                     params: Any, logical_axes: Any, mesh: Mesh,
                     rules: dict | None = None, grad_accum: int = 1,
                     trainable_keys: tuple | None = None):
    """Returns (compiled_step, sharded_initial_state).

    loss_fn(params, batch) -> (loss, aux_dict). State = {params, opt_state,
    step}. The step donates the state buffers (in-place update in HBM).

    trainable_keys: top-level param-dict keys to train (e.g. ("lora",) for
    adapter fine-tuning). The rest move to state["frozen"]: the backward
    pass never computes their gradients and the optimizer holds no moments
    for them — the LoRA FLOP/memory win, not a zero-masked imitation.
    """
    rules = rules or DEFAULT_RULES
    param_shardings = shard_params(params, logical_axes, mesh, rules)
    params = jax.tree.map(
        lambda x, s: jax.device_put(jnp.asarray(x), s), params, param_shardings)
    frozen = {}
    if trainable_keys is not None:
        missing = [k for k in trainable_keys if k not in params]
        if missing:
            raise ValueError(f"trainable_keys {missing} not in params")
        frozen = {k: v for k, v in params.items() if k not in trainable_keys}
        params = {k: params[k] for k in trainable_keys}
        param_shardings = {k: param_shardings[k] for k in trainable_keys}
    opt_state = jax.jit(
        optimizer.init,
        out_shardings=_opt_state_shardings(optimizer, params, param_shardings,
                                           mesh))(params)
    state = {"params": params, "opt_state": opt_state,
             "step": jax.device_put(jnp.zeros((), jnp.int32),
                                    NamedSharding(mesh, P()))}
    if frozen:
        state["frozen"] = frozen
    state_shardings = jax.tree.map(
        lambda x: x.sharding, state,
        is_leaf=lambda x: isinstance(x, jax.Array))

    def one_step(state, batch):
        # traced under the mesh, so model code that must know it (a
        # Pallas kernel has to be shard_map'ped by hand, ops/attention.py)
        # finds it with jax.sharding.get_abstract_mesh()
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _one_step(state, batch)

    def _one_step(state, batch):
        def compute(p, b):
            # params stay an arbitrary pytree unless a frozen split exists
            full = {**state["frozen"], **p} if "frozen" in state else p
            loss, aux = loss_fn(full, b)
            return loss, aux

        # scope names are HLO metadata: a profiler trace of the step
        # says which device operations are the loss (jax adds jvp,
        # transpose and checkpoint to the path itself; under accumulation
        # the scan's own slices and sums are the loss's too) and which
        # the optimizer
        with jax.named_scope("loss"):
            if grad_accum > 1:
                def micro(carry, mb):
                    g_acc, aux_acc = carry
                    (_, aux), g = jax.value_and_grad(
                        compute, has_aux=True)(state["params"], mb)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
                    return (g_acc, aux_acc), None

                mb0 = jax.tree.map(
                    lambda x: x.reshape((grad_accum, -1) + x.shape[1:]), batch)
                zeros_g = jax.tree.map(jnp.zeros_like, state["params"])
                (_, aux0), _ = jax.value_and_grad(compute, has_aux=True)(
                    state["params"], jax.tree.map(lambda x: x[0], mb0))
                zeros_aux = jax.tree.map(jnp.zeros_like, aux0)
                (grads, aux), _ = jax.lax.scan(micro, (zeros_g, zeros_aux), mb0)
                grads = jax.tree.map(lambda g: g / grad_accum, grads)
                aux = jax.tree.map(lambda a: a / grad_accum, aux)
            else:
                (_, aux), grads = jax.value_and_grad(
                    compute, has_aux=True)(state["params"], batch)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            # keep param dtype stable (optax promotes on mixed dtypes)
            new_params = jax.tree.map(
                lambda new, old: new.astype(old.dtype), new_params,
                state["params"])
        out = {"params": new_params, "opt_state": new_opt,
               "step": state["step"] + 1}
        if "frozen" in state:
            out["frozen"] = state["frozen"]  # donated buffers pass through
        return (out, aux)

    step = jax.jit(
        one_step,
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,))
    return step, state


def _opt_state_shardings(optimizer, params, param_shardings, mesh):
    """Optimizer state mirrors param shardings where shapes match (adam
    moments), replicated otherwise (counts)."""
    shapes = jax.eval_shape(optimizer.init, params)
    flat_params, _ = jax.tree.flatten(params)
    flat_shard, _ = jax.tree.flatten(param_shardings)
    by_shape = {}
    for p, s in zip(flat_params, flat_shard):
        by_shape.setdefault((p.shape, p.dtype), s)

    def pick(leaf):
        s = by_shape.get((leaf.shape, leaf.dtype))
        if s is not None and leaf.ndim > 0:
            return s
        return NamedSharding(mesh, P())

    return jax.tree.map(pick, shapes)


def build_eval_step(loss_fn: Callable):
    def eval_one(params, batch):
        _, aux = loss_fn(params, batch)
        return aux
    return jax.jit(eval_one)
