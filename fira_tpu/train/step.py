"""Jitted train / dev steps.

One ``jax.jit`` program per step kind, compiled once over fixed shapes and
sharded over the (data, model) mesh via NamedShardings — the TPU equivalent
of the reference's per-batch DataParallel scatter/forward/gather/backward
(/root/reference/run_model.py:102-109). Buffers are donated so the optimizer
update happens in place in HBM.

Loss semantics match the reference exactly: the model returns
(nll_sum, token_count) and the step normalizes sum/count over the GLOBAL
batch (run_model.py:104-105 normalizes after DataParallel's gather — same
thing).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from fira_tpu.config import FiraConfig
from fira_tpu.model.model import FiraModel
from fira_tpu.parallel import mesh as pmesh
from fira_tpu.train.state import TrainState, make_optimizer, prng_impl_name


def loss_fn(model: FiraModel, params, batch, dropout_rng) -> jnp.ndarray:
    nll_sum, count = model.apply(
        {"params": params}, batch, deterministic=False,
        rngs={"dropout": dropout_rng},
    )
    return nll_sum / jnp.maximum(count, 1)


def make_train_step(model: FiraModel, cfg: FiraConfig
                    ) -> Callable[[TrainState, Dict[str, Any]],
                                  Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    optimizer = make_optimizer(cfg)

    rng_impl = prng_impl_name(cfg.rng_impl)

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        # state.rng is raw key data (checkpoint-friendly); re-wrap with the
        # configured generator (threefry default / TPU-fast rbg)
        key = jax.random.wrap_key_data(state.rng, impl=rng_impl)
        step_rng, next_key = jax.random.split(key)
        next_rng = jax.random.key_data(next_key)
        loss, grads = jax.value_and_grad(
            partial(loss_fn, model)
        )(state.params, batch, step_rng)
        with jax.named_scope("optimizer"):   # a name in the device trace
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = jax.tree_util.tree_map(
                lambda p, u: (p + u).astype(p.dtype), state.params, updates
            )
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state,
            rng=next_rng,
        )
        return new_state, {"loss": loss}

    return train_step


def make_multi_step(model: FiraModel, cfg: FiraConfig
                    ) -> Callable[[TrainState, Dict[str, Any]],
                                  Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """K train steps per dispatch: ``lax.scan`` over batches stacked on a
    leading axis — the TPU device-loop pattern.

    One host->device dispatch then runs K full steps on-chip, which bounds
    per-step host/dispatch overhead at 1/K. The reference's loop pays
    per-batch Python +
    DataParallel scatter/gather overhead every step (run_model.py:94-109);
    here the scan body is the SAME train_step the per-step path compiles,
    so semantics are identical (tests pin loss equality step-for-step).

    Returns ``(final_state, {"loss": (K,) losses})``; dev-gate cadence and
    checkpointing happen at scan-group boundaries in the caller.
    """
    step = make_train_step(model, cfg)

    def multi_step(state: TrainState, stacked_batch) -> Tuple[TrainState, Dict]:
        def body(s, b):
            s2, metrics = step(s, b)
            return s2, metrics["loss"]

        final, losses = jax.lax.scan(body, state, stacked_batch)
        return final, {"loss": losses}

    return multi_step


def stack_batches(batches) -> Dict[str, Any]:
    """Stack host batches along a new leading axis for make_multi_step /
    make_accum_step. The batches must share one geometry — under buckets
    the grouped scheduler guarantees bucket-homogeneous groups, and its
    ``data.grouping.stack_group`` owns the accum-tail variant that pads
    short groups with all-invalid micro-batches."""
    import numpy as np

    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)


def make_accum_step(model: FiraModel, cfg: FiraConfig
                    ) -> Callable[[TrainState, Dict[str, Any]],
                                  Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """ONE optimizer step from A accumulated micro-batches (leading axis A).

    Reproduces the reference's multi-GPU global-batch dynamics on a single
    chip: DataParallel splits batch 680 over 4 GPUs and normalizes the
    gathered (nll_sum, token_count) over the GLOBAL batch
    (run_model.py:102-105). Counts carry no gradient, so
    d[(Σ nll_i)/(Σ cnt_i)]/dθ = (Σ d nll_i)/(Σ cnt_i): accumulate raw
    nll-gradients and counts over a lax.scan, divide once, then update —
    bit-equal (up to f32 reassociation) to stepping one A·B batch, which
    the tests pin in deterministic mode.

    Each micro-batch draws its own dropout key (folded from the state key),
    mirroring the distinct per-GPU streams of the reference.
    """
    optimizer = make_optimizer(cfg)
    rng_impl = prng_impl_name(cfg.rng_impl)

    def raw_nll(params, batch, rng):
        nll_sum, count = model.apply(
            {"params": params}, batch, deterministic=False,
            rngs={"dropout": rng},
        )
        return nll_sum, count

    def accum_step(state: TrainState, stacked_batch) -> Tuple[TrainState, Dict]:
        key = jax.random.wrap_key_data(state.rng, impl=rng_impl)
        step_key, next_key = jax.random.split(key)
        next_rng = jax.random.key_data(next_key)

        zero_g = jax.tree_util.tree_map(jnp.zeros_like, state.params)

        def body(carry, mb):
            g_acc, nll_acc, cnt_acc, i = carry
            sub = jax.random.fold_in(step_key, i)
            (nll, cnt), g = jax.value_and_grad(raw_nll, has_aux=True)(
                state.params, mb, sub)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            return (g_acc, nll_acc + nll, cnt_acc + cnt, i + 1), None

        (g_sum, nll_sum, cnt_sum, _), _ = jax.lax.scan(
            body, (zero_g, jnp.zeros(()), jnp.zeros(()), 0), stacked_batch)

        denom = jnp.maximum(cnt_sum, 1)
        grads = jax.tree_util.tree_map(lambda g: g / denom, g_sum)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), state.params, updates)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state,
            rng=next_rng,
        )
        return new_state, {"loss": nll_sum / denom}

    return accum_step


def make_dev_step(model: FiraModel) -> Callable:
    """Teacher-forced greedy ids (Model.py:86 'dev' stage)."""

    def dev_step(params, batch) -> jnp.ndarray:
        return model.apply({"params": params}, batch,
                           method=FiraModel.dev_predict)

    return dev_step


def state_shardings(state: TrainState, mesh: Mesh) -> TrainState:
    """NamedSharding pytree for a TrainState: params (and their Adam
    moments) by the TP rules, scalars/PRNG replicated."""
    import optax

    params_sh = pmesh.params_shardings(state.params, mesh)

    # Adam moments (mu/nu) live with their params — same mesh layout — so the
    # optimizer update is fully local; counts/scalars are replicated.
    def opt_component_shardings(o):
        if isinstance(o, optax.ScaleByAdamState):
            return optax.ScaleByAdamState(
                count=pmesh.replicated(mesh), mu=params_sh, nu=params_sh
            )
        return jax.tree_util.tree_map(lambda _: pmesh.replicated(mesh), o)

    return TrainState(
        step=pmesh.replicated(mesh),
        params=params_sh,
        opt_state=tuple(opt_component_shardings(o) for o in state.opt_state),
        rng=pmesh.replicated(mesh),
    )


def jit_train_step(model: FiraModel, cfg: FiraConfig, mesh: Optional[Mesh],
                   state: TrainState, sample_batch) -> Callable:
    """Compile the train step; with a mesh, pin params/opt-state/batch
    shardings so XLA lays out DP gradient psums + TP all-reduces over ICI."""
    step = make_train_step(model, cfg)
    if mesh is None:
        return jax.jit(step, donate_argnums=(0,))

    state_sh = state_shardings(state, mesh)
    batch_sh = pmesh.batch_shardings(sample_batch, mesh)
    return jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, pmesh.replicated(mesh)),
        donate_argnums=(0,),
    )


def jit_multi_step(model: FiraModel, cfg: FiraConfig, mesh: Optional[Mesh],
                   state: TrainState, stacked_sample) -> Callable:
    """Compile the K-step device loop; with a mesh, batches shard along
    their SECOND axis (leading axis is the scan/step axis).

    Per-BucketGeom specialization falls out of jit's shape cache: the ONE
    returned callable compiles one program per stacked input shape, i.e.
    one per (geometry, K) family member — NamedShardings constrain layout,
    not shape, so the mesh path needs no per-geometry re-wrapping. The
    train loop pre-warms every member on a throwaway state
    (train/loop.py), so the epoch loop never compiles."""
    return _jit_stacked(make_multi_step(model, cfg), mesh, state,
                        stacked_sample)


def jit_accum_step(model: FiraModel, cfg: FiraConfig, mesh: Optional[Mesh],
                   state: TrainState, stacked_sample) -> Callable:
    """Compile the A-micro-batch accumulation step (same stacked layout as
    the device loop: leading axis = micro-batch, second axis = batch/data;
    same per-(geometry, A) shape-cache specialization as jit_multi_step).
    Bucketed accum tails keep the stacked shape — the scheduler pads short
    groups with all-invalid micro-batches (data/grouping.py) — so A is the
    only leading dim ever compiled."""
    return _jit_stacked(make_accum_step(model, cfg), mesh, state,
                        stacked_sample)


def _jit_stacked(fn: Callable, mesh: Optional[Mesh], state: TrainState,
                 stacked_sample) -> Callable:
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0,))
    state_sh = state_shardings(state, mesh)
    stacked_sh = pmesh.stacked_batch_shardings(stacked_sample, mesh)
    return jax.jit(
        fn,
        in_shardings=(state_sh, stacked_sh),
        out_shardings=(state_sh, pmesh.replicated(mesh)),
        donate_argnums=(0,),
    )
