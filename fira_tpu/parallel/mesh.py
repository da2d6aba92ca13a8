"""Device mesh + sharding layout for multi-chip training.

The reference's only accelerator parallelism is single-process
``nn.DataParallel`` scatter/gather over local GPUs
(/root/reference/run_model.py:392-394) — no process groups, no collectives.
The TPU-native replacement is SPMD over a ``jax.sharding.Mesh`` with two
axes:

- ``data``: batch sharding; XLA inserts the gradient ``psum`` over ICI that
  DataParallel's backward gather performed on the host.
- ``model``: Megatron-style tensor parallelism for the d_model-sized
  matmuls — column-parallel first projections (q/k/v, FFN fc1), row-parallel
  second projections (out_proj, fc2, out_fc) — so each pair costs exactly
  one all-reduce, inserted by XLA from the shardings alone.

Everything is laid out with `jax.jit` + `NamedSharding`; there is no
hand-written communication. Loss normalization happens inside the jitted
program over the *global* batch, matching the reference's post-gather
normalization (run_model.py:104-105).
"""

from __future__ import annotations

import re
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Sharding-invariant RNG is part of the mesh contract: a sharded program's
# dropout draws must not depend on the mesh factorization. That needs the
# partitionable threefry lowering, which is jax's default
# (jax_threefry_partitionable=True) on the installed 0.9.0 — nothing to set.

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(n_data: Optional[int] = None, n_model: int = 1,
              devices: Optional[Sequence] = None,
              axis_names: Tuple[str, str] = (DATA_AXIS, MODEL_AXIS)) -> Mesh:
    """Build a 2-axis mesh, (data, model) by default — the reference's DP
    regime with all devices on the data axis. ``n_model > 1`` turns on
    tensor parallelism for fira-large-scale runs; other second axes (e.g.
    ring.SEQ_AXIS) reuse the same grid construction via ``axis_names``."""
    devices = list(devices if devices is not None else jax.devices())
    if n_data is None:
        if len(devices) % n_model:
            raise ValueError(f"{len(devices)} devices not divisible by n_model={n_model}")
        n_data = len(devices) // n_model
    if len(devices) < n_data * n_model:
        raise ValueError(
            f"need {n_data * n_model} devices, have {len(devices)}")
    grid = np.asarray(devices[: n_data * n_model]).reshape(n_data, n_model)
    return Mesh(grid, axis_names)


# (regex over the "/"-joined param path) -> PartitionSpec. First match wins;
# default replicated. Column-parallel layers shard their output feature dim
# (and bias); row-parallel layers shard the contraction dim, XLA closes each
# pair with one psum over MODEL_AXIS.
_PARAM_RULES: Tuple[Tuple[str, P], ...] = (
    # embeddings: shard the feature dim (vocab sizes are odd; d is 2^k)
    (r"embedding$", P(None, MODEL_AXIS)),
    # column-parallel kernels
    (r"(q_proj|k_proj|v_proj|fc1|src_proj|tgt_proj)/kernel$", P(None, MODEL_AXIS)),
    (r"(q_proj|k_proj|v_proj|fc1)/bias$", P(MODEL_AXIS)),
    # row-parallel kernels (bias replicated: applied after the psum)
    (r"(out_proj|fc2)/kernel$", P(MODEL_AXIS, None)),
    # vocab head: contract over sharded d_model -> psum, output replicated
    (r"out_fc/kernel$", P(MODEL_AXIS, None)),
)


def param_spec(path: str) -> P:
    for pattern, spec in _PARAM_RULES:
        if re.search(pattern, path):
            return spec
    return P()


def params_shardings(params, mesh: Mesh):
    """PartitionSpec pytree for a params pytree (rules over joined paths)."""

    def spec_for(key_path, _leaf):
        path = "/".join(str(getattr(k, "key", k)) for k in key_path)
        return NamedSharding(mesh, param_spec(path))

    return jax.tree_util.tree_map_with_path(spec_for, params)


def batch_shardings(batch, mesh: Mesh):
    """Shard every batch array along its leading (batch) dim."""
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(DATA_AXIS)), batch
    )


def stacked_batch_shardings(stacked_batch, mesh: Mesh):
    """Shardings for a K-stacked batch (train.step.stack_batches): axis 0 is
    the scan/step axis (replicated), axis 1 is the batch dim (data axis)."""
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(None, DATA_AXIS)), stacked_batch
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def feed_shardings(mesh: Optional[Mesh]):
    """Feeder ``sharding=`` callable for the grouped/bucketed train stream.

    Mixed-geometry streams pick their sharding by SHAPE, not by bucket
    identity: a K-stacked group (2-D ``valid``) shards axis 1 on the data
    axis with the scan/step axis replicated, a per-step batch shards axis
    0 — so ONE callable covers every member of the (geometry x K) program
    family, and each K-group ships as a single worker-side sharded
    ``device_put``. ``mesh=None`` returns None (the feeder's single-chip
    default placement), so drivers can pass this unconditionally."""
    if mesh is None:
        return None

    def shardings(batch):
        if batch["valid"].ndim == 2:  # K-stacked group (fused/accum)
            return stacked_batch_shardings(batch, mesh)
        return batch_shardings(batch, mesh)

    return shardings


def divisibility_errors(cfg, n_data: int) -> List[str]:
    """Parse-time mesh admission check: every dispatched train batch
    shards its batch axis over the ``data`` mesh axis, so each bucket's
    batch size must divide by ``n_data`` — otherwise the run dies mid-epoch
    in an XLA reshape/sharding error long after startup. Returns one named
    message per offending bucket (all buckets dispatch at ``cfg.batch_size``
    today, but the check prices each declared geometry so a future
    per-bucket batch size cannot silently regress the guarantee). The
    engine fleet's twin (engine_slots vs replica count) lives with the
    fleet (parallel/fleet.py)."""
    errs: List[str] = []
    if n_data <= 1:
        return errs
    from fira_tpu.data.buckets import bucket_table, geom_tag

    for geom in bucket_table(cfg):
        if cfg.batch_size % n_data:
            errs.append(
                f"bucket {geom_tag(geom)}: batch_size {cfg.batch_size} is "
                f"not divisible by the mesh's data axis (n_data={n_data}); "
                f"every dispatched batch shards rows over that axis")
    return errs


def shard_batch(batch, mesh: Mesh):
    """Place a host batch onto the mesh, split along the data axis."""
    return jax.device_put(batch, batch_shardings(batch, mesh))


def shard_params(params, mesh: Mesh):
    return jax.device_put(params, params_shardings(params, mesh))
