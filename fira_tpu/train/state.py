"""Train state + checkpointing.

The reference checkpoints only the best-on-dev ``model.state_dict()``
(/root/reference/run_model.py:94-96) — no optimizer state, no resume. Here
the full train state (step, params, Adam moments, dev-gating bookkeeping,
PRNG key) round-trips through orbax, so a preempted TPU run resumes exactly;
the best-on-dev params are additionally kept as their own checkpoint, like
the reference's ``best_model.pt``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from fira_tpu.config import FiraConfig
from fira_tpu.model.model import FiraModel


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    rng: jax.Array


def prng_impl_name(cfg_value: str) -> str:
    """Map the config's generator name to JAX's registered impl name."""
    return {"threefry": "threefry2x32"}.get(cfg_value, cfg_value)


def make_optimizer(cfg: FiraConfig) -> optax.GradientTransformation:
    """Adam(lr=1e-4) with torch defaults (run_model.py:396): betas (0.9,
    0.999), eps 1e-8 — identical to optax defaults."""
    return optax.adam(cfg.lr)


def init_state(model: FiraModel, cfg: FiraConfig, sample_batch: Dict[str, Any],
               seed: Optional[int] = None) -> TrainState:
    # rng_impl "rbg" swaps the dropout-stream generator for the
    # hardware-friendly RBG one (threefry is the reproducible-everywhere
    # default). Param INIT always uses threefry so initial weights are
    # identical across the knob; only the dropout stream differs. A
    # checkpoint stores the key, so resumes must keep the same impl.
    impl = prng_impl_name(cfg.rng_impl)
    s = cfg.seed if seed is None else seed
    init_rng, _ = jax.random.split(jax.random.PRNGKey(s))
    # State carries RAW key data (orbax-serializable); train_step re-wraps it
    # with cfg.rng_impl. For threefry this is bit-identical to the historical
    # split(PRNGKey(seed))[1] layout.
    state_rng = jax.random.key_data(
        jax.random.split(jax.random.key(s, impl=impl))[1])
    # Parameter shapes do not depend on the batch size, and flax runs init's
    # forward pass op by op on the default device: at a mesh's global batch
    # that is the whole (B, T, S, D) copy-head intermediate on device 0
    # (10 GB at 4 x 170, seen on the v5e — PERF.md "Bring-up"). One row gives
    # bit-identical parameters.
    one_row = {k: v[:1] for k, v in sample_batch.items()
               if not k.startswith("_")}
    params = model.init(init_rng, one_row, deterministic=True)["params"]
    opt_state = make_optimizer(cfg).init(params)
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=opt_state, rng=state_rng,
    )


def param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


class CheckpointManager:
    """Orbax-backed save/restore of (state, best_params, metadata)."""

    LATEST = "latest"
    BEST = "best"

    def __init__(self, ckpt_dir: str):
        import orbax.checkpoint as ocp

        self.ckpt_dir = os.path.abspath(ckpt_dir)
        os.makedirs(self.ckpt_dir, exist_ok=True)
        self._ckpt = ocp.PyTreeCheckpointer()

    def _path(self, name: str) -> str:
        return os.path.join(self.ckpt_dir, name)

    def save_latest(self, state: TrainState, *, best_bleu: float,
                    epoch: int, rng_impl: str = "threefry") -> None:
        payload = {
            "state": jax.device_get(state),
            "meta": {"best_bleu": float(best_bleu), "epoch": int(epoch),
                     "rng_impl": rng_impl},
        }
        self._ckpt.save(self._path(self.LATEST), payload, force=True)

    def save_best(self, params) -> None:
        """The reference's best_model.pt equivalent (run_model.py:96):
        params only, gated on dev BLEU by the caller."""
        self._ckpt.save(self._path(self.BEST), jax.device_get(params),
                        force=True)

    def has(self, name: str) -> bool:
        return os.path.isdir(self._path(name))

    def restore_latest(self, template_state: TrainState, *,
                       expect_rng_impl: Optional[str] = None
                       ) -> Tuple[TrainState, Dict[str, Any]]:
        state_t = jax.device_get(template_state)
        # Probe the saved tree's structure to decide the restore template:
        # checkpoints written before the rng_impl field lack meta.rng_impl,
        # and restoring them against a template that has it raises. Probing
        # (rather than try/restore/except Exception) keeps a transient I/O
        # failure from being misread as "old checkpoint" and silently
        # mislabelled threefry (advisor r3).
        meta_t = {"best_bleu": 0.0, "epoch": 0, "rng_impl": "threefry"}
        # orbax changed the metadata() return shape across versions: older
        # releases hand back the metadata tree as a plain dict, newer ones
        # wrap it in CheckpointMetadata.item_metadata.tree
        meta_obj = self._ckpt.metadata(self._path(self.LATEST))
        if hasattr(meta_obj, "item_metadata"):
            meta_obj = meta_obj.item_metadata.tree
        saved_meta_keys = (meta_obj or {}).get("meta", {})
        if "rng_impl" not in saved_meta_keys:
            del meta_t["rng_impl"]
        payload = self._ckpt.restore(
            self._path(self.LATEST),
            item={"state": state_t, "meta": meta_t},
        )
        payload["meta"].setdefault("rng_impl", "threefry")
        saved_impl = payload["meta"].get("rng_impl", "threefry")
        if expect_rng_impl is not None and saved_impl != expect_rng_impl:
            # fail HERE with the cause, not later with an opaque key-shape
            # error inside the jitted step's wrap_key_data
            raise ValueError(
                f"checkpoint was trained with rng_impl={saved_impl!r} but "
                f"this run is configured with rng_impl={expect_rng_impl!r}; "
                f"resume with the matching --rng-impl or use a fresh "
                f"checkpoint dir")
        return payload["state"], payload["meta"]

    def restore_best(self, template_params):
        return self._ckpt.restore(self._path(self.BEST),
                                  item=jax.device_get(template_params))
