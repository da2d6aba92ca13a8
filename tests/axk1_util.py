"""Shared by the A.X-K1 tests: the program's ``lm`` block as the plain
reference's configuration dict (the benchmark's file layout), and seeded
float32 weights from the benchmark's own builder."""

import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True, scope="module")
def small_query_blocks():
    """Prefill attention in blocks of 16 queries, so that the tiny preset's
    buckets (16, 32, 64) run one, two and four blocks in two spans, as the
    real buckets do at 128 (a module that imports this fixture has it)."""
    from fira_tpu.model import axk1

    keep, axk1.ATTN_Q_BLOCK = axk1.ATTN_Q_BLOCK, 16
    yield
    axk1.ATTN_Q_BLOCK = keep


def ref_cfg(lm) -> dict:
    d = dataclasses.asdict(lm)
    d["rope_scaling"] = {
        "type": "yarn", "factor": lm.rope_factor,
        "beta_fast": lm.rope_beta_fast, "beta_slow": lm.rope_beta_slow,
        "mscale": lm.rope_mscale, "mscale_all_dim": lm.rope_mscale_all_dim,
        "original_max_position_embeddings":
            lm.rope_original_max_position_embeddings}
    d["published"] = {"n_routed_experts": lm.n_routed_experts}
    d["n_routed_experts"] = lm.experts_held      # the file counts the held
    for k in [k for k in d if k.startswith("rope_")
              and k not in ("rope_theta", "rope_scaling")]:
        d.pop(k)
    d.pop("prompt_buckets")
    return d


def weights(lm, seed: int = 3):
    import jax.numpy as jnp

    from benchmark import weights_axk1

    return weights_axk1.make_params(ref_cfg(lm), seed, jnp.float32)
