"""Shared by the LFM2-8B-A1B (``arch="lfm2"``) tests: the program's key block
as the plain reference's configuration dict (the benchmark's file layout),
and seeded float32 weights from the benchmark's own builder."""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the tiny preset's router sees 8 experts over 64 widths: a larger bias than
# the published widths' moves a share of picks alike (weights_lfm2)
EXPERT_BIAS_STD = 0.05


def ref_cfg(lm) -> dict:
    d = dataclasses.asdict(lm)
    d.pop("prompt_buckets")
    d["expert_bias_std"] = EXPERT_BIAS_STD
    return d


def weights(lm, seed: int = 3):
    import jax.numpy as jnp

    from benchmark import weights_lfm2

    return weights_lfm2.make_params(ref_cfg(lm), seed, jnp.float32)
