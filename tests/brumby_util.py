"""Shared by the Brumby-14B-Base (``arch="brumby"``) tests: the program's
key block as the plain reference's configuration dict (the benchmark's file
layout), and seeded float32 weights from the benchmark's own ``make_params``."""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def ref_cfg(lm) -> dict:
    d = dataclasses.asdict(lm)
    d.pop("prompt_buckets")
    return d


def weights(lm, seed: int = 3):
    import jax.numpy as jnp

    from benchmark import weights_brumby

    return weights_brumby.make_params(ref_cfg(lm), seed, jnp.float32)
