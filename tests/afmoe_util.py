"""Shared by the Trinity-Mini (``arch="afmoe"``) tests: the program's key
block as the plain reference's configuration dict (the benchmark's file
layout), and seeded float32 weights from the benchmark's own builder."""

import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True, scope="module")
def small_query_blocks():
    """Prefill attention in blocks of 4 queries: with the tiny preset's
    window of 8 a window layer then scores a band of 12 keys out of its
    bucket's 16, 32 or 64, as the real buckets do at 128 queries and a
    window of 2,048 (a module that imports this fixture has it)."""
    from fira_tpu.model import afmoe

    keep, afmoe.ATTN_Q_BLOCK = afmoe.ATTN_Q_BLOCK, 4
    yield
    afmoe.ATTN_Q_BLOCK = keep


def ref_cfg(lm) -> dict:
    d = dataclasses.asdict(lm)
    d["layer_types"] = list(lm.layer_types)
    d.pop("prompt_buckets")
    return d


def weights(lm, seed: int = 3):
    import jax.numpy as jnp

    from benchmark import weights_afmoe

    return weights_afmoe.make_params(ref_cfg(lm), seed, jnp.float32)
