"""Third-stage attribution: scatter, bmm, encoder-only vs full fwd+bwd.

Every timed program returns ONE on-device scalar that depends on all of its
real output (sums folded inside the jit), so the float() sync is honest and
the D2H transfer is 4 bytes, not the whole buffer.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import fira_full
from fira_tpu.data.batching import make_batch
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.model.model import FiraModel, dense_adjacency
from fira_tpu.train.state import init_state

from fira_tpu.utils.startup import configure_compile_cache  # noqa: E402

configure_compile_cache()

N = 8
cfg = fira_full(batch_size=170, compute_dtype="bfloat16")
cfg, split, _ = make_memory_split(cfg, 256, seed=0,
                                  pad_vocab_to=24650, pad_ast_vocab_to=71)
rng = np.random.RandomState(0)
host = [make_batch(split, rng.choice(256, 170, replace=True), cfg)
        for _ in range(2)]
model = FiraModel(cfg, dtype=jnp.bfloat16)
state = init_state(model, cfg, host[0])
params = state.params
dev = jax.device_put(host)
jax.block_until_ready(dev)
rng_key = jax.random.PRNGKey(0)


def timeit(tag, scalar_fn, *args):
    jitted = jax.jit(scalar_fn)
    t0 = time.perf_counter()
    _ = float(jitted(*args))
    compile_s = time.perf_counter() - t0
    for _ in range(N):          # saturation throwaway
        out = jitted(*args)
    _ = float(out)
    times = []
    for _w in range(2):
        t0 = time.perf_counter()
        for _ in range(N):
            out = jitted(*args)
        _ = float(out)
        times.append(time.perf_counter() - t0)
    dt = min(times) / N
    print(json.dumps({"tag": tag, "ms": round(dt * 1e3, 2),
                      "compile_s": round(compile_s, 1)}), flush=True)


b0 = dev[0]
x0 = jnp.full((170, cfg.graph_len, cfg.embedding_dim), 0.1, jnp.bfloat16)


def scatter_only(b):
    adj = dense_adjacency(b["senders"], b["receivers"], b["values"],
                          cfg.graph_len)
    return jnp.sum(adj)


def scatter_plus_6bmm(b, x):
    adj = dense_adjacency(b["senders"], b["receivers"], b["values"],
                          cfg.graph_len)
    for _ in range(6):
        x = jnp.einsum("bij,bjd->bid", adj.astype(x.dtype), x)
    return jnp.sum(x.astype(jnp.float32))


def encoder_grad_norm(p, b):
    def loss(pp):
        states, _mask = model.apply({"params": pp}, b,
                                    method=FiraModel.encode,
                                    deterministic=False,
                                    rngs={"dropout": rng_key})
        return jnp.sum(states.astype(jnp.float32))

    g = jax.grad(loss)(p)
    return sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
               for l in jax.tree_util.tree_leaves(g))


def full_grad_norm(p, b):
    def loss(pp):
        nll, cnt = model.apply({"params": pp}, b, deterministic=False,
                               rngs={"dropout": rng_key})
        return nll / jnp.maximum(cnt, 1)

    g = jax.grad(loss)(p)
    return sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
               for l in jax.tree_util.tree_leaves(g))


timeit("scatter_only", scatter_only, b0)
timeit("scatter_plus_6bmm", scatter_plus_6bmm, b0, x0)
timeit("encoder_grad", encoder_grad_norm, params, b0)
timeit("full_grad", full_grad_norm, params, b0)
