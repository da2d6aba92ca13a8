"""Fourth-stage attribution: the adjacency scatter under the production
config, and candidate replacements.

Round-4 per-op trace + diag3 put the COO->dense adjacency scatter at the
top of the step attribution (~22 ms f32/unsorted/8192-pad of the 86 ms
base step). Variants here isolate, on real TPU with the honest D2H sync:

  scatter_8192_f32       diag3's row (baseline continuity)
  scatter_8192_bf16_sorted  the round-4 production path at the old pad
  scatter_6144_bf16_sorted  production path at the new fira-full pad
  scatter_flat_6144      linearized 1-D scatter: flat = (b*N+s)*N+r int32,
                         scatter into (B*N*N,), reshape — with sort_edges
                         the stream is fully ascending (pads (0,0) sort
                         first within each row and rows ascend), so
                         indices_are_sorted covers the whole stream
  matvec_6144            scatter + 6 bmm (GCN-shaped consumption check)

Every program folds its output to one scalar inside the jit; float() of
that scalar is the sync (4-byte D2H). See docs/PERF.md "Measurement
integrity".
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
from fira_tpu.model.model import dense_adjacency

from fira_tpu.utils.startup import configure_compile_cache  # noqa: E402

configure_compile_cache()

N = 8
B = 170


def batch_for(max_edges: int, sort: bool, dtype: str = "bfloat16"):
    # NOTE dtype also controls the wire: make_batch ships bf16 edge values
    # under bf16 compute (dense/untyped path), f32 otherwise — the f32
    # continuity row below must therefore build an f32 config
    cfg = fira_full(batch_size=B, compute_dtype=dtype,
                    max_edges=max_edges, sort_edges=sort)
    cfg, split, _ = make_memory_split(cfg, 256, seed=0)
    rng = np.random.RandomState(0)
    b = make_batch(split, rng.choice(256, B, replace=True), cfg)
    d = jax.device_put({k: b[k] for k in ("senders", "receivers", "values")})
    jax.block_until_ready(d)
    return cfg, d


def timeit(tag, fn, *args):
    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    _ = float(jitted(*args))
    compile_s = time.perf_counter() - t0
    for _ in range(N):
        out = jitted(*args)
    _ = float(out)
    times = []
    for _w in range(2):
        t0 = time.perf_counter()
        for _ in range(N):
            out = jitted(*args)
        _ = float(out)
        times.append(time.perf_counter() - t0)
    print(json.dumps({"tag": tag, "ms": round(min(times) / N * 1e3, 2),
                      "compile_s": round(compile_s, 1)}), flush=True)


def flat_adjacency(senders, receivers, values, graph_len, sorted_flag):
    Bx, E = senders.shape
    b_idx = jnp.arange(Bx, dtype=jnp.int32)[:, None]
    flat = ((b_idx * graph_len + senders.astype(jnp.int32)) * graph_len
            + receivers.astype(jnp.int32))
    out = jnp.zeros((Bx * graph_len * graph_len,), jnp.bfloat16)
    out = out.at[flat.reshape(-1)].add(values.astype(jnp.bfloat16).reshape(-1),
                                       indices_are_sorted=sorted_flag)
    return out.reshape(Bx, graph_len, graph_len)


cfg_f32, d_8192 = batch_for(8192, sort=False, dtype="float32")
GL = cfg_f32.graph_len

timeit("scatter_8192_f32",
       lambda d: jnp.sum(dense_adjacency(
           d["senders"], d["receivers"], d["values"], GL)), d_8192)

_, d_8192s = batch_for(8192, sort=True)
timeit("scatter_8192_bf16_sorted",
       lambda d: jnp.sum(dense_adjacency(
           d["senders"], d["receivers"], d["values"], GL,
           indices_sorted=True, out_dtype=jnp.bfloat16).astype(jnp.float32)),
       d_8192s)

_, d_6144s = batch_for(6144, sort=True)
timeit("scatter_6144_bf16_sorted",
       lambda d: jnp.sum(dense_adjacency(
           d["senders"], d["receivers"], d["values"], GL,
           indices_sorted=True, out_dtype=jnp.bfloat16).astype(jnp.float32)),
       d_6144s)

timeit("scatter_flat_6144",
       lambda d: jnp.sum(flat_adjacency(
           d["senders"], d["receivers"], d["values"], GL, True
       ).astype(jnp.float32)), d_6144s)

x0 = jnp.full((B, GL, 256), 0.1, jnp.bfloat16)


def matvec(d, x):
    adj = dense_adjacency(d["senders"], d["receivers"], d["values"], GL,
                          indices_sorted=True, out_dtype=jnp.bfloat16)
    for _ in range(6):
        x = jnp.einsum("bij,bjd->bid", adj, x)
    return jnp.sum(x.astype(jnp.float32))


timeit("matvec_6144", matvec, d_6144s, x0)

# equivalence pin: flat and 3-D scatter agree bit-for-bit
a3 = dense_adjacency(d_6144s["senders"], d_6144s["receivers"],
                     d_6144s["values"], GL, indices_sorted=True,
                     out_dtype=jnp.bfloat16)
a1 = flat_adjacency(d_6144s["senders"], d_6144s["receivers"],
                    d_6144s["values"], GL, True)
print(json.dumps({"tag": "flat_equals_3d",
                  "equal": bool(jnp.all(a3 == a1))}), flush=True)
