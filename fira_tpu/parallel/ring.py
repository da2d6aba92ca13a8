"""Ring attention: sequence-parallel exact attention over a mesh axis.

The reference has no long-context machinery at all — every sequence length is
a small compile-time constant (/root/reference/run_model.py:31-35) and
attention spans at most 370 keys. This module is the capability the TPU
framework adds on top of parity: exact attention whose keys/values are
sharded across devices on a ``seq`` mesh axis, with K/V blocks rotating
around the ICI ring (``jax.lax.ppermute``) while each device keeps a running
flash-style online softmax. Peak memory per device is O(T_local^2) instead of
O(T^2), and the rotation overlaps with compute, so sequences can scale with
the mesh.

Numerics contract: identical (up to fp error) to the repo's dense attention
— additive ``-1e9`` masking where mask==0 (model/layers.py Attention), NOT
-inf, so fully-masked queries produce the same uniform-ish softmax as the
dense path instead of NaN.

Usage: the ``ring_*`` functions are per-shard bodies meant to run inside
``shard_map`` over a mesh with a ``seq`` axis (see ``seq_mesh`` /
``ring_attention_sharded``).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _sharded(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


SEQ_AXIS = "seq"
NEG_INF = -1e9


def seq_mesh(n_data: int, n_seq: int,
             devices: Optional[Sequence] = None) -> Mesh:
    """A (data, seq) mesh for sequence-parallel attention."""
    from fira_tpu.parallel.mesh import make_mesh

    return make_mesh(n_data=n_data, n_model=n_seq, devices=devices,
                     axis_names=("data", SEQ_AXIS))


def _block(q, k, v, kv_mask, bias):
    """One attention block's (unnormalized) contribution with running max.

    Returns (m, l, o): rowwise max of the masked scores, sum of exp, and the
    exp-weighted value accumulation, all float32.
    """
    d_head = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    s = s / np.sqrt(d_head)
    s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                                   # (B,H,Tq)
    p = jnp.exp(s - m[..., None])                             # (B,H,Tq,Tk)
    l = jnp.sum(p, axis=-1)                                   # (B,H,Tq)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m, l, o


def ring_attention(q, k, v, kv_mask, *, axis_name: str = SEQ_AXIS,
                   causal: bool = False):
    """Exact attention with K/V sharded over ``axis_name`` (per-shard body).

    q:       (B, H, Tq_local, Dh)  — queries of this shard
    k, v:    (B, H, Tk_local, Dh)  — this shard's K/V block (rotates)
    kv_mask: (B, Tk_local) bool    — key-padding mask (rotates with K/V)
    causal:  mask out keys with global position > the query's global
             position (both sequences assumed sharded contiguously:
             global position = shard_index * local_len + local offset).

    Returns (B, H, Tq_local, Dh) in q.dtype.
    """
    n_shards = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, Tq, Dh = q.shape
    Tk = k.shape[2]

    q_pos = my_idx * Tq + jnp.arange(Tq)                      # global q rows

    def causal_bias(src_idx):
        k_pos = src_idx * Tk + jnp.arange(Tk)
        allowed = k_pos[None, :] <= q_pos[:, None]            # (Tq, Tk)
        return jnp.where(allowed, 0.0, NEG_INF)[None, None, :, :]

    def merge(carry, k_i, v_i, mask_i, src_idx):
        m_run, l_run, o_run = carry
        bias = causal_bias(src_idx) if causal else None
        m_blk, l_blk, o_blk = _block(q, k_i, v_i, mask_i, bias)
        m_new = jnp.maximum(m_run, m_blk)
        alpha = jnp.exp(m_run - m_new)                        # rescale old
        beta = jnp.exp(m_blk - m_new)                         # rescale new
        l_new = l_run * alpha + l_blk * beta
        o_new = o_run * alpha[..., None] + o_blk * beta[..., None]
        return m_new, l_new, o_new

    def step(i, carry):
        acc, k_i, v_i, mask_i = carry
        # rotate FIRST (the local block was consumed before the loop), so
        # the final iteration's rotation isn't dead work: n_shards-1
        # permutes total, like standard ring-attention schedules
        perm = [(j, (j - 1) % n_shards) for j in range(n_shards)]
        k_i = jax.lax.ppermute(k_i, axis_name, perm)
        v_i = jax.lax.ppermute(v_i, axis_name, perm)
        mask_i = jax.lax.ppermute(mask_i, axis_name, perm)
        acc = merge(acc, k_i, v_i, mask_i, (my_idx + i) % n_shards)
        return acc, k_i, v_i, mask_i

    # Initial running max NEG_INF (matches dense masking floor); one block is
    # always processed, so l > 0 even fully masked, exactly like the dense
    # softmax over all -1e9 rows.
    m0 = jnp.full((B, H, Tq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, Tq), dtype=jnp.float32)
    o0 = jnp.zeros((B, H, Tq, Dh), dtype=jnp.float32)

    acc = merge((m0, l0, o0), k, v, kv_mask, my_idx)  # local block, no comm
    (m_f, l_f, o_f), *_ = jax.lax.fori_loop(1, n_shards, step,
                                            (acc, k, v, kv_mask))
    out = o_f / l_f[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, kv_mask, mesh: Mesh, *,
                           causal: bool = False,
                           batch_axis: str = "data",
                           seq_axis: str = SEQ_AXIS):
    """shard_map wrapper: q/k/v (B, H, T, Dh) sharded on batch + sequence
    axes; returns the attention output with the same sharding as q."""
    qkv_spec = P(batch_axis, None, seq_axis, None)
    mask_spec = P(batch_axis, seq_axis)
    body = functools.partial(ring_attention, causal=causal,
                             axis_name=seq_axis)
    fn = _sharded(
        body, mesh,
        (qkv_spec, qkv_spec, qkv_spec, mask_spec),
        qkv_spec,
    )
    return fn(q, k, v, kv_mask)


def dense_reference_attention(q, k, v, kv_mask, *, causal: bool = False):
    """Single-device oracle with the exact masking semantics ring_attention
    must reproduce (used by tests and docs)."""
    d_head = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d_head)
    s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        allowed = jnp.arange(Tk)[None, :] <= jnp.arange(Tq)[:, None]
        s = s + jnp.where(allowed, 0.0, NEG_INF)[None, None, :, :]
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)
