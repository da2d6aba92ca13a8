"""Fused Bahdanau pointer scoring: score[b,t,s] = w . tanh(src[b,s] + tgt[b,t]) + bias.

This is the copy head's hot op (reference CopyNet, /root/reference/Model.py:
7-20: ``LinearRes(tanh(W_s.src_j + W_t.tgt_i))``). Naively it materializes a
(B, T, S, D) intermediate — 7.7 GB at the flagship geometry (B=170, T=30,
S=370, D=256) — which either OOMs alongside model+optimizer state or forces
rematerialization and small batches. The Pallas kernel streams S in chunks
through VMEM and never writes the intermediate to HBM: forward emits only
the (B, T, S) scores; the custom-VJP backward recomputes tanh chunkwise and
emits exactly the gradients (dsrc, dtgt, dw, dbias). Peak memory is
O(B.S.D) — the win is memory headroom, i.e. batch size, not speed (the
kernel pins tanh to f32 for precision; XLA's fused path runs it in the
compute dtype) — so "xla" stays the default and "pallas" is the choice when
the intermediate doesn't fit. Its time on the chip is not measured;
``chip_smoke.py`` compiles it there (forward and backward, d 256 and d 512),
checks it against the oracle and runs a train dispatch through it (PERF.md
"Bring-up"; ROADMAP D1 decides whether it stays).

On the CPU backend the same kernels run under the Pallas interpreter, so
CPU tests validate the math; ``copy_scores_reference`` is the XLA oracle
both paths are checked against.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_CHUNK = 128          # S-chunk streamed through VMEM
_T_ALIGN = 8          # sublane alignment for the T dimension


def copy_scores_reference(src, tgt, w, bias):
    """XLA oracle: materializes the (B, T, S, D) intermediate."""
    inter = jnp.tanh(src[:, None, :, :] + tgt[:, :, None, :])
    return jnp.dot(inter, w)[..., 0] + bias[0]


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fwd_kernel(src_ref, tgt_ref, w_ref, out_ref):
    # tanh + matvec run in f32 whatever the input dtype: Mosaic rejects
    # HIGHEST-precision matmuls on bf16 operands, and f32 keeps parity with
    # XLA's fused path; the op is VPU-tanh-bound so this costs nothing.
    tgt = tgt_ref[0].astype(jnp.float32)                 # (Tp, D)
    Tp, D = tgt.shape
    n_chunks = src_ref.shape[1] // _CHUNK

    def body(j, _):
        s = src_ref[0, pl.ds(j * _CHUNK, _CHUNK), :].astype(jnp.float32)
        x = jnp.tanh(s[None, :, :] + tgt[:, None, :])    # (Tp, C, D)
        sc = jnp.dot(x.reshape(-1, D), w_ref[:, :],
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)  # (Tp*C, 1)
        out_ref[0, :, pl.ds(j * _CHUNK, _CHUNK)] = (
            sc.reshape(Tp, _CHUNK).astype(out_ref.dtype))
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


def _bwd_kernel(src_ref, tgt_ref, w_ref, dout_ref,
                dsrc_ref, dtgt_ref, dw_ref):
    tgt = tgt_ref[0].astype(jnp.float32)                 # (Tp, D)
    Tp, D = tgt.shape
    w = w_ref[:, 0].astype(jnp.float32)                  # (D,)
    n_chunks = src_ref.shape[1] // _CHUNK

    def body(j, carry):
        dtgt_acc, dw_acc = carry
        s = src_ref[0, pl.ds(j * _CHUNK, _CHUNK), :].astype(jnp.float32)
        dout = dout_ref[0, :, pl.ds(j * _CHUNK, _CHUNK)].astype(jnp.float32)
        x = jnp.tanh(s[None, :, :] + tgt[:, None, :])    # (Tp, C, D)
        g = (1.0 - x * x) * w[None, None, :] * dout[..., None]
        dsrc_ref[0, pl.ds(j * _CHUNK, _CHUNK), :] = (
            jnp.sum(g, axis=0).astype(dsrc_ref.dtype))
        dtgt_acc = dtgt_acc + jnp.sum(g, axis=1)
        dw_acc = dw_acc + jnp.sum(x * dout[..., None], axis=(0, 1))
        return dtgt_acc, dw_acc

    dtgt_acc = jnp.zeros((Tp, D), jnp.float32)
    dw_acc = jnp.zeros((D,), jnp.float32)
    dtgt_acc, dw_acc = jax.lax.fori_loop(0, n_chunks, body,
                                         (dtgt_acc, dw_acc))
    dtgt_ref[0] = dtgt_acc.astype(dtgt_ref.dtype)
    dw_ref[0] = dw_acc[:, None].astype(dw_ref.dtype)


def _use_interpret(interpret: Optional[bool]) -> bool:
    """Interpret only on the CPU backend (where Mosaic cannot compile);
    any other backend compiles the kernel or fails loudly — an
    accelerator run never falls to the interpreter unasked."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def copy_scores(src, tgt, w, bias, interpret: Optional[bool] = None):
    """Fused pointer scores. src: (B,S,D), tgt: (B,T,D), w: (D,1),
    bias: (1,). Returns (B,T,S) in src.dtype."""
    return _copy_scores_fwd_impl(src, tgt, w, bias, interpret)


def _copy_scores_fwd_impl(src, tgt, w, bias, interpret):
    B, S, D = src.shape
    T = tgt.shape[1]
    src_p = _pad_to(src, 1, _CHUNK)
    tgt_p = _pad_to(tgt, 1, _T_ALIGN)
    Sp, Tp = src_p.shape[1], tgt_p.shape[1]

    out = pl.pallas_call(
        _fwd_kernel,
        out_shape=jax.ShapeDtypeStruct((B, Tp, Sp), src.dtype),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Sp, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Tp, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((D, 1), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Tp, Sp), lambda b: (b, 0, 0)),
        interpret=_use_interpret(interpret),
    )(src_p, tgt_p, w.astype(jnp.float32))
    return out[:, :T, :S] + bias[0].astype(src.dtype)


def _copy_scores_fwd(src, tgt, w, bias, interpret):
    return _copy_scores_fwd_impl(src, tgt, w, bias, interpret), (src, tgt, w)


def _copy_scores_bwd(interpret, residuals, dout):
    src, tgt, w = residuals
    B, S, D = src.shape
    T = tgt.shape[1]
    src_p = _pad_to(src, 1, _CHUNK)
    tgt_p = _pad_to(tgt, 1, _T_ALIGN)
    Sp, Tp = src_p.shape[1], tgt_p.shape[1]
    # zero-padded dout => padded rows/cols contribute nothing to any grad
    dout_p = _pad_to(_pad_to(dout, 1, _T_ALIGN), 2, _CHUNK)

    dsrc_p, dtgt_p, dw_part = pl.pallas_call(
        _bwd_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, D), src.dtype),
            jax.ShapeDtypeStruct((B, Tp, D), tgt.dtype),
            jax.ShapeDtypeStruct((B, D, 1), jnp.float32),
        ],
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Sp, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Tp, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((D, 1), lambda b: (0, 0)),
            pl.BlockSpec((1, Tp, Sp), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Sp, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Tp, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, D, 1), lambda b: (b, 0, 0)),
        ],
        interpret=_use_interpret(interpret),
    )(src_p, tgt_p, w.astype(jnp.float32), dout_p)

    dsrc = dsrc_p[:, :S, :]
    dtgt = dtgt_p[:, :T, :]
    dw = jnp.sum(dw_part, axis=0).astype(w.dtype)
    dbias = jnp.sum(dout).reshape(1).astype(w.dtype)
    return dsrc, dtgt, dw, dbias


copy_scores.defvjp(_copy_scores_fwd, _copy_scores_bwd)
