"""Low-precision serving tiers for the slot engine (docs/DECODE_ENGINE.md
"Low-precision tiers").

Two independent knobs, f32 staying the default CONTRACT path (labels,
digests, and output bytes unchanged when both are "f32"):

- ``cfg.kv_dtype`` ("f32" | "bf16") — storage dtype of the decode
  self-attention K/V arena, the paged pool's blocks. The prefill
  program emits a ``cache_seed`` of this dtype (:func:`kv_seed_dtype`),
  so the engine's arena allocation and its ``kv_bytes_per_slot``
  accounting follow automatically; writes cast on append
  (model/layers.append_block_kv) and reads upcast on gather
  (``gather_block_kv``), so the attention math itself stays in the
  compute dtype. Cross-attention K/V and the copy-head source projection
  are request-lifetime activations, not the per-step arena — they stay
  f32.

- ``cfg.serve_precision`` ("f32" | "bf16" | "int8w") — weight tier of the
  DECODE-ONLY program family (step / spec draft / verify; prefill and the
  encoder keep the original params). The engine builds a quantized COPY of
  the dominant matmul weights once at construction
  (:func:`quantize_decode_params` over :data:`DECODE_WEIGHT_SCOPES` —
  decoder stack, vocab projection, copy head); a fleet respawn or spare
  prewarm re-runs it by constructing a fresh engine from the original
  params. "bf16" stores the weights half-width and the existing
  ``kernel.astype(dtype)`` upcast in the matmul layers consumes them;
  "int8w" stores per-channel symmetric int8 (:func:`quantize_int8`) and
  the step programs dequantize on the fly with f32 accumulate
  (:func:`dequant_tree` at the top of the traced step — the scales embed
  as trace-time constants, so static shapes and the declared program
  family are unchanged, labels merely suffixed via :func:`tier_tag`).

The quality contract is MEASURED, never assumed: bench records carry
``bleu_delta_vs_f32`` and ``logprob_divergence_{mean,p99}`` vs the f32
reference (docs/QUANT_BENCH_r01.jsonl), and within a tier output bytes
remain a pure function of the input stream (the engine's existing
determinism contract, re-pinned per tier in tests/test_quant_tiers.py).

Precedent: LLM.int8() (Dettmers et al.) for post-training per-channel
int8 weights with higher-precision accumulate; GShard/T5 for static-shape
mixed precision on TPU; vLLM for KV bytes — not FLOPs — capping slot
concurrency (PAPERS.md).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KV_DTYPES = ("f32", "bf16")
SERVE_PRECISIONS = ("f32", "bf16", "int8w")

# param subtrees the weight tier rewrites: the decode-side matmul owners.
# The encoder (prefill-only) and everything 1-D (biases, LayerNorm
# scales) keep the original f32 params.
DECODE_WEIGHT_SCOPES = ("decoder", "out_fc", "copy_net")


def quant_errors(cfg, *, train: bool = False) -> List[str]:
    """Parse-time validation for the serving-tier knobs. ``train=True``
    is the training path, where any non-f32 tier is rejected outright:
    quantized serving reads frozen weights, it never trains them."""
    errs: List[str] = []
    if cfg.kv_dtype not in KV_DTYPES:
        errs.append(f"kv_dtype {cfg.kv_dtype!r} not in "
                    f"{{{', '.join(map(repr, KV_DTYPES))}}}")
    if cfg.serve_precision not in SERVE_PRECISIONS:
        errs.append(f"serve_precision {cfg.serve_precision!r} not in "
                    f"{{{', '.join(map(repr, SERVE_PRECISIONS))}}}")
    armed = cfg.kv_dtype != "f32" or cfg.serve_precision != "f32"
    if train and armed:
        errs.append(
            "kv_dtype/serve_precision are serving-tier knobs; the training "
            "path runs full precision — leave both 'f32'")
        return errs
    if cfg.kv_dtype in KV_DTYPES and cfg.kv_dtype != "f32" \
            and not cfg.decode_engine:
        errs.append(
            f"kv_dtype {cfg.kv_dtype!r} requires the slot engine "
            f"(--engine / decode_engine=True): the low-precision KV "
            f"arena is the engine's slot arena")
    if cfg.serve_precision in SERVE_PRECISIONS \
            and cfg.serve_precision != "f32" and not cfg.decode_engine:
        errs.append(
            f"serve_precision {cfg.serve_precision!r} requires the slot "
            f"engine (--engine / decode_engine=True): the weight tier "
            f"quantizes the decode-only program family")
    return errs


def kv_seed_dtype(cfg, compute_dtype):
    """Dtype of the prefill program's ``cache_seed`` marker — what the
    engine allocates its K/V arena at. "f32" keeps the historical rule
    (the encoder-state dtype, which may be wider under stable_residual);
    "bf16" pins the arena half-width regardless of compute dtype."""
    return jnp.bfloat16 if cfg.kv_dtype == "bf16" else compute_dtype


def tier_tag(cfg) -> str:
    """Program-label tier mod ("" on the f32/f32 contract path, so the
    default label set is byte-for-byte unchanged). Composes into the
    engine's mods chain: ``engine_step[bf16kv.int8w.r1]``."""
    parts = []
    if cfg.kv_dtype != "f32":
        parts.append(f"{cfg.kv_dtype}kv")
    if cfg.serve_precision != "f32":
        sp = cfg.serve_precision
        parts.append(sp if sp.endswith("w") else sp + "w")
    return ".".join(parts)


def tier_namespace(cfg) -> bytes:
    """Digest namespace for prefix-cache / dedup content addressing:
    prefill artifacts carry their tier, so a cached f32 artifact can
    never seat a bf16 slot (and vice versa). Empty — digests unchanged —
    on the f32/f32 contract path."""
    tag = tier_tag(cfg)
    return tag.encode("ascii") if tag else b""


# --- per-channel symmetric int8 --------------------------------------------

def quantize_int8(w) -> Tuple[np.ndarray, np.ndarray]:
    """Per-channel symmetric int8: channel = LAST axis (the output
    features of every kernel in this stack). scale[c] = amax(|w[..., c]|)
    / 127 (zero columns get scale 1.0 so the divide is exact), values
    round-to-nearest then clip. Max absolute error per element is
    scale/2: |w| <= 127*scale means the clip never binds, so the only
    error is the rounding's half-step (pinned in tests)."""
    a = np.asarray(jax.device_get(w), np.float32)  # firacheck: allow[HOST-SYNC] engine-BUILD-time quantization (once per engine/respawn/spare prewarm, before any serving dispatch); never runs inside the step loop
    reduce_axes = tuple(range(a.ndim - 1))
    scale = np.max(np.abs(a), axis=reduce_axes) / 127.0
    scale = np.where(scale == 0.0, np.float32(1.0), scale).astype(np.float32)
    q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize_int8(q, scale):
    """f32 reconstruction (host or traced): int8 codes x per-channel
    scale, broadcast over the last axis."""
    return q.astype(jnp.float32) * scale


def _eligible(leaf) -> bool:
    """Weight-tier eligibility: float leaves of rank >= 2 — the matmul
    kernels and embedding tables. 1-D params (biases, LayerNorm
    scale/bias) stay f32: they are O(d) bytes and numerics-sensitive."""
    return (hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and getattr(leaf, "ndim", 0) >= 2)


def quantize_decode_params(params, cfg):
    """Build the decode-side param tree for ``cfg.serve_precision``.

    Returns ``(decode_params, scales)``:

    - "f32": ``(params, None)`` — the ORIGINAL tree, no copy (identity is
      what the f32 byte-identity contract rides on).
    - "bf16": eligible leaves under :data:`DECODE_WEIGHT_SCOPES` stored
      bf16, everything else shared; ``scales`` is None (the layers' own
      ``astype`` upcast consumes bf16 directly).
    - "int8w": eligible scoped leaves stored int8; ``scales`` mirrors the
      FULL tree (unquantized leaves carry a scalar 1.0 sentinel) so
      :func:`dequant_tree` is one structure-aligned tree.map inside the
      step trace.

    Quantization happens ONCE per engine build — a respawned replica or
    prewarmed spare re-runs it from the original f32 params by
    construction (parallel/fleet.py builds a fresh SlotEngine).
    """
    sp = cfg.serve_precision
    if sp == "f32":
        return params, None
    out = {}
    scales = {} if sp == "int8w" else None
    for k, v in params.items():
        if k not in DECODE_WEIGHT_SCOPES:
            out[k] = v
            if scales is not None:
                scales[k] = jax.tree.map(
                    lambda _l: np.ones((), np.float32), v)
            continue
        leaves, treedef = jax.tree_util.tree_flatten(v)
        if sp == "bf16":
            out[k] = treedef.unflatten([
                np.asarray(jax.device_get(l)).astype(jnp.bfloat16)  # firacheck: allow[HOST-SYNC] engine-BUILD-time weight cast (once per engine/respawn/spare prewarm, before any serving dispatch); never runs inside the step loop
                if _eligible(l) else l for l in leaves])
        else:
            qs, ss = [], []
            for l in leaves:
                if _eligible(l):
                    q, s = quantize_int8(l)
                else:
                    q, s = l, np.ones((), np.float32)
                qs.append(q)
                ss.append(s)
            out[k] = treedef.unflatten(qs)
            scales[k] = treedef.unflatten(ss)
    return out, scales


def dequant_tree(params, scales):
    """On-the-fly dequant at the top of the decode-only traced programs:
    int8 leaves reconstruct to f32 against their per-channel scales
    (embedded as trace-time constants), every other leaf passes through.
    ``scales is None`` (f32/bf16 tiers) is the identity — the call sites
    stay branch-free in the trace."""
    if scales is None:
        return params

    def dq(p, s):
        if p.dtype == jnp.int8:
            return dequantize_int8(p, s)
        return p

    return jax.tree.map(dq, params, scales)
