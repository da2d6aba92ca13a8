"""FIRA model: GCN graph encoder + Transformer decoder + dual copy head.

TPU-first rebuild of /root/reference/Model.py and gnn_transformer.py. The
whole forward is one jittable program over fixed shapes. The adjacency
arrives as COO triplets and is applied per ``cfg.adjacency_impl``: "dense"
scatters it once per call into a (B, graph_len, graph_len) array reused by
all GCN rounds (an MXU bmm, right for the reference's 650 nodes); "segment"
keeps it as COO and message-passes by gather/scatter in O(edges), the path
that scales past that geometry. Everything else is batched matmuls.

Live-path math matches the reference exactly (parity-tested by weight
transplant in tests/test_model_parity.py); the dead modules (Encoder.lstm,
combination_list1, TransModel.gate_fc, the attr input) are omitted
(SURVEY.md Appendix B).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from fira_tpu.config import FiraConfig
from fira_tpu.model.layers import (
    stable_dtype,
    append_block_kv,
    gather_block_kv,
    lane_mask,
    Attention,
    Combination,
    FeedForward,
    GCN,
    TorchDense,
    position_encoding,
    torch_bias_init,
    torch_embed_init,
    torch_kernel_init,
)
from fira_tpu.ops import copy_score


def dense_adjacency(senders, receivers, values, graph_len: int,
                    indices_sorted: bool = False,
                    out_dtype=None) -> jnp.ndarray:
    """Scatter padded COO triplets into a dense batched adjacency.

    Pad entries are (0, 0, 0.0); scatter-ADD of zero is a no-op, so no
    masking is needed. Replaces the reference's host-side per-sample densify
    (Dataset.py:336-343) with one on-device scatter per step: ONE
    linearized 1-D scatter, flat = (b*N + s)*N + r. (A batched N-D scatter
    ``adj.at[b, s, r]`` fills the same cells on the CPU but under
    ``indices_are_sorted`` dropped most edges on the TPU at batch 340 and
    680 — PERF.md section 7; chip_smoke.py's ``adjacency`` phase holds
    this one to a plain float32 scatter there.)
    ``out_dtype``: scatter directly in the compute dtype instead of f32 —
    bit-identical to scattering f32 then casting, because graph_build's
    dedup guarantees each cell receives exactly one value (plus exact zero
    pads), so no cross-edge accumulation happens in the narrow dtype; the
    (B, N, N) buffer is built at half the bytes with no cast pass.
    ``indices_sorted``: promise that the (batch-major, cell-ascending) index
    stream is sorted — so XLA can skip its scatter sorting prologue. Under
    sort_edges the flat stream is FULLY ascending (pads (0,0) sort first
    within each row and rows ascend), so the promise covers the whole
    stream.

    CALLER CONTRACT: pass ``indices_sorted=True`` ONLY for batches built by
    ``data.batching.make_batch`` under ``cfg.sort_edges=True`` (it performs
    the host-side sort this flag promises). A hand-built batch with unsorted
    triplets under this flag produces silently undefined scatter results on
    TPU — there is no runtime check.
    """
    B, _ = senders.shape
    dt = values.dtype if out_dtype is None else out_dtype
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    # indices travel int16 to halve H2D traffic; scatter wants int32
    idx = ((b_idx * graph_len + senders.astype(jnp.int32)) * graph_len
           + receivers.astype(jnp.int32))
    out = jnp.zeros((B * graph_len * graph_len,), dtype=dt)
    out = out.at[idx.reshape(-1)].add(
        values.astype(dt).reshape(-1), indices_are_sorted=indices_sorted)
    return out.reshape(B, graph_len, graph_len)


def coo_matvec(senders, receivers, values, x,
               indices_sorted: bool = False) -> jnp.ndarray:
    """(A @ x) directly on COO triplets: gather each edge's source column,
    weight, scatter-add into its destination row. Semantically identical to
    ``dense_adjacency(...) @ x`` (dense[b, senders, receivers] = values), but
    O(edges) instead of O(graph_len^2) — the message-passing path for graphs
    larger than the reference's 650 nodes. Pad edges (0,0,0.0) contribute 0.
    ``indices_sorted``: cfg.sort_edges ordered each row by (sender,
    receiver), so the (b, s) scatter stream here is sorted too. Same caller
    contract as ``dense_adjacency``: only ``make_batch``-built batches
    satisfy the promise; violating it is silently undefined on TPU.
    """
    B = senders.shape[0]
    b_idx = jnp.arange(B)[:, None]
    senders = senders.astype(jnp.int32)    # indices travel int16 (H2D size)
    receivers = receivers.astype(jnp.int32)
    # accumulate in f32 like the dense einsum does on the MXU: bf16 scatter
    # sums over high-in-degree nodes would otherwise drift from the dense path
    acc_dtype = stable_dtype(x.dtype)
    msgs = x.astype(acc_dtype)[b_idx, receivers] * values[..., None].astype(acc_dtype)
    out = jnp.zeros(x.shape, acc_dtype).at[b_idx, senders].add(
        msgs, indices_are_sorted=indices_sorted)
    return out.astype(x.dtype)


class Encoder(nn.Module):
    """gnn_transformer.py:21-62: embeddings + 6 rounds of
    {mark-fusion Combination -> concat [diff || sub || ast_change] -> GCN}."""

    cfg: FiraConfig
    dtype: jnp.dtype = jnp.float32

    def _residual_dtype(self):
        return None if self.cfg.stable_residual else self.dtype

    @nn.compact
    def __call__(self, diff, mark, ast_change, adj, sub_token,
                 *, deterministic: bool):
        cfg = self.cfg
        word_embed = nn.Embed(
            cfg.vocab_size, cfg.embedding_dim,
            embedding_init=torch_embed_init, dtype=self.dtype, name="word_embed",
        )
        mark_embed = nn.Embed(
            4, cfg.embedding_dim,
            embedding_init=torch_embed_init, dtype=self.dtype, name="mark_embed",
        )
        ast_change_embed = nn.Embed(
            cfg.ast_change_vocab_size, cfg.embedding_dim,
            embedding_init=torch_embed_init, dtype=self.dtype,
            name="ast_change_embed",
        )

        # padding_idx=0 semantics (gnn_transformer.py:32-39): pad rows
        # contribute exactly zero.
        def embed_padded(table, ids):
            return table(ids) * (ids != 0)[..., None].astype(self.dtype)

        pos = jnp.asarray(position_encoding(cfg.sou_len, cfg.embedding_dim),
                          dtype=self.dtype)
        input_em = embed_padded(word_embed, diff) + pos[None, :, :]
        mark_em = embed_padded(mark_embed, mark)
        ast_change_em = embed_padded(ast_change_embed, ast_change)
        sub_token_em = embed_padded(word_embed, sub_token)

        # One persistent (B, graph_len, d) node buffer for the whole stack:
        # each round the Combination rewrites only the first sou_len rows
        # (diff nodes fused with their marks) in place, then the GCN mixes
        # the full graph. The reference splits the buffer into three tensors
        # and re-concatenates every round (gnn_transformer.py:46-58) — six
        # (B, 650, 256) relayout copies per step that a static update-slice
        # never materializes. Same values, same parameter tree.
        if cfg.encoder_buffer not in ("single", "split"):
            raise ValueError(
                f"unknown encoder_buffer {cfg.encoder_buffer!r}; "
                f"choose 'single' or 'split'")
        split = cfg.encoder_buffer == "split"
        if split and callable(adj):
            raise ValueError(
                "encoder_buffer='split' needs the dense adjacency (its A.x "
                "runs as two column slabs); use adjacency_impl='dense'")
        if split:
            top = input_em
            rest = jnp.concatenate([sub_token_em, ast_change_em], axis=1)
            # loop-invariant column slabs: sliced once, reused by all rounds
            adj = (adj[:, :, : cfg.sou_len], adj[:, :, cfg.sou_len :])
            graph_em = (top, rest)
        else:
            graph_em = jnp.concatenate(
                [input_em, sub_token_em, ast_change_em], axis=1)
        for i in range(cfg.num_layers):
            input_em = graph_em[0] if split else graph_em[:, : cfg.sou_len]
            input_em = Combination(
                num_heads=cfg.num_head, d_model=cfg.embedding_dim,
                dropout_rate=cfg.dropout_rate, dtype=self.dtype,
                residual_dtype=self._residual_dtype(),
                name=f"combination_{i}",
            )(input_em, input_em, mark_em, deterministic=deterministic)
            # the buffer update does not promote dtypes the way the old
            # concatenate did: round 0's buffer is the compute dtype while
            # the Combination's post-LN output is the stable dtype — cast
            # the update (f32/f64: no-op; bf16: affects only round 0's GCN
            # residual precision, the fc1 input is cast either way)
            if split:
                graph_em = (input_em.astype(graph_em[1].dtype), graph_em[1])
            else:
                graph_em = jax.lax.dynamic_update_slice_in_dim(
                    graph_em, input_em.astype(graph_em.dtype), 0, axis=1)
            graph_em = GCN(
                d_model=cfg.embedding_dim, dropout_rate=cfg.gcn_dropout_rate,
                dtype=self.dtype, residual_dtype=self._residual_dtype(),
                name=f"gcn_{i}",
            )(graph_em, adj, deterministic=deterministic)

        if split:
            return graph_em[0], graph_em[1][:, : cfg.sub_token_len]
        return (graph_em[:, : cfg.sou_len],
                graph_em[:, cfg.sou_len : cfg.sou_len + cfg.sub_token_len])


class Decoder(nn.Module):
    """gnn_transformer.py:88-122: 6 x {causal self-attn, cross-attn over
    [diff || sub-token] encoder states, FFN}, all post-LN.

    setup-based so the KV-cached decode path (``cross_kv`` once per batch +
    ``decode_step`` once per position) can reuse the exact same parameters
    as the full-prefix ``__call__``. Layer scope names (self_attn_i /
    cross_attn_i / ffn_i / embed) are unchanged from the previous compact
    layout — checkpoints and parity tests see the same tree."""

    cfg: FiraConfig
    dtype: jnp.dtype = jnp.float32
    ring_mesh: object = None  # (data, seq) mesh for cross-attention SP

    def setup(self):
        cfg = self.cfg
        # no padding_idx on the decoder embedding (gnn_transformer.py:93-94)
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.embedding_dim,
            embedding_init=torch_embed_init, dtype=self.dtype,
        )
        for i in range(cfg.num_layers):
            # setattr keeps the historical per-layer scope names; Flax
            # registers setup attribute assignments whatever their spelling
            rdt = None if cfg.stable_residual else self.dtype
            setattr(self, f"self_attn_{i}", Attention(
                num_heads=cfg.num_head, d_model=cfg.embedding_dim,
                dropout_rate=cfg.dropout_rate, dtype=self.dtype,
                residual_dtype=rdt))
            # only cross-attention rides the ring: its key axis ([diff||sub]
            # source states) is the one that grows with context length;
            # causal self-attention stays dense (attend() keeps causal=True
            # off the ring path, and these layers get no ring_mesh)
            setattr(self, f"cross_attn_{i}", Attention(
                num_heads=cfg.num_head, d_model=cfg.embedding_dim,
                dropout_rate=cfg.dropout_rate, dtype=self.dtype,
                residual_dtype=rdt, ring_mesh=self.ring_mesh))
            setattr(self, f"ffn_{i}", FeedForward(
                d_model=cfg.embedding_dim, mult=cfg.ffn_mult,
                dropout_rate=cfg.dropout_rate, dtype=self.dtype,
                residual_dtype=rdt))

    def _pos_table(self) -> jnp.ndarray:
        cfg = self.cfg
        return jnp.asarray(position_encoding(cfg.tar_len, cfg.embedding_dim),
                           dtype=self.dtype)

    def __call__(self, tar, sou_embedding, sou_mask, tar_mask_pad,
                 *, deterministic: bool):
        cfg = self.cfg
        T = tar.shape[1]
        x = self.embed(tar) + self._pos_table()[None, :T, :]

        # (B,1,1,T) pad mask AND (1,1,T,T) causal (gnn_transformer.py:117),
        # applied as two chained where-terms inside attend (causal=True) so
        # the combined (B,1,T,T) boolean buffer never materializes
        for i in range(cfg.num_layers):
            x = getattr(self, f"self_attn_{i}")(
                x, x, x, tar_mask_pad, deterministic=deterministic,
                causal=True)
            x = getattr(self, f"cross_attn_{i}")(
                x, sou_embedding, sou_embedding, sou_mask,
                deterministic=deterministic)
            x = getattr(self, f"ffn_{i}")(x, deterministic=deterministic)
        return x

    def cross_kv(self, sou_embedding):
        """Per-layer cross-attention K/V of the encoder states, computed
        once per batch: (L, B, H, S, d_head) x 2. The full-prefix path
        recomputes these every beam step (the reference recomputes them
        every step x beam, run_model.py:256)."""
        ks, vs = [], []
        for i in range(self.cfg.num_layers):
            k, v = getattr(self, f"cross_attn_{i}").project_kv(
                sou_embedding, sou_embedding)
            ks.append(k)
            vs.append(v)
        return jnp.stack(ks), jnp.stack(vs)

    def decode_step(self, tok, pos_idx, k_cache, v_cache, cross_k, cross_v,
                    sou_mask, self_mask):
        """One decode position with cached K/V.

        tok: (B, 1) token ids at position ``pos_idx`` (traced scalar);
        k_cache/v_cache: (L, B, H, tar_len, d_head) self-attention caches;
        cross_k/cross_v: from :meth:`cross_kv`; self_mask: (B, 1, 1, tar_len)
        validity of cached positions. Returns (x (B,1,D), k_cache, v_cache)
        with position ``pos_idx`` of the caches filled.

        Mathematically identical to slicing position ``pos_idx`` out of
        ``__call__`` over the full prefix: post-LN blocks act per position,
        and causality makes cached K/V equal recomputed K/V.
        """
        x = self.embed(tok) + jax.lax.dynamic_slice_in_dim(
            self._pos_table(), pos_idx, 1, axis=0)[None, :, :]
        # cache writes CAST to the arena's storage dtype and reads UPCAST
        # to the stable dtype (both no-ops for the default f32 arena):
        # cfg.kv_dtype="bf16" stores the K/V stripes half-width while the
        # attention math stays full precision (decode/quant.py)
        cd = stable_dtype(k_cache.dtype)
        for i in range(self.cfg.num_layers):
            sa = getattr(self, f"self_attn_{i}")
            k_new, v_new = sa.project_kv(x, x)       # (B, H, 1, d_head)
            k_cache = k_cache.at[i, :, :, pos_idx, :].set(
                k_new[:, :, 0, :].astype(k_cache.dtype))
            v_cache = v_cache.at[i, :, :, pos_idx, :].set(
                v_new[:, :, 0, :].astype(v_cache.dtype))
            x = sa.attend(x, k_cache[i].astype(cd), v_cache[i].astype(cd),
                          self_mask, deterministic=True)
            x = getattr(self, f"cross_attn_{i}").attend(
                x, cross_k[i], cross_v[i], sou_mask, deterministic=True)
            x = getattr(self, f"ffn_{i}")(x, deterministic=True)
        return x, k_cache, v_cache

    def embed_at(self, tok, pos_idx):
        """Decoder INPUT stem at per-row positions — token embedding plus
        the positional row, k-position capable: ``tok`` (B, n) with
        ``pos_idx`` (B, n) embeds n positions per row at once; the cached
        step paths call it at n=1 with a (B,) vector. Exposed on its own
        so the speculative copy drafter (decode/spec.py) can score the
        copy head against the raw target embedding WITHOUT running any
        decoder layer."""
        pos = pos_idx.astype(jnp.int32)
        table = self._pos_table()[pos]
        if table.ndim == 2:            # (B,) positions -> (B, 1, D) rows
            table = table[:, None, :]
        return self.embed(tok) + table

    def decode_step_multi(self, tok, pos_idx, k_cache, v_cache, cross_k,
                          cross_v, sou_mask, self_mask):
        """One cached decode position PER ROW over a dense whole-sequence
        cache: like :meth:`decode_step` but ``pos_idx`` is a (B,) vector —
        row b advances its own position ``pos_idx[b]``. ONE caller: the
        speculative ``draft`` tier's scratch roll (decode/spec.py, through
        FiraModel's dense fused step), which steps a dense
        view of each slot's top beam gathered off the paged pool; the
        engine's own step is :meth:`decode_step_paged`. Per row the math
        is identical to :meth:`decode_step` at that row's scalar position:
        the position-table row is gathered per row instead of sliced
        once, and the cache write scatters per-row columns."""
        B = tok.shape[0]
        pos = pos_idx.astype(jnp.int32)
        b_idx = jnp.arange(B)
        x = self.embed_at(tok, pos)
        # same storage-cast / read-upcast rule as decode_step (no-op f32)
        cd = stable_dtype(k_cache.dtype)
        for i in range(self.cfg.num_layers):
            sa = getattr(self, f"self_attn_{i}")
            k_new, v_new = sa.project_kv(x, x)       # (B, H, 1, d_head)
            k_cache = k_cache.at[i, b_idx, :, pos, :].set(
                k_new[:, :, 0, :].astype(k_cache.dtype))
            v_cache = v_cache.at[i, b_idx, :, pos, :].set(
                v_new[:, :, 0, :].astype(v_cache.dtype))
            x = sa.attend(x, k_cache[i].astype(cd), v_cache[i].astype(cd),
                          self_mask, deterministic=True)
            x = getattr(self, f"cross_attn_{i}").attend(
                x, cross_k[i], cross_v[i], sou_mask, deterministic=True)
            x = getattr(self, f"ffn_{i}")(x, deterministic=True)
        return x, k_cache, v_cache

    def decode_step_paged(self, tok, pos_idx, k_pool, v_pool, block_tab,
                          ancestry, cross_k, cross_v, sou_mask, self_mask):
        """The slot engine's step (decode/engine.py): one cached decode
        position per row at the row's OWN position (``pos_idx`` a vector:
        slots hold samples at mixed depths), with the self-attention
        cache behind BLOCK-TABLE INDIRECTION: instead of each row owning
        a whole-sequence (tar_len) cache stripe as in the batched beam's
        :meth:`decode_step`, the cache lives in a fixed pool of KV
        blocks — k_pool/v_pool: (L*P, G, H*d_head), a block a (layer,
        pool block), a row a (beam lane, position) with its heads side by
        side, G = K*block rounded up to whole sublane tiles
        (layers.pool_block_rows), so the layout the runtime gives the pool
        is the one this step computes in — and ``block_tab`` (S, W) maps
        slot s's position range [w*block, (w+1)*block) to a pool block
        (sentinel id P = unmapped: reads land on garbage the mask zeroes
        exactly, writes drop).

        The pool is WRITTEN ONCE AND NEVER MOVED: row (s, k)'s new K/V
        goes into lane k of slot s's tail block, and ``ancestry``
        (S, K, tar_len) — the engine's, ``ancestry[s, k, t]`` the lane
        that holds position t of beam k's history, this position already
        lane k — says where a beam's past lies after the selections that
        re-sorted the beams. Each layer reads every slot's blocks ONCE,
        all K lanes (layers.gather_block_kv), and the slot's K beams
        attend over its W x G cached rows together, each under
        its own mask ``valid[t] & (ancestry[s, k, t] == lane)``
        (layers.lane_mask). A masked entry gets the -1e9 of an unwritten
        position — softmax weight an exact 0.0 — so per beam this is the
        attention of :meth:`decode_step` over the same keys and values at
        the same precision; what differs is the order in which the
        softmax and the value product sum their exact zeros, i.e. the
        last bits (tests/test_paged_kv.py pins tokens bitwise and probs to
        float32 rounding against the batched beam; tests/
        test_beam_ancestry.py that the entries selected ARE the reordered
        whole-sequence cache, bit for bit).

        The source side is held ONCE A SLOT: cross_k/cross_v
        (L, S, H, src_len, d_head) and ``sou_mask`` (S, src_len). A slot's
        K beams read them as they read its blocks, as the query axis of one
        attention a slot — per head one (K x d_head) x (d_head x src_len)
        product where K rows of one query each would read K copies.

        tok: (S*K, 1) token ids; pos_idx: (S*K,) per-row positions (rows
        of one slot share theirs); self_mask: (S*K, 1, 1, tar_len) per-row
        validity; W*block must equal tar_len. Returns x (S, K, D) and the
        pools."""
        LP, G, HD = k_pool.shape
        L, D, H, K = (self.cfg.num_layers, self.cfg.embedding_dim,
                      self.cfg.num_head, self.cfg.beam_size)
        B = tok.shape[0]
        S, W = block_tab.shape
        T = self_mask.shape[-1]
        BS = T // W
        P = LP // L                                  # blocks a layer
        if (W * BS != T or B != S * K or P * L != LP or G < K * BS
                or HD != D):
            raise ValueError(
                f"paged cache geometry mismatch: table {W} blocks must "
                f"tile the {T}-position budget, beam lanes {K} x {S} slots "
                f"must equal the {B} rows, pool blocks {LP} must be {L} "
                f"layers of blocks, a block of {G} rows must hold {K} "
                f"lanes x {BS} positions and a pool row of {HD} must hold "
                f"the {H} heads of {D // H}")
        pos = pos_idx.astype(jnp.int32)
        slot = jnp.arange(B, dtype=jnp.int32) // K
        krow = jnp.arange(B, dtype=jnp.int32) % K
        blk = block_tab[slot, pos // BS]             # (B,) current tail block
        # a masked row (sentinel block) lies past the last layer's blocks,
        # so its write drops whatever the layer
        blk = jnp.where(blk < P, blk, LP)
        row = krow * BS + pos % BS                   # its row in the block
        mask = lane_mask(ancestry, self_mask.reshape(S, K, W * BS), BS, G)
        # a slot's K beams share its blocks and its source: they are the
        # query axis of both attentions, so x is (S, K, D) throughout
        x = (self.embed(tok) + self._pos_table()[pos][:, None, :]
             ).reshape(S, K, -1)
        for i in range(self.cfg.num_layers):
            sa = getattr(self, f"self_attn_{i}")
            rows = x.reshape(B, 1, -1)
            k_new, v_new = sa.project_kv(rows, rows)  # (B, H, 1, d_head)
            k_pool = append_block_kv(k_pool, blk + i * P, row,
                                     k_new[:, :, 0, :])
            v_pool = append_block_kv(v_pool, blk + i * P, row,
                                     v_new[:, :, 0, :])
            x = sa.attend(x, gather_block_kv(k_pool, block_tab + i * P, H),
                          gather_block_kv(v_pool, block_tab + i * P, H),
                          mask, deterministic=True)
            x = getattr(self, f"cross_attn_{i}").attend(
                x, cross_k[i], cross_v[i], sou_mask, deterministic=True)
            x = getattr(self, f"ffn_{i}")(x, deterministic=True)
        return x, k_pool, v_pool


class _ScoreHead(nn.Module):
    """Parameter container matching TorchDense(1, name="score") exactly
    (names, shapes, init), so both score implementations share one
    checkpoint-compatible param tree."""

    d_in: int

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", torch_kernel_init, (self.d_in, 1),
                            jnp.float32)
        bias = self.param(
            "bias",
            lambda k, s, d: torch_bias_init(k, s, d, self.d_in),
            (1,), jnp.float32,
        )
        return kernel, bias


class CopyNet(nn.Module):
    """Model.py:7-20: Bahdanau-style pointer scores over source positions
    plus a 2-way generate/copy gate.

    ``impl`` selects the scoring path: "xla" materializes the (B,T,S,D)
    tanh intermediate in forward and rematerializes it in backward
    (jax.checkpoint); "pallas" runs the fused kernel (ops/copy_score.py)
    that streams it through VMEM and never touches HBM with it."""

    d_model: int
    impl: str = "xla"
    dtype: jnp.dtype = jnp.float32
    remat: bool = True  # False stores the (B,T,S,D) tanh for backward

    def setup(self):
        self.src_proj = TorchDense(self.d_model, use_bias=False,
                                   dtype=self.dtype)
        self.tgt_proj = TorchDense(self.d_model, use_bias=False,
                                   dtype=self.dtype)
        self.score = _ScoreHead(self.d_model)
        self.gate = TorchDense(2, dtype=self.dtype)

    def project_src(self, source):
        """(B,S,D) source projection — constant per batch, computed once by
        the KV-cached decode instead of once per beam step."""
        return self.src_proj(source)

    def score_gate(self, src, target):
        """Pointer scores + gate from a pre-projected source."""
        tgt = self.tgt_proj(target)                   # (B,T,D)
        kernel, bias = self.score()
        if self.impl == "pallas":
            scores = copy_score.copy_scores(
                src, tgt, kernel.astype(self.dtype), bias.astype(self.dtype)
            )
        elif self.impl == "xla":
            # remat (default): recompute the (B,T,S,D) tanh intermediate in
            # backward instead of storing it; cfg.copy_head_remat=False
            # stores it instead — values identical either way
            fn = copy_score.copy_scores_reference
            if self.remat:
                fn = jax.checkpoint(fn)
            scores = fn(
                src, tgt, kernel.astype(self.dtype), bias.astype(self.dtype)
            )
        else:
            raise ValueError(
                f"copy_head_impl={self.impl!r} not in {{'xla', 'pallas'}}")
        gate = jax.nn.softmax(
            self.gate(target).astype(stable_dtype(self.dtype)), axis=-1,
        )
        return scores, gate

    def __call__(self, source, target):
        return self.score_gate(self.project_src(source), target)


class FiraModel(nn.Module):
    """Model.py:24-86: encoder + decoder + fused gen/copy distribution."""

    cfg: FiraConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.cfg
        ring_mesh = None
        if cfg.seq_shards > 1:
            from fira_tpu.parallel.ring import seq_mesh
            import jax as _jax

            n_dev = len(_jax.devices())
            if n_dev % cfg.seq_shards:
                raise ValueError(
                    f"seq_shards={cfg.seq_shards} does not divide the "
                    f"{n_dev} visible devices")
            ring_mesh = seq_mesh(n_data=n_dev // cfg.seq_shards,
                                 n_seq=cfg.seq_shards)
        self.encoder = Encoder(cfg, dtype=self.dtype)
        self.decoder = Decoder(cfg, dtype=self.dtype, ring_mesh=ring_mesh)
        self.copy_net = CopyNet(cfg.embedding_dim, impl=cfg.copy_head_impl,
                                dtype=self.dtype, remat=cfg.copy_head_remat)
        self.out_fc = TorchDense(cfg.vocab_size, dtype=self.dtype)
        if cfg.typed_edges:
            from fira_tpu.data.graph_build import N_EDGE_KINDS

            self.edge_gain = self.param(
                "edge_gain", nn.initializers.ones, (N_EDGE_KINDS,),
                jnp.float32)

    def encode(self, batch: Dict[str, jnp.ndarray], *,
               deterministic: bool = True):
        """Run the graph encoder once; returns ([diff||sub] states, mask)."""
        cfg = self.cfg
        batch = dict(batch)
        # node count from the BATCH, not the config: equals cfg.graph_len at
        # full pad, smaller under a bucketed geometry (data/buckets.py) whose
        # ast_change tail was truncated — the diff/sub regions are pinned by
        # the copy-label id space and never shrink
        graph_len = (batch["diff"].shape[1] + batch["sub_token"].shape[1]
                     + batch["ast_change"].shape[1])
        if cfg.typed_edges:
            # typed-edge extension: per-family learned gain on the normalized
            # weights; at init (all ones) this is bit-identical to the
            # reference's flattened adjacency
            batch["values"] = batch["values"] * self.edge_gain.astype(
                batch["values"].dtype)[batch["edge_kinds"].astype(jnp.int32)]
        # jax.named_scope here and below: names on the DEVICE's work, in
        # the op metadata only (same HLO, same compile-cache key) — the
        # parts PERF.md section 5 otherwise knows by fusion number
        if cfg.adjacency_impl == "segment":
            adj = functools.partial(
                coo_matvec, batch["senders"], batch["receivers"],
                batch["values"], indices_sorted=cfg.sort_edges,
            )
        elif cfg.adjacency_impl == "dense":
            # scatter straight into the compute dtype: dedup guarantees one
            # value per cell (dense_adjacency docstring), so this is
            # bit-identical to the f32 scatter + cast it replaces while
            # never materializing the f32 (B, N, N) buffer at all
            with jax.named_scope("adjacency"):
                adj = dense_adjacency(
                    batch["senders"], batch["receivers"], batch["values"],
                    graph_len, indices_sorted=cfg.sort_edges,
                    out_dtype=self.dtype,
                )
        else:
            raise ValueError(
                f"adjacency_impl={cfg.adjacency_impl!r} not in "
                f"{{'dense', 'segment'}}")
        sou_mask = batch["diff"] != 0
        sub_mask = batch["sub_token"] != 0
        with jax.named_scope("gcn"):
            sou_emb, sub_emb = self.encoder(
                batch["diff"], batch["diff_mark"], batch["ast_change"], adj,
                batch["sub_token"], deterministic=deterministic,
            )
        states = jnp.concatenate([sou_emb, sub_emb], axis=1)
        mask = jnp.concatenate([sou_mask, sub_mask], axis=1)
        return states, mask

    def _dist_parts(self, states, mask, tar, tar_mask_pad, *,
                    deterministic: bool = True):
        """The fused distribution's three factors — generation softmax over
        the vocab, copy softmax over source positions, 2-way gate — WITHOUT
        assembling the (B, T, vocab+sou+sub) concatenation. The training
        loss gathers one label per position from the factors directly
        (gate*dist then gather == gather then gate — multiplication is
        elementwise), skipping ~1.5 GB/step of full-vocab f32 assembly at
        flagship geometry; the beam consumes the assembled form via
        :meth:`fused_probs`."""
        with jax.named_scope("decoder"):
            tar_emb = self.decoder(tar, states, mask, tar_mask_pad,
                                   deterministic=deterministic)
        with jax.named_scope("output_head"):
            gen = jax.nn.softmax(
                self.out_fc(tar_emb).astype(stable_dtype(self.dtype)),
                axis=-1)
        with jax.named_scope("copy_head"):
            scores, gate = self.copy_net(states, tar_emb)
            scores = jnp.where(mask[:, None, :], scores,
                               jnp.asarray(-1e9, scores.dtype))
            copy = jax.nn.softmax(scores.astype(stable_dtype(self.dtype)),
                                  axis=-1)
        return gen, copy, gate

    def fused_probs(self, states, mask, tar, tar_mask_pad, *,
                    deterministic: bool = True):
        """Decoder + copy fusion -> probability-space distribution over
        vocab_size + sou_len + sub_token_len (Model.py:52-64). The beam
        search consumes this directly in its reference-compat prob-space
        accumulation mode (run_model.py:257-271)."""
        gen, copy, gate = self._dist_parts(states, mask, tar, tar_mask_pad,
                                           deterministic=deterministic)
        return jnp.concatenate(
            [gate[:, :, 0:1] * gen, gate[:, :, 1:2] * copy], axis=-1
        )

    def decode_init(self, states):
        """Everything constant across decode steps, computed once per batch:
        per-layer cross-attention K/V of the encoder states and the copy
        head's source projection. The reference recomputes all of it every
        step x beam (run_model.py:256-259)."""
        cross_k, cross_v = self.decoder.cross_kv(states)
        return cross_k, cross_v, self.copy_net.project_src(states)

    def copy_draft_scores(self, mask, src_proj, tok, pos_idx):
        """Speculative COPY drafter head (decode/spec.py, tier ``copy``):
        the pointer scores ALONE against the raw target-embedding proxy
        ``Decoder.embed_at(tok, pos_idx)`` — no decoder layer runs and no
        cache is touched, so a k-token draft roll costs k embedding rows
        plus k copy-score passes. Scores get the same source-validity mask
        as :meth:`_step_heads`; the drafter argmaxes them into copy-space
        proposals (``vocab_size +`` source position). Draft quality only
        moves the acceptance rate — never output bytes (the verify program
        is the exact step body) — so the proxy target is deliberately
        cheap."""
        x = self.decoder.embed_at(tok, pos_idx)
        scores, _gate = self.copy_net.score_gate(src_proj, x)
        return jnp.where(mask[:, None, :], scores,
                         jnp.asarray(-1e9, scores.dtype))

    def dist_parts(self, states, mask, tar, tar_mask_pad, *,
                   deterministic: bool = True):
        """Public factor view for the factored beam (cfg.beam_factored_topk):
        (gen, copy, gate) with no fused assembly — see :meth:`_dist_parts`."""
        return self._dist_parts(states, mask, tar, tar_mask_pad,
                                deterministic=deterministic)

    def _step_heads(self, mask, src_proj, tar_emb):
        """Shared generation/copy/gate head of the cached one-position
        decode paths (the batched beam's :meth:`dist_parts_step`, the
        engine's :meth:`dist_parts_step_paged`, the drafter's dense
        fused step). ``tar_emb`` (B, n, D) scores n targets against row
        b's source: n = 1 for a row a beam, and the engine's n = K beams
        of a slot against the ONE ``src_proj`` (S, src_len, D) and
        ``mask`` (S, src_len) the slot holds."""
        with jax.named_scope("output_head"):
            gen = jax.nn.softmax(
                self.out_fc(tar_emb).astype(stable_dtype(self.dtype)),
                axis=-1)
        with jax.named_scope("copy_head"):
            scores, gate = self.copy_net.score_gate(src_proj, tar_emb)
            scores = jnp.where(mask[:, None, :], scores,
                               jnp.asarray(-1e9, scores.dtype))
            copy = jax.nn.softmax(scores.astype(stable_dtype(self.dtype)),
                                  axis=-1)
        return gen, copy, gate

    def dist_parts_step(self, mask, tok, pos_idx, k_cache, v_cache,
                        cross_k, cross_v, src_proj, self_mask):
        """One-position distribution FACTORS with KV caching: the
        (gen, copy, gate) triple of :meth:`fused_probs_step` without the
        fused concatenation/gate products. The factored beam takes per-side
        top-k from these directly (the fused distribution is the two sides
        scaled by their gate weights, so the global top-k lives in the
        union of the per-side top-ks)."""
        with jax.named_scope("decoder"):
            tar_emb, k_cache, v_cache = self.decoder.decode_step(
                tok, pos_idx, k_cache, v_cache, cross_k, cross_v, mask,
                self_mask,
            )
        gen, copy, gate = self._step_heads(mask, src_proj, tar_emb)
        return gen, copy, gate, k_cache, v_cache

    def dist_parts_step_paged(self, mask, tok, pos_idx, k_pool, v_pool,
                              block_tab, ancestry, cross_k, cross_v,
                              src_proj, self_mask):
        """THE slot engine's step (decode/slot_model.FiraSlotModel):
        :meth:`dist_parts_step` at a per-row position vector, the self-
        attention cache read and written through block-table indirection
        and followed by beam ancestry (Decoder.decode_step_paged) instead
        of whole-sequence stripes; heads are the shared
        :meth:`_step_heads`. ``mask`` (S, src_len), ``cross_k``/
        ``cross_v`` (L, S, ...) and ``src_proj`` (S, src_len, D) are a
        SLOT's, read by its K beams as K queries; the factors come back
        a slot a row: gen (S, K, V), copy (S, K, src_len), gate
        (S, K, 2)."""
        with jax.named_scope("decoder"):
            tar_emb, k_pool, v_pool = self.decoder.decode_step_paged(
                tok, pos_idx, k_pool, v_pool, block_tab, ancestry, cross_k,
                cross_v, mask, self_mask,
            )
        gen, copy, gate = self._step_heads(mask, src_proj, tar_emb)
        return gen, copy, gate, k_pool, v_pool

    def fused_probs_step_multi(self, mask, tok, pos_idx, k_cache, v_cache,
                               cross_k, cross_v, src_proj, self_mask):
        """Per-ROW-position twin of :meth:`fused_probs_step` over a dense
        whole-sequence cache, for ONE caller: the speculative ``draft``
        tier's scratch roll (decode/spec.py), whose caches are a dense
        view of each slot's top beam that lives and dies in its scan
        carry. Returns (fused (B, 1, V_out), caches)."""
        with jax.named_scope("decoder"):
            tar_emb, k_cache, v_cache = self.decoder.decode_step_multi(
                tok, pos_idx, k_cache, v_cache, cross_k, cross_v, mask,
                self_mask,
            )
        gen, copy, gate = self._step_heads(mask, src_proj, tar_emb)
        fused = jnp.concatenate(
            [gate[:, :, 0:1] * gen, gate[:, :, 1:2] * copy], axis=-1
        )
        return fused, k_cache, v_cache

    def fused_probs_step(self, mask, tok, pos_idx, k_cache, v_cache,
                         cross_k, cross_v, src_proj, self_mask):
        """One-position fused distribution with KV caching: same math as
        slicing position ``pos_idx`` out of :meth:`fused_probs`, at O(1)
        decoder cost per step instead of O(tar_len). Returns
        (fused (B, 1, V_out), k_cache, v_cache)."""
        gen, copy, gate, k_cache, v_cache = self.dist_parts_step(
            mask, tok, pos_idx, k_cache, v_cache, cross_k, cross_v,
            src_proj, self_mask)
        fused = jnp.concatenate(
            [gate[:, :, 0:1] * gen, gate[:, :, 1:2] * copy], axis=-1
        )
        return fused, k_cache, v_cache

    def fused_log_probs(self, states, mask, tar, tar_mask_pad, *,
                        deterministic: bool = True):
        """log-clamped fused distribution (Model.py:69: clip to [1e-10, 1])."""
        fused = self.fused_probs(states, mask, tar, tar_mask_pad,
                                 deterministic=deterministic)
        return jnp.log(jnp.clip(fused, 1e-10, 1.0))

    def __call__(self, batch: Dict[str, jnp.ndarray], *,
                 deterministic: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Training/dev forward. Returns (loss_sum, token_count) like the
        reference (Model.py:83-84); callers normalize (run_model.py:105)."""
        states, mask = self.encode(batch, deterministic=deterministic)
        tar = batch["msg"]
        gen, copy, gate = self._dist_parts(
            states, mask, tar, tar != 0, deterministic=deterministic
        )
        with jax.named_scope("loss"):
            # label = tar_label shifted left with a zero column
            # (Model.py:71-79)
            label = jnp.concatenate(
                [batch["msg_tar"][:, 1:],
                 jnp.zeros((tar.shape[0], 1),
                           dtype=batch["msg_tar"].dtype)],
                axis=1,
            )
            label_mask = label != 0
            # Gather the label's probability from the distribution FACTORS,
            # then log-clamp (Model.py:69's clip to [1e-10, 1]). Equivalent
            # to assembling the fused (B, T, 25k) tensor, log-clamping it,
            # and gathering after — gate multiplication and log are
            # elementwise, so both commute with the gather — but neither
            # the concatenation nor the full-vocab gate products nor the
            # full f32 log tensor (~2 GB/step combined at flagship) is ever
            # materialized.
            V = self.cfg.vocab_size
            label = label.astype(jnp.int32)
            is_gen = label < V
            gi = jnp.where(is_gen, label, 0)[..., None]
            ci = jnp.clip(label - V, 0, copy.shape[-1] - 1)[..., None]
            pg = (jnp.take_along_axis(gen, gi, axis=-1)[..., 0]
                  * gate[..., 0])
            pc = (jnp.take_along_axis(copy, ci, axis=-1)[..., 0]
                  * gate[..., 1])
            p = jnp.where(is_gen, pg, pc)
            nll = -jnp.log(jnp.clip(p, 1e-10, 1.0))
            nll = jnp.where(label_mask, nll, 0.0)
            return nll.sum(), label_mask.sum()

    def dev_predict(self, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Teacher-forced greedy ids for all positions at once (Model.py:86).

        argmax over the probability-space distribution: log-clamp is
        monotonic on [1e-10, 1] and the 25k-way softmax's max is always
        >= 1/25020 > 1e-10, so the argmax is identical to the reference's
        argmax over the clamped log — minus a full-vocab f32 log pass."""
        states, mask = self.encode(batch, deterministic=True)
        tar = batch["msg"]
        fused = self.fused_probs(states, mask, tar, tar != 0)
        return jnp.argmax(fused, axis=-1)
