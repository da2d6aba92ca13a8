"""Building-block Flax modules for the FIRA graph encoder / decoder.

Each module is a TPU-first rebuild of a reference layer (cited per class from
/root/reference/gnn_transformer.py and combination_layer.py), matching the
live math exactly — post-LN residuals, dropout sites (0.2 in the GCN, 0.1
elsewhere), additive -1e9 masking, interleaved sin/cos positions — while
omitting the reference's dead modules (lstm, combination_list1, gate_fc;
SURVEY.md Appendix B).

Initializers mirror PyTorch defaults so training dynamics are comparable:
Linear weights ~ U(+-1/sqrt(fan_in)) (kaiming_uniform with a=sqrt(5)),
Linear biases ~ U(+-1/sqrt(fan_in)), Embedding ~ N(0,1).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from flax import linen as nn

# torch nn.Linear default: kaiming_uniform(a=sqrt(5)) == U(+-sqrt(1/fan_in))
torch_kernel_init = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform")
torch_embed_init = nn.initializers.normal(stddev=1.0)


def stable_dtype(dtype):
    """Numerics-sensitive ops (LayerNorm, softmax, log) run in at least
    float32: bf16 compute promotes to f32, f64 (parity testing) stays f64."""
    return jnp.promote_types(dtype, jnp.float32)


def residual_out(x, residual_dtype):
    """Post-LN output cast for the stable_residual=False perf knob: LN
    statistics stay in the stable dtype; only the STORED residual stream is
    narrowed (no-op when residual_dtype is None — the default f32 parity
    numerics)."""
    return x if residual_dtype is None else x.astype(residual_dtype)


def torch_bias_init(key, shape, dtype, fan_in: int):
    bound = 1.0 / np.sqrt(fan_in)
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class TorchDense(nn.Module):
    """nn.Dense with PyTorch nn.Linear default initialization."""

    features: int
    use_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        fan_in = x.shape[-1]
        kernel = self.param(
            "kernel", torch_kernel_init, (fan_in, self.features), jnp.float32
        )
        y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype))
        if self.use_bias:
            bias = self.param(
                "bias",
                lambda k, s, d: torch_bias_init(k, s, d, fan_in),
                (self.features,),
                jnp.float32,
            )
            y = y + bias.astype(self.dtype)
        return y


def position_encoding(length: int, dmodel: int) -> np.ndarray:
    """Interleaved sin/cos positions (gnn_transformer.py:10-19): for each
    frequency j the pair (sin, cos) is laid out adjacently — NOT the usual
    all-sin-then-all-cos layout."""
    pos = np.zeros((length, dmodel), dtype=np.float32)
    i = np.arange(length)[:, None].astype(np.float64)
    j = np.arange(dmodel // 2)[None, :].astype(np.float64)
    angle = i / np.power(10000.0, 2.0 * j / dmodel)
    pos[:, 0::2] = np.sin(angle)
    pos[:, 1::2] = np.cos(angle)
    return pos


def combination_gate(query, key, value, *, dropout=None, scale=None):
    """combination_layer.py:6-17: attention-free two-channel gating.

    Per element: weights = softmax over the pair (q*k/sqrt(d), q*v/sqrt(d));
    output = w0*k + w1*v, then dropout. Used to fuse token vs. diff-mark
    channels. ``scale`` overrides the 1/sqrt(last-dim) default — the
    multi-head wrapper passes 1/sqrt(d_head) while keeping tensors in
    merged (B, S, d_model) layout.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(query.shape[-1])
    qk = query * key * scale
    qv = query * value * scale
    # The 2-way softmax in closed form: softmax([a, b]) = (sigmoid(a-b),
    # sigmoid(b-a)). Same math in the same stable dtype as an explicit pair
    # softmax (no-op in f32; guards bf16 exp precision) WITHOUT stacking a
    # (..., 2) logits tensor — at flagship geometry that stack plus its
    # softmax round-trips ~146 MB of f32 per encoder round, pure HBM
    # traffic the closed form never touches.
    sd = stable_dtype(qk.dtype)
    diff = qk.astype(sd) - qv.astype(sd)
    w0 = jax.nn.sigmoid(diff).astype(qk.dtype)
    w1 = jax.nn.sigmoid(-diff).astype(qk.dtype)
    out = w0 * key + w1 * value
    if dropout is not None:
        out = dropout(out)
    return out


class Combination(nn.Module):
    """Multi-head wrapper around the combination gate
    (gnn_transformer.py:176-205): three input projections, per-head gating,
    output projection, post-LN residual on the query. Dropout is applied both
    inside the gate and after the output projection, as the reference does.
    """

    num_heads: int
    d_model: int
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    residual_dtype: object = None  # see residual_out

    @nn.compact
    def __call__(self, query, key, value, *, deterministic: bool):
        old_query = query
        # the reshape-based head split used to enforce divisibility; keep
        # the guard so a bad head count fails fast instead of silently
        # training with a scale that matches no valid head layout
        assert self.d_model % self.num_heads == 0, \
            f"d_model={self.d_model} not divisible by num_heads={self.num_heads}"
        d_head = self.d_model // self.num_heads

        # The gate is purely elementwise, so the reference's head
        # split/merge transposes (gnn_transformer.py:185-198) are layout
        # no-ops: elementwise math on (B, H, S, d_head) equals the same
        # math on (B, S, d_model). The head count only enters through the
        # 1/sqrt(d_head) scale, passed explicitly — bit-identical in
        # deterministic mode (what the torch-parity tests pin); the inner
        # dropout mask is now drawn in merged layout (same distribution,
        # different stream). Six (B, S, d_model) transpose copies per
        # layer saved (fwd + bwd).
        q = TorchDense(self.d_model, dtype=self.dtype, name="q_proj")(query)
        k = TorchDense(self.d_model, dtype=self.dtype, name="k_proj")(key)
        v = TorchDense(self.d_model, dtype=self.dtype, name="v_proj")(value)

        inner_dropout = nn.Dropout(self.dropout_rate, deterministic=deterministic)
        x = combination_gate(q, k, v, dropout=inner_dropout,
                             scale=1.0 / np.sqrt(d_head))
        out = TorchDense(self.d_model, dtype=self.dtype, name="out_proj")(x)
        out = nn.Dropout(self.dropout_rate, deterministic=deterministic)(out)
        return residual_out(
            nn.LayerNorm(epsilon=1e-5, dtype=stable_dtype(self.dtype),
                         name="norm")(out + old_query), self.residual_dtype)


class GCN(nn.Module):
    """One graph-convolution round (gnn_transformer.py:64-86):
    fc1 -> A.x -> fc2 -> dropout(0.2) + residual -> LayerNorm, over the
    shared normalized adjacency. ``adj`` is either a dense (B, N, N) batch
    (one MXU bmm) or a callable applying A.x directly from COO triplets
    (model.coo_matvec, the O(edges) path for large graphs)."""

    d_model: int
    dropout_rate: float = 0.2
    dtype: jnp.dtype = jnp.float32
    residual_dtype: object = None  # see residual_out

    @nn.compact
    def __call__(self, graph_em, adj, *, deterministic: bool):
        fc1 = TorchDense(self.d_model, dtype=self.dtype, name="fc1")
        fc2 = TorchDense(self.d_model, dtype=self.dtype, name="fc2")
        drop = nn.Dropout(self.dropout_rate)
        norm = nn.LayerNorm(epsilon=1e-5, dtype=stable_dtype(self.dtype),
                            name="norm")
        if isinstance(graph_em, tuple):
            # split-buffer mode (cfg.encoder_buffer="split"): the node
            # buffer never exists as one tensor — fc1/fc2/norm are the SAME
            # parameters applied per segment, A.x runs as two column-slab
            # bmms, and the single full-width dropout call keeps the RNG
            # stream identical to the single-buffer path. Outputs match
            # "single" to matmul-reassociation tolerance (two partial sums
            # instead of one 650-long contraction).
            top, rest = graph_em
            adj_top, adj_rest = adj
            s = top.shape[1]
            x = (jnp.einsum("bij,bjd->bid", adj_top.astype(self.dtype),
                            fc1(top))
                 + jnp.einsum("bij,bjd->bid", adj_rest.astype(self.dtype),
                              fc1(rest)))
            x = drop(fc2(x), deterministic=deterministic)
            y_top = residual_out(norm(x[:, :s] + top), self.residual_dtype)
            y_rest = residual_out(norm(x[:, s:] + rest), self.residual_dtype)
            return y_top, y_rest
        x = fc1(graph_em)
        if callable(adj):  # COO message-passing path (model.coo_matvec)
            x = adj(x)
        else:
            x = jnp.einsum("bij,bjd->bid", adj.astype(self.dtype), x)
        x = drop(fc2(x), deterministic=deterministic)
        return residual_out(norm(x + graph_em), self.residual_dtype)


class Attention(nn.Module):
    """Post-LN multi-head attention (gnn_transformer.py:124-161): additive
    -1e9 masking where mask==0, softmax, output projection, dropout, residual
    on the ORIGINAL query, LayerNorm.

    setup-based (not compact) so the K/V projection is callable separately
    from the attention itself: the KV-cached beam decode projects each new
    position once (``project_kv``) and attends over the cache (``attend``)
    instead of re-running the whole stack on the full prefix. Param names are
    identical to the previous compact layout (q_proj/k_proj/v_proj/out_proj/
    norm), so checkpoints and the weight-transplant parity tests are
    unaffected."""

    num_heads: int
    d_model: int
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    residual_dtype: object = None  # see residual_out
    # a (data, seq) jax.sharding.Mesh routes this module's attention core
    # through ring attention (parallel/ring.py) whenever the mask is a pure
    # key-padding mask and both sequence lengths divide the seq axis; adds
    # no parameters, so checkpoints are interchangeable with dense attention
    ring_mesh: object = None

    def setup(self):
        self.q_proj = TorchDense(self.d_model, dtype=self.dtype)
        self.k_proj = TorchDense(self.d_model, dtype=self.dtype)
        self.v_proj = TorchDense(self.d_model, dtype=self.dtype)
        self.out_proj = TorchDense(self.d_model, dtype=self.dtype)
        self.norm = nn.LayerNorm(epsilon=1e-5, dtype=stable_dtype(self.dtype))
        self.dropout = nn.Dropout(self.dropout_rate)

    def _ring_applicable(self, q, k, mask) -> bool:
        if self.ring_mesh is None or mask.ndim != 2:
            # ring carries key-padding semantics only; callers with richer
            # masking (4D decode-step masks, or causal=True — excluded at
            # the attend() call site) stay on the dense path
            return False
        from fira_tpu.parallel.ring import SEQ_AXIS

        n_seq = self.ring_mesh.shape[SEQ_AXIS]
        n_data = self.ring_mesh.shape["data"]
        return (q.shape[2] % n_seq == 0 and k.shape[2] % n_seq == 0
                and q.shape[0] % n_data == 0)

    def _split_heads(self, x):
        B, length = x.shape[0], x.shape[1]
        d_head = self.d_model // self.num_heads
        return x.reshape(B, length, self.num_heads, d_head).transpose(0, 2, 1, 3)

    def project_kv(self, key, value):
        """(B, L, D) inputs -> head-split (B, H, L, d_head) K and V."""
        return self._split_heads(self.k_proj(key)), \
            self._split_heads(self.v_proj(value))

    def attend(self, query, k, v, mask, *, deterministic: bool,
               causal: bool = False):
        """Attention over pre-projected K/V (as returned by project_kv).

        ``causal=True`` applies the lower-triangular mask as a SEPARATE
        broadcast where-term over the logits instead of expecting it folded
        into ``mask``: pad AND causal -> one (B,1,T,T) boolean buffer that
        XLA materializes and copies between fusions (~4 ms/step of pred
        copies in the round-4 per-op trace, docs/TPU_OP_TIMES.json); two
        chained wheres with (B,1,1,T) and (1,1,T,T) operands fuse into the
        logits computation with no batched mask buffer. Elementwise
        identical: both fills are the same -1e9."""
        old_query = query
        B, q_len = query.shape[0], query.shape[1]
        d_head = self.d_model // self.num_heads

        q = self._split_heads(self.q_proj(query))
        if not causal and self._ring_applicable(q, k, mask):
            # sequence-parallel exact attention: K/V blocks rotate over the
            # seq mesh axis with an online softmax (same -1e9 key-padding
            # semantics as the dense branch below)
            from fira_tpu.parallel.ring import ring_attention_sharded

            out = ring_attention_sharded(q, k, v, mask != 0, self.ring_mesh)
        else:
            weight = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d_head)
            if mask.ndim < 4:  # (B, kv_len) key-padding mask -> (B,1,1,kv)
                mask = mask[:, None, None, :]
            weight = jnp.where(mask == 0, jnp.asarray(-1e9, weight.dtype), weight)
            if causal:
                # the triangle below assumes queries start at key position 0;
                # a cached/offset decode step (q_len=1, kv_len=T) would get a
                # mask attending only key 0 — fail loudly on that misuse
                # (offset decode goes through the KV-cache path instead).
                # Shapes are static so this costs nothing at trace time; a
                # bare assert would vanish under `python -O`
                if q_len != k.shape[2]:
                    raise ValueError(
                        f"causal=True requires q_len == kv_len (got {q_len} "
                        f"vs {k.shape[2]}); offset decode must use the "
                        f"cache path")
                tri = jnp.tril(jnp.ones((q_len, k.shape[2]), dtype=bool))
                weight = jnp.where(tri[None, None],
                                   weight, jnp.asarray(-1e9, weight.dtype))
            weight = jax.nn.softmax(weight.astype(stable_dtype(self.dtype)), axis=-1).astype(self.dtype)
            out = jnp.einsum("bhqk,bhkd->bhqd", weight, v)
        out = out.transpose(0, 2, 1, 3).reshape(B, q_len, self.d_model)
        out = self.out_proj(out)
        out = self.dropout(out, deterministic=deterministic)
        return residual_out(self.norm(out + old_query), self.residual_dtype)

    def __call__(self, query, key, value, mask, *, deterministic: bool,
                 causal: bool = False):
        k, v = self.project_kv(key, value)
        return self.attend(query, k, v, mask, deterministic=deterministic,
                           causal=causal)


def pool_block_rows(lanes: int, block: int, dtype) -> int:
    """Rows of one block of the paged pool: its K beam lanes x BS
    positions, rounded up to the chip's sublane tile for ``dtype`` (8 rows
    of 4-byte values, 16 of 2-byte ones).

    The pool (decode/engine.py; docs/DECODE_ENGINE.md "Paged KV arena")
    is (L*P, G, H*d_head): a block a (layer, pool block), a row a (beam
    lane, position) — lane ``j``'s position ``b`` in row ``j*BS + b`` —
    and a position's heads side by side in the row. With G whole
    sublane tiles and H*d_head whole lanes (d 256 and 512) the row-major
    layout pads nothing, so the chip's runtime keeps the pool row-major —
    the layout the step's scan computes in — and a gather of blocks
    moves whole (8, 128) tiles. Rows ``K*BS .. G-1`` of a block are
    never written and always masked (:func:`lane_mask`)."""
    tile = 32 // np.dtype(dtype).itemsize
    return -(-lanes * block // tile) * tile


def gather_block_kv(pool: jnp.ndarray, blocks: jnp.ndarray,
                    num_heads: int) -> jnp.ndarray:
    """One layer's K (or V) cache of every slot out of the paged pool,
    ALL beam lanes. pool: (L*P, G, H*d_head) (:func:`pool_block_rows`);
    blocks: (S, W) int32 — slot s's position range [w*BS, (w+1)*BS)
    lives in block ``blocks[s, w]``, the engine's block table plus the
    layer's first block ``l*P``. The table's sentinel id P marks unmapped
    entries, which read the next layer's first block (the last layer's
    CLAMP to its last): garbage whose values the mask's -1e9 zeroes
    exactly. The pool is written once — a step puts beam k's new K/V
    into lane k of the slot's tail block (:func:`append_block_kv`) — and
    never moved: after the selections that re-sorted the beams, position
    t of beam q's history lies in lane ``ancestry[s, q, t]`` (the
    engine's table), not in lane q.

    Every slot's blocks are read ONCE, whole. Returns (S, H, W*G,
    d_head): the slot's W*G rows as ONE key axis, ordered (block, lane,
    offset, then the block's unwritten rows) — what ``Attention.attend``
    consumes with the slot's K beams as its query axis and
    :func:`lane_mask` as its mask; the heads are split after the gather.
    A low-precision pool (cfg.kv_dtype="bf16" — decode/quant.py) UPCASTS
    on read to the stable dtype, so the attention math downstream runs
    full precision whatever the arena stores; for an f32 pool the cast is
    a no-op."""
    S, W = blocks.shape
    _LP, G, HD = pool.shape
    keys = pool[blocks].reshape(S, W * G, num_heads, HD // num_heads)
    return keys.transpose(0, 2, 1, 3).astype(stable_dtype(pool.dtype))


def gather_block_kv_beam(pool: jnp.ndarray, blocks: jnp.ndarray,
                         ancestry: jnp.ndarray, beam: int,
                         num_heads: int) -> jnp.ndarray:
    """One BEAM's dense cache view from the paged pool, (S, H, T,
    d_head): what a whole-sequence cache would hold for beam ``beam`` of
    every slot. The speculative draft-tier roll (decode/spec.py) copies
    the top beam's history into a dense scratch cache once per draft and
    rolls on that — the pool itself is never written by a drafter. The
    pool is written once and never moved, so lane ``beam`` does NOT hold
    the beam's history: position t is row ``ancestry[s, beam, t]*BS + t %
    BS`` (the engine's table, (S, K, T)) of block ``blocks[s, t // BS]``
    (pool and blocks as :func:`gather_block_kv`) — the stored bits, no
    arithmetic. Read-upcast as :func:`gather_block_kv`."""
    S, W = blocks.shape
    _S, _K, T = ancestry.shape
    BS = T // W
    t = jnp.arange(T, dtype=blocks.dtype)
    keys = pool[blocks[:, t // BS], ancestry[:, beam] * BS + t % BS]
    keys = keys.reshape(S, T, num_heads, pool.shape[-1] // num_heads)
    return keys.transpose(0, 2, 1, 3).astype(stable_dtype(pool.dtype))


def lane_mask(ancestry: jnp.ndarray, valid: jnp.ndarray, block_size: int,
              block_rows: int) -> jnp.ndarray:
    """Which of a slot's cached entries each of its beams attends:
    entry (block w, lane j, offset b) belongs to beam q's history iff
    position t = w*BS + b is a valid one of q's (``valid``: (S, K, T)
    bool, beam.step_valid_mask) and ``ancestry[s, q, t] == j``; a block's
    rows past its K*BS (``block_rows``: :func:`pool_block_rows`) belong
    to no history. Returns (S, 1, K, W*block_rows) bool over
    :func:`gather_block_kv`'s key axis, the heads broadcast. An entry
    outside a beam's history gets the -1e9 of an unwritten position, so
    its softmax weight is an exact 0.0: per beam the same 1..T keys and
    values are attended as over a whole-sequence cache reordered after
    every selection."""
    S, K, T = ancestry.shape
    W = T // block_size
    lanes = jnp.arange(K, dtype=ancestry.dtype)[:, None]
    own = ancestry.reshape(S, K, W, 1, block_size) == lanes
    own = own & valid.reshape(S, K, W, 1, block_size)   # (S, K, W, Kj, BS)
    own = own.reshape(S, K, W, K * block_size)
    own = jnp.pad(own, ((0, 0), (0, 0), (0, 0),
                        (0, block_rows - K * block_size)))
    return own.reshape(S, 1, K, W * block_rows)


def append_block_kv(pool: jnp.ndarray, block: jnp.ndarray, row: jnp.ndarray,
                    new: jnp.ndarray) -> jnp.ndarray:
    """Write one decode position into the paged pool: row r's projected
    K (or V) at this step lands at ``pool[block[r], row[r]]``, its heads
    side by side. pool: (L*P, G, H*d_head) (:func:`pool_block_rows`);
    block: (B,) int32, the row's tail block plus the layer's first block;
    row: (B,) int32, ``lane*BS + offset`` in it; new: (B, H, d_head).
    ``mode="drop"``: a row the engine masked out (idle/done slots, whose
    table rows are the sentinel) carries a block past the pool's last and
    writes NOWHERE — a freed block can never be scribbled on by the slot
    that used to own it. The write CASTS to the pool's storage dtype
    (cfg.kv_dtype="bf16" stores the arena half-width — decode/quant.py;
    a no-op for the f32 pool)."""
    return pool.at[block, row].set(
        new.reshape(new.shape[0], -1).astype(pool.dtype), mode="drop")


class FeedForward(nn.Module):
    """Post-LN 4x ReLU FFN (gnn_transformer.py:163-174)."""

    d_model: int
    mult: int = 4
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    residual_dtype: object = None  # see residual_out

    @nn.compact
    def __call__(self, x, *, deterministic: bool):
        h = TorchDense(self.mult * self.d_model, dtype=self.dtype, name="fc1")(x)
        h = jax.nn.relu(h)
        h = TorchDense(self.d_model, dtype=self.dtype, name="fc2")(h)
        h = nn.Dropout(self.dropout_rate, deterministic=deterministic)(h)
        return residual_out(
            nn.LayerNorm(epsilon=1e-5, dtype=stable_dtype(self.dtype),
                         name="norm")(h + x), self.residual_dtype)
