"""Typed configuration for FIRA-TPU.

The reference keeps hyperparameters in a hardcoded DotDict literal in the
driver (/root/reference/run_model.py:30-46) with no CLI surface beyond the
positional ``train|test``. Here every knob is a frozen dataclass field, with
the reference values as defaults, plus named configs (fira-tiny / fira-full /
fira-large per BASELINE.json) and the three paper ablations as switches.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


class _PromptGeometry:
    """What data/buckets.py asks of any token model's key block: the
    prompt-length buckets (the engine's geometry tags), the padded tokens
    one prefill dispatch may hold, and the longest prompt a slot keeps."""

    @property
    def prompt_len_max(self) -> int:
        return int(self.prompt_buckets[-1])

    def bucket_rows(self, bucket: int) -> int:
        """Requests one prefill dispatch of ``bucket``-long prompts holds."""
        return max(1, self.prefill_token_budget // int(bucket))

    def bucket_errors(self) -> list:
        if list(self.prompt_buckets) != sorted(set(self.prompt_buckets)):
            return [f"lm.prompt_buckets {self.prompt_buckets} must ascend"]
        return []

    def share_errors(self) -> list:
        """The checks every key block with routed experts shares: the
        experts held lie inside the router's width, the buckets ascend."""
        errs = []
        if not 0 <= self.expert_offset \
                <= self.n_routed_experts - self.experts_held:
            errs.append(
                f"lm.expert_offset {self.expert_offset} + lm.experts_held "
                f"{self.experts_held} must lie within lm.n_routed_experts "
                f"{self.n_routed_experts}")
        return errs + self.bucket_errors()


@dataclasses.dataclass(frozen=True)
class LMConfig(_PromptGeometry):
    """A.X-K1's key block (``arch="axk1"``): the published keys of that
    latent-attention, routed-expert decoder by the names its
    ``config.json`` gives them, with A.X-K1's values as the defaults
    (https://huggingface.co/skt/A.X-K1/blob/main/config.json). What this
    engine holds of a deployment comes after them: the experts held here
    of ``n_routed_experts`` (the router keeps its full width), the rows of
    the vocabulary held, and the prefill geometry."""

    hidden_size: int = 7168
    intermediate_size: int = 18432       # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 2048    # each routed / shared expert
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192          # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 32.0            # rope_scaling (type "yarn"), flat
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_original_max_position_embeddings: int = 4096
    vocab_size: int = 163840             # rows of embedding and head held
    # this chip's share of an expert-parallel deployment: experts
    # [expert_offset, expert_offset + experts_held) of every expert layer
    experts_held: int = 192
    expert_offset: int = 0
    # prefill: prompt-length buckets (the engine's geometry tags), the
    # padded tokens one dispatch may hold, and the per-slot prompt arena
    prompt_buckets: tuple = (512, 1024, 2048, 4096)
    prefill_token_budget: int = 8192

    @property
    def latent_dim(self) -> int:
        """What one token caches a layer: [c_kv | rotated k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def errors(self) -> list:
        errs = self.share_errors()
        if self.n_routed_experts % self.n_group:
            errs.append(f"lm.n_group {self.n_group} does not divide "
                        f"lm.n_routed_experts {self.n_routed_experts}")
        return errs


SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(_PromptGeometry):
    """Trinity-Mini's key block (``arch="afmoe"``): the published keys of
    that window-and-full-attention, routed-expert decoder by the names its
    ``config.json`` gives them (``model_type: afmoe``), Trinity-Mini's
    values as the defaults
    (https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json).
    After them, as in :class:`LMConfig`: the experts held here of
    ``num_experts`` and the prefill geometry."""

    hidden_size: int = 2048
    intermediate_size: int = 6144        # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1024    # each routed / shared expert
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    # one entry a layer: every 4th attends over the whole context, without
    # rotary positions; the others over a window, with them
    layer_types: tuple = ((SLIDING,) * 3 + (FULL,)) * 8
    num_experts: int = 128               # the router's outputs
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_norm: bool = True
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True             # embedding times sqrt(hidden_size)
    vocab_size: int = 200192
    # this chip's share: experts [expert_offset, expert_offset +
    # experts_held) of every expert layer (all of them at the defaults)
    experts_held: int = 128
    expert_offset: int = 0
    prompt_buckets: tuple = (1024, 2048, 4096, 8192, 16384)
    prefill_token_budget: int = 16384

    @property
    def n_routed_experts(self) -> int:
        """``num_experts`` under the name the shared grouped products
        (model/axk1.routed_experts) know the router's width by."""
        return self.num_experts

    @property
    def kv_dim(self) -> int:
        """What one token caches a layer: [k | v], every key/value head."""
        return 2 * self.num_key_value_heads * self.head_dim

    def layers_of(self, kind: str) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def errors(self) -> list:
        errs = self.share_errors()
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (SLIDING, FULL) for t in self.layer_types):
            errs.append(
                f"lm.layer_types {self.layer_types} must name "
                f"{SLIDING!r} or {FULL!r} for each of lm.num_hidden_layers "
                f"{self.num_hidden_layers}")
        if self.num_attention_heads % self.num_key_value_heads:
            errs.append(
                f"lm.num_key_value_heads {self.num_key_value_heads} does "
                f"not divide lm.num_attention_heads "
                f"{self.num_attention_heads}")
        return errs


@dataclasses.dataclass(frozen=True)
class JambaConfig(_PromptGeometry):
    """Jamba2-3B's key block (``arch="jamba"``): the published keys of that
    state-space-and-attention decoder by the names its ``config.json`` gives
    them (``model_type: jamba``), Jamba2-3B's values as the defaults
    (https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json).
    Layer i is an attention layer iff ``i % attn_layer_period ==
    attn_layer_offset``; every other layer is a Mamba layer. After the
    published keys: the prefill geometry."""

    hidden_size: int = 2560
    intermediate_size: int = 8192        # every layer's dense SwiGLU
    num_hidden_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    mamba_expand: int = 2                # d_inner = mamba_expand x hidden
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_proj_bias: bool = False
    mamba_conv_bias: bool = True
    num_experts: int = 1                 # 1: no layer routes
    tie_word_embeddings: bool = True
    rms_norm_eps: float = 1e-6
    vocab_size: int = 65536
    prompt_buckets: tuple = (256, 512, 1024, 2048, 4096)
    prefill_token_budget: int = 4096

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def kv_dim(self) -> int:
        """What one token caches an ATTENTION layer: [k | v]."""
        return 2 * self.num_key_value_heads * self.head_dim

    def layer_is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    @property
    def attention_layers(self) -> tuple:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_is_attention(i))

    @property
    def mamba_layers(self) -> tuple:
        return tuple(i for i in range(self.num_hidden_layers)
                     if not self.layer_is_attention(i))

    def errors(self) -> list:
        errs = self.bucket_errors()
        if self.num_experts != 1:
            errs.append(f"lm.num_experts {self.num_experts}: only the dense "
                        f"feed-forward (num_experts 1) is implemented")
        if self.mamba_proj_bias or not self.mamba_conv_bias \
                or not self.tie_word_embeddings:
            errs.append("lm.mamba_proj_bias / lm.mamba_conv_bias / "
                        "lm.tie_word_embeddings other than false / true / "
                        "true are not implemented")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            errs.append(
                f"lm.num_attention_heads {self.num_attention_heads} must "
                f"divide lm.hidden_size {self.hidden_size} and be a "
                f"multiple of lm.num_key_value_heads "
                f"{self.num_key_value_heads}")
        if not self.attention_layers or not self.mamba_layers:
            errs.append(
                f"lm.attn_layer_period {self.attn_layer_period} / "
                f"lm.attn_layer_offset {self.attn_layer_offset} must leave "
                f"lm.num_hidden_layers {self.num_hidden_layers} with layers "
                f"of both kinds")
        return errs


@dataclasses.dataclass(frozen=True)
class BrumbyConfig(_PromptGeometry):
    """Brumby-14B-Base's key block (``arch="brumby"``): the published keys
    of that power-retention decoder (a Qwen3 block whose attention's
    softmax is replaced by power retention) by the names its
    ``config.json`` gives them (``model_type: brumby``), its values as the
    defaults
    (https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json).
    After them: the retention's degree and the normaliser's eps, which the
    row does not carry (benchmark/configs/brumby-14b-l4.json lists them
    under ``assumed``), and the prefill geometry."""

    hidden_size: int = 5120
    intermediate_size: int = 17408      # every layer's SwiGLU
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    vocab_size: int = 151936
    retention_degree: int = 2
    retention_eps: float = 1e-6
    prompt_buckets: tuple = (2048, 4096, 8192, 16384)
    prefill_token_budget: int = 16384

    @property
    def kv_dim(self) -> int:
        """What one generated position keeps a layer: [k | v]."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def state_dim(self) -> int:
        """D: the entries of the degree-2 feature map of a key, d(d+1)/2."""
        return self.head_dim * (self.head_dim + 1) // 2

    def errors(self) -> list:
        errs = self.bucket_errors()
        if self.retention_degree != 2:
            errs.append(f"lm.retention_degree {self.retention_degree}: only "
                        f"degree 2 is implemented")
        if self.attention_bias or self.tie_word_embeddings \
                or self.use_sliding_window:
            errs.append("lm.attention_bias / lm.tie_word_embeddings / "
                        "lm.use_sliding_window other than false are not "
                        "implemented")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            errs.append(
                f"lm.num_key_value_heads {self.num_key_value_heads} must "
                f"divide lm.num_attention_heads {self.num_attention_heads} "
                f"and lm.head_dim {self.head_dim} be even")
        return errs


CONV = "conv"

# LFM2-8B-A1B's 24 layers as published: 18 gated short convolutions, 6
# attention layers (one in three of the layers from 2 on)
_LFM2_LAYERS = ((CONV, CONV, FULL) + (CONV, CONV, CONV, FULL) * 4
                + (CONV, CONV, FULL, CONV, CONV))


@dataclasses.dataclass(frozen=True)
class Lfm2Config(_PromptGeometry):
    """LFM2-8B-A1B's key block (``arch="lfm2"``): the published keys of that
    gated-short-convolution, attention and routed-expert decoder by the
    names its ``config.json`` gives them (``model_type: lfm2_moe``), its
    values as the defaults
    (https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json).
    A layer's token mixer is ``layer_types[i]``: ``"conv"`` or
    ``"full_attention"``; layers from ``num_dense_layers`` on route. After
    them: the prefill geometry. Every expert is held (one chip holds each
    layer whole). The head is the embedding
    (benchmark/configs/lfm2-8b-a1b-l12.json, ``assumed`` (a))."""

    hidden_size: int = 2048
    intermediate_size: int = 7168        # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1792    # each routed expert
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = _LFM2_LAYERS
    conv_L_cache: int = 3                # the short convolution's taps
    conv_bias: bool = False
    num_experts: int = 32                # the router's outputs
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    vocab_size: int = 65536
    prompt_buckets: tuple = (256, 512, 1024, 2048, 4096)
    prefill_token_budget: int = 16384

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_dim(self) -> int:
        """What one token caches an ATTENTION layer: [k | v]."""
        return 2 * self.num_key_value_heads * self.head_dim

    # the published keys under the names the shared pieces know them by
    # (model/afmoe.route, model/axk1.routed_experts, model/jamba.lm_head);
    # every expert held
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def experts_held(self) -> int:
        return self.num_experts

    expert_offset = 0

    @property
    def route_norm(self) -> bool:
        return self.norm_topk_prob

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    @property
    def rms_norm_eps(self) -> float:
        return self.norm_eps

    def layers_of(self, kind: str) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def errors(self) -> list:
        errs = self.bucket_errors()
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (CONV, FULL) for t in self.layer_types):
            errs.append(
                f"lm.layer_types {self.layer_types} must name {CONV!r} or "
                f"{FULL!r} for each of lm.num_hidden_layers "
                f"{self.num_hidden_layers}")
        if self.conv_bias or not self.use_expert_bias \
                or self.conv_L_cache < 2:
            errs.append("lm.conv_bias / lm.use_expert_bias other than false "
                        "/ true, or lm.conv_L_cache under 2, are not "
                        "implemented")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            errs.append(
                f"lm.num_attention_heads {self.num_attention_heads} must "
                f"divide lm.hidden_size {self.hidden_size} and be a "
                f"multiple of lm.num_key_value_heads "
                f"{self.num_key_value_heads}")
        return errs


@dataclasses.dataclass(frozen=True)
class FiraConfig:
    # --- architecture (ARCH_TABLE below): "fira" (the paper's
    # encoder-decoder, every field below) or a decoder-only token model —
    # "axk1" (A.X-K1: latent attention, group-limited routed experts) or
    # "afmoe" (Trinity-Mini: window and full attention layers, 128 small
    # experts) or "jamba" (Jamba2-3B: state-space layers with a recurrent
    # state a beam, two attention layers) or "brumby" (Brumby-14B-Base:
    # power-retention layers, a prompt's state a slot) or "lfm2"
    # (LFM2-8B-A1B: gated short convolutions with a two-token tail a beam,
    # attention layers, 32 routed experts) — whose published
    # keys ``lm`` holds in its own key block; of
    # the fields below such a model reads beam_size, tar_len, the
    # engine/paging knobs and seed ---
    arch: str = "fira"
    lm: Optional[object] = None         # the arch's key block (ARCH_TABLE)

    # --- sequence geometry (reference run_model.py:31-35) ---
    sou_len: int = 210          # diff tokens incl. <start>/<eos>
    tar_len: int = 30           # message tokens incl. <start>/<eos>
    att_len: int = 25           # max sub-tokens per integral token
    ast_change_len: int = 280   # AST-type nodes + edit-op nodes
    sub_token_len: int = 160    # deduplicated sub-token nodes

    # --- model (reference run_model.py:37-39, gnn_transformer.py:41-43) ---
    embedding_dim: int = 256
    num_head: int = 8
    num_layers: int = 6         # shared by GCN stack and decoder
    dropout_rate: float = 0.1   # attention / FFN / combination dropout
    gcn_dropout_rate: float = 0.2  # GCN-layer dropout (gnn_transformer.py:43)
    ffn_mult: int = 4           # FFN hidden = 4 * d (gnn_transformer.py:166)

    # --- vocabulary (filled in from data; run_model.py:44-56) ---
    vocab_size: int = 0
    ast_change_vocab_size: int = 0

    # --- optimization (run_model.py:36,40-43,396) ---
    lr: float = 1e-4
    batch_size: int = 170       # per-chip batch; reference scales 170 x n_gpus
    test_batch_size: int = 20
    epochs: int = 150
    beam_size: int = 3
    seed: int = 0
    # dev-gating cadence (run_model.py:89: epoch>=15, every 10 batches)
    dev_start_epoch: int = 15
    dev_every_batches: int = 10

    # --- ablations (paper Table 3; OUTPUT/output_fira_{no_edit,no_subtoken,nothing}) ---
    use_edit: bool = True           # False => drop change nodes + change edges
    use_subtoken_copy: bool = True  # False => no sub-token copy labels/pointer span

    # --- TPU-first data layout ---
    # Adjacency travels host->device as padded COO (senders/receivers/values),
    # NOT a dense graph_len^2 array (the reference densifies per sample,
    # Dataset.py:336-343 — its biggest throughput sin). Densification to a
    # batch of graph_len^2 happens once per step inside the jitted program.
    # The ADMISSION BOUND on a sample's edges — not the wire's width. The
    # full-scale 90,661-commit corpus measures p100 < 6,000 edges
    # (fullscale/FULLSCALE.json era builds), so 6144 keeps headroom;
    # make_batch raises loudly if a sample ever exceeds it. What a batch's
    # COO rows are PADDED to is a bucket geometry's max_edges: a train
    # dispatch takes the least rung of the halving ladder max_edges / 2^k
    # that holds its commits (data/buckets.edge_ladder — the adjacency
    # scatter prices every slot alike, pad or real), a decode batch the
    # bound itself unless cfg.buckets declares otherwise.
    max_edges: int = 6144
    # "dense": scatter COO into a (B, graph_len^2) adjacency once per step and
    #   run the GCN as a bmm (MXU-friendly at the reference's 650 nodes);
    # "segment": gather/scatter message passing directly on the COO triplets —
    #   O(edges) memory, the path that scales past the 650-node geometry.
    adjacency_impl: str = "dense"
    # Sort each sample's COO edges by (sender, receiver) on the host so the
    # on-device scatter gets indices_are_sorted=True (XLA can lower sorted
    # scatters without its sorting prologue). Semantically a no-op —
    # scatter-add order is irrelevant; equality is pinned by tests.
    sort_edges: bool = False
    # NO READER: the dense adjacency is ONE linearized 1-D scatter whatever
    # this says (model.dense_adjacency). Declared only because
    # benchmark/configs/fira-*.json and tests/benchmark/tiny/configs/
    # fira-tiny.json pass it by keyword; goes with them (ROADMAP D14).
    flat_scatter: bool = True
    # "single": one persistent (B, graph_len, d) encoder node buffer; each
    #   round static-update-slices the Combination rows in place. "split":
    #   the diff rows and the [sub||ast] rows live as two tensors for the
    #   whole stack and the GCN's A.x runs as two column-slab bmms
    #   (A[:,:,:sou] @ top + A[:,:,sou:] @ rest — same FLOPs; the two
    #   adjacency slabs are loop-invariant so XLA hoists them once) — no
    #   650-row buffer update ever materializes (the update-slice's
    #   (B,650,256) copy pairs are the largest single item in the round-4
    #   per-op trace, docs/TPU_OP_TIMES.json). Split sums the bmm in two
    #   parts, so outputs match "single" to matmul reassociation tolerance,
    #   not bitwise; dense adjacency only.
    encoder_buffer: str = "single"
    # "xla": pointer scores materialize the (B,T,S,D) tanh intermediate;
    # "pallas": fused kernel streams it through VMEM (ops/copy_score.py) —
    #   same math, no HBM intermediate (interpreted on the CPU backend only).
    copy_head_impl: str = "xla"

    # --- precision ---
    # Compute dtype for matmuls/attention. Params and the fused output
    # distribution stay float32 for parity; bf16 is the TPU fast path.
    compute_dtype: str = "float32"
    # True (default): post-LN residual streams stay in the stable dtype
    # (f32 under bf16 compute) between layers — the reference's f32
    # numerics. False: LayerNorm statistics still compute in f32 but the
    # output is cast back to the compute dtype, halving every inter-layer
    # activation's HBM bytes under bf16. Exact no-op in f32; a measured
    # perf knob, not a parity path.
    stable_residual: bool = True
    # True (default): the copy head's (B,T,S,D) tanh intermediate is
    # rematerialized in backward (jax.checkpoint) instead of stored —
    # ~1 GB bf16 at flagship. False stores it: ~16 GB HBM chips can afford
    # that at batch 170, trading memory for the recompute.
    copy_head_remat: bool = True

    # --- decode ---
    beam_compat_prob_space: bool = True  # reference prob-space accumulation
                                         # (run_model.py:271,305); False => log-space
    # O(T) cached decode vs full-prefix re-decode — the BATCHED beam's
    # choice (decode/beam.make_beam_search); the slot engine does not read
    # it: its arena is always the cached, paged one.
    beam_kv_cache: bool = True
    # Beam candidate selection from the distribution FACTORS: per-side
    # top-k over the generation softmax (vocab) and the copy softmax
    # (sou+sub positions), gate-scaled and merged — 2k candidates per beam
    # instead of a top-k over the assembled 25,020-way fused tensor. Exact
    # for the top-k VALUES (the fused dist is the two sides scaled by their
    # gate weights, so any global top-k entry is inside a side's top-k);
    # ties between exactly-equal probabilities may break differently than
    # the fused scan order, which is why this is a knob and the
    # token-equality pins ride the test fixtures. The BATCHED beam's
    # choice; the slot engine does not read it and always selects from
    # the factors.
    beam_factored_topk: bool = False
    # Stop the decode loop once every beam of every batch item has emitted
    # EOS (plus ONE settling step), instead of always scanning tar_len-1
    # positions. Bit-exact vs the full scan: finished beams are masked to
    # the sentinel construction, whose only effect past saturation is a
    # single prob-descending re-sort of the beams — the settling step runs
    # it, after which the state is an element-wise fixed point (top_k is
    # stable on the already-sorted sentinel vector). The reference's own
    # Python loop early-exits the same way (run_model.py:276-279). Wall
    # clock scales with the batch's LONGEST message instead of tar_len —
    # the win on real corpora (mean message ~8-10 of 30 positions) is
    # bounded by the per-batch max length, so smaller test batches win
    # more. Parity default off; pinned equivalent in all four
    # kv-cache x factored-topk modes by tests/test_beam_early_exit.py.
    beam_early_exit: bool = False

    # --- continuous-batching decode engine (decode/engine.py) ---
    # True routes run_test through the slot-refill engine: S static slots
    # each advance their own beam one token per step program; EOS-settled
    # slots are harvested and refilled mid-flight from the packer stream,
    # so decode wall clock scales with TOTAL tokens emitted instead of
    # per-batch max length (Orca/vLLM iteration-level batching under this
    # stack's static shapes — docs/DECODE_ENGINE.md). Output is bit-exact
    # per sample vs the batched beam in all four kv-cache x factored-topk
    # modes (tests/test_engine.py).
    decode_engine: bool = False
    # Slot count S (the engine's fixed arena). 0 = test_batch_size: equal
    # geometry with the batched beam — the apples-to-apples default the
    # golden tests pin.
    engine_slots: int = 0
    # Prefilled chunks staged ahead of the refill loop (each holds one
    # packed batch's encoder outputs on device): 1 = prefill strictly on
    # demand; higher overlaps the next chunk's encoder work with the step
    # loop at O(depth * chunk encoder state) extra device memory.
    engine_prefill_depth: int = 2
    # Harvest cadence R: each step dispatch advances live slots R beam
    # positions (a lax.scan of identical one-step bodies) before the host
    # harvests/refills. Slots that settle mid-scan self-mask out, so the
    # cadence changes WHICH dispatch a harvest lands in, never the output
    # (pinned by tests/test_engine.py). R divides per-dispatch overhead
    # (dispatch latency + the done-mask readback sync + insert dispatch
    # coalescing) by R at the cost of settled slots idling up to R-1
    # micro-steps before refill — the R=4 default measures fastest on the
    # CPU length-mix bench (scripts/tpu_decode_bench.py engine_mixed row)
    # and the occupancy loss shows up honestly in slot_occupancy.
    engine_harvest_every: int = 4
    # --- paged KV arena (decode/paging.py; docs/DECODE_ENGINE.md): the
    # engine's per-slot self-attention K/V caches live in a FIXED POOL of
    # KV blocks addressed through per-slot block tables ---
    # NO READER but the refusal of False (decode/paging.paging_errors: that
    # value asked for the whole-sequence arena, which is gone). Declared
    # only because benchmark/configs/fira-*.json and tests/benchmark/tiny/
    # configs/fira-tiny.json pass it by keyword; goes with them (ROADMAP
    # D14).
    engine_paged_kv: bool = True
    # KV block size (positions per block). Must divide EVERY declared
    # decode tar length (cfg.tar_len plus, under decode_tar_buckets, each
    # bucket's tar) so block tables tile each budget exactly — validated
    # at parse time (decode/paging.paging_errors, CLI exit 2). 0 = auto:
    # the largest common divisor of the declared tars <= min(16, tar/2).
    kv_block_size: int = 0
    # Total KV pool size in blocks (the fleet-TOTAL, split evenly across
    # engine_replicas like engine_slots). Must keep every slot servable:
    # per replica, pool >= slots x ceil(smallest decode tar / block) and
    # >= ceil(largest decode tar / block) (one worst-case sample must
    # always fit — the no-livelock floor). 0 = auto: full residency,
    # slots x ceil(tar_len / block) per replica — admission never waits
    # for blocks.
    kv_pool_blocks: int = 0
    # True: the decode bucket table keeps each declared bucket's OWN
    # tar_len instead of pinning tar full, and the engine caps each
    # slot's generation at its bucket's tar budget (its block
    # reservation). Packing assigns by reference-message extent
    # (smallest admissible tar bucket). This is the longer-target-
    # geometry door: raise cfg.tar_len (say 64) and declare the common
    # case (say tar 30) as a bucket — short messages reserve half the
    # blocks, long ones get the full budget, ONE step program serves
    # both. Off (default): tar pinned full on every decode bucket, the
    # byte-identical historical behavior.
    decode_tar_buckets: bool = False
    # --- cross-request prefix cache + in-flight dedup (decode/prefix_cache
    # .py; docs/DECODE_ENGINE.md "Prefix cache & dedup") ---
    # True arms BOTH reuse mechanisms on the engine path: (a) the
    # content-addressed prefill-result cache — each request's prefill
    # artifacts (encoder output / per-layer cross K/V / copy-head src
    # projections) are keyed by a keyed-blake2b digest of its packed
    # payload, and a repeat request seats from the cached artifacts
    # WITHOUT dispatching prefill — and (b) in-flight dedup: a request
    # byte-identical to one already admitted coalesces onto the existing
    # seat and is delivered by fan-out at harvest (one decode, N output
    # positions, each request keeping its own arrival/deadline/TTFT
    # stamps). Both are host-side (no new program geometry: zero
    # post-warmup retraces hold with the cache armed) and bit-exact: a
    # cache-hit or deduped response is byte-identical to its cold run
    # (tests/test_prefix_cache.py). False (default) keeps the historical
    # byte-identical behavior — the equivalence comparator. `cli serve`
    # defaults this ON (--prefix-cache off opts out); drain decode opts
    # in via --prefix-cache on.
    prefix_cache: bool = False
    # LRU capacity of the prefill-result cache, in cached request entries
    # (per engine replica — caches are per-chip like the KV arena they
    # feed). Must be >= 1 when prefix_cache is on (validated at parse
    # time, exit 2 — decode/paging.prefix_cache_errors).
    prefix_cache_entries: int = 256
    # Optional HOST-memory budget for the cache in bytes, per replica
    # (entry payloads are per-layer cross K/V + src projections — MBs per
    # entry at production geometry, so an entry-count bound alone can
    # pin gigabytes of host RAM). 0 = unbounded (the entry cap is the
    # only bound); otherwise LRU entries evict until total payload bytes
    # fit. Must be >= 0 (validated at parse time, exit 2).
    prefix_cache_bytes: int = 0
    # Replicated-engine decode fleet (parallel/fleet.py; docs/MULTICHIP.md):
    # N SlotEngine replicas — one per device/data-mesh slice, each with its
    # own per-chip KV arena and compiled program set — pull packed chunks
    # from ONE shared admission queue, with harvest/refill interleaved
    # across replicas. 1 = the single-engine path, byte-identical behavior.
    # A nonzero engine_slots is the fleet-TOTAL arena and must divide by
    # the replica count (validated at parse time, exit 2); engine_slots=0
    # keeps the per-replica default (test_batch_size slots EACH). Decoded
    # file bytes are invariant to the replica count and to refill
    # interleaving (tests/test_fleet.py).
    engine_replicas: int = 1
    # --- speculative draft-and-verify decode (decode/spec.py;
    # docs/DECODE_ENGINE.md "Speculative drafting") ---
    # "off" (default) | "copy" | "draft": arm draft-and-verify on the slot
    # engine. A drafter proposes engine_spec_k tokens per live slot —
    # "copy": the copy-head distribution alone, scored from the cached
    # source projections against the raw target embedding (NO decoder
    # stack — near-free, rides FIRA's verbatim-copy fraction); "draft": a
    # greedy argmax roll of the existing cached step program on each
    # slot's top beam only (1/beam of the step's decoder rows, scratch
    # caches, real state untouched). ONE verify program then advances the
    # exact one-step body per drafted position under a per-row accept
    # gate (lax.while_loop — early-exits the dispatch once every row has
    # diverged), so ACCEPTED output is bit-exact vs the plain engine BY
    # CONSTRUCTION: every advanced position ran the identical step math,
    # and rejected tails simply were never advanced (tests/test_spec.py
    # pins tokens+probs+file bytes across k, replica count and harvest
    # cadence). Default off: the plain f32 non-spec path stays the
    # byte-identical contract path.
    spec_decode: str = "off"
    # Drafted tokens per slot per verify dispatch (the (S, k) geometry of
    # the engine_draft/engine_verify program family). Must be in
    # [1, smallest declared decode tar budget - 1] and requires
    # decode_engine (validated at parse time, exit 2 —
    # decode/spec.spec_errors).
    engine_spec_k: int = 4
    # --- low-precision serving tiers (decode/quant.py;
    # docs/DECODE_ENGINE.md "Low-precision tiers") ---
    # Storage dtype of the decode self-attention K/V arena — the paged
    # pool's blocks. "f32" (default)
    # is the byte-identical contract path; "bf16" stores the arena at
    # half the bytes (append casts on write, gathers upcast on read, so
    # attention math stays in the compute dtype) — kv_bytes_per_slot
    # halves and the equal-HBM slot count doubles again on top of the
    # paged pool's gain (docs/QUANT_BENCH_r01.jsonl). Engine/fleet
    # program labels carry the tier (…|bf16kv) and prefix-cache digests
    # are tier-namespaced, so a cached f32 artifact can never seat a
    # bf16 slot. Must be f32|bf16; a serving-tier knob, rejected on the
    # training path (validated at parse time, exit 2 —
    # decode/quant.quant_errors).
    kv_dtype: str = "f32"
    # Weight tier of the DECODE-ONLY program family (step / spec draft /
    # verify — prefill and the encoder stay f32): "f32" (default) is the
    # contract path; "bf16" stores the dominant decode matmul weights
    # (decoder stack, copy-head/vocab projections) in bf16 with the
    # matmuls accumulating in the compute dtype; "int8w" stores them as
    # per-channel symmetric int8 with on-the-fly dequant and f32
    # accumulate — quantized ONCE at engine build (and once per
    # respawn/spare prewarm), static shapes unchanged, labels suffixed
    # (…|int8w). Quality is measured, never assumed: BLEU delta +
    # per-request logprob divergence vs the f32 reference land in the
    # bench records (docs/QUANT_BENCH_r01.jsonl). Must be f32|bf16|int8w;
    # int8w/bf16 require decode_engine and are rejected on the training
    # path (validated at parse time, exit 2 — decode/quant.quant_errors).
    serve_precision: str = "f32"

    # --- online serving (serve/; docs/SERVING.md) ---
    # Offered load in requests/second for the open-loop Poisson arrival
    # generator (serve/arrivals.poisson_times). Only read by the serve
    # driver when no arrival-trace file is given; must then be > 0
    # (validated at parse time, CLI exit 2 — serve.server.serve_errors).
    serve_rate: float = 0.0
    # Latency-aware refill: the maximum prefill dispatches interleaved
    # between consecutive step dispatches, PER REPLICA. Every prefill
    # admitted mid-stream stalls the seated slots' next decode step, so
    # a small budget bounds the per-admission stall seated requests pay
    # (tail latency) while a large one maximizes admission throughput —
    # the A/B knob of the serve bench. Must be >= 1 and <= the
    # per-replica slot count (validated at parse time, exit 2).
    serve_prefill_budget: int = 1
    # Per-request deadline in STEP DISPATCHES (the scheduler's clock-free
    # time unit): a request still queued after this many step dispatches
    # since its arrival is SHED (recorded, never a hang); a seated
    # request always runs to harvest and a late completion is flagged,
    # not killed. 0 = no deadline. Must be 0 or >= 1 — a request cannot
    # complete in less than one step (validated at parse time, exit 2).
    serve_deadline_steps: int = 0
    # Admission-queue bound: an arrival that finds this many requests
    # already queued is rejected on the spot (structured shed-on-
    # backpressure — the rejection is recorded in ServeStats and the
    # output file keeps the position with an empty line). 0 = unbounded.
    serve_queue_cap: int = 0

    # --- disaggregated serving tiers (serve/disagg.py; docs/SERVING.md
    # "Disaggregated tiers") ---
    # Tier topology: "off" = historical in-process serve (prefill and
    # decode share the scheduler's jax runtime); "prefill-pool" =
    # DistServe-style process split — a pool of prefill worker processes
    # (each with its own jax runtime + params) computes seat-ready
    # artifacts (the prefix-cache payload) and ships them over a
    # pipe/shared-memory transport, so decode replicas admit every
    # request through the all-hit cache path and NEVER dispatch a
    # prefill program post-warmup. Requires prefix_cache and
    # decode_engine. Must be off|prefill-pool (validated at parse time,
    # exit 2 — serve.disagg.disagg_errors).
    serve_tiers: str = "off"
    # Prefill-pool width: worker processes in the prefill tier. Each
    # holds a full jax runtime (spawn-context process, the
    # ingest_exec=process template), so startup costs one runtime init +
    # per-bucket prefill compile per worker. Output bytes are invariant
    # to this knob by contract (tests/test_disagg.py). Must be >= 1
    # (validated at parse time, exit 2 — serve.disagg.disagg_errors).
    prefill_workers: int = 2
    # Backpressure bound on the prefill tier: total artifact bytes
    # in flight (submitted to workers, not yet delivered to the decode
    # tier's caches) stays under this budget, so a fast prefill tier
    # cannot OOM the host by racing ahead of decode. Sized from the
    # per-row artifact estimate the worker ready-handshake reports; a
    # single over-budget group alone still ships (same degrade rule as
    # the prefix cache's byte cap). 0 = unbounded. Must be >= 0
    # (validated at parse time, exit 2 — serve.disagg.disagg_errors).
    serve_artifact_budget_mb: int = 64

    # --- online raw-diff ingest (ingest/; docs/INGEST.md) ---
    # Feeder workers dedicated to per-request diff ingest tasks (parse +
    # AST extraction + encode + single-row assembly, run worker-side so
    # the scheduler thread never pays them). 0 = reuse feeder_workers —
    # the default; ingest is the same bounded worker pool as corpus
    # assembly, just heavier per task. Must be >= 0 (validated at parse
    # time, CLI exit 2 — ingest.service.ingest_errors).
    ingest_workers: int = 0
    # Over-budget policy for a diff whose measured extents exceed the
    # config geometry (sou/sub/ast-change/max_edges budgets):
    # "clip" (default) deterministically truncates — trailing diff
    # tokens at a chunk-safe boundary, whole tokens' sub-token lists,
    # trailing AST/change nodes with their edges, trailing family edges
    # — and records exactly what was dropped in the request's ingest
    # stamps; "shed" rejects the request with a recorded error (empty
    # output line, the quarantine contract). Either way the assembled
    # payload ALWAYS fits its bucket: admissibility is decided here, at
    # ingest, never by a mid-loop make_batch backstop. Must be
    # clip|shed (validated at parse time, exit 2).
    ingest_truncate: str = "clip"
    # --- ingest fast path (ingest/cache.py; docs/INGEST.md "Fast path") ---
    # True (default) arms BOTH ingest reuse layers on the raw-diff path:
    # (a) the whole-diff result cache — requests content-addressed by a
    # keyed blake2b digest of the raw diff BYTES at intake, in front of
    # lex/parse: a byte-identical repeat skips the entire lex/AST/
    # assemble pipeline and seats from a capacity/byte-bounded LRU of
    # assembled wire payloads, its `_ingest` stamps replayed with a
    # `cached` flag (the PR-10 prefill cache then also fires on the same
    # payload digest — two cache layers, one repeat); and (b) hunk-level
    # AST memoization — the AST parse/diff stage is memoized per typed
    # hunk content, so NEAR-identical diffs (one file changed out of
    # many) reuse parsed sub-results with the merge re-run
    # deterministically. Both are bit-exact: cache-on output bytes equal
    # cache-off equal the frozen-corpus path (tests + check.sh ingest-
    # cache smoke). False is the pristine comparator.
    ingest_cache: bool = True
    # Whole-diff result-cache LRU capacity in cached request entries.
    # 0 = unbounded (the byte budget, if set, is then the only bound).
    # Must be >= 0 (validated at parse time, CLI exit 2).
    ingest_cache_entries: int = 512
    # Optional host-memory budget for the whole-diff cache in bytes
    # (assembled single-row payloads are ~tens of KB at tiny geometry,
    # ~MB at production). 0 = unbounded. Must be >= 0 (validated at
    # parse time, exit 2).
    ingest_cache_bytes: int = 0
    # Execution mode for the GIL-bound AST parse/diff stage of ingest:
    # "thread" (default) runs it inline on the feeder worker threads
    # (the native astdiff calls release the GIL, but the JSON/tree/edge
    # mapping around them is pure Python); "process" ships the stage to
    # a spawned process pool sized by the ingest worker count — the
    # worker thread parks on the result (GIL released) while OTHER
    # workers keep lexing/assembling, so a slow AST parse never
    # head-of-line-blocks the next request's lex. Output is bit-exact
    # either way (the stage is a pure function of its inputs). Must be
    # thread|process (validated at parse time, exit 2).
    ingest_exec: str = "thread"

    # --- robustness / fault injection (robust/; docs/FAULTS.md) ---
    # Seeded fault-injection spec "site:kind:rate:seed[,...]" arming named
    # injection points along the request path (sites: feeder.assemble,
    # feeder.device_put, ingest.parse, engine.prefill, engine.step,
    # engine.harvest, fleet.replica, serve.admit, cache.lookup,
    # ingest.cache, disagg.transport, disagg.worker; kinds:
    # raise | hang | corrupt).
    # Deterministic given the seed — every chaos run replays exactly —
    # and validated at parse time (robust.faults.robust_errors, CLI
    # exit 2). "" = off: the injector is None and every site check is one
    # is-None branch, zero hot-path overhead.
    inject_faults: str = ""
    # Per-dispatch wall-clock watchdog in seconds: a fleet/serve replica
    # dispatch (prefill/step/harvest) that exceeds it is ABANDONED on its
    # worker thread and the replica retired, its in-flight requests
    # requeued onto survivors; in train, a dev gate that exceeds it is
    # skipped with a recorded warning instead of wedging the epoch.
    # 0 = off (dispatches run inline, zero overhead); must be 0 or > 0
    # (validated at parse time, exit 2).
    dispatch_watchdog_s: float = 0.0
    # Poison-request quarantine depth: how many retries (with backoff) a
    # request gets when its host-side assembly, admission, or prefill
    # raises, before it is SHED with a recorded error and an empty output
    # line (extending the serve shed contract — the feeder's per-task
    # error channel keeps one bad sample from poisoning the whole feed).
    # Must be >= 0 (validated at parse time, exit 2).
    robust_retries: int = 1
    # Wall seconds an injected "hang" fault sleeps — bounded on purpose,
    # so an unwatched chaos run stalls and recovers instead of wedging
    # forever; set it well above dispatch_watchdog_s to exercise
    # retirement. Must be > 0 (validated at parse time, exit 2).
    fault_hang_s: float = 2.0

    # --- self-healing fleet (robust/recovery.py; docs/FAULTS.md
    # "Recovery contracts") ---
    # Replacement budget PER REPLICA LINEAGE: how many times a retired
    # replica slot may be respawned (fresh engine on the dead replica's
    # device — params re-device_put, paged pool re-allocated, prewarmed
    # through the declared label family — or a warm spare attached)
    # before the lineage degrades permanently (the PR-9 retire-and-
    # requeue behavior). 0 (default) = respawn off: retirement stays
    # terminal, byte-identical historical behavior. Must be >= 0
    # (validated at parse time, exit 2 — recovery.recovery_errors).
    max_respawns: int = 0
    # Pre-built prewarmed standby engines (the warm-spare pool): a
    # retirement attaches a spare to the shared admission queue in O(1)
    # instead of paying a mid-run engine build + prewarm. Spares idle
    # until attached and count against max_respawns when they attach
    # (the budget bounds REPLACEMENTS, however they are built). Only
    # meaningful with max_respawns >= 1 (validated at parse time,
    # exit 2). Must be >= 0.
    engine_spares: int = 0
    # Respawn backoff BASE in wall seconds: a crash-looping lineage waits
    # the shared robust.faults.backoff_s curve (linear in the attempt,
    # capped at 5x) rescaled to this base between replacements — and, on
    # the deterministic virtual clock, min(attempt, 5) scheduler rounds
    # (wall sleeps only happen on the wall clock, the quarantine-backoff
    # split). Must be > 0 (validated at parse time, exit 2).
    respawn_backoff_s: float = 0.25

    # --- typed edges (beyond-parity extension) ---
    # The reference computes six edge families then flattens them into one
    # untyped adjacency (process_edge's `kind` is dead, Dataset.py:346-357;
    # SURVEY Appendix B). True learns one scalar gain per family
    # (graph_build.EDGE_KIND_*) applied to the normalized edge weights;
    # initialized to 1.0, i.e. exactly the reference graph at init.
    typed_edges: bool = False

    # --- dropout PRNG ---
    # "threefry" (default): JAX's counter-based generator, reproducible
    # across backends. "rbg": hardware random-bit generator — faster random
    # bits on TPU (dropout costs ~10 ms of the measured 107 ms fira-full
    # step on the earlier machine, docs/PERF.md). Param init is threefry
    # either way (identical initial weights); checkpoints store the key, so
    # a resume must use the impl it was trained with.
    rng_impl: str = "threefry"

    # --- gradient accumulation ---
    # >1 accumulates A micro-batches of batch_size into ONE optimizer step
    # normalized over the global (sum, count) — the single-chip reproduction
    # of the reference's 4-GPU DataParallel batch-680 dynamics
    # (run_model.py:102-105; A=4, batch_size=170 matches it exactly).
    # Mutually exclusive with fused_steps>1. Epoch tails smaller than A run
    # as ONE accumulated step padded with all-invalid micro-batches — the
    # same smaller-final-batch dynamics as the reference's DataLoader tail.
    # Composes with cfg.buckets: the grouped scheduler (data/grouping.py)
    # packs A same-geometry micro-batches per dispatch, per bucket.
    accum_steps: int = 1

    # --- device loop ---
    # >1 runs K train steps per dispatch via lax.scan over K stacked batches
    # (train.step.make_multi_step): host/dispatch overhead drops to 1/K and
    # the host loop can't jitter the chip. Semantics are step-identical to
    # K single dispatches (pinned by tests); dev-gate/log/checkpoint
    # boundaries round to group edges. NOTE the gate fires BEFORE the group
    # with the params from before it, so best-checkpoint evaluation can be
    # up to K-1 steps stale and multiple due gates inside one group collapse
    # to one — pick K dividing dev_every_batches (then the only staleness is
    # the gate-before-group ordering, same as the reference's evaluate-then-
    # train batch loop; train() now warns loudly — console + TrainResult
    # .warnings — when K does not divide the cadence). Epoch-tail batches
    # (< K) run per-step. Composes with cfg.buckets: the grouped scheduler
    # (data/grouping.py) packs K same-geometry batches per dispatch.
    fused_steps: int = 1

    # --- host input pipeline (data/feeder.py; docs/PIPELINE.md) ---
    # Background threads assembling batches (make_batch + sharded
    # device_put) ahead of the train/dev/decode loops. 0 = synchronous
    # assembly on the consumer thread (debug fallback + the control leg
    # feed_stall_frac is measured against). Batch ORDER is identical for
    # any worker count — the deterministic (seed, epoch) sequence is
    # computed up front and reassembled in order (pinned by tests).
    feeder_workers: int = 2
    # Max batches in flight (dispatched, not yet consumed): bounds host
    # memory at O(depth * batch_bytes) while keeping assembly + H2D ahead
    # of the step dispatch.
    feeder_depth: int = 4

    # --- bucketed padding geometry (data/buckets.py; docs/BUCKETING.md) ---
    # Declared family of smaller padding geometries, each entry
    # (ast_change_len, max_edges, tar_len) <= the full values above; the
    # full geometry is always the implicit fallback bucket. The packer
    # assigns every sample to its smallest admissible bucket and groups
    # same-bucket samples into batches, so XLA compiles |buckets|+1
    # programs per entry point — all pre-warmed at startup, zero
    # post-warmup retraces (the sanitizer learns the declared family).
    # () = no declared table: decode/dev run the single full geometry
    # (byte-identical batches), training the edge ladder (the COO pad
    # alone follows the split, AST and target axes full; max_edges above).
    # sou_len/sub_token_len are NOT bucketable (the copy-label id space
    # and fused output width bake them in). Composes with the grouped
    # device programs: fused_steps/accum_steps > 1 makes the scheduler
    # (data/grouping.py) pack bucket-HOMOGENEOUS groups of K (or A)
    # same-geometry batches per dispatch — the program family becomes
    # (geometry x entrypoint x group size), all pre-warmed, still zero
    # post-warmup retraces. The CLI's --buckets auto fills this from the
    # corpus length histograms.
    buckets: tuple = ()

    # --- long context ---
    # >1 routes decoder cross-attention through ring attention
    # (parallel/ring.py) over a (data, seq) mesh with that many sequence
    # shards: K/V blocks rotate on the ICI ring, peak attention memory drops
    # to O(T_local^2) per device. 0/1 = dense attention (FIRA's 370-key
    # geometry fits one chip; the knob is the long-context scaling path).
    seq_shards: int = 0

    @property
    def graph_len(self) -> int:
        # 650 = 210 + 160 + 280 (run_model.py note; paper §5.4 "up to 650 nodes")
        return self.sou_len + self.sub_token_len + self.ast_change_len

    @property
    def copy_len(self) -> int:
        # pointer span: diff positions + sub-token positions
        return self.sou_len + self.sub_token_len

    @property
    def output_vocab_size(self) -> int:
        # fused gen+copy distribution width (Model.py:81: 24650+210+160=25020)
        return self.vocab_size + self.sou_len + self.sub_token_len

    def replace(self, **kw) -> "FiraConfig":
        return dataclasses.replace(self, **kw)


# Named configs per BASELINE.json "configs".
def fira_full(**kw) -> FiraConfig:
    """Paper hyperparameters (reference run_model.py:30-46)."""
    return FiraConfig(**kw)


def fira_tiny(**kw) -> FiraConfig:
    """2-layer GNN, d=64 — CPU smoke / overfit config."""
    base = dict(
        embedding_dim=64,
        num_layers=2,
        num_head=4,
        sou_len=32,
        tar_len=12,
        att_len=6,
        ast_change_len=24,
        sub_token_len=24,
        batch_size=16,
        test_batch_size=8,
        epochs=30,
        dev_start_epoch=0,
        dev_every_batches=4,
        max_edges=512,
    )
    base.update(kw)
    return FiraConfig(**base)


def fira_large(**kw) -> FiraConfig:
    """8-layer, d=512, beam-8 (BASELINE.json v4-32 config)."""
    base = dict(
        embedding_dim=512,
        num_layers=8,
        beam_size=8,
    )
    base.update(kw)
    return FiraConfig(**base)


# The measured production performance knob set — the "stacked" row of the
# round-4 honest TPU ablation (docs/PERF.md: 68.75 ms/step vs 86.0 with the
# parity defaults at fira-full/170/bf16; the knobs interact, their solo
# deltas sum to less). Every knob is semantics-preserving or
# equivalence-tested; presets keep parity defaults, callers opt in:
#   cfg.replace(**PRODUCTION_PERF_KNOBS)
# bench.py applies this set by default (FIRA_BENCH_PRODUCTION_KNOBS
# overrides), so the single definition lives here.
PRODUCTION_PERF_KNOBS = {
    "rng_impl": "rbg",
    "fused_steps": 8,
    "sort_edges": True,
    "stable_residual": False,
    "copy_head_remat": False,
}


# The decode-side production set (the CPU-provable half): the three beam
# levers whose output equivalence is already pinned —
# beam_kv_cache (token-identical to full-prefix re-decode), factored
# per-side top-k (token-exact vs the assembled 25,020-way fused tensor),
# and the while_loop early exit (bit-exact tokens AND probs in all four
# kv x factored modes, tests/test_beam_early_exit.py). The set is not
# measured on the chip (scripts/tpu_decode_bench.py has the rows to run);
# per-config defaults stay parity until it is. `--perf production` on the
# CLI applies this set alongside PRODUCTION_PERF_KNOBS.
DECODE_PERF_KNOBS = {
    "beam_kv_cache": True,
    "beam_factored_topk": True,
    "beam_early_exit": True,
    # Slot-refill continuous batching (decode/engine.py): run_test decodes
    # through the S-slot engine — per-sample bit-exact vs the batched beam
    # (tests/test_engine.py), wall clock scales with total tokens emitted.
    # engine_slots/engine_prefill_depth keep their config defaults (slots
    # = test_batch_size).
    "decode_engine": True,
}


# What every token model's preset fixes outside ``lm``: the slot engine
# with a paged pool for the generated positions, log-space beams (the head
# is a log-softmax and has no copy side), bfloat16 weights and cache.
_LM_ENGINE = dict(
    decode_engine=True, beam_compat_prob_space=False,
    compute_dtype="bfloat16", beam_size=3, tar_len=64,
)


def _lm_preset(arch: str, lm, kw: dict) -> FiraConfig:
    """``lm=`` in ``kw`` may be a key block or a dict of its keys to
    replace; ``vocab_size`` always follows the block's."""
    over = kw.pop("lm", None)
    if isinstance(over, dict):
        lm = dataclasses.replace(lm, **over)
    elif over is not None:
        lm = over
    base = dict(_LM_ENGINE, arch=arch, lm=lm)
    base.update(kw)
    base["vocab_size"] = lm.vocab_size
    return FiraConfig(**base)


def axk1_ep16(**kw) -> FiraConfig:
    """A.X-K1 at its published widths, one chip's share of a 16-chip
    expert-parallel deployment (benchmark/configs/axk1-ep16.json says how
    it was cut): the dense layer + 6 expert layers, 12 of 192 routed
    experts, an eighth of the vocabulary."""
    base = dict(engine_slots=64, test_batch_size=16)
    base.update(kw)
    return _lm_preset("axk1", LMConfig(num_hidden_layers=7, experts_held=12,
                                       vocab_size=20480), base)


def axk1_tiny(**kw) -> FiraConfig:
    """Every mechanism of A.X-K1 at CPU-test widths: d 64, 4 heads, 16
    routed experts in 4 groups, top-4, 3 layers (1 dense + 2 expert)."""
    base = dict(engine_slots=4, test_batch_size=4, tar_len=16,
                compute_dtype="float32")
    base.update(kw)
    return _lm_preset("axk1", LMConfig(
        hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
        n_group=4, topk_group=2, vocab_size=512, experts_held=16,
        rope_original_max_position_embeddings=32,
        prompt_buckets=(16, 32, 64), prefill_token_budget=64), base)


# published layers 1-5 of Trinity-Mini's 32: the second dense layer, then
# one whole period of expert layers, three window to one full
_AFMOE_STAGE = (SLIDING, SLIDING, FULL, SLIDING, SLIDING)


def trinity_mini_l5(**kw) -> FiraConfig:
    """Trinity-Mini at its published widths, one pipeline stage on one
    chip (benchmark/configs/trinity-mini-l5.json says how it was cut):
    published layers 1-5 with all 128 experts of every expert layer and
    the whole vocabulary."""
    base = dict(engine_slots=48, test_batch_size=16)
    base.update(kw)
    return _lm_preset("afmoe", AfmoeConfig(
        num_hidden_layers=5, num_dense_layers=1, layer_types=_AFMOE_STAGE),
        base)


def afmoe_tiny(**kw) -> FiraConfig:
    """Every mechanism of Trinity-Mini at CPU-test widths: d 64, 4 query
    over 2 key/value heads of 16, a window of 8, the stage's five layer
    types (1 dense + 4 expert), 8 experts of width 32, top-2."""
    base = dict(engine_slots=4, test_batch_size=4, tar_len=16,
                compute_dtype="float32")
    base.update(kw)
    return _lm_preset("afmoe", AfmoeConfig(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window=8,
        layer_types=_AFMOE_STAGE, num_experts=8, num_experts_per_tok=2,
        vocab_size=64, experts_held=8, prompt_buckets=(16, 32, 64),
        prefill_token_budget=64), base)


def jamba2_3b(**kw) -> FiraConfig:
    """Jamba2-3B as published, whole on one chip
    (benchmark/configs/jamba2-3b.json): 26 Mamba layers and 2 attention
    layers, the whole vocabulary, 6.06 GB of bfloat16 weights."""
    base = dict(engine_slots=64, test_batch_size=16)
    base.update(kw)
    return _lm_preset("jamba", JambaConfig(), base)


def jamba_tiny(**kw) -> FiraConfig:
    """Every mechanism of Jamba2-3B at CPU-test widths: d 64, 4 layers
    (layer 1 attention: 4 query heads over 1 key/value head of 16; layers
    0, 2, 3 Mamba: d_inner 128, d_state 16, d_conv 4, dt_rank 4)."""
    base = dict(engine_slots=4, test_batch_size=4, tar_len=16,
                compute_dtype="float32")
    base.update(kw)
    return _lm_preset("jamba", JambaConfig(
        hidden_size=64, intermediate_size=96, num_hidden_layers=4,
        attn_layer_period=4, attn_layer_offset=1, num_attention_heads=4,
        mamba_dt_rank=4, vocab_size=64, prompt_buckets=(16, 32, 64),
        prefill_token_budget=64), base)


def brumby_14b_l4(**kw) -> FiraConfig:
    """Brumby-14B-Base at its published widths, one pipeline stage on one
    chip (benchmark/configs/brumby-14b-l4.json says how it was cut):
    published layers 0-3 of 40 and the whole vocabulary, 5.75 GB of
    bfloat16 weights."""
    base = dict(engine_slots=32, test_batch_size=16)
    base.update(kw)
    return _lm_preset("brumby", BrumbyConfig(num_hidden_layers=4), base)


def brumby_tiny(**kw) -> FiraConfig:
    """Every mechanism of Brumby-14B-Base at CPU-test widths: d 64, 2
    layers, 4 query over 2 key/value heads of 16 (D = 136)."""
    base = dict(engine_slots=4, test_batch_size=4, tar_len=16,
                compute_dtype="float32")
    base.update(kw)
    return _lm_preset("brumby", BrumbyConfig(
        hidden_size=64, intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=512, prompt_buckets=(16, 32, 64),
        prefill_token_budget=64), base)


# published layers 0-11 of LFM2-8B-A1B's 24: both dense layers, then ten
# expert layers, three of them attention (two and a half periods)
_LFM2_STAGE = _LFM2_LAYERS[:12]


def lfm2_8b_a1b_l12(**kw) -> FiraConfig:
    """LFM2-8B-A1B at its published widths, one pipeline stage on one chip
    (benchmark/configs/lfm2-8b-a1b-l12.json says how it was cut): published
    layers 0-11 of 24 with all 32 experts of every expert layer and the
    whole vocabulary, 7.86 GB of bfloat16 weights."""
    base = dict(engine_slots=64, test_batch_size=16)
    base.update(kw)
    return _lm_preset("lfm2", Lfm2Config(num_hidden_layers=12,
                                         layer_types=_LFM2_STAGE), base)


def lfm2_tiny(**kw) -> FiraConfig:
    """Every mechanism of LFM2-8B-A1B at CPU-test widths: d 64, the stage's
    first five layer types (2 dense + 3 expert: conv, conv, attention,
    conv, conv), 4 query over 2 key/value heads of 16, 8 experts of width
    32, top-2, the convolution's 3 taps."""
    base = dict(engine_slots=4, test_batch_size=4, tar_len=16,
                compute_dtype="float32")
    base.update(kw)
    return _lm_preset("lfm2", Lfm2Config(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        layer_types=_LFM2_STAGE[:5], num_experts=8, num_experts_per_tok=2,
        vocab_size=64, prompt_buckets=(16, 32, 64), prefill_token_budget=64),
        base)


NAMED_CONFIGS = {
    "fira-tiny": fira_tiny,
    "fira-full": fira_full,
    "fira-large": fira_large,
    "axk1-ep16": axk1_ep16,
    "axk1-tiny": axk1_tiny,
    "trinity-mini-l5": trinity_mini_l5,
    "afmoe-tiny": afmoe_tiny,
    "jamba2-3b": jamba2_3b,
    "jamba-tiny": jamba_tiny,
    "brumby-14b-l4": brumby_14b_l4,
    "brumby-tiny": brumby_tiny,
    "lfm2-8b-a1b-l12": lfm2_8b_a1b_l12,
    "lfm2-tiny": lfm2_tiny,
}


def get_config(name: str, **kw) -> FiraConfig:
    if name not in NAMED_CONFIGS:
        raise KeyError(f"unknown config {name!r}; choose from {sorted(NAMED_CONFIGS)}")
    return NAMED_CONFIGS[name](**kw)


def config_errors(cfg: FiraConfig) -> list:
    """Parse-time admission for the core train-loop knobs the CLI
    exposes with bare integer flags (--epochs/--fused-steps/
    --accum-steps/--seq-shards): one named-knob message per violation,
    CLI exit 2 — the same contract as mesh.divisibility_errors /
    serve.server.serve_errors, enforced for every CLI-writable knob by
    the firacheck KNOB-VALIDATE lint (docs/ANALYSIS.md)."""
    errs = []
    if cfg.epochs < 1:
        errs.append(f"epochs {cfg.epochs} must be >= 1")
    if cfg.fused_steps < 1:
        errs.append(
            f"fused_steps {cfg.fused_steps} must be >= 1 (1 = per-step "
            f"dispatch; K > 1 runs K steps per dispatch as one device "
            f"loop)")
    if cfg.accum_steps < 1:
        errs.append(
            f"accum_steps {cfg.accum_steps} must be >= 1 (1 = no "
            f"gradient accumulation)")
    if cfg.fused_steps > 1 and cfg.accum_steps > 1:
        errs.append(
            f"fused_steps {cfg.fused_steps} and accum_steps "
            f"{cfg.accum_steps} are mutually exclusive (one device-loop "
            f"axis per dispatch); set one of them to 1")
    if cfg.seq_shards < 0:
        errs.append(
            f"seq_shards {cfg.seq_shards} must be >= 0 (0/1 = dense "
            f"cross-attention, N > 1 ring-shards K/V over N devices)")
    return errs + arch_errors(cfg)


@dataclasses.dataclass(frozen=True)
class Arch:
    """What an ``arch`` name stands for, in ONE place: the key block
    ``cfg.lm`` must be (None: the model reads FiraConfig's own fields),
    the module that holds the model (a token model's has ``init_params``,
    ``prefill``, ``decode_step``, ``COUNTERS``), and the class of
    decode/slot_model.py that puts it behind the engine's seam."""

    lm_block: Optional[type]
    model: str
    slot_model: str


ARCH_TABLE = {
    "fira": Arch(None, "fira_tpu.model.model", "FiraSlotModel"),
    "axk1": Arch(LMConfig, "fira_tpu.model.axk1", "LMSlotModel"),
    "afmoe": Arch(AfmoeConfig, "fira_tpu.model.afmoe", "AfmoeSlotModel"),
    "jamba": Arch(JambaConfig, "fira_tpu.model.jamba", "JambaSlotModel"),
    "brumby": Arch(BrumbyConfig, "fira_tpu.model.brumby",
                   "BrumbySlotModel"),
    "lfm2": Arch(Lfm2Config, "fira_tpu.model.lfm2", "Lfm2SlotModel"),
}
ARCHS = tuple(ARCH_TABLE)


def arch_errors(cfg: FiraConfig, command: Optional[str] = None) -> list:
    """What an architecture does not run yet is refused by name, never
    run silently as something else: every token model (``axk1``, ``afmoe``,
    ``jamba``, ``brumby``, ``lfm2``) goes through the same lines below. ``command``: the CLI's (``train`` /
    ``test`` / ``serve`` / ``message``), where there is one."""
    if cfg.arch not in ARCHS:
        return [f"arch {cfg.arch!r} not in {list(ARCHS)}"]
    block = ARCH_TABLE[cfg.arch].lm_block
    if block is None:
        return ([f"arch {cfg.arch!r} takes no lm block (got {cfg.lm!r})"]
                if cfg.lm is not None else [])
    arch, lm, errs = cfg.arch, cfg.lm, []
    if not isinstance(lm, block):
        return [f"arch {arch!r} needs an lm block "
                f"(config.{block.__name__})"]

    def no(what: str) -> None:
        errs.append(f"arch {arch!r} does not support {what} yet")
    if command in ("train", "serve", "message"):
        no(f"cli {command} (cli test --engine runs it)")
    if not cfg.decode_engine:
        no("the batched non-engine beam (decode_engine off); run it "
           "through the slot engine (--engine)")
    if cfg.beam_compat_prob_space:
        no("probability-space beams (beam_compat_prob_space): its head "
           "is a log-softmax")
    if cfg.prefix_cache:
        no("prefix_cache")
    if cfg.spec_decode not in (None, "off"):
        no(f"spec_decode {cfg.spec_decode!r}")
    if cfg.serve_precision != "f32" or cfg.kv_dtype != "f32":
        no(f"serve_precision {cfg.serve_precision!r} / kv_dtype "
           f"{cfg.kv_dtype!r} tiers (int8w among them): its weights and "
           f"cache are bfloat16 from creation")
    if cfg.engine_replicas > 1:
        no(f"engine_replicas {cfg.engine_replicas} > 1")
    if cfg.serve_tiers != "off":
        no(f"serve_tiers {cfg.serve_tiers!r} (serve/disagg.py)")
    if cfg.buckets or cfg.decode_tar_buckets:
        no("graph bucket tables (buckets / decode_tar_buckets): its "
           "prefill buckets are lm.prompt_buckets")
    return errs + lm.errors()


def apply_ablation(cfg: FiraConfig, ablation: Optional[str]) -> FiraConfig:
    """Map the paper's ablation names onto config switches.

    no_edit     -> drop edit (change) nodes and their edges (Table 3 row 2)
    no_subtoken -> drop the sub-token copy pointer span (Table 3 row 3)
    nothing     -> both (Table 3 row 4)
    """
    if ablation in (None, "", "none", "full"):
        return cfg
    if ablation == "no_edit":
        return cfg.replace(use_edit=False)
    if ablation == "no_subtoken":
        return cfg.replace(use_subtoken_copy=False)
    if ablation == "nothing":
        return cfg.replace(use_edit=False, use_subtoken_copy=False)
    raise KeyError(f"unknown ablation {ablation!r}")
