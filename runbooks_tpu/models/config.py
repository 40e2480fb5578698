"""Model architecture configs for the decoder-only transformer families.

The reference framework ships no model code at all — it schedules external
CUDA/PyTorch containers for families documented in its examples/ tree
(reference: examples/llama2-7b/finetuned-model.yaml, examples/falcon-40b/
server.yaml, examples/facebook-opt-125m/base-model.yaml). Here those families
are first-class: one `ModelConfig` describes any of them, and
`runbooks_tpu.models.transformer` consumes it.

All sizes chosen to map well onto the TPU MXU (multiples of 128 where the
family allows it); dtypes default to bfloat16 params/activations with float32
logits/softmax.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp

# Allowed collective_matmul modes (framework-side single source of truth;
# the controller's jax-free validation table mirrors it, like quantize).
COLLECTIVE_MATMUL_MODES = ("off", "ring", "auto")
# Accepted spec.params spellings: snake_case params.json convention, the
# reference's camelCase spec style, and the PARAM_* env round-trip's
# lowercase — same set the controller validates and the trainer aliases.
COLLECTIVE_MATMUL_PARAM_KEYS = (
    "collective_matmul", "collectiveMatmul", "collectivematmul")


def check_collective_matmul(mode: str) -> str:
    """Validate a collective_matmul mode string (single source for the
    error message — transformer/serve/trainer all funnel through here,
    mirroring ops.quantization.resolve_quantize_mode)."""
    mode = str(mode)
    if mode not in COLLECTIVE_MATMUL_MODES:
        raise ValueError(
            f"unknown collective_matmul {mode!r}; expected "
            f"{'|'.join(COLLECTIVE_MATMUL_MODES)}")
    return mode


def resolve_collective_matmul_param(params: dict) -> Optional[str]:
    """First present spelling of the collective_matmul contract param,
    validated; None when the spec doesn't set it. Shared by the serving
    entrypoint and anything else reading a raw params dict, so a
    controller-validated spec can never be silently ignored over a
    spelling mismatch."""
    val = next((params[k] for k in COLLECTIVE_MATMUL_PARAM_KEYS
                if params.get(k) is not None), None)
    return None if val is None else check_collective_matmul(val)


# Token-mixer kinds a layer pattern may name (ModelConfig.layer_types).
LAYER_KINDS = ("full_attention", "linear_attention", "latent_attention",
               "sliding_attention", "conv")
# The kinds whose layers are the period's ONE stack params["layers"] (keys
# and values, or a latent, a token): exactly one of them a period. Every
# other layer of a period (linear_attention, sliding_attention, conv) has a
# stack a position of its kind (params["linear_layers"],
# params["window_layers"], params["conv_layers"]).
ATTENTION_KINDS = ("full_attention", "latent_attention")
# Slots a window layer's ring cache has beyond its window: one dispatch may
# write RING_MARGIN + 1 tokens of a row before its first query reads
# (transformer.KVCache). A decode step writes one.
RING_MARGIN = 8


class AttnShape(NamedTuple):
    """What per-head attention layers of one kind differ in."""
    kv_heads: int
    rope_theta: float
    sink: bool          # a learned logit a query head beside the keys'
    window: int         # 0 = every earlier key
    heads: int          # query heads: wq, wo and the gate are this wide
    rotary_dim: int     # the first rotary_dim dimensions of a head rotate
    rope_yarn: tuple    # () = plain frequencies (ModelConfig.rope_yarn)
    rope_factor: float  # on sin and cos: the rotated part of a score
    #                     carries its square, the part that passes 1
    gate: bool          # sigmoid(u W_g), a number a head, on the core's output
    #                     (ModelConfig.attn_gate_width: or one an element)


MOE_ROUTERS = ("softmax", "sigmoid")
# The mixers a "linear_attention" layer may be (ModelConfig.linear_mixer).
LINEAR_MIXERS = ("gated_delta", "lightning", "kda")


class SparseRead(NamedTuple):
    """The sizes of a full-attention layer's sparse read
    (ops/block_sparse_attention.py), in keys but for topk and init."""
    block: int          # keys a block
    topk: int           # blocks a query reads of a KV head, init among them
    window: int         # keys before and with the query, always read
    init: int           # leading blocks, always read
    kernel: int         # keys a compressed key is the mean of
    stride: int         # keys between the starts of two compressed keys
    dense_len: int      # a row shorter than this is read whole


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a decoder-only transformer."""

    name: str = "custom"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32            # < num_heads => GQA; == 1 => MQA
    head_dim: int = 128
    max_seq_len: int = 4096

    # Normalization
    norm_type: str = "rmsnorm"        # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-5

    # MLP
    gated_mlp: bool = True            # SwiGLU-style gate (llama) vs plain MLP
    activation: str = "silu"          # "silu" | "gelu" | "relu"
    mlp_bias: bool = False

    # Mixture of Experts (models/moe.py, docs/sparse-latent-models.md).
    # 0 experts = dense MLP. With experts, the FFN becomes top-k-routed
    # gated experts, dropless, whose leading dim shards over the "expert"
    # mesh axis (expert parallelism).
    moe_num_experts: int = 0          # experts the router scores over
    moe_top_k: int = 2
    moe_aux_coef: float = 0.01        # load-balance loss weight
    # "softmax": softmax over all experts, the chosen k renormalised
    # (Mixtral). "sigmoid": a sigmoid a score, a selection bias added ONLY
    # to choose, gate weights the chosen scores over their sum. Either
    # way the renormalised weights are multiplied by moe_routed_scale.
    moe_router: str = "softmax"
    moe_router_bias: bool = False     # the selection bias (sigmoid router)
    # Standard deviation of the selection bias in SEEDED weights (a
    # checkpoint brings its own). In score units: at top-8 of 256 a bias of
    # 0.05 is 0.43 logits, a factor 2.4 in an expert's popularity a
    # standard deviation, which no trained, load-balancing bias has.
    moe_router_bias_std: float = 0.05
    moe_routed_scale: float = 1.0
    # What the chosen scores' sum is made safe to divide by: 0 = max(sum,
    # 1e-9); eps > 0 = sum + eps, the form of the models whose published
    # router adds it (a sigmoid's chosen scores may sum to little).
    moe_router_eps: float = 0.0
    # Width of an expert (0 = intermediate_size) and how many shared
    # experts run on every token beside the routed ones (one dense gated
    # MLP of moe_shared_experts x that width, added unscaled).
    moe_intermediate_size: int = 0
    moe_shared_experts: int = 0
    # A chip's share: this process holds the weights of experts
    # [moe_experts_first, moe_experts_first + moe_experts_held) and
    # computes their part of the sum; what the other experts would add is
    # left out (0 held = all of them).
    moe_experts_held: int = 0
    moe_experts_first: int = 0
    # Layers BEFORE the period scan with a dense FFN of intermediate_size
    # (params["leading_layers"]); the periods take num_layers minus these.
    # Their token mixer is of leading_kind: "" = the period's attention
    # kind, or "conv" / "linear_attention" where the pattern has such layers.
    leading_dense_layers: int = 0
    leading_kind: str = ""

    # Attention
    attn_bias: bool = False
    qk_norm: bool = False
    # Width of the QK norm: "head" normalizes each head's head_dim on its
    # own (one [head_dim] scale), "full" the whole projected q / k before
    # the heads are split (one [q_dim] / [kv_dim] scale; OLMo 2/3).
    qk_norm_width: str = "head"
    logit_softcap: Optional[float] = None

    # Positional encoding. "none": no positional signal beside causality
    # (hybrids whose recurrent layers carry order).
    position_type: str = "rope"       # "rope" | "alibi" | "learned" | "none"
    rope_theta: float = 10000.0
    # YaRN (ops/rotary.yarn_inv_freq): () = plain rotary, else
    # (factor, original_max_position, beta_fast, beta_slow, mscale,
    # mscale_all_dim): blended inverse frequencies over the rotated width,
    # sin and cos times yarn_rotary_factor = m(mscale) / m(mscale_all_dim),
    # and (latent attention) the softmax scale times yarn_attn_factor ** 2 =
    # m(mscale_all_dim) ** 2, with m(a) = 0.1 a ln(factor) + 1. The layers
    # of params["layers"] take it; window layers rotate plainly (their keys
    # are never more than a window away).
    rope_yarn: tuple = ()

    # Latent attention (layer kind "latent_attention", MLA): what a token
    # and layer caches is [c (kv_lora_rank), k_r (qk_rope_head_dim)], with
    # no head axis; a head's query is qk_nope_head_dim + qk_rope_head_dim
    # wide, its value v_head_dim.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    # A head's value width. Per-head attention (full_attention,
    # sliding_attention): 0 = head_dim; keys and queries stay head_dim wide.
    v_head_dim: int = 0

    # Per-head attention beyond one shape for every layer
    # (docs/window-full-models.md). rotary_dim: the first rotary_dim of a
    # head's head_dim dimensions rotate, the rest pass (0 = all of them).
    # attn_value_scale multiplies the projected values.
    rotary_dim: int = 0
    attn_value_scale: float = 1.0
    # Layer kind "sliding_attention": a query at position t sees the keys
    # j with 0 <= t - j < sliding_window. Such layers may differ from the
    # full ones in query heads, KV heads, rotary base and rotated width (0
    # = as the full layers), and may have a sink: one learned logit a query
    # head that takes weight in the softmax and gives no value.
    sliding_window: int = 0
    sliding_num_kv_heads: int = 0
    sliding_rope_theta: float = 0.0
    sliding_sink: bool = False
    sliding_num_heads: int = 0
    sliding_rotary_dim: int = 0
    # Per-head output gate, both kinds: g = sigmoid(u W_g) with W_g
    # [hidden, query heads of the kind] on the layer's normed input u, one
    # number a head and token, times the core's output before wo.
    attn_gate: bool = False
    # "head": that. "element": W_g [hidden, query heads x value_head_dim],
    # one number an element of the core's output.
    attn_gate_width: str = "head"
    # Sparse read of the full_attention layers (InfLLM-v2;
    # ops/block_sparse_attention.py, docs/hybrid-models.md). 0 = every
    # layer reads every key its mask allows. With sparse_topk > 0 a query
    # of a row at least sparse_dense_len long reads, of each KV head, its
    # sparse_window last keys, the sparse_init_blocks first blocks of
    # sparse_block keys and the blocks that score highest against the
    # compressed keys (means of sparse_kernel keys, sparse_stride apart),
    # sparse_topk blocks with the initial ones. A row keeps the compressed
    # keys beside k and v (KVCache.ckeys).
    sparse_block: int = 0
    sparse_topk: int = 0
    sparse_window: int = 0
    sparse_init_blocks: int = 0
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_dense_len: int = 0
    # Blocks that lie wholly inside the window are no candidates for the
    # choice (False: they are, and a chosen one is read twice over).
    sparse_exclude_window: bool = True

    # Block structure
    parallel_block: bool = False      # falcon/gpt-neox parallel attn+mlp
    shared_layer_norm: bool = True    # for parallel_block: one LN feeds both
    # "pre": x + f(norm(x)) (every preset above). "post": x + norm(f(x)),
    # the reordered-norm block (the norm sits on the sub-layer's OUTPUT).
    norm_position: str = "pre"

    # Layer pattern (docs/hybrid-models.md): ONE period of token-mixer
    # kinds, repeated num_layers / len(layer_types) times. () = every
    # layer is "full_attention" (a period of one). "linear_attention" is
    # the gated delta rule (ops/gated_delta.py): a fixed-size recurrent
    # state a head instead of keys and values a token.
    layer_types: tuple = ()
    # What a "linear_attention" layer is. "gated_delta": the gated delta
    # rule behind a short convolution (a state and a conv tail a row).
    # "lightning": the decay-only recurrence S_t = lambda_h S_{t-1} + k_t
    # v_t^T (ops/lightning_attention.py): a QK norm a head, no convolution
    # (linear_conv_kernel 0), an output norm over all the heads and an
    # elementwise sigmoid gate; a row keeps the state alone. "kda": Kimi
    # Delta Attention (ops/kda.py), the delta rule with a decay a CHANNEL of
    # the key (g [.., H, d_k], from a low-rank map of linear_gate_rank), a
    # low-rank sigmoid output gate behind the head-wise output norm; a state
    # and a conv tail a row, as the gated delta rule's.
    linear_mixer: str = "gated_delta"
    # KDA's two low-rank maps (the decay's and the output gate's): hidden ->
    # linear_gate_rank -> heads x head width.
    linear_gate_rank: int = 0
    # Lightning's decay lambda_h = exp(-2^(-8 (h + 1) / H) c_l), c_l = 1 -
    # l / lightning_decay_layers + 1e-5 with l the layer's index as run
    # (0 = no layer factor, c_l = 1).
    lightning_decay_layers: int = 0
    # > 0: the linear layers rotate q and k over the whole head at this
    # base, whatever position_type says of the layers with keys.
    linear_rope_theta: float = 0.0
    linear_num_heads: int = 0         # key heads = value heads
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4       # causal depthwise conv on q, k, v
    # beta in (0, 2) instead of (0, 1): the state transition may have
    # negative eigenvalues.
    linear_allow_neg_eigval: bool = True
    # Layer kind "conv", the gated short convolution: [B | C | X] = u W_in,
    # a = (C * conv(B * X)) W_out, conv depthwise and causal over conv_kernel
    # tokens with no bias and no activation. A row keeps B * X at its last
    # conv_kernel - 1 tokens, and no keys or values.
    conv_kernel: int = 3

    # Embeddings / head
    tie_embeddings: bool = False
    embed_scale: bool = False         # multiply embeddings by sqrt(hidden)
    # MiniCPM's scalings (0 / 1 = none): the embeddings times a number, each
    # sub-layer's output times a number before it joins the residual stream,
    # the final norm's output over a number before the head.
    embed_multiplier: float = 0.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0

    # Attention implementation: "auto" picks ring when the active mesh has
    # a sequence axis > 1, else the Pallas flash kernel on TPU, else the
    # XLA reference path. Explicit: "xla" | "flash" | "ring".
    # Measured (v5e-1, bench-410m-d128 bs8x2048 train): flash 44.2% MFU vs
    # xla 23.1% — the XLA path materializes [b,h,s,s] f32 scores in HBM.
    attention_impl: str = "auto"
    # Flash kernel block sizes. None = from the call's own shapes
    # (ops/flash_attention.block_shape: which kernel, the lengths, the
    # window, the group a grid step holds; its table is a sweep on the chip
    # at the calls the benchmark's cells compile, PERF.md section 6, PR 39). An
    # integer is honoured as given (clamped to the lengths): tests pin
    # 16 / 32 / 64 to get several blocks out of toy lengths. No preset
    # sets them.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None

    # Ring attention's per-step inner kernel. None = auto: the Pallas
    # flash kernel per rotated K/V block on TPU (out/lse merge forward, a
    # hand-written second ring pass backward — parallel/ring_attention.py),
    # the XLA einsum path elsewhere. Without the flash inner a
    # sequence-parallel mesh pays the HBM-materialized-scores cost that
    # flash exists to avoid (measured 0.10-0.23 vs 0.44 MFU single-chip).
    ring_flash_inner: Optional[bool] = None

    # Overlapped collective-matmul tensor parallelism
    # (ops/collective_matmul.py): decompose the per-layer TP collectives
    # into lax.ppermute ring steps hidden behind per-shard partial dots —
    # ring all-gather-matmul for the column-parallel q/k/v/gate/up
    # projections, matmul-reduce-scatter for the row-parallel o/down
    # projections (the post-dot all-reduce never exists; the residual
    # stream stays tensor-sharded between layers). "off" (default) keeps
    # the GSPMD collectives — the parity-oracle reference path; "ring"
    # requests the ring; "auto" = ring whenever the active mesh has
    # tensor > 1 ("ring" and "auto" resolve identically today). The
    # pipeline (stage > 1) path always keeps GSPMD TP (see
    # transformer.resolve_collective_matmul); weights whose shapes don't
    # divide the ring fall back per-matmul.
    collective_matmul: str = "off"
    # Circulate ring shards in both directions, halving sequential hop
    # count (takes effect at tensor > 2; a 2-ring has one hop either way).
    collective_matmul_bidirectional: bool = True

    # Embedding lookup as one-hot matmul instead of gather. Under a
    # tensor-sharded vocab, GSPMD partitions the matmul cleanly where the
    # gather forces an involuntary full-remat reshard. Measured on the
    # 8-way virtual mesh (fsdp2 x seq2 x tp2 train step): one-hot removes
    # the all-to-all + all 3 collective-permutes and 3 all-gathers from
    # the compiled HLO; a sequence-sharded mesh hits the same involuntary
    # reshard through the gather's scatter-add transpose. None = auto
    # (one-hot when the active mesh has tensor > 1 OR sequence > 1);
    # True/False force.
    embed_one_hot: Optional[bool] = None

    # Dtypes
    dtype: str = "bfloat16"           # activation dtype
    param_dtype: str = "float32"      # master param dtype

    # Weight-only quantization applied at load time (ops/quantization.py):
    # "none" | "int8" | "int4" (blockwise symmetric; int4 packs two
    # nibbles/byte). Mirrors the reference's Server `quantize: int4`
    # contract (reference: examples/llama2-70b/server.yaml) — the knob that
    # fits the 70B tier on a v5e-8 host and feeds the bandwidth-bound
    # decode path packed weights. The transformer dispatches on the param
    # type (QuantizedArray), so this field only drives the loaders.
    quantize: str = "none"

    # Training-time behavior. "nothing_saveable" = full remat (memory-safe
    # default); "dots_saveable" / "dots_with_no_batch_dims_saveable" save
    # matmul outputs; "save_attn_out" saves only the named per-layer
    # attention output (skips the O(s^2) attention recompute in bwd at
    # O(L*b*s*h) bf16 cost — the selective middle ground); "none" disables
    # remat entirely (all activations saved — single-chip HBM-rich configs
    # only).
    remat_policy: str = "nothing_saveable"

    # Pipeline parallelism: microbatches per step when the mesh has a
    # "stage" axis > 1 (parallel/pipeline.py). 0 = one microbatch per
    # stage; more microbatches shrink the (S-1)/(S+M-1) bubble.
    pipeline_microbatches: int = 0
    # Training schedule when stage > 1: "1f1b" (default) runs the explicit
    # fwd/bwd-interleaved schedule with in-flight activations bounded by
    # O(stages) regardless of microbatch count (parallel/pipeline.py:
    # pipeline_1f1b_grads); "gpipe" differentiates through the forward
    # pipeline (simpler, O(microbatches) live activations — the oracle the
    # 1F1B parity tests compare against).
    pipeline_schedule: str = "1f1b"

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def parameter_dtype(self):
        return jnp.dtype(self.param_dtype)

    def __post_init__(self):
        # A params.json gives a list; the config is hashed (a static
        # argument of jitted and checkpointed functions).
        object.__setattr__(self, "rope_yarn", tuple(self.rope_yarn))
        kinds = self.layer_pattern
        bad = [k for k in kinds if k not in LAYER_KINDS]
        if bad:
            raise ValueError(
                f"unknown layer type(s) {bad}; expected {LAYER_KINDS}")
        if sum(kinds.count(k) for k in ATTENTION_KINDS) != 1:
            raise ValueError(
                "a period of the layer pattern holds exactly one "
                "full_attention or latent_attention layer (params['layers'] "
                "is that layer's stack, scanned a period a step) beside any "
                "number of linear_attention, sliding_attention and conv "
                f"layers; got {kinds}")
        if "conv" in kinds:
            if "linear_attention" in kinds:
                raise ValueError(
                    "conv and linear_attention layers in one pattern would "
                    "share the cache's conv leaf: not written")
            if self.conv_kernel < 2:
                raise ValueError("conv layers need conv_kernel >= 2")
        if self.leading_kind not in ("", self.attention_kind) and not (
                self.leading_kind in ("conv", "linear_attention")
                and self.leading_kind in kinds):
            raise ValueError(
                f"leading_kind {self.leading_kind!r}: the leading layers "
                "are of the period's attention kind, or conv or "
                "linear_attention layers of a pattern that has them")
        if (self.num_layers - self.leading_dense_layers) % len(kinds) \
                or self.leading_dense_layers >= self.num_layers:
            raise ValueError(
                f"num_layers {self.num_layers} less the "
                f"{self.leading_dense_layers} leading layers is not a whole "
                f"number of periods of the layer pattern (length "
                f"{len(kinds)})")
        if "sliding_attention" in kinds:
            if self.sliding_window < 1:
                raise ValueError(
                    "sliding_attention layers need sliding_window >= 1")
            if "latent_attention" in kinds:
                raise ValueError(
                    "sliding_attention layers are per-head attention; "
                    "beside latent_attention they have no form")
            window = self.attn_shape("sliding_attention")
            if window.heads % window.kv_heads:
                raise ValueError(
                    "sliding_num_kv_heads does not divide the window "
                    f"layers' {window.heads} query heads")
        elif self.sliding_num_heads or self.sliding_rotary_dim:
            raise ValueError(
                "sliding_num_heads and sliding_rotary_dim describe "
                "sliding_attention layers; the layer pattern has none")
        for name in ("rotary_dim", "sliding_rotary_dim"):
            r = getattr(self, name)
            if r % 2 or not 0 <= r <= self.head_dim:
                raise ValueError(
                    f"{name} {r} is not an even number of a head's "
                    f"{self.head_dim} dimensions")
        if "latent_attention" in kinds and not (
                self.kv_lora_rank and self.qk_nope_head_dim
                and self.qk_rope_head_dim and self.v_head_dim):
            raise ValueError(
                "latent_attention layers need kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
        if self.moe_router not in MOE_ROUTERS:
            raise ValueError(
                f"unknown moe_router {self.moe_router!r}; expected "
                f"{'|'.join(MOE_ROUTERS)}")
        if self.moe_num_experts:
            first, held = self.moe_experts_first, self.moe_experts_here
            if not 0 <= first <= first + held <= self.moe_num_experts:
                raise ValueError(
                    f"experts [{first}, {first + held}) are not among the "
                    f"{self.moe_num_experts} the router scores over")
        if self.rope_yarn and len(self.rope_yarn) != 6:
            raise ValueError(
                "rope_yarn is (factor, original_max_position, beta_fast, "
                "beta_slow, mscale, mscale_all_dim)")
        if not self.latent_cache and self.yarn_attn_factor != 1.0:
            raise ValueError(
                "rope_yarn with mscale_all_dim on per-head attention: the "
                "softmax scale's m ** 2 is latent attention's; per-head "
                "layers carry YaRN's factor on sin and cos (mscale, with "
                "mscale_all_dim 0)")
        if self.norm_position not in ("pre", "post"):
            raise ValueError(
                f"unknown norm_position {self.norm_position!r}; "
                "expected pre|post")
        if self.norm_position == "post" and self.parallel_block:
            raise ValueError(
                "norm_position: post has no parallel_block form")
        if self.qk_norm_width not in ("head", "full"):
            raise ValueError(
                f"unknown qk_norm_width {self.qk_norm_width!r}; "
                "expected head|full")
        if "linear_attention" in kinds and not (
                self.linear_num_heads and self.linear_key_head_dim
                and self.linear_value_head_dim):
            raise ValueError(
                "linear_attention layers need linear_num_heads, "
                "linear_key_head_dim and linear_value_head_dim")
        if self.linear_mixer not in LINEAR_MIXERS:
            raise ValueError(
                f"unknown linear_mixer {self.linear_mixer!r}; expected "
                f"{'|'.join(LINEAR_MIXERS)}")
        if self.lightning:
            if self.linear_conv_kernel:
                raise ValueError(
                    "linear_mixer: lightning has no short convolution; set "
                    "linear_conv_kernel 0")
            if self.linear_key_head_dim % 2:
                raise ValueError(
                    "lightning's rotary needs an even linear_key_head_dim")
        if self.kda and self.linear_gate_rank < 1:
            raise ValueError(
                "linear_mixer: kda needs linear_gate_rank >= 1 (the width "
                "of its two low-rank gates)")
        if self.leading_kind == "linear_attention" and self.lightning:
            raise ValueError(
                "a leading lightning layer is not written: its decay "
                "depends on the layer's index among the periods' layers")
        if self.attn_gate_width not in ("head", "element"):
            raise ValueError(
                f"unknown attn_gate_width {self.attn_gate_width!r}; "
                "expected head|element")
        if self.sparse_topk:
            sp = self.sparse_read
            if "sliding_attention" in kinds or "latent_attention" in kinds:
                raise ValueError(
                    "a sparse read (sparse_topk) is the full_attention "
                    "layers'; beside sliding_attention layers (whose mask "
                    "is a window already) or latent_attention (no keys a "
                    "head) it has no form")
            if min(sp._replace(init=1)) < 1 or not 0 <= sp.init <= sp.topk \
                    or sp.block % sp.stride or sp.kernel % sp.stride:
                raise ValueError(
                    "a sparse read needs sparse_block, sparse_window, "
                    "sparse_kernel, sparse_stride and sparse_dense_len all "
                    ">= 1, 0 <= sparse_init_blocks <= sparse_topk, and "
                    "sparse_stride dividing sparse_block and sparse_kernel; "
                    f"got {sp}")

    @property
    def layer_pattern(self) -> tuple:
        """One period of layer kinds (never empty)."""
        return tuple(self.layer_types) or ("full_attention",)

    @property
    def num_periods(self) -> int:
        return ((self.num_layers - self.leading_dense_layers)
                // len(self.layer_pattern))

    def layers_of(self, kind: str) -> int:
        """How many of the model's layers are of this kind: those of the
        periods and, for leading_layer_kind, the leading ones. It is the
        length of the kind's leaves in the cache, the leading layers
        first."""
        return (self.num_periods * self.layer_pattern.count(kind)
                + self.leading_layers_of(kind))

    @property
    def leading_layer_kind(self) -> str:
        """The token mixer of the leading layers."""
        return self.leading_kind or self.attention_kind

    def leading_layers_of(self, kind: str) -> int:
        """How many leading layers are of this kind (all or none)."""
        return (self.leading_dense_layers
                if kind == self.leading_layer_kind else 0)

    @property
    def attention_kind(self) -> str:
        """The kind of the period's one layer in params["layers"]."""
        return next(k for k in self.layer_pattern if k in ATTENTION_KINDS)

    @property
    def has_window(self) -> bool:
        """Some layer attends a sliding window and caches a ring."""
        return "sliding_attention" in self.layer_pattern

    @property
    def ring_len(self) -> int:
        """Slots a row of a window layer's ring cache has."""
        return self.sliding_window + RING_MARGIN

    def attn_shape(self, kind: str) -> AttnShape:
        """What the per-head attention layers of `kind` are shaped by: KV
        and query heads, rotary (base, rotated width, YaRN and its factor
        on sin and cos), sink, window and output gate."""
        if kind == "sliding_attention":
            return AttnShape(
                self.sliding_num_kv_heads or self.num_kv_heads,
                self.sliding_rope_theta or self.rope_theta,
                self.sliding_sink, self.sliding_window,
                self.sliding_num_heads or self.num_heads,
                self.sliding_rotary_dim or self.rotary_dim or self.head_dim,
                (), 1.0, self.attn_gate)
        return AttnShape(self.num_kv_heads, self.rope_theta, False, 0,
                         self.num_heads, self.rotary_dim or self.head_dim,
                         self.rope_yarn, self.yarn_rotary_factor,
                         self.attn_gate)

    @property
    def value_head_dim(self) -> int:
        """A head's value width in per-head attention (a latent model's
        v_head_dim is its latent attention's; it has no per-head layer)."""
        if self.latent_cache:
            return self.head_dim
        return self.v_head_dim or self.head_dim

    def q_dim_of(self, kind: str) -> int:
        """Width of wq's output in a per-head layer of `kind`."""
        return self.attn_shape(kind).heads * self.head_dim

    def o_dim_of(self, kind: str) -> int:
        """Width of wo's input in a per-head layer of `kind`."""
        return self.attn_shape(kind).heads * self.value_head_dim

    def gate_dim_of(self, kind: str) -> int:
        """Columns of a per-head layer's output gate W_g."""
        return (self.o_dim_of(kind) if self.attn_gate_width == "element"
                else self.attn_shape(kind).heads)

    @property
    def latent_cache(self) -> bool:
        """The attention layers cache one latent a token, with no head
        axis, instead of keys and values a KV head."""
        return self.attention_kind == "latent_attention"

    @property
    def latent_width(self) -> int:
        """What a latent-attention layer caches a token."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def moe_experts_here(self) -> int:
        """Experts whose weights this process holds."""
        return self.moe_experts_held or self.moe_num_experts

    @property
    def moe_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    def _yarn_m(self, a: float) -> float:
        """m(a) = 0.1 a ln(factor) + 1 of rope_yarn (1 without YaRN)."""
        if not self.rope_yarn or self.rope_yarn[0] <= 1 or not a:
            return 1.0
        import math

        return 0.1 * a * math.log(self.rope_yarn[0]) + 1.0

    @property
    def yarn_attn_factor(self) -> float:
        """m of the YaRN softmax scale, m(mscale_all_dim): latent
        attention's scores are scaled by q_head_dim^-1/2 m^2."""
        return self._yarn_m(self.rope_yarn[5]) if self.rope_yarn else 1.0

    @property
    def yarn_rotary_factor(self) -> float:
        """What YaRN multiplies sin and cos by, m(mscale) /
        m(mscale_all_dim): 1 where the two are equal."""
        if not self.rope_yarn:
            return 1.0
        return (self._yarn_m(self.rope_yarn[4])
                / self._yarn_m(self.rope_yarn[5]))

    @property
    def has_linear_attention(self) -> bool:
        """Some layer is a linear-attention mixer: a matrix state a head
        (and, for the gated delta rule, the tail of its short
        convolution)."""
        return "linear_attention" in self.layer_pattern

    @property
    def lightning(self) -> bool:
        """The linear-attention layers are the decay-only recurrence."""
        return self.has_linear_attention and self.linear_mixer == "lightning"

    @property
    def kda(self) -> bool:
        """The linear-attention layers are Kimi Delta Attention."""
        return self.has_linear_attention and self.linear_mixer == "kda"

    @property
    def sparse_read(self) -> Optional[SparseRead]:
        """The full-attention layers' sparse read, or None (dense)."""
        if not self.sparse_topk:
            return None
        return SparseRead(self.sparse_block, self.sparse_topk,
                          self.sparse_window, self.sparse_init_blocks,
                          self.sparse_kernel, self.sparse_stride,
                          self.sparse_dense_len)

    def compressed_len(self, cache_len: int) -> int:
        """Compressed keys a row of cache_len slots can complete."""
        sp = self.sparse_read
        return max((cache_len - sp.kernel) // sp.stride + 1, 0)

    @property
    def has_short_conv(self) -> bool:
        """Some layer is a gated short convolution: a tail, and no state."""
        return "conv" in self.layer_pattern

    @property
    def has_recurrent_state(self) -> bool:
        """Some layer keeps something of fixed size a row instead of keys
        and values a token (a delta-rule state, a convolution's tail): what
        the serving engine's stale-data invariant does not cover."""
        return self.has_linear_attention or self.has_short_conv

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels under the short convolution: q | k | v."""
        return 2 * self.linear_key_dim + self.linear_value_dim

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def _ffn_params(self, width: int) -> int:
        h = self.hidden_size
        n = (2 if self.gated_mlp else 1) * h * width + width * h
        if self.mlp_bias:
            n += (2 if self.gated_mlp else 1) * width + h
        return n

    def _sparse_ffn_params(self) -> int:
        """The experts HELD, the router (and its selection bias) and the
        shared expert of one sparse layer."""
        E = self.moe_num_experts
        return (self.moe_experts_here * self._ffn_params(self.moe_width)
                + self.hidden_size * E
                + (E if self.moe_router_bias else 0)
                + (self._ffn_params(self.moe_width * self.moe_shared_experts)
                   if self.moe_shared_experts else 0))

    def _attn_params(self, kind: Optional[str] = None) -> int:
        """Attention parameters of one layer of `kind` (None: the kind of
        params["layers"])."""
        kind = kind or self.attention_kind
        if kind == "latent_attention":
            h, H, r = self.hidden_size, self.num_heads, self.kv_lora_rank
            return (h * H * self.q_head_dim + h * self.latent_width
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * h
                    + (self.q_head_dim if self.qk_norm else 0) + r)
        return self._attn_matrices(kind) + self._attn_extras(kind)

    def _attn_matrices(self, kind: str) -> int:
        """wq, wk, wv, wo and the gate of a per-head attention layer:
        queries and keys head_dim wide, values and the output projection's
        input value_head_dim, the gate a column a query head."""
        shape = self.attn_shape(kind)
        return self.hidden_size * (
            self.q_dim_of(kind)
            + shape.kv_heads * (self.head_dim + self.value_head_dim)
            + self.o_dim_of(kind) + (self.gate_dim_of(kind)
                                     if shape.gate else 0))

    def _attn_extras(self, kind: str) -> int:
        """Per-head attention parameters that are no matrix (biases, norm
        scales, sinks): in num_params, not in flops_per_token."""
        shape = self.attn_shape(kind)
        q_dim, k_dim = self.q_dim_of(kind), shape.kv_heads * self.head_dim
        n = shape.heads if shape.sink else 0
        if self.attn_bias:
            n += (q_dim + k_dim
                  + shape.kv_heads * self.value_head_dim + self.hidden_size)
        if self.qk_norm:
            n += (2 * self.head_dim if self.qk_norm_width == "head"
                  else q_dim + k_dim)
        return n

    def _linear_params(self) -> int:
        """One linear-attention mixer. Gated delta: q, k, v, output gate,
        out; the a / b heads; conv; A_log, dt_bias; the output norm (one
        head's width, shared by the heads). Lightning: q, k, v, gate, out;
        the QK norms (a head's width each) and the output norm (all the
        heads'). KDA: q, k, v, out; the two low-rank gates; the b head;
        conv; A_log (a head), dt_bias (a channel); the output norm."""
        h, H = self.hidden_size, self.linear_num_heads
        kd, vd = self.linear_key_dim, self.linear_value_dim
        mats = h * (2 * kd + 2 * vd) + vd * h
        if self.lightning:
            return mats + 2 * self.linear_key_head_dim + vd
        if self.kda:
            return (self._kda_matrices()
                    + self.linear_conv_kernel * self.linear_conv_dim
                    + H + kd + self.linear_value_head_dim)
        return (mats + 2 * h * H
                + self.linear_conv_kernel * self.linear_conv_dim
                + 2 * H + self.linear_value_head_dim)

    def _kda_matrices(self) -> int:
        """W_q, W_k, W_v, W_o, the two low-rank gates and W_beta of one
        KDA mixer."""
        h, r = self.hidden_size, self.linear_gate_rank
        kd, vd = self.linear_key_dim, self.linear_value_dim
        return (h * (2 * kd + vd) + vd * h + 2 * h * r + r * (kd + vd)
                + h * self.linear_num_heads)

    def _short_conv_params(self) -> int:
        """W_in [h, 3h], W_out [h, h] and the kernel of one conv layer."""
        h = self.hidden_size
        return 4 * h * h + self.conv_kernel * h

    @property
    def num_params(self) -> int:
        """Parameter count of what this process holds (embedding included
        once if tied; of a sparse layer the experts held)."""
        h, v = self.hidden_size, self.vocab_size
        embed = v * h
        head = 0 if self.tie_embeddings else v * h
        pos = v * 0
        if self.position_type == "learned":
            pos = self.max_seq_len * h
        attn = self._attn_params()
        dense = self._ffn_params(self.intermediate_size)
        mlp_mats = self._sparse_ffn_params() if self.moe_num_experts \
            else dense
        norms_per_layer = h if (self.parallel_block and self.shared_layer_norm) else 2 * h
        if self.norm_type == "layernorm":
            norms_per_layer *= 2  # scale + bias
        rest = mlp_mats + norms_per_layer
        linear = self._linear_params()
        final_norm = h * (2 if self.norm_type == "layernorm" else 1)
        mixer = {self.attention_kind: attn, "linear_attention": linear,
                 "conv": self._short_conv_params()}
        if self.has_window:
            mixer["sliding_attention"] = self._attn_params(
                "sliding_attention")
        return (embed + head + pos + final_norm
                + self.leading_dense_layers
                * (mixer[self.leading_layer_kind] + dense + norms_per_layer)
                + self.num_periods * sum(
                    mixer[kind] + rest for kind in self.layer_pattern))

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Forward-pass matmul FLOPs per token (2*N plus attention quadratic).

        Used for MFU accounting (train step multiplies by 3 for fwd+bwd).
        """
        s = seq_len or self.max_seq_len
        h = self.hidden_size
        if self.latent_cache:
            # The expanded form: projections, then scores at the query
            # width and the weighted sum at the value width.
            attn_proj = 2 * (self._attn_params() - self.kv_lora_rank
                             - (self.q_head_dim if self.qk_norm else 0))
            attn_scores = 2 * s * self.num_heads * (self.q_head_dim
                                                    + self.v_head_dim)
        else:
            # QK^T at the key width and PV at the value width, per token.
            attn_proj = 2 * self._attn_matrices(self.attention_kind)
            seen, choosing = s, 0
            sp = self.sparse_read
            if sp is not None and s >= sp.dense_len:
                # The keys a query reads at most, and its scores against
                # the compressed keys that choose them.
                seen = min(s, sp.topk * sp.block + sp.window)
                choosing = 2 * (s // sp.stride) * self.num_heads \
                    * self.head_dim
            attn_scores = choosing + 2 * seen * self.num_heads * (
                self.head_dim + self.value_head_dim)
        # A window layer's token sees at most its window, whatever the
        # context.
        sliding = 0.0
        if self.has_window:
            sliding = (
                2 * self._attn_matrices("sliding_attention")
                + 2 * min(s, self.sliding_window)
                * self.attn_shape("sliding_attention").heads
                * (self.head_dim + self.value_head_dim))
        gates = 2 if self.gated_mlp else 1
        dense = 2 * (gates + 1) * h * self.intermediate_size
        mlp = dense
        if self.moe_num_experts:
            # top-k active experts per token (the share of them held
            # here), the shared expert, and the router matmul.
            share = self.moe_experts_here / self.moe_num_experts
            mlp = (2 * (gates + 1) * h * self.moe_width
                   * (self.moe_top_k * share + self.moe_shared_experts)
                   + 2 * h * self.moe_num_experts)
        kd, vd = self.linear_key_dim, self.linear_value_dim
        state = (self.linear_num_heads * self.linear_key_head_dim
                 * self.linear_value_head_dim)
        if self.lightning:
            # The update k v^T and the read S^T q.
            linear = 2 * (h * (2 * kd + 2 * vd) + vd * h) + 4 * state
        elif self.kda:
            # The delta rule (S^T k, the rank-one update, S^T q) and the
            # decay's multiply an element of the state.
            linear = (2 * self._kda_matrices()
                      + 2 * self.linear_conv_kernel * self.linear_conv_dim
                      + 7 * state)
        else:
            # The delta rule itself: S^T k, the rank-one update, S^T q.
            linear = (2 * (h * (2 * kd + 2 * vd) + vd * h
                           + 2 * h * self.linear_num_heads)
                      + 2 * self.linear_conv_kernel * self.linear_conv_dim
                      + 6 * state)
        head = 2 * h * self.vocab_size
        # The two projections, the two gates and the kernel's taps.
        conv = 2 * 4 * h * h + 2 * (self.conv_kernel + 2) * h
        mixer = {self.attention_kind: attn_proj + attn_scores,
                 "linear_attention": linear, "sliding_attention": sliding,
                 "conv": conv}
        return float(
            self.leading_dense_layers
            * (mixer[self.leading_layer_kind] + dense)
            + self.num_periods * sum(
                mixer[kind] + mlp for kind in self.layer_pattern) + head)


def _llama(name, v=32000, h=4096, i=11008, l=32, q=32, kv=32, d=128, s=4096,
           theta=10000.0):
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=l, num_heads=q, num_kv_heads=kv, head_dim=d, max_seq_len=s,
        norm_type="rmsnorm", norm_eps=1e-5, gated_mlp=True, activation="silu",
        position_type="rope", rope_theta=theta,
    )


def _falcon(name, v=65024, h=4544, l=32, q=71, kv=71, s=2048):
    # Falcon: parallel attention+MLP block, layernorm, no gate, GELU,
    # rotary embeddings, biases off for matmuls but LN has bias.
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=4 * h,
        num_layers=l, num_heads=q, num_kv_heads=kv, head_dim=h // q,
        max_seq_len=s, norm_type="layernorm", norm_eps=1e-5, gated_mlp=False,
        activation="gelu", position_type="rope", parallel_block=True,
        tie_embeddings=True,
    )


def _opt(name, v=50272, h=768, i=3072, l=12, q=12, s=2048):
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=l, num_heads=q, num_kv_heads=q, head_dim=h // q,
        max_seq_len=s, norm_type="layernorm", norm_eps=1e-5, gated_mlp=False,
        activation="relu", position_type="learned", attn_bias=True,
        mlp_bias=True, tie_embeddings=True,
    )


def _gemma(name, v=256000, h=2048, i=16384, l=18, q=8, kv=1, d=256, s=8192):
    # Gemma: GeGLU (gated tanh-gelu), embeddings scaled by sqrt(h), tied
    # head, RMSNorm with a (1 + w) scale (handled in the converter).
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=l, num_heads=q, num_kv_heads=kv, head_dim=d,
        max_seq_len=s, norm_type="rmsnorm", norm_eps=1e-6, gated_mlp=True,
        activation="gelu", position_type="rope", tie_embeddings=True,
        embed_scale=True,
    )


def _gpt2(name, v=50257, h=768, i=3072, l=12, q=12, s=1024):
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=l, num_heads=q, num_kv_heads=q, head_dim=h // q,
        max_seq_len=s, norm_type="layernorm", norm_eps=1e-5, gated_mlp=False,
        activation="gelu", position_type="learned", attn_bias=True,
        mlp_bias=True, tie_embeddings=True,
    )


def _olmo_hybrid(name, v=100352, h=3840, i=11008, l=32, q=30, d=128,
                 s=65536, lin_heads=30, lin_dk=96, lin_dv=192):
    # Three gated-delta linear-attention layers, then one full-attention
    # layer; reordered-norm blocks; QK norm over the whole projection; no
    # rotary embedding (the recurrent layers carry order); untied head.
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=l, num_heads=q, num_kv_heads=q, head_dim=d, max_seq_len=s,
        norm_type="rmsnorm", norm_eps=1e-6, gated_mlp=True, activation="silu",
        position_type="none", qk_norm=True, qk_norm_width="full",
        norm_position="post",
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_num_heads=lin_heads, linear_key_head_dim=lin_dk,
        linear_value_head_dim=lin_dv, linear_conv_kernel=4,
        linear_allow_neg_eigval=True,
    )


def _sarvam_mla(name, v=262144, h=4096, i=16384, l=32, q=64, s=131072,
                nope=128, rope=64, vd=128, rank=512, experts=128, top_k=8,
                moe_i=2048, yarn=(40.0, 4096, 32.0, 1.0, 1.0, 1.0)):
    # Latent attention (MLA, direct query projection), one leading dense
    # layer, then sparse layers: sigmoid router with a selection bias,
    # top-k of the routed experts beside one shared expert
    # (docs/sparse-latent-models.md). head_dim is the cached, absorbed
    # width kv_lora_rank + qk_rope_head_dim, as the published config has it.
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=l, num_heads=q, num_kv_heads=q, head_dim=rank + rope,
        max_seq_len=s, norm_type="rmsnorm", norm_eps=1e-6, gated_mlp=True,
        activation="silu", position_type="rope", rope_theta=10000.0,
        rope_yarn=yarn, qk_norm=True,
        layer_types=("latent_attention",), kv_lora_rank=rank,
        qk_nope_head_dim=nope, qk_rope_head_dim=rope, v_head_dim=vd,
        leading_dense_layers=1, moe_num_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_i, moe_shared_experts=1,
        moe_router="sigmoid", moe_router_bias=True, moe_routed_scale=2.5,
    )


def _mimo_v2(name, v=152576, h=4096, i=16384, periods=7, q=64, kv=4,
             swa_kv=8, d=192, vd=128, rot=64, s=262144, window=128,
             windows_a_period=5, experts=256, top_k=8, moe_i=2048):
    # Window layers (a sink, KV heads and rotary base of their own, a ring
    # cache) beside full layers, windows_a_period : 1; keys wider than
    # values; a rotary over part of a head; one leading full-attention
    # layer with a dense FFN, then sparse layers: sigmoid router with a
    # selection bias, no shared expert (docs/window-full-models.md). The
    # published 48 layers are F W W W W F then 7 x (W W W W W F): the first
    # period is one window layer short, which a repeated pattern cannot
    # say, so the preset is the leading layer and the seven whole periods
    # (43 layers).
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=1 + periods * (windows_a_period + 1), num_heads=q,
        num_kv_heads=kv, head_dim=d, v_head_dim=vd, rotary_dim=rot,
        attn_value_scale=0.707, max_seq_len=s, norm_type="rmsnorm",
        norm_eps=1e-5, gated_mlp=True, activation="silu",
        position_type="rope", rope_theta=5000000.0,
        layer_types=("sliding_attention",) * windows_a_period
        + ("full_attention",),
        sliding_window=window, sliding_num_kv_heads=swa_kv,
        sliding_rope_theta=10000.0, sliding_sink=True,
        leading_dense_layers=1, moe_num_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_i, moe_router="sigmoid",
        moe_router_bias=True, moe_router_bias_std=0.01, moe_routed_scale=1.0,
    )


def _laguna(name, v=100352, h=2048, i=8192, periods=9, q=48, swa_q=64,
            kv=8, d=128, rot=64, s=262144, window=512, windows_a_period=3,
            experts=256, top_k=8, moe_i=512,
            yarn=(64.0, 4096, 64.0, 1.0, 1.0, 0.0)):
    # Window and full layers that differ in everything but the KV heads:
    # query heads (q / swa_q), the rotary (full: YaRN over the first `rot`
    # dimensions of a head, its factor m(1) = 0.1 ln(factor) + 1 on sin and
    # cos; window: plain, the whole head), and a per-head sigmoid gate on
    # both. One leading full layer with a dense FFN, then sparse layers:
    # softmax router, the chosen weights renormalised and times 2.5, one
    # shared expert (docs/window-full-models.md). The published 40 layers
    # are (F W W W) x 10: behind the leading layer lie nine whole periods
    # (W W W F) and three window layers more, a period's remainder that a
    # repeated pattern cannot say, so the preset is 1 + 9 x 4 = 37 layers.
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=1 + periods * (windows_a_period + 1), num_heads=q,
        num_kv_heads=kv, head_dim=d, rotary_dim=rot, max_seq_len=s,
        norm_type="rmsnorm", norm_eps=1e-6, gated_mlp=True,
        activation="silu", position_type="rope", rope_theta=500000.0,
        rope_yarn=yarn, attn_gate=True,
        layer_types=("sliding_attention",) * windows_a_period
        + ("full_attention",),
        sliding_window=window, sliding_rope_theta=10000.0,
        sliding_num_heads=swa_q, sliding_rotary_dim=d,
        leading_dense_layers=1, moe_num_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_i, moe_shared_experts=1,
        moe_router="softmax", moe_routed_scale=2.5,
    )


def _lfm2_moe(name, v=65536, h=2048, i=11776, periods=9, lead=2, q=32, kv=8,
              s=128000, convs_a_period=3, kernel=3, experts=64, top_k=4,
              moe_i=1536):
    # Gated short-convolution layers (a tail of kernel - 1 tokens a row, no
    # keys, no values) beside one grouped-query layer in four, which comes
    # FIRST in its period; QK norm a head, before a plain rotary; leading
    # layers that are conv layers with a dense FFN, then sparse layers:
    # sigmoid router with a selection bias, the chosen scores over their sum
    # + 1e-6, no shared expert; tied head (docs/hybrid-models.md). The
    # published 40 layers are c c (F c c c) x 9 F c: behind the two leading
    # layers lie nine whole periods and F c, a period's remainder that a
    # repeated pattern cannot say, so the preset is 2 + 9 x 4 = 38 layers.
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=lead + periods * (convs_a_period + 1), num_heads=q,
        num_kv_heads=kv, head_dim=h // q, max_seq_len=s,
        norm_type="rmsnorm", norm_eps=1e-5, gated_mlp=True,
        activation="silu", position_type="rope", rope_theta=1000000.0,
        qk_norm=True, qk_norm_width="head", tie_embeddings=True,
        layer_types=("full_attention",) + ("conv",) * convs_a_period,
        conv_kernel=kernel, leading_dense_layers=lead, leading_kind="conv",
        moe_num_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_i, moe_router="sigmoid",
        moe_router_bias=True, moe_router_bias_std=0.01,
        moe_routed_scale=1.0, moe_router_eps=1e-6,
    )


def _minicpm_sala(name, v=73448, h=4096, i=16384, periods=8, q=32, kv=2,
                  d=128, s=524288, lin_heads=32, lin_d=128,
                  published_layers=32, block=64, topk=64, window=2048,
                  kernel=32, stride=16, dense_len=8192, scale_emb=12.0,
                  scale_depth=1.4, dim_model_base=256):
    # One full-attention layer with a SPARSE read (InfLLM-v2: blocks chosen
    # by scores against compressed keys, beside a window and the initial
    # block; no rotary; an elementwise output gate) and three lightning
    # layers (decay-only linear attention: QK norm a head, a rotary of
    # their own, an output norm over all heads, an elementwise gate);
    # MiniCPM's scalings with the PUBLISHED depth: embeddings x scale_emb,
    # each sub-layer x scale_depth / sqrt(published layers), the head's
    # input / (hidden / dim_model_base) (docs/hybrid-models.md). The
    # published 32 layers are 8 sparse and 24 lightning ones in NO
    # repeating order (sparse at 0, 9, 16, 17, 22, 29, 30, 31), which a
    # repeated pattern cannot say, so the preset is `periods` regular
    # periods (F L L L) at the published ratio.
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=4 * periods, num_heads=q, num_kv_heads=kv, head_dim=d,
        max_seq_len=s, norm_type="rmsnorm", norm_eps=1e-6, gated_mlp=True,
        activation="silu", position_type="none", qk_norm=True,
        qk_norm_width="head", attn_gate=True, attn_gate_width="element",
        layer_types=("full_attention",) + ("linear_attention",) * 3,
        linear_mixer="lightning", linear_conv_kernel=0,
        linear_rope_theta=10000.0,
        lightning_decay_layers=published_layers - 1,
        linear_num_heads=lin_heads, linear_key_head_dim=lin_d,
        linear_value_head_dim=lin_d,
        sparse_block=block, sparse_topk=topk, sparse_window=window,
        sparse_init_blocks=1, sparse_kernel=kernel, sparse_stride=stride,
        sparse_dense_len=dense_len, embed_multiplier=scale_emb,
        residual_scale=scale_depth / published_layers ** 0.5,
        logit_divisor=h / dim_model_base,
    )


def _kimi_linear(name, v=163840, h=2304, i=9216, periods=6, q=32, s=1048576,
                 nope=128, rope=64, vd=128, rank=512, lin_heads=32, lin_d=128,
                 gate_rank=128, experts=256, top_k=8, moe_i=1024):
    # Kimi Delta Attention layers (the delta rule with a decay a channel,
    # low-rank gates, a short convolution) three to one beside latent
    # attention with NO rotary (direct query projection, no QK norm); one
    # leading KDA layer with a dense FFN, then sparse layers: sigmoid router
    # with a selection bias, the chosen weights renormalised and times
    # 2.446, one shared expert (docs/hybrid-models.md,
    # docs/sparse-latent-models.md). The published 27 layers are K then
    # (K K F K) x 6 then K F: behind the leading layer lie six whole
    # periods and K F, a period's remainder that a repeated pattern cannot
    # say, so the preset is 1 + 6 x 4 = 25 layers. head_dim is the cached
    # width kv_lora_rank + qk_rope_head_dim, as sarvam's.
    return ModelConfig(
        name=name, vocab_size=v, hidden_size=h, intermediate_size=i,
        num_layers=1 + 4 * periods, num_heads=q, num_kv_heads=q,
        head_dim=rank + rope, max_seq_len=s, norm_type="rmsnorm",
        norm_eps=1e-5, gated_mlp=True, activation="silu",
        position_type="none",
        layer_types=("linear_attention", "linear_attention",
                     "latent_attention", "linear_attention"),
        kv_lora_rank=rank, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=vd, linear_mixer="kda", linear_gate_rank=gate_rank,
        linear_num_heads=lin_heads, linear_key_head_dim=lin_d,
        linear_value_head_dim=lin_d, linear_conv_kernel=4,
        linear_allow_neg_eigval=False,
        leading_dense_layers=1, leading_kind="linear_attention",
        moe_num_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_i, moe_shared_experts=1,
        moe_router="sigmoid", moe_router_bias=True, moe_router_bias_std=0.01,
        moe_routed_scale=2.446,
    )


# Registry mirrors the reference's documented example configs
# (reference: examples/ tree — llama2-7b, llama2-70b, falcon-7b/40b,
# facebook-opt-125m) plus debug sizes for tests/benchmarks.
CONFIGS = {
    # Llama-2 family (reference: examples/llama2-7b, examples/llama2-70b)
    "llama2-7b": _llama("llama2-7b"),
    "llama2-13b": _llama("llama2-13b", h=5120, i=13824, l=40, q=40, kv=40, d=128),
    "llama2-70b": _llama("llama2-70b", h=8192, i=28672, l=80, q=64, kv=8, d=128),
    # Llama-3-ish long-context config (net-new capability; SURVEY.md §5.7)
    "llama3-8b": _llama("llama3-8b", v=128256, h=4096, i=14336, l=32, q=32,
                        kv=8, d=128, s=8192, theta=500000.0),
    # Falcon family (reference: examples/falcon-7b-instruct, examples/falcon-40b)
    # 7b: multi-query (1 kv head), single shared layernorm per block;
    # 40b: 8 kv groups, separate attn/mlp layernorms.
    "falcon-7b": _falcon("falcon-7b", kv=1),
    "falcon-40b": dataclasses.replace(
        _falcon("falcon-40b", h=8192, l=60, q=128, kv=8),
        shared_layer_norm=False),
    # OPT (reference: examples/facebook-opt-125m — the CPU smoke model)
    "opt-125m": _opt("opt-125m"),
    "opt-1.3b": _opt("opt-1.3b", h=2048, i=8192, l=24, q=32),
    # Mixtral-style MoE (net-new: the reference has no MoE; expert
    # parallelism over the "expert" mesh axis — models/moe.py)
    "mixtral-8x7b": dataclasses.replace(
        _llama("mixtral-8x7b", v=32000, h=4096, i=14336, l=32, q=32, kv=8,
               d=128, s=32768, theta=1e6),
        moe_num_experts=8, moe_top_k=2),
    # Gemma (MQA 2b / MHA 7b; GeGLU, scaled embeddings, tied head)
    "gemma-2b": _gemma("gemma-2b"),
    "gemma-7b": _gemma("gemma-7b", h=3072, i=24576, l=28, q=16, kv=16),
    # OLMo hybrid: linear-attention (gated delta rule) layers beside full
    # ones, 3:1 (docs/hybrid-models.md)
    "olmo-hybrid-7b": _olmo_hybrid("olmo-hybrid-7b"),
    # Latent attention + sparse experts + a leading dense layer
    # (docs/sparse-latent-models.md)
    "sarvam-105b": _sarvam_mla("sarvam-105b"),
    # Window layers with a sink beside full layers of another KV head
    # count, 192-wide keys on 128-wide values, 256 routed experts
    # (docs/window-full-models.md)
    "mimo-v2-flash": _mimo_v2("mimo-v2-flash"),
    # Window and full layers of other query head counts, rotaries and a
    # per-head output gate, 256 narrow experts beside a shared one
    # (docs/window-full-models.md)
    "laguna-xs.2": _laguna("laguna-xs.2"),
    # Gated short convolutions beside one grouped-query layer in four,
    # leading conv layers with a dense FFN, 64 experts of width 1536
    # (docs/hybrid-models.md)
    "lfm2-24b-a2b": _lfm2_moe("lfm2-24b-a2b"),
    # Sparse-read full layers (blocks chosen by compressed keys) beside
    # lightning linear-attention layers, 1 : 3; MiniCPM's scalings
    # (docs/hybrid-models.md)
    "minicpm-sala": _minicpm_sala("minicpm-sala"),
    # Kimi Delta Attention (a decay a channel) beside latent attention
    # without a rotary, 3 : 1; a leading KDA layer with a dense FFN, 256
    # experts of width 1024 beside a shared one (docs/hybrid-models.md)
    "kimi-linear-48b-a3b": _kimi_linear("kimi-linear-48b-a3b"),
    # GPT-2 (fused-qkv Conv1D checkpoints; learned positions)
    "gpt2": _gpt2("gpt2"),
    "gpt2-xl": _gpt2("gpt2-xl", h=1600, i=6400, l=48, q=25),
    # Debug/bench sizes
    "debug": _llama("debug", v=512, h=128, i=384, l=2, q=4, kv=2, d=32, s=256),
    # One period of the hybrid pattern at toy widths (rbt check, tests)
    "debug-hybrid": _olmo_hybrid("debug-hybrid", v=512, h=128, i=384, l=4,
                                 q=4, d=32, s=128, lin_heads=4, lin_dk=32,
                                 lin_dv=64),
    # The same mechanisms at toy widths: 1 dense + 2 sparse layers, 16
    # experts of which a process may hold a share (rbt check, tests)
    "debug-sparse-latent": _sarvam_mla(
        "debug-sparse-latent", v=512, h=128, i=384, l=3, q=4, s=256,
        nope=32, rope=16, vd=32, rank=64, experts=16, top_k=4, moe_i=64,
        yarn=(4.0, 64, 32.0, 1.0, 1.0, 1.0)),
    # The same mechanisms at the published RATIOS and toy widths: 1 dense
    # full layer + 1 period of (3 window, 1 full), window 8, KV heads 4 / 2
    # under 8 query heads, keys 24 wide (8 rotate) on values 16 wide, 16
    # experts (rbt check, tests)
    "debug-window-full": _mimo_v2(
        "debug-window-full", v=512, h=128, i=384, periods=1, q=8, kv=2,
        swa_kv=4, d=24, vd=16, rot=8, s=256, window=8, windows_a_period=3,
        experts=16, top_k=4, moe_i=64),
    # The same mechanisms at the published RATIOS and toy widths: 1 dense
    # full layer + 1 period of (3 window, 1 full), 6 gated query heads with
    # half a YaRN rotary on full layers, 8 with a plain one on window
    # layers, 2 KV heads (groups of 3 and 4), window 8, 16 experts beside a
    # shared one (rbt check, tests)
    "debug-laguna": _laguna(
        "debug-laguna", v=512, h=128, i=384, periods=1, q=6, swa_q=8, kv=2,
        d=16, rot=8, s=256, window=8, experts=16, top_k=4, moe_i=64,
        yarn=(8.0, 32, 8.0, 1.0, 1.0, 0.0)),
    # The same mechanisms at toy widths: 1 leading conv layer with a dense
    # FFN + 2 periods of (1 full, 3 conv), 4 query heads on 2 KV heads, 8
    # experts, 2 a token (rbt check, tests)
    "debug-lfm2": _lfm2_moe(
        "debug-lfm2", v=512, h=128, i=384, periods=2, lead=1, q=4, kv=2,
        s=256, experts=8, top_k=2, moe_i=64),
    # The same mechanisms at toy widths: 2 periods of (1 sparse-read full,
    # 3 lightning), 4 query heads on 2 KV heads, blocks of 8 keys, 4 chosen
    # with the initial one, a window of 16, compressed keys over 4 keys 2
    # apart, dense below 64 (rbt check, tests)
    "debug-minicpm-sala": _minicpm_sala(
        "debug-minicpm-sala", v=512, h=128, i=384, periods=2, q=4, kv=2,
        d=32, s=256, lin_heads=4, lin_d=32, published_layers=8, block=8,
        topk=4, window=16, kernel=4, stride=2, dense_len=64,
        dim_model_base=32),
    # The same mechanisms at toy widths: 1 leading KDA layer with a dense
    # FFN + 2 periods of (KDA, KDA, latent, KDA), 4 heads, 32 experts, 4 a
    # token (rbt check, tests)
    "debug-kimi-linear": _kimi_linear(
        "debug-kimi-linear", v=512, h=128, i=384, periods=2, q=4, s=256,
        nope=32, rope=16, vd=32, rank=64, lin_heads=4, lin_d=32,
        gate_rank=32, experts=32, top_k=4, moe_i=64),
    "bench-1b": _llama("bench-1b", h=2048, i=5632, l=22, q=16, kv=16, d=128, s=2048),
    "bench-410m": _llama("bench-410m", h=1024, i=2816, l=24, q=16, kv=16, d=64, s=2048),
    # Same params/FLOPs as bench-410m but 8 heads x d128: wider MXU
    # contractions (the 128x128 systolic array wants k>=128).
    "bench-410m-d128": _llama("bench-410m-d128", h=1024, i=2816, l=24, q=8,
                              kv=8, d=128, s=2048),
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in CONFIGS:
        raise KeyError(f"unknown model config {name!r}; known: {sorted(CONFIGS)}")
    cfg = CONFIGS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg
