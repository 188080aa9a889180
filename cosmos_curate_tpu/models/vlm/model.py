"""CurateVLM: the vision-language captioning model.

Equivalent capability of the reference's vLLM-served VLM captioners
(cosmos_curate/models/vllm_qwen.py, vllm_interface.py — Qwen-VL-class
models behind the plugin ABC). This is our own Flax architecture, TPU-first:

- vision tower = the shared ViT backbone (models/vit.py), whose patch
  tokens are projected into the LM embedding space (one image/frame-group →
  ``vision_tokens`` embeddings);
- language model = decoder-only transformer with RoPE and grouped-query
  attention, TP-sharded via the Megatron-style annotations in
  models/layers.py (replaces vLLM's NCCL TP with pjit sharding);
- inference is cache-centric: ``apply`` consumes and returns a static-shape
  slot-based KV cache ``[L, B, Hkv, S, Dh]``, so prefill (T=bucket) and
  decode (T=1) are the same compiled family of programs. No dynamic shapes.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field, replace
from functools import partial
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from cosmos_curate_tpu.models.layers import MODEL_AXIS, dense
from cosmos_curate_tpu.models.vlm.gated_delta import GatedDeltaMixer
from cosmos_curate_tpu.models.vlm.mamba2 import Mamba2Mixer
from cosmos_curate_tpu.models.vlm.short_conv import ShortConvMixer
from cosmos_curate_tpu.ops import delta_rule as delta_ops
from cosmos_curate_tpu.ops import ssm as ssm_ops
from cosmos_curate_tpu.ops.tiling import round_up
from cosmos_curate_tpu.models.vit import VIT_B_16, VIT_TINY_TEST, ViT, ViTConfig, preprocess_frames
from cosmos_curate_tpu.models.vlm.vision_qwen import (
    QWEN2_VL_2B_VISION,
    QWEN25_VL_7B_VISION,
    QWEN3_VL_MOE_VISION,
    QWEN3_VISION_TINY_TEST,
    QWEN25_VISION_TINY_TEST,
    QWEN_VISION_TINY_TEST,
    QwenVisionConfig,
    QwenVisionTower,
    frames_to_patches,
)


@dataclass(frozen=True)
class MoEConfig:
    """Sparse mixture-of-experts FFN (the Qwen3-VL-MoE captioner class,
    reference models/vllm_qwen.py:313-349 serves Qwen3-VL-30B/235B via
    vLLM expert parallelism). Router semantics match HF Qwen3MoE: softmax
    over ALL experts in fp32, THEN top-k, renormalized."""

    n_experts: int = 8
    top_k: int = 2
    hidden: int = 512  # per-expert intermediate (HF moe_intermediate_size)
    # expert-queue capacity = ceil(top_k * tokens / n_experts * factor);
    # None = no-drop (capacity = token count) — exact HF equivalence, used
    # by tests and small decode batches
    capacity_factor: float | None = None
    # -- the DeepSeek-V2 class (HF ``DeepseekV2MoE``); the defaults are Qwen3's --
    # one more SwiGLU of this width that EVERY token visits, added to the
    # routed sum (HF ``n_shared_experts * moe_intermediate_size``); 0 = none
    shared_hidden: int = 0
    # leading layers that keep the dense SwiGLU (HF ``first_k_dense_replace``)
    first_dense: int = 0
    # group-limited routing (HF ``topk_method: group_limited_greedy``): the
    # experts are ``n_group`` runs of consecutive experts, a token keeps the
    # ``topk_group`` groups whose best expert scores highest and takes its
    # top-k among those only; 1 group = plain top-k
    n_group: int = 1
    topk_group: int = 1
    # HF ``norm_topk_prob`` and ``routed_scaling_factor``: the top-k scores
    # renormalised to sum to one or left as they are, then times the factor
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # what a sigmoid router adds to the chosen scores' sum before it divides by
    # it: the model's own constant (afmoe publishes 1e-20, lfm2_moe 1e-6)
    norm_topk_eps: float = 1e-20
    # "queue": GShard's fixed queues (``capacity_factor``). "sorted": exact
    # and proportional, the assignments sorted by expert into one grouped
    # matrix product (ops/grouped_matmul.py): no queue, no drop, and rows
    # that do not share a queue cannot move each other's results
    dispatch: str = "queue"
    # the experts THIS program holds, (first, count), of an expert-parallel
    # deployment's ``n_experts`` (sorted dispatch only). The router keeps all
    # its outputs and its top-k; the layer returns the held experts' part of
    # the sum (plus the shared expert), what such a chip contributes before
    # the exchange. None = all of them
    held: tuple[int, int] | None = None
    # -- the afmoe class (HF ``AfmoeTokenChoiceRouter``) --
    # what the router's outputs become before the choice: "softmax" over all
    # experts, or "sigmoid", each expert's score on its own
    score_func: str = "softmax"
    # a stored float32 ``[n_experts]`` bias added to the scores for the CHOICE
    # and not for the weights (the load balancer's handle on the router: HF
    # ``expert_bias``); False = none stored
    selection_bias: bool = False
    # the router's product in full float32 ("highest"): on the chip a float32
    # matmul at the default precision multiplies bfloat16 operands, so the
    # stored float32 kernel is ROUNDED there, and a score moves by what a
    # bfloat16 router's would (2e-3). None = the backend's default, what the
    # older sparse flavors were measured with
    router_precision: str | None = None
    # the engine's programs also hand out what every token's router CHOSE in
    # every sparse layer (``[sparse layers, rows, T, top_k]`` int32, their last
    # output; the engine reads none of it). For whoever holds the programs to a
    # plain reference where no routing margin can be counted on: with every
    # expert held (``held=None``) each layer's near-tie counts, a bfloat16
    # hidden state takes another expert than float32 does at one token in six,
    # and the only comparison that holds every layer follows the program's own
    # choice (perfbench/drivers/caption_engine_conv.py, caption_engine_mellum.py).
    # A hybrid's programs, and the paged programs over two pools
    hand_out_choice: bool = False

    def __post_init__(self) -> None:
        if self.dispatch not in ("queue", "sorted"):
            raise ValueError(f"dispatch must be queue|sorted, got {self.dispatch!r}")
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"score_func must be softmax|sigmoid, got {self.score_func!r}")
        if self.score_func == "sigmoid" and self.n_group > 1:
            raise ValueError("sigmoid scores under a group limit: no flavor here defines that routing")
        if self.n_experts % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"{self.n_experts} experts do not make {self.n_group} groups, top {self.topk_group}")
        if self.dispatch == "queue" and (self.held is not None or self.shared_hidden or self.first_dense or self.hand_out_choice):
            raise ValueError("held / shared / leading dense layers / hand_out_choice need dispatch='sorted'")
        if self.dispatch == "sorted" and self.capacity_factor is not None:
            raise ValueError("sorted dispatch drops nothing: it takes no capacity_factor")
        first, count = self.held_experts
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"held={self.held} is no run of the {self.n_experts} experts")

    @property
    def held_experts(self) -> tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2; HF ``DeepseekV2Attention``):
    low-rank queries, ONE compressed row a token in the cache (``kv_lora_rank``
    latent values and a ``qk_rope_head_dim`` key shared by all heads: decoupled
    rope), per-head keys and values up-projected from it. The rope carries
    YaRN's frequency interpolation, whose numbers are the last six."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # HF ``rope_scaling`` of type "yarn"; factor 1 = plain rope
    yarn_factor: float = 1.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @property
    def cache_width(self) -> int:
        """Lanes of a cached row: ``[c_kv | k_rope]`` padded with zeros to whole
        128-lane tiles (576 -> 640), the only width the chip stores or Mosaic
        slices (models/vlm/paged_kv.py::init_latent_pool)."""
        return round_up(self.kv_lora_rank + self.qk_rope_head_dim, 128)

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope) ** -0.5`` times YaRN's ``mscale(all_dim) ** 2``."""
        m = yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def yarn_mscale(factor: float, mscale: float) -> float:
    """HF ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(
    dim: int, theta: float, factor: float, original_max: int, beta_fast: float = 32.0, beta_slow: float = 1.0
) -> np.ndarray:
    """The rope frequencies of ``dim`` rotary dims under YaRN (HF
    ``_compute_yarn_parameters``, ``DeepseekV2YarnRotaryEmbedding``):
    extrapolated (plain) for the fast dims, interpolated (/ factor) for the slow
    ones, a linear ramp between the dims that turn ``beta_fast`` and
    ``beta_slow`` times over the ORIGINAL context. Depends on the factor and
    that context alone, not on ``max_seq``; factor <= 1 is plain rope. The one
    table of the latent layer's decoupled dims and of a GQA layer's whole head
    (``YarnConfig``). float32 ``[dim / 2]``."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra.astype(np.float32)

    def dim_of(rotations: float) -> float:
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


@dataclass(frozen=True)
class YarnConfig:
    """YaRN on a GQA layer's rope (HF ``rope_parameters`` of ``rope_type: yarn``):
    ``yarn_inv_freq``'s numbers, and what cos and sin are multiplied by (HF
    ``attention_factor``; None = ``0.1 ln(factor) + 1``), so that a logit
    carries its square."""

    factor: float = 1.0
    original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None

    @property
    def gain(self) -> float:
        return yarn_mscale(self.factor, 1.0) if self.attention_factor is None else self.attention_factor

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        return yarn_inv_freq(dim, theta, self.factor, self.original_max, self.beta_fast, self.beta_slow)


@dataclass(frozen=True)
class Mamba2Config:
    """The Mamba-2 mixer of a hybrid decoder (models/vlm/mamba2.py; HF
    ``GraniteMoeHybridMambaLayer``). ``d_inner = n_heads * head_dim``; B and C
    are shared by all heads (one group)."""

    n_heads: int = 64
    head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    # tokens a step of the prefill scan covers (HF ``mamba_chunk_size``)
    chunk: int = 256

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:  # x | B | C pass through the convolution
        return self.d_inner + 2 * self.d_state


@dataclass(frozen=True)
class GatedDeltaConfig:
    """The gated-delta-rule mixer of a hybrid decoder (models/vlm/gated_delta.py;
    flash-linear-attention's ``GatedDeltaNet``, HF's ``linear_*`` keys, or its
    ``KimiDeltaAttention``, HF's ``linear_attn_config``): ``n_heads`` heads, each
    a ``[key_dim, value_dim]`` float32 state; q, k and v each pass through a
    short convolution of their own."""

    n_heads: int = 30
    key_dim: int = 96
    value_dim: int = 192
    d_conv: int = 4
    # beta = 2 sigmoid(.) in place of sigmoid(.): a state's eigenvalue along k
    # is 1 - beta, in (-1, 1) (HF ``linear_allow_neg_eigval``)
    allow_neg_eigval: bool = True
    # tokens a step of the prefill scan solves at once (ops/delta_rule.py)
    chunk: int = 64
    # -- Kimi Delta Attention (arXiv:2510.26692; flash-linear-attention's
    # ``KimiDeltaAttention``); the defaults are GatedDeltaNet's --
    # the decay a CHANNEL, ``[n_heads, key_dim]`` a token, out of a low-rank
    # pair of this rank (``f_a_proj``, ``f_b_proj``; ``dt_bias`` a channel,
    # ``A_log`` a head); None = a scalar a head out of ``a_proj``
    decay_rank: int | None = None
    # the output gate a SIGMOID of a low-rank pair of this rank (``g_a_proj``,
    # ``g_b_proj``); None = silu of one full projection (``g_proj``)
    gate_rank: int | None = None

    def __post_init__(self) -> None:
        if (self.decay_rank is None) != (self.gate_rank is None):
            raise ValueError("decay_rank and gate_rank are Kimi Delta Attention's pair: both set or neither")

    @property
    def conv_dim(self) -> int:  # q | k | v pass through the convolutions
        return self.n_heads * (2 * self.key_dim + self.value_dim)


@dataclass(frozen=True)
class ShortConvConfig:
    """The gated short convolution of a hybrid decoder (models/vlm/short_conv.py;
    HF ``Lfm2ShortConv``): a causal depthwise convolution of ``l_cache`` taps
    over the model's own width, gated before and after. It has no state matrix:
    what a request carries is the convolution's last ``l_cache - 1`` inputs."""

    l_cache: int = 3  # taps (HF ``conv_L_cache``)


@dataclass(frozen=True)
class IndexerConfig:
    """A learned indexer beside GQA (DeepSeek-Sparse-Attention; HF ``sa_config``):
    every layer scores the earlier positions for each query with ``n_heads``
    small heads against ONE index key a position, ``I(t, s) = sum_j w_t[j] *
    relu(qI_t[j] . kI_s)``, and the query attends to the ``top_k`` positions
    that score highest (every position while there are no more than that). The
    index keys are a second array a position beside K/V (``cache_width`` lanes a
    row: paged_kv.init_index_pool); ops/sparse_attention.py has the programs."""

    n_heads: int = 16
    head_dim: int = 64
    top_k: int = 2048

    @property
    def cache_width(self) -> int:
        """Lanes of a cached index key: ``[kI | zeros]`` in whole 128-lane tiles,
        the only width the chip stores or Mosaic slices (as ``MLAConfig``'s)."""
        return round_up(self.head_dim, 128)

    @property
    def weight_scale(self) -> float:
        """What the heads' weights are multiplied by: ``n_heads ** -0.5`` times the
        index heads' own softmax scale ``head_dim ** -0.5``."""
        return self.n_heads**-0.5 * self.head_dim**-0.5


_RECURRENT_KINDS = ("mamba", "linear_attention", "conv")


@dataclass(frozen=True)
class VLMConfig:
    vocab: int = 512
    dim: int = 1024
    n_layers: int = 12
    n_heads: int = 16
    n_kv_heads: int = 8
    head_dim: int = 64
    hidden_mult: float = 4.0
    max_seq: int = 1024
    rope_theta: float = 10000.0
    # Qwen2-family checkpoints put biases on q/k/v (not o); ours default off.
    qkv_bias: bool = False
    vision: ViTConfig = VIT_B_16
    vision_tokens: int = 64  # LM embeddings per image after pooling
    # "vit" = our shared ViT backbone + projector; "qwen2" = the Qwen2-VL
    # vision tower (vision_qwen.py), whose merger IS the projector
    vision_variant: str = "vit"
    qwen_vision: QwenVisionConfig | None = None
    # Qwen2-VL multimodal rope: freq dims split into (t, h, w) sections
    # (HF `rope_scaling.mrope_section`); None = standard 1D rope
    mrope_section: tuple[int, int, int] | None = None
    rms_eps: float = 1e-6
    # tied = logits via embed.attend (Qwen2-VL-2B); untied checkpoints
    # (Qwen2.5-VL-7B) carry a separate lm_head matrix
    tied_embeddings: bool = True
    # Qwen3 family: per-head-dim RMSNorm on q/k before rope
    qk_norm: bool = False
    # sparse MoE FFN replaces the dense SwiGLU on every layer when set
    moe: MoEConfig | None = None
    # Qwen3-VL interleaves the (t, h, w) m-rope components across frequency
    # dims ([THW THW ... TT], preserving frequency continuity) instead of
    # Qwen2-VL's chunked [TTT HHH WWW] sections
    mrope_interleaved: bool = False
    # Hybrid decoders (Granite-4.0-H): the kind of every layer, "attention"
    # or "mamba"; None = attention throughout. A "mamba" layer replaces the
    # attention half of a layer with the Mamba-2 mixer ``mamba`` describes;
    # its state lives in the engine's recurrent store, not in the KV pool.
    # A "linear_attention" layer (Olmo-Hybrid; HF's own word) replaces it with
    # the gated-delta-rule mixer ``gated_delta`` describes, whose state lives
    # in the same store. A "conv" layer (LFM2; HF's own word) replaces it with
    # the gated short convolution ``short_conv`` describes, which carries its
    # tails in the store and no state beside them. One decoder has one
    # recurrent kind.
    # Window and full attention mixed (afmoe; HF's own two words):
    # "sliding_attention" layers see ``sliding_window`` positions and keep
    # their K/V in the engine's window pool, "full_attention" layers (like
    # "attention") see every earlier position out of the pool every flavor has.
    layer_types: tuple[str, ...] | None = None
    sliding_window: int | None = None
    mamba: Mamba2Config | None = None
    gated_delta: GatedDeltaConfig | None = None
    short_conv: ShortConvConfig | None = None
    # latent attention (DeepSeek-V2) in place of GQA in every attention layer:
    # its sizes and YaRN's numbers; None = ``DecoderLayer``'s attention. Such a
    # flavor's cache is one latent row a token a layer (``cache_row_elems``)
    mla: MLAConfig | None = None
    # a learned indexer in every attention layer: each query attends to the
    # ``top_k`` positions its index heads score highest; None = every position.
    # Such a flavor's caches are a pair where others have K: (K, index keys)
    indexer: IndexerConfig | None = None
    # False = no position embedding at all (HF ``position_embedding_type:
    # nope``): the state-space layers carry the order
    use_rope: bool = True
    # False = rope on the "sliding_attention" layers only, none on the
    # "full_attention" ones (afmoe: the window layers carry the order)
    full_attention_rope: bool = True
    # rope parameters by layer type (HF ``rope_parameters.full_attention``): YaRN
    # on the "full_attention" layers' rope, the "sliding_attention" layers' plain
    # (both at ``rope_theta``); None = every layer's rope plain
    full_attention_yarn: YarnConfig | None = None
    # afmoe's attention: the heads' outputs times sigmoid of one more
    # projection of the layer's input (``g``, as wide as ``q``) before ``o``
    attention_gate: bool = False
    # afmoe's "sandwich": a second RMSNorm on each BRANCH, after ``o`` and after
    # the FFN, before the residual sum (``post_attn_norm``, ``post_mlp_norm``)
    sandwich_norm: bool = False
    # False = no RMSNorm BEFORE a branch (``ln1``, ``ln2``). With
    # ``sandwich_norm`` that is the Olmo 2 / 3 block: ``x + RMSNorm(f(x))``
    pre_norm: bool = True
    # ``qk_norm`` over the WHOLE projection, all heads at once (Olmo 2 / 3),
    # in place of one RMSNorm a head
    qk_norm_whole: bool = False
    # softmax scale of the attention layers; None = head_dim ** -0.5
    attention_multiplier: float | None = None
    # Granite's scalings: x0 = E[ids] * embedding_multiplier, every residual
    # branch * residual_multiplier, logits / logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    def __post_init__(self) -> None:
        if self.indexer is not None and (self.mla is not None or self.window_layers or self.ssm_layers):
            raise ValueError("an indexer beside latent attention, window layers or state-space layers: no program here")
        if self.layer_types is None:
            return
        kinds = {"attention", "mamba", "linear_attention", "conv", "sliding_attention", "full_attention"}
        if len(self.layer_types) != self.n_layers or set(self.layer_types) - kinds:
            raise ValueError(f"layer_types must name {self.n_layers} layers out of {sorted(kinds)}")
        if "mamba" in self.layer_types and self.mamba is None:
            raise ValueError("layer_types has a 'mamba' layer and mamba= gives no sizes")
        if "linear_attention" in self.layer_types and self.gated_delta is None:
            raise ValueError("layer_types has a 'linear_attention' layer and gated_delta= gives no sizes")
        if "conv" in self.layer_types and self.short_conv is None:
            raise ValueError(
                "layer_types has a 'conv' layer and short_conv= gives no sizes "
                "(ShortConvConfig: l_cache, the convolution's taps)"
            )
        recurrent = sorted(set(self.layer_types) & set(_RECURRENT_KINDS))
        if len(recurrent) > 1:
            raise ValueError(
                f"layer_types mixes {recurrent} layers: the recurrent store holds one kind of "
                "state (Mamba-2's, the delta rule's, or a short convolution's tails alone), "
                "and no program here carries two"
            )
        if self.window_layers and not self.sliding_window:
            raise ValueError("layer_types has a 'sliding_attention' layer and sliding_window= is not set")
        if self.window_layers and (self.mla is not None or self.ssm_layers):
            raise ValueError("window layers beside latent attention or state-space layers: no program here")
        if self.full_attention_yarn is not None and (
            not self.full_attention_rope or not self.use_rope or self.mrope_section is not None or self.mla is not None
        ):
            raise ValueError("full_attention_yarn scales the full layers' plain 1D rope: it needs one")

    @property
    def kv_layers(self) -> tuple[int, ...]:
        """Indices of the layers that hold K/V, in order: a slot cache's leading
        dimension counts these. They split into ``full_layers``, which the
        KV pool's leading dimension counts, and ``window_layers`` (none but
        in a flavor that mixes the two), which the window pool's does."""
        if self.layer_types is None:
            return tuple(range(self.n_layers))
        return tuple(i for i, kind in enumerate(self.layer_types) if kind not in _RECURRENT_KINDS)

    @property
    def window_layers(self) -> tuple[int, ...]:
        """Indices of the "sliding_attention" layers, in order."""
        if self.layer_types is None:
            return ()
        return tuple(i for i, kind in enumerate(self.layer_types) if kind == "sliding_attention")

    @property
    def full_layers(self) -> tuple[int, ...]:
        """Indices of the attention layers that see their whole context, in order."""
        return tuple(i for i in self.kv_layers if i not in self.window_layers)

    def rope_in_layer(self, i: int) -> bool:
        return self.use_rope and (self.full_attention_rope or i in self.window_layers)

    def yarn_in_layer(self, i: int) -> "YarnConfig | None":
        """YaRN's numbers for layer ``i``'s rope: the full layers' where the
        flavor gives any (a window layer never looks past its window)."""
        return None if i in self.window_layers else self.full_attention_yarn

    @property
    def cache_row_elems(self) -> int:
        """Elements a token holds in the pool in ONE layer: K and V of every KV
        head, or a latent flavor's one row (its zero padding counted: the pool
        stores it)."""
        if self.mla is not None:
            return self.mla.cache_width
        return 2 * self.n_kv_heads * self.head_dim

    @property
    def ssm_layers(self) -> tuple[int, ...]:
        """Indices of the recurrent layers, state-space ("mamba"), linear
        attention or short convolution (one kind a decoder:
        ``recurrent_kind``): the recurrent store's leading dimension counts
        these, in this order."""
        if self.layer_types is None:
            return ()
        return tuple(i for i, kind in enumerate(self.layer_types) if kind in _RECURRENT_KINDS)

    @property
    def recurrent_kind(self) -> str | None:
        """"mamba", "linear_attention" or "conv": what the recurrent store
        holds; None without recurrent layers."""
        return self.layer_types[self.ssm_layers[0]] if self.ssm_layers else None


VLM_BASE = VLMConfig()
# Qwen2-VL-2B-class shapes (reference serves Qwen2/2.5-VL via vLLM,
# cosmos_curate/models/vllm_qwen.py:122-260): both halves match
# Qwen2-VL-2B-Instruct tensor-for-tensor — the LM stack (GQA 12/2 heads,
# SwiGLU 8960, tied embeddings, rope 1e6, m-rope 16/24/24) via
# convert_qwen.convert_qwen2_lm, and the vision tower (32-deep 1280-wide
# windowless ViT with 3D-conv patchify, 2D rope, patch merger) via
# convert_qwen.convert_qwen2_vision — so a real checkpoint loads completely.
VLM_QWEN2_2B = VLMConfig(
    vocab=151936,
    dim=1536,
    n_layers=28,
    n_heads=12,
    n_kv_heads=2,
    head_dim=128,
    hidden_mult=8960 / 1536,
    max_seq=4096,
    rope_theta=1_000_000.0,
    qkv_bias=True,
    vision=VIT_B_16,
    vision_tokens=64,
    vision_variant="qwen2",
    qwen_vision=QWEN2_VL_2B_VISION,
    mrope_section=(16, 24, 24),
)
# Qwen2.5-VL-7B-Instruct — the family the reference actually serves for
# captions (vllm_qwen.py; CosmosReason shares this architecture): GQA
# 28/4 heads, SwiGLU 18944, untied head, m-rope 16/24/24, windowed vision.
VLM_QWEN25_7B = VLMConfig(
    vocab=152064,
    dim=3584,
    n_layers=28,
    n_heads=28,
    n_kv_heads=4,
    head_dim=128,
    hidden_mult=18944 / 3584,
    max_seq=4096,
    rope_theta=1_000_000.0,
    qkv_bias=True,
    vision=VIT_B_16,
    vision_tokens=64,
    vision_variant="qwen2",
    qwen_vision=QWEN25_VL_7B_VISION,
    mrope_section=(16, 24, 24),
    tied_embeddings=False,
)
VLM_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
)
# Qwen3-VL-30B-A3B-class sparse captioner LM (reference roster:
# models/vllm_qwen.py:313-349 serves the Qwen3-VL MoE family via vLLM
# expert parallelism). Nominal checkpoint shapes; at conversion time
# `convert_qwen.qwen3_moe_lm_config(hf_config)` derived from the actual
# checkpoint is authoritative. The Qwen3-VL DEEPSTACK vision tower is not
# implemented yet — this flavor serves the text/chat-LM paths (caption
# enhancement) and the EP-sharded serving plumbing; see PARITY.md.
VLM_QWEN3_MOE_A3B = VLMConfig(
    vocab=151936,
    dim=2048,
    n_layers=48,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    hidden_mult=6144 / 2048,
    max_seq=4096,
    rope_theta=1_000_000.0,
    qkv_bias=False,
    qk_norm=True,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    mrope_section=(24, 20, 20),
    mrope_interleaved=True,
    tied_embeddings=False,
    moe=MoEConfig(n_experts=128, top_k=8, hidden=768, capacity_factor=2.0),
)
# Full Qwen3-VL-MoE: the deepstack vision tower + sparse LM (reference's
# newest captioner roster, vllm_qwen.py:313-349). Nominal 30B-A3B shapes;
# conversion derives exact configs from the checkpoint
# (qwen3_moe_lm_config + qwen3_vision_config).
VLM_QWEN3_VL_MOE_A3B = VLMConfig(
    vocab=151936,
    dim=2048,
    n_layers=48,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    hidden_mult=6144 / 2048,
    max_seq=4096,
    rope_theta=1_000_000.0,
    qkv_bias=False,
    qk_norm=True,
    vision=VIT_TINY_TEST,
    vision_variant="qwen3",
    qwen_vision=QWEN3_VL_MOE_VISION,
    mrope_section=(24, 20, 20),
    mrope_interleaved=True,
    tied_embeddings=False,
    moe=MoEConfig(n_experts=128, top_k=8, hidden=768, capacity_factor=2.0),
)
VLM_QWEN3VL_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_variant="qwen3",
    qwen_vision=QWEN3_VISION_TINY_TEST,
    mrope_section=(2, 3, 3),
    mrope_interleaved=True,
    qk_norm=True,
    moe=MoEConfig(n_experts=4, top_k=2, hidden=32),
)
VLM_MOE_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    qk_norm=True,
    moe=MoEConfig(n_experts=4, top_k=2, hidden=32),
)
# Granite-4.0-H-Micro (HF ``granitemoehybrid``, config.json of
# ibm-granite/granite-4.0-h-micro): 40 layers in periods of ten, nine Mamba-2
# mixers and one GQA attention layer (at 5, 15, 25, 35) without any position
# embedding, every layer followed by the shared SwiGLU (``num_local_experts``
# 0: no routed experts), Granite's four multipliers, tied head. Text only:
# the vision slot holds the test-size tower no request may reach.
_GRANITE_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
VLM_GRANITE_4_H_MICRO = VLMConfig(
    vocab=100352,
    dim=2048,
    n_layers=40,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    hidden_mult=8192 / 2048,
    max_seq=4096,
    qkv_bias=False,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    layer_types=_GRANITE_PERIOD * 4,
    mamba=Mamba2Config(n_heads=64, head_dim=64, d_state=128, d_conv=4, chunk=256),
    use_rope=False,
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
)
# one period of the same pattern at test size (CPU tests, --rehearse); the
# scan's chunk is under the engine's test chunk so a prefill crosses chunks
VLM_GRANITE_HYBRID_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=10,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    layer_types=_GRANITE_PERIOD,
    mamba=Mamba2Config(n_heads=8, head_dim=16, d_state=16, d_conv=4, chunk=8),
    use_rope=False,
    attention_multiplier=1 / 16,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
)
# DeepSeek-V2 (HF ``deepseek_v2``, config.json of deepseek-ai/DeepSeek-V2) as ONE
# CHIP OF AN 8-WAY EXPERT-PARALLEL DEPLOYMENT sees it: every width as published
# (5120; 128 latent-attention heads, ranks 1536 / 512, head sizes 128 + 64 / 128,
# YaRN 40 over 4096; a dense SwiGLU of 12288 in layer 0, then 160 routed experts
# of 1536, top 6 of the top 3 of 8 groups, unnormalised times 16, and a shared
# SwiGLU of 3072), the router whole, and of the rest this chip's share: one
# routing group of 20 consecutive experts (group 0), a vocabulary slice of
# 12,800 rows, and the first 7 of the 60 layers (a pipeline stage). Attention and
# the shared expert are replicated in that deployment, so they are whole here.
# The layer runs without its exchange: its output is this chip's partial sum.
# Text only: the vision slot holds the test-size tower no request may reach.
_DEEPSEEK_V2_MLA = MLAConfig(
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, yarn_factor=40.0, yarn_original_max=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
)
VLM_DEEPSEEK_V2_EP8 = VLMConfig(
    vocab=12800,
    dim=5120,
    n_layers=7,
    n_heads=128,
    n_kv_heads=128,
    head_dim=128,
    hidden_mult=12288 / 5120,
    max_seq=4096,
    rope_theta=10000.0,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    tied_embeddings=False,
    mla=_DEEPSEEK_V2_MLA,
    moe=MoEConfig(
        n_experts=160, top_k=6, hidden=1536, shared_hidden=3072, first_dense=1, n_group=8,
        topk_group=3, norm_topk_prob=False, routed_scaling_factor=16.0, dispatch="sorted",
        held=(0, 20),
    ),
)
# the same mechanisms at test size: 4 groups of 4 experts (this chip: group 1),
# top 3 of the top 2 groups, a shared expert, one leading dense layer, latent
# attention at small ranks, YaRN on (original context 32, so the ramp is inside
# the eight rotary dims)
VLM_DEEPSEEK_V2_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=3,
    n_heads=4,
    n_kv_heads=4,
    head_dim=16,
    hidden_mult=2.0,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    tied_embeddings=False,
    mla=MLAConfig(
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        yarn_factor=4.0, yarn_original_max=32, yarn_beta_fast=4.0, yarn_beta_slow=1.0,
        yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
    ),
    moe=MoEConfig(
        n_experts=16, top_k=3, hidden=32, shared_hidden=48, first_dense=1, n_group=4,
        topk_group=2, norm_topk_prob=False, routed_scaling_factor=4.0, dispatch="sorted",
        held=(4, 4),
    ),
)
# Trinity-Large-Preview (HF ``afmoe``, config.json of arcee-ai/Trinity-Large-Preview)
# as ONE CHIP OF AN 8-WAY EXPERT-PARALLEL DEPLOYMENT sees it: every width as
# published (3072; 48 query / 8 KV heads x 128 with per-head q/k norms and an
# output gate; window 4096 on the "sliding_attention" layers, which alone carry
# rope; a dense SwiGLU of 12288 in the leading layer, then 256 routed experts of
# 3072, top 4 by sigmoid score plus a stored selection bias, renormalised times
# 2.448, and one shared SwiGLU of 3072; a second norm on each branch; the
# embedding times sqrt(3072)), the router whole, and of the rest this chip's
# share: 32 consecutive experts (0-31), a vocabulary slice of 25,024 rows, and 5
# of the 60 layers: the first five entries of the published ``layer_types`` (S S
# S F S), of them ONE leading dense layer (6 published: they count once) and four
# sparse ones, a whole period of three window layers to one full. Attention and
# the shared expert are replicated in that deployment, so they are whole here;
# the layer runs without its exchange. Text only. The first flavor whose
# requests pass 4,096 positions: its K/V lives in two pools (engine.py).
_TRINITY_LAYERS = ("sliding_attention",) * 3 + ("full_attention", "sliding_attention")
VLM_TRINITY_LARGE_EP8 = VLMConfig(
    vocab=25024,
    dim=3072,
    n_layers=5,
    n_heads=48,
    n_kv_heads=8,
    head_dim=128,
    hidden_mult=12288 / 3072,
    max_seq=12288,
    rope_theta=10000.0,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    tied_embeddings=False,
    qk_norm=True,
    layer_types=_TRINITY_LAYERS,
    sliding_window=4096,
    full_attention_rope=False,
    attention_gate=True,
    sandwich_norm=True,
    embedding_multiplier=math.sqrt(3072),
    moe=MoEConfig(
        n_experts=256, top_k=4, hidden=3072, shared_hidden=3072, first_dense=1,
        norm_topk_prob=True, routed_scaling_factor=2.448, dispatch="sorted", held=(0, 32),
        score_func="sigmoid", selection_bias=True,
    ),
)
# the same mechanisms at test size: a dense leading window layer, then window,
# full, window; a window of 10 (no multiple of the engine's test block of 4, so
# its edge falls inside a page); 8 experts of which 4 (2-5) are held
VLM_TRINITY_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    hidden_mult=2.0,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    tied_embeddings=False,
    qk_norm=True,
    layer_types=("sliding_attention", "sliding_attention", "full_attention", "sliding_attention"),
    sliding_window=10,
    full_attention_rope=False,
    attention_gate=True,
    sandwich_norm=True,
    embedding_multiplier=8.0,
    moe=MoEConfig(
        n_experts=8, top_k=2, hidden=32, shared_hidden=32, first_dense=1, norm_topk_prob=True,
        routed_scaling_factor=2.448, dispatch="sorted", held=(2, 4), score_func="sigmoid",
        selection_bias=True,
    ),
)
# Keye-VL-2.0-30B-A3B's language model (HF ``KeyeVL2``, config.json of
# Kwai-Keye/Keye-VL-2.0-30B-A3B) as ONE CHIP OF AN 8-WAY EXPERT-PARALLEL DEPLOYMENT
# sees it: every width as published (2048; 32 query / 4 KV heads x 128 with
# per-head q/k norms, rope 1e7 in m-rope sections 16/24/24; every layer sparse:
# 128 routed experts of 768, softmax over all of them, top 8 renormalised, no
# shared expert; and in every layer an INDEXER of 16 heads x 64 against one index
# key a position that picks the 2,048 positions a query attends to), the router
# whole, and of the rest this chip's share: 16 consecutive experts (0-15), a
# vocabulary slice of 18,992 rows, and 8 of the 48 layers (every layer is of one
# kind). Attention, indexer and router are replicated in that deployment, so
# they are whole here; the layer runs without its exchange. Text only (the
# tower is not modelled). The first flavor whose lanes reach 32,768 positions,
# and the first with a second array a position beside K/V (engine.py).
VLM_KEYE_VL2_A3B_EP8 = VLMConfig(
    vocab=18992,
    dim=2048,
    n_layers=8,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    hidden_mult=6144 / 2048,
    max_seq=32768,
    rope_theta=10_000_000.0,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    mrope_section=(16, 24, 24),
    tied_embeddings=False,
    qk_norm=True,
    indexer=IndexerConfig(n_heads=16, head_dim=64, top_k=2048),
    moe=MoEConfig(n_experts=128, top_k=8, hidden=768, norm_topk_prob=True, dispatch="sorted", held=(0, 16)),
)
# the same mechanisms at test size: a top-k of 32 that both lanes (64, 128) pass
# (a smaller one makes a toy of the comparison with float32: of 12 positions
# out of 77 the one that bfloat16 scores pick differently moves a logit by a
# third); 16 experts in eight shares of 2 (this chip: experts 2-3)
VLM_KEYE_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    hidden_mult=2.0,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    mrope_section=(2, 3, 3),
    tied_embeddings=False,
    qk_norm=True,
    indexer=IndexerConfig(n_heads=4, head_dim=8, top_k=32),
    moe=MoEConfig(n_experts=16, top_k=4, hidden=32, norm_topk_prob=True, dispatch="sorted", held=(2, 2)),
)
# Olmo-Hybrid-7B (HF ``olmo_hybrid``, config.json of allenai/Olmo-Hybrid-7B): 32
# layers in periods of four, three gated-delta-rule mixers (30 heads, a [96, 192]
# float32 state each, three short convolutions) and one full-attention layer (30
# query and 30 KV heads x 128, RMSNorm over the whole q and k, no position
# embedding), every layer followed by a SwiGLU of 11008; the Olmo 2 / 3 block
# (no norm before a branch, one on its output), untied head. Text only: the
# vision slot holds the test-size tower no request may reach. Its serving
# parameters are 14.8 GiB: no 16 GB chip holds them beside a pool, and a
# recurrent store is not split over a mesh, so on v5e it is served as a
# pipeline of two stages, of which ``VLM_OLMO_HYBRID_7B_PP2`` is the first:
# the first 16 layers (four whole periods), the table and the head (the second
# stage's head rides with the first's table).
_OLMO_PERIOD = ("linear_attention",) * 3 + ("full_attention",)
VLM_OLMO_HYBRID_7B = VLMConfig(
    vocab=100352,
    dim=3840,
    n_layers=32,
    n_heads=30,
    n_kv_heads=30,
    head_dim=128,
    hidden_mult=11008 / 3840,
    max_seq=4096,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-6,
    tied_embeddings=False,
    layer_types=_OLMO_PERIOD * 8,
    gated_delta=GatedDeltaConfig(n_heads=30, key_dim=96, value_dim=192, d_conv=4, allow_neg_eigval=True, chunk=64),
    use_rope=False,
    pre_norm=False,
    sandwich_norm=True,
    qk_norm_whole=True,
)
VLM_OLMO_HYBRID_7B_PP2 = replace(VLM_OLMO_HYBRID_7B, n_layers=16, layer_types=_OLMO_PERIOD * 4)
# two periods of the same pattern at test size (CPU tests, --rehearse): dk !=
# dv, and 4 heads x 24 lanes of state make no whole tile, so the store's
# layout is exercised; the scan's chunk is under the engine's test chunk so a
# prefill crosses chunks
VLM_OLMO_HYBRID_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=8,
    n_heads=4,
    n_kv_heads=4,
    head_dim=16,
    hidden_mult=2.0,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-6,
    tied_embeddings=False,
    layer_types=_OLMO_PERIOD * 2,
    gated_delta=GatedDeltaConfig(n_heads=4, key_dim=16, value_dim=24, d_conv=4, allow_neg_eigval=True, chunk=8),
    use_rope=False,
    pre_norm=False,
    sandwich_norm=True,
    qk_norm_whole=True,
)
# Solar-Open2-250B (HF ``solar_open2``, config.json of upstage/Solar-Open2-250B)
# as ONE CHIP OF AN 8-WAY EXPERT-PARALLEL STAGE sees it: 48 layers x 4096 in
# periods of four (one gated GQA layer, 64 query / 8 KV heads x 128, no position
# embedding, ``o * sigmoid(W_g x)`` before ``W_o``; then three Kimi-Delta-Attention
# layers: 64 heads, a [128, 128] float32 state each whose decay is a vector over
# the 128 key channels, three short convolutions, low-rank decay and gate
# projections of rank 128), EVERY layer followed by 320 routed experts of 1280
# (sigmoid scores + a stored selection bias, top 8 renormalised) and one shared
# expert; untied head. Every width as published, the router whole, and of the
# rest this chip's share: 40 consecutive experts (0-39), a vocabulary slice of
# 24,576 rows, and ONE period of the twelve (the first of twelve four-layer
# pipeline stages: 96 v5e chips hold the model). Mixers, attention, router and
# shared expert are replicated over the eight in that deployment, so they are
# whole here; the layer runs without its exchange. Text only. The first flavor
# with a recurrent store BESIDE sorted experts (engine.py: the recurrent
# programs carry the held-assignment rider).
_SOLAR_PERIOD = ("full_attention",) + ("linear_attention",) * 3
VLM_SOLAR_OPEN2_EP8 = VLMConfig(
    vocab=24576,
    dim=4096,
    n_layers=4,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    hidden_mult=10240 / 4096,  # published; serves no layer (``first_k_dense_replace`` 0)
    max_seq=4096,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    tied_embeddings=False,
    layer_types=_SOLAR_PERIOD,
    gated_delta=GatedDeltaConfig(
        n_heads=64, key_dim=128, value_dim=128, d_conv=4, allow_neg_eigval=True, chunk=64,
        decay_rank=128, gate_rank=128,
    ),
    use_rope=False,
    attention_gate=True,
    moe=MoEConfig(
        n_experts=320, top_k=8, hidden=1280, shared_hidden=1280, norm_topk_prob=True,
        routed_scaling_factor=1.0, dispatch="sorted", held=(0, 40), score_func="sigmoid",
        selection_bias=True, router_precision="highest",
    ),
)
# the same mechanisms at test size: one period; dk != dv; a scan chunk of two
# sub-blocks of 16, so a prefill chunk of 40 crosses scan chunks AND sub-blocks;
# 16 experts in eight shares of 2 (this chip: experts 2-3)
VLM_SOLAR_OPEN2_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    hidden_mult=2.0,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    tied_embeddings=False,
    layer_types=_SOLAR_PERIOD,
    gated_delta=GatedDeltaConfig(
        n_heads=4, key_dim=16, value_dim=32, d_conv=4, allow_neg_eigval=True, chunk=32,
        decay_rank=8, gate_rank=8,
    ),
    use_rope=False,
    attention_gate=True,
    moe=MoEConfig(
        n_experts=16, top_k=4, hidden=32, shared_hidden=32, norm_topk_prob=True,
        dispatch="sorted", held=(2, 2), score_func="sigmoid", selection_bias=True, router_precision="highest",
    ),
)
# LFM2-24B-A2B (HF ``lfm2_moe``, config.json of LiquidAI/LFM2-24B-A2B) as the
# FIRST OF FIVE PIPELINE STAGES holds it: 40 layers x 2048, thirty gated
# short-convolution layers (3 taps, no bias) and ten GQA layers (32 query / 8 KV
# heads x 64, per-head q / k RMSNorm, rope 1e6) in the order c c A c | c c A c ...,
# two leading dense layers (SwiGLU 11776), then 64 routed experts of 1536 a layer
# (sigmoid scores, a stored selection bias, top 4 renormalised with 1e-6 in the
# sum, no shared expert), tied head, vocabulary 65,536. Every width as published
# and EVERY EXPERT HELD (``held = None``: one chip holds a layer's 64 tables, 1.2
# GB); the cut is in depth alone: the first ten layers, c c | A c c c | A c c c
# (the two dense layers and two whole periods), the table and the tied head kept
# with the first stage. Text only. The first flavor whose recurrent store is
# TAILS ALONE (a convolution's last two inputs a channel, no state matrix).
_LFM2_LAYERS = ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 2
VLM_LFM2_24B_A2B_PP5 = VLMConfig(
    vocab=65536,
    dim=2048,
    n_layers=10,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    hidden_mult=11776 / 2048,
    max_seq=4096,
    rope_theta=1_000_000.0,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    qk_norm=True,
    layer_types=_LFM2_LAYERS,
    short_conv=ShortConvConfig(l_cache=3),
    moe=MoEConfig(
        n_experts=64, top_k=4, hidden=1536, first_dense=2, norm_topk_prob=True,
        routed_scaling_factor=1.0, norm_topk_eps=1e-6, dispatch="sorted", score_func="sigmoid",
        selection_bias=True, router_precision="highest", hand_out_choice=True,
    ),
)
# the same mechanisms at test size: one dense conv layer, then A c c c over 8
# experts, top 2
VLM_LFM2_MOE_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=5,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    hidden_mult=2.0,
    max_seq=128,
    rope_theta=1_000_000.0,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    rms_eps=1e-5,
    qk_norm=True,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    short_conv=ShortConvConfig(l_cache=3),
    moe=MoEConfig(
        n_experts=8, top_k=2, hidden=32, first_dense=1, norm_topk_prob=True, norm_topk_eps=1e-6,
        dispatch="sorted", score_func="sigmoid", selection_bias=True, router_precision="highest",
        hand_out_choice=True,
    ),
)
# Mellum2-12B-A2.5B-Instruct (HF ``mellum``, config.json of
# JetBrains/Mellum2-12B-A2.5B-Instruct) as THE FIRST OF FOUR PIPELINE STAGES sees
# it: every width as published (2304; 32 query / 4 KV heads x 128 with per-head
# q / k RMSNorm; window 1,024 on the "sliding_attention" layers, three to each
# "full_attention" one; rope theta 500,000 in both kinds, the full layers' under
# YaRN 16 over 8,192; EVERY layer sparse: 64 routed experts of 896, softmax over
# all of them, top 8 renormalised, no shared expert and no leading dense layer:
# ``intermediate_size`` 7168 is read by no layer), EVERY EXPERT HELD (``held =
# None``: a layer's 64 tables are 0.79 GB), the vocabulary whole and the head
# untied. The cut is in depth alone: the first eight of 28 layers, S S S F | S S
# S F. Text only. The first flavor with every expert held whose programs are the
# PAGED ones over two pools, and the first whose rope differs by layer type.
_MELLUM2_LAYERS = (("sliding_attention",) * 3 + ("full_attention",)) * 2
VLM_MELLUM2_12B_PP4 = VLMConfig(
    vocab=98304,
    dim=2304,
    n_layers=8,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    hidden_mult=7168 / 2304,
    max_seq=32768,
    rope_theta=500_000.0,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    tied_embeddings=False,
    qk_norm=True,
    layer_types=_MELLUM2_LAYERS,
    sliding_window=1024,
    full_attention_yarn=YarnConfig(
        factor=16.0, original_max=8192, beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782
    ),
    moe=MoEConfig(
        n_experts=64, top_k=8, hidden=896, norm_topk_prob=True, dispatch="sorted", held=None,
        hand_out_choice=True, router_precision="highest",
    ),
)
# the same mechanisms at test size: window, window, full, window; a window of 10
# (no multiple of the engine's test block of 4, so its edge falls inside a page);
# YaRN 4 over an original context of 32 (the ramp lies inside the eight rotary
# dims, and positions past 32 are where the interpolated dims differ from plain
# rope); 8 experts, top 2, all held
VLM_MELLUM2_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    hidden_mult=2.0,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_tokens=8,
    tied_embeddings=False,
    qk_norm=True,
    layer_types=("sliding_attention", "sliding_attention", "full_attention", "sliding_attention"),
    sliding_window=10,
    full_attention_yarn=YarnConfig(factor=4.0, original_max=32, beta_fast=4.0, beta_slow=1.0),
    moe=MoEConfig(
        n_experts=8, top_k=2, hidden=32, norm_topk_prob=True, dispatch="sorted", held=None,
        hand_out_choice=True, router_precision="highest",
    ),
)
# Named caption-model flavors selectable from pipeline args (CLI
# --caption-model); each pairs an architecture with its weight-registry id
# plus the serving knobs that must travel with the checkpoint choice.
@dataclass(frozen=True)
class FlavorSpec:
    cfg: "VLMConfig"
    model_id: str
    # Converted-HF-checkpoint flavors index embeddings by the checkpoint's
    # EXACT token ids and were trained on its chat template: serving them
    # requires HFVocabTokenizer (staged vocab.json/merges.txt) + the
    # Qwen chat layout (vlm/chat.py). Repo-native flavors use the local
    # byte/BPE tokenizer and raw prompts.
    hf_chat: bool = False
    # Flavors naming a real checkpoint must refuse to run random-init
    # (a user asking for qwen25vl-7b must not silently get gibberish).
    require_weights: bool = True
    # hf_chat special-token table override (None = Qwen2 defaults); tuple
    # of (token, id) pairs so the spec stays hashable.
    specials: tuple[tuple[str, int], ...] | None = None
    # The flavor serves TEXT ONLY (no trained vision tower): frame-bearing
    # requests must be refused loudly, never encoded through a placeholder
    # tower into silent gibberish.
    text_only: bool = False
    # Default KV lane layout ((length, n_slots), ...) for the caption
    # engine — memory-bounding by actual request lengths (None = one
    # worst-case-length pool). Chosen per checkpoint size so the
    # production caption stage runs laned by default.
    kv_lanes: tuple[tuple[int, int], ...] | None = None
    # Chips of one host the flavor's parameters and KV pool are split over
    # (the extent of the ``model`` mesh axis the caption stage builds at
    # setup); 1 = the whole model on one chip, no mesh. Travels with the
    # checkpoint choice as the lanes do: a 7B's 16.5 GiB of serving
    # parameters (bfloat16, its head float32) fit no 16 GB chip.
    model_chips: int = 1
    # Prompts one prefill program takes at most (``CaptionEngine``'s
    # ``max_prefill_rows``; None = as many as a lane has waiting). Travels
    # with the lanes: a lane of 256 slots can have dozens of prompts waiting
    # after a stall, and a program's scratch grows with rows x chunk tokens
    # (DeepSeek-V2: 64 rows x 256 tokens wanted 10.5 GB, PERF.md PR 33).
    prefill_rows: int | None = None

    def __post_init__(self) -> None:
        if self.model_chips < 1 or self.cfg.n_kv_heads % self.model_chips:
            raise ValueError(
                f"{self.model_id}: model_chips={self.model_chips} does not divide "
                f"n_kv_heads={self.cfg.n_kv_heads} (the KV pool is split by head planes)"
            )
        if self.model_chips > 1 and self.cfg.mla is not None:
            raise ValueError(
                f"{self.model_id}: a latent pool has one head plane and is not split "
                "over a model mesh; serve a latent-attention flavor with model_chips=1"
            )
        if self.model_chips > 1 and self.cfg.ssm_layers:
            raise ValueError(
                f"{self.model_id}: the recurrent store (Mamba-2 states, delta-rule states "
                "or short-convolution tails) and its mixers are not split over a model mesh; "
                "serve a hybrid flavor with model_chips=1"
            )
        if self.model_chips > 1 and self.cfg.indexer is not None:
            raise ValueError(
                f"{self.model_id}: the index-key array has one head plane and is not split "
                "over a model mesh; serve an indexer flavor with model_chips=1"
            )


VLM_FLAVORS: dict[str, FlavorSpec] = {}


def vlm_flavor(name: str) -> FlavorSpec:
    """The full serving spec for a named caption flavor."""
    try:
        return VLM_FLAVORS[name]
    except KeyError:
        raise ValueError(
            f"unknown caption model {name!r}; choose from {sorted(VLM_FLAVORS)}"
        ) from None


VLM_QWEN2VL_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    max_seq=128,
    vision=VIT_TINY_TEST,
    vision_variant="qwen2",
    qwen_vision=QWEN_VISION_TINY_TEST,
    mrope_section=(2, 3, 3),
)
# Qwen2.5-VL-7B's serving shape at test size: windowed tower, qkv bias,
# untied head, and 4 KV heads so that a model=4 mesh gets a head plane a chip
VLM_QWEN25VL_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=2,
    n_heads=8,
    n_kv_heads=4,
    head_dim=16,
    max_seq=128,
    qkv_bias=True,
    vision=VIT_TINY_TEST,
    vision_variant="qwen2",
    qwen_vision=QWEN25_VISION_TINY_TEST,
    mrope_section=(2, 3, 3),
    tied_embeddings=False,
)
# chat-template prompts in byte-level test tokens run ~170 ids — the
# hf_chat test flavor needs the extra context
VLM_QWEN_CHAT_TINY_TEST = VLMConfig(
    vocab=512,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    max_seq=256,
    vision=VIT_TINY_TEST,
    vision_variant="qwen2",
    qwen_vision=QWEN_VISION_TINY_TEST,
    mrope_section=(2, 3, 3),
)

# Special-token ids small enough for the tiny test config's 512-row
# embedding table; layout mirrors QWEN2_SPECIAL_TOKENS.
_TINY_CHAT_SPECIALS = (
    ("<|endoftext|>", 500),
    ("<|im_start|>", 501),
    ("<|im_end|>", 502),
    ("<|vision_start|>", 503),
    ("<|vision_end|>", 504),
    ("<|vision_pad|>", 505),
    ("<|image_pad|>", 506),
    ("<|video_pad|>", 507),
)

VLM_FLAVORS.update(
    {
        "base": FlavorSpec(VLM_BASE, "caption-vlm-tpu", require_weights=False),
        "qwen2vl-2b": FlavorSpec(
            VLM_QWEN2_2B,
            "caption-qwen2vl-2b-tpu",
            hf_chat=True,
            # 2B-class KV is cheap (2 kv-heads): plenty of short-lane slots
            # for caption windows, a few full-context rows for long prompts
            kv_lanes=((1024, 8), (4096, 4)),
        ),
        "qwen25vl-7b": FlavorSpec(
            VLM_QWEN25_7B,
            "caption-qwen25vl-7b-tpu",
            hf_chat=True,
            # 7B KV rows are 4x the 2B's — halve the lane budget
            kv_lanes=((1024, 4), (4096, 2)),
            # 4.1 GiB of parameters a chip: one KV head and 7 query heads each
            model_chips=4,
        ),
        "tiny-test": FlavorSpec(VLM_TINY_TEST, "caption-vlm-tpu", require_weights=False),
        # the 7B's deployment at test size: served over a model=4 mesh
        # (four virtual CPU devices do for the chips)
        "qwen25vl-tiny-test": FlavorSpec(
            VLM_QWEN25VL_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            kv_lanes=((64, 4), (128, 2)),
            model_chips=4,
        ),
        # MoE chat-LM slot for LM-ONLY converted checkpoints (enhancement
        # and other text paths); the full-VL flavor below serves frames
        "qwen3moe-a3b-lm": FlavorSpec(
            VLM_QWEN3_MOE_A3B,
            "caption-qwen3moe-a3b-tpu",
            hf_chat=True,
            text_only=True,  # this slot's checkpoints carry no vision params
            kv_lanes=((1024, 4), (4096, 2)),
        ),
        # full Qwen3-VL-MoE: deepstack vision + EP-sharded sparse LM
        "qwen3vl-moe-a3b": FlavorSpec(
            VLM_QWEN3_VL_MOE_A3B,
            "caption-qwen3vl-moe-a3b-tpu",
            hf_chat=True,
            kv_lanes=((1024, 4), (4096, 2)),
        ),
        "qwen3moe-tiny-test": FlavorSpec(
            VLM_MOE_TINY_TEST, "caption-vlm-tpu", require_weights=False
        ),
        # hybrid text LM for the LM-only passes (--enhance-captions): 36 of 40
        # layers keep a fixed-size Mamba-2 state, so a row costs 72 MiB of
        # recurrent store whatever its context and KV only 8 KiB a token (4
        # attention layers): many rows a step on one chip. The store, not
        # the pool, is what the rows cost.
        "granite-4.0-h-micro": FlavorSpec(
            VLM_GRANITE_4_H_MICRO,
            "caption-granite-4.0-h-micro-tpu",
            text_only=True,
            kv_lanes=((1024, 48), (4096, 8)),
        ),
        "granite-hybrid-tiny-test": FlavorSpec(
            VLM_GRANITE_HYBRID_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 4), (128, 2)),
        ),
        # a large sparse text LM served expert-parallel, seen from one of its
        # eight chips (the LM-only passes, --enhance-captions): a latent row
        # costs 8,960 B a position over the 7 layers, so 256 short-lane rows
        # are 2.2 GiB, and 256 rows give each of the 20 held experts the ten
        # assignments a step that 32 rows a chip give it in the deployment
        "deepseek-v2-ep8": FlavorSpec(
            VLM_DEEPSEEK_V2_EP8,
            "caption-deepseek-v2-ep8-tpu",
            text_only=True,
            kv_lanes=((1024, 256), (4096, 8)),
            prefill_rows=8,  # 2,048 tokens a prefill program: 0.75 GB of scratch
        ),
        "deepseek-v2-tiny-test": FlavorSpec(
            VLM_DEEPSEEK_V2_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 4), (128, 2)),
        ),
        # a large sparse text LM with window and full attention layers mixed,
        # served expert-parallel, seen from one of its eight chips (the LM-only
        # passes over LONG text: a per-video digest of every window's caption).
        # A position costs 4 KiB a layer; a window layer's row is bounded to
        # window + chunk positions whatever the lane (17.5 MiB a layer), so 24
        # rows of 12,288 cost 2.6 GiB of window pool + 1.4 of full pool where
        # one pool over five layers would want 6.9. 40 decoding rows give the
        # 32 held experts the 20 assignments a step that 5 rows a chip give them
        "trinity-large-ep8": FlavorSpec(
            VLM_TRINITY_LARGE_EP8,
            "caption-trinity-large-ep8-tpu",
            text_only=True,
            kv_lanes=((4096, 16), (12288, 24)),
            prefill_rows=4,  # 1,024 tokens a prefill program
        ),
        "trinity-tiny-test": FlavorSpec(
            VLM_TRINITY_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 2), (128, 2)),
        ),
        # a sparse text LM with a learned indexer in every layer, served
        # expert-parallel, seen from one of its eight chips (the LM-only passes
        # over VERY long text: every window's caption of a multi-hour video in
        # one prompt). A position costs 2,048 B of K/V and 256 B of index key a
        # layer (64 values in a whole 128-lane row); 4 x 8,192 + 12 x 32,768
        # positions are 7.0 + 0.9 GiB over 8 layers. 16 decoding rows give the
        # 16 held experts the 16 assignments a step that 2 rows a chip give them
        "keye-vl2-a3b-ep8": FlavorSpec(
            VLM_KEYE_VL2_A3B_EP8,
            "caption-keye-vl2-a3b-ep8-tpu",
            text_only=True,
            kv_lanes=((8192, 4), (32768, 12)),
            # 1,024 tokens a prefill program: a row's chunk keeps [256, 32768]
            # float32 index scores and their mask (60 MB a row a layer), and a
            # step's two decode programs cost what 2.5 rows of prefill cost
            # whatever decodes, so the prompts (63 chunks a mean request) want
            # several rows a program (PERF.md, PR 40)
            prefill_rows=4,
        ),
        "keye-tiny-test": FlavorSpec(
            VLM_KEYE_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 2), (128, 2)),
        ),
        # a 7B-class hybrid text LM (the LM-only passes, --enhance-captions): 24
        # of 32 layers keep a fixed-size gated-delta-rule state, so a row costs
        # 53 MB of recurrent store whatever its context and K/V 120 KiB a
        # position over the 8 attention layers of 30 KV heads. Whole, it needs a
        # device of 24 GB or more; on v5e it is served as the pipeline below
        "olmo-hybrid-7b": FlavorSpec(
            VLM_OLMO_HYBRID_7B,
            "caption-olmo-hybrid-7b-tpu",
            text_only=True,
            kv_lanes=((1024, 40), (4096, 4)),
        ),
        # ...seen from the first of its two pipeline stages: 16 layers, table
        # and head, 8.4 GiB of parameters. A row costs 26.5 MB of state + 0.8 MB
        # of tails + 60 KiB of K/V a position: 40 + 4 rows are 1.2 GiB of store
        # and 3.3 GiB of pool
        "olmo-hybrid-7b-pp2": FlavorSpec(
            VLM_OLMO_HYBRID_7B_PP2,
            "caption-olmo-hybrid-7b-pp2-tpu",
            text_only=True,
            kv_lanes=((1024, 40), (4096, 4)),
        ),
        "olmo-hybrid-tiny-test": FlavorSpec(
            VLM_OLMO_HYBRID_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 4), (128, 2)),
        ),
        # a 250B-class sparse hybrid text LM served expert-parallel, seen from one
        # of the eight chips of its first four-layer stage (the LM-only passes,
        # --enhance-captions): a row costs 12 MiB of state + 0.43 MiB of tails
        # whatever its context and 4 KiB of K/V a position in the ONE attention
        # layer; 264 rows are 3.2 GiB of store and 1.1 GiB of pool beside 6.4
        # GiB of parameters. 256 decoding rows give the 40 held experts the 256
        # assignments a step that 32 rows a chip give them in the deployment
        "solar-open2-ep8": FlavorSpec(
            VLM_SOLAR_OPEN2_EP8,
            "caption-solar-open2-ep8-tpu",
            text_only=True,
            kv_lanes=((1024, 256), (4096, 8)),
            prefill_rows=8,  # 2,048 tokens a prefill program
        ),
        "solar-open2-tiny-test": FlavorSpec(
            VLM_SOLAR_OPEN2_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 4), (128, 2)),
        ),
        # a 24B sparse hybrid text LM seen from the first of its five pipeline
        # stages (the LM-only passes, --enhance-captions): ten layers with every
        # one of a layer's 64 experts on the chip, 9.8 GiB of parameters. A row
        # costs 64 KiB of convolution tails whatever its context and 4 KiB of
        # K/V a position in the two attention layers: 264 rows are 17 MB of
        # store and 1.1 GiB of pool. 256 decoding rows give each expert 16
        # assignments a step, a deployment's own
        "lfm2-24b-a2b-pp5": FlavorSpec(
            VLM_LFM2_24B_A2B_PP5,
            "caption-lfm2-24b-a2b-pp5-tpu",
            text_only=True,
            kv_lanes=((1024, 256), (4096, 8)),
            prefill_rows=8,  # 2,048 tokens a prefill program
        ),
        "lfm2-moe-tiny-test": FlavorSpec(
            VLM_LFM2_MOE_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 4), (128, 2)),
        ),
        # a 12B sparse text LM with window and full attention layers mixed, seen
        # from the first of its four pipeline stages (the LM-only passes over VERY
        # long text behind a long shared instruction): eight layers with every one
        # of a layer's 64 experts on the chip, 8.0 GB of parameters with the table
        # and the float32 head. A position costs 2 KiB of K/V a layer; a window
        # layer's row is a ring of 11 blocks of 128 whatever the lane, so 28 rows
        # cost 0.45 GiB of window pool and 4 x 8,192 + 24 x 32,768 positions 3.1
        # GiB of full pool over the two full layers. 28 decoding rows give each
        # expert 3.5 assignments a step
        "mellum2-12b-a2.5b-pp4": FlavorSpec(
            VLM_MELLUM2_12B_PP4,
            "caption-mellum2-12b-a2.5b-pp4-tpu",
            text_only=True,
            kv_lanes=((8192, 4), (32768, 24)),
            prefill_rows=4,  # 1,024 tokens a prefill program
        ),
        "mellum2-tiny-test": FlavorSpec(
            VLM_MELLUM2_TINY_TEST,
            "caption-vlm-tpu",
            require_weights=False,
            text_only=True,
            kv_lanes=((64, 2), (128, 2)),
        ),
        # hf_chat plumbing under test shapes: exercises HFVocabTokenizer +
        # chat-template request building without a real checkpoint
        "qwen-chat-tiny-test": FlavorSpec(
            VLM_QWEN_CHAT_TINY_TEST,
            "caption-vlm-tpu",
            hf_chat=True,
            require_weights=False,
            specials=_TINY_CHAT_SPECIALS,
            kv_lanes=((192, 4), (256, 2)),
        ),
    }
)


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def mrope_component_map(
    mrope_section: tuple[int, int, int], interleaved: bool
) -> np.ndarray:
    """Which (t=0, h=1, w=2) position component drives each of the D/2
    rotary frequency dims.

    Chunked (Qwen2-VL): [T]*s0 + [H]*s1 + [W]*s2. Interleaved (Qwen3-VL,
    HF ``apply_interleaved_mrope``): start all-T, then dims 1,4,7,..
    (< 3*s1) become H and dims 2,5,8,.. (< 3*s2) become W."""
    if not interleaved:
        return np.repeat(np.arange(3), np.asarray(mrope_section))
    d2 = int(sum(mrope_section))
    comp = np.zeros(d2, np.int64)
    comp[1 : 3 * mrope_section[1] : 3] = 1
    comp[2 : 3 * mrope_section[2] : 3] = 2
    return comp


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float,
    mrope_section: tuple[int, int, int] | None = None,
    mrope_interleaved: bool = False,
    freqs=None,
    gain: float = 1.0,
) -> jnp.ndarray:
    """x: [B, T, H, D]; positions: [B, T] absolute positions, or [B, T, 3]
    (t, h, w) multimodal positions under m-rope. ``freqs`` ([D/2]) takes the
    place of the plain ``theta`` frequencies (YaRN: ``yarn_inv_freq``) and
    ``gain`` multiplies cos and sin (YaRN's ``attention_factor``).

    M-rope (HF apply_multimodal_rotary_pos_emb semantics): each of the D/2
    rotary frequency dims takes its angle from one position component,
    assigned by ``mrope_component_map`` (chunked sections for Qwen2-VL,
    interleaved for Qwen3-VL). With all three components equal (any
    pure-text span) both layouts reduce exactly to standard 1D rope.
    """
    if freqs is None:
        freqs = rope_frequencies(x.shape[-1], theta)  # [D/2]
    if positions.ndim == 3:
        if mrope_section is None:
            raise ValueError("3-component positions require mrope_section")
        comp = mrope_component_map(mrope_section, mrope_interleaved)
        pos_sel = positions[..., comp].astype(jnp.float32)  # [B, T, D/2]
        angles = pos_sel * freqs
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if gain != 1.0:
        cos, sin = cos * gain, sin * gain
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def build_mrope_positions(
    n_text_before: int,
    grid_merged: tuple[int, int, int] | None,
    n_text_after: int,
    t_scale: float = 1.0,
) -> tuple[np.ndarray, int]:
    """(t, h, w) position ids for a [text][vision][text] prompt layout.

    HF ``Qwen2VLModel.get_rope_index`` semantics: text tokens carry equal
    components; a vision block starting at offset ``st`` gets
    ``st + (t_idx, h_idx, w_idx)`` over the MERGED token grid in t-major
    row-major order (exactly the merger's output order); text resumes at
    ``st + max(vision indices) + 1``. Returns ([T, 3] int32, next_position).

    ``t_scale`` is Qwen2.5-VL's absolute-time temporal component
    (HF ``Qwen2_5_VLModel.get_rope_index``):
    ``t_index = floor(grid_t_idx * second_per_grid_t * tokens_per_second)``
    with ``t_scale = second_per_grid_t * tokens_per_second``. The default
    1.0 reproduces Qwen2-VL's unscaled ``arange`` exactly.
    """
    parts = []
    if n_text_before:
        t = np.arange(n_text_before, dtype=np.int32)
        parts.append(np.stack([t, t, t], axis=-1))
    offset = n_text_before
    if grid_merged is not None:
        gt, gh, gw = grid_merged
        t_idx = np.floor(
            np.repeat(np.arange(gt, dtype=np.float64), gh * gw) * t_scale
        ).astype(np.int32)
        h_idx = np.tile(np.repeat(np.arange(gh, dtype=np.int32), gw), gt)
        w_idx = np.tile(np.tile(np.arange(gw, dtype=np.int32), gh), gt)
        parts.append(offset + np.stack([t_idx, h_idx, w_idx], axis=-1))
        offset += max(int(t_idx[-1]) + 1 if gt else 0, gh, gw)
    if n_text_after:
        t = offset + np.arange(n_text_after, dtype=np.int32)
        parts.append(np.stack([t, t, t], axis=-1))
        offset += n_text_after
    if not parts:
        return np.zeros((0, 3), np.int32), offset
    return np.concatenate(parts, axis=0).astype(np.int32), offset


class RMSNorm(nn.Module):
    eps: float = 1e-6
    scale_init: float = 1.0  # what a SEEDED scale starts at (a checkpoint brings its own)

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.constant(self.scale_init), (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


def route(moe: MoEConfig, logits, bias=None):
    """The router's choice for every token. logits: ``[N, E]`` float32. Softmax
    over ALL experts, the groups that lose zeroed (group-limited routing: a
    group's score is its best expert's), top-k of what is left, renormalised
    or not, times the scaling factor. A tie goes to the lower index, in the
    groups as in the experts. With ``score_func="sigmoid"`` (afmoe) an expert's
    score is its own sigmoid, the top-k is taken of score + ``bias`` (``[E]``
    float32, stored; None = no bias) and the WEIGHTS are the unbiased scores of
    the chosen, renormalised with the model's own constant in the sum
    (``MoEConfig.norm_topk_eps``). Returns
    (weights ``[N, k]``, experts ``[N, k]``)."""
    if moe.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_i = jax.lax.top_k(scores if bias is None else scores + bias, moe.top_k)
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
        if moe.norm_topk_prob:
            top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + moe.norm_topk_eps)
        return top_w * moe.routed_scaling_factor, top_i
    probs = jax.nn.softmax(logits, axis=-1)
    if moe.n_group > 1:
        n, e = probs.shape
        best = probs.reshape(n, moe.n_group, e // moe.n_group).max(axis=-1)
        _, groups = jax.lax.top_k(best, moe.topk_group)  # [N, topk_group]
        kept = jnp.zeros_like(best, dtype=bool).at[jnp.arange(n)[:, None], groups].set(True)
        probs = jnp.where(jnp.repeat(kept, e // moe.n_group, axis=1), probs, 0.0)
    top_w, top_i = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    if moe.routed_scaling_factor != 1.0:
        top_w = top_w * moe.routed_scaling_factor
    return top_w, top_i


class MoEFFN(nn.Module):
    """Expert-parallel sparse FFN, GShard-style static dispatch.

    TPU-first formulation: routing becomes one-hot einsum dispatch into a
    fixed per-expert queue of ``capacity`` slots, the expert SwiGLU runs
    as ONE batched [E, C, D] x [E, D, 2H] einsum (expert axis sharded over
    the ``model`` mesh axis = expert parallelism under pjit — each device
    holds E/ep experts and XLA all-to-alls the queues), and the combine is
    the transpose einsum weighted by the router. No dynamic shapes, no
    per-expert Python loops; compiled once per (tokens, capacity) bucket.

    Numerics match HF Qwen3MoE (softmax-then-topk in fp32, renormalized;
    fused gate_up chunked into gate|up; silu(gate)*up) exactly when no
    token overflows its expert queue (``capacity_factor=None`` guarantees
    this; a finite factor trades exactness at overflow for memory, the
    standard GShard drop semantics)."""

    cfg: VLMConfig
    dtype: jnp.dtype = jnp.bfloat16
    # storage type of the expert tables (consumed in ``dtype``); the router
    # computes in float32 and stores float32 (VLM.param_dtype has the rule)
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        moe = self.cfg.moe
        b, t, d = x.shape
        n = b * t
        e, k, h = moe.n_experts, moe.top_k, moe.hidden
        tokens = x.reshape(n, d)
        logits = dense(e, None, name="router", use_bias=False, dtype=jnp.float32, precision=moe.router_precision)(
            tokens.astype(jnp.float32)
        )
        bias = (
            self.param("router_bias", nn.initializers.zeros, (e,), jnp.float32)
            if moe.selection_bias else None
        )
        with jax.named_scope("moe.route"):
            top_w, top_i = route(moe, logits, bias)  # [N, k]
        # for the program that hands the choice out (``MoEConfig.hand_out_choice``)
        if self.is_mutable_collection("expert_choice") and not self.is_initializing():
            self.sow("expert_choice", "top_i", top_i.reshape(b, t, k))
        if moe.dispatch == "sorted":
            y = self._sorted_experts(tokens, top_w, top_i)
            return y.reshape(b, t, d).astype(x.dtype)
        if moe.capacity_factor is None:
            cap = n
        else:
            cap = max(1, min(n, int(np.ceil(k * n / e * moe.capacity_factor))))
        # assignment axis A = N*k, token-major; queue position = number of
        # earlier assignments to the same expert. Dispatch/combine are
        # scatter/gather over queue-slot ids — O(A·D) data movement —
        # instead of one-hot einsums whose [A, E, cap] contraction costs
        # as much FLOPs as the expert matmuls themselves.
        a_ids = top_i.reshape(-1)  # [A] expert id per assignment
        e_onehot32 = jax.nn.one_hot(a_ids, e, dtype=jnp.float32)  # [A, E]
        prior = jnp.cumsum(e_onehot32, axis=0) - e_onehot32
        pos = jnp.sum(prior * e_onehot32, axis=-1).astype(jnp.int32)  # [A]
        a = a_ids.shape[0]
        # destination queue slot per assignment; overflow (pos >= cap)
        # lands out of range and is DROPPED by the scatter
        dest = jnp.where(pos < cap, a_ids * cap + pos, e * cap)
        gather = jnp.full((e * cap,), a, jnp.int32)  # sentinel -> zero fill
        gather = gather.at[dest].set(jnp.arange(a, dtype=jnp.int32), mode="drop")
        x_a = jnp.repeat(tokens, k, axis=0).astype(self.dtype)  # [A, D]
        # OOB sentinel reads fill with zeros — no padded-copy of x_a needed
        expert_in = jnp.take(x_a, gather, axis=0, mode="fill", fill_value=0).reshape(
            e, cap, d
        )
        gate_up = self.param(
            "gate_up",
            nn.with_partitioning(
                nn.initializers.normal(0.02), (MODEL_AXIS, None, None)
            ),
            (e, d, 2 * h),
            self.param_dtype,
        )
        down = self.param(
            "down",
            nn.with_partitioning(
                nn.initializers.normal(0.02), (MODEL_AXIS, None, None)
            ),
            (e, h, d),
            self.param_dtype,
        )
        z = jnp.einsum("ecd,edh->ech", expert_in, gate_up.astype(self.dtype))
        gate, up = jnp.split(z, 2, axis=-1)
        out = jnp.einsum(
            "ech,ehd->ecd", nn.silu(gate) * up, down.astype(self.dtype)
        )  # [E, C, D]
        # combine: each assignment reads back its queue slot (overflow
        # dest is already out of range -> zero fill), weighted by the
        # renormalized router prob
        out_a = jnp.take(
            out.reshape(e * cap, d), dest, axis=0, mode="fill", fill_value=0
        ).astype(jnp.float32)
        y = (out_a * top_w.reshape(-1)[:, None]).reshape(n, k, d).sum(axis=1)
        return y.reshape(b, t, d).astype(x.dtype)

    def _sorted_experts(self, tokens, top_w, top_i):
        """Exact dispatch whose cost follows the assignments that land on the
        experts held here: the ``N * k`` assignments are sorted by expert (those
        of absent experts last, in a group no table belongs to), each held
        expert's run of rows goes through its SwiGLU in one grouped matrix
        product (ops/grouped_matmul.py: a tile of rows is visited only where an
        assignment lies), and every token sums its own rows, weighted. The
        length is static at its worst case: group-limited routing lets a
        token's whole top-k fall into the one group held here, so no bound
        under ``N * k`` holds. Nothing is queued and nothing dropped: a row's
        result depends on its own token alone. Returns ``[N, D]`` float32, the
        shared expert (every token's, counted once) added."""
        from cosmos_curate_tpu.ops.grouped_matmul import grouped_matmul

        moe = self.cfg.moe
        n, d = tokens.shape
        k, h = moe.top_k, moe.hidden
        first, count = moe.held_experts
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)
        init = nn.with_partitioning(nn.initializers.normal(0.02), (MODEL_AXIS, None, None))
        gate_up = self.param("gate_up", init, (count, d, 2 * h), self.param_dtype)
        down = self.param("down", init, (count, h, d), self.param_dtype)
        with jax.named_scope("moe.experts"):
            local = top_i.reshape(-1) - first  # [A], token-major
            expert = jnp.where((local >= 0) & (local < count), local, count)
            order = jnp.argsort(expert, stable=True)
            sizes = jnp.zeros(count + 1, jnp.int32).at[expert].add(1)[:count]
            held = sizes.sum()
            # read with stats(), never in the step loop (engine: expert_assignments_held)
            self.sow("intermediates", "held", held)
            # for the program that tells live rows from idle (init would keep the collection as a variable)
            if self.is_mutable_collection("held_by_token") and not self.is_initializing():
                self.sow("held_by_token", "held", (expert < count).reshape(n, k).sum(axis=1))
            rows = tokens.astype(self.dtype)[order // k]  # [A, D], expert-major
            # every table held and every row some table's: the product's tiles follow that
            whole = moe.held is None
            z = grouped_matmul(rows, gate_up.astype(self.dtype), sizes, whole=whole)
            gate, up = jnp.split(z, 2, axis=-1)
            out = grouped_matmul(nn.silu(gate) * up, down.astype(self.dtype), sizes, whole=whole)
            # rows past the held assignments belong to no expert: whatever the
            # product left there is not a number anybody computed
            out = jnp.where(jnp.arange(n * k)[:, None] < held, out, 0).astype(jnp.float32)
            back = jnp.zeros(n * k, jnp.int32).at[order].set(jnp.arange(n * k, dtype=jnp.int32))
            y = (out[back] * top_w.reshape(-1)[:, None]).reshape(n, k, d).sum(axis=1)
        if moe.shared_hidden:
            with jax.named_scope("moe.shared"):
                x = tokens.astype(self.dtype)
                up = proj(moe.shared_hidden, "out", name="shared_up", use_bias=False)(x)
                gate = proj(moe.shared_hidden, "out", name="shared_gate", use_bias=False)(x)
                y = y + proj(d, "in", name="shared_down", use_bias=False)(nn.silu(gate) * up)
        return y


# jax.named_scope names of the sites where a program partitioned over the
# ``model`` axis crosses chips: the all-reduce that ends each row-parallel
# matmul (attention output, MLP down) and the gathers around the embedding
# table (split by feature) and the head (split by vocabulary). Free at run
# time; they put a name on a trace's collectives (docs/OBSERVABILITY.md).
TP_SCOPES = {
    "attn_out": "tp_reduce.attn_out",
    "mlp_down": "tp_reduce.mlp_down",
    "embed": "tp_gather.embed",
    "head": "tp_gather.head",
}


class DecoderLayer(nn.Module):
    cfg: VLMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32  # see VLM.param_dtype
    # optional device mesh: when set and it names the model axis, the paged
    # path runs head-parallel (shard_map over Hkv) — see paged_head_attention
    mesh: object = None
    # a "sliding_attention" layer's window (its cache is then the window pool
    # and ``block_tables`` that pool's table); None = the whole context
    window: int | None = None
    # rope in THIS layer (``VLMConfig.rope_in_layer``); None = ``cfg.use_rope``
    use_rope: bool | None = None
    dense_ffn: bool = False  # a leading layer of a sparse model (``moe.first_dense``)
    yarn: YarnConfig | None = None  # YaRN on THIS layer's rope (``VLMConfig.yarn_in_layer``)

    @nn.compact
    def __call__(
        self, x, cache_k, cache_v, positions, write_index, kv_len,
        block_tables=None, layer_index=0,
    ):
        """One decoder layer over a KV cache.

        x: [B, T, D]; cache_k/v: [B, Hkv, S, Dh], one row a slot (the
        ``gather`` programs' view and the shared prefix's build); positions:
        [B, T] rope positions (or [B, T, 3] m-rope components — under
        m-rope, rope position ≠ cache index, so causality derives from
        write_index, not positions); write_index: [B] offset where this
        chunk's K/V land; kv_len: [B] valid cache length AFTER writing
        (= write_index + T for active rows). The chunk is written, then
        attended by the XLA reference (ops/paged_attention.py).
        Returns (y, new_cache_k, new_cache_v).

        Paged mode (``block_tables`` set): cache_k/v are the FULL block
        pools ``[L, NB, Hkv, bs, Dh]`` and block_tables is ``[B, nbl]``.
        K/V scatter through the table and attention reads the pool in place
        (ops/paged_attention.py) — no contiguous working-set view exists.
        Returns the updated pools in place of cache rows.
        """
        cfg = self.cfg
        b, t, _ = x.shape
        h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        # every projection here computes in ``dtype`` and stores ``param_dtype``
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)

        y = RMSNorm(eps=cfg.rms_eps, name="ln1")(x) if cfg.pre_norm else x
        q = proj(h * dh, "out", name="q", use_bias=cfg.qkv_bias)(y)
        k = proj(hk * dh, "out", name="k", use_bias=cfg.qkv_bias)(y)
        v = proj(hk * dh, "out", name="v", use_bias=cfg.qkv_bias)(y)
        if cfg.qk_norm_whole:  # Olmo 2 / 3: one RMSNorm over all heads' width
            q = RMSNorm(eps=cfg.rms_eps, name="q_norm")(q)
            k = RMSNorm(eps=cfg.rms_eps, name="k_norm")(k)
        q = q.reshape(b, t, h, dh)
        k = k.reshape(b, t, hk, dh)
        if cfg.qk_norm:  # Qwen3 family: per-HEAD-DIM RMSNorm before rope
            q = RMSNorm(eps=cfg.rms_eps, name="q_norm")(q)
            k = RMSNorm(eps=cfg.rms_eps, name="k_norm")(k)
        if cfg.use_rope if self.use_rope is None else self.use_rope:
            scaled = {} if self.yarn is None else {
                "freqs": jnp.asarray(self.yarn.inv_freq(dh, cfg.rope_theta)), "gain": self.yarn.gain,
            }
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_section, cfg.mrope_interleaved, **scaled)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_section, cfg.mrope_interleaved, **scaled)
        v = v.reshape(b, t, hk, dh)
        # a "sliding_attention" layer hands its window on; where the kinds mix
        # each is named in a compiled program (no other flavor's programs
        # carry these scopes, nor the keyword)
        edge = {} if self.window is None else {"window": self.window}
        mixed = cfg.window_layers or cfg.recurrent_kind == "linear_attention"
        kind = contextlib.nullcontext() if not mixed else jax.named_scope(
            "attn.full" if self.window is None else "attn.window"
        )
        with kind:
            if cfg.indexer is not None:
                attn, new_k, new_v = self._index_and_attend(
                    y, q, k, v, cache_k, cache_v, positions, write_index, kv_len, block_tables, layer_index
                )
            else:
                attn, new_k, new_v = self._write_and_attend(
                    q, k, v, cache_k, cache_v, write_index, kv_len, block_tables, layer_index, edge
                )
        attn = attn.reshape(b, t, h * dh)
        if cfg.attention_gate:
            with jax.named_scope("attn.gate"):
                gate = proj(h * dh, "out", name="g", use_bias=False)(y)
                attn = attn * jax.nn.sigmoid(gate)
        # the row-parallel matmuls end in an all-reduce over the model axis:
        # the scope names it in a compiled program and in a device trace
        with jax.named_scope(TP_SCOPES["attn_out"]):
            branch = proj(cfg.dim, "in", name="o", use_bias=False)(attn)
            if cfg.sandwich_norm:
                branch = _branch_norm(cfg, "post_attn_norm")(branch)
            x = _residual(cfg, x, branch)
        ffn = _ffn_half(cfg, x, proj, self.dtype, self.param_dtype, dense_ffn=self.dense_ffn)
        return ffn, new_k, new_v

    def _write_and_attend(self, q, k, v, cache_k, cache_v, write_index, kv_len, block_tables, layer_index, edge):
        """This chunk's K/V written into the cache, then attention over it
        (``edge``: the layer's window, if it has one). Returns (attn ``[B, T, H,
        Dh]``-shaped values as the branches leave them, new_k, new_v)."""
        from cosmos_curate_tpu.ops.paged_attention import (
            paged_attention,
            paged_head_attention,
            reference_attention,
        )

        cfg = self.cfg
        b, t, h, dh = q.shape
        hk = cfg.n_kv_heads
        group = h // hk
        if block_tables is not None:
            # paged path: scatter this chunk's K/V through the block table
            # (the same full-window write the gather path's scatter-back
            # performs — positions past t_valid land in-table and carry
            # identical garbage both ways), then attend straight out of the
            # pool. No gathered view, no scatter-back.
            from cosmos_curate_tpu.models.vlm.paged_kv import paged_head_update, paged_update
            from cosmos_curate_tpu.parallel.axes import MODEL

            head_parallel = self.mesh is not None and MODEL in self.mesh.axis_names
            if head_parallel and edge:
                raise ValueError("window layers are not served over a mesh")
            if head_parallel:
                new_k, new_v = paged_head_update(
                    self.mesh, cache_k, cache_v, k, v, block_tables, write_index,
                    layer_index=layer_index,
                )
            else:
                new_k, new_v = paged_update(
                    cache_k, cache_v, k, v, block_tables, write_index,
                    layer_index=layer_index,
                )
            qk = q.reshape(b, t, hk, group, dh)
            if head_parallel:
                attn = paged_head_attention(
                    self.mesh, qk, new_k, new_v, block_tables, write_index, kv_len,
                    layer_index=layer_index, sm_scale=cfg.attention_multiplier,
                )
            else:
                attn = paged_attention(
                    qk, new_k, new_v, block_tables, write_index, kv_len,
                    layer_index=layer_index, sm_scale=cfg.attention_multiplier, **edge,
                )
            return attn.astype(self.dtype), new_k, new_v

        # scatter this chunk into the cache at each row's write_index
        def write_row(cache, chunk, idx):
            return jax.lax.dynamic_update_slice(cache, chunk, (0, idx, 0))

        new_k = jax.vmap(write_row)(
            cache_k, k.astype(cache_k.dtype).swapaxes(1, 2), write_index
        )
        new_v = jax.vmap(write_row)(
            cache_v, v.astype(cache_v.dtype).swapaxes(1, 2), write_index
        )
        attn = reference_attention(
            q.reshape(b, t, hk, group, dh), new_k, new_v, write_index, kv_len,
            sm_scale=cfg.attention_multiplier or dh**-0.5, **edge,
        )
        return attn, new_k, new_v

    def _index_and_attend(self, y, q, k, v, caches, cache_v, positions, write_index, kv_len, block_tables, layer_index):
        """A layer with an indexer (``cfg.indexer``): this chunk's K/V AND its
        index keys written, every query's scores of the positions it can see,
        its choice, and attention over the chosen positions only
        (ops/sparse_attention.py). ``y``: the layer's normed input, which feeds
        the indexer's three projections as it feeds q, k and v; ``caches``: the
        pair (K, index keys), slot rows ``[B, 1, S, W]`` or the paged array
        ``[L, NB, 1, bs, W]`` beside the K pool. A decode step (one query a
        row) walks its row's live pages under the chosen set's mask where the
        kernel runs and the lane is short enough for that to be the cheaper
        read, and gathers the K/V of its chosen positions and no others elsewhere
        (``sparse.decode_attention``: the one choice); a longer chunk attends
        densely under its queries' masks. What each row's last query
        chose is sown as ``choice/digest`` (``pack_choice``: a bit a position): a
        program that asks for the collection hands it out, no other computes it.
        Returns (attn, (new K, new index keys), new V)."""
        from cosmos_curate_tpu.models.vlm.paged_kv import latent_update, paged_update
        from cosmos_curate_tpu.ops import sparse_attention as sparse

        cfg, ix = self.cfg, self.cfg.indexer
        b, t, h, dh = q.shape
        hk = cfg.n_kv_heads
        cache_k, cache_i = caches
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)
        sm_scale = cfg.attention_multiplier or dh**-0.5
        with jax.named_scope("attn.index"):
            # text positions: under m-rope the three components are equal there
            pos = positions[..., 0] if positions.ndim == 3 else positions
            qi = proj(ix.n_heads * ix.head_dim, "out", name="index_q", use_bias=False)(y)
            qi = apply_rope(qi.reshape(b, t, ix.n_heads, ix.head_dim), pos, cfg.rope_theta)
            ki = proj(ix.head_dim, None, name="index_k", use_bias=False)(y)
            ki = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, name="index_k_norm")(ki).astype(self.dtype)
            ki = apply_rope(ki[:, :, None], pos, cfg.rope_theta)[:, :, 0]  # ONE key head
            w = dense(ix.n_heads, None, name="index_w", use_bias=False, dtype=jnp.float32)(
                y.astype(jnp.float32)
            ) * ix.weight_scale
            row = jnp.pad(ki, ((0, 0), (0, 0), (0, ix.cache_width - ix.head_dim)))
        qk = q.reshape(b, t, hk, h // hk, dh)
        paged = block_tables is not None
        if paged:
            cache_i = latent_update(cache_i, row, block_tables, write_index, layer_index=layer_index)
            new_k, new_v = paged_update(cache_k, cache_v, k, v, block_tables, write_index, layer_index=layer_index)
            with jax.named_scope("attn.index_score"):
                scores = sparse.index_scores(
                    qi, w, cache_i, block_tables, write_index, kv_len, layer_index=layer_index
                )
        else:  # slot rows: the chunk at each row's write index, in all three

            def write_row(cache, chunk):
                put = lambda rows, new, idx: jax.lax.dynamic_update_slice(rows, new, (0, idx, 0))  # noqa: E731
                return jax.vmap(put)(cache, chunk.astype(cache.dtype), write_index)

            cache_i = write_row(cache_i, row[:, None])
            new_k, new_v = write_row(cache_k, k.swapaxes(1, 2)), write_row(cache_v, v.swapaxes(1, 2))
            with jax.named_scope("attn.index_score"):
                scores = sparse.index_scores_reference(
                    qi, w, cache_i[:, 0, :, : ix.head_dim], write_index, kv_len
                )
        if paged and t == 1:  # (the scopes `attn.select` and `attn.sparse` are opened inside)
            attn, *numbers = sparse.decode_attention(
                qk[:, 0], new_k, new_v, block_tables, kv_len, scores[:, 0], ix.top_k, layer_index=layer_index,
                sm_scale=sm_scale,
            )
            attn = attn[:, None]
            last = sparse.chosen_mask(scores[:, 0], *numbers)
        else:
            with jax.named_scope("attn.select"):
                if paged:  # (how many positions a query can choose from bounds the kernel's work)
                    seen = jnp.minimum(kv_len[:, None], write_index[:, None] + jnp.arange(t)[None, :] + 1)
                    numbers = sparse.select_threshold(scores, ix.top_k, seen)
                else:
                    numbers = sparse.select_threshold_reference(scores, ix.top_k)
                chosen = sparse.chosen_mask(scores, *numbers)
            with jax.named_scope("attn.sparse"):
                if paged:
                    attn = sparse.sparse_prefill_attention(
                        qk, new_k, new_v, block_tables, write_index, kv_len, chosen,
                        layer_index=layer_index, sm_scale=sm_scale,
                    )
                else:
                    attn = sparse.sparse_reference_attention(qk, new_k, new_v, chosen, sm_scale=sm_scale)
            last = jnp.take_along_axis(
                chosen, jnp.clip(kv_len - write_index - 1, 0, t - 1)[:, None, None], axis=1
            )[:, 0]  # [B, S]: the row's last valid query's set
        self.sow("choice", "digest", sparse.pack_choice(last))
        return attn.astype(self.dtype), (new_k, cache_i), new_v


def _branch_norm(cfg: VLMConfig, name: str) -> RMSNorm:
    """The RMSNorm on a branch's OUTPUT (``sandwich_norm``). Where nothing
    norms a branch's input (the Olmo block) a seeded scale starts at a quarter
    of the embedding table's 0.02, not at 1: sixteen layers of unit branches
    on a 0.02 stream amplify a bfloat16 rounding until the logits agree with
    nothing (0.74 of their largest at the tiny preset over 16 layers, 0.02
    so; PERF.md, PR 44), and a comparison with a reference would say nothing.
    A trained model's scales are its checkpoint's."""
    return RMSNorm(eps=cfg.rms_eps, name=name, scale_init=1.0 if cfg.pre_norm else 0.005)


def _residual(cfg: VLMConfig, x, branch):
    r = cfg.residual_multiplier
    return x + branch if r == 1.0 else x + branch * r


def _ffn_half(cfg: VLMConfig, x, proj, dtype, param_dtype, dense_ffn=False):
    """``x + ffn(RMSNorm(x))``: the second half of every kind of layer. Called
    inside a layer's compact method, so the submodules are that layer's.
    ``dense_ffn``: a sparse model's leading layer that keeps the dense SwiGLU."""
    y = RMSNorm(eps=cfg.rms_eps, name="ln2")(x) if cfg.pre_norm else x

    def branch(out):  # the sandwich's second slice of bread, where a flavor has it
        return _branch_norm(cfg, "post_mlp_norm")(out) if cfg.sandwich_norm else out

    if cfg.moe is not None and not dense_ffn:
        moe = MoEFFN(cfg, dtype=dtype, param_dtype=param_dtype, name="moe")
        return _residual(cfg, x, branch(moe(y)))
    up = proj(int(cfg.dim * cfg.hidden_mult), "out", name="up", use_bias=False)(y)
    gate = proj(int(cfg.dim * cfg.hidden_mult), "out", name="gate", use_bias=False)(y)
    with jax.named_scope(TP_SCOPES["mlp_down"]):
        down = proj(cfg.dim, "in", name="down", use_bias=False)(nn.silu(gate) * up)
    return _residual(cfg, x, branch(down))


class LatentAttentionLayer(nn.Module):
    """A decoder layer with multi-head latent attention (DeepSeek-V2) where
    ``DecoderLayer`` has GQA, then the same FFN half. The cache holds one row a
    token, ``[c_kv | k_rope | zeros]`` (``MLAConfig.cache_width``), and
    attention runs ABSORBED against it (ops/latent_attention.py): ``W_UK`` and
    ``W_UV`` are the two halves of the one stored ``kv_b`` table, folded into
    the query and the output at trace time, so prefill and decode are one set
    of weights and no per-head K or V ever exists. Rope is the half-split
    layout the other layers use; the converter owns the permutation from HF's
    interleaved pairs (models/convert_deepseek.py)."""

    cfg: VLMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32  # see VLM.param_dtype
    dense_ffn: bool = False  # a leading layer of a sparse model (``moe.first_dense``)

    @nn.compact
    def __call__(
        self, x, cache, cache_v, positions, write_index, kv_len,
        block_tables=None, layer_index=0,
    ):
        """x: [B, T, D]; cache: the slots' latent rows ``[B, 1, S, W]``, or in
        paged mode (``block_tables`` set) the whole latent pool ``[L, NB, 1,
        bs, W]``; cache_v: the zero-width companion every program threads
        (paged_kv.init_latent_pool), handed back untouched. The rest as
        ``DecoderLayer``. Returns (y, the updated cache, cache_v)."""
        from cosmos_curate_tpu.models.vlm.paged_kv import latent_update
        from cosmos_curate_tpu.ops.latent_attention import (
            latent_attention,
            latent_reference_attention,
        )

        cfg, mla = self.cfg, self.cfg.mla
        b, t, _ = x.shape
        h, c = cfg.n_heads, mla.kv_lora_rank
        dn, dr, dv, w = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim, mla.cache_width
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)
        freqs = jnp.asarray(yarn_inv_freq(
            dr, cfg.rope_theta, mla.yarn_factor, mla.yarn_original_max, mla.yarn_beta_fast, mla.yarn_beta_slow
        ))
        # what YaRN puts on cos and sin: mscale / mscale(all_dim), 1 when they agree
        rope_gain = yarn_mscale(mla.yarn_factor, mla.yarn_mscale) / yarn_mscale(
            mla.yarn_factor, mla.yarn_mscale_all_dim
        )

        def rope(v):  # [B, T, heads, dr]
            v = apply_rope(v, positions, cfg.rope_theta, freqs=freqs)
            return v if rope_gain == 1.0 else v * rope_gain

        y = RMSNorm(eps=cfg.rms_eps, name="ln1")(x)
        c_q = RMSNorm(eps=cfg.rms_eps, name="q_a_norm")(
            proj(mla.q_lora_rank, None, name="q_a", use_bias=False)(y)
        )
        q = proj(h * (dn + dr), "out", name="q_b", use_bias=False)(c_q).reshape(b, t, h, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
        kv = proj(c + dr, None, name="kv_a", use_bias=False)(y)
        c_kv = RMSNorm(eps=cfg.rms_eps, name="kv_a_norm")(kv[..., :c])
        k_rope = rope(kv[..., None, c:])[:, :, 0]
        row = jnp.concatenate(
            [c_kv, k_rope, jnp.zeros((b, t, w - c - dr), c_kv.dtype)], axis=-1
        )  # [B, T, W]
        # HF ``kv_b_proj``, stored once: [C, H, nope | v] are W_UK and W_UV
        kv_b = self.param(
            "kv_b", nn.with_partitioning(nn.initializers.xavier_uniform(), (None, MODEL_AXIS)),
            (c, h * (dn + dv)), self.param_dtype,
        ).astype(self.dtype).reshape(c, h, dn + dv)
        w_uk, w_uv = kv_b[..., :dn], kv_b[..., dn:]
        q_abs = jnp.concatenate(
            [
                jnp.einsum("bthd,chd->bthc", q_nope, w_uk), q_rope,
                jnp.zeros((b, t, h, w - c - dr), q_rope.dtype),
            ],
            axis=-1,
        )  # [B, T, H, W]: scores against a cached row are one dot product
        with jax.named_scope("mla.decode" if t == 1 else "mla.prefill"):
            if block_tables is not None:
                cache = latent_update(cache, row, block_tables, write_index, layer_index=layer_index)
                u = latent_attention(
                    q_abs, cache, block_tables, write_index, kv_len, layer_index=layer_index,
                    sm_scale=mla.softmax_scale, v_width=c,
                )
            else:
                cache = jax.vmap(
                    lambda rows, chunk, idx: jax.lax.dynamic_update_slice(rows, chunk, (0, idx, 0))
                )(cache, row.astype(cache.dtype)[:, None], write_index)
                u = latent_reference_attention(
                    q_abs, cache[:, 0], write_index, kv_len, sm_scale=mla.softmax_scale, v_width=c,
                )
        attn = jnp.einsum("bthc,chd->bthd", u.astype(self.dtype), w_uv).reshape(b, t, h * dv)
        with jax.named_scope(TP_SCOPES["attn_out"]):
            x = _residual(cfg, x, proj(cfg.dim, "in", name="o", use_bias=False)(attn))
        ffn = _ffn_half(cfg, x, proj, self.dtype, self.param_dtype, dense_ffn=self.dense_ffn)
        return ffn, cache, cache_v


class MambaLayer(nn.Module):
    """A hybrid decoder's state-space layer: the Mamba-2 mixer
    (models/vlm/mamba2.py) where ``DecoderLayer`` has attention, then the
    same FFN half. Its state is two rows of the engine's recurrent store."""

    cfg: VLMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32  # see VLM.param_dtype

    @nn.compact
    def __call__(self, x, ssm, tail, rows, valid, *, layer_index=0, use_kernel=None):
        """x: [B, T, D]; ssm: states ``[Lm, R, H, P, N]`` float32, this
        layer's at ``[layer_index, rows]``; tail: the rows' convolution
        tails ``[B, (d_conv - 1) * conv_dim]``; valid: [B] positions of this
        chunk that advance the state (the rest is padding). Returns (y, ssm,
        the new tails)."""
        cfg = self.cfg
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)
        mixer = Mamba2Mixer(
            cfg.mamba, cfg.dim, cfg.rms_eps, dtype=self.dtype, param_dtype=self.param_dtype,
            name="mixer",
        )
        y, ssm, tail = mixer(
            RMSNorm(eps=cfg.rms_eps, name="ln1")(x), ssm, tail, rows, valid,
            layer_index=layer_index, use_kernel=use_kernel,
        )
        x = _residual(cfg, x, y)
        return _ffn_half(cfg, x, proj, self.dtype, self.param_dtype), ssm, tail


class LinearAttentionLayer(nn.Module):
    """A hybrid decoder's linear-attention layer: the gated-delta-rule mixer
    (models/vlm/gated_delta.py) where ``DecoderLayer`` has attention, under
    the flavor's own norm placement, then the same FFN half. Its state is
    two rows of the engine's recurrent store, as ``MambaLayer``'s."""

    cfg: VLMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32  # see VLM.param_dtype

    @nn.compact
    def __call__(self, x, ssm, tail, rows, valid, *, layer_index=0, use_kernel=None):
        """As ``MambaLayer``'s; ssm: states ``[Ll, R, dk, H * dv]`` float32;
        tail: ``[B, (d_conv - 1) * conv_dim]``, the three convolutions' tails."""
        cfg = self.cfg
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)
        mixer = GatedDeltaMixer(
            cfg.gated_delta, cfg.dim, cfg.rms_eps, dtype=self.dtype, param_dtype=self.param_dtype,
            name="mixer",
        )
        y, ssm, tail = mixer(
            RMSNorm(eps=cfg.rms_eps, name="ln1")(x) if cfg.pre_norm else x, ssm, tail, rows, valid,
            layer_index=layer_index, use_kernel=use_kernel,
        )
        if cfg.sandwich_norm:
            y = _branch_norm(cfg, "post_attn_norm")(y)
        x = _residual(cfg, x, y)
        return _ffn_half(cfg, x, proj, self.dtype, self.param_dtype), ssm, tail


class ShortConvLayer(nn.Module):
    """A hybrid decoder's short-convolution layer (LFM2): the gated short
    convolution (models/vlm/short_conv.py) where ``DecoderLayer`` has
    attention, then the same FFN half. What it carries is a row of the
    recurrent store's tails; the store's state half is empty for this kind and
    passes through untouched."""

    cfg: VLMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32  # see VLM.param_dtype
    dense_ffn: bool = False  # a leading layer of a sparse model (``moe.first_dense``)

    @nn.compact
    def __call__(self, x, ssm, tail, rows, valid, *, layer_index=0, use_kernel=None):
        """As ``MambaLayer``'s; tail: ``[B, (l_cache - 1) * dim]``."""
        cfg = self.cfg
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)
        mixer = ShortConvMixer(
            cfg.short_conv, cfg.dim, dtype=self.dtype, param_dtype=self.param_dtype, name="mixer"
        )
        y, tail = mixer(RMSNorm(eps=cfg.rms_eps, name="ln1")(x), tail, valid)
        x = _residual(cfg, x, y)
        return _ffn_half(cfg, x, proj, self.dtype, self.param_dtype, dense_ffn=self.dense_ffn), ssm, tail


class VLM(nn.Module):
    cfg: VLMConfig
    dtype: jnp.dtype = jnp.bfloat16
    # The type in which the parameters of every layer that computes in
    # ``dtype`` are stored: matmul kernels and biases, the embedding table,
    # the MoE expert tables, the Qwen vision tower. A layer that computes in
    # float32 says so itself (``lm_head``, the MoE ``router``, every norm
    # scale, Qwen3's position table) and stores float32 whatever this is. The
    # default inits what every loader and converter expects; the caption
    # engine builds its model with ``param_dtype=dtype`` and serves from
    # bf16(w), the operands a float32 tree is rounded to at every call.
    param_dtype: jnp.dtype = jnp.float32
    # optional device mesh threaded to every DecoderLayer: enables the
    # head-parallel paged-attention path (tensor parallelism over Hkv)
    mesh: object = None

    def setup(self) -> None:
        cfg = self.cfg
        self.embed = nn.Embed(
            cfg.vocab,
            cfg.dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            embedding_init=nn.with_partitioning(nn.initializers.normal(0.02), (None, MODEL_AXIS)),
        )
        def leading_dense(i):
            return cfg.moe is not None and i < cfg.moe.first_dense

        def attention_layer(i):
            dense_ffn = leading_dense(i)
            if cfg.mla is not None:
                return LatentAttentionLayer(
                    cfg, dtype=self.dtype, param_dtype=self.param_dtype, name=f"layer_{i}",
                    dense_ffn=dense_ffn,
                )
            # where window and full layers mix each is told its kind; for every
            # other flavor these are the defaults
            return DecoderLayer(
                cfg, dtype=self.dtype, param_dtype=self.param_dtype, mesh=self.mesh,
                name=f"layer_{i}", window=cfg.sliding_window if i in cfg.window_layers else None,
                use_rope=cfg.rope_in_layer(i), dense_ffn=dense_ffn, yarn=cfg.yarn_in_layer(i),
            )

        def recurrent_layer(i):
            own = dict(dtype=self.dtype, param_dtype=self.param_dtype, name=f"layer_{i}")
            if cfg.layer_types[i] == "conv":  # (the one recurrent kind a sparse model's leading layers come in)
                return ShortConvLayer(cfg, dense_ffn=leading_dense(i), **own)
            return {"mamba": MambaLayer, "linear_attention": LinearAttentionLayer}[cfg.layer_types[i]](cfg, **own)

        self.layers = [
            recurrent_layer(i) if i in cfg.ssm_layers else attention_layer(i) for i in range(cfg.n_layers)
        ]
        self.ln_f = RMSNorm(eps=cfg.rms_eps, name="ln_f")
        self.lm_head = (
            None
            if cfg.tied_embeddings
            else dense(cfg.vocab, "out", name="lm_head", use_bias=False, dtype=jnp.float32)
        )
        if cfg.vision_variant in ("qwen2", "qwen3"):
            self.vision_tower = QwenVisionTower(
                cfg.qwen_vision, dtype=self.dtype, param_dtype=self.param_dtype, name="vision"
            )
            self.projector = None  # the Qwen merger already maps to LM dim
        else:
            # models/vit.py is shared with the embedding stages and keeps
            # float32 parameters (a few MB in the flavors that use it)
            self.vision_tower = ViT(cfg.vision, dtype=self.dtype, name="vision")
            self.projector = nn.Sequential(
                [
                    dense(cfg.dim * 2, None, dtype=self.dtype, param_dtype=self.param_dtype),
                    nn.gelu,
                    dense(cfg.dim, None, dtype=self.dtype, param_dtype=self.param_dtype),
                ],
                name="projector",
            )

    def encode_images(self, frames_u8):
        """uint8 [B, N, Hp, Wp, 3] -> [B, T_vis, dim] LM embeddings.

        ``vit`` variant: frames through the ViT, patch tokens mean-pooled
        over frames, strided to ``vision_tokens``, projected.
        ``qwen2`` variant: frames → 3D patches → QwenVisionTower; the merged
        token grid (t·h·w/merge²) IS the LM embedding sequence, ordered
        t-major row-major (what build_mrope_positions assumes).
        ``qwen3`` variant: same, but returns (embeds, deepstack) — the
        deepstack levels [L_ds, B, T_vis, dim] inject into the first LM
        layers (HF Qwen3VLTextModel._deepstack_process).
        """
        cfg = self.cfg
        if cfg.vision_variant in ("qwen2", "qwen3"):
            patches, grid = frames_to_patches(frames_u8, cfg.qwen_vision)
            return self.vision_tower(patches, grid)
        b, n = frames_u8.shape[:2]
        pixels = preprocess_frames(
            frames_u8, image_size=cfg.vision.image_size, mode=cfg.vision.preprocess
        )
        _, tokens = self.vision_tower(pixels.reshape((b * n, *pixels.shape[2:])))
        tokens = tokens[:, 1:]  # drop cls
        tokens = tokens.reshape(b, n, tokens.shape[1], tokens.shape[2]).mean(axis=1)
        # stride-pool the patch grid down to vision_tokens
        stride = max(1, tokens.shape[1] // cfg.vision_tokens)
        tokens = tokens[:, :: stride][:, : cfg.vision_tokens]
        return self.projector(tokens)

    def embed_tokens(self, token_ids):
        with jax.named_scope(TP_SCOPES["embed"]):
            x = self.embed(token_ids)
        m = self.cfg.embedding_multiplier
        return x if m == 1.0 else x * m

    def init_everything(self, frames_u8, token_ids, cache_k, cache_v):
        """Init-only method touching every submodule (flax only creates
        params for modules traced during init)."""
        vis = self.encode_images(frames_u8)
        txt = self.embed_tokens(token_ids)
        deepstack = None
        if isinstance(vis, tuple):  # qwen3: (embeds, deepstack levels)
            vis, ds = vis
            pad = jnp.zeros((ds.shape[0], ds.shape[1], txt.shape[1], ds.shape[-1]), ds.dtype)
            deepstack = jnp.concatenate([ds, pad], axis=2)
        embeds = jnp.concatenate([vis, txt], axis=1)
        t = embeds.shape[1]
        positions = jnp.broadcast_to(jnp.arange(t), (embeds.shape[0], t))
        return self(
            embeds,
            cache_k,
            cache_v,
            positions,
            jnp.zeros((embeds.shape[0],), jnp.int32),
            jnp.full((embeds.shape[0],), t, jnp.int32),
            deepstack=deepstack,
        )

    def _logits(self, x, logits_at):
        """Final norm + LM head. ``logits_at`` ([B] positions) keeps one
        position per row BEFORE the head: a prefill needs only its last
        valid position, and at a real vocabulary the full ``[B, T, vocab]``
        fp32 logits of a long bucket outweigh the model (8 x 1024 x 151936
        x 4 B = 5 GB)."""
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None].astype(jnp.int32), axis=1)
        x = self.ln_f(x)
        with jax.named_scope(TP_SCOPES["head"]):
            if self.lm_head is not None:  # untied checkpoints (Qwen2.5-VL-7B)
                logits = self.lm_head(x.astype(jnp.float32))
            else:
                logits = self.embed.attend(x.astype(jnp.float32))
        s = self.cfg.logits_scaling
        return logits if s == 1.0 else logits / s

    def __call__(
        self, embeds, cache_k, cache_v, positions, write_index, kv_len, deepstack=None,
        logits_at=None, recurrent=None,
    ):
        """Forward over input *embeddings* (text and vision already spliced).

        embeds: [B, T, D]; cache_k/v: [L, B, Hkv, S, Dh], ``L`` counting the
        attention layers (``cfg.kv_layers``); deepstack:
        optional [L_ds, B, T, D] visual features added to the hidden states
        AFTER each of the first L_ds layers (zeros at text positions — HF
        Qwen3VL deepstack semantics; prefill-only, decode passes None).
        Returns (logits [B, T, vocab] — or [B, 1, vocab] at ``logits_at``
        — new_cache_k, new_cache_v).

        A hybrid (``cfg.ssm_layers``) also takes ``recurrent``, the engine's
        store as ``(ssm, conv, rows, valid)`` (see ``MambaLayer``), and then
        returns the two stores after the caches; None runs its state-space
        layers from a zero state that is dropped (a whole prompt at once).
        This is the ``gather`` programs' forward: the recurrence runs in
        plain XLA, token by token (ops/ssm.py).
        """
        return self._forward(
            embeds, cache_k, cache_v, positions, write_index, kv_len, None, deepstack,
            logits_at, recurrent,
        )

    def paged_forward(
        self, embeds, pool_k, pool_v, positions, write_index, kv_len, block_tables,
        deepstack=None, logits_at=None, recurrent=None,
    ):
        """Forward straight against the paged KV pool — no working-set view.

        embeds: [B, T, D]; pool_k/pool_v: the FULL block pools
        ``[L, NB, Hkv, bs, Dh]`` threaded through every layer (each layer
        scatters its chunk through ``block_tables`` [B, nbl] and attends in
        place via ops/paged_attention.py); write_index/kv_len as in
        ``__call__``. Returns (logits, pool_k, pool_v) — the updated pools, never a ``jnp.stack`` of per-layer copies, so XLA
        donation keeps the scatters in-place. ``recurrent`` as in
        ``__call__``; here ops/ssm.py decides how the recurrence runs.
        """
        return self._forward(
            embeds, pool_k, pool_v, positions, write_index, kv_len, block_tables, deepstack,
            logits_at, recurrent,
        )

    def _forward(
        self, embeds, cache_k, cache_v, positions, write_index, kv_len, block_tables,
        deepstack, logits_at, recurrent,
    ):
        cfg = self.cfg
        paged = block_tables is not None
        x = embeds.astype(self.dtype)
        n_ds = 0 if deepstack is None else deepstack.shape[0]
        new_ks, new_vs = [], []
        ssm_layers = cfg.ssm_layers
        if ssm_layers:
            store_ssm, store_conv, store_rows, valid = recurrent or self._zero_state(
                x.shape[0], kv_len - write_index
            )
            use_kernel = None if paged else False
            # The rows' states are read out of the store ONCE, up front, and
            # written back ONCE, at the end; the layers work on the copy.
            # Thirty-six read-modify-writes of a donated 4 GiB store in the
            # middle of a program are what XLA's rematerialisation, under
            # memory pressure, ran twice (PERF.md, PR 30: a layer's state
            # advanced twice). Only the decode kernel walks the store's own
            # rows in place: a custom call is not rematerialised.
            tails, new_tails = store_conv[:, store_rows], []
            if cfg.recurrent_kind == "conv":  # tails alone: the empty state half passes through as it is
                in_place = True
            else:
                ops = delta_ops if cfg.recurrent_kind == "linear_attention" else ssm_ops
                in_place = x.shape[1] == 1 and ops.decode_in_place(use_kernel)
            ssm, rows = (
                (store_ssm, store_rows) if in_place
                else (_rows_of(store_ssm, store_rows), jnp.arange(x.shape[0], dtype=jnp.int32))
            )
        # window and full layers mixed, paged: ``cache_k`` / ``cache_v`` /
        # ``block_tables`` are pairs, (the pool every flavor has, the window
        # pool), each with its own table; a layer's index counts its own kind
        two_pools = paged and bool(cfg.window_layers)
        if two_pools:
            pools = {False: [cache_k[0], cache_v[0], block_tables[0], 0], True: [cache_k[1], cache_v[1], block_tables[1], 0]}
        kv_i = ssm_i = 0  # a layer's index in the KV caches / the recurrent store
        for i, layer in enumerate(self.layers):
            if i in ssm_layers:
                x, ssm, tail = layer(
                    x, ssm, tails[ssm_i], rows, valid, layer_index=ssm_i, use_kernel=use_kernel,
                )
                new_tails.append(tail)
                ssm_i += 1
            elif two_pools:
                own = pools[i in cfg.window_layers]
                x, own[0], own[1] = layer(
                    x, own[0], own[1], positions, write_index, kv_len,
                    block_tables=own[2], layer_index=own[3],
                )
                own[3] += 1
            elif paged:
                x, cache_k, cache_v = layer(
                    x, cache_k, cache_v, positions, write_index, kv_len,
                    block_tables=block_tables, layer_index=kv_i,
                )
                kv_i += 1
            else:
                # (an indexer flavor's ``cache_k`` is the pair (K, index keys))
                own_k = jax.tree.map(lambda c: c[kv_i], cache_k)
                x, nk, nv = layer(x, own_k, cache_v[kv_i], positions, write_index, kv_len)
                new_ks.append(nk)
                new_vs.append(nv)
                kv_i += 1
            if i < n_ds:
                x = x + deepstack[i].astype(x.dtype)
        logits = self._logits(x, logits_at)
        if two_pools:
            cache_k, cache_v = (pools[False][0], pools[True][0]), (pools[False][1], pools[True][1])
        if not paged:
            cache_k, cache_v = jax.tree.map(lambda *layers: jnp.stack(layers), *new_ks), jnp.stack(new_vs)
        out = (logits, cache_k, cache_v)
        if recurrent is None:
            return out
        if not in_place:
            ssm = store_ssm.at[:, store_rows].set(ssm)
        return (*out, ssm, store_conv.at[:, store_rows].set(jnp.stack(new_tails)))

    def _zero_state(self, batch: int, valid):
        """A scratch recurrent store of ``batch`` rows, all zeros."""
        ssm, conv = init_recurrent_store(self.cfg, batch, dtype=self.dtype)
        return ssm, conv, jnp.arange(batch, dtype=jnp.int32), valid


def _rows_of(store, rows):
    """``store[:, rows]`` (``[L, R, ...]`` -> ``[L, B, ...]``) as ONE DYNAMIC SLICE A
    ROW. As a gather the chip's compiler copies the whole store to take a
    program's few rows out of it (3.1 GiB of scratch beside a 265-row store of
    12 MiB rows, read off an ahead-of-time compile: the compiled text's
    ``mini-gather-slice``, PERF.md PR 49)."""
    return jnp.stack([jax.lax.dynamic_index_in_dim(store, r, 1, keepdims=False) for r in rows], axis=1)


def init_recurrent_store(cfg: VLMConfig, rows: int, dtype=jnp.bfloat16):
    """The state of a hybrid's recurrent layers, one row a request, sized by
    the mixer's kind (``cfg.recurrent_kind``): ``ssm`` float32 (a state is
    rounded once a token for as long as its request lives, so it keeps
    float32), ``[Lm, rows, H, P, N]`` for Mamba-2 and ``[Ll, rows, dk, H *
    dv]`` for the gated delta rule (the heads side by side on the lanes:
    ops/delta_rule.py has the why), and ``conv`` ``[Lm, rows, (d_conv - 1) *
    conv_dim]``, the convolutions' last inputs in the type they were
    computed in (a row's taps side by side: a ``[3, conv_dim]`` plane would
    be padded to a tile of 16 rows on the chip; the delta rule's three
    convolutions share the row, q | k | v). A short convolution ("conv") has
    tails and no state: its ``ssm`` is ``[Lc, rows, 0]``, an array every
    program threads and none reads (as a latent pool's zero-width companion),
    and its ``conv`` ``[Lc, rows, (l_cache - 1) * dim]``."""
    lm = len(cfg.ssm_layers)
    if cfg.recurrent_kind == "conv":
        tails = (cfg.short_conv.l_cache - 1) * cfg.dim
        return jnp.zeros((lm, rows, 0), jnp.float32), jnp.zeros((lm, rows, tails), dtype)
    if cfg.recurrent_kind == "linear_attention":
        m = cfg.gated_delta
        ssm = jnp.zeros((lm, rows, m.key_dim, m.n_heads * m.value_dim), jnp.float32)
    else:
        m = cfg.mamba
        ssm = jnp.zeros((lm, rows, m.n_heads, m.head_dim, m.d_state), jnp.float32)
    conv = jnp.zeros((lm, rows, (m.d_conv - 1) * m.conv_dim), dtype)
    return ssm, conv


def init_cache(cfg: VLMConfig, batch: int, dtype=jnp.bfloat16, length: int | None = None):
    """Slot-row caches ``[L, batch, Hkv, length, Dh]`` for K and V; a latent
    flavor's one row a token and its zero-width companion
    (paged_kv.init_latent_pool has the why)."""
    if cfg.mla is not None:
        shape = (len(cfg.kv_layers), batch, 1, length or cfg.max_seq)
        return jnp.zeros((*shape, cfg.mla.cache_width), dtype), jnp.zeros((*shape, 0), dtype)
    shape = (len(cfg.kv_layers), batch, cfg.n_kv_heads, length or cfg.max_seq, cfg.head_dim)
    if cfg.indexer is not None:  # beside K a row's index keys: the caches' first is the pair
        index = jnp.zeros((*shape[:2], 1, shape[3], cfg.indexer.cache_width), dtype)
        return (jnp.zeros(shape, dtype), index), jnp.zeros(shape, dtype)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
