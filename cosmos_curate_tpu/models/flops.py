"""Analytic FLOPs accounting for MFU reporting.

Equivalent capability of the reference's speed-of-light perf method
(docs/curator/design/SPEED_OF_LIGHT.md:22-81 — tokens/s and pipeline
efficiency vs hardware peak), translated to TPU: every model family gets an
analytic forward-FLOPs formula, and ``mfu(flops, seconds)`` divides the
achieved rate by the chip's bf16 peak. The formulas count matmul FLOPs only
(2·M·N·K per GEMM) — elementwise/normalization work is bandwidth-, not
FLOP-bound on TPU and is excluded, matching standard MFU conventions.
"""

from __future__ import annotations


def transformer_layer_flops(tokens: int, width: int, *, mlp_ratio: int = 4) -> float:
    """One pre-LN transformer block forward: QKVO projections + attention
    score/value matmuls + 2-layer MLP."""
    proj = 8.0 * tokens * width * width  # 4 projections, 2·T·W·W each
    attn = 4.0 * tokens * tokens * width  # QK^T and attn·V
    mlp = 2.0 * 2.0 * tokens * width * (mlp_ratio * width)
    return proj + attn + mlp


def vit_forward_flops(cfg) -> float:
    """One image through models/vit.ViT (patch conv + blocks + projection)."""
    n = cfg.num_patches + 1  # + cls token
    patch = 2.0 * cfg.num_patches * (cfg.patch_size * cfg.patch_size * 3) * cfg.width
    blocks = cfg.layers * transformer_layer_flops(n, cfg.width)
    proj = 2.0 * cfg.width * cfg.projection_dim
    return patch + blocks + proj


def video_embed_forward_flops(cfg) -> float:
    """One clip through models/embedder.VideoEmbedModel."""
    frames = cfg.num_frames * vit_forward_flops(cfg.vit)
    t = cfg.num_frames + 1  # + query token
    d = cfg.vit.projection_dim
    temporal = cfg.temporal_layers * transformer_layer_flops(t, d)
    out = 2.0 * d * cfg.output_dim
    return frames + temporal + out


def vlm_decode_flops_per_token(cfg) -> float:
    """One decode step for one sequence through models/vlm.VLM's LM stack
    (GQA + SwiGLU + tied head). Decode attention reads the whole KV cache:
    score/value matmuls scale with max_seq (upper estimate)."""
    d = cfg.dim
    q_inner = cfg.n_heads * cfg.head_dim
    kv_inner = cfg.n_kv_heads * cfg.head_dim
    proj = 2.0 * d * q_inner + 2.0 * 2.0 * d * kv_inner + 2.0 * q_inner * d
    attn = 2.0 * 2.0 * cfg.max_seq * q_inner
    ff = int(d * cfg.hidden_mult)
    mlp = 3.0 * 2.0 * d * ff  # gate + up + down
    head = 2.0 * d * cfg.vocab
    return cfg.n_layers * (proj + attn + mlp) + head


# Peak bf16 FLOP/s of one chip, keyed by the exact ``device_kind`` string
# JAX reports for it. One row per chip this repo has actually run on — the
# string is what ``chip_smoke.py`` printed there — with the source of the
# number. A device that is not in the table is an error, not a default.
CHIP_PEAK_FLOPS = {
    # TPU v5e. Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per
    # chip (16 GB HBM at 819 GB/s).
    "TPU v5 lite": 197e12,
}


def chip_peak_flops() -> float:
    """Published bf16 peak of the attached chip; an unknown kind raises."""
    import jax

    kind = jax.devices()[0].device_kind
    try:
        return CHIP_PEAK_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {kind!r}: add it to "
            "models/flops.py::CHIP_PEAK_FLOPS with its source"
        ) from None


def mfu(total_flops: float, seconds: float, *, peak: float | None = None) -> float:
    """Model FLOPs utilization: achieved FLOPs/s over chip peak."""
    if seconds <= 0:
        return 0.0
    return (total_flops / seconds) / (peak or chip_peak_flops())
