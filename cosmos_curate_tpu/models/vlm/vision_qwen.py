"""Qwen2-VL vision tower in Flax — 3D-conv patchify, 2D rope, patch merger.

Equivalent capability of the vision encoder the reference serves through
vLLM for its Qwen-VL captioners (cosmos_curate/models/vllm_qwen.py:122-260;
HF `Qwen2VisionTransformerPretrainedModel`): tensor-for-tensor the same
architecture, so `convert_qwen.convert_qwen2_vision` can load a real
Qwen2-VL checkpoint's ``visual.*`` weights and multimodal captions see the
trained tower, not a random-init stand-in.

TPU-first differences from the HF implementation (behavior-preserving):

- **Static shapes.** HF flattens all images of a request into one ragged
  sequence partitioned by ``cu_seqlens``; here a batch is a dense
  ``[B, S, patch_dim]`` array with one static ``(t, h, w)`` grid per
  compiled program (the caption engine buckets by shape anyway), and the
  ``cu_seqlens`` segments (temporal slices, Qwen2.5-VL's windows) are
  static runs of that grid: attention is one batched matmul over
  ``[B, segments, L]``, never over the whole ``S x S``.
- **Patchify as a matmul.** The Conv3d with kernel == stride over
  pre-extracted patches is exactly a dense layer on the flattened patch
  vector — one ``[B*S, patch_dim] @ [patch_dim, embed]`` MXU call.
- The 2D rotary tables and the merge-window patch ordering are computed
  host-side once per grid (static) and closed over by the jitted program.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from cosmos_curate_tpu.models.layers import dense, quick_gelu


@dataclass(frozen=True)
class QwenVisionConfig:
    depth: int = 32
    embed_dim: int = 1280
    num_heads: int = 16
    hidden_size: int = 1536  # LM dim the merger projects into
    mlp_ratio: float = 4.0
    patch_size: int = 14
    temporal_patch_size: int = 2
    spatial_merge_size: int = 2
    in_channels: int = 3
    image_size: int = 224  # our fixed inference resolution
    # "qwen2" = LayerNorm blocks + quick_gelu MLP, full per-frame attention;
    # "qwen2_5" = RMSNorm blocks + SwiGLU MLP, windowed attention with
    # full-attention blocks at fullatt_block_indexes (also CosmosReason's
    # vision architecture)
    variant: str = "qwen2"
    intermediate_size: int | None = None  # qwen2_5 sets this explicitly
    window_size: int = 112  # pixels; qwen2_5 only
    fullatt_block_indexes: tuple[int, ...] = ()
    # Qwen2.5-VL scales the temporal m-rope component to absolute time:
    # t_index = floor(grid_t_idx * second_per_grid_t * tokens_per_second)
    # (HF get_rope_index). None = unscaled (Qwen2-VL behavior).
    tokens_per_second: float | None = None
    # "qwen3" (deepstack) only: side length of the learned pos-embed grid
    # (HF num_position_embeddings = side²), bilinearly interpolated to the
    # actual patch grid; and the block indexes whose hidden states feed
    # the deepstack mergers (injected into the LM's first layers).
    pos_embed_side: int = 0
    deepstack_indexes: tuple[int, ...] = ()

    @property
    def mlp_hidden(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        return int(self.embed_dim * self.mlp_ratio)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def patch_dim(self) -> int:
        return self.in_channels * self.temporal_patch_size * self.patch_size**2

    def grid(self, n_frames: int) -> tuple[int, int, int]:
        """Static (t, h, w) patch grid for n_frames at image_size."""
        t = -(-n_frames // self.temporal_patch_size)
        hw = self.image_size // self.patch_size
        return t, hw, hw

    def tokens_out(self, n_frames: int) -> int:
        t, h, w = self.grid(n_frames)
        return t * h * w // self.spatial_merge_size**2

    def merged_grid(self, n_frames: int) -> tuple[int, int, int]:
        """Grid of MERGED tokens (what the LM sees; m-rope position space)."""
        t, h, w = self.grid(n_frames)
        m = self.spatial_merge_size
        return t, h // m, w // m


# Qwen2-VL-2B-Instruct's visual config (depth 32 / 1280 / 16 heads,
# merger → 1536). hidden_size must match the LM dim.
QWEN2_VL_2B_VISION = QwenVisionConfig()
# Qwen2.5-VL-7B-Instruct's visual config (windowed attention; also the
# CosmosReason family's tower): depth 32 / 1280 / 16 heads, SwiGLU 3420,
# window 112px, full attention at blocks 7/15/23/31, merger → 3584.
QWEN25_VL_7B_VISION = QwenVisionConfig(
    depth=32,
    embed_dim=1280,
    num_heads=16,
    hidden_size=3584,
    intermediate_size=3420,
    variant="qwen2_5",
    window_size=112,
    fullatt_block_indexes=(7, 15, 23, 31),
    tokens_per_second=2.0,  # HF Qwen2.5-VL vision_config.tokens_per_second
)
# Qwen3-VL(-MoE) deepstack vision tower (SigLIP-shaped: 27 deep / 1152 /
# 16 heads / gelu-tanh MLP 4304), learned 48x48 pos-embed grid, deepstack
# taps at blocks 8/16/24; merger projects into the LM dim per checkpoint.
QWEN3_VL_MOE_VISION = QwenVisionConfig(
    depth=27,
    embed_dim=1152,
    num_heads=16,
    hidden_size=2048,  # 30B-A3B text hidden; conversion derives from config
    intermediate_size=4304,
    patch_size=16,
    variant="qwen3",
    pos_embed_side=48,
    deepstack_indexes=(8, 16, 24),
)
QWEN3_VISION_TINY_TEST = QwenVisionConfig(
    depth=3,
    embed_dim=32,
    num_heads=4,
    hidden_size=64,
    intermediate_size=64,
    patch_size=8,
    image_size=32,
    variant="qwen3",
    pos_embed_side=4,
    deepstack_indexes=(0, 1),
)
QWEN_VISION_TINY_TEST = QwenVisionConfig(
    depth=2,
    embed_dim=64,
    num_heads=4,
    hidden_size=64,
    mlp_ratio=2.0,
    patch_size=8,
    image_size=32,
)


# Qwen2.5-VL's tower at test size: RMSNorm blocks, SwiGLU, 16 px windows of
# 2 x 2 merge units over a 5 x 5 unit grid (so the last window of a row and
# of a column is cut), one full-attention block between two windowed ones.
QWEN25_VISION_TINY_TEST = QwenVisionConfig(
    depth=3,
    embed_dim=64,
    num_heads=4,
    hidden_size=64,
    intermediate_size=96,
    patch_size=4,
    image_size=40,
    variant="qwen2_5",
    window_size=16,
    fullatt_block_indexes=(1,),
    tokens_per_second=2.0,
)


def pos_embed_interp_matrix(cfg: QwenVisionConfig, grid: tuple[int, int, int]) -> np.ndarray:
    """Host-side [h*w, side²] bilinear interpolation matrix mapping the
    learned pos-embed table onto ONE temporal slice of the (t, h, w) patch
    grid in merge-window order (HF ``fast_pos_embed_interpolate``
    semantics: linspace over the side, 4-neighbor weights, merge
    permutation; the caller broadcasts the interpolated product over t —
    tiling the matrix itself would bake a t× larger constant into the
    jitted program)."""
    _t, h, w = grid
    side = cfg.pos_embed_side
    msz = cfg.spatial_merge_size
    h_idx = np.linspace(0, side - 1, h)
    w_idx = np.linspace(0, side - 1, w)
    h0 = h_idx.astype(np.int64)
    w0 = w_idx.astype(np.int64)
    h1 = np.clip(h0 + 1, None, side - 1)
    w1 = np.clip(w0 + 1, None, side - 1)
    dh = (h_idx - h0)[:, None]
    dw = (w_idx - w0)[None, :]
    mat = np.zeros((h * w, side * side), np.float32)
    rows = np.arange(h * w).reshape(h, w)
    for hi, wi, wgt in (
        (h0, w0, (1 - dh) * (1 - dw)),
        (h0, w1, (1 - dh) * dw),
        (h1, w0, dh * (1 - dw)),
        (h1, w1, dh * dw),
    ):
        cols = hi[:, None] * side + wi[None, :]
        # accumulate: clipped edge neighbors can collide on the same cell
        np.add.at(mat, (rows.reshape(-1), cols.reshape(-1)), wgt.reshape(-1))
    perm = (
        np.arange(h * w)
        .reshape(h // msz, msz, w // msz, msz)
        .transpose(0, 2, 1, 3)
        .reshape(-1)
    )
    return mat[perm]  # [h*w, side²] in merge-window order


def rotary_tables(cfg: QwenVisionConfig, grid: tuple[int, int, int]) -> np.ndarray:
    """Host-side [S, head_dim] rope angles in merge-window patch order.

    Matches HF ``rot_pos_emb`` (modeling_qwen2_vl.py): h/w position ids are
    permuted so each spatial_merge_size² window is contiguous, each position
    indexes a 1D table of ``outer(pos, inv_freq(head_dim//2))``, the (h, w)
    halves concatenate to head_dim//2, then the whole thing doubles for
    rotate-half cos/sin.
    """
    t, h, w = grid
    msz = cfg.spatial_merge_size
    hpos = np.arange(h)[:, None].repeat(w, axis=1)
    wpos = np.arange(w)[None, :].repeat(h, axis=0)

    def merge_order(pos):
        return (
            pos.reshape(h // msz, msz, w // msz, msz).transpose(0, 2, 1, 3).reshape(-1)
        )

    hpos, wpos = merge_order(hpos), merge_order(wpos)  # [h*w]
    dim = cfg.head_dim // 2
    inv_freq = 1.0 / (10000.0 ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    table = np.arange(max(h, w), dtype=np.float64)[:, None] * inv_freq[None, :]
    angles = np.concatenate([table[hpos], table[wpos]], axis=-1)  # [h*w, dim]
    angles = np.tile(angles, (t, 1))  # temporal repeat: same 2D pos every t
    return np.concatenate([angles, angles], axis=-1).astype(np.float32)  # [S, head_dim]


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def window_partition(cfg: QwenVisionConfig, grid: tuple[int, int, int]):
    """Host-side window permutation for the qwen2_5 variant.

    HF ``get_window_index`` semantics for one static grid: merge units
    (spatial_merge_size² consecutive tokens) are regrouped into
    window-major order; returns (token_perm [S], window segment id per
    permuted token [S]) — static arrays the jitted program closes over.
    Frame (t) boundaries are preserved by the permutation, so the
    full-attention blocks' segments (one per temporal slice) are unchanged.
    """
    t, h, w = grid
    msz = cfg.spatial_merge_size
    unit = msz * msz
    lh, lw = h // msz, w // msz
    vws = max(1, cfg.window_size // msz // cfg.patch_size)
    index = np.arange(t * lh * lw).reshape(t, lh, lw)
    pad_h = (-lh) % vws
    pad_w = (-lw) % vws
    nh, nw = (lh + pad_h) // vws, (lw + pad_w) // vws
    padded = np.full((t, lh + pad_h, lw + pad_w), -100, dtype=np.int64)
    padded[:, :lh, :lw] = index
    padded = (
        padded.reshape(t, nh, vws, nw, vws)
        .transpose(0, 1, 3, 2, 4)
        .reshape(t, nh * nw, vws, vws)
    )
    seqlens = (padded != -100).sum(axis=(2, 3)).reshape(-1)  # merge units/window
    flat = padded.reshape(-1)
    unit_perm = flat[flat != -100]  # [S/unit] merge-unit permutation
    token_perm = (unit_perm[:, None] * unit + np.arange(unit)).reshape(-1)
    # window segment id per permuted TOKEN (empty windows contribute none)
    seg = np.repeat(np.arange(len(seqlens)), seqlens * unit)
    return token_perm.astype(np.int64), seg.astype(np.int64), unit_perm.astype(np.int64)


class _VisionRMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (scale * normed).astype(x.dtype)


def _segment_attention(q, k, v, seg_lens: np.ndarray):
    """Softmax attention inside each of the consecutive token runs that
    ``seg_lens`` (static) measures out: q, k, v ``[B, S, H, Dh]`` ->
    ``[B, S, H * Dh]``. Float32 logits and softmax, probabilities in v's
    type, as HF computes a ``cu_seqlens`` segment.

    Runs of one length are a reshape to ``[B, n_seg, L, H, Dh]``. Runs of
    several lengths (windows the grid's edge cuts) are gathered into
    ``n_seg`` rows of the longest, the slots past a run's end masked as
    keys and dropped as queries.
    """
    b, _, h, dh = q.shape
    n_seg, longest = len(seg_lens), int(seg_lens.max())
    live = np.arange(longest)[None, :] < seg_lens[:, None]  # [n_seg, L]
    ragged = not live.all()
    if ragged:
        # a slot past its run's end reads the run's last token: any token
        # would do, its logits are masked and its output row is dropped
        starts = np.cumsum(seg_lens) - seg_lens
        offsets = np.minimum(np.arange(longest), seg_lens[:, None] - 1)
        slots = (starts[:, None] + offsets).reshape(-1)
        q, k, v = q[:, slots], k[:, slots], v[:, slots]
    q, k, v = (a.reshape(b, n_seg, longest, h, dh) for a in (q, k, v))
    logits = jnp.einsum(
        "bnqhd,bnkhd->bnhqk", q.astype(jnp.float32) * dh**-0.5, k.astype(jnp.float32)
    )
    if ragged:
        logits = jnp.where(live[None, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    attn = jnp.einsum("bnhqk,bnkhd->bnqhd", probs.astype(v.dtype), v)
    attn = attn.reshape(b, n_seg * longest, h * dh)
    if ragged:
        attn = attn[:, np.flatnonzero(live)]
    return attn


class QwenVisionBlock(nn.Module):
    cfg: QwenVisionConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32  # see VLM.param_dtype

    def _norm(self, name: str):
        if self.cfg.variant == "qwen2_5":
            return _VisionRMSNorm(name=name)
        return nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, name=name)

    @nn.compact
    def __call__(self, x, cos, sin, seg_lens):
        """x: [B, S, E]; cos/sin: [S, head_dim] rope tables; seg_lens:
        static int array, the lengths of the consecutive runs of tokens
        that attend to one another. HF splits attention at cu_seqlens
        boundaries (per temporal slice, or per window for qwen2_5's
        windowed blocks); for our static grid those are these runs, and
        ``np.cumsum(seg_lens)`` is HF's ``cu_seqlens``."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, dh = cfg.num_heads, cfg.head_dim
        # every projection here computes in ``dtype`` and stores ``param_dtype``
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)

        y = self._norm("ln1")(x)
        # fused qkv (one MXU matmul), as in the checkpoint layout
        qkv = proj(3 * cfg.embed_dim, "out", name="qkv")(y)
        q, k, v = jnp.split(qkv.reshape(b, s, 3, h, dh), 3, axis=2)
        q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]  # [B, S, H, Dh]
        cos_ = cos[None, :, None, :]
        sin_ = sin[None, :, None, :]
        qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        q = (qf * cos_ + _rotate_half(qf) * sin_).astype(self.dtype)
        k = (kf * cos_ + _rotate_half(kf) * sin_).astype(self.dtype)

        with jax.named_scope("vision.attention"):
            attn = _segment_attention(q, k, v, seg_lens)
        x = x + proj(cfg.embed_dim, "in", name="proj")(attn)

        y = self._norm("ln2")(x)
        hdim = cfg.mlp_hidden
        if cfg.variant == "qwen2_5":  # SwiGLU (with biases, HF Qwen2_5_VLMLP)
            gate = proj(hdim, "out", name="gate")(y)
            up = proj(hdim, "out", name="up")(y)
            y = nn.silu(gate) * up
            return x + proj(cfg.embed_dim, "in", name="down")(y)
        y = proj(hdim, "out", name="fc1")(y)
        if cfg.variant == "qwen3":  # HF hidden_act gelu_pytorch_tanh
            y = nn.gelu(y, approximate=True)
        else:
            y = quick_gelu(y)
        return x + proj(cfg.embed_dim, "in", name="fc2")(y)


class QwenVisionTower(nn.Module):
    """[B, S, patch_dim] pixel patches -> [B, S/merge², hidden_size].

    Every block is handed the segments it attends inside, as static run
    lengths read off the grid (temporal slices; for qwen2_5's windowed
    blocks ``window_partition``'s windows): HF's ``cu_seqlens`` semantics,
    with no ``[S, S]`` mask anywhere in the program."""

    cfg: QwenVisionConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32  # see VLM.param_dtype

    @nn.compact
    def __call__(self, patches, grid: tuple[int, int, int]):
        cfg = self.cfg
        b, s, _ = patches.shape
        # every projection here computes in ``dtype`` and stores ``param_dtype``
        proj = partial(dense, dtype=self.dtype, param_dtype=self.param_dtype)
        assert s == grid[0] * grid[1] * grid[2], (s, grid)
        x = proj(
            cfg.embed_dim,
            None,
            name="patch_embed",
            use_bias=cfg.variant == "qwen3",  # Qwen3's Conv3d carries a bias
        )(patches.astype(self.dtype))
        if cfg.variant == "qwen3":
            # learned pos-embed table, bilinearly interpolated to the grid
            # (host-precomputed static matrix; HF fast_pos_embed_interpolate)
            table = self.param(
                "pos_embed",
                nn.initializers.normal(0.02),
                (cfg.pos_embed_side**2, cfg.embed_dim),
                jnp.float32,
            )
            interp = jnp.asarray(pos_embed_interp_matrix(cfg, grid))
            pos = jnp.tile(interp @ table, (grid[0], 1))  # temporal repeat
            x = (x.astype(jnp.float32) + pos).astype(self.dtype)
        angles = rotary_tables(cfg, grid)
        # HF cu_seqlens semantics: full attention inside a temporal slice
        full_lens = np.full(grid[0], grid[1] * grid[2])
        window_lens = None
        inverse_unit_perm = None
        if cfg.variant == "qwen2_5":
            # static window permutation: tokens regroup window-major; all
            # blocks except fullatt_block_indexes attend within windows
            token_perm, seg, unit_perm = window_partition(cfg, grid)
            x = x[:, token_perm]
            angles = angles[token_perm]
            window_lens = np.unique(seg, return_counts=True)[1]  # seg never decreases
            inverse_unit_perm = np.argsort(unit_perm)
        cos, sin = jnp.cos(jnp.asarray(angles)), jnp.sin(jnp.asarray(angles))
        msz2 = cfg.spatial_merge_size**2
        deepstack = []
        for i in range(cfg.depth):
            windowed = cfg.variant == "qwen2_5" and i not in cfg.fullatt_block_indexes
            x = QwenVisionBlock(
                cfg, dtype=self.dtype, param_dtype=self.param_dtype, name=f"block_{i}"
            )(x, cos, sin, window_lens if windowed else full_lens)
            if cfg.variant == "qwen3" and i in cfg.deepstack_indexes:
                # deepstack merger (postshuffle norm): merge-window group
                # FIRST, LayerNorm over the grouped features, then the MLP
                level = cfg.deepstack_indexes.index(i)
                d = x.reshape(b, s // msz2, msz2 * cfg.embed_dim)
                d = nn.LayerNorm(
                    epsilon=1e-6, dtype=jnp.float32, name=f"ds{level}_norm"
                )(d)
                d = proj(msz2 * cfg.embed_dim, "out", name=f"ds{level}_fc1")(d)
                d = nn.gelu(d, approximate=False)
                d = proj(cfg.hidden_size, "in", name=f"ds{level}_fc2")(d)
                deepstack.append(d)
        # merger: group each merge-window's msz² consecutive tokens
        if cfg.variant == "qwen2_5":
            x = _VisionRMSNorm(name="ln_q")(x)
        else:
            x = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, name="ln_q")(x)
        x = x.reshape(b, s // msz2, msz2 * cfg.embed_dim)
        x = proj(msz2 * cfg.embed_dim, "out", name="merger_fc1")(x)
        x = nn.gelu(x, approximate=False)
        x = proj(cfg.hidden_size, "in", name="merger_fc2")(x)
        if inverse_unit_perm is not None:
            # undo the window permutation so outputs are t-major row-major
            # (what build_mrope_positions and the engine assume)
            x = x[:, inverse_unit_perm]
        if cfg.variant == "qwen3":
            return x, jnp.stack(deepstack) if deepstack else jnp.zeros((0, *x.shape))
        return x


def frames_to_patches(frames_u8, cfg: QwenVisionConfig):
    """uint8 [B, N, H, W, 3] -> ([B, S, patch_dim], grid), HF processor order.

    Device-side equivalent of Qwen2VLImageProcessor._preprocess: CLIP
    mean/std normalization at image_size, last frame repeated to a multiple
    of temporal_patch_size, then the
    (t, tps, C, h/m, m, ps, w/m, m, ps) → (t, h/m, w/m, m, m, C, tps, ps, ps)
    transpose that puts each merge window's patches contiguous.
    """
    from cosmos_curate_tpu.models.vit import preprocess_frames

    b, n = frames_u8.shape[:2]
    tps, ps, msz = cfg.temporal_patch_size, cfg.patch_size, cfg.spatial_merge_size
    x = preprocess_frames(frames_u8, image_size=cfg.image_size, mode="clip")
    if n % tps:
        pad = tps - n % tps
        x = jnp.concatenate([x, jnp.repeat(x[:, -1:], pad, axis=1)], axis=1)
        n += pad
    t, gh, gw = cfg.grid(n)
    # [B, N, H, W, C] -> channel-first patch blocks
    x = x.transpose(0, 1, 4, 2, 3)  # [B, N, C, H, W]
    x = x.reshape(b, t, tps, cfg.in_channels, gh // msz, msz, ps, gw // msz, msz, ps)
    x = x.transpose(0, 1, 4, 7, 5, 8, 3, 2, 6, 9)
    patches = x.reshape(b, t * gh * gw, cfg.patch_dim)
    return patches, (t, gh, gw)
