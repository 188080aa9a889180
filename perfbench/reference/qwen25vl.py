"""Plain reference of the whole Qwen2.5-VL model: the windowed vision tower and
the decoder with three-component m-rope, after the published description
(Qwen2.5-VL technical report, sec. 2.1; ``modeling_qwen2_5_vl.py`` and
``image_processing_qwen2_vl.py`` of transformers). float32 throughout,
``jax.numpy`` only, matmuls at ``highest`` precision; no kernels, no cache, no
batching, no sharding. One temporal slice of the video, or one whole prompt, at
a time. It shares the parameter tree's names with the program
(``params["params"]["vision"]["block_<i>"]`` ...) and none of its code.

Vision tower, for a video of N frames at H x W pixels (p = patch 14, tp =
temporal patch 2, m = merge 2, window 112 px, so v = 112 / m / p = 4 merge
units a window side):

    x = (frame / 255 - mean) / std             CLIP's mean and std, per channel
    frames padded to a multiple of tp by repeating the last (the processor)
    slice s = frames [tp s, tp s + tp); patch (r, c) of it is the pixels
        [tp, p, p, C] at rows p r .., columns p c ..
    e[s, r, c] = sum_{ch, dt, dy, dx} x[tp s + dt, p r + dy, p c + dx, ch] W[ch, dt, dy, dx, :]
        (a Conv3d whose kernel equals its stride, no bias)
    rotary angles of patch (r, c), d = head_dim / 2, f_j = 10000^(-2j/d), j < d/2:
        a = [r f_0 .. r f_{d/2-1}, c f_0 .. c f_{d/2-1}]   (d numbers)
        q, k <- q cos([a, a]) + rotate_half(q) sin([a, a])
    window of patch (r, c): ((r // m) // v, (c // m) // v): merge units of m x m
        patches grouped v x v, a partial window where the grid's edge cuts one
        (``get_window_index`` pads the unit grid with -100 and drops the pads:
        the same sets)
    for block i of `depth`:
        n = rmsnorm(h) ; q, k, v = split(n Wqkv + b) as [3, heads, head_dim]
        tokens attend inside their own window of their own slice, but in the
        blocks of `fullatt_block_indexes` over their whole slice
        (``cu_window_seqlens`` / ``cu_seqlens``); never across slices
        h = h + softmax(q k^T / sqrt(head_dim)) v Wproj + b
        n = rmsnorm(h) ; h = h + (silu(n Wgate + b) * (n Wup + b)) Wdown + b
    merger: g = rmsnorm(h) ; for every merge unit (R, C):
        u = [g(mR, mC), g(mR, mC+1), g(mR+1, mC), g(mR+1, mC+1)]   (m^2 E numbers)
        out[s, R, C] = gelu(u W1 + b1) W2 + b2           (gelu exact, erf)
    outputs in time-major, row-major order (s, R, C): what ``get_rope_index``
    numbers. The published code permutes tokens window-major before the blocks
    and back after the merger; here nothing is permuted, the window is a
    token's property, which gives each token the same set to attend to.

Decoder, for the sequence [text before][vision tokens][text after]:

    positions (``get_rope_index``): a text token has (t, h, w) all equal,
        counting on from the largest position so far plus one; vision token
        (s, R, C) of a block that starts at position o has
        (o + floor(s * t_scale), o + R, o + C), t_scale = second_per_grid_t *
        tokens_per_second; the text after starts at the largest of these + 1
    h_0 = E[ids] for text, the tower's outputs for vision tokens
    for every layer: as in ``qwen2_decoder.py``'s header, but the rotary angle
        of rotate-half pair j (of head_dim / 2) is p_c(j) * theta^(-2j/head_dim)
        with c(j) = t for the first 16 pairs, h for the next 24, w for the last
        24 (``mrope_section``; ``apply_multimodal_rotary_pos_emb`` splits the
        doubled table into 16/24/24/16/24/24, which is the same assignment);
        the causal mask is over sequence order, not over positions
    logits = rmsnorm(h) Whead at the positions asked for (untied head), or
        rmsnorm(h) E^T (tied)

Decoding is compared through one full forward pass over the prompt and the
tokens the engine emitted: ``logits_at`` returns the logits at a list of
sequence positions.

Departures from the published description, all of them the program's stated
assumptions (``perfbench/configs/qwen25vl-7b-tp4.json``, ``assumed``), none a
change of an equation:
- frames arrive at the tower's resolution (224 px there) and are not resized:
  the processor's ``smart_resize`` is not part of the model, and the reference
  refuses a frame whose sides are not multiples of m p;
- ``t_scale`` is an argument. The published default for a video with no
  ``second_per_grid_ts`` is 1.0 * tokens_per_second (2 for the 7B); the program
  uses 1.0 when a request carries no frame rate, and tokens_per_second * tp /
  fps when it does. The comparison hands the reference the program's value;
- the values of ``rope_theta`` and of the norms' epsilon are read from the
  configuration, the vision rotary base 10000 and epsilon 1e-6 are the
  published constants.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CLIP_MEAN = (0.48145466, 0.4578275, 0.40821073)
CLIP_STD = (0.26862954, 0.26130258, 0.27577711)
VISION_ROPE_BASE = 10000.0
VISION_EPS = 1e-6


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _linear(x, p):
    y = x @ p["kernel"].astype(jnp.float32)
    return y + p["bias"].astype(jnp.float32) if "bias" in p else y


def _rotate_half(x):
    d = x.shape[-1]
    return jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)


# -- the vision tower ---------------------------------------------------------


def normalise(frames_u8, *, temporal_patch):
    """uint8 [N, H, W, C] -> float32, CLIP-normalised, N padded to a multiple
    of ``temporal_patch`` by repeating the last frame."""
    x = (jnp.asarray(frames_u8, jnp.float32) / 255.0 - jnp.asarray(CLIP_MEAN)) / jnp.asarray(CLIP_STD)
    pad = -x.shape[0] % temporal_patch
    return jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)], axis=0) if pad else x


def window_of_patch(rows: int, cols: int, *, merge: int, patch: int, window_px: int) -> np.ndarray:
    """[rows * cols] window number of every patch, row-major."""
    v = window_px // merge // patch
    r, c = np.divmod(np.arange(rows * cols), cols)
    n_across = -(-(cols // merge) // v)
    return ((r // merge) // v) * n_across + (c // merge) // v


def patch_angles(rows: int, cols: int, head_dim: int) -> np.ndarray:
    """[rows * cols, head_dim] rotary angles, row-major, already doubled."""
    d = head_dim // 2
    f = VISION_ROPE_BASE ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    r, c = np.divmod(np.arange(rows * cols), cols)
    a = np.concatenate([r[:, None] * f[None, :], c[:, None] * f[None, :]], axis=-1)
    return np.concatenate([a, a], axis=-1).astype(np.float32)


def embed_patches(pixels, kernel, *, patch, temporal_patch):
    """One temporal slice [tp, H, W, C] -> patch tokens [rows * cols, E]."""
    with jax.default_matmul_precision("highest"):
        tp, height, width, ch = pixels.shape
        rows, cols = height // patch, width // patch
        w = kernel.astype(jnp.float32).reshape(ch, tp, patch, patch, -1)
        x = pixels.reshape(tp, rows, patch, cols, patch, ch)
        return jnp.einsum("trycxh,htyxe->rce", x, w).reshape(rows * cols, -1)


def vision_block(h, bp, angles, same_set, *, num_heads):
    """One block on one temporal slice, [S, E] -> [S, E]. ``same_set`` [S, S]
    says which tokens a token attends to."""
    with jax.default_matmul_precision("highest"):
        s, e = h.shape
        dh = e // num_heads
        cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
        n = _rmsnorm(h, bp["ln1"]["scale"], VISION_EPS)
        q, k, v = jnp.moveaxis(_linear(n, bp["qkv"]).reshape(s, 3, num_heads, dh), 1, 0)
        q = q * cos + _rotate_half(q) * sin
        k = k * cos + _rotate_half(k) * sin
        a = jnp.einsum("qhd,khd->hqk", q, k) * dh**-0.5
        a = jnp.where(same_set[None], a, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(a, axis=-1), v).reshape(s, e)
        h = h + _linear(o, bp["proj"])
        n = _rmsnorm(h, bp["ln2"]["scale"], VISION_EPS)
        return h + _linear(jax.nn.silu(_linear(n, bp["gate"])) * _linear(n, bp["up"]), bp["down"])


def merge_patches(h, ln_q, fc1, fc2, *, rows, cols, merge):
    """[rows * cols, E] -> [rows/m * cols/m, out], merge units row-major."""
    with jax.default_matmul_precision("highest"):
        g = _rmsnorm(h, ln_q["scale"], VISION_EPS).reshape(rows // merge, merge, cols // merge, merge, -1)
        u = g.transpose(0, 2, 1, 3, 4).reshape((rows // merge) * (cols // merge), -1)
        return _linear(jax.nn.gelu(_linear(u, fc1), approximate=False), fc2)


def vision_tower(params, frames_u8, *, depth, num_heads, patch, temporal_patch, merge,
                 window_px, fullatt_blocks, place=lambda tree: tree):
    """uint8 frames [N, H, W, C] -> [slices * rows/m * cols/m, out] LM
    embeddings in (slice, row, column) order. A block's parameters are placed
    once and used for every slice; a slice is computed on its own."""
    vp = params["params"]["vision"]
    x = normalise(frames_u8, temporal_patch=temporal_patch)
    n, height, width, _ = x.shape
    if height % (patch * merge) or width % (patch * merge):
        raise ValueError(f"frames of {height} x {width} px are no whole number of merge units")
    rows, cols = height // patch, width // patch
    embed = jax.jit(functools.partial(embed_patches, patch=patch, temporal_patch=temporal_patch))
    kernel = place(vp["patch_embed"]["kernel"])
    slices = [embed(x[i : i + temporal_patch], kernel) for i in range(0, n, temporal_patch)]
    angles = jnp.asarray(patch_angles(rows, cols, kernel.shape[-1] // num_heads))
    window = window_of_patch(rows, cols, merge=merge, patch=patch, window_px=window_px)
    in_window = jnp.asarray(window[:, None] == window[None, :])
    whole_slice = jnp.ones_like(in_window)
    block = jax.jit(functools.partial(vision_block, num_heads=num_heads))
    for i in range(depth):
        bp = place(vp[f"block_{i}"])
        same_set = whole_slice if i in fullatt_blocks else in_window
        slices = [block(h, bp, angles, same_set) for h in slices]
    merger = jax.jit(functools.partial(merge_patches, rows=rows, cols=cols, merge=merge))
    tail = [place(vp[name]) for name in ("ln_q", "merger_fc1", "merger_fc2")]
    return jnp.concatenate([merger(h, *tail) for h in slices], axis=0)


# -- positions ----------------------------------------------------------------


def mrope_positions(n_before: int, merged_grid, n_after: int, t_scale: float = 1.0) -> np.ndarray:
    """[T, 3] (t, h, w) positions of [text before][vision][text after].
    ``merged_grid`` is (slices, rows / m, columns / m) or None."""
    out = [np.repeat(np.arange(n_before)[:, None], 3, axis=1)]
    nxt = n_before
    if merged_grid is not None:
        s, rows, cols = merged_grid
        idx = np.arange(s * rows * cols)
        t = np.floor((idx // (rows * cols)) * t_scale).astype(np.int64)
        vis = nxt + np.stack([t, (idx // cols) % rows, idx % cols], axis=1)
        out.append(vis)
        nxt = int(vis.max()) + 1
    out.append(nxt + np.repeat(np.arange(n_after)[:, None], 3, axis=1))
    return np.concatenate(out, axis=0).astype(np.int32)


def continue_positions(positions: np.ndarray, n_more: int) -> np.ndarray:
    """``positions`` followed by ``n_more`` text tokens (decoded ones)."""
    nxt = int(positions.max()) + 1 if len(positions) else 0
    more = nxt + np.repeat(np.arange(n_more)[:, None], 3, axis=1)
    return np.concatenate([positions, more.astype(np.int32)], axis=0)


# -- the decoder --------------------------------------------------------------


def _mrope(x, positions, theta, sections):
    """x: [T, H, D]; positions: [T, 3]; pair j turns by the component that
    ``sections`` gives it."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # [D/2]
    component = np.repeat(np.arange(3), np.asarray(sections))  # [D/2]
    ang = positions.astype(jnp.float32)[:, component] * inv[None, :]  # [T, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def embed(table, ids):
    return table.astype(jnp.float32)[ids]


def decoder_layer(h, lp, positions, *, n_heads, n_kv_heads, head_dim, rope_theta, rms_eps, sections):
    """One decoder layer on the whole sequence, [T, dim] -> [T, dim]."""
    with jax.default_matmul_precision("highest"):
        t = h.shape[0]
        group = n_heads // n_kv_heads
        a = _rmsnorm(h, lp["ln1"]["scale"], rms_eps)
        q = _mrope(_linear(a, lp["q"]).reshape(t, n_heads, head_dim), positions, rope_theta, sections)
        k = _mrope(_linear(a, lp["k"]).reshape(t, n_kv_heads, head_dim), positions, rope_theta, sections)
        v = _linear(a, lp["v"]).reshape(t, n_kv_heads, head_dim)
        k = jnp.repeat(k, group, axis=1)  # KV head j serves query heads j*G .. j*G+G-1
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * head_dim**-0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        h = h + _linear(o.reshape(t, n_heads * head_dim), lp["o"])
        n = _rmsnorm(h, lp["ln2"]["scale"], rms_eps)
        return h + _linear(jax.nn.silu(_linear(n, lp["gate"])) * _linear(n, lp["up"]), lp["down"])


def head(h_rows, scale, matrix, *, rms_eps, tied_embeddings):
    """Logits [R, vocab] of R positions: tied table [vocab, dim] or untied head [dim, vocab]."""
    with jax.default_matmul_precision("highest"):
        rows = _rmsnorm(h_rows, scale, rms_eps)
        matrix = matrix.astype(jnp.float32)
        return rows @ (matrix.T if tied_embeddings else matrix)


def logits_at(params, before_ids, vision, after_ids, positions, at, *, n_layers, n_heads,
              n_kv_heads, head_dim, rope_theta, rms_eps, sections, tied_embeddings,
              place=lambda tree: tree):
    """Logits [len(at), vocab] at the sequence positions ``at`` of one full
    forward pass over [before_ids][vision embeddings or None][after_ids] with
    the (t, h, w) ``positions`` [T, 3]. ``place`` is applied to each piece's
    parameters just before use (a layer at a time onto one chip)."""
    p = params["params"]
    table = place(p["embed"]["embedding"])
    lookup = jax.jit(embed)
    parts = [lookup(table, jnp.asarray(before_ids, jnp.int32))] if len(before_ids) else []
    if vision is not None:
        parts.append(vision)
    if len(after_ids):
        parts.append(lookup(table, jnp.asarray(after_ids, jnp.int32)))
    h = jnp.concatenate(parts, axis=0)
    if len(positions) != h.shape[0]:
        raise ValueError(f"{len(positions)} positions for {h.shape[0]} tokens")
    if not tied_embeddings:
        del table
    layer = jax.jit(functools.partial(
        decoder_layer, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        rope_theta=rope_theta, rms_eps=rms_eps, sections=tuple(sections),
    ))
    positions = jnp.asarray(positions)
    for i in range(n_layers):
        h = layer(h, place(p[f"layer_{i}"]), positions)
    matrix = table if tied_embeddings else place(p["lm_head"]["kernel"])
    return jax.jit(functools.partial(head, rms_eps=rms_eps, tied_embeddings=tied_embeddings))(
        h[jnp.asarray(at)], place(p["ln_f"]["scale"]), matrix
    )


# -- sizes from the program's configuration -----------------------------------


def decoder_kwargs(cfg) -> dict:
    return dict(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
        sections=tuple(cfg.mrope_section), tied_embeddings=cfg.tied_embeddings,
    )


def vision_kwargs(cfg) -> dict:
    qv = cfg.qwen_vision
    return dict(
        depth=qv.depth, num_heads=qv.num_heads, patch=qv.patch_size,
        temporal_patch=qv.temporal_patch_size, merge=qv.spatial_merge_size,
        window_px=qv.window_size, fullatt_blocks=tuple(qv.fullatt_block_indexes),
    )
