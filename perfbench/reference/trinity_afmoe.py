"""Plain reference of the Trinity decoder (HF ``afmoe``, as
arcee-ai/Trinity-Large-Preview publishes it): float32 throughout, ``jax.numpy``
only, matmuls at ``highest`` precision, dense masks, no cache, no kernels, no
batching; attention computed in blocks of queries so that a 9k-token prompt's
scores fit beside a serving engine.

    x0 = E[ids] * sqrt(d)                                        (mup_enabled)
    layer l of kind t_l in {sliding_attention, full_attention}:
      h  = rmsnorm_in(x)
      q, k, v, g = Wq h, Wk h, Wv h, Wg h                        (g as wide as q: the output gate)
      q, k = rmsnorm_q(q), rmsnorm_k(k)                          (over head_dim, before rope)
      sliding: q, k = rope(q, k, pos)      full: no position embedding at all
      a_i = sum_j softmax_j(q_i . k_j / sqrt(head_dim)) v_j      over j <= i, and for sliding j > i - W
      x  = x + rmsnorm_post_attn( Wo (a * sigmoid(g)) )          (the norm is on the branch)
      h2 = rmsnorm_pre_mlp(x)
      l < first_dense:  m = Wd( silu(Wgate h2) * Wup h2 )
      else:  s = sigmoid(Wr h2) over ALL experts;  sel = top_k(s + b)   (b: the stored bias, choice only)
             w = s[sel] / (sum s[sel] + 1e-20) * route_scale
             m = sum_{e in sel, e held} w_e E_e(h2)  +  S(h2)
      x  = x + rmsnorm_post_mlp(m)
    logits = rmsnorm_f(x) Whead

**The held share.** ``held = (first, count)`` says which experts the tree's
tables ``moe/gate_up [count, D, 2 * width]`` and ``moe/down`` are: the layer adds
the held experts' part of the routed sum (the router still scores all of them;
the absent experts' terms are left out) and the shared expert, whole, where
``with_shared``. Summed over the shares of a deployment, the shared expert
counted once, that is the uncut layer (tests/perfbench/test_trinity_cell.py).

Departures from the published description, each at its line below: (1) four
points are not keys of ``config.json`` and follow HF ``modeling_afmoe.py`` as
the catalog row's ``described_as`` summarises it: the output gate, the q/k
norms before rope, no position embedding on full layers, the second norm on
each branch (the configuration file lists them under ``assumed``); (2) rope in
the halves layout (first half, second half: ``rotate_half``), which is HF's own
for this family: no permutation; (3) float32 throughout, where HF computes in
the checkpoint's bfloat16 and the router in float32; (4) the renormalisation's
``1e-20`` is kept as published although float32 cannot see it.

The parameter tree is the program's own (``params["params"]["layer_<i>"]``...);
only its names are shared with the program, none of its code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256  # queries a block of the attention: [heads, 256, T] float32 scores at a time


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _f32(w):
    """A stored table in float32, AT ITS USE: a sparse layer's tables widened
    at once are 3.6 GB beside the serving engine's 12 (one expert's are 113 MB)."""
    return w.astype(jnp.float32)


def _linear(x, p):
    return x @ _f32(p["kernel"])


def _round(v, mantissa_bits):
    """``v`` rounded to a float with that many bits of mantissa (23: as it is).
    ``reduce_precision`` because XLA elides a convert pair."""
    if mantissa_bits >= 23:
        return v
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=mantissa_bits)


def _rope(x, theta):
    """x: [T, heads, D], halves layout (departure 2); position t is row t."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq  # [T, D / 2]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def keys_and_values(a, lp, *, window, attn, rms_eps):
    """What the cache holds of every position: ``k`` [T, Hkv, D] after its norm
    and (window layers) rope, ``v`` [T, Hkv, D]. ``a``: the layer's normed input."""
    t, hk, d = a.shape[0], attn["n_kv_heads"], attn["head_dim"]
    k = _rmsnorm(_linear(a, lp["k"]).reshape(t, hk, d), lp["k_norm"]["scale"], rms_eps)  # (1) before rope
    if window is not None:  # (1) rope on the window layers only
        k = _rope(k, attn["theta"])
    return k, _linear(a, lp["v"]).reshape(t, hk, d)


def attention(a, lp, *, window, attn, rms_eps):
    """The attention branch before its second norm: ``Wo (a * sigmoid(g))``.
    ``window``: the layer's, or None on a full layer. Block of queries by block
    (``lax.map``), every key under a dense mask."""
    t = a.shape[0]
    h, hk, d = attn["n_heads"], attn["n_kv_heads"], attn["head_dim"]
    q = _rmsnorm(_linear(a, lp["q"]).reshape(t, h, d), lp["q_norm"]["scale"], rms_eps)
    if window is not None:
        q = _rope(q, attn["theta"])
    k, v = keys_and_values(a, lp, window=window, attn=attn, rms_eps=rms_eps)
    pad = -t % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, hk, h // hk, d)
    starts = jnp.arange(q.shape[0]) * QUERY_BLOCK
    key_pos = jnp.arange(t)

    def block(inp):
        qb, start = inp  # [QUERY_BLOCK, Hkv, G, D]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * d**-0.5
        q_pos = start + jnp.arange(QUERY_BLOCK)
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen &= key_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        # (a padded query past the prompt sees what the masks leave it, or nothing
        # at all: its row is cut off below)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q, starts)).reshape(-1, h * d)[:t]
    gate = jax.nn.sigmoid(_linear(a, lp["g"]))  # (1) the output gate, per head and dim
    return _linear(o * gate, lp["o"])


def route(n, mp, *, moe, router_mantissa_bits=23):
    """(weights [T, k], experts [T, k], margin [T]). ``margin`` is how far, as
    a share of the (biased) score, a token's choice of HELD experts is from
    changing: the gap between the last expert taken and the first left out
    where either is held (1 where neither is). A comparison with a program
    that computes in fewer bits means something only where this is wide."""
    e, k = moe["n_experts"], moe["top_k"]
    first, count = moe["held"]
    s = _round(jax.nn.sigmoid(_round(_linear(n, mp["router"]), router_mantissa_bits)), router_mantissa_bits)
    chosen_by = s + mp["router_bias"].astype(jnp.float32) if "router_bias" in mp else s
    c, idx = jax.lax.top_k(chosen_by, k + 1)  # ties to the lower index
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    touches = is_held[idx[:, k - 1]] | is_held[idx[:, k]]
    gap = (c[:, k - 1] - c[:, k]) / jnp.abs(c[:, k - 1])
    margin = jnp.where(touches, gap, 1.0)
    idx = idx[:, :k]
    w = jnp.take_along_axis(s, idx, axis=-1)  # the bias chooses; it is no part of the weight
    if moe["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)  # (4)
    return w * moe["routed_scaling_factor"], idx, margin


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def experts(n, mp, *, moe, with_shared=True, router_mantissa_bits=23, drop_every=0):
    """The held experts' part of the routed sum, plus the shared expert: [T, D],
    and the routing margin [T]. A loop over the held experts, every token
    through each, weighted by what the router gave it (zero where it was not
    chosen): the definition, at ``count`` times the needed work. ``drop_every``
    > 0 forgets every that-many-th assignment (the lower-precision readings)."""
    first, count = moe["held"]
    w, idx, margin = route(n, mp, moe=moe, router_mantissa_bits=router_mantissa_bits)
    if drop_every:
        order = jnp.arange(w.size).reshape(w.shape)
        w = jnp.where(order % drop_every == drop_every - 1, 0.0, w)
    width = mp["down"].shape[1]

    def one(acc, inp):
        e, gate_up, down = inp
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        return acc + weight * _swiglu(n, gate_up[:, :width], gate_up[:, width:], down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (jnp.arange(count), mp["gate_up"], mp["down"]))
    if with_shared and "shared_gate" in mp:
        y = y + _swiglu(n, mp["shared_gate"]["kernel"], mp["shared_up"]["kernel"], mp["shared_down"]["kernel"])
    return y, margin


def layer(h, lp, *, dense, window, attn, moe, rms_eps, with_shared=True, activation_mantissa_bits=23,
          router_mantissa_bits=23, norm_mantissa_bits=23, drop_every=0):
    """One decoder layer on the whole prompt: ([T, dim], routing margin [T]).
    ``activation_mantissa_bits`` under 23 rounds what a serving engine keeps in
    its activation type (the normed inputs of both halves and both branches'
    outputs): 7 is bfloat16, 3 an 8-bit float; ``norm_mantissa_bits`` rounds what
    the configuration keeps in float32 inside a norm (its input and its result).
    Only the benchmark's lower-precision readings pass these."""
    act = functools.partial(_round, mantissa_bits=activation_mantissa_bits)

    def norm(x, name):
        low = functools.partial(_round, mantissa_bits=norm_mantissa_bits)
        return low(_rmsnorm(low(x), lp[name]["scale"].astype(jnp.float32), rms_eps))

    with jax.default_matmul_precision("highest"):
        branch = act(attention(act(norm(h, "ln1")), lp, window=window, attn=attn, rms_eps=rms_eps))
        h = h + act(norm(branch, "post_attn_norm"))  # (1) the norm is on the branch
        n = act(norm(h, "ln2"))
        if dense:
            y = _swiglu(n, lp["gate"]["kernel"], lp["up"]["kernel"], lp["down"]["kernel"])
            margin = jnp.ones((h.shape[0],), jnp.float32)
        else:
            y, margin = experts(
                n, lp["moe"], moe=moe, with_shared=with_shared,
                router_mantissa_bits=router_mantissa_bits, drop_every=drop_every,
            )
        return h + act(norm(act(y), "post_mlp_norm")), margin


def embed(table, ids, multiplier):
    return table.astype(jnp.float32)[ids] * multiplier


def head(h, scale, kernel, *, rms_eps, head_mantissa_bits=23):
    with jax.default_matmul_precision("highest"):
        x = _round(_rmsnorm(h, scale.astype(jnp.float32), rms_eps), head_mantissa_bits)
        return _round(x @ _round(kernel.astype(jnp.float32), head_mantissa_bits), head_mantissa_bits)


def _frozen(value):
    return tuple(sorted((k, _frozen(v)) for k, v in value.items())) if isinstance(value, dict) else value


@functools.lru_cache(maxsize=None)
def _layer_program(dense, window, attn, moe, rms_eps, low):
    return jax.jit(functools.partial(
        layer, dense=dense, window=window, attn=dict(attn), moe=dict(moe), rms_eps=rms_eps, **dict(low)
    ))


def _layer_of(*, dense, window, attn, moe, rms_eps, **low):
    """ONE jitted program a (dense?, window) pair and a set of lower-precision
    arguments, kept for the life of the process: every layer of the kind and
    every later forward pass of the same length run the program compiled first."""
    low = {k: v for k, v in low.items() if k != "head_mantissa_bits"}
    return _layer_program(dense, window, _frozen(attn), _frozen(moe), rms_eps, _frozen(low))


@functools.lru_cache(maxsize=None)
def _rows_program(window, attn, rms_eps, norm_bits, activation_bits):
    attn = dict(attn)

    def rows(h, lp):
        with jax.default_matmul_precision("highest"):
            a = _round(_rmsnorm(_round(h, norm_bits), lp["ln1"]["scale"].astype(jnp.float32), rms_eps), norm_bits)
            k, _ = keys_and_values(_round(a, activation_bits), lp, window=window, attn=attn, rms_eps=rms_eps)
            return k.reshape(k.shape[0], -1)

    return jax.jit(rows)


_embed = jax.jit(embed)


def forward(params, ids, *, windows, first_dense, rms_eps, attn, moe, embedding_multiplier,
            place=lambda tree: tree, upto=None, rows_of=(), **low):
    """(hidden states [T, dim] after layer ``upto`` - 1 (None: the last), routing
    margin [T]: the least over the sparse layers run, {layer: (K rows [T, Hkv *
    D] the cache must hold of it, the margin of the layers BEFORE it)} for the
    layers in ``rows_of``). ``windows``: a window or None for every layer.
    ``place`` is applied to a layer's parameters just before use, so that a
    tree that lives elsewhere is widened a layer at a time."""
    p = params["params"]
    h = _embed(place(p["embed"]["embedding"]), ids, embedding_multiplier)
    margin = jnp.ones((ids.shape[0],), jnp.float32)
    rows = {}
    for i, window in enumerate(windows[:upto]):
        lp = place(p[f"layer_{i}"])
        if i in rows_of:
            program = _rows_program(
                window, _frozen(attn), rms_eps, low.get("norm_mantissa_bits", 23), low.get("activation_mantissa_bits", 23)
            )
            rows[i] = (program(h, lp), margin)
        h, m = _layer_of(dense=i < first_dense, window=window, attn=attn, moe=moe, rms_eps=rms_eps, **low)(h, lp)
        margin = jnp.minimum(margin, m)
    return h, margin, rows


@functools.lru_cache(maxsize=None)
def _head_program(rms_eps, head_mantissa_bits):
    return jax.jit(functools.partial(head, rms_eps=rms_eps, head_mantissa_bits=head_mantissa_bits))


def logits_at(params, ids, positions, *, rms_eps, place=lambda tree: tree, head_mantissa_bits=23, **sizes):
    """(logits [len(positions), vocab], routing margins [len(positions)]) of the
    prompt ``ids`` [T] at ``positions``: the full forward pass, no cache."""
    p = params["params"]
    h, margin, _ = forward(params, ids, rms_eps=rms_eps, place=place, **sizes)
    at = jnp.asarray(positions)
    logits = _head_program(rms_eps, head_mantissa_bits)(
        h[at], place(p["ln_f"]["scale"]), place(p["lm_head"]["kernel"])
    )
    return logits, margin[at]


def last_logits(params, ids, **sizes):
    """(logits [vocab] at the last position of ``ids`` [T], its routing margin)."""
    logits, margin = logits_at(params, ids, [ids.shape[0] - 1], **sizes)
    return logits[0], margin[0]


def cache_rows(params, ids, layers, **sizes):
    """K [T, Hkv * D] of the layers in ``layers`` (one index, or several: ONE
    forward pass as far as the last of them) for every position: what the
    engine's pool of that layer's kind must hold there; with each the routing
    margin [T] of the layers before it. A late layer's rows carry every earlier
    layer's experts for EVERY token, where logits carry one position. Returns
    (rows, margin) for one index, {layer: (rows, margin)} for several."""
    several = not isinstance(layers, int)
    wanted = tuple(layers) if several else (layers,)
    sizes.pop("head_mantissa_bits", None)
    _, _, rows = forward(params, ids, upto=max(wanted) + 1, rows_of=wanted, **sizes)
    return rows if several else rows[layers]


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``, as plain numbers."""
    m = cfg.moe
    return dict(
        windows=tuple(cfg.sliding_window if i in cfg.window_layers else None for i in range(cfg.n_layers)),
        first_dense=m.first_dense,
        rms_eps=cfg.rms_eps,
        embedding_multiplier=cfg.embedding_multiplier,
        attn=dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, theta=cfg.rope_theta),
        moe=dict(
            n_experts=m.n_experts, top_k=m.top_k, norm_topk_prob=m.norm_topk_prob,
            routed_scaling_factor=m.routed_scaling_factor, held=tuple(m.held_experts),
        ),
    )
