"""Plain reference of the Mellum2 decoder (HF ``mellum``, as
JetBrains/Mellum2-12B-A2.5B-Instruct publishes its ``config.json``): float32
throughout, ``jax.numpy`` only, matmuls at ``highest`` precision, dense masks, no
cache, no kernels, no batching; attention computed in blocks of queries so that a
32k-token prompt's scores fit beside a serving engine; the experts by a plain
loop over ALL of a layer's 64 experts (nothing is left out: the layer is uncut).

    x0 = E[ids]
    layer l of kind t_l in {sliding_attention, full_attention} (eps 1e-6, no bias anywhere):
      h  = rmsnorm_1(x)
      q, k, v = Wq h, Wk h, Wv h                 32 query / 4 KV heads x 128
      q, k = rmsnorm_q(q), rmsnorm_k(k)          over head_dim, before rope
      sliding: inv_freq_i = theta^(-2i/128)                      (rope_type default, theta 500,000)
      full:    YaRN (HF ``_compute_yarn_parameters``): the plain frequencies for the dims that
               turn more than beta_fast = 32 times over the ORIGINAL 8,192 positions, those / 16
               for the dims that turn less than beta_slow = 1 time, a linear ramp between
               (floor / ceil of the two correction dims, as HF truncates); cos and sin times
               attention_factor 1.2772588722239782, so a logit carries its square
      q, k = rope(q, k, pos)                     the halves layout (``rotate_half``)
      a_i = sum_j softmax_j(q_i . k_j / sqrt(128)) v_j      over j <= i, and for sliding j > i - 1024
      x  = x + Wo a
      h2 = rmsnorm_2(x)
      p = softmax(Wr h2) over ALL 64 experts in float32;  sel = top_8(p)  (ties to the lower index)
      w = p[sel] / sum p[sel]                    (norm_topk_prob)
      x  = x + sum_{e in sel} w_e Wdown_e( silu(Wgate_e h2) * Wup_e h2 )       width 896, no shared expert
    logits = rmsnorm_f(x) Whead                  (untied)

DEPARTURES from the published description, each the configuration file's
``assumed``: (1) the installed transformers (4.57.6) has no ``mellum``: the layer
is written from the config's keys, which are Qwen3-MoE's letter for letter plus
the three transformers-5 lists (``layer_types``, ``mlp_layer_types``,
``rope_parameters`` by layer type); the RMSNorm over ``head_dim`` on q and k
before rope is that family's and is no key of the config; (2) the window's edge:
a query sees itself and the ``sliding_window - 1`` positions before it (HF's
``sliding_window_overlay``: ``kv_idx > q_idx - sliding_window``); (3)
``intermediate_size`` 7168 is read by no layer (``mlp_layer_types`` is all
``sparse``); (4) the ``MTP head`` of the model card is no key of ``config`` and is
not modelled; (5) float32 throughout, where HF computes in the checkpoint's
bfloat16 and the router in float32.

The parameter tree is the program's own (``params["params"]["layer_<i>"]``...);
only its names are shared with the program, none of its code: the YaRN table, the
masks and the router below are this file's own. ``follow`` makes a layer take a
GIVEN choice of experts in place of its router's own (weighed by this router's
float32 probabilities of them; everything else stays the reference's): with all
64 experts held a program in bfloat16 takes another expert at some (token, layer)s,
each such flip swaps an eighth of a layer, and a comparison of the layers above
it means something only along the program's own choice. Whether that choice was
right is asked apart: the reference hands out its own (``choices``) and its
margin (``margins``). Two knobs serve the benchmark's lower-precision readings
alone (23 bits = float32 = off).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128  # queries a block of the attention: [32 heads, 128, T] float32 scores at a time


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _f32(w):
    """A stored table in float32, AT ITS USE (one expert's are 25 MB; a layer's 1.6 GB)."""
    return w.astype(jnp.float32)


def _linear(x, p):
    return x @ _f32(p["kernel"])


def _round(x, mantissa_bits):
    """``reduce_precision`` because XLA elides a convert pair; 23 = float32."""
    if mantissa_bits >= 23:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=mantissa_bits)


def rope_table(head_dim: int, theta: float, yarn: dict | None) -> tuple[tuple, float]:
    """(inv_freq, ``head_dim / 2`` float32 values, what cos and sin are multiplied by).
    ``yarn`` None: ``rope_type: default``. Else HF ``_compute_yarn_parameters``
    with ``truncate`` at its default (True), written out: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor`` (None: ``0.1 ln(factor) + 1``)."""
    plain = theta ** -(np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if yarn is None:
        return tuple(plain.astype(np.float32).tolist()), 1.0
    factor, original = float(yarn["factor"]), float(yarn["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:  # the dim that turns `rotations` times over the original context
        return head_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(yarn["beta_slow"]))), head_dim - 1)
    if low == high:
        high += 0.001  # HF: prevent the singularity
    interpolated = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv_freq = plain / factor * interpolated + plain * (1.0 - interpolated)
    gain = yarn.get("attention_factor")
    return tuple(inv_freq.astype(np.float32).tolist()), float(0.1 * math.log(factor) + 1.0 if gain is None else gain)


def _rope(x, inv_freq, gain):
    """x: [T, heads, D], halves layout; position t is row t."""
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)  # [T, D / 2]
    cos, sin = jnp.cos(angles)[:, None] * gain, jnp.sin(angles)[:, None] * gain
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def keys_and_values(a, lp, *, kind, attn, rms_eps):
    """What the cache holds of every position: ``k`` [T, Hkv, D] after its norm
    and its layer kind's rope, ``v`` [T, Hkv, D]. ``a``: the layer's normed input."""
    t, hk, d = a.shape[0], attn["n_kv_heads"], attn["head_dim"]
    k = _rmsnorm(_linear(a, lp["k"]).reshape(t, hk, d), _f32(lp["k_norm"]["scale"]), rms_eps)  # (1)
    return _rope(k, *attn["rope"][kind]), _linear(a, lp["v"]).reshape(t, hk, d)


def attention(a, lp, *, kind, attn, rms_eps):
    """``Wo a``. Block of queries by block (``lax.map``), every key under a dense mask."""
    t = a.shape[0]
    h, hk, d = attn["n_heads"], attn["n_kv_heads"], attn["head_dim"]
    q = _rmsnorm(_linear(a, lp["q"]).reshape(t, h, d), _f32(lp["q_norm"]["scale"]), rms_eps)
    q = _rope(q, *attn["rope"][kind])
    k, v = keys_and_values(a, lp, kind=kind, attn=attn, rms_eps=rms_eps)
    pad = -t % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, hk, h // hk, d)
    starts = jnp.arange(q.shape[0]) * QUERY_BLOCK
    key_pos = jnp.arange(t)

    def block(inp):
        qb, start = inp  # [QUERY_BLOCK, Hkv, G, D]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * d**-0.5
        q_pos = start + jnp.arange(QUERY_BLOCK)
        seen = key_pos[None, :] <= q_pos[:, None]
        if kind == "sliding_attention":  # (2) itself and the window - 1 before it
            seen &= key_pos[None, :] > q_pos[:, None] - attn["window"]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        # (a padded query past the prompt sees every key or its window of them: its row is cut off below)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q, starts)).reshape(-1, h * d)[:t]
    return _linear(o, lp["o"])


def route(n, mp, *, moe, router_mantissa_bits=23, follow=None):
    """(weights [T, k], experts [T, k], margin [T], the router's OWN choice [T,
    k]). ``margin`` is how far, as a share of the probability, a token's choice
    is from changing: the gap between the last expert taken and the first left
    out. ``follow`` [T, k] int32: the experts a token takes INSTEAD of the
    router's own choice (a row of -1: its own), weighed by this router's
    probabilities of them."""
    k = moe["top_k"]
    p = _round(jax.nn.softmax(_round(_linear(n, mp["router"]), router_mantissa_bits), axis=-1), router_mantissa_bits)
    c, idx = jax.lax.top_k(p, k + 1)  # ties to the lower index
    margin = (c[:, k - 1] - c[:, k]) / jnp.abs(c[:, k - 1])
    own = idx = idx[:, :k]
    if follow is not None:
        idx = jnp.where(follow[:, :1] >= 0, follow, own)
    w = jnp.take_along_axis(p, idx, axis=-1)
    if moe["norm_topk_prob"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return w, idx, margin, own


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def experts(n, mp, *, moe, router_mantissa_bits=23, follow=None):
    """The routed sum [T, dim], the routing margin [T] and the router's own
    choice [T, k]. A loop over ALL the experts, every token through each,
    weighted by what the router gave it (zero where it was not chosen): the
    definition, at ``n_experts / top_k`` times the needed work."""
    w, idx, margin, own = route(n, mp, moe=moe, router_mantissa_bits=router_mantissa_bits, follow=follow)
    width = mp["down"].shape[1]

    def one(acc, inp):
        e, gate_up, down = inp
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        return acc + weight * _swiglu(n, gate_up[:, :width], gate_up[:, width:], down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (jnp.arange(moe["n_experts"]), mp["gate_up"], mp["down"]))
    return y, margin, own


def layer(h, lp, follow=None, *, kind, rms_eps, attn, moe, router_only=False, activation_mantissa_bits=23,
          router_mantissa_bits=23):
    """One decoder layer on the whole prompt: ([T, dim], routing margin [T], the
    router's own choice [T, k], the K rows [T, Hkv * D] the cache must hold of
    it). ``follow``: :func:`route`'s;
    with ``router_only`` what enters the layer's router and what leaves it: (n
    [T, dim], weights [T, k], experts [T, k], margin [T]).
    ``activation_mantissa_bits`` under 23 rounds what a serving engine keeps in
    its activation type (the normed inputs of both halves and both branches'
    outputs): 7 is bfloat16, as the engine computes; 3 an 8-bit float."""
    act = functools.partial(_round, mantissa_bits=activation_mantissa_bits)
    with jax.default_matmul_precision("highest"):
        a = act(_rmsnorm(h, _f32(lp["ln1"]["scale"]), rms_eps))
        rows = keys_and_values(a, lp, kind=kind, attn=attn, rms_eps=rms_eps)[0].reshape(h.shape[0], -1)
        h = h + act(attention(a, lp, kind=kind, attn=attn, rms_eps=rms_eps))
        n = act(_rmsnorm(h, _f32(lp["ln2"]["scale"]), rms_eps))
        if router_only:
            return (n, *route(n, lp["moe"], moe=moe, router_mantissa_bits=router_mantissa_bits)[:3])
        y, margin, own = experts(n, lp["moe"], moe=moe, router_mantissa_bits=router_mantissa_bits, follow=follow)
        return h + act(y), margin, own, rows


def embed(table, ids):
    """h_0 = E[ids]: [T, dim] float32."""
    return _f32(table)[ids]


def head(h, scale, kernel, *, rms_eps):
    """Logits of the given positions, [..., vocab], from the UNTIED float32 head."""
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(h, _f32(scale), rms_eps) @ _f32(kernel)


def _frozen(value):
    return tuple(sorted((k, _frozen(v)) for k, v in value.items())) if isinstance(value, dict) else value


_PROGRAMS: dict = {}


def _layer_program(kind, rms_eps, attn, moe, low):
    """ONE jitted program a kind of layer and a set of lower-precision arguments,
    kept for the life of the process."""
    key = (kind, rms_eps, _frozen(attn), _frozen(moe), _frozen(low))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(functools.partial(layer, kind=kind, rms_eps=rms_eps, attn=attn, moe=moe, **low))
    return _PROGRAMS[key]


_embed = jax.jit(embed)


def forward(params, ids, *, layer_types, rms_eps, attn, moe, place=lambda tree: tree, upto=None, z=None,
            margins=None, choices=None, follow=None, rows_of=(), rows=None, **low):
    """(hidden states [T, dim] after layer ``upto`` - 1 (None: the last), the
    routing margin [T]: the least over those layers). IN BLOCKS: one jitted
    program a kind of layer (kept for the life of the process: every later
    forward of the same length runs the program compiled first), ``place``
    applied to each layer's parameters just before use. ``margins``: a list
    that is given EVERY layer's routing margin [T]; ``choices``: one that is
    given every layer's own choice [T, k]; ``follow`` [layers, T, k] int32: the
    experts each layer takes instead (:func:`route`; -1: its own); ``rows``: a
    dict that is given the K rows [T, Hkv * D] of the layers in ``rows_of``;
    ``z``: nothing here fills it (the choice-following judges of a flavor with
    convolutions pass it)."""
    p = params["params"]
    h = _embed(place(p["embed"]["embedding"]), ids)
    margin = jnp.ones((ids.shape[0],), jnp.float32)
    none = jnp.full((ids.shape[0], moe["top_k"]), -1, jnp.int32)
    for i, kind in enumerate(layer_types[:upto]):
        program = _layer_program(kind, rms_eps, attn, moe, low)
        h, m, own, k_rows = program(h, place(p[f"layer_{i}"]), none if follow is None else follow[i])
        margin = jnp.minimum(margin, m)
        if margins is not None:
            margins.append(m)
        if choices is not None:
            choices.append(own)
        if rows is not None and i in rows_of:
            rows[i] = k_rows
    return h, margin


def logits_of(params, h, *, rms_eps, place=lambda tree: tree, **_):
    """The head on hidden states ``h`` [N, dim] (rows of :func:`forward`'s)."""
    p = params["params"]
    return jax.jit(functools.partial(head, rms_eps=rms_eps))(h, place(p["ln_f"]["scale"]), place(p["lm_head"]["kernel"]))


def logits_at(params, ids, positions, **sizes):
    """(logits [len(positions), vocab], routing margins [len(positions)]) of the
    prompt ``ids`` [T] at ``positions``: the full forward pass, no cache."""
    h, margin = forward(params, ids, **sizes)
    at = jnp.asarray(positions)
    return logits_of(params, h[at], **sizes), margin[at]


def last_logits(params, ids, **sizes):
    """(logits [vocab] at the last position of ``ids`` [T], its routing margin)."""
    logits, margin = logits_at(params, ids, [ids.shape[0] - 1], **sizes)
    return logits[0], margin[0]


def cache_rows(params, ids, layers, **sizes):
    """{layer: K [T, Hkv * D]} of the layers in ``layers`` for every position
    (ONE forward pass as far as the last of them): what the engine's pool of
    that layer's kind must hold there."""
    rows: dict = {}
    forward(params, ids, upto=max(layers) + 1, rows_of=tuple(layers), rows=rows, **sizes)
    return rows


def first_router(params, ids, *, layer_types, rms_eps, attn, moe, place=lambda tree: tree, **low):
    """(n [T, dim], weights [T, k], experts [T, k], margin [T]) of the FIRST
    layer's router over the prompt: the normed hidden states that enter it, in
    float32, and what it makes of them. A program's router handed the same ``n``
    must answer alike to the last bits of float32: no rounding stands between."""
    h = _embed(place(params["params"]["embed"]["embedding"]), ids)
    return jax.jit(functools.partial(
        layer, kind=layer_types[0], rms_eps=rms_eps, attn=attn, moe=moe, router_only=True, **low
    ))(h, place(params["params"]["layer_0"]))


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``, as plain numbers;
    the rope tables are computed HERE, by :func:`rope_table`, from the numbers."""
    m, y = cfg.moe, cfg.full_attention_yarn
    yarn = None if y is None else {
        "factor": y.factor, "original_max_position_embeddings": y.original_max, "beta_fast": y.beta_fast,
        "beta_slow": y.beta_slow, "attention_factor": y.attention_factor,
    }
    return dict(
        layer_types=tuple(cfg.layer_types),
        rms_eps=cfg.rms_eps,
        attn=dict(
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, window=cfg.sliding_window,
            rope={
                "sliding_attention": rope_table(cfg.head_dim, cfg.rope_theta, None),
                "full_attention": rope_table(cfg.head_dim, cfg.rope_theta, yarn),
            },
        ),
        moe=dict(n_experts=m.n_experts, top_k=m.top_k, first_dense=m.first_dense, norm_topk_prob=m.norm_topk_prob),
    )
