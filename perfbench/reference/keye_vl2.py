"""Plain reference of Keye-VL-2.0-30B-A3B's language model (HF ``KeyeVL2``, as
Kwai-Keye/Keye-VL-2.0-30B-A3B publishes it): float32 throughout, ``jax.numpy``
only, matmuls at ``highest`` precision, dense masks, no cache, no kernels, no
batching; attention computed in blocks of queries so that a 26k-token prompt's
scores fit beside a serving engine. ``h`` is a layer's input after its RMSNorm,
``t`` a position, ``s <= t`` the positions before it and itself:

    x0 = E[ids]
    layer l:
      h  = rmsnorm_in(x)
      q_t = rmsnorm_128(Wq h_t) [32 x 128]   k_t = rmsnorm_128(Wk h_t) [4 x 128]   v_t = Wv h_t
      q, k = rope(q, k, pos)                                          (theta 1e7)
      qI_t = rope(WqI h_t) [16 x 64]      kI_t = rope(LayerNorm_64(WkI h_t)) [64]     (ONE key head)
      w_t  = (Ww h_t) * 16^-1/2 * 64^-1/2 [16]
      I(t, s) = sum_j w_t[j] * relu(qI_t[j] . kI_s)
      S_t = all s <= t where t + 1 <= topk, else the topk positions s <= t with the largest I(t, s)
            (ties: the lower position)
      o_t[head] = sum_{s in S_t} softmax_s(q_t[head] . k_s[head // 8] / sqrt(128)) v_s[head // 8]
      x  = x + Wo o
      h' = rmsnorm_pre_mlp(x)
      p  = softmax_128(Wr h'_t) in float32;  sel = top 8;  g = p[sel] / sum p[sel]
      x  = x + sum_{e in sel, e held} g_e E_e(h'_t)        (SwiGLU 2048 -> 768 -> 2048; no shared expert)
    logits = rmsnorm_f(x) Whead

**The held share.** ``held = (first, count)`` says which experts the tree's
tables ``moe/gate_up [count, D, 2 * width]`` and ``moe/down`` are: the layer adds
the held experts' part of the routed sum (the router still scores all of them;
the absent experts' terms are left out). Summed over the shares of a
deployment, attention counted once, that is the uncut layer
(tests/perfbench/test_keye_cell.py).

Departures from the published description, each at its line below: (1) five
points are no keys of ``config.json`` (the configuration file lists them under
``assumed``): the choice is by token as in DeepSeek-Sparse-Attention, the
config's ``q_chunk_size`` / ``kv_chunk_size`` read as a tiling that changes no
value; the index key's LayerNorm, the scale of ``w``, relu and ``h`` as the
indexer's input; rope on all 64 index dims with the layer's own theta; the
q/k norms of the Qwen3 family; no Hadamard rotation and no 8-bit index keys;
(2) rope in the halves layout (``rotate_half``), on text positions, where
m-rope's three components are equal: plain rope; (3) float32 throughout, where
the checkpoint computes in bfloat16 and the router in float32; (4) the vision
tower is not modelled.

The lower-precision arguments (``*_mantissa_bits``, ``topk``, ``index_shift``)
are the benchmark's named faults: the second reading of each of ``check``'s
limits. The parameter tree is the program's own
(``params["params"]["layer_<i>"]``...); only its names are shared with the
program, none of its code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128  # queries a block of the attention: [heads, 128, T] float32 scores at a time


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layernorm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _f32(w):
    """A stored table in float32, AT ITS USE (a layer's tables widened at once
    would stand beside the serving engine's pools)."""
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


def keys_and_values(a, lp, *, attn, rms_eps):
    """What the K/V cache holds of every position: ``k`` [T, Hkv, D] after its
    norm and rope, ``v`` [T, Hkv, D]. ``a``: the layer's normed input."""
    t, hk, d = a.shape[0], attn["n_kv_heads"], attn["head_dim"]
    k = _rmsnorm(_linear(a, lp["k"]).reshape(t, hk, d), lp["k_norm"]["scale"], rms_eps)  # (1) before rope
    return _rope(k, attn["theta"]), _linear(a, lp["v"]).reshape(t, hk, d)


def index_keys(a, lp, *, attn, indexer_mantissa_bits=23, index_shift=0):
    """What the index-key array holds of every position: ``kI`` [T, Di] after its
    LayerNorm and rope. ``index_shift`` > 0 is a fault: every key one (or more)
    position late, as a write index off by that many would leave them."""
    ki = _layernorm(_linear(a, lp["index_k"]), lp["index_k_norm"]["scale"], lp["index_k_norm"]["bias"])
    ki = _round(_rope(ki[:, None], attn["theta"])[:, 0], indexer_mantissa_bits)  # (1) rope on all its dims
    if index_shift:
        ki = jnp.concatenate([jnp.zeros_like(ki[:index_shift]), ki[:-index_shift]], axis=0)
    return ki


def choose(score, seen, key_pos, k_top: int):
    """Equation 3 for a block of queries. score: [Q, T] with ``-inf`` outside
    ``seen``. Returns (chosen [Q, T] bool, the relative gap [Q] between the last
    score taken and the first left out: 1 where nothing is left out)."""
    if k_top >= score.shape[-1]:
        return seen, jnp.ones((score.shape[0],), jnp.float32)
    vals, idx = jax.lax.top_k(score, k_top + 1)  # ties to the lower position
    last, first_out = vals[:, k_top - 1], vals[:, k_top]
    chosen = (score > last[:, None]) | ((score == last[:, None]) & (key_pos[None, :] <= idx[:, k_top - 1][:, None]))
    gap = jnp.where(first_out > -jnp.inf, (last - first_out) / jnp.maximum(jnp.abs(last), 1e-30), 1.0)
    return chosen & seen, gap


def attention(a, lp, sets_at, *, attn, indexer, rms_eps, indexer_mantissa_bits=23, index_shift=0, topk=None):
    """(``Wo o`` [T, dim], what the queries at the positions ``sets_at`` [P] chose:
    [P, T] bool, and the relative gap [P] between the last score each took and
    the first it left out). ``topk``: the configuration's, or a fault's (a number
    past the prompt's length leaves the choice out). Block of queries by block
    (``lax.map``), every key under a dense mask."""
    t = a.shape[0]
    h, hk, d = attn["n_heads"], attn["n_kv_heads"], attn["head_dim"]
    hi, di = indexer["n_heads"], indexer["head_dim"]
    k_top = int(indexer["top_k"] if topk is None else topk)
    low = functools.partial(_round, mantissa_bits=indexer_mantissa_bits)
    q = _rope(_rmsnorm(_linear(a, lp["q"]).reshape(t, h, d), lp["q_norm"]["scale"], rms_eps), attn["theta"])
    k, v = keys_and_values(a, lp, attn=attn, rms_eps=rms_eps)
    qi = low(_rope(_linear(a, lp["index_q"]).reshape(t, hi, di), attn["theta"]))
    ki = index_keys(a, lp, attn=attn, indexer_mantissa_bits=indexer_mantissa_bits, index_shift=index_shift)
    w = low(_linear(a, lp["index_w"]) * hi**-0.5 * di**-0.5)  # (1) the heads' weights, scaled
    key_pos = jnp.arange(t)

    def scores(qib, wb, q_pos):  # equation 2 for a block: [Q, T], -inf where s > t
        seen = key_pos[None, :] <= q_pos[:, None]
        score = low(jnp.einsum("qj,qjs->qs", wb, jax.nn.relu(jnp.einsum("qjd,sd->qjs", qib, ki))))
        return jnp.where(seen, score, -jnp.inf), seen

    pad = -t % QUERY_BLOCK
    blocks = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(-1, QUERY_BLOCK, *x.shape[1:])
    starts = jnp.arange((t + pad) // QUERY_BLOCK) * QUERY_BLOCK

    def block(inp):
        qb, qib, wb, start = inp  # [Q, H, D], [Q, Hi, Di], [Q, Hi]
        chosen, _ = choose(*scores(qib, wb, start + jnp.arange(QUERY_BLOCK)), key_pos, k_top)
        s = jnp.einsum("qkgd,skd->kgqs", qb.reshape(QUERY_BLOCK, hk, h // hk, d), k) * d**-0.5
        s = jnp.where(chosen[None, None], s, -jnp.inf)
        # (a padded query past the prompt sees what the masks leave it: its row is cut off below)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(QUERY_BLOCK, h * d)

    o = jax.lax.map(block, (blocks(q), blocks(qi), blocks(w), starts)).reshape(-1, h * d)[:t]
    sets, gap = choose(*scores(qi[sets_at], w[sets_at], sets_at), key_pos, k_top)
    return _linear(o, lp["o"]), sets, gap


def route(n, mp, *, moe, router_mantissa_bits=23):
    """(weights [T, k], experts [T, k], margin [T]). ``margin`` is how far, as a
    share of the probability, a token's choice of HELD experts is from changing:
    the gap between the last expert taken and the first left out where either
    is held (1 where neither is). A comparison with a program that computes in
    fewer bits means something only where this is wide."""
    e, k = moe["n_experts"], moe["top_k"]
    first, count = moe["held"]
    p = _round(jax.nn.softmax(_round(_linear(n, mp["router"]), router_mantissa_bits), axis=-1), router_mantissa_bits)
    c, idx = jax.lax.top_k(p, k + 1)  # ties to the lower index
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    touches = is_held[idx[:, k - 1]] | is_held[idx[:, k]]
    margin = jnp.where(touches, (c[:, k - 1] - c[:, k]) / c[:, k - 1], 1.0)
    w, idx = c[:, :k], idx[:, :k]
    if moe["norm_topk_prob"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return w, idx, margin


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def experts(n, mp, *, moe, router_mantissa_bits=23):
    """The held experts' part of the routed sum [T, D] and the routing margin
    [T]. A loop over the held experts, every token through each, weighted by
    what the router gave it (zero where it was not chosen): the definition, at
    ``count`` times the needed work."""
    first, count = moe["held"]
    w, idx, margin = route(n, mp, moe=moe, router_mantissa_bits=router_mantissa_bits)
    width = mp["down"].shape[1]

    def one(acc, inp):
        e, gate_up, down = inp
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        return acc + weight * _swiglu(n, gate_up[:, :width], gate_up[:, width:], down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (jnp.arange(count), mp["gate_up"], mp["down"]))
    return y, margin


def layer(h, lp, sets_at, *, attn, indexer, moe, rms_eps, activation_mantissa_bits=23, router_mantissa_bits=23,
          indexer_mantissa_bits=23, index_shift=0, topk=None):
    """One decoder layer on the whole prompt: ([T, dim], routing margin [T], what
    the queries at ``sets_at`` chose [P, T], their gaps [P]).
    ``activation_mantissa_bits`` under 23 rounds what a serving engine keeps in
    its activation type (the normed inputs of both halves and both branches'
    outputs): 7 is bfloat16, 3 an 8-bit float; ``indexer_mantissa_bits`` rounds
    the indexer's queries, keys, weights and scores. Only the benchmark's second
    readings pass these."""
    act = functools.partial(_round, mantissa_bits=activation_mantissa_bits)
    with jax.default_matmul_precision("highest"):
        a = act(_rmsnorm(h, lp["ln1"]["scale"].astype(jnp.float32), rms_eps))
        branch, sets, gap = attention(
            a, lp, sets_at, attn=attn, indexer=indexer, rms_eps=rms_eps,
            indexer_mantissa_bits=indexer_mantissa_bits, index_shift=index_shift, topk=topk,
        )
        h = h + act(branch)
        n = act(_rmsnorm(h, lp["ln2"]["scale"].astype(jnp.float32), rms_eps))
        y, margin = experts(n, lp["moe"], moe=moe, router_mantissa_bits=router_mantissa_bits)
        return h + act(y), margin, sets, gap


def embed(table, ids):
    return table.astype(jnp.float32)[ids]


def head(h, scale, kernel, *, rms_eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(h, scale.astype(jnp.float32), rms_eps) @ kernel.astype(jnp.float32)


def _frozen(value):
    return tuple(sorted((k, _frozen(v)) for k, v in value.items())) if isinstance(value, dict) else value


@functools.lru_cache(maxsize=None)
def _layer_program(attn, indexer, moe, rms_eps, low):
    return jax.jit(functools.partial(
        layer, attn=dict(attn), indexer=dict(indexer), moe=dict(moe), rms_eps=rms_eps, **dict(low)
    ))  # (h, lp, sets_at)


@functools.lru_cache(maxsize=None)
def _rows_program(attn, rms_eps, activation_bits, indexer_bits, index_shift):
    attn = dict(attn)

    def rows(h, lp):
        with jax.default_matmul_precision("highest"):
            a = _round(_rmsnorm(h, lp["ln1"]["scale"].astype(jnp.float32), rms_eps), activation_bits)
            k, _ = keys_and_values(a, lp, attn=attn, rms_eps=rms_eps)
            ki = index_keys(a, lp, attn=attn, indexer_mantissa_bits=indexer_bits, index_shift=index_shift)
            return k.reshape(k.shape[0], -1), ki

    return jax.jit(rows)


_embed = jax.jit(embed)


def forward(params, ids, *, n_layers, rms_eps, attn, indexer, moe, place=lambda tree: tree, upto=None,
            rows_of=(), sets_at=(0,), **low):
    """(hidden states [T, dim] after layer ``upto`` - 1 (None: the last), routing
    margin [T]: the least over the layers run, {layer: (K rows [T, Hkv * D],
    index-key rows [T, Di], the margin of the layers BEFORE it)} for the layers
    in ``rows_of``, what the queries at ``sets_at`` chose in every layer run
    [layers, P, T] bool, their gaps [layers, P]). ``place`` is applied to a
    layer's parameters just before use, so that a tree that lives elsewhere is
    widened a layer at a time."""
    p = params["params"]
    h = _embed(place(p["embed"]["embedding"]), ids)
    margin = jnp.ones((ids.shape[0],), jnp.float32)
    rows, sets, gaps = {}, [], []
    program = _layer_program(_frozen(attn), _frozen(indexer), _frozen(moe), rms_eps, _frozen(low))
    at = jnp.asarray(sets_at, jnp.int32)
    for i in range(n_layers if upto is None else upto):
        lp = place(p[f"layer_{i}"])
        if i in rows_of:
            get = _rows_program(
                _frozen(attn), rms_eps, low.get("activation_mantissa_bits", 23),
                low.get("indexer_mantissa_bits", 23), low.get("index_shift", 0),
            )
            rows[i] = (*get(h, lp), margin)
        h, m, chosen, gap = program(h, lp, at)
        margin = jnp.minimum(margin, m)
        sets.append(chosen)
        gaps.append(gap)
    return h, margin, rows, jnp.stack(sets), jnp.stack(gaps)


@functools.lru_cache(maxsize=None)
def _head_program(rms_eps):
    return jax.jit(functools.partial(head, rms_eps=rms_eps))


def logits_at(params, ids, positions, *, rms_eps, place=lambda tree: tree, rows_of=(), **sizes):
    """(logits [len(positions), vocab], routing margins [len(positions)], what
    the queries at ``positions`` chose in every layer [layers, len(positions), T]
    bool, {layer: (K rows, index-key rows, margin)} for ``rows_of``) of the
    prompt ``ids`` [T]: ONE full forward pass, no cache."""
    p = params["params"]
    h, margin, rows, sets, _ = forward(
        params, ids, rms_eps=rms_eps, place=place, rows_of=tuple(rows_of), sets_at=tuple(positions), **sizes
    )
    at = jnp.asarray(positions)
    logits = _head_program(rms_eps)(h[at], place(p["ln_f"]["scale"]), place(p["lm_head"]["kernel"]))
    return logits, margin[at], sets, rows


def cache_rows(params, ids, layers, **sizes):
    """{layer: (K rows [T, Hkv * D], index-key rows [T, Di], the routing margin
    [T] of the layers before it)} for the layers in ``layers``: ONE forward pass
    as far as the last of them. What the engine's K pool and its index-key
    array must hold of every position."""
    wanted = tuple(layers)
    return forward(params, ids, upto=max(wanted) + 1, rows_of=wanted, **sizes)[2]


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``, as plain numbers."""
    m, ix = cfg.moe, cfg.indexer
    return dict(
        n_layers=cfg.n_layers,
        rms_eps=cfg.rms_eps,
        attn=dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, theta=cfg.rope_theta),
        indexer=dict(n_heads=ix.n_heads, head_dim=ix.head_dim, top_k=ix.top_k),
        moe=dict(
            n_experts=m.n_experts, top_k=m.top_k, norm_topk_prob=m.norm_topk_prob, held=tuple(m.held_experts),
        ),
    )
