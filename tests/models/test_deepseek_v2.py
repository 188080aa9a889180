"""DeepSeek-V2 at test size (``VLM_DEEPSEEK_V2_TINY_TEST``): latent attention
with an absorbed path, group-limited routing with a shared expert over the
experts held, an exact sorted dispatch, a latent paged pool: against the plain
reference (perfbench/reference/deepseek_v2.py), and through the engine."""

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
from cosmos_curate_tpu.models.vlm import model as vlm_model
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_DEEPSEEK_V2_EP8, VLM_DEEPSEEK_V2_TINY_TEST, MoEConfig, init_cache, route,
    yarn_inv_freq, yarn_mscale,
)
from cosmos_curate_tpu.ops import grouped_matmul as gmm_ops
from cosmos_curate_tpu.ops import latent_attention as mla_ops
from perfbench.reference import deepseek_v2 as ref

CFG = VLM_DEEPSEEK_V2_TINY_TEST


@pytest.fixture(scope="module")
def params():
    return nn.unbox(_init_params(VLM(CFG), 0))


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(10, 500, n).tolist()


def _forward(cfg, params, ids, dtype=jnp.float32):
    """The program's slot-cache forward over a whole prompt: (logits [T, V], latent cache)."""
    model = VLM(cfg, dtype=dtype)
    ids = jnp.asarray(ids, jnp.int32)[None]
    t = ids.shape[1]
    embeds = model.apply(params, ids, method=model.embed_tokens)
    ck, cv = init_cache(cfg, 1, dtype=dtype, length=t)
    with jax.default_matmul_precision("highest"):
        logits, nk, _ = model.apply(
            params, embeds, ck, cv, jnp.arange(t)[None], jnp.zeros(1, jnp.int32), jnp.full((1,), t, jnp.int32)
        )
    return logits[0], nk


# -- (a) the model against the plain reference --------------------------------


def test_whole_model_logits_match_the_reference_at_every_position(params):
    ids = _ids(40)
    logits, cache = _forward(CFG, params, ids)
    want, _ = ref.logits_at(params, jnp.asarray(ids), list(range(40)), **ref.model_kwargs(CFG))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=2e-5)
    # the cache rows of the second layer: [c_kv | k_rope | zeros]
    rows, _ = ref.cache_rows(params, jnp.asarray(ids), 1, **ref.model_kwargs(CFG))
    used = CFG.mla.kv_lora_rank + CFG.mla.qk_rope_head_dim
    np.testing.assert_allclose(np.asarray(cache[1, 0, 0, :, :used]), np.asarray(rows), atol=1e-5)
    assert not np.asarray(cache[..., used:]).any()


@pytest.mark.parametrize("layer", [0, 1], ids=["dense-layer", "sparse-layer"])
def test_one_layer_matches_the_reference(params, layer):
    one = dataclasses.replace(CFG, n_layers=layer + 1)
    tree = {"params": {k: v for k, v in params["params"].items() if not k.startswith("layer_") or int(k[6:]) <= layer}}
    ids = _ids(24, seed=3)
    logits, _ = _forward(one, tree, ids)
    want, _ = ref.logits_at(tree, jnp.asarray(ids), list(range(24)), **ref.model_kwargs(one))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=2e-5)


def test_bfloat16_model_is_close_to_the_reference(params):
    ids = _ids(40)
    logits, _ = _forward(CFG, params, ids, dtype=jnp.bfloat16)
    want, margin = ref.logits_at(params, jnp.asarray(ids), list(range(40)), **ref.model_kwargs(CFG))
    err = np.abs(np.asarray(logits, np.float32) - np.asarray(want)).max(axis=-1) / np.abs(np.asarray(want)).max()
    assert np.median(err) < 0.03
    assert err[np.asarray(margin) > 0.1].max() < 0.1  # where no routing choice is a near-tie


# -- (b) absorbed = decompressed; kernel = reference --------------------------


def _attention_case(seed=0, b=3, t=5, s=48):
    rng = np.random.default_rng(seed)
    mla = CFG.mla
    h, c, dn, dr, dv, w = CFG.n_heads, mla.kv_lora_rank, mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim, mla.cache_width
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    rows = jnp.concatenate([f(b, s, c + dr), jnp.zeros((b, s, w - c - dr))], axis=-1)
    write = jnp.asarray([0, 7, 30], jnp.int32)
    return f(b, t, h, dn), f(b, t, h, dr), rows, f(c, h, dn), f(c, h, dv), write, write + t


def test_absorbed_attention_equals_the_decompressed_equations():
    q_nope, q_rope, rows, w_uk, w_uv, write, kv_len = _attention_case()
    mla = CFG.mla
    with jax.default_matmul_precision("highest"):
        want = mla_ops.decompressed_reference_attention(
            q_nope, q_rope, rows, w_uk, w_uv, write, kv_len, sm_scale=mla.softmax_scale
        )
        pad = jnp.zeros((*q_rope.shape[:-1], mla.cache_width - mla.kv_lora_rank - mla.qk_rope_head_dim))
        q_abs = jnp.concatenate([jnp.einsum("bthd,chd->bthc", q_nope, w_uk), q_rope, pad], axis=-1)
        u = mla_ops.latent_reference_attention(
            q_abs, rows, write, kv_len, sm_scale=mla.softmax_scale, v_width=mla.kv_lora_rank
        )
        got = jnp.einsum("bthc,chd->bthd", u, w_uv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("t", [1, 6], ids=["decode", "prefill"])
def test_latent_kernel_matches_the_xla_reference_through_the_pool(t):
    rng = np.random.default_rng(4)
    mla = CFG.mla
    w, used, bs, nbl, b = mla.cache_width, mla.kv_lora_rank + mla.qk_rope_head_dim, 8, 6, 3

    def rows(*shape):
        x = rng.normal(size=(*shape, w)).astype(np.float32)
        x[..., used:] = 0
        return jnp.asarray(x, jnp.bfloat16)

    pool = rows(2, 40, 1, bs)
    tables = jnp.asarray(np.stack([rng.permutation(39)[:nbl] + 1 for _ in range(b)]), jnp.int32)
    write = jnp.asarray([0, 9, 40], jnp.int32)
    kv_len = write + jnp.asarray([t, t, max(1, t - 2)])  # the last row's chunk is padded
    q = rows(b, t, CFG.n_heads)
    kw = dict(layer_index=1, sm_scale=mla.softmax_scale, v_width=mla.kv_lora_rank)
    want = mla_ops.latent_attention(q, pool, tables, write, kv_len, use_kernel=False, **kw)
    got = mla_ops.latent_attention(q, pool, tables, write, kv_len, use_kernel=True, interpret=True, **kw)
    live = (np.arange(t)[None] < np.asarray(kv_len - write)[:, None])[..., None, None]
    np.testing.assert_allclose(
        np.where(live, np.asarray(got, np.float32), 0), np.where(live, np.asarray(want, np.float32), 0),
        atol=3e-2, rtol=3e-2,
    )
    assert not np.where(live, 0, np.asarray(got, np.float32)).any()  # padding walks no page


def test_grouped_matmul_kernel_matches_ragged_dot():
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(200, 128)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(4, 128, 256)), jnp.bfloat16)
    sizes = jnp.asarray([10, 0, 100, 30], jnp.int32)
    want = gmm_ops.grouped_matmul(lhs, rhs, sizes, use_kernel=False)
    got = gmm_ops.grouped_matmul(lhs, rhs, sizes, use_kernel=True, interpret=True)
    assert got.shape == want.shape == (200, 256)
    np.testing.assert_allclose(np.asarray(got[:140], np.float32), np.asarray(want[:140], np.float32), atol=0.1, rtol=2e-2)


# -- (c) through the engine ---------------------------------------------------


def _serve(engine, requests):
    first, steps = {}, {}
    start, collect = engine._start_slot, engine._decode_collect

    def on_start(lane, slot_idx, req, t_valid, next_rope, logits_row):
        first[req.request_id] = np.array(logits_row, np.float32)
        return start(lane, slot_idx, req, t_valid, next_rope, logits_row)

    def on_collect(lane, flight):
        logits = np.asarray(flight.logits, np.float32)
        for i, s in flight.emitted(lane).items():
            steps.setdefault(s.request.request_id, []).append((s.generated[-1], logits[i]))
        return collect(lane, flight)

    engine._start_slot, engine._decode_collect = on_start, on_collect
    for r in requests:
        engine.add_request(r)
    done = {r.request_id: r for r in engine.run_until_complete()}
    assert sorted(done) == sorted(r.request_id for r in requests)
    return first, steps


def _requests(max_new=5):
    prefix = _ids(12, seed=7)
    return [
        CaptionRequest(
            request_id=f"r{i}", prefix_ids=prefix, prompt_ids=_ids(n, seed=10 + i),
            sampling=SamplingConfig(max_new_tokens=max_new),
        )
        for i, n in enumerate((9, 20, 40))  # 40: three chunks of 16, the last padded
    ]


@pytest.mark.parametrize("mode", ["auto", "auto-kernels", "gather"])
def test_engine_prefills_in_chunks_and_decodes_through_the_latent_pool(params, monkeypatch, mode):
    """Chunked prefill (two requests from the shared prefix's blocks), then
    decode through the pool, against the reference's ONE full forward over
    prompt + generated ids; ``auto-kernels`` with the Pallas kernels in
    interpret mode."""
    if mode == "auto-kernels":
        monkeypatch.setattr(mla_ops, "_on_tpu", lambda: True)
        monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    engine = CaptionEngine(
        CFG, kv_lanes=((64, 4), (128, 2)), paged_attention=mode.split("-")[0], prefill_chunk=16,
        block_size=8, params=jax.tree.map(jnp.copy, params),
    )
    engine.setup(0)
    assert engine._looks_ahead
    requests = _requests()
    first, steps = _serve(engine, requests)
    stats = engine.stats()
    engine.shutdown()
    assert stats["latent_pool_bytes_per_chip"] == stats["kv_pool_bytes_per_chip"] > 0
    assert stats["mla_decode_calls"] > 0 and stats["expert_assignments_held"] > 0
    assert stats["decode_programs_ahead"] > 0
    assert engine.prefix_block_refs > 0  # the second and third request start from the prefix's blocks
    sizes = ref.model_kwargs(CFG)
    compared = 0
    for r in requests:
        fed = [tok for tok, _ in steps[r.request_id][:4]]
        ids = jnp.asarray(r.prefix_ids + r.prompt_ids + fed, jnp.int32)
        t = len(r.prefix_ids) + len(r.prompt_ids)
        want, margin = ref.logits_at(engine.params, ids, list(range(t - 1, t + 4)), **sizes)
        got = [first[r.request_id]] + [row for _, row in steps[r.request_id][:4]]
        for g, w, m in zip(got, np.asarray(want), np.asarray(margin)):
            if m > 0.1:  # under it another choice of expert is rounding, not an error
                compared += 1
                assert np.abs(g - w).max() / np.abs(w).max() < 0.08
    assert compared >= 6


# -- (d) the shares add up ----------------------------------------------------


def test_the_shares_of_the_experts_sum_to_the_uncut_layer(params):
    """Every share of a deployment's experts computes its part of the routed
    sum; the parts, the shared expert counted once, are the layer."""
    whole = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, held=None))
    tree = nn.unbox(_init_params(VLM(whole), 1))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, CFG.dim)), jnp.float32)
    layer = tree["params"]["layer_1"]["moe"]

    def part(cfg, moe_params):
        with jax.default_matmul_precision("highest"):
            return vlm_model.MoEFFN(cfg, dtype=jnp.float32).apply({"params": moe_params}, x)

    uncut = part(whole, layer)
    shared_only = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, held=(0, 1)))
    zero = dict(layer, gate_up=jnp.zeros_like(layer["gate_up"][:1]), down=jnp.zeros_like(layer["down"][:1]))
    shared = part(shared_only, zero)
    total = shared
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, held=(first, 4)))
        share = dict(layer, gate_up=layer["gate_up"][first : first + 4], down=layer["down"][first : first + 4])
        total = total + part(cfg, share) - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=1e-5)
    assert float(jnp.abs(uncut - shared).max()) > 1e-2  # the routed part is not nothing


# -- (e) group-limited routing ------------------------------------------------


def test_group_limited_routing_by_hand_with_a_tie():
    """8 experts in 4 groups of 2, top 2 groups, top 3. Token 0: groups score
    (5, 3, 5, 1) by their best expert: the tie between groups 0 and 2 keeps
    both; expert 3 (score 3) is out although it beats expert 1, whose group
    is in. Token 1: a tie INSIDE the top k goes to the lower index."""
    moe = MoEConfig(
        n_experts=8, top_k=3, hidden=4, n_group=4, topk_group=2, norm_topk_prob=False,
        routed_scaling_factor=2.0, dispatch="sorted",
    )
    scores = np.array([[5.0, 2.0, 1.0, 3.0, 5.0, 4.0, 1.0, 0.5], [1.0, 1.0, 6.0, 0.0, 0.0, 0.0, 1.0, 1.0]])
    probs = scores / scores.sum(axis=1, keepdims=True)
    w, idx = route(moe, jnp.log(jnp.asarray(probs, jnp.float32) + 1e-30))
    assert idx.tolist() == [[0, 4, 5], [2, 0, 1]]
    np.testing.assert_allclose(np.asarray(w), 2.0 * np.take_along_axis(probs, np.asarray(idx), 1), rtol=1e-5)
    renorm = dataclasses.replace(moe, norm_topk_prob=True, routed_scaling_factor=1.0)
    w, _ = route(renorm, jnp.log(jnp.asarray(probs, jnp.float32) + 1e-30))
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), [1.0, 1.0], rtol=1e-5)


# -- (f) YaRN by hand, for the published numbers ------------------------------


def test_yarn_numbers_of_the_published_config():
    mla = VLM_DEEPSEEK_V2_EP8.mla
    m = yarn_mscale(mla.yarn_factor, mla.yarn_mscale_all_dim)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert round(m, 5) == 1.26080 and round(m * m, 5) == 1.58963
    assert round(mla.softmax_scale, 6) == 0.114721
    dim = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(10000))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (10, 23)
    inv = yarn_inv_freq(
        mla.qk_rope_head_dim, 10000.0, mla.yarn_factor, mla.yarn_original_max, mla.yarn_beta_fast, mla.yarn_beta_slow
    )
    f = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)  # extrapolated: as published
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)  # interpolated
    np.testing.assert_allclose(inv[16], f[16] * (1 - 6 / 13) + f[16] / 40 * (6 / 13), rtol=1e-6)
    # the reference's own, computed apart
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(**ref.model_kwargs(VLM_DEEPSEEK_V2_EP8)["attn"]), rtol=1e-7)
    assert mla.cache_width == 640 and VLM_DEEPSEEK_V2_EP8.cache_row_elems == 640


# -- (g) rows are each other's bystanders --------------------------------------


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_idle_and_stale_rows_do_not_move_a_live_row_to_the_bit(params, monkeypatch, kernels):
    """A decode program's rows share no queue: whatever tokens its idle and
    stale rows carry, a live row's logits are the same bits (the look-ahead
    engine's condition, which a ``capacity_factor`` breaks)."""
    if kernels:
        monkeypatch.setattr(mla_ops, "_on_tpu", lambda: True)
        monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    model = VLM(CFG)
    ck, cv = init_cache(CFG, 4, length=16)
    ck = ck + jnp.asarray(np.random.default_rng(0).normal(size=ck.shape), ck.dtype)
    pos = jnp.asarray([5, 0, 3, 9], jnp.int32)

    def logits_of(tokens):
        embeds = model.apply(params, jnp.asarray(tokens, jnp.int32)[:, None], method=model.embed_tokens)
        out, _, _ = model.apply(params, embeds, ck, cv, pos[:, None], pos, pos + 1)
        return np.asarray(out[0, 0])

    a, b = logits_of([17, 3, 3, 3]), logits_of([17, 401, 17, 255])
    assert np.array_equal(a, b)
    assert CFG.moe.capacity_factor is None


# -- a prefill program's rows are bounded ---------------------------------------


def test_a_prefill_program_takes_at_most_max_prefill_rows_prompts(params):
    """A lane's waiting prompts beyond ``max_prefill_rows`` take the next
    program: what keeps a program's scratch bounded where a lane has hundreds
    of slots. Same outputs as the engine that takes them all at once. (Since
    PR 61 the cap is also what a lane's pending chunks are held FOR while a
    lane decodes, ``_prefill_due``: here seven prompts meet an idle engine of
    eight rows and are prefilled whole, two a program, so nothing is ever
    pending beside a decoding row and nothing is held:
    ``tests/models/test_engine_prefill_hold.py`` has the cases that are.)"""
    def serve(cap):
        engine = CaptionEngine(
            CFG, kv_lanes=((64, 8),), prefill_chunk=16, block_size=8, max_prefill_rows=cap,
            params=jax.tree.map(jnp.copy, params), enable_prefix_cache=False,
        )
        engine.setup(0)
        rows = []
        run = engine._prefill_batch
        engine._prefill_batch = lambda *a: (rows.append(a[3].shape[0]), run(*a))[1]
        for i in range(7):
            engine.add_request(CaptionRequest(
                request_id=f"r{i}", prompt_ids=_ids(20 + i, seed=i), sampling=SamplingConfig(max_new_tokens=3),
            ))
        done = {r.request_id: r.text for r in engine.run_until_complete()}
        engine.shutdown()
        return rows, done

    capped_rows, capped = serve(2)
    free_rows, free = serve(None)
    assert max(capped_rows) == 2 and max(free_rows) == 8
    assert capped == free and len(capped) == 7
    assert vlm_model.vlm_flavor("deepseek-v2-ep8").prefill_rows == 8
    with pytest.raises(ValueError, match="max_prefill_rows"):
        CaptionEngine(CFG, max_prefill_rows=0)
