"""The caption engine serving LFM2-MoE (gated short-convolution layers, whose
recurrent store is TAILS ALONE, beside GQA layers with per-head q / k norms and
rope; a sorted dispatch over EVERY expert of a layer, ``MoEConfig.held = None``)
against the plain float32 reference, on seeded weights at the tiny preset:
logits, not tokens. Both families of programs: ``paged`` (the paged programs)
and ``gather`` (attention over gathered views). The helpers are the Granite
hybrid's: the store, its spies and its admission are the same code."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine
from cosmos_curate_tpu.models.vlm import model as vlm_model
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_LFM2_24B_A2B_PP5, VLM_LFM2_MOE_TINY_TEST as CFG, MoEConfig, MoEFFN, ShortConvConfig,
    VLMConfig, init_cache, init_recurrent_store, route, vlm_flavor,
)
from cosmos_curate_tpu.ops import grouped_matmul as gmm_ops
from perfbench.reference import lfm2_moe as ref
from tests.models.test_hybrid_engine import Spy, _ids, _rel, _request, _run

KINDS = ["paged", "gather"]
# bfloat16 activations over five layers at width 64 against float32, at
# positions whose routing is no near-tie: 0.01-0.04 seen; a tail that is stale,
# advanced by padding or reused is off by 0.2+
TOL = 0.08
# the FIRST conv layer's tails in the store against the reference's z_{t-1},
# z_{t-2} (root-mean-square over root-mean-square): nothing but bfloat16's
# rounding of the normed input, the projection and the product stands between
# them: 0.003-0.006 seen; a tail one position off is off by 1.4
TAIL_TOL = 0.012
# ...and every conv layer's (the later ones' inputs have passed experts in bfloat16)
TAILS_TOL = 0.05
# a position is compared where no layer's choice of experts is within this
# share of changing (the reference's routing margin): closer, another choice is
# rounding. All 8 experts are held, so every layer's near-tie counts
MARGIN = 0.02
CHUNK = 16
SIZES = ref.model_kwargs(CFG)


@pytest.fixture(scope="module")
def params():
    tree = nn.unbox(_init_params(VLM(CFG), seed=5))
    for i in range(CFG.moe.first_dense, CFG.n_layers):  # an untrained selection bias is zero: untested
        moe = tree["params"][f"layer_{i}"]["moe"]
        moe["router_bias"] = 0.02 * jax.random.normal(jax.random.key(100 + i), moe["router_bias"].shape)
    return tree


def _build(kind, params, lanes=((128, 4),), chunk=CHUNK):
    engine = CaptionEngine(
        CFG, kv_lanes=lanes, params=jax.tree.map(jnp.copy, params), prefill_chunk=chunk,
        paged_attention="gather" if kind == "gather" else "auto", block_size=8, max_prefill_rows=2,
    )
    engine.setup()
    return engine, Spy(engine)


@pytest.fixture(scope="module")
def engines(params):
    built = {kind: _build(kind, params) for kind in KINDS}
    yield built
    for engine, _ in built.values():
        engine.shutdown()


def _rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean(np.square(got - want))) / np.sqrt(np.mean(np.square(want))))


def _reference(params, ids):
    """(logits at every position, margins, every conv layer's z) of ONE full forward."""
    z = []
    h, margin = ref.forward(params, jnp.asarray(ids, jnp.int32), z=z, **SIZES)
    return np.asarray(ref.logits_of(params, h, **SIZES)), np.asarray(margin), z


def _assert_decode_matches(params, spy, engine_tokens, name, prompt, least=2):
    """The first-step logits and every decode step's against the reference's ONE
    full forward over prompt + generated ids, at the positions whose routing
    margin is wide (at least ``least`` of them), and the tails the request left
    in its row of the store against the reference's ``z`` after the same ids."""
    generated = engine_tokens[name]
    ids = list(prompt) + generated[:-1]
    logits, margin, z = _reference(params, ids)
    at = slice(len(prompt) - 1, len(ids))
    got = np.stack([spy.first[name], *spy.steps.get(name, [])])
    assert got.shape == logits[at].shape
    wide = margin[at] >= MARGIN
    assert wide.sum() >= min(least, len(wide)), margin[at]
    errs = [_rel(g, w) for g, w in zip(got[wide], logits[at][wide])]
    assert max(errs) < TOL, errs
    tails = np.asarray(spy.engine._conv[:, spy.row[name]], np.float32)
    want = np.asarray(ref.tails_after(z, len(ids)))
    assert tails.shape == want.shape == (len(CFG.ssm_layers), 2 * CFG.dim)
    assert _rms(tails[0], want[0]) < TAIL_TOL and _rms(tails, want) < TAILS_TOL


@pytest.mark.parametrize("product", ["ragged-dot", "gmm-k-whole"])
def test_model_against_the_reference_on_logits(params, product, monkeypatch):
    """The model's own forward over a whole prompt (no engine, no cache kept);
    and the same with its experts' products through the Pallas kernel (interpret
    mode), K whole in a tile as every program of a flavor with ``held=None``
    runs them on the chip: the same limit."""
    if product == "gmm-k-whole":
        monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    asked = gmm_ops.tiles.cache_info()
    ids = _ids(11, 48)
    model = VLM(CFG)
    embeds = model.apply(params, jnp.asarray([ids], jnp.int32), method=model.embed_tokens)
    got, *_ = model.apply(
        params, embeds, *init_cache(CFG, 1, length=64), jnp.arange(48)[None], jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 48, jnp.int32),
    )
    now = gmm_ops.tiles.cache_info()  # only the kernel asks for tiles
    assert (now.hits + now.misses > asked.hits + asked.misses) == (product == "gmm-k-whole")
    want, margin, _ = _reference(params, ids)
    wide = margin >= MARGIN
    assert wide.sum() >= 8
    assert max(_rel(g, w) for g, w in zip(np.asarray(got[0], np.float32)[wide], want[wide])) < TOL


@pytest.mark.parametrize("kind", KINDS)
def test_prompt_over_three_chunks_while_another_row_decodes(kind, params, engines):
    """37 tokens in chunks of 16, 16 and 5 (the last padded at its end), the
    chunks interleaved with the decode steps of a request that is already
    running: the pending row is an idle row of those steps, and its tails pass
    from chunk to chunk through the store. (The engine states a cap of 2 rows a
    prefill program; ONE prompt is outstanding and nothing can join it, so
    ``_prefill_due`` dispatches its chunk in every step, as before PR 61.)"""
    engine, spy = engines[kind]
    before = engine.stats()["prefill_tokens"]
    first, long = _ids(1, 12), _ids(2, 37)
    engine.add_request(_request("a", first, max_new=12))
    while not engine.slots:
        engine.step()
    engine.add_request(_request("b", long, max_new=3))
    tokens = _run(engine)
    assert engine.stats()["prefill_tokens"] - before == 12 + 37
    _assert_decode_matches(params, spy, tokens, "b", long)
    _assert_decode_matches(params, spy, tokens, "a", first, least=5)


def test_a_prompt_in_three_chunks_equals_the_same_prompt_in_one(params, engines):
    """The same 37 tokens prefilled whole in one bucket of 64 (an idle engine
    whose chunk holds them) and in chunks of 16 beside a decoding row: three
    programs, one a step, none held back (no prompt waits that could join)."""
    prompt = _ids(2, 37)
    whole, spy_whole = _build("paged", params, chunk=64)
    whole.add_request(_request("w", prompt, max_new=2))
    _run(whole)
    chunked, spy = engines["paged"]
    chunked.add_request(_request("hold", _ids(1, 12), max_new=10))
    while not chunked.slots:
        chunked.step()
    programs = chunked.phase_seconds["prefill_dispatch_n"]
    chunked.add_request(_request("c", prompt, max_new=2))
    _run(chunked)
    assert chunked.phase_seconds["prefill_dispatch_n"] - programs == 3
    assert chunked.phase_seconds["step_held"] == 0
    assert _rel(spy.first["c"], spy_whole.first["w"]) < 0.02  # bfloat16 sums in another order
    tails = [np.asarray(s.engine._conv[:, s.row[n]], np.float32) for s, n in ((spy, "c"), (spy_whole, "w"))]
    assert _rms(*tails) < 0.01
    whole.shutdown()


@pytest.mark.parametrize("kind", KINDS)
def test_padding_does_not_enter_a_tail(kind, params, engines):
    """30 and 19 tokens prefilled whole in ONE program's bucket of 32 on an idle
    engine: each row's tails are its own last two tokens', not the padding's."""
    engine, spy = engines[kind]
    programs = engine.phase_seconds["prefill_dispatch_n"]
    long, short = _ids(3, 30), _ids(4, 19)
    engine.add_request(_request("p", long, max_new=1))
    engine.add_request(_request("q", short, max_new=1))
    _run(engine)
    assert engine.phase_seconds["prefill_dispatch_n"] - programs == 1
    for name, ids in (("p", long), ("q", short)):
        _, _, z = _reference(params, ids)
        tails = np.asarray(engine._conv[:, spy.row[name]], np.float32)
        assert _rms(tails[0], np.asarray(ref.tails_after(z, len(ids)))[0]) < TAIL_TOL
        # one position later (what a tail that took a padded position in would hold) is far off
        assert _rms(tails[0], np.asarray(ref.tails_after(z, len(ids) - 1))[0]) > 0.5


@pytest.mark.parametrize("kind", KINDS)
def test_prefix_snapshot_against_the_same_request_unshared(kind, params, engines):
    """A request that starts from the shared prefix's blocks and TAILS snapshot,
    twice (the build, then a hit), against the same ids unshared."""
    engine, spy = engines[kind]
    snapshots, hits = engine.stats()["prefix_state_snapshots"], engine.prefix_cache_hits
    prefix, prompt = _ids(4, 16), _ids(5, 13)
    for name, share in (("build", True), ("hit", True), ("unshared", False)):
        engine.add_request(_request(name, prompt, prefix=prefix, max_new=4, share=share))
        tokens = _run(engine)
        _assert_decode_matches(params, spy, tokens, name, prefix + prompt)
    assert engine.stats()["prefix_state_snapshots"] - snapshots == 2 and engine.prefix_cache_hits > hits
    assert _rel(spy.first["hit"], spy.first["unshared"]) < TOL / 2


@pytest.mark.parametrize("kind", KINDS)
def test_sixteen_decode_steps_through_pool_and_tails(kind, params, engines):
    """One active row of a four-slot lane, three idle; the counters by kind."""
    engine, spy = engines[kind]
    for seed in range(7, 17):  # (the first seeded prompt whose seventeen tokens hold no EOS)
        before, dispatched = engine.stats(), engine.phase_seconds["decode_dispatch_n"]
        prompt = _ids(seed, 20)
        spy.steps.pop("d", None)
        engine.add_request(_request("d", prompt, max_new=17))
        tokens = _run(engine)
        if len(tokens["d"]) == 17:
            break
    assert len(spy.steps["d"]) == 16
    _assert_decode_matches(params, spy, tokens, "d", prompt, least=4)
    stats = engine.stats()
    # a short convolution calls no recurrence: neither family's counter moves
    assert [stats[k] - before[k] for k in ("ssm_decode_calls", "delta_decode_calls", "delta_prefill_chunks")] == [0, 0, 0]
    assert stats["recurrent_rows_total"] == 4 and stats["recurrent_rows_used_peak"] >= 1
    assert engine._ssm.shape == (4, 5, 0) and engine._ssm.nbytes == 0  # the store is tails alone
    assert stats["conv_tail_bytes_per_chip"] == stats["recurrent_state_bytes_per_chip"] == engine._conv.nbytes
    assert engine._conv.shape == (4, 5, 2 * CFG.dim) and engine._conv.dtype == jnp.bfloat16
    # every expert is held: the device's count is every assignment of every row (idle rows' too)
    programs = engine.phase_seconds["decode_dispatch_n"] - dispatched
    sparse = CFG.n_layers - CFG.moe.first_dense
    assert stats["expert_assignments_held"] - before["expert_assignments_held"] == programs * 4 * CFG.moe.top_k * sparse
    assert stats["expert_assignments_held_live"] - before["expert_assignments_held_live"] == 16 * CFG.moe.top_k * sparse
    np.testing.assert_array_equal(np.asarray(engine._conv[:, 0], np.float32), 0.0)  # the garbage row never moves


def test_slot_reused_after_a_longer_tenant(params):
    """One slot; the second tenant must not inherit the first's tails."""
    engine, spy = _build("paged", params, lanes=((128, 1),))
    long, short = _ids(7, 40), _ids(8, 9)
    engine.add_request(_request("long", long, max_new=8))
    _run(engine)
    engine.add_request(_request("short", short, max_new=5))
    _assert_decode_matches(params, spy, _run(engine), "short", short)
    engine.shutdown()


def test_paged_engine_agrees_with_gather_engine(engines):
    prompt, prefix = _ids(9, 60), _ids(10, 8)
    firsts, tokens = {}, {}
    for kind in KINDS:
        engine, spy = engines[kind]
        engine.add_request(_request("x", prompt, prefix=prefix, max_new=6))
        tokens[kind] = _run(engine)["x"]
        firsts[kind] = spy.first["x"]
    assert _rel(firsts["paged"], firsts["gather"]) < TOL / 2
    assert tokens["paged"] == tokens["gather"]


def _layer_params(rng, d, h, e):
    return {
        "router": {"kernel": jnp.asarray(rng.normal(size=(d, e)) / d**0.5, jnp.float32)},
        "router_bias": jnp.asarray(0.02 * rng.normal(size=(e,)), jnp.float32),
        "gate_up": jnp.asarray(rng.normal(size=(e, d, 2 * h)) / d**0.5, jnp.float32),
        "down": jnp.asarray(rng.normal(size=(e, h, d)) / h**0.5, jnp.float32),
    }


def test_the_routers_choice_uses_the_bias_and_the_weights_do_not():
    """A bias that lifts expert 5 over every score makes it everyone's first
    choice; its WEIGHT stays its own sigmoid over (the chosen scores' sum +
    1e-6), in the program's ``route`` as in the reference's."""
    moe = CFG.moe
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(32, moe.n_experts)), jnp.float32)
    bias = jnp.zeros(moe.n_experts).at[5].set(10.0)
    w, idx = route(moe, logits, bias)
    w0, idx0 = route(moe, logits, jnp.zeros(moe.n_experts))
    assert (np.asarray(idx)[:, 0] == 5).all() and not (np.asarray(idx0)[:, 0] == 5).all()
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(np.asarray(w), chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert (np.asarray(w).sum(-1) < 1.0).all()  # the 1e-6 is in the sum (afmoe's 1e-20 would read 1 to the last bit)
    # the reference's router on the same logits (an identity router kernel)
    eye = {"router": {"kernel": jnp.eye(moe.n_experts)}, "router_bias": bias}
    w_ref, idx_ref, *_ = ref.route(logits, eye, moe=SIZES["moe"])
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-6)
    # the renormalisation's constant is the model's: the older sigmoid flavors keep afmoe's
    assert (moe.norm_topk_eps, MoEConfig().norm_topk_eps, vlm_model.VLM_TRINITY_TINY_TEST.moe.norm_topk_eps) == (1e-6, 1e-20, 1e-20)


@pytest.mark.parametrize("seed", [0, 7, 23])
def test_held_none_is_the_uncut_layer_and_the_eight_shares_add_up_to_it(seed):
    """The guide's share test on the layer this cell runs WHOLE: the program's
    expert layer with ``held = None`` is the reference's uncut layer, and the
    parts that eight chips holding one expert each (``held = (j, 1)`` of 8 here)
    would give add up to it."""
    rng = np.random.default_rng(seed)
    d, h, e = CFG.dim, CFG.moe.hidden, CFG.moe.n_experts
    n = jnp.asarray(rng.normal(size=(24, d)), jnp.float32)
    whole = _layer_params(rng, d, h, e)
    with jax.default_matmul_precision("highest"):
        uncut, *_ = ref.experts(n, whole, moe=SIZES["moe"])

        def program(held, tree):
            cfg = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, held=held))
            return MoEFFN(cfg, dtype=jnp.float32, param_dtype=jnp.float32).apply({"params": tree}, n[None])[0]

        scale = float(np.abs(uncut).max())
        np.testing.assert_allclose(np.asarray(program(None, whole)), np.asarray(uncut), rtol=0, atol=2e-5 * scale)
        total = sum(
            program((j, e // 8), dict(whole, gate_up=whole["gate_up"][j : j + e // 8], down=whole["down"][j : j + e // 8]))
            for j in range(0, e, e // 8)
        )
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=0, atol=2e-5 * scale)


def test_the_store_is_tails_alone():
    ssm, conv = jax.eval_shape(lambda: init_recurrent_store(CFG, 5))
    assert ssm.shape == (4, 5, 0) and conv.shape == (4, 5, 2 * CFG.dim) and conv.dtype == jnp.bfloat16
    # the issue's numbers: 8 conv layers x 2 x 2,048 values x 2 B = 64 KiB a row, 17 MB for 265 rows
    ssm, conv = jax.eval_shape(lambda: init_recurrent_store(VLM_LFM2_24B_A2B_PP5, 265))
    assert ssm.shape == (8, 265, 0) and conv.shape == (8, 265, 4096)
    assert 8 * 4096 * 2 == 64 * 2**10 and 16e6 < 265 * 8 * 4096 * 2 < 18e6


def test_published_preset_and_flavors():
    """Every published width, the first ten layers (c c | A c c c | A c c c),
    every expert held, and the flavor's serving fields."""
    cfg = VLM_LFM2_24B_A2B_PP5
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab) == (2048, 10, 32, 8, 64, 65536)
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv")
    assert int(cfg.dim * cfg.hidden_mult) == 11776 and cfg.rope_theta == 1e6 and cfg.use_rope and cfg.rms_eps == 1e-5
    assert cfg.tied_embeddings and cfg.qk_norm and not cfg.qk_norm_whole and not cfg.qkv_bias and not cfg.attention_gate
    assert (cfg.pre_norm, cfg.sandwich_norm, cfg.max_seq) == (True, False, 4096)
    assert cfg.short_conv == ShortConvConfig(l_cache=3) and cfg.recurrent_kind == "conv"
    assert cfg.ssm_layers == (0, 1, 3, 4, 5, 7, 8, 9) and cfg.kv_layers == (2, 6)
    e = cfg.moe
    assert (e.n_experts, e.top_k, e.hidden, e.shared_hidden, e.first_dense, e.held) == (64, 4, 1536, 0, 2, None)
    assert e.held_experts == (0, 64)
    assert (e.score_func, e.selection_bias, e.norm_topk_prob, e.routed_scaling_factor, e.dispatch, e.norm_topk_eps) == (
        "sigmoid", True, True, 1.0, "sorted", 1e-6)
    assert e.router_precision == "highest"
    flavor = vlm_flavor("lfm2-24b-a2b-pp5")
    assert flavor.cfg is cfg and flavor.text_only and flavor.require_weights and flavor.model_chips == 1
    assert flavor.kv_lanes == ((1024, 256), (4096, 8)) and flavor.prefill_rows == 8
    tiny = vlm_flavor("lfm2-moe-tiny-test")
    assert tiny.cfg is CFG and not tiny.require_weights and CFG.recurrent_kind == "conv"
    # the issue's arithmetic of the fit, from the shapes: 5.27 B parameters
    shapes = jax.eval_shape(
        lambda: VLM(cfg, param_dtype=jnp.bfloat16).init(
            jax.random.key(0), jnp.zeros((1, 1, 32, 32, 3), jnp.uint8), jnp.zeros((1, 4), jnp.int32),
            *init_cache(cfg, 1, length=64), method=VLM.init_everything,
        )
    )
    lm = {k: v for k, v in nn.unbox(shapes)["params"].items() if k.startswith(("layer_", "embed", "ln_f"))}
    assert abs(sum(x.size for x in jax.tree.leaves(lm)) / 1e9 - 5.27) < 0.01


def test_the_mixers_parameters_and_serving_types(params):
    p = params["params"]
    assert set(p["layer_0"]["mixer"]) == {"in_proj", "out_proj", "conv_kernel"}  # conv_bias false: no bias anywhere
    assert set(p["layer_0"]["mixer"]["in_proj"]) == {"kernel"}
    assert p["layer_0"]["mixer"]["in_proj"]["kernel"].shape == (CFG.dim, 3 * CFG.dim)
    assert p["layer_0"]["mixer"]["conv_kernel"].shape == (3, CFG.dim)
    assert {"up", "gate", "down"} <= set(p["layer_0"]) and "moe" not in p["layer_0"]  # the leading dense layer
    assert {"q", "k", "v", "o", "q_norm", "k_norm", "moe"} <= set(p["layer_1"]) and "mixer" not in p["layer_1"]
    for i in range(1, CFG.n_layers):
        moe = p[f"layer_{i}"]["moe"]
        assert moe["gate_up"].shape == (8, CFG.dim, 64) and "shared_up" not in moe and "router_bias" in moe
    assert "lm_head" not in p  # the tied head
    engine = CaptionEngine(CFG, kv_lanes=((64, 1),), params=jax.tree.map(jnp.copy, params), block_size=8)
    engine.setup()
    served = engine.params["params"]["layer_2"]
    assert served["mixer"]["in_proj"]["kernel"].dtype == jnp.bfloat16 and served["moe"]["gate_up"].dtype == jnp.bfloat16
    small = [served["mixer"]["conv_kernel"], served["moe"]["router"]["kernel"], served["moe"]["router_bias"]]
    assert {x.dtype for x in small} == {jnp.dtype("float32")}
    engine.shutdown()


def test_a_conv_layer_without_its_sizes_and_two_recurrent_kinds_are_refused():
    with pytest.raises(ValueError, match="short_conv=.*l_cache"):
        dataclasses.replace(CFG, short_conv=None)
    for other in ("mamba", "linear_attention"):
        with pytest.raises(ValueError, match="one kind of state.*tails alone"):
            VLMConfig(
                n_layers=2, layer_types=("conv", other), short_conv=ShortConvConfig(),
                mamba=vlm_model.Mamba2Config(), gated_delta=vlm_model.GatedDeltaConfig(),
            )
    with pytest.raises(ValueError, match="short-convolution tails.*model_chips=1"):
        vlm_model.FlavorSpec(CFG, "x", model_chips=2)


@pytest.mark.parametrize("preset", ["VLM_TINY_TEST", "VLM_GRANITE_HYBRID_TINY_TEST", "VLM_SOLAR_OPEN2_TINY_TEST", "VLM_TRINITY_TINY_TEST"])
def test_a_flavor_without_conv_layers_builds_the_programs_it_built(preset):
    """The new kind adds no array, no scope and no constant to a flavor that has
    no "conv" layer: its store's shapes, its decode program's compiled scopes
    and its router's constant are what they were."""
    cfg = getattr(vlm_model, preset)
    assert cfg.short_conv is None and cfg.recurrent_kind != "conv"
    if cfg.ssm_layers:
        ssm, conv = jax.eval_shape(lambda: init_recurrent_store(cfg, 3))
        m = cfg.gated_delta or cfg.mamba
        assert ssm.size > 0 and conv.shape == (len(cfg.ssm_layers), 3, (m.d_conv - 1) * m.conv_dim)
    engine = CaptionEngine(cfg, kv_lanes=((64, 2),), block_size=8)
    engine.setup()
    lane = engine.lanes[0]
    zeros = jnp.zeros(lane.n_slots, jnp.int32)
    args = [engine.params, *engine._pools(), jnp.zeros_like(jnp.asarray(lane.table)), zeros, zeros, zeros]
    if cfg.window_layers:
        args[3] = (jnp.zeros_like(jnp.asarray(lane.table)), jnp.zeros_like(jnp.asarray(lane.wtable)))
    if engine._recurrent:
        args += [engine._ssm, engine._conv, zeros]
    if engine._expert_held is not None:
        args.append(engine._expert_held)
    text = engine._decode.lower(*args).as_text(debug_info=True)
    assert "mixer.short_conv" not in text
    # ...and hands out what it handed out: no flavor but LFM2's says `hand_out_choice`
    outputs = jax.eval_shape(engine._decode, *args)
    assert len(outputs) == 4 + 2 * bool(engine._recurrent) + (engine._expert_held is not None)
    stats = engine.stats()
    assert stats["conv_tail_bytes_per_chip"] == (engine._conv.nbytes if engine._recurrent else 0)
    if cfg.moe is not None and cfg.moe.score_func == "sigmoid":
        jaxpr = str(jax.make_jaxpr(lambda x: route(cfg.moe, x, None))(jnp.zeros((2, cfg.moe.n_experts))))
        assert str(float(np.float32(1e-20))) in jaxpr and str(float(np.float32(1e-6))) not in jaxpr
    engine.shutdown()


def test_the_programs_hand_out_what_their_routers_chose(params):
    """``MoEConfig.hand_out_choice``: the prefill, decode and prefix programs'
    LAST output is every token's experts in every sparse layer; the float32
    reference that FOLLOWS that choice then agrees with the engine at EVERY
    position to bfloat16 rounding, near-ties and all, and where its own margin
    is wide its own choice is the program's."""
    assert CFG.moe.hand_out_choice and VLM_LFM2_24B_A2B_PP5.moe.hand_out_choice
    engine = CaptionEngine(CFG, kv_lanes=((128, 4),), params=jax.tree.map(jnp.copy, params), prefill_chunk=32, block_size=8)
    engine.setup()
    sparse, k = CFG.n_layers - CFG.moe.first_dense, CFG.moe.top_k
    lane = engine.lanes[0]
    zeros, ids = jnp.zeros(2, jnp.int32), _ids(31, 24)
    embeds = engine._embed_tokens(engine.params, jnp.asarray([ids, ids], jnp.int32))
    table = np.zeros((2, lane.length // 8), np.int32)
    table[0, :3], table[1, :3] = [1, 2, 3], [4, 5, 6]
    rows = jnp.asarray([1, 2], jnp.int32)
    rope = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    logits, pk, pv, ssm, conv, choice = engine._prefill_batch(
        engine.params, engine._pool_k, engine._pool_v, jnp.asarray(table), embeds.astype(jnp.float32), zeros,
        jnp.asarray([24, 20], jnp.int32), rope, None, engine._ssm, engine._conv, rows,
    )
    assert choice.shape == (sparse, 2, 24, k) and choice.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(choice[:, 0, :20]), np.asarray(choice[:, 1, :20]))  # a row's choice is its own tokens'
    own, margins = [], []
    follow = jnp.asarray(np.asarray(choice[:, 0]))
    h, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), choices=own, margins=margins, follow=follow, **SIZES)
    want = np.asarray(ref.logits_of(params, h[-1:], **SIZES))[0]
    assert _rel(np.asarray(logits[0], np.float32), want) < 0.03  # (TOL is 0.08 at positions picked for their margins)
    other = ~(np.sort(np.asarray(choice[:, 0]), -1) == np.sort(np.stack([np.asarray(c) for c in own]), -1)).all(-1)
    wide = np.stack([np.asarray(m) for m in margins[CFG.moe.first_dense:]]) >= MARGIN
    assert wide.mean() > 0.5 and not (other & wide).any()
    # following its own choice, or none, the reference is what it was; following another, it is not
    alone, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), **SIZES)
    same, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), follow=jnp.stack(own), **SIZES)
    none, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), follow=jnp.full((sparse, 24, k), -1), **SIZES)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(same))
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(none))
    moved, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), follow=(jnp.stack(own) + 1) % CFG.moe.n_experts, **SIZES)
    assert _rel(np.asarray(moved[-1]), np.asarray(alone[-1])) > 1e-3  # (seeded tables at width 64 add little to the stream)
    # the decode program: one position a row; the prefix's build: its positions
    out = engine._decode(engine.params, pk, pv, jnp.asarray(np.zeros_like(lane.table)), jnp.zeros(4, jnp.int32),
                         jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32), ssm, conv, jnp.zeros(4, jnp.int32), engine._expert_held)
    assert len(out) == 8 and out[-1].shape == (sparse, 4, 1, k) and out[-2].shape == (4,)
    built = engine._prefix_prefill(engine.params, embeds[:1, :16].astype(jnp.float32), rope[:1, :16], jnp.asarray(12, jnp.int32))
    assert len(built) == 5 and built[-1].shape == (sparse, 16, k)
    np.testing.assert_array_equal(np.asarray(built[-1][:, :12]), np.asarray(choice[:, 0, :12]))
    engine.shutdown()


def test_hand_out_choice_is_a_sorted_dispatchs_and_a_paged_programs():
    with pytest.raises(ValueError, match="hand_out_choice"):
        MoEConfig(hand_out_choice=True)  # the queue dispatch
    # without a recurrent store the PAGED programs hand it out (tests/models/test_mellum2_windowed.py): not `gather`
    cfg = dataclasses.replace(
        vlm_model.VLM_MOE_TINY_TEST, moe=MoEConfig(n_experts=4, top_k=2, hidden=32, dispatch="sorted", hand_out_choice=True)
    )
    with pytest.raises(ValueError, match="the paged programs hand it out"):
        CaptionEngine(cfg, kv_lanes=((64, 2),), block_size=8, paged_attention="gather").setup()
