"""The caption engine serving Solar-Open2 (Kimi Delta Attention's state in the
recurrent store BESIDE a sorted dispatch over held experts, one gated attention
layer without rope) against the plain float32 reference, on seeded weights at
the tiny preset: logits, not tokens. Both families of programs: ``kernel`` (the
paged programs with ops/delta_rule.py forced onto its TPU side: the chunked scan
with its sub-blocks and the Pallas decode kernel with its third column in
interpret mode) and ``gather`` (the recurrence in plain XLA). The helpers are
the Granite hybrid's: the store, its spies and its admission are the same code."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_OLMO_HYBRID_TINY_TEST, VLM_SOLAR_OPEN2_EP8, VLM_SOLAR_OPEN2_TINY_TEST as CFG,
    init_recurrent_store, vlm_flavor,
)
from cosmos_curate_tpu.ops import delta_rule as delta_ops
from perfbench.reference import solar_open2 as ref
from tests.models.test_hybrid_engine import KINDS, Spy, _ids, _rel, _request, _run

# bfloat16 activations over four layers at width 64 against float32, at
# positions whose routing is no near-tie: 0.02-0.05 seen; a state that is
# stale, advanced by padding or reused is off by 0.3+
TOL = 0.09
# the first linear-attention layer's state in the store against the
# reference's (root-mean-square over root-mean-square): its inputs have passed
# the attention layer and its experts in bfloat16: 0.015-0.022 seen; padding
# that advances it, a token taken twice or a stale row are off by 0.09 and more
STATE_TOL = 0.04
# a position is compared where no layer's choice of held experts is within
# this share of changing (the reference's routing margin): closer, another
# choice is rounding
MARGIN = 0.05
CHUNK = 40  # a prefill chunk: a scan chunk of 32 (two sub-blocks of 16) and a quarter of the next


@pytest.fixture(scope="module")
def params():
    tree = nn.unbox(_init_params(VLM(CFG), seed=5))
    for i in range(CFG.n_layers):  # an untrained selection bias is zero: its addition would go untested
        moe = tree["params"][f"layer_{i}"]["moe"]
        moe["router_bias"] = 0.02 * jax.random.normal(jax.random.key(100 + i), moe["router_bias"].shape)
    return tree


def _build(kind, params, lanes=((128, 4),)):  # one lane: a long prompt meets the rows that decode
    engine = CaptionEngine(
        CFG, kv_lanes=lanes, params=jax.tree.map(jnp.copy, params), prefill_chunk=CHUNK,
        paged_attention="gather" if kind == "gather" else "auto", block_size=8, max_prefill_rows=2,
    )
    engine.setup()
    return engine, Spy(engine)


@pytest.fixture(scope="module")
def engines(params):
    """One engine a family of programs for the whole file: ops/delta_rule.py is
    on its TPU side throughout, which the ``gather`` programs never ask."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(delta_ops, "_on_tpu", lambda: True)
        built = {kind: _build(kind, params) for kind in KINDS}
        yield built
        for engine, _ in built.values():
            engine.shutdown()


def _rms(got, want):
    return float(np.sqrt(np.mean(np.square(got - want))) / np.sqrt(np.mean(np.square(want))))


def _assert_decode_matches(params, spy, engine_tokens, name, prompt, least=2):
    """The first-step logits and every decode step's against the reference's ONE
    full forward over prompt + generated ids, at the positions whose routing
    margin is wide (at least ``least`` of them), and the state the request left
    in its row of the store against the reference's after the same ids."""
    generated = engine_tokens[name]
    ids = jnp.asarray(list(prompt) + generated[:-1], jnp.int32)
    sizes = ref.model_kwargs(CFG)
    want, margin = ref.logits_at(params, ids, list(range(len(prompt) - 1, ids.shape[0])), **sizes)
    got = np.stack([spy.first[name], *spy.steps.get(name, [])])
    assert got.shape == want.shape
    wide = np.asarray(margin) >= MARGIN
    assert wide.sum() >= min(least, len(wide)), np.asarray(margin)
    errs = [_rel(g, w) for g, w in zip(got[wide], np.asarray(want)[wide])]
    assert max(errs) < TOL, errs
    state = delta_ops.unpack_state(spy.engine._ssm[0, spy.row[name]], CFG.gated_delta.n_heads)
    assert _rms(np.asarray(state), np.asarray(ref.first_ssm_state(params, ids, **sizes))) < STATE_TOL


def _since(engine, before, *keys):
    now = engine.stats()
    return [now[k] - before[k] for k in keys]


@pytest.mark.parametrize("kind", KINDS)
def test_prompt_over_prefill_chunks_while_another_row_decodes(kind, params, engines):
    """100 tokens in chunks of 40, 40 and 20 (the last padded at its end; a scan
    chunk is 32, a sub-block 16), the chunks interleaved with the decode steps
    of a request that is already running: the pending row is an idle row of
    those steps, and the experts' count rides in them. (A cap of 2 rows a
    prefill program is stated; with ONE prompt outstanding and none in line
    ``_prefill_due`` holds nothing: a chunk program every step, as before PR 61.)"""
    engine, spy = engines[kind]
    before = engine.stats()
    first, long = _ids(1, 12), _ids(2, 100)
    engine.add_request(_request("a", first, max_new=12))
    while not engine.slots:
        engine.step()
    engine.add_request(_request("b", long, max_new=3))
    tokens = _run(engine)
    # scan chunks of 32 that held a token: ceil(12 / 32), then 2 + 2 + 1, a linear layer each
    assert _since(engine, before, "prefill_tokens", "delta_prefill_chunks") == [12 + 100, (1 + 5) * len(CFG.ssm_layers)]
    assert engine.phase_seconds["step_held"] == 0
    _assert_decode_matches(params, spy, tokens, "b", long)
    _assert_decode_matches(params, spy, tokens, "a", first, least=6)


@pytest.mark.parametrize("kind", KINDS)
def test_two_requests_share_a_program_with_different_lengths(kind, params, engines):
    """30 and 19 tokens prefilled whole in ONE program's bucket of 32 on an idle
    engine: each row's padding must leave its state where its last token did."""
    engine, spy = engines[kind]
    programs = engine.phase_seconds["prefill_dispatch_n"]
    long, short = _ids(3, 30), _ids(4, 19)
    engine.add_request(_request("p", long, max_new=4))
    engine.add_request(_request("q", short, max_new=4))
    tokens = _run(engine)
    assert engine.phase_seconds["prefill_dispatch_n"] - programs == 1
    _assert_decode_matches(params, spy, tokens, "p", long)
    _assert_decode_matches(params, spy, tokens, "q", short)


@pytest.mark.parametrize("kind", KINDS)
def test_prefix_snapshot_against_the_same_request_unshared(kind, params, engines):
    """A request that starts from the shared prefix's blocks and state snapshot,
    twice (the build, then a hit), against the same ids unshared."""
    engine, spy = engines[kind]
    before, hits = engine.stats(), engine.prefix_cache_hits
    prefix, prompt = _ids(4, 16), _ids(5, 13)
    for name, share in (("build", True), ("hit", True), ("unshared", False)):
        engine.add_request(_request(name, prompt, prefix=prefix, max_new=4, share=share))
        tokens = _run(engine)
        _assert_decode_matches(params, spy, tokens, name, prefix + prompt)
    assert _since(engine, before, "prefix_state_snapshots") == [2] and engine.prefix_cache_hits > hits
    assert _rel(spy.first["hit"], spy.first["unshared"]) < TOL / 2


@pytest.mark.parametrize("kind", KINDS)
def test_sixteen_decode_steps_through_store_and_pool(kind, params, engines):
    """One active row of a four-slot lane, three idle; the counters by kind."""
    engine, spy = engines[kind]
    before = engine.stats()
    prompt = _ids(7, 20)  # (a prompt whose seventeen tokens hold no EOS)
    engine.add_request(_request("d", prompt, max_new=17))
    tokens = _run(engine)
    assert len(spy.steps["d"]) == 16
    _assert_decode_matches(params, spy, tokens, "d", prompt, least=4)
    assert _since(engine, before, "delta_decode_calls", "ssm_decode_calls") == [16 * len(CFG.ssm_layers), 0]
    stats = engine.stats()
    assert stats["recurrent_rows_total"] == 4 and stats["recurrent_rows_used_peak"] >= 1
    assert stats["recurrent_state_bytes_per_chip"] == engine._ssm.nbytes + engine._conv.nbytes
    assert stats["expert_assignments_held"] > 0  # the recurrent decode program's rider counts
    # the three idle rows' token is routed and multiplied too: the live row's count stands apart
    assert 0 < stats["expert_assignments_held_live"] <= stats["expert_assignments_held"]
    np.testing.assert_array_equal(np.asarray(engine._ssm[:, 0]), 0.0)  # the garbage row never moves


@pytest.mark.parametrize("kind", KINDS)
def test_held_assignments_equal_the_hosts_own_count(kind, params, engines):
    """A lane of ONE slot, so that a decode program is the one live row: the
    device's count over the decode programs (the rider of the recurrent decode
    program) against the reference's router on the same ids, position by
    position and layer by layer, on a request none of whose decode positions'
    routing is a near-tie (the first such of a few seeded prompts; at 16
    experts of which 2 are held most positions have a rival within 5%, so the
    bar here is 2%)."""
    del engines  # (the module's patch: the kernel side)
    engine, _ = _build(kind, params, lanes=((128, 1),))
    sizes = ref.model_kwargs(CFG)
    try:
        for seed in range(20, 40):
            prompt = _ids(seed, 15)
            before = engine.stats()
            engine.add_request(_request(f"h{seed}", prompt, max_new=4))
            generated = _run(engine)[f"h{seed}"]
            ids = jnp.asarray(prompt + generated[:-1], jnp.int32)
            held = []
            _, margin = ref.forward(params, ids, held=held, **sizes)
            steps = slice(len(prompt), len(ids))  # the positions the decode programs took in
            if float(np.asarray(margin)[steps].min()) < 0.02:
                continue
            assert len(held) == CFG.n_layers
            want = int(sum(np.asarray(h)[steps].sum() for h in held))
            programs, counted, live = _since(
                engine, before, "paged_kernel_steps", "expert_assignments_held", "expert_assignments_held_live"
            )
            assert counted == live == want and want > 0 and (kind == "gather" or programs == 3)
            return
        pytest.fail("no seeded prompt whose decode positions all route with a wide margin")
    finally:
        engine.shutdown()


def test_slot_reused_after_a_longer_tenant(params, engines):
    """One slot; the second tenant must not inherit the first's state."""
    del engines  # (the module's patch: the kernel side)
    engine, spy = _build("kernel", params, lanes=((128, 1),))
    long, short = _ids(7, 40), _ids(8, 9)
    engine.add_request(_request("long", long, max_new=8))
    _run(engine)
    engine.add_request(_request("short", short, max_new=5))
    _assert_decode_matches(params, spy, _run(engine), "short", short)
    engine.shutdown()


def test_kernel_engine_agrees_with_gather_engine(engines):
    prompt, prefix = _ids(9, 60), _ids(10, 8)
    firsts, tokens = {}, {}
    for kind in KINDS:
        engine, spy = engines[kind]
        engine.add_request(_request("x", prompt, prefix=prefix, max_new=6))
        tokens[kind] = _run(engine)["x"]
        firsts[kind] = spy.first["x"]
    assert _rel(firsts["kernel"], firsts["gather"]) < TOL / 2
    assert tokens["kernel"] == tokens["gather"]


@pytest.mark.parametrize("seed", [0, 7, 23])
def test_the_eight_shares_add_up_to_the_uncut_layer(seed):
    """The guide's share test: the routed parts of the eight chips that share a
    layer (two consecutive experts each of 16 here) plus the shared expert
    counted ONCE are the uncut layer of the reference, and a share's tables
    are the uncut layer's rows."""
    rng = np.random.default_rng(seed)
    d, h, e = CFG.dim, CFG.moe.hidden, CFG.moe.n_experts
    n = jnp.asarray(rng.normal(size=(24, d)), jnp.float32)
    whole = {
        "router": {"kernel": jnp.asarray(rng.normal(size=(d, e)) / d**0.5, jnp.float32)},
        "router_bias": jnp.asarray(0.02 * rng.normal(size=(e,)), jnp.float32),
        "gate_up": jnp.asarray(rng.normal(size=(e, d, 2 * h)) / d**0.5, jnp.float32),
        "down": jnp.asarray(rng.normal(size=(e, h, d)) / h**0.5, jnp.float32),
        **{f"shared_{name}": {"kernel": jnp.asarray(rng.normal(size=shape) / shape[0] ** 0.5, jnp.float32)}
           for name, shape in (("gate", (d, h)), ("up", (d, h)), ("down", (h, d)))},
    }
    moe = ref.model_kwargs(CFG)["moe"]
    with jax.default_matmul_precision("highest"):
        uncut, _, held = ref.experts(n, whole, moe=dict(moe, held=(0, e)))
        assert np.asarray(held).tolist() == [CFG.moe.top_k] * 24
        shared = ref._swiglu(n, whole["shared_gate"]["kernel"], whole["shared_up"]["kernel"], whole["shared_down"]["kernel"])
        total, assignments = shared, 0
        for share in range(8):
            first = share * e // 8
            own = dict(whole, gate_up=whole["gate_up"][first : first + e // 8], down=whole["down"][first : first + e // 8])
            part, _, held = ref.experts(n, own, moe=dict(moe, held=(first, e // 8)), with_shared=False)
            total, assignments = total + part, assignments + int(np.asarray(held).sum())
    assert assignments == 24 * CFG.moe.top_k  # every assignment lands on exactly one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=0, atol=2e-5 * float(np.abs(uncut).max()))


def test_the_store_is_sized_by_the_mixers_kind():
    m = CFG.gated_delta
    ssm, conv = jax.eval_shape(lambda: init_recurrent_store(CFG, 5))
    assert ssm.shape == (3, 5, m.key_dim, m.n_heads * m.value_dim) and ssm.dtype == jnp.float32
    assert conv.shape == (3, 5, 3 * m.n_heads * (2 * m.key_dim + m.value_dim)) and conv.dtype == jnp.bfloat16
    # the issue's numbers: [3, rows + 1, 128, 8192] float32 (12 MiB a row) and three tails of 3 x 24,576
    ssm, conv = jax.eval_shape(lambda: init_recurrent_store(VLM_SOLAR_OPEN2_EP8, 265))
    assert ssm.shape == (3, 265, 128, 8192) and conv.shape == (3, 265, 3 * 24576)
    assert 3 * 128 * 8192 * 4 == 12 * 2**20


def test_published_preset_and_flavors():
    """Every published width, one period G K K K, the share held, and the
    flavor's serving fields."""
    cfg = VLM_SOLAR_OPEN2_EP8
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab) == (4096, 4, 64, 8, 128, 24576)
    assert cfg.layer_types == ("full_attention", "linear_attention", "linear_attention", "linear_attention")
    assert not cfg.use_rope and cfg.attention_gate and not cfg.tied_embeddings and cfg.rms_eps == 1e-5
    assert (cfg.pre_norm, cfg.sandwich_norm, cfg.qk_norm, cfg.qk_norm_whole, cfg.qkv_bias) == (True, False, False, False, False)
    m = cfg.gated_delta
    assert (m.n_heads, m.key_dim, m.value_dim, m.d_conv, m.allow_neg_eigval, m.chunk) == (64, 128, 128, 4, True, 64)
    assert (m.decay_rank, m.gate_rank) == (128, 128) and m.conv_dim == 24576
    e = cfg.moe
    assert (e.n_experts, e.top_k, e.hidden, e.shared_hidden, e.first_dense, e.held) == (320, 8, 1280, 1280, 0, (0, 40))
    assert (e.score_func, e.selection_bias, e.norm_topk_prob, e.routed_scaling_factor, e.dispatch) == (
        "sigmoid", True, True, 1.0, "sorted")
    flavor = vlm_flavor("solar-open2-ep8")
    assert flavor.cfg is cfg and flavor.text_only and flavor.require_weights and flavor.model_chips == 1
    assert flavor.kv_lanes == ((1024, 256), (4096, 8)) and flavor.prefill_rows == 8
    tiny = vlm_flavor("solar-open2-tiny-test")
    assert tiny.cfg is CFG and not tiny.require_weights and CFG.recurrent_kind == "linear_attention"
    # the defaults are every other flavor's mixer
    assert VLM_OLMO_HYBRID_TINY_TEST.gated_delta.decay_rank is None and VLM_OLMO_HYBRID_TINY_TEST.gated_delta.gate_rank is None


def test_the_mixers_parameters_and_serving_types(params):
    """Low-rank decay and gate pairs in place of ``a_proj`` / ``g_proj``, a decay
    bias a channel and ``A_log`` a head; the attention layer carries its gate
    and no q / k norm; every layer its experts, router bias and shared expert."""
    p = params["params"]
    m = CFG.gated_delta
    mixer = p["layer_1"]["mixer"]
    assert {"f_a_proj", "f_b_proj", "g_a_proj", "g_b_proj"} <= set(mixer) and not {"a_proj", "g_proj"} & set(mixer)
    assert mixer["f_a_proj"]["kernel"].shape == (CFG.dim, m.decay_rank)
    assert mixer["f_b_proj"]["kernel"].shape == (m.decay_rank, m.n_heads * m.key_dim)
    assert mixer["g_b_proj"]["kernel"].shape == (m.gate_rank, m.n_heads * m.value_dim)
    assert mixer["dt_bias"].shape == (m.n_heads * m.key_dim,) and mixer["A_log"].shape == (m.n_heads,)
    assert {"q", "k", "v", "g", "o", "ln1", "ln2", "moe"} <= set(p["layer_0"]) and "q_norm" not in p["layer_0"]
    for i in range(CFG.n_layers):
        moe = p[f"layer_{i}"]["moe"]
        assert moe["router"]["kernel"].shape == (CFG.dim, 16) and moe["gate_up"].shape == (2, CFG.dim, 64)
        assert {"router_bias", "shared_up", "shared_gate", "shared_down"} <= set(moe)
    engine = CaptionEngine(CFG, kv_lanes=((64, 1),), params=jax.tree.map(jnp.copy, params), block_size=8)
    engine.setup()  # sorted experts beside a recurrent store HAVE programs
    served = engine.params["params"]["layer_1"]
    assert served["mixer"]["f_a_proj"]["kernel"].dtype == jnp.bfloat16 and served["moe"]["gate_up"].dtype == jnp.bfloat16
    small = [served["mixer"][n] for n in ("A_log", "dt_bias", "q_conv", "o_norm_scale")] + [
        served["moe"]["router"]["kernel"], served["moe"]["router_bias"]]
    assert {x.dtype for x in small} == {jnp.dtype("float32")}
    assert engine.stats()["expert_assignments_held"] == engine.stats()["expert_assignments_held_live"] == 0
    engine.shutdown()


def test_sorted_experts_beside_mrope_are_still_refused():
    with pytest.raises(ValueError, match="m-rope"):
        cfg = dataclasses.replace(CFG, layer_types=None, gated_delta=None, mrope_section=(2, 3, 3), use_rope=True)
        CaptionEngine(cfg, kv_lanes=((64, 1),), block_size=8).setup()
