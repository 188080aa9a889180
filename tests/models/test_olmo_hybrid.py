"""The caption engine serving Olmo-Hybrid (gated-delta-rule state beside the
paged KV pool) against the plain float32 reference, on seeded weights at the
tiny preset: logits, not tokens. Both families of programs: ``kernel`` (the
paged programs with ops/delta_rule.py forced onto its TPU side: the chunked
prefill scan and the Pallas decode kernel in interpret mode) and ``gather``
(the recurrence in plain XLA). The helpers are the Granite hybrid's: the
store, its spies and its admission are the same code."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_GRANITE_HYBRID_TINY_TEST, VLM_OLMO_HYBRID_7B, VLM_OLMO_HYBRID_7B_PP2,
    VLM_OLMO_HYBRID_TINY_TEST as CFG, init_recurrent_store, vlm_flavor,
)
from cosmos_curate_tpu.ops import delta_rule as delta_ops
from perfbench.reference import olmo_hybrid as ref
from tests.models.test_hybrid_engine import CHUNK, KINDS, Spy, _ids, _rel, _request, _run

# bfloat16 activations over eight layers at width 64 against float32: 0.01-0.03
# seen; a state that is stale, advanced by padding or reused is off by 0.3+
TOL = 0.06
# the first linear-attention layer's state in the store against the
# reference's (root-mean-square over root-mean-square): float32 both, the
# inputs bfloat16 here: 0.003-0.006 seen; padding that advances it, a token
# taken twice or a stale row are off by 0.09 and more
STATE_TOL = 0.015


@pytest.fixture(scope="module")
def params():
    return nn.unbox(_init_params(VLM(CFG), seed=5))


def _build(kind, params, lanes=((64, 4), (128, 2))):
    engine = CaptionEngine(
        CFG, kv_lanes=lanes, params=jax.tree.map(jnp.copy, params), prefill_chunk=CHUNK,
        paged_attention="gather" if kind == "gather" else "auto", block_size=8,
    )
    engine.setup()
    return engine, Spy(engine)


@pytest.fixture(scope="module")
def engines(params):
    """One engine a family of programs for the whole file (a test compiles
    nothing twice): ops/delta_rule.py is on its TPU side throughout, which
    the ``gather`` programs never ask (``use_kernel=False``)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(delta_ops, "_on_tpu", lambda: True)
        built = {kind: _build(kind, params) for kind in KINDS}
        yield built
        for engine, _ in built.values():
            engine.shutdown()


def _rms(got, want):
    return float(np.sqrt(np.mean(np.square(got - want))) / np.sqrt(np.mean(np.square(want))))


def _assert_decode_matches(params, spy, engine_tokens, name, prompt):
    """The first-step logits and every decode step's against the reference's
    ONE full forward over prompt + generated ids, and the state the request
    left in its row of the store against the reference's after the same ids."""
    generated = engine_tokens[name]
    ids = jnp.asarray(list(prompt) + generated[:-1], jnp.int32)
    sizes = ref.model_kwargs(CFG)
    want = np.asarray(ref.logits_at(params, ids, list(range(len(prompt) - 1, ids.shape[0])), **sizes))
    got = np.stack([spy.first[name], *spy.steps.get(name, [])])
    assert got.shape == want.shape
    assert _rel(got, want) < TOL
    state = delta_ops.unpack_state(spy.engine._ssm[0, spy.row[name]], CFG.gated_delta.n_heads)
    assert _rms(np.asarray(state), np.asarray(ref.first_ssm_state(params, ids, **sizes))) < STATE_TOL


def _since(engine, before, *keys):
    now = engine.stats()
    return [now[k] - before[k] for k in keys]


@pytest.mark.parametrize("kind", KINDS)
def test_prompt_over_prefill_chunks_while_another_row_decodes(kind, params, engines):
    """37 tokens in chunks of 16, 16 and 5 (the last padded at its end; a scan
    chunk is 8), the chunks interleaved with the decode steps of a request
    that is already running: the pending row is an idle row of those steps."""
    engine, spy = engines[kind]
    before = engine.stats()
    first, long = _ids(1, 12), _ids(2, 37)
    engine.add_request(_request("a", first, max_new=12))
    while not engine.slots:
        engine.step()
    engine.add_request(_request("b", long, max_new=3))
    tokens = _run(engine)
    # chunks of 8 that held a token: ceil(12 / 8), then 2 + 2 + 1, a layer each
    assert _since(engine, before, "prefill_tokens", "delta_prefill_chunks") == [12 + 37, (2 + 5) * len(CFG.ssm_layers)]
    _assert_decode_matches(params, spy, tokens, "b", long)
    _assert_decode_matches(params, spy, tokens, "a", first)


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
    """A request that starts from the shared prefix's blocks and state
    snapshot, twice (the build, then a hit), against the same ids unshared."""
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
def test_sixteen_decode_steps_through_the_store(kind, params, engines):
    """One active row of a four-slot lane, three idle; the counters by kind."""
    engine, spy = engines[kind]
    before = engine.stats()
    prompt = _ids(6, 20)
    engine.add_request(_request("d", prompt, max_new=17))
    tokens = _run(engine)
    assert len(spy.steps["d"]) == 16
    _assert_decode_matches(params, spy, tokens, "d", prompt)
    assert _since(engine, before, "delta_decode_calls", "ssm_decode_calls") == [16 * len(CFG.ssm_layers), 0]
    stats = engine.stats()
    assert stats["recurrent_rows_total"] == 6 and stats["recurrent_rows_used_peak"] >= 1
    assert stats["recurrent_state_bytes_per_chip"] == engine._ssm.nbytes + engine._conv.nbytes
    np.testing.assert_array_equal(np.asarray(engine._ssm[:, 0]), 0.0)  # the garbage row never moves


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
    prompt, prefix = _ids(9, 30), _ids(10, 8)
    firsts, tokens = {}, {}
    for kind in KINDS:
        engine, spy = engines[kind]
        engine.add_request(_request("x", prompt, prefix=prefix, max_new=6))
        tokens[kind] = _run(engine)["x"]
        firsts[kind] = spy.first["x"]
    assert _rel(firsts["kernel"], firsts["gather"]) < TOL / 2
    assert tokens["kernel"] == tokens["gather"]


def test_the_store_is_sized_by_the_mixers_kind():
    """The delta rule's heads lie side by side on the lanes and its three
    convolutions' tails share a row; Granite's store keeps its shape."""
    m = CFG.gated_delta
    ssm, conv = jax.eval_shape(lambda: init_recurrent_store(CFG, 5))
    assert ssm.shape == (6, 5, m.key_dim, m.n_heads * m.value_dim) and ssm.dtype == jnp.float32
    assert conv.shape == (6, 5, 3 * m.n_heads * (2 * m.key_dim + m.value_dim)) and conv.dtype == jnp.bfloat16
    big = VLM_OLMO_HYBRID_7B_PP2
    ssm, conv = jax.eval_shape(lambda: init_recurrent_store(big, 45))
    assert ssm.shape == (12, 45, 96, 5760) and conv.shape == (12, 45, 3 * (2880 + 2880 + 5760))
    g = VLM_GRANITE_HYBRID_TINY_TEST
    ssm, conv = jax.eval_shape(lambda: init_recurrent_store(g, 5))
    assert ssm.shape == (9, 5, g.mamba.n_heads, g.mamba.head_dim, g.mamba.d_state)


def test_a_flavor_mixing_both_recurrent_kinds_is_refused():
    kinds = ("mamba", "linear_attention", "full_attention", "linear_attention") * 2
    with pytest.raises(ValueError, match="one kind of state"):
        dataclasses.replace(CFG, layer_types=kinds, mamba=VLM_GRANITE_HYBRID_TINY_TEST.mamba)
    with pytest.raises(ValueError, match="gated_delta= gives no sizes"):
        dataclasses.replace(CFG, gated_delta=None)


def test_published_presets_and_flavors():
    """Every published width, the pattern in periods of four, and the first
    pipeline stage as the first four periods of the whole."""
    cfg = VLM_OLMO_HYBRID_7B
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab) == (3840, 32, 30, 30, 128, 100352)
    assert int(cfg.dim * cfg.hidden_mult) == 11008 and not cfg.tied_embeddings and not cfg.use_rope
    assert (cfg.pre_norm, cfg.sandwich_norm, cfg.qk_norm_whole, cfg.qk_norm) == (False, True, True, False)
    m = cfg.gated_delta
    assert (m.n_heads, m.key_dim, m.value_dim, m.d_conv, m.allow_neg_eigval, m.chunk) == (30, 96, 192, 4, True, 64)
    assert len(cfg.ssm_layers) == 24 and cfg.kv_layers == tuple(range(3, 32, 4))
    stage = vlm_flavor("olmo-hybrid-7b-pp2")
    assert stage.cfg == dataclasses.replace(cfg, n_layers=16, layer_types=cfg.layer_types[:16])
    assert stage.text_only and vlm_flavor("olmo-hybrid-7b").cfg is cfg
    assert vlm_flavor("olmo-hybrid-tiny-test").cfg is CFG and not vlm_flavor("olmo-hybrid-tiny-test").require_weights
    # the defaults are every other flavor's behaviour
    assert VLM_GRANITE_HYBRID_TINY_TEST.pre_norm and not VLM_GRANITE_HYBRID_TINY_TEST.qk_norm_whole
    assert VLM_GRANITE_HYBRID_TINY_TEST.recurrent_kind == "mamba" and CFG.recurrent_kind == "linear_attention"


def test_the_block_has_no_norm_before_a_branch(params):
    """``x + RMSNorm(f(x))``: a layer holds the two norms on its branches'
    outputs and none before them; an attention layer norms q and k over the
    whole projection; the small parameters serve in float32."""
    p = params["params"]
    assert {"post_attn_norm", "post_mlp_norm"} <= set(p["layer_0"]) and not {"ln1", "ln2"} & set(p["layer_0"])
    assert not {"ln1", "ln2"} & set(p["layer_3"])
    assert p["layer_3"]["q_norm"]["scale"].shape == (CFG.n_heads * CFG.head_dim,)
    mixer = p["layer_0"]["mixer"]
    m = CFG.gated_delta
    assert mixer["v_conv"].shape == (m.d_conv, m.n_heads * m.value_dim) and mixer["q_conv"].shape == (m.d_conv, m.n_heads * m.key_dim)
    assert "lm_head" in p
    engine = CaptionEngine(CFG, kv_lanes=((64, 1),), params=jax.tree.map(jnp.copy, params), block_size=8)
    engine.setup()
    served = engine.params["params"]["layer_0"]["mixer"]
    assert served["q_proj"]["kernel"].dtype == jnp.bfloat16
    assert {served[n].dtype for n in ("A_log", "dt_bias", "q_conv", "o_norm_scale")} == {jnp.dtype("float32")}
    # a seeded decay remembers: exp(g) within (0, 1), most heads over tens of tokens
    a = np.exp(np.asarray(mixer["A_log"]))
    assert ((a > 0) & (a <= 16)).all()
