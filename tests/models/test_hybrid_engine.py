"""The caption engine serving a hybrid decoder (Mamba-2 state beside the paged
KV pool) against the plain float32 reference, on seeded weights at the tiny
preset: logits, not tokens. Both families of programs: ``kernel`` (the paged
programs with ops/ssm.py forced onto its TPU side: the chunked SSD prefill and
the Pallas decode kernel in interpret mode) and ``gather`` (the recurrence in
plain XLA)."""

import dataclasses
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import VLM, VLM_GRANITE_HYBRID_TINY_TEST as CFG
from cosmos_curate_tpu.ops import ssm as ssm_ops
from perfbench.reference import granite_hybrid as ref

# bfloat16 activations over ten layers at width 64 against float32: 0.01-0.03
# seen; a state that is stale, advanced by padding or reused is off by 0.3+
TOL = 0.06
# the first state-space layer's state in the store against the reference's:
# float32 both, the inputs bfloat16 here: 0.005-0.006 seen; padding that
# advances it, a token taken twice or a stale row are off by 0.09 and more
STATE_TOL = 0.015
CHUNK = 16


# the tiny preset with Granite-4.0-H-Micro's attention heads (8 KV heads of 64,
# 4 query heads each): the pool holds two KV heads a 128-lane row
CFG_GRANITE_HEADS = dataclasses.replace(CFG, n_heads=32, n_kv_heads=8, head_dim=64, attention_multiplier=1 / 64)


@pytest.fixture(scope="module")
def params():
    return nn.unbox(_init_params(VLM(CFG), seed=5))


@pytest.fixture(scope="module")
def params_granite_heads():
    return nn.unbox(_init_params(VLM(CFG_GRANITE_HEADS), seed=6))


def _ids(seed, n):
    return np.random.default_rng(seed).integers(256, 500, n).tolist()


class Spy:
    """First-step logits and every decode step's logits, by request id."""

    def __init__(self, engine):
        self.first, self.steps, self.row = {}, {}, {}
        self.engine = engine
        start, decode = engine._start_slot, engine._decode

        def start_slot(lane, slot_idx, req, t_valid, next_rope, logits_row):
            self.first[req.request_id] = np.array(logits_row, np.float32)
            self.row[req.request_id] = int(engine._state_rows(lane, slot_idx))
            return start(lane, slot_idx, req, t_valid, next_rope, logits_row)

        def decode_step(*args):
            out = decode(*args)
            logits = np.asarray(out[1], np.float32)
            for lane in engine.lanes:
                if lane.n_slots == logits.shape[0] and np.array_equal(np.asarray(args[3]), lane.table):
                    for i, slot in lane.slots.items():
                        self.steps.setdefault(slot.request.request_id, []).append(logits[i])
            return out

        engine._start_slot, engine._decode = start_slot, decode_step


def _engine(kind, params, monkeypatch, lanes=((64, 4), (128, 2)), cfg=CFG):
    if kind == "kernel":
        monkeypatch.setattr(ssm_ops, "_on_tpu", lambda: True)
    engine = CaptionEngine(
        cfg, kv_lanes=lanes, params=jax.tree.map(jnp.copy, params), prefill_chunk=CHUNK,
        paged_attention="gather" if kind == "gather" else "auto", block_size=8,
    )
    engine.setup()
    return engine, Spy(engine)


def _request(name, prompt, prefix=(), max_new=1, share=True):
    return CaptionRequest(
        request_id=name, prompt_ids=list(prompt), prefix_ids=list(prefix),
        sampling=SamplingConfig(max_new_tokens=max_new), share_prefix=share,
    )


def _reference(params, ids, positions, cfg=CFG):
    return np.asarray(
        ref.logits_at(params, jnp.asarray(ids, jnp.int32), positions, **ref.model_kwargs(cfg))
    )


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _assert_decode_matches(params, spy, engine_tokens, name, prompt, cfg=CFG):
    """The first-step logits and every decode step's against the reference's
    full forward over prompt + generated ids, and the state the request left
    in its row of the store (a released row keeps it until the next claim)
    against the reference's after the same ids."""
    generated = engine_tokens[name]
    ids = list(prompt) + generated[:-1]
    want = _reference(params, ids, list(range(len(prompt) - 1, len(ids))), cfg)
    got = np.stack([spy.first[name], *spy.steps.get(name, [])])
    assert got.shape == want.shape
    assert _rel(got, want) < TOL
    state = np.asarray(spy.engine._ssm[0, spy.row[name]])
    want_state = np.asarray(
        ref.first_ssm_state(params, jnp.asarray(ids, jnp.int32), **ref.model_kwargs(cfg))
    )
    assert _rel(state, want_state) < STATE_TOL


def _run(engine):
    """Drive to completion; the tokens every request generated."""
    tokens = {}
    finish = engine._maybe_finish

    def keep(lane, slot_idx, slot):
        tokens[slot.request.request_id] = list(slot.generated)
        return finish(lane, slot_idx, slot)

    engine._maybe_finish = keep
    engine.run_until_complete()
    return tokens


KINDS = ["kernel", "gather"]


@pytest.mark.parametrize("kind", KINDS)
def test_prompt_over_prefill_chunks_while_another_row_decodes(kind, params, monkeypatch):
    """(a) + (f): 37 tokens in chunks of 16, 16 and 5 (the last padded at
    its end), the chunks interleaved with the decode steps of a request that
    is already running: the pending row is an idle row of those steps."""
    engine, spy = _engine(kind, params, monkeypatch)
    first, long = _ids(1, 12), _ids(2, 37)
    engine.add_request(_request("a", first, max_new=12))
    while not engine.slots:
        engine.step()
    engine.add_request(_request("b", long, max_new=3))
    tokens = _run(engine)
    assert engine.stats()["prefill_tokens"] == 12 + 37
    _assert_decode_matches(params, spy, tokens, "b", long)
    _assert_decode_matches(params, spy, tokens, "a", first)


@pytest.mark.parametrize("kind", KINDS)
def test_bucketed_prompt_with_padding(kind, params, monkeypatch):
    """(b): 21 tokens prefilled whole in a bucket of 32 on an idle engine;
    the eleven positions of padding must leave the state where token 21 did."""
    engine, spy = _engine(kind, params, monkeypatch)
    prompt = _ids(3, 21)
    engine.add_request(_request("p", prompt, max_new=4))
    _assert_decode_matches(params, spy, _run(engine), "p", prompt)


@pytest.mark.parametrize("kind", KINDS)
def test_prefix_snapshot_against_the_same_request_unshared(kind, params, monkeypatch):
    """(c): a request that starts from the shared prefix's blocks and state
    snapshot, twice (the build, then a hit), against the same ids unshared."""
    engine, spy = _engine(kind, params, monkeypatch)
    prefix, prompt = _ids(4, 16), _ids(5, 13)
    for name, share in (("build", True), ("hit", True), ("unshared", False)):
        engine.add_request(_request(name, prompt, prefix=prefix, max_new=4, share=share))
        tokens = _run(engine)
        _assert_decode_matches(params, spy, tokens, name, prefix + prompt)
    stats = engine.stats()
    assert stats["prefix_state_snapshots"] == 2 and engine.prefix_cache_hits >= 1
    assert _rel(spy.first["hit"], spy.first["unshared"]) < TOL / 2
    engine.shutdown()


@pytest.mark.parametrize("kind", KINDS)
def test_sixteen_decode_steps_through_the_store(kind, params, monkeypatch):
    """(d) + (f): one active row of a four-slot lane, three idle."""
    engine, spy = _engine(kind, params, monkeypatch)
    prompt = _ids(6, 20)
    engine.add_request(_request("d", prompt, max_new=17))
    tokens = _run(engine)
    assert len(spy.steps["d"]) == 16
    _assert_decode_matches(params, spy, tokens, "d", prompt)
    stats = engine.stats()
    assert stats["ssm_decode_calls"] == 16 * len(CFG.ssm_layers)
    assert stats["recurrent_rows_total"] == 6 and stats["recurrent_rows_used_peak"] == 1
    assert stats["recurrent_state_bytes_per_chip"] == engine._ssm.nbytes + engine._conv.nbytes


@pytest.mark.parametrize("kind", KINDS)
def test_slot_reused_after_a_longer_tenant(kind, params, monkeypatch):
    """(e): one slot; the second tenant must not inherit the first's state."""
    engine, spy = _engine(kind, params, monkeypatch, lanes=((128, 1),))
    long, short = _ids(7, 40), _ids(8, 9)
    engine.add_request(_request("long", long, max_new=8))
    _run(engine)
    engine.add_request(_request("short", short, max_new=5))
    tokens = _run(engine)
    _assert_decode_matches(params, spy, tokens, "short", short)


@pytest.mark.parametrize("heads", ["tiny", "granite-heads"])
def test_kernel_engine_agrees_with_gather_engine(heads, request, monkeypatch):
    """Token for token, at the tiny preset's heads (a pool row a head) and at
    Granite's (two a row)."""
    cfg, params = {
        "tiny": (CFG, "params"), "granite-heads": (CFG_GRANITE_HEADS, "params_granite_heads")
    }[heads]
    params = request.getfixturevalue(params)
    prompt, prefix = _ids(9, 30), _ids(10, 8)
    firsts, tokens = {}, {}
    for kind in KINDS:
        with monkeypatch.context() as m:
            engine, spy = _engine(kind, params, m, cfg=cfg)
            assert engine.stats()["kv_heads_per_pool_row"] == (1 if cfg is CFG else 2)
            engine.add_request(_request("x", prompt, prefix=prefix, max_new=6))
            tokens[kind] = _run(engine)["x"]
            firsts[kind] = spy.first["x"]
    assert _rel(firsts["kernel"], firsts["gather"]) < TOL / 2
    assert tokens["kernel"] == tokens["gather"]


@pytest.mark.parametrize("kind", KINDS)
def test_two_kv_heads_a_pool_row_against_the_reference(kind, params_granite_heads, monkeypatch):
    """Granite's attention heads on the tiny preset, so the KV pool is ``[1,
    NB, 4, 8, 128]``: a prompt behind a shared prefix (its blocks written by
    ``write_prefix_blocks``, twice read: the build and a hit), prefilled in
    chunks while another row decodes, then decoded, against the float32
    reference, which knows of no pool."""
    cfg, params = CFG_GRANITE_HEADS, params_granite_heads
    engine, spy = _engine(kind, params, monkeypatch, cfg=cfg)
    assert engine._pool_k.shape == (1, engine.kv_pool_blocks, 4, 8, 128)
    prefix, first, long = _ids(11, 16), _ids(12, 12), _ids(13, 37)
    engine.add_request(_request("a", first, prefix=prefix, max_new=12))
    while not engine.slots:
        engine.step()
    engine.add_request(_request("b", long, prefix=prefix, max_new=4))
    tokens = _run(engine)
    assert engine.prefix_cache_hits >= 1
    _assert_decode_matches(params, spy, tokens, "b", prefix + long, cfg)
    _assert_decode_matches(params, spy, tokens, "a", prefix + first, cfg)
    engine.shutdown()


def test_a_hybrid_is_refused_over_a_mesh():
    from cosmos_curate_tpu.models.vlm.model import FlavorSpec

    with pytest.raises(ValueError, match="model_chips=1"):
        FlavorSpec(CFG, "x", model_chips=2)


@pytest.mark.parametrize("kind", KINDS)
def test_a_prefill_program_writes_the_store_once(kind, params, monkeypatch):
    """The rows' states are read out of the store once and written back once
    (``VLM._forward``): on the chip, under memory pressure, XLA's
    rematerialisation ran one of thirty-six in-place updates of the donated
    store twice and a layer's state advanced twice (PERF.md, PR 30). A
    compile-time property: counted in the lowered program."""
    engine, _ = _engine(kind, params, monkeypatch)
    lane, rows, t = engine.lanes[0], 2, CHUNK
    zeros = jnp.zeros(rows, jnp.int32)
    text = engine._prefill_batch.lower(
        engine.params, engine._pool_k, engine._pool_v,
        jnp.zeros((rows, lane.length // engine.block_size), jnp.int32),
        jnp.zeros((rows, t, CFG.dim), jnp.float32), zeros, jnp.ones(rows, jnp.int32),
        jnp.zeros((rows, t), jnp.int32), None, engine._ssm, engine._conv, zeros,
    ).as_text()
    # a scatter's signature closes its update region: `}) : (tensor<operand>, ...`
    store = "x".join(map(str, engine._ssm.shape)) + "xf32"
    assert len(re.findall(r"\}\) : \(tensor<%s>, " % store, text)) == 1
    assert text.count("stablehlo.scatter") >= 1 + len(CFG.ssm_layers)  # the layers write the copy
