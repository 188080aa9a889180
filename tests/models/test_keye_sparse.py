"""A learned indexer in every layer (HF ``KeyeVL2``'s language model) at test size
(``VLM_KEYE_TINY_TEST``: a top-k of 32, lanes of 64 and 128): the model and the
engine's index-key array against the plain reference
(perfbench/reference/keye_vl2.py), logits and the chosen sets and never tokens;
what the prefix cache's shared blocks keep; and what the config refuses."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_KEYE_TINY_TEST, VLM_KEYE_VL2_A3B_EP8, FlavorSpec, IndexerConfig, MLAConfig, Mamba2Config, init_cache,
    vlm_flavor,
)
from cosmos_curate_tpu.ops import sparse_attention as sparse
from perfbench.reference import keye_vl2 as ref

CFG = VLM_KEYE_TINY_TEST
TOP_K = CFG.indexer.top_k  # 32
BLOCK, CHUNK = 4, 8
LANES = ((64, 2), (128, 2))


def stirred(cfg, seed=0):
    """Seeded, with what a fresh init leaves trivial made to matter: norm scales
    off 1, the index key's LayerNorm bias off 0, a router and an indexer whose
    scores spread."""
    tree = nn.unbox(_init_params(VLM(cfg), seed))
    rng = np.random.default_rng(7)

    def stir(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            return jnp.asarray(1 + 0.2 * rng.standard_normal(leaf.shape), leaf.dtype)
        if "index_k_norm" in name and name.endswith("['bias']"):
            return jnp.asarray(0.1 * rng.standard_normal(leaf.shape), leaf.dtype)
        if "router" in name:
            return leaf * 20
        if "index_" in name and name.endswith("['kernel']"):
            return leaf * 3
        return leaf

    return jax.tree_util.tree_map_with_path(stir, tree)


@pytest.fixture(scope="module")
def params():
    return stirred(CFG)


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(10, 500, n).tolist()


def _forward(cfg, params, ids, dtype=jnp.float32):
    """The program's slot-cache forward over a whole prompt: logits [T, V], the
    caches, and what each layer's LAST query chose ([layers, T] bool)."""
    model = VLM(cfg, dtype=dtype)
    ids = jnp.asarray(ids, jnp.int32)[None]
    t = ids.shape[1]
    embeds = model.apply(params, ids, method=model.embed_tokens)
    ck, cv = init_cache(cfg, 1, dtype=dtype, length=t)
    with jax.default_matmul_precision("highest"):
        (logits, (nk, ni), _), aux = model.apply(
            params, embeds, ck, cv, jnp.arange(t)[None], jnp.zeros(1, jnp.int32), jnp.full((1,), t, jnp.int32),
            mutable=["choice"],
        )
    words = np.stack([np.asarray(aux["choice"][f"layer_{i}"]["digest"][-1][0]) for i in range(cfg.n_layers)])
    return logits[0], nk, ni, _unpack(words, t)


def _unpack(words, n):
    bits = (np.asarray(words).astype(np.uint32)[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(*words.shape[:-1], -1)[..., :n].astype(bool)


# -- (a) the config ------------------------------------------------------------


def test_presets_and_flavors():
    big = VLM_KEYE_VL2_A3B_EP8
    assert (big.dim, big.n_heads, big.n_kv_heads, big.head_dim, big.n_layers) == (2048, 32, 4, 128, 8)
    assert big.indexer == IndexerConfig(n_heads=16, head_dim=64, top_k=2048) and big.indexer.cache_width == 128
    assert big.moe.n_experts == 128 and big.moe.top_k == 8 and big.moe.held_experts == (0, 16)
    assert big.moe.dispatch == "sorted" and big.moe.shared_hidden == 0 and big.max_seq == 32768
    spec = vlm_flavor("keye-vl2-a3b-ep8")
    assert spec.text_only and spec.require_weights and spec.kv_lanes == ((8192, 4), (32768, 12)) and spec.prefill_rows == 4
    assert vlm_flavor("keye-tiny-test").cfg is CFG and CFG.indexer.cache_width == 128
    assert IndexerConfig().weight_scale == 16**-0.5 * 64**-0.5


@pytest.mark.parametrize(
    "change",
    [
        dict(mla=MLAConfig(q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)),
        dict(layer_types=("sliding_attention", "full_attention"), sliding_window=10),
        dict(layer_types=("mamba", "attention"), mamba=Mamba2Config(n_heads=8, head_dim=16, d_state=16, chunk=8)),
    ],
    ids=["latent-attention", "window-layers", "state-space-layers"],
)
def test_config_refuses_an_indexer_beside_what_no_program_defines(change):
    with pytest.raises(ValueError, match="an indexer beside"):
        dataclasses.replace(CFG, **change)


def test_flavor_refuses_an_indexer_over_a_model_mesh():
    with pytest.raises(ValueError, match="index-key array"):
        FlavorSpec(CFG, "x", model_chips=2)


def test_engine_refuses_gather_programs_for_an_indexer():
    with pytest.raises(ValueError, match="gather"):
        CaptionEngine(CFG, kv_lanes=LANES, paged_attention="gather")


# -- (b) the model against the plain reference --------------------------------


def test_whole_model_logits_and_choices_match_the_reference_at_every_position(params):
    ids = _ids(60)  # under, at and over the top-k of 32
    logits, nk, ni, chose = _forward(CFG, params, ids)
    sizes = ref.model_kwargs(CFG)
    want, _, sets, rows = ref.logits_at(params, jnp.asarray(ids), list(range(60)), **sizes, rows_of=(0, 1))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=3e-5)
    np.testing.assert_array_equal(chose, np.asarray(sets[:, -1]))  # the last query's set, layer by layer
    assert np.asarray(sets).sum(-1).tolist() == [[min(t + 1, TOP_K) for t in range(60)]] * 2
    for layer in (0, 1):  # what the two arrays a position must hold
        k = np.asarray(nk[layer, 0]).swapaxes(0, 1).reshape(60, -1)
        np.testing.assert_allclose(k, np.asarray(rows[layer][0]), atol=2e-5)
        index = np.asarray(ni[layer, 0, 0])
        np.testing.assert_allclose(index[:, : CFG.indexer.head_dim], np.asarray(rows[layer][1]), atol=2e-5)
        assert not index[:, CFG.indexer.head_dim :].any()  # the row's upper lanes stay zero


def test_the_choice_changes_the_result_and_only_past_the_top_k(params):
    ids = _ids(70)
    sizes = ref.model_kwargs(CFG)
    chosen, _, _, _ = ref.logits_at(params, jnp.asarray(ids), list(range(70)), **sizes)
    dense, _, _, _ = ref.logits_at(params, jnp.asarray(ids), list(range(70)), **sizes, topk=10**6)
    np.testing.assert_allclose(np.asarray(chosen[:TOP_K]), np.asarray(dense[:TOP_K]), atol=1e-6)
    assert np.abs(np.asarray(chosen[50:]) - np.asarray(dense[50:])).max() > 0.05
    logits, *_ = _forward(dataclasses.replace(CFG, indexer=dataclasses.replace(CFG.indexer, top_k=128)), params, ids)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(dense), atol=3e-5)  # a top-k past the prompt: every position


@pytest.mark.parametrize(
    "fault",
    [dict(topk=16), dict(index_shift=1), dict(indexer_mantissa_bits=3), dict(router_mantissa_bits=7)],
    ids=["half-the-top-k", "index-keys-one-position-off", "indexer-in-8-bit-floats", "bfloat16-router"],
)
def test_the_references_named_faults_move_what_the_check_reads(params, fault):
    """Each fault the benchmark's second readings inject changes the chosen sets
    or the logits: none is a no-op at test size."""
    ids = jnp.asarray(_ids(90, seed=2))
    sizes = ref.model_kwargs(CFG)
    at = list(range(50, 90))
    want, _, sets, _ = ref.logits_at(params, ids, at, **sizes)
    got, _, faulty, _ = ref.logits_at(params, ids, at, **sizes, **fault)
    moved = np.abs(np.asarray(got) - np.asarray(want)).max() / np.abs(np.asarray(want)).max()
    same_sets = (np.asarray(sets) == np.asarray(faulty)).all(axis=-1).mean()
    if "router" in str(fault):  # the router's rounding moves the logits; at this size it flips no set
        assert moved > 1e-6
    else:
        assert moved > 1e-3 and same_sets < 1.0


# -- (c) the engine: the index-key array beside the pool -------------------------


def _engine(params, **kw):
    engine = CaptionEngine(
        CFG, kv_lanes=LANES, params=jax.tree.map(jnp.copy, params), block_size=BLOCK,
        prefill_chunk=CHUNK, **kw,
    )
    engine.setup()
    return engine


class _Spy:
    """First-step logits and chosen sets at ``_start_slot``; decode logits, sets
    and tokens at ``_decode_collect``, where the look-ahead engine reads them."""

    def __init__(self, engine):
        self.first, self.first_sets, self.steps, self.step_sets, self.tokens = {}, {}, {}, {}, {}
        start, collect, finish = engine._start_slot, engine._decode_collect, engine._maybe_finish
        run_prefill = engine._run_prefill
        last = {}

        def on_prefill(lane, slots_arr, *rest):
            out = run_prefill(lane, slots_arr, *rest)
            last["rows"], last["choice"] = [int(s) for s in slots_arr], engine._choice_digest
            return out

        def on_start(lane, slot_idx, req, t_valid, next_rope, logits_row):
            self.first[req.request_id] = np.asarray(logits_row, np.float32)
            words = np.asarray(last["choice"])[:, last["rows"].index(slot_idx)]
            self.first_sets[req.request_id] = _unpack(words, t_valid)
            return start(lane, slot_idx, req, t_valid, next_rope, logits_row)

        def on_collect(lane, flight):
            logits, choice = np.asarray(flight.logits, np.float32), np.asarray(flight.choice)
            for i, slot in flight.emitted(lane).items():
                name = slot.request.request_id
                self.steps.setdefault(name, []).append(logits[i])
                self.step_sets.setdefault(name, []).append(_unpack(choice[:, i], int(flight.positions[i]) + 1))
            return collect(lane, flight)

        def on_finish(lane, slot_idx, slot):
            self.tokens[slot.request.request_id] = list(slot.generated)
            return finish(lane, slot_idx, slot)

        engine._run_prefill, engine._start_slot = on_prefill, on_start
        engine._decode_collect, engine._maybe_finish = on_collect, on_finish


def _rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.abs(np.asarray(want)).max())


def _against_reference(params, spy, name, prefix, ids, steps):
    """[(relative error of the logits, the engine's sets == the reference's in
    every layer, routing margin, the sets' least overlap over the layers)] for
    the first step and ``steps`` decode steps, against the reference's ONE
    forward over prompt + generated ids."""
    full = prefix + ids + spy.tokens[name][:steps]
    t = len(prefix) + len(ids)
    want, margin, sets, _ = ref.logits_at(params, jnp.asarray(full), list(range(t - 1, t + steps)), **ref.model_kwargs(CFG))
    got = [spy.first[name], *spy.steps[name]]
    got_sets = [spy.first_sets[name], *spy.step_sets[name]]
    assert len(got) == steps + 1
    out = []
    for s in range(steps + 1):
        context = t + s
        assert got_sets[s].shape == (CFG.n_layers, context)
        assert (got_sets[s].sum(-1) == min(context, TOP_K)).all()  # every position while there are no more than the top-k
        want_sets = np.asarray(sets[:, s])[:, :context]
        same = bool((got_sets[s] == want_sets).all())
        overlap = min((g & w).sum() / (g | w).sum() for g, w in zip(got_sets[s], want_sets))
        out.append((_rel(got[s], want[s]), same, float(margin[s]), float(overlap)))
    return out


def _judge(seen, least: int):
    """The engine scores in bfloat16 what the reference scores in float32: of 32
    positions picked out of a hundred one at the boundary may differ, and with
    thirty-two values in a softmax one other value moves a logit by some per cent. So:
    the sets overlap (a wrong choice shares next to nothing), and where they
    are the reference's own and the routing is no near-tie the logits agree, on
    the median (an EARLIER token's flipped expert sits in a chosen value)."""
    assert np.median([overlap for *_, overlap in seen]) >= 0.7, seen
    errs = [err for err, same, margin, _ in seen if same and margin >= 0.05]
    # bfloat16 activations at width 64 against float32: 0.005-0.01 a step, 0.05-0.1
    # at a step with an earlier token's flipped expert among its twelve values
    # (two steps in five of one request); a key one position off or the choice
    # left out: over 0.1 at every step
    assert len(errs) >= least and min(errs) < 0.02 and np.median(errs) < 0.08, seen


def test_pools_and_the_index_key_array(params):
    engine = _engine(params)
    try:
        nb = engine.kv_pool_blocks
        assert engine._pool_k.shape == (2, nb, 2, BLOCK, 16)
        assert engine._pool_i.shape == (2, nb, 1, BLOCK, 128)  # the SAME blocks: one allocator, one table
        stats = engine.stats()
        assert stats["index_pool_bytes_per_chip"] == engine._pool_i.nbytes
        assert stats["full_pool_bytes_per_chip"] == engine._pool_k.nbytes * 2
        assert stats["kv_pool_bytes_per_chip"] == engine._pool_k.nbytes * 2 + engine._pool_i.nbytes == engine.kv_bytes()
        (pool_k, pool_i), pool_v = engine._pools()
        assert pool_k is engine._pool_k and pool_i is engine._pool_i and pool_v is engine._pool_v
    finally:
        engine.shutdown()


@pytest.mark.parametrize(
    "n,lane", [(TOP_K - 4, 64), (TOP_K, 64), (50, 64), (100, 128)],
    ids=["under-the-top-k-and-across-it-in-decode", "at-the-top-k", "over-it", "the-long-lane"],
)
def test_engine_prefill_then_decode_match_the_reference(params, n, lane):
    """Prefill in chunks of 8, then 8 decode steps through the pool and the
    index-key array, against the reference's ONE full forward: the logits where
    the engine's bfloat16 scores picked the reference's own set and the routing
    is no near-tie, the sets' size everywhere."""
    engine = _engine(params)
    spy = _Spy(engine)
    steps = 8
    try:
        # a hold request decodes meanwhile, so that the prompt is prefilled in chunks
        engine.add_request(CaptionRequest("hold", _ids(5, seed=9), sampling=SamplingConfig(max_new_tokens=40)))
        while not any(l.slots for l in engine.lanes):
            engine.step()
        engine.add_request(CaptionRequest("r", _ids(n, seed=n), sampling=SamplingConfig(max_new_tokens=steps + 1)))
        engine.run_until_complete()
        assert engine.stats()["sparse_decode_calls"] > 0
    finally:
        engine.shutdown()
    assert engine._allocator.free_blocks == engine._allocator.capacity
    seen = _against_reference(params, spy, "r", [], _ids(n, seed=n), steps)
    _judge(seen, least=3 if n <= 50 else 1)
    if n < TOP_K:
        assert all(same for _, same, *_ in seen[: TOP_K - n + 1])  # under the top-k a set is every position


def test_the_kernels_in_the_engine_are_the_xla_lines(params, monkeypatch):
    """The same requests with ``ops/sparse_attention.py`` on its Pallas kernels
    (interpret mode): the scoring, threshold and masked-prefill kernels in the
    prefill programs (and the decode programs' walk: the test below). Logits as
    on the XLA lines, sets identical."""
    prompts = {"a": _ids(60, seed=3), "b": _ids(40, seed=4)}

    def serve():
        engine = _engine(params)
        spy = _Spy(engine)
        try:
            engine.add_request(CaptionRequest("hold", _ids(5, seed=9), sampling=SamplingConfig(max_new_tokens=30)))
            while not any(l.slots for l in engine.lanes):
                engine.step()
            for name, ids in prompts.items():
                engine.add_request(CaptionRequest(name, ids, sampling=SamplingConfig(max_new_tokens=4)))
            engine.run_until_complete()
        finally:
            engine.shutdown()
        return spy

    plain = serve()
    called = []
    for name in ("_sparse_index_score", "_sparse_select", "_sparse_prefill"):
        kernel = getattr(sparse, name)
        monkeypatch.setattr(sparse, name, lambda *a, _k=kernel, _n=name, **kw: (called.append(_n), _k(*a, **kw))[1])
    monkeypatch.setattr(sparse, "_on_tpu", lambda: True)
    kernels = serve()
    assert {"_sparse_index_score", "_sparse_select", "_sparse_prefill"} <= set(called)
    for name in prompts:
        np.testing.assert_array_equal(kernels.first_sets[name], plain.first_sets[name])
        assert _rel(kernels.first[name], plain.first[name]) < 0.02
        assert kernels.tokens[name] == plain.tokens[name]


@pytest.mark.parametrize("n,lane", [(40, 64), (100, 128)], ids=["the-short-lane", "the-long-lane"])
def test_a_decode_step_walks_on_the_kernels_and_gathers_on_the_xla_lines(params, monkeypatch, n, lane):
    """Prefill and 8 decode steps twice, on the XLA lines (a decode step gathers
    its chosen positions) and on the Pallas kernels in interpret mode (it walks
    its row's live pages under the chosen set's mask, ``_sparse_decode``): the
    same tokens, the same ``choice/digest`` at every step, and counters that say
    which path ran and what it read."""
    steps = 8

    def serve():
        engine = _engine(params)
        spy = _Spy(engine)
        try:
            engine.add_request(CaptionRequest("r", _ids(n, seed=n), sampling=SamplingConfig(max_new_tokens=steps + 1)))
            engine.run_until_complete()
            return spy, engine.stats()
        finally:
            engine.shutdown()

    plain, gathered = serve()
    called = []
    walk = sparse._sparse_decode
    monkeypatch.setattr(sparse, "_sparse_decode", lambda *a, **kw: (called.append(a[3].shape[1] * BLOCK), walk(*a, **kw))[1])
    monkeypatch.setattr(sparse, "_on_tpu", lambda: True)
    kernels, walked = serve()
    assert set(called) == {lane}  # traced once a decode program of the request's lane
    assert kernels.tokens["r"] == plain.tokens["r"] and len(plain.steps["r"]) == steps
    for step in range(steps):
        np.testing.assert_array_equal(kernels.step_sets["r"][step], plain.step_sets["r"][step])
        assert _rel(kernels.steps["r"][step], plain.steps["r"][step]) < 0.02
    contexts = [n + 1 + step for step in range(steps)]
    live = CFG.n_layers * sum(contexts)
    chosen = CFG.n_layers * sum(min(c, TOP_K) for c in contexts)
    for stats in (gathered, walked):  # what they read before, whichever path ran
        assert stats["sparse_decode_positions_live"] == live and stats["sparse_decode_positions_chosen"] == chosen
    rows = CFG.n_layers * steps
    assert (gathered["sparse_decode_rows_gathered"], gathered["sparse_decode_rows_walked"]) == (rows, 0)
    assert (walked["sparse_decode_rows_gathered"], walked["sparse_decode_rows_walked"]) == (0, rows)
    assert gathered["sparse_decode_positions_read"] == chosen and walked["sparse_decode_positions_read"] == live


@pytest.mark.parametrize("prefix_len", [8, 9], ids=["two-whole-blocks", "a-partial-tail-block"])
def test_a_block_shared_through_the_prefix_cache_keeps_its_index_keys(params, prefix_len):
    """Two requests behind one instruction: the second starts from the cached
    prefix's blocks. The shared blocks hold the prefix's index keys (the
    reference's rows), a tail block copied on write takes its index keys along,
    and both requests compute what the reference computes over prefix + prompt."""
    engine = _engine(params)
    spy = _Spy(engine)
    prefix = _ids(prefix_len, seed=11)
    prompts = {"first": _ids(40, seed=5), "second": _ids(51, seed=6)}
    rows = {}
    start = engine._start_slot

    def reading(lane, slot_idx, req, t_valid, *rest):
        blocks = lane.table[slot_idx][: -(-t_valid // BLOCK)]
        rows[req.request_id] = (
            np.asarray(engine._pool_i[0][blocks][:, 0], np.float32).reshape(-1, 128)[:t_valid, : CFG.indexer.head_dim],
            [int(b) for b in blocks],
        )
        return start(lane, slot_idx, req, t_valid, *rest)

    engine._start_slot = reading
    try:
        for name, ids in prompts.items():
            engine.add_request(CaptionRequest(name, ids, prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=5)))
            engine.run_until_complete()
        assert engine.prefix_cache_hits >= 1
    finally:
        engine.shutdown()
    whole = prefix_len // BLOCK
    assert rows["first"][1][:whole] == rows["second"][1][:whole]  # referenced, not copied
    assert rows["first"][1][whole] != rows["second"][1][whole]
    for name, ids in prompts.items():
        want = ref.cache_rows(params, jnp.asarray(prefix + ids), (0,), **ref.model_kwargs(CFG))[0][1]
        got = rows[name][0]
        assert np.sqrt(np.mean((got - np.asarray(want)) ** 2)) / np.sqrt(np.mean(np.asarray(want) ** 2)) < 0.02
        # the prefix's own positions, the tail block's copy included
        assert np.abs(got[:prefix_len] - np.asarray(want)[:prefix_len]).max() < 0.05
        _judge(_against_reference(params, spy, name, prefix, ids, 4), least=1)


def test_decode_counters_count_positions_seen_and_read(params):
    engine = _engine(params)
    try:
        engine.add_request(CaptionRequest("r", _ids(40, seed=8), sampling=SamplingConfig(max_new_tokens=4)))
        engine.run_until_complete()
        stats = engine.stats()
    finally:
        engine.shutdown()
    # three decode programs with the row live (contexts 41, 42, 43), two layers each
    programs = stats["sparse_decode_calls"] // CFG.n_layers
    assert programs >= 3
    assert stats["sparse_decode_positions_live"] == CFG.n_layers * (41 + 42 + 43)
    assert stats["sparse_decode_positions_chosen"] == CFG.n_layers * 3 * TOP_K


def test_another_flavor_has_no_index_keys():
    from cosmos_curate_tpu.models.vlm.model import VLM_MOE_TINY_TEST

    engine = CaptionEngine(VLM_MOE_TINY_TEST, kv_lanes=((64, 2),))
    engine.setup()
    try:
        assert engine._pool_i is None and not engine._indexed
        stats = engine.stats()
        assert stats["index_pool_bytes_per_chip"] == 0 and stats["sparse_decode_calls"] == 0
        assert engine._pools() == (engine._pool_k, engine._pool_v)
    finally:
        engine.shutdown()
