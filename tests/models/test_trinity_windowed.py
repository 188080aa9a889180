"""Window and full attention layers mixed (HF ``afmoe``) at test size
(``VLM_TRINITY_TINY_TEST``): the model and the engine's two pools against the
plain reference (perfbench/reference/trinity_afmoe.py), logits and never
tokens; the sigmoid router with its selection bias against numpy; and what a
row that wraps its ring of window blocks may not do to its neighbours."""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_TRINITY_LARGE_EP8, VLM_TRINITY_TINY_TEST, MoEConfig, init_cache, route, vlm_flavor,
)
from perfbench.reference import trinity_afmoe as ref

CFG = VLM_TRINITY_TINY_TEST
BLOCK, CHUNK = 4, 8  # ring = ceil((10 + 8) / 4) + 1 = 6 blocks: 24 positions
LANES = ((64, 2), (128, 2))


@pytest.fixture(scope="module")
def params():
    """Seeded, with what a fresh init leaves trivial made to matter: norm scales
    off 1 and a selection bias large enough to change choices."""
    tree = nn.unbox(_init_params(VLM(CFG), 0))
    rng = np.random.default_rng(7)

    def stir(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            return jnp.asarray(1 + 0.2 * rng.standard_normal(leaf.shape), leaf.dtype)
        if "router_bias" in name:
            return jnp.asarray(0.05 * rng.standard_normal(leaf.shape), leaf.dtype)
        if "router" in name:  # scores spread over (0, 1), not all 0.5
            return leaf * 20
        return leaf

    return jax.tree_util.tree_map_with_path(stir, tree)


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(10, 500, n).tolist()


def _forward(cfg, params, ids, dtype=jnp.float32):
    """The program's slot-cache forward over a whole prompt: logits [T, V], K cache."""
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


def test_config_splits_its_layers_by_kind():
    assert CFG.kv_layers == (0, 1, 2, 3) and CFG.window_layers == (0, 1, 3) and CFG.full_layers == (2,)
    assert [CFG.rope_in_layer(i) for i in range(4)] == [True, True, False, True]
    big = VLM_TRINITY_LARGE_EP8
    assert big.window_layers == (0, 1, 2, 4) and big.full_layers == (3,) and big.max_seq == 12288
    assert big.moe.held_experts == (0, 32) and big.moe.score_func == "sigmoid" and big.moe.first_dense == 1
    spec = vlm_flavor("trinity-large-ep8")
    assert spec.text_only and spec.kv_lanes[-1][0] == 12288
    with pytest.raises(ValueError, match="sliding_window"):
        dataclasses.replace(CFG, sliding_window=None)
    with pytest.raises(ValueError, match="score_func"):
        MoEConfig(score_func="tanh")


def test_whole_model_logits_match_the_reference_at_every_position(params):
    ids = _ids(40)  # four windows deep
    logits, cache = _forward(CFG, params, ids)
    sizes = ref.model_kwargs(CFG)
    want, _ = ref.logits_at(params, jnp.asarray(ids), list(range(40)), **sizes)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=3e-5)
    for layer in (2, 3):  # a full layer's keys (no rope) and a window layer's
        rows, _ = ref.cache_rows(params, jnp.asarray(ids), layer, **sizes)
        got = np.asarray(cache[layer, 0]).swapaxes(0, 1).reshape(40, -1)
        np.testing.assert_allclose(got, np.asarray(rows), atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["dense-window-layer", "sparse-window-layer", "full-layer"])
def test_leading_layers_match_the_reference(params, layer):
    one = dataclasses.replace(CFG, n_layers=layer + 1, layer_types=CFG.layer_types[: layer + 1])
    tree = {"params": {k: v for k, v in params["params"].items() if not k.startswith("layer_") or int(k[6:]) <= layer}}
    ids = _ids(24, seed=3)
    logits, _ = _forward(one, tree, ids)
    want, _ = ref.logits_at(tree, jnp.asarray(ids), list(range(24)), **ref.model_kwargs(one))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=3e-5)


def test_the_window_changes_the_result(params):
    """The mask is not a no-op at this size: a wider window moves the logits."""
    ids = _ids(40)
    narrow, _ = _forward(CFG, params, ids)
    wide, _ = _forward(dataclasses.replace(CFG, sliding_window=64), params, ids)
    np.testing.assert_allclose(np.asarray(narrow[:10]), np.asarray(wide[:10]), atol=3e-5)  # inside the window
    assert np.abs(np.asarray(narrow[20:]) - np.asarray(wide[20:])).max() > 1e-2


# -- (b) the router ------------------------------------------------------------


def _numpy_route(logits, bias, k, scale):
    """Ten lines of numpy: sigmoid scores, top-k of score + bias (ties to the
    lower index), the UNBIASED scores of the chosen renormalised, times scale."""
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    order = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(s, order, axis=-1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * scale, order


def test_sigmoid_route_with_a_selection_bias_against_numpy():
    moe = MoEConfig(n_experts=8, top_k=2, norm_topk_prob=True, routed_scaling_factor=2.448,
                    dispatch="sorted", score_func="sigmoid", selection_bias=True)
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((64, 8)).astype(np.float32) * 3
    bias = (0.1 * rng.standard_normal(8)).astype(np.float32)
    w, i = route(moe, jnp.asarray(logits), jnp.asarray(bias))
    want_w, want_i = _numpy_route(logits, bias, 2, 2.448)
    np.testing.assert_array_equal(np.asarray(i), want_i)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    # no bias: the plain top-k of the scores
    w0, i0 = route(moe, jnp.asarray(logits))
    np.testing.assert_array_equal(np.asarray(i0), _numpy_route(logits, np.zeros(8), 2, 2.448)[1])


def test_the_bias_changes_the_choice_and_not_the_weight():
    moe = MoEConfig(n_experts=4, top_k=2, norm_topk_prob=True, routed_scaling_factor=1.0,
                    dispatch="sorted", score_func="sigmoid", selection_bias=True)
    logits = jnp.asarray([[2.0, 1.0, 0.9, -3.0]])
    s = 1 / (1 + np.exp(-np.asarray(logits[0], np.float64)))
    w, i = route(moe, logits, jnp.zeros(4))
    assert i.tolist() == [[0, 1]]
    w_b, i_b = route(moe, logits, jnp.asarray([0.0, 0.0, 0.1, 0.0]))  # lifts expert 2 over expert 1
    assert i_b.tolist() == [[0, 2]]
    np.testing.assert_allclose(np.asarray(w_b[0]), s[[0, 2]] / s[[0, 2]].sum(), rtol=1e-6)  # no 0.1 in it
    # a tie goes to the lower index
    _, i_t = route(moe, jnp.asarray([[1.0, 1.0, 1.0, 1.0]]), jnp.zeros(4))
    assert i_t.tolist() == [[0, 1]]


# -- (c) the engine: two pools -------------------------------------------------


def _engine(params, **kw):
    engine = CaptionEngine(
        CFG, kv_lanes=LANES, params=jax.tree.map(jnp.copy, params), block_size=BLOCK,
        prefill_chunk=CHUNK, **kw,
    )
    engine.setup()
    return engine


class _Spy:
    """First-step logits at ``_start_slot``; decode logits and tokens at
    ``_decode_collect``, where the look-ahead engine reads them."""

    def __init__(self, engine):
        self.first, self.steps, self.tokens = {}, {}, {}
        start, collect, finish = engine._start_slot, engine._decode_collect, engine._maybe_finish

        def on_start(lane, slot_idx, req, t_valid, next_rope, logits_row):
            self.first[req.request_id] = np.asarray(logits_row, np.float32)
            return start(lane, slot_idx, req, t_valid, next_rope, logits_row)

        def on_collect(lane, flight):
            logits = np.asarray(flight.logits, np.float32)
            for i, slot in flight.emitted(lane).items():
                self.steps.setdefault(slot.request.request_id, []).append(logits[i])
            return collect(lane, flight)

        def on_finish(lane, slot_idx, slot):
            self.tokens[slot.request.request_id] = list(slot.generated)
            return finish(lane, slot_idx, slot)

        engine._start_slot, engine._decode_collect, engine._maybe_finish = on_start, on_collect, on_finish


def _rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.abs(np.asarray(want)).max())


def test_pools_tables_and_ring(params):
    engine = _engine(params)
    try:
        assert engine._ring_blocks == 6
        assert engine._pool_k.shape == (1, engine.kv_pool_blocks, 2, BLOCK, 16)  # the full layer alone
        # a ring a row, never a lane's length: 2 x min(16, 6) + 2 x min(32, 6) blocks + block 0 + the prefix reserve
        assert engine._wpool_k.shape[0] == 3 and engine._wallocator.capacity - (engine.kv_pool_blocks - 1 - 96) == 24
        stats = engine.stats()
        assert stats["window_pool_bytes_per_chip"] == engine._wpool_k.nbytes * 2
        assert stats["full_pool_bytes_per_chip"] == engine._pool_k.nbytes * 2
        assert stats["kv_pool_bytes_per_chip"] == stats["window_pool_bytes_per_chip"] + stats["full_pool_bytes_per_chip"]
        with pytest.raises(ValueError, match="gather"):
            CaptionEngine(CFG, kv_lanes=LANES, paged_attention="gather")
    finally:
        engine.shutdown()


@pytest.mark.parametrize("shared_prefix", [False, True], ids=["no-prefix", "shared-prefix"])
def test_engine_prefill_then_decode_match_the_reference_in_both_lanes(params, shared_prefix):
    """Chunked prefill through a table that wraps (80 positions over a ring of
    24: three times round) and one that does not, then 12 decode steps each,
    against the reference's ONE full forward over prompt + generated ids."""
    engine = _engine(params)
    spy = _Spy(engine)
    prefix = _ids(9, seed=11) if shared_prefix else []
    prompts = {"long": _ids(71 - len(prefix), seed=5), "short": _ids(14, seed=6)}
    steps = 12
    run_prefill, chunks = engine._run_prefill, []

    def recording(lane, slots_arr, tables, embeds, write_index, t_valid, rope, ds):
        chunks.append((tables.shape, embeds.shape[1], np.asarray(write_index), np.asarray(t_valid)))
        return run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, rope, ds)

    engine._run_prefill = recording
    try:
        for name, ids in prompts.items():
            engine.add_request(CaptionRequest(
                name, ids, prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=steps + 1)
            ))
        engine.run_until_complete()
        stats = engine.stats()
        assert stats["paged_decode_pages_walked"] < stats["paged_decode_pages_spanned"]
        # the prefill kernel's walk, kind by kind: a full layer stops at the
        # chunk's newest key, a window layer also starts at its oldest
        from cosmos_curate_tpu.ops.paged_attention import prefill_pages_walked

        full = win = spanned = 0
        for (rows, nbl), t, write, t_valid in chunks:
            walk = functools.partial(prefill_pages_walked, write, write + t_valid, t, BLOCK, engine.model.dtype)
            full, win = full + walk()[0], win + walk(window=CFG.sliding_window)[0]
            spanned += walk()[1] * nbl
        n_full, n_win = len(CFG.full_layers), len(CFG.window_layers)
        assert 0 < win < full < spanned  # walked < spanned in both kinds
        assert stats["paged_prefill_pages_walked"] == n_full * full + n_win * win
        assert stats["paged_prefill_pages_spanned"] == (n_full + n_win) * spanned
    finally:
        engine.shutdown()
    assert engine._allocator.free_blocks == engine._allocator.capacity
    assert engine._wallocator.free_blocks == engine._wallocator.capacity
    sizes = ref.model_kwargs(CFG)
    for name, ids in prompts.items():
        full = prefix + ids + spy.tokens[name][:steps]
        t = len(prefix) + len(ids)
        want, _ = ref.logits_at(params, jnp.asarray(full), list(range(t - 1, t + steps)), **sizes)
        got = [spy.first[name], *spy.steps[name]]
        assert len(got) == steps + 1
        errs = [_rel(g, w) for g, w in zip(got, np.asarray(want))]
        # bfloat16 activations at width 64 against float32: 0.01-0.03 seen; a
        # key one position off, a page a ring off or rope on the full layer: over 0.3
        assert np.median(errs) < 0.04 and max(errs) < 0.12, (name, errs)


def test_a_wrapped_window_is_what_the_reference_computes_and_a_wrong_window_is_not(params):
    """The comparison above can tell: the same engine output against the
    reference with another window is several times further away."""
    engine = _engine(params)
    spy = _Spy(engine)
    ids = _ids(70, seed=5)
    try:
        engine.add_request(CaptionRequest("long", ids, sampling=SamplingConfig(max_new_tokens=1)))
        engine.run_until_complete()
    finally:
        engine.shutdown()
    sizes = ref.model_kwargs(CFG)
    right, _ = ref.last_logits(params, jnp.asarray(ids), **sizes)
    other = ref.model_kwargs(dataclasses.replace(CFG, sliding_window=14))
    wrong, _ = ref.last_logits(params, jnp.asarray(ids), **other)
    assert _rel(spy.first["long"], right) < 0.04 < 0.12 < _rel(spy.first["long"], wrong)


def test_a_row_that_wraps_leaves_the_shared_prefix_blocks_alone(params):
    """Two rows share a prefix. The short one references the prefix's window
    blocks; the long one will wrap its ring and so takes private copies. The
    short request served before, beside and after the long one reads the same
    logits to the bit, and the prefix's blocks in the window pool do not change."""
    engine = _engine(params)
    spy = _Spy(engine)
    prefix, short, long_ = _ids(9, seed=11), _ids(10, seed=12), _ids(80, seed=13)

    def serve(*names_ids, new=4):
        for name, ids in names_ids:
            engine.add_request(CaptionRequest(
                name, ids, prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=new)
            ))
        engine.run_until_complete()

    try:
        serve(("before", short))
        (entry,) = engine._prefix_cache.values()
        assert len(entry.wblocks) == 3 and entry.n_full == 2
        kept = np.asarray(engine._wpool_k[:, np.asarray(entry.wblocks)])
        serve(("beside", short), ("wraps", long_))
        serve(("after", short))
        assert engine.prefix_cache_hits >= 3
        np.testing.assert_array_equal(np.asarray(engine._wpool_k[:, np.asarray(entry.wblocks)]), kept)
    finally:
        engine.shutdown()
    for name in ("beside", "after"):
        np.testing.assert_array_equal(spy.first[name], spy.first["before"])
        np.testing.assert_array_equal(np.stack(spy.steps[name]), np.stack(spy.steps["before"]))
    # and the long one is right: it started from copies of the same blocks
    want, _ = ref.last_logits(params, jnp.asarray(prefix + long_), **ref.model_kwargs(CFG))
    assert _rel(spy.first["wraps"], want) < 0.04


def test_table_contents_by_kind_of_row(params):
    """A row that never wraps has the same table in both pools' terms (shared
    blocks first, private after, zeros past its need); a row that wraps repeats
    its ring, holds no shared block, and claims min(need, ring) blocks."""
    engine = _engine(params, async_prep=False)
    prefix = _ids(9, seed=11)
    try:
        engine.add_request(CaptionRequest("warm", _ids(5), prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=2)))
        engine.run_until_complete()
        (entry,) = engine._prefix_cache.values()
        for name, n in (("short", 6), ("long", 90)):
            engine.add_request(CaptionRequest(name, _ids(n), prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=8)))
        engine.step()
        rows = {
            (lane.slots.get(i) or lane.pending.get(i)).request.request_id: (lane, i)
            for lane in engine.lanes for i in list(lane.slots) + list(lane.pending)
        }
        lane, i = rows["short"]
        need = -(-(9 + 6 + 8 + 1) // BLOCK)
        assert (lane.wtable[i] != 0).sum() == need == (lane.table[i] != 0).sum()
        assert list(lane.wtable[i][:2]) == entry.wblocks[:2] and list(lane.table[i][:2]) == entry.blocks[:2]
        lane, i = rows["long"]
        need = -(-(9 + 90 + 8 + 1) // BLOCK)
        ring = lane.wtable[i][:6]
        assert len(set(ring)) == 6 and not set(ring) & set(entry.wblocks) and 0 not in ring
        np.testing.assert_array_equal(lane.wtable[i][:need], np.resize(ring, need))
        assert not lane.wtable[i][need:].any() and len(lane.claims[i].window) == 6
        assert list(lane.table[i][:2]) == entry.blocks[:2]  # the full pool shares as ever
        engine.run_until_complete()
    finally:
        engine.shutdown()


def test_long_prompts_always_prefill_in_chunks(params):
    """With no lane decoding the other flavors admit a long prompt as ONE
    bucket; a write that long would land on ring positions its own queries
    still see, so this flavor never does."""
    engine = _engine(params, async_prep=False)
    seen = []
    run = engine._run_prefill

    def spy(lane, slots_arr, tables, embeds, *rest):
        seen.append(np.asarray(embeds).shape[1])
        return run(lane, slots_arr, tables, embeds, *rest)

    engine._run_prefill = spy
    try:
        engine.add_request(CaptionRequest("long", _ids(60), sampling=SamplingConfig(max_new_tokens=2)))
        engine.run_until_complete()
    finally:
        engine.shutdown()
    assert seen and max(seen) == CHUNK and len(seen) == 8  # 60 tokens: 7 chunks and the shifted last


def test_another_flavor_has_one_pool_and_no_window_state():
    from cosmos_curate_tpu.models.vlm.model import VLM_TINY_TEST

    engine = CaptionEngine(VLM_TINY_TEST, kv_lanes=((64, 2),), block_size=4)
    engine.setup()
    try:
        assert engine._wallocator is None and engine._wpool_k is None and engine.lanes[0].wtable is None
        stats = engine.stats()
        assert stats["window_pool_bytes_per_chip"] == 0
        assert stats["full_pool_bytes_per_chip"] == stats["kv_pool_bytes_per_chip"] > 0
    finally:
        engine.shutdown()
