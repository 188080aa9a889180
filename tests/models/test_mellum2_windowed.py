"""Mellum2 (HF ``mellum``) at test size (``VLM_MELLUM2_TINY_TEST``): window and
YaRN full attention layers over two pools, every expert held, the PAGED programs
handing out the experts' choice, and a shared prefix LONGER than a row's ring of
window blocks. The engine against the plain reference
(perfbench/reference/mellum2_moe.py) on logits under the program's own choice;
the one ``yarn_inv_freq`` against transformers' own YaRN; the whole layer against
the uncut reference layer."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_DEEPSEEK_V2_EP8, VLM_MELLUM2_12B_PP4, VLM_MELLUM2_TINY_TEST, VLM_TRINITY_TINY_TEST, YarnConfig,
    init_cache, vlm_flavor, yarn_inv_freq,
)
from cosmos_curate_tpu.ops import grouped_matmul as gmm_ops
from perfbench.reference import mellum2_moe as ref

CFG = VLM_MELLUM2_TINY_TEST
BLOCK, CHUNK = 4, 8  # ring = ceil((10 + 8) / 4) + 1 = 6 blocks: 24 positions
LANES = ((64, 2), (128, 2))


@pytest.fixture(scope="module")
def params():
    """Seeded, with what a fresh init leaves trivial made to matter: norm scales
    off 1 (the seeded router's logits already spread by one or so: its softmax
    is neither flat nor saturated)."""
    tree = nn.unbox(_init_params(VLM(CFG), 0))
    rng = np.random.default_rng(7)

    def stir(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            return jnp.asarray(1 + 0.2 * rng.standard_normal(leaf.shape), leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(stir, tree)


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(10, 500, n).tolist()


def _forward(cfg, params, ids):
    """The program's slot-cache forward over a whole prompt in float32: logits [T, V], K cache."""
    model = VLM(cfg, dtype=jnp.float32)
    ids = jnp.asarray(ids, jnp.int32)[None]
    t = ids.shape[1]
    embeds = model.apply(params, ids, method=model.embed_tokens)
    ck, cv = init_cache(cfg, 1, dtype=jnp.float32, length=t)
    with jax.default_matmul_precision("highest"):
        logits, nk, _ = model.apply(
            params, embeds, ck, cv, jnp.arange(t)[None], jnp.zeros(1, jnp.int32), jnp.full((1,), t, jnp.int32)
        )
    return logits[0], nk


def _rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.abs(np.asarray(want)).max())


# -- (a) YaRN: one function of sizes ---------------------------------------------


def _hf_yarn(head_dim, theta, **scaling):
    from types import SimpleNamespace

    from transformers.modeling_rope_utils import _compute_yarn_parameters

    config = SimpleNamespace(
        rope_theta=theta, head_dim=head_dim, hidden_size=head_dim, num_attention_heads=1,
        max_position_embeddings=131072, rope_scaling=dict(rope_type="yarn", **scaling),
    )
    inv, gain = _compute_yarn_parameters(config, "cpu")
    return inv.numpy(), float(gain)


def test_yarn_table_against_transformers_for_mellum2s_numbers_and_deepseeks():
    yarn = VLM_MELLUM2_12B_PP4.full_attention_yarn
    assert yarn == YarnConfig(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    want, gain = _hf_yarn(128, 500000.0, factor=16, original_max_position_embeddings=8192, beta_fast=32, beta_slow=1,
                          attention_factor=1.2772588722239782)
    np.testing.assert_allclose(yarn.inv_freq(128, 500000.0), want, rtol=2e-7)
    assert yarn.gain == gain == 1.2772588722239782
    # the published attention_factor is the formula's own: 0.1 ln(16) + 1
    assert dataclasses.replace(yarn, attention_factor=None).gain == pytest.approx(1.2772588722239782, rel=1e-12)
    # the dims that turn more than 32 times over 8,192 positions are plain, those under once are / 16
    inv, plain = yarn.inv_freq(128, 500000.0), 500000.0 ** (-np.arange(64) / 64)
    turns = 8192 * plain / (2 * np.pi)
    np.testing.assert_allclose(inv[turns > 40], plain[turns > 40], rtol=1e-6)
    np.testing.assert_allclose(inv[turns < 0.8], plain[turns < 0.8] / 16, rtol=1e-6)
    assert ((inv < plain * (1 - 1e-6)) & (inv > plain / 16 * (1 + 1e-6))).sum() > 5  # and a ramp between
    # DeepSeek-V2's: the latent layer calls the same function with its own sizes
    mla = VLM_DEEPSEEK_V2_EP8.mla
    want, _ = _hf_yarn(64, 10000.0, factor=40, original_max_position_embeddings=4096, beta_fast=32, beta_slow=1)
    got = yarn_inv_freq(mla.qk_rope_head_dim, 10000.0, mla.yarn_factor, mla.yarn_original_max, mla.yarn_beta_fast, mla.yarn_beta_slow)
    np.testing.assert_allclose(got, want, rtol=2e-7)
    # the reference's table is its own code: the same numbers
    table, ref_gain = ref.model_kwargs(VLM_MELLUM2_12B_PP4)["attn"]["rope"]["full_attention"]
    np.testing.assert_allclose(np.asarray(table, np.float32), inv, rtol=2e-7)
    assert ref_gain == 1.2772588722239782
    assert ref.model_kwargs(VLM_MELLUM2_12B_PP4)["attn"]["rope"]["sliding_attention"][1] == 1.0
    # factor 1 is plain rope
    np.testing.assert_allclose(yarn_inv_freq(16, 1e4, 1.0, 32), 1e4 ** (-np.arange(8) / 8), rtol=1e-6)


def test_config_rope_by_layer_type():
    assert CFG.window_layers == (0, 1, 3) and CFG.full_layers == (2,)
    assert [CFG.yarn_in_layer(i) is not None for i in range(4)] == [False, False, True, False]
    big = VLM_MELLUM2_12B_PP4
    assert big.window_layers == (0, 1, 2, 4, 5, 6) and big.full_layers == (3, 7) and big.max_seq == 32768
    assert big.moe.held is None and big.moe.hand_out_choice and big.moe.n_experts == 64 and big.moe.top_k == 8
    assert (big.vocab, big.dim, big.n_heads, big.n_kv_heads, big.head_dim, big.sliding_window) == (98304, 2304, 32, 4, 128, 1024)
    spec = vlm_flavor("mellum2-12b-a2.5b-pp4")
    assert spec.text_only and spec.kv_lanes == ((8192, 4), (32768, 24)) and spec.prefill_rows == 4
    assert vlm_flavor("mellum2-tiny-test").cfg is CFG
    with pytest.raises(ValueError, match="full_attention_yarn"):  # afmoe's full layers carry no rope to scale
        dataclasses.replace(VLM_TRINITY_TINY_TEST, full_attention_yarn=YarnConfig(4.0, 32))
    # the tiny ramp is inside the eight rotary dims
    inv, plain = CFG.full_attention_yarn.inv_freq(16, CFG.rope_theta), CFG.rope_theta ** (-np.arange(8) / 8)
    assert inv[0] == pytest.approx(plain[0]) and inv[-1] == pytest.approx(plain[-1] / 4) and plain[1] / 4 < inv[1] < plain[1]


# -- (b) the model against the plain reference -------------------------------------


@pytest.mark.parametrize("product", ["ragged-dot", "gmm-k-whole"])
def test_whole_model_logits_and_k_rows_match_the_reference_at_every_position(params, product, monkeypatch):
    """``gmm-k-whole``: the experts' products through the Pallas kernel in interpret
    mode, K whole in a tile as a flavor with every expert held runs them on the chip."""
    if product == "gmm-k-whole":
        monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    asked = gmm_ops.tiles.cache_info()
    ids = _ids(70)  # seven windows deep, twice the YaRN table's original context
    logits, cache = _forward(CFG, params, ids)
    now = gmm_ops.tiles.cache_info()  # only the kernel asks for tiles
    assert (now.hits + now.misses > asked.hits + asked.misses) == (product == "gmm-k-whole")
    sizes = ref.model_kwargs(CFG)
    want, _ = ref.logits_at(params, jnp.asarray(ids), list(range(70)), **sizes)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=3e-5)
    rows = ref.cache_rows(params, jnp.asarray(ids), (2, 3), **sizes)
    for layer in (2, 3):  # the full layer's keys (YaRN) and a window layer's (plain rope)
        got = np.asarray(cache[layer, 0]).swapaxes(0, 1).reshape(70, -1)
        np.testing.assert_allclose(got, np.asarray(rows[layer]), atol=2e-5)


def test_yarn_and_the_window_change_the_result(params):
    """Neither is a no-op at this size: plain rope on the full layer, or a wider
    window, moves the logits past position 32."""
    ids = _ids(70)
    right, _ = _forward(CFG, params, ids)
    plain, _ = _forward(dataclasses.replace(CFG, full_attention_yarn=None), params, ids)
    wide, _ = _forward(dataclasses.replace(CFG, sliding_window=64), params, ids)
    assert _rel(plain[40:], right[40:]) > 1e-2 and _rel(wide[40:], right[40:]) > 1e-2
    np.testing.assert_allclose(np.asarray(wide[:10]), np.asarray(right[:10]), atol=3e-5)  # inside the window
    # a gain of 1 is another model too: a logit carries the factor's square
    flat, _ = _forward(dataclasses.replace(CFG, full_attention_yarn=dataclasses.replace(CFG.full_attention_yarn, attention_factor=1.0)), params, ids)
    assert _rel(flat[40:], right[40:]) > 1e-3


def test_the_whole_layer_with_every_expert_held_is_the_uncut_reference_layer(params):
    """One sparse window layer, all 8 experts held, against the reference's loop
    over ALL experts; and the reference following another choice is another answer."""
    one = dataclasses.replace(CFG, n_layers=1, layer_types=CFG.layer_types[:1])
    tree = {"params": {k: v for k, v in params["params"].items() if not k.startswith("layer_") or k == "layer_0"}}
    ids = _ids(24, seed=3)
    logits, _ = _forward(one, tree, ids)
    sizes = ref.model_kwargs(one)
    want, _ = ref.logits_at(tree, jnp.asarray(ids), list(range(24)), **sizes)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=3e-5)
    own = []
    ref.forward(tree, jnp.asarray(ids), choices=own, **sizes)
    other = (jnp.stack(own) + 1) % CFG.moe.n_experts
    h, _ = ref.forward(tree, jnp.asarray(ids), follow=other, **sizes)
    assert _rel(ref.logits_of(tree, h, **sizes), want) > 1e-3
    h, _ = ref.forward(tree, jnp.asarray(ids), follow=jnp.stack(own), **sizes)  # its own choice, followed: itself
    np.testing.assert_allclose(np.asarray(ref.logits_of(tree, h, **sizes)), np.asarray(want), atol=1e-6)


# -- (c) the engine ---------------------------------------------------------------


def _engine(params, **kw):
    engine = CaptionEngine(
        CFG, kv_lanes=LANES, params=jax.tree.map(jnp.copy, params), block_size=BLOCK, prefill_chunk=CHUNK, **kw,
    )
    engine.setup()
    return engine


class _Spy:
    """First-step logits, decode logits and tokens, and what the programs hand
    out LAST: a request's choice of experts from its prefill chunks and its
    decode steps (the prefix's build apart)."""

    def __init__(self, engine):
        self.first, self.steps, self.tokens, self.choice, self.step_choice = {}, {}, {}, {}, {}
        self.prefix_choice = None
        start, collect, finish = engine._start_slot, engine._decode_collect, engine._maybe_finish
        prefill, run_prefill, decode, prefix = engine._prefill_batch, engine._run_prefill, engine._decode, engine._prefix_prefill
        chunks, last = {}, []

        def on_start(lane, slot_idx, req, t_valid, next_rope, logits_row):
            self.first[req.request_id] = np.asarray(logits_row, np.float32)
            self.choice[req.request_id] = chunks.pop((lane.length, int(slot_idx)), [])
            return start(lane, slot_idx, req, t_valid, next_rope, logits_row)

        def on_prefill(*args):
            out = prefill(*args)
            assert len(out) == 4  # logits, K pools, V pools, the choice
            last[:] = [np.asarray(out[-1])]
            return out

        def on_run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest):
            logits = run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest)
            for j, slot_idx in enumerate(np.asarray(slots_arr)):
                chunks.setdefault((lane.length, int(slot_idx)), []).append((int(write_index[j]), int(t_valid[j]), last[0][:, j]))
            return logits

        def on_decode(params, pool_k, pool_v, tables, *rest):
            out = decode(params, pool_k, pool_v, tables, *rest)
            assert len(out) == 6 and out[-1].shape == (CFG.n_layers, tables[0].shape[0], 1, CFG.moe.top_k)
            lane = next(l for l in engine.lanes if l.table.shape == tables[0].shape)
            for i, slot in lane.slots.items():
                self.step_choice.setdefault(slot.request.request_id, []).append(np.asarray(out[-1][:, i, 0]))
            return out

        def on_prefix(*args):
            out = prefix(*args)
            self.prefix_choice = np.asarray(out[-1])
            return out

        def on_collect(lane, flight):
            logits = np.asarray(flight.logits, np.float32)
            for i, slot in flight.emitted(lane).items():
                self.steps.setdefault(slot.request.request_id, []).append(logits[i])
            return collect(lane, flight)

        def on_finish(lane, slot_idx, slot):
            self.tokens[slot.request.request_id] = list(slot.generated)
            return finish(lane, slot_idx, slot)

        engine._start_slot, engine._decode_collect, engine._maybe_finish = on_start, on_collect, on_finish
        engine._prefill_batch, engine._run_prefill, engine._decode, engine._prefix_prefill = on_prefill, on_run_prefill, on_decode, on_prefix

    def choice_of(self, name, n_prefix, n, steps):
        out = np.full((CFG.n_layers, n + steps, CFG.moe.top_k), -1, np.int32)
        if n_prefix:
            out[:, :n_prefix] = self.prefix_choice[:, :n_prefix]
        for at, valid, chunk in self.choice[name]:
            out[:, at : at + valid] = chunk[:, :valid]
        for j, step in enumerate(self.step_choice[name][:steps]):
            out[:, n + j] = step
        assert (out >= 0).all()
        return jnp.asarray(out)


def _followed(params, ids, choice, at):
    sizes = ref.model_kwargs(CFG)
    h, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), follow=choice, **sizes)
    return np.asarray(ref.logits_of(params, h[jnp.asarray(at)], **sizes))


def test_pools_ring_and_the_programs_hand_out_the_choice(params):
    engine = _engine(params)
    try:
        assert engine._ring_blocks == 6 and engine._hands_choice and engine._windowed and not engine._recurrent
        assert engine._pool_k.shape[0] == 1 and engine._wpool_k.shape[0] == 3
        assert engine._expert_held.shape == (2,)
        with pytest.raises(ValueError, match="gather"):
            CaptionEngine(CFG, kv_lanes=LANES, paged_attention="gather")
    finally:
        engine.shutdown()
    # a flavor without the flag compiles the programs it compiled before: no fourth output
    other = CaptionEngine(VLM_TRINITY_TINY_TEST, kv_lanes=LANES, block_size=BLOCK, prefill_chunk=CHUNK)
    other.setup()
    try:
        assert not other._hands_choice and other._prefill_batch.__name__ == "prefill_batch_paged"
        assert other._decode.__name__ == "decode_step_counted"
    finally:
        other.shutdown()


@pytest.mark.parametrize("prefix_len", [0, 9, 37], ids=["no-prefix", "prefix-inside-the-ring", "prefix-past-the-ring"])
def test_engine_prefill_then_decode_match_the_reference_under_the_programs_choice(params, prefix_len):
    """Chunked prefill through a table that wraps (the ring written round more
    than three times), then 12 decode steps through both pools, in both lanes,
    against the reference's ONE full forward that follows the program's choice."""
    engine = _engine(params)
    spy = _Spy(engine)
    prefix = _ids(prefix_len, seed=11)
    prompts = {"long": _ids(90 - prefix_len, seed=5), "short": _ids(14, seed=6)}
    steps = 12
    try:
        if prefix:  # the build first, so that both are hits
            engine.add_request(CaptionRequest("build", _ids(5, seed=2), prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=1)))
            engine.run_until_complete()
        for name, ids in prompts.items():
            engine.add_request(CaptionRequest(name, ids, prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=steps + 1)))
        engine.run_until_complete()
        stats = engine.stats()
        if prefix:
            assert stats["prefix_cache_hits"] == 2 and stats["prefix_tokens_saved"] == 2 * prefix_len
        if prefix_len == 37:  # 10 blocks > the ring of 6: the entry holds the 4 of its tail, each row copied them
            assert stats["prefix_window_blocks_held"] == 4 and stats["prefix_tail_blocks_copied"] == 3 * 4
        assert stats["expert_assignments_held"] > 0
    finally:
        engine.shutdown()
    assert engine._allocator.free_blocks == engine._allocator.capacity
    assert engine._wallocator.free_blocks == engine._wallocator.capacity
    assert engine.stats()["prefix_window_blocks_held"] == 0
    for name, ids in prompts.items():
        t = prefix_len + len(ids)
        full = prefix + ids + spy.tokens[name][:steps]
        want = _followed(params, full, spy.choice_of(name, prefix_len, t, steps), list(range(t - 1, t + steps)))
        got = [spy.first[name], *spy.steps[name]]
        assert len(got) == steps + 1
        errs = [_rel(g, w) for g, w in zip(got, want)]
        # bfloat16 activations at width 64 against float32 along ONE choice: 0.005-0.02 seen; a key one position
        # off, a page a ring slot off, plain rope on the full layer or a copied tail in the wrong slots: over 0.2
        assert max(errs) < 0.05, (name, errs)


def test_a_prefix_past_the_ring_serves_what_the_prefix_cache_off_serves_and_frees_what_it_took(params):
    """37 tokens are 10 blocks, the ring 6: the entry keeps all 10 in the full
    pool and blocks 6-9 in the window pool; a row admitted on it shares the
    full blocks, copies the tail into ring slots 0-3 (6 % 6 ...) and reads, to
    the bit on the CPU, what the same ids prefilled whole read."""
    prefix = _ids(37, seed=11)
    reqs = [("a", _ids(30, seed=5)), ("b", _ids(60, seed=6)), ("c", _ids(5, seed=7))]
    seen = {}
    for cache in (True, False):
        engine = _engine(params, enable_prefix_cache=cache)
        spy = _Spy(engine)
        try:
            for name, ids in reqs:
                engine.add_request(CaptionRequest(name, ids, prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=6)))
            engine.run_until_complete()
            if cache:
                (entry,) = engine._prefix_cache.values()
                assert (entry.wfirst, len(entry.wblocks), len(entry.blocks), entry.n_full) == (6, 4, 10, 9)
                assert engine.prefix_cache_hits == 2 and engine.stats()["prefix_tail_blocks_copied"] == 12
                assert engine.phase_seconds["prefix_tail_copy_n"] == 3 and engine.phase_seconds["prefix_tail_copy_s"] > 0
                used, wused = engine._allocator.used_blocks, engine._wallocator.used_blocks
                assert (used, wused) == (10, 4)  # the rows released theirs: the entry's own are what is left
                engine.clear_prefix_cache()  # eviction returns what was taken
            else:
                assert engine.prefix_cache_hits == 0 and engine.stats()["prefix_tail_blocks_copied"] == 0
            assert engine._allocator.free_blocks == engine._allocator.capacity
            assert engine._wallocator.free_blocks == engine._wallocator.capacity
        finally:
            engine.shutdown()
        seen[cache] = spy
    for name, _ in reqs:
        np.testing.assert_array_equal(seen[True].first[name], seen[False].first[name])
        np.testing.assert_array_equal(np.stack(seen[True].steps[name]), np.stack(seen[False].steps[name]))
        assert seen[True].tokens[name] == seen[False].tokens[name]


def test_the_tail_lands_in_the_ring_slots_of_its_logical_blocks(params):
    """The row's window table after admission: logical block j in ring slot j %
    6, no block shared; the admission's one copy takes the entry's tail (logical
    blocks 6-9) to ring slots 0-3; the full table shares the entry's whole blocks."""
    engine = _engine(params, async_prep=False)
    prefix = _ids(37, seed=11)
    copies, copy = [], engine._copy_blocks

    def on_copy(pool_k, pool_v, src, dst):
        if pool_k.shape == engine._wpool_k.shape:
            copies.append((np.asarray(src).tolist(), np.asarray(dst).tolist()))
        return copy(pool_k, pool_v, src, dst)

    engine._copy_blocks = on_copy
    try:
        engine.add_request(CaptionRequest("build", _ids(5), prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=1)))
        engine.run_until_complete()
        (entry,) = engine._prefix_cache.values()
        del copies[:]
        engine.add_request(CaptionRequest("row", _ids(40), prefix_ids=list(prefix), sampling=SamplingConfig(max_new_tokens=4)))
        engine.step()
        lane, i = next((l, j) for l in engine.lanes for j in list(l.pending) + list(l.slots))
        ring = lane.wtable[i][:6].tolist()
        need = -(-(37 + 40 + 4 + 1) // BLOCK)
        assert len(set(ring)) == 6 and 0 not in ring and not set(ring) & set(entry.wblocks)
        np.testing.assert_array_equal(lane.wtable[i][:need], np.resize(ring, need))
        assert list(lane.table[i][:9]) == entry.blocks[:9] and lane.table[i][9] != entry.blocks[9]  # the tail block: copy-on-write
        assert copies == [(entry.wblocks, [ring[j % 6] for j in range(6, 10)])]
        engine.run_until_complete()
    finally:
        engine.shutdown()
