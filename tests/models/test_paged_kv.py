"""Paged KV cache: block allocator, refcounted prefix blocks, and greedy
parity with the slot-row engine's math (tiny config, CPU).

The parity reference below reproduces the OLD slot-row engine exactly: one
request at a time through a private contiguous ``[L, 1, Hkv, S, Dh]`` cache
(the unchanged model's own layout), prefilled in one shot and greedily
decoded token by token. The paged engine — block tables, shared refcounted
prefix blocks, copy-on-write tails, batched admission, chunked prefill —
must produce byte-identical text, across lane buckets and under m-rope:
paging is a memory-management change, not an approximation.
"""

import dataclasses
import threading
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.tokenizer import ByteTokenizer
from cosmos_curate_tpu.models.vlm import (
    BlockAllocator,
    CaptionEngine,
    CaptionRequest,
    PoolExhausted,
    SamplingConfig,
    VLM_TINY_TEST,
)
from cosmos_curate_tpu.models.vlm.model import init_cache
from tests.ops.test_tpu_compile import WIDTHS  # (Hkv, G, D) of the flavors the kernels serve

TOK = ByteTokenizer()
PREFIX = "system: you are a terse captioner. user:"


def _req(rid, text="describe", prefix=PREFIX, frames=2, max_new=6, **kw):
    return CaptionRequest(
        request_id=rid,
        prefix_ids=TOK.encode(prefix) if prefix else [],
        prompt_ids=TOK.encode(text),
        frames=(
            # crc32, not hash(): frames must be identical across processes
            # (greedy parity on a random-init bf16 model is full of
            # near-ties — per-process PYTHONHASHSEED draws would make these
            # tests a dice roll)
            np.random.default_rng(zlib.crc32(rid.encode())).integers(
                0, 255, (frames, 32, 32, 3), np.uint8
            )
            if frames
            else None
        ),
        sampling=SamplingConfig(max_new_tokens=max_new),
        **kw,
    )


def _drain(eng, reqs):
    for r in reqs:
        eng.add_request(r)
    return {r.request_id: r.text for r in eng.run_until_complete()}


# (Hkv, G, D) of the tiny preset, and of the two served flavors whose pool
# holds two KV heads a 128-lane row, on the tiny preset's other widths
HEAD_WIDTHS = {"tiny-test": (2, 2, 16), "base": WIDTHS["base"], "granite-4.0-h-micro": WIDTHS["granite-4.0-h-micro"]}


def _with_heads(name):
    """(the tiny preset with ``name``'s attention heads, KV heads a pool row)."""
    from cosmos_curate_tpu.ops.paged_attention import heads_per_row

    hk, g, d = HEAD_WIDTHS[name]
    cfg = dataclasses.replace(VLM_TINY_TEST, n_heads=hk * g, n_kv_heads=hk, head_dim=d)
    return cfg, heads_per_row(hk, d)


def slot_row_reference(eng: CaptionEngine, req: CaptionRequest, cache_len: int) -> str:
    """Greedy decode of ONE request through the SLOT-ROW engine's exact
    jitted programs: batched prefill that gathers the slot's contiguous
    cache rows inside the program, scatters them back and takes the
    last-position logits; an input-fed full-cache decode step. Program
    structure is replicated deliberately — it is what makes the comparison
    byte-exact rather than merely close (XLA fuses a scatter-free or
    differently-consumed graph into different FP schedules)."""
    from cosmos_curate_tpu.models.batching import next_pow2

    cfg, model, params = eng.cfg, eng.model, eng.params
    mrope = cfg.mrope_section is not None

    @partial(jax.jit, donate_argnums=(1, 2))
    def prefill(params, cache_k, cache_v, embeds, slots, write_index, t_valid, rope_pos):
        ck = cache_k[:, slots]
        cv = cache_v[:, slots]
        logits, nk, nv = model.apply(
            params, embeds, ck, cv, rope_pos, write_index, write_index + t_valid,
            logits_at=t_valid - 1,
        )
        cache_k = cache_k.at[:, slots].set(nk)
        cache_v = cache_v.at[:, slots].set(nv)
        return logits[:, 0], cache_k, cache_v

    @partial(jax.jit, donate_argnums=(1, 2))
    def decode(params, cache_k, cache_v, tokens, positions, rope_positions):
        embeds = model.apply(params, tokens[:, None], method=model.embed_tokens)
        rp = rope_positions[:, None]
        if mrope:
            rp = jnp.broadcast_to(rp[..., None], (*rp.shape, 3))
        logits, ck, cv = model.apply(
            params, embeds, cache_k, cache_v, rp, positions, positions + 1
        )
        greedy = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return greedy, ck, cv

    embeds, t_valid, rope, next_rope, ds = eng._prepare_embeds(req)
    assert ds is None, "reference covers non-deepstack configs"
    bucket = min(next_pow2(t_valid), cache_len)
    emb_pad = np.zeros((1, bucket, embeds.shape[-1]), np.float32)
    emb_pad[0, :t_valid] = np.asarray(embeds, np.float32)[:t_valid]
    rope_np = np.asarray(rope)
    rope_pad = np.zeros((1, bucket, *rope_np.shape[1:]), np.int32)
    rope_pad[0, :t_valid] = rope_np[:t_valid]
    ck, cv = init_cache(cfg, 1, length=cache_len)
    last, ck, cv = prefill(
        params,
        ck,
        cv,
        jnp.asarray(emb_pad),
        jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int32),
        jnp.full((1,), t_valid, jnp.int32),
        jnp.asarray(rope_pad),
    )
    generated = [int(np.argmax(np.asarray(last)[0]))]
    position, rope_position = t_valid, next_rope
    while (
        generated[-1] != eng.tokenizer.eos_id
        and len(generated) < req.sampling.max_new_tokens
        and position + 1 < cache_len
    ):
        greedy, ck, cv = decode(
            params,
            ck,
            cv,
            jnp.asarray([generated[-1]], jnp.int32),
            jnp.asarray([position], jnp.int32),
            jnp.asarray([rope_position], jnp.int32),
        )
        generated.append(int(np.asarray(greedy)[0]))
        position += 1
        rope_position += 1
    return eng.tokenizer.decode(
        [t for t in generated if t != eng.tokenizer.eos_id]
    )


class TestBlockAllocator:
    def test_alloc_refcount_lifecycle(self):
        a = BlockAllocator(8)
        assert a.capacity == 7 and a.free_blocks == 7
        ids = a.alloc(3)
        assert 0 not in ids  # the garbage block is never handed out
        assert a.used_blocks == 3
        a.incref(ids[:2])
        assert a.decref(ids) == [ids[2]]  # two still referenced
        assert a.used_blocks == 2
        assert sorted(a.decref(ids[:2])) == sorted(ids[:2])
        assert a.used_blocks == 0 and a.free_blocks == 7

    def test_exhaustion_and_misuse(self):
        a = BlockAllocator(4)
        ids = a.alloc(3)
        assert not a.can_alloc(1)
        with pytest.raises(PoolExhausted):
            a.alloc(1)
        a.decref(ids)
        with pytest.raises(ValueError):
            a.decref([ids[0]])  # double free
        with pytest.raises(ValueError):
            a.incref([ids[0]])  # incref on a free block


# The paged engine under the gnarly geometry: short/long lanes, small
# prefill chunks, a small block size — every parity case also exercises
# lane routing, base-offset chunk placement, and non-aligned prefix tails
# (PREFIX is 41 byte-tokens: 2 full blocks + a copy-on-write tail at bs=16).
@pytest.fixture(scope="module")
def paged():
    eng = CaptionEngine(
        VLM_TINY_TEST, max_batch=4, kv_lanes=((64, 2), (128, 2)), prefill_chunk=16
    )
    eng.setup()
    return eng


class TestSlotRowParity:
    def test_batched_paged_matches_slot_row_reference(self, paged):
        """A batched drive through block tables + shared prefix blocks must
        be byte-identical to one-request-at-a-time contiguous-cache
        decoding at each request's lane length."""
        reqs = [_req(f"r{i}", text=f"clip number {i}") for i in range(4)]
        got = _drain(paged, reqs)
        for i in range(4):
            # prefix + vision + prompt + max_new needs > 64: the 128 lane
            # serves these, so the reference row is 128 long too
            want = slot_row_reference(paged, _req(f"r{i}", text=f"clip number {i}"), 128)
            assert got[f"r{i}"] == want, f"r{i}"

    def test_parity_across_lane_buckets(self, paged):
        """Short request (64 lane) and long request (128 lane): each must
        match the reference at ITS lane's cache length."""
        got = _drain(
            paged,
            [_req("short", text="hi", max_new=4), _req("long", text="w " * 30, max_new=6)],
        )
        assert got["short"] == slot_row_reference(
            paged, _req("short", text="hi", max_new=4), 64
        )
        assert got["long"] == slot_row_reference(
            paged, _req("long", text="w " * 30, max_new=6), 128
        )

    def test_parity_under_chunked_prefill(self, paged):
        """Chunk writes at base + progress through the block table (final
        chunk shifts back) must land exactly where one-shot prefill puts
        them."""
        paged.add_request(_req("warm", text="zz", max_new=24, frames=0))
        paged.step()  # decode active -> the next admit must chunk
        paged.add_request(_req("x", text="c " * 20, max_new=8))
        paged.step()
        assert paged.pending, "long suffix should chunk while decoding"
        got = {r.request_id: r.text for r in paged.run_until_complete()}
        assert got["x"] == slot_row_reference(
            paged, _req("x", text="c " * 20, max_new=8), 128
        )

    def test_parity_under_mrope(self):
        """Qwen2-VL m-rope: vision tokens share (t, h, w) rope coordinates
        while the cache index keeps marching — block-table gathers must not
        disturb the rope/cache-position split."""
        from cosmos_curate_tpu.models.vlm.model import VLM_QWEN2VL_TINY_TEST

        eng = CaptionEngine(VLM_QWEN2VL_TINY_TEST, max_batch=2, block_size=8)
        eng.setup()
        got = _drain(eng, [_req(f"q{i}", text=f"scene {i}", max_new=4) for i in range(2)])
        for i in range(2):
            want = slot_row_reference(
                eng, _req(f"q{i}", text=f"scene {i}", max_new=4), eng.cfg.max_seq
            )
            assert got[f"q{i}"] == want, f"q{i}"


class TestRefcountedPrefixBlocks:
    def test_admission_references_instead_of_copying(self, paged):
        """Prefix sharing is copy-free: block references accumulate, and
        only the non-aligned tail pays a one-block copy-on-write."""
        paged.reset_stats()
        pre = "system: reference, do not copy, these tokens. user:"
        tp = len(TOK.encode(pre))
        n_full = tp // paged.block_size
        assert n_full >= 1 and tp % paged.block_size, "test wants a CoW tail"
        _drain(paged, [_req(f"c{i}", prefix=pre, text=f"v{i}") for i in range(3)])
        assert paged.prefix_block_refs == 3 * n_full
        assert paged.kv_cow_copies == 3
        assert paged.prefix_tokens_saved == tp * 2  # builder pays once

    def test_eviction_defers_free_while_referenced(self):
        """Evicting a prefix whose blocks are mapped by an in-flight slot
        must NOT free them — the slot keeps decoding against intact K/V and
        the blocks free only at release."""
        eng = CaptionEngine(
            VLM_TINY_TEST, max_batch=2, kv_lanes=((128, 2),), prefix_cache_size=1
        )
        eng.setup()
        pre_a = "system: the first shared prefix text. user:"
        pre_b = "system: a second, different prefix. user:"
        eng.add_request(_req("a", prefix=pre_a, text="go", max_new=48, frames=0))
        eng.step()  # admit: slot now references pre_a's blocks
        entry = next(iter(eng._prefix_cache.values()))
        shared = entry.blocks[: entry.n_full]
        assert all(eng._allocator.ref(b) == 2 for b in shared)  # LRU + slot
        # capacity-1 LRU: building pre_b evicts pre_a while 'a' is in flight
        eng.add_request(_req("b", prefix=pre_b, text="hm", max_new=2, frames=0))
        results = {}
        while len(eng.slots) or eng.waiting or eng.pending:
            eng.step()
            for r in eng.completed:
                results[r.request_id] = r.text
        assert tuple(TOK.encode(pre_a)) not in eng._prefix_cache  # evicted
        # deferred free happened at 'a's release, not at eviction: pool
        # drains to exactly the surviving LRU entry's blocks
        eng.run_until_complete()
        live = next(iter(eng._prefix_cache.values()))
        assert eng.kv_blocks_used == len(live.blocks)
        # and the evicted-prefix request decoded against intact blocks
        ref = CaptionEngine(VLM_TINY_TEST, max_batch=2, enable_prefix_cache=False)
        ref.setup()
        ref.params = eng.params
        want = slot_row_reference(
            ref, _req("a", prefix=pre_a, text="go", max_new=48, frames=0), 128
        )
        done = {r.request_id: r.text for r in eng.completed} | results
        assert done["a"] == want

    def test_shutdown_after_drain_leaves_pool_fully_free(self):
        """No leaks: after draining in-flight work and shutting down (which
        releases the LRU's own block references), every pool block is
        free."""
        eng = CaptionEngine(VLM_TINY_TEST, max_batch=4, kv_lanes=((64, 2), (128, 2)))
        eng.setup()
        _drain(eng, [_req(f"s{i}", text=f"t{i}") for i in range(5)])
        assert eng.kv_blocks_used > 0  # prefix entry still cached
        eng.shutdown()
        assert eng.kv_blocks_used == 0, (
            f"{eng.kv_blocks_used} blocks leaked of {eng.kv_blocks_total}"
        )

    def test_pool_exhaustion_backpressures_admission(self):
        """Occupancy-based admission: a pool too small for every slot makes
        later requests WAIT for blocks (not fail), and all complete."""
        eng = CaptionEngine(
            VLM_TINY_TEST,
            max_batch=4,
            kv_lanes=((128, 4),),
            enable_prefix_cache=False,
            # room for ~2 in-flight worst-case requests, not 4
            kv_pool_blocks=1 + 2 * (128 // 16),
        )
        eng.setup()
        # kv_pool_blocks is floored at the lane sum so a full slot load
        # cannot deadlock — verify the floor held
        assert eng.kv_blocks_total == 4 * (128 // 16)
        got = _drain(
            eng, [_req(f"p{i}", text="x " * 40, max_new=8, frames=0) for i in range(4)]
        )
        assert sorted(got) == [f"p{i}" for i in range(4)]

    def test_prefix_hoarding_idle_pool_does_not_deadlock(self):
        """A prefix entry hoarding an otherwise-idle pool must not wedge
        admission: with nothing in flight to wait on, the engine folds the
        prefix back into the request, evicts the idle entry, and serves
        the request uncached."""
        eng = CaptionEngine(
            VLM_TINY_TEST,
            max_batch=1,
            kv_lanes=((128, 1),),
            kv_pool_blocks=1 + 8,  # floored: room for ONE worst-case request
        )
        eng.setup()
        # prefix (3 blocks) + suffix + generation spans the whole pool:
        # shared claim cannot fit beside the cached entry
        got = _drain(eng, [_req("h", text="x " * 28, max_new=24, frames=0)])
        assert "h" in got and got["h"]
        eng.shutdown()
        assert eng.kv_blocks_used == 0

    def test_kv_reservation_below_worst_case(self, paged):
        # sized to land in the 128 lane while needing only ~6 blocks —
        # ceil(len/bs) must undershoot the worst-case lane row
        paged.reset_stats()  # the peak restarts at what the prefix cache holds
        held = paged.kv_blocks_used
        _drain(paged, [_req(f"k{i}", text="w " * 15, max_new=4) for i in range(2)])
        block_bytes = paged.kv_bytes() // paged.kv_pool_blocks  # K + V of one block, every layer
        claimed_bytes = (paged.kv_blocks_used_peak - held) * block_bytes
        # what two whole rows of the 128 lane hold: a slot-row engine's reservation
        lane_rows_bytes = 2 * (128 // paged.block_size) * block_bytes
        assert 0 < claimed_bytes < lane_rows_bytes


class TestPagedAttentionModes:
    """The paged programs (ops/paged_attention.py, reference path on CPU)
    vs the legacy gather-view programs: byte-identical outputs AND pool
    contents, with the working-set counters proving which path ran."""

    GNARLY = dict(max_batch=4, kv_lanes=((64, 2), (128, 2)), prefill_chunk=16)

    @staticmethod
    def _mode_engine(mode, params=None, cfg=VLM_TINY_TEST, **kw):
        eng = CaptionEngine(cfg, paged_attention=mode, **kw)
        eng.setup()
        if params is not None:
            eng.params = params
        return eng

    @pytest.mark.parametrize("mode", ["bogus", "kernel"])  # "kernel" is no alias of "auto"
    def test_invalid_mode_rejected(self, mode):
        with pytest.raises(ValueError, match=r"auto\|gather"):
            CaptionEngine(VLM_TINY_TEST, paged_attention=mode)

    @pytest.mark.parametrize(
        "name",
        ["CURATE_PAGED_ATTENTION", "CURATE_PAGED_KERNEL", "CURATE_FLASH_DECODE", "CURATE_FLASH_PREFILL"],
    )
    def test_a_deleted_switch_left_in_the_environment_is_inert(self, monkeypatch, name):
        """The four variables that once chose an attention family are read
        by nothing: what an operator's shell or the benchmark's driver
        still sets changes neither the programs an engine builds nor a
        token of its output."""

        def run():
            eng = self._mode_engine("auto", max_batch=2, kv_lanes=((64, 2),), prefill_chunk=16)
            got = _drain(eng, [_req("short", text="hi", max_new=4)])
            return got, eng.stats()

        want, _ = run()
        monkeypatch.setenv(name, "1")
        monkeypatch.setenv("CURATE_PAGED_ATTENTION", "gather")
        got, stats = run()
        assert stats["paged_attention"] == "auto"
        assert stats["paged_kernel_steps"] > 0
        assert got == want

    def test_stats_surface_block_size_fallback_and_mode(self):
        # 24 does not divide 64/128 lanes: gcd fallback shrinks it to 8 —
        # stats must show BOTH sides so runs compared are not apples-to-oranges
        eng = self._mode_engine("auto", **self.GNARLY, block_size=24)
        stats = eng.stats()
        assert stats["kv_block_size_requested"] == 24
        assert stats["kv_block_size"] == 8 == eng.block_size
        assert stats["paged_attention"] == "auto"
        assert stats["mesh_geometry"] == ()
        for key in (
            "paged_kernel_steps", "paged_decode_pages_walked", "paged_decode_pages_spanned",
            "paged_prefill_pages_walked", "paged_prefill_pages_spanned",
        ):
            assert key in stats

    @pytest.mark.parametrize("heads", sorted(HEAD_WIDTHS))
    def test_kernel_vs_gather_bit_equal_across_lane_buckets(self, heads):
        """Same prompts through both program families, spanning both lane
        buckets and chunked prefill: greedy texts AND every written pool
        cell must match bitwise (block 0 is the garbage block — idle rows
        park writes there and the two families park different garbage).
        At the tiny preset's own heads (a pool row a head) and at `base`'s
        and Granite's, whose pool holds two 64-wide heads a row: the paged
        programs read that pool in place, the gather programs through views
        split into head planes, and both against the slot-row reference,
        which knows of no pool."""
        cfg, r = _with_heads(heads)
        kernel = self._mode_engine("auto", cfg=cfg, **self.GNARLY)
        gather = self._mode_engine("gather", kernel.params, cfg=cfg, **self.GNARLY)
        assert kernel.stats()["kv_heads_per_pool_row"] == gather.stats()["kv_heads_per_pool_row"] == r
        assert kernel._pool_k.shape[2:] == (cfg.n_kv_heads // r, kernel.block_size, r * cfg.head_dim)
        if r > 1:
            assert kernel._pool_k.shape[-1] == 128

        def reqs():
            return [
                _req("short", text="hi", max_new=4),  # 64 lane
                _req("long", text="w " * 30, max_new=6),  # 128 lane
                _req("mid", text="clip number 9", max_new=6),
            ]

        got_k = _drain(kernel, reqs())
        got_g = _drain(gather, reqs())
        assert got_k == got_g
        assert got_k["mid"] == slot_row_reference(kernel, reqs()[2], 128)
        np.testing.assert_array_equal(
            np.asarray(kernel._pool_k)[:, 1:], np.asarray(gather._pool_k)[:, 1:]
        )
        np.testing.assert_array_equal(
            np.asarray(kernel._pool_v)[:, 1:], np.asarray(gather._pool_v)[:, 1:]
        )
        # structural proof the gathered working set was eliminated vs kept
        assert kernel.paged_kernel_steps > 0
        assert gather.paged_kernel_steps == 0

    def test_pages_walked_and_spanned_follow_the_rows_lengths(self):
        """``paged_decode_pages_walked`` is what the decode kernel's loop
        covers (each row's valid length in pages, idle rows one page),
        ``paged_decode_pages_spanned`` what the rows' tables hold: both
        recomputed here from the arguments of every decode program of a
        two-lane engine."""
        eng = self._mode_engine("auto", **self.GNARLY)
        decode, seen = eng._decode, []

        def recording(params, pool_k, pool_v, tables, tokens, positions, rope_positions):
            seen.append((tables.shape, np.asarray(positions)))
            return decode(params, pool_k, pool_v, tables, tokens, positions, rope_positions)

        eng._decode = recording
        _drain(eng, [_req("short", text="hi", max_new=4), _req("long", text="w " * 30, max_new=6)])
        stats, bs = eng.stats(), eng.block_size
        assert {shape for shape, _ in seen} == {(2, 64 // bs), (2, 128 // bs)}  # both lanes decoded
        assert stats["paged_kernel_steps"] == len(seen)
        assert stats["paged_decode_pages_spanned"] == sum(rows * nbl for (rows, nbl), _ in seen)
        walked = sum(int(np.ceil((pos + 1) / bs).sum()) for _, pos in seen)
        assert stats["paged_decode_pages_walked"] == walked
        assert len(seen) * 2 <= walked < stats["paged_decode_pages_spanned"]
        eng.reset_stats()
        assert eng.stats()["paged_decode_pages_walked"] == eng.stats()["paged_decode_pages_spanned"] == 0

    def test_prefill_pages_walked_and_spanned_follow_the_rows_lengths(self):
        """``paged_prefill_pages_walked`` is what the prefill kernel's loops
        cover (for each block of queries the pages up to its newest visible
        key, within the row's valid length), ``paged_prefill_pages_spanned``
        what one page a grid step over the table stepped (blocks of queries x
        entries a row): both recomputed here from the arguments of every
        prefill program of a two-lane engine, whole prompts and chunks."""
        from cosmos_curate_tpu.ops.paged_attention import _prefill_block_q

        eng = self._mode_engine("auto", **self.GNARLY)
        prefill, seen = eng._prefill_batch, []

        def recording(params, pool_k, pool_v, tables, embeds, write_index, t_valid, rope_pos, ds):
            seen.append((tables.shape, embeds.shape[1], np.asarray(write_index), np.asarray(t_valid)))
            return prefill(params, pool_k, pool_v, tables, embeds, write_index, t_valid, rope_pos, ds)

        eng._prefill_batch = recording
        _drain(eng, [_req("short", text="hi", max_new=4), _req("long", text="w " * 30, max_new=6)])
        stats, bs = eng.stats(), eng.block_size
        assert len(seen) >= 2 and any(write.any() for _, _, write, _ in seen)  # a later chunk among them
        walked = spanned = 0
        for (rows, nbl), t, write, t_valid in seen:
            block_q = _prefill_block_q(t, eng.model.dtype)
            for row in range(rows):
                for first in range(int(write[row]), int(write[row]) + t, block_q):
                    newest = min(int(write[row] + t_valid[row]), first + block_q)  # one past it
                    walked += -(-newest // bs)
                    spanned += nbl
        assert stats["paged_prefill_pages_walked"] == walked
        assert stats["paged_prefill_pages_spanned"] == spanned
        assert 0 < walked < spanned
        eng.reset_stats()
        assert eng.stats()["paged_prefill_pages_walked"] == eng.stats()["paged_prefill_pages_spanned"] == 0

    def test_parity_with_fragmented_block_table(self):
        """Blocks deliberately NON-CONTIGUOUS in the pool — the layout the
        gather path never distinguishes but the table-walking op must: punch
        holes in the allocator so the request's table interleaves recycled
        and fresh blocks, then demand byte parity with the slot-row
        reference."""
        eng = CaptionEngine(
            VLM_TINY_TEST,
            max_batch=2,
            kv_lanes=((128, 2),),
            enable_prefix_cache=False,
            block_size=16,
        )
        eng.setup()
        held = eng._allocator.alloc(6)
        eng._allocator.decref(held[::2])  # free every other -> holes
        eng.add_request(_req("frag", text="scatter me around", max_new=6, frames=0))
        eng.step()
        claim = next(iter(eng.lanes[0].claims.values()))
        blocks = claim.all_blocks
        assert blocks != sorted(blocks) or any(
            b - a != 1 for a, b in zip(blocks, blocks[1:])
        ), f"table {blocks} is contiguous; fragmentation precondition failed"
        got = {r.request_id: r.text for r in eng.run_until_complete()}
        want = slot_row_reference(
            eng, _req("frag", text="scatter me around", max_new=6, frames=0), 128
        )
        assert got["frag"] == want
        eng._allocator.decref(held[1::2])


class TestSlotBranchAgainstPagedBranch:
    """``DecoderLayer``'s slot-cache branch (the ``gather`` programs) and
    its paged branch on the XLA reference run one attention function on the
    same shapes, so a layer's output and the K/V it wrote are bit-equal at
    every width the kernels are compiled for, not only at ``VLM_TINY_TEST``."""

    B, S, BS, LAYER = 2, 64, 16, 1

    @pytest.mark.parametrize("widths", sorted(WIDTHS))
    @pytest.mark.parametrize("t,write", [(1, [37, 5]), (16, [21, 32])], ids=["decode", "chunk-T16"])
    def test_bit_equal(self, widths, t, write):
        from cosmos_curate_tpu.models.vlm.model import DecoderLayer, VLMConfig
        from cosmos_curate_tpu.models.vlm.paged_kv import gather_block_views
        from cosmos_curate_tpu.ops.paged_attention import heads_per_row, join_rows

        hk, g, d = WIDTHS[widths]
        cfg = VLMConfig(dim=64, n_heads=hk * g, n_kv_heads=hk, head_dim=d, hidden_mult=2.0)
        layer = DecoderLayer(cfg)
        rng = np.random.default_rng(zlib.crc32(widths.encode()) + t)
        nbl = self.S // self.BS
        n_blocks = self.B * nbl + 2
        a_head_a_row = [
            jnp.asarray(rng.standard_normal((2, n_blocks, hk, self.BS, d)), jnp.bfloat16)
            for _ in range(2)
        ]
        # the pool as the engine stores it: two 64-wide heads a row
        r = heads_per_row(hk, d)
        assert r == (2 if d == 64 else 1)
        pool_k, pool_v = (join_rows(pool, r) for pool in a_head_a_row)
        ids = rng.permutation(np.arange(1, n_blocks))[: self.B * nbl]
        tables = jnp.asarray(ids.reshape(self.B, nbl), jnp.int32)
        x = jnp.asarray(rng.standard_normal((self.B, t, cfg.dim)), jnp.bfloat16)
        write = jnp.asarray(write, jnp.int32)
        positions = write[:, None] + jnp.arange(t)[None, :]
        rows_k, rows_v = (c[self.LAYER] for c in gather_block_views(pool_k, pool_v, tables, r))
        assert rows_k.shape == (self.B, hk, self.S, d)
        for got, want in zip((rows_k, rows_v), gather_block_views(*a_head_a_row, tables)):
            np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want[self.LAYER], np.float32))
        params = layer.init(
            jax.random.PRNGKey(0), x, rows_k, rows_v, positions, write, write + t
        )

        y_slot, new_k, new_v = jax.jit(layer.apply)(
            params, x, rows_k, rows_v, positions, write, write + t
        )
        y_paged, new_pool_k, new_pool_v = jax.jit(
            partial(layer.apply, layer_index=self.LAYER)
        )(params, x, pool_k, pool_v, positions, write, write + t, block_tables=tables)

        np.testing.assert_array_equal(
            np.asarray(y_paged, np.float32), np.asarray(y_slot, np.float32)
        )
        paged_k, paged_v = (
            c[self.LAYER] for c in gather_block_views(new_pool_k, new_pool_v, tables, r)
        )
        # and out of the same K/V one head a row: the same to the bit
        y_unpacked, *unpacked = jax.jit(partial(layer.apply, layer_index=self.LAYER))(
            params, x, *a_head_a_row, positions, write, write + t, block_tables=tables
        )
        np.testing.assert_array_equal(np.asarray(y_paged, np.float32), np.asarray(y_unpacked, np.float32))
        for got, want in zip((new_pool_k, new_pool_v), unpacked):
            np.testing.assert_array_equal(
                np.asarray(got[:, 1:], np.float32), np.asarray(join_rows(want, r)[:, 1:], np.float32)
            )
        np.testing.assert_array_equal(np.asarray(paged_k, np.float32), np.asarray(new_k, np.float32))
        np.testing.assert_array_equal(np.asarray(paged_v, np.float32), np.asarray(new_v, np.float32))
        assert not np.array_equal(np.asarray(new_k, np.float32), np.asarray(rows_k, np.float32))


class TestSharedEngineMeshGeometry:
    """EngineKey includes the sharding geometry: engines built over
    different model-axis extents compile different programs and must not
    collide on one registry slot."""

    def test_two_geometries_two_engines_same_geometry_shared(self):
        from jax.sharding import Mesh

        from cosmos_curate_tpu.models.vlm import SharedCaptionEngine

        SharedCaptionEngine.reset()
        try:
            mesh2 = Mesh(np.array(jax.devices()[:2]), axis_names=("model",))
            kw = dict(model_id="tiny-geom", tokenizer=TOK, max_batch=2)
            unsharded = SharedCaptionEngine.get(VLM_TINY_TEST, **kw)
            sharded = SharedCaptionEngine.get(VLM_TINY_TEST, mesh=mesh2, **kw)
            assert sharded is not unsharded
            assert sharded.mesh_geometry == (("model", 2),)
            assert unsharded.mesh_geometry == ()
            assert SharedCaptionEngine.get(VLM_TINY_TEST, mesh=mesh2, **kw) is sharded
            assert SharedCaptionEngine.get(VLM_TINY_TEST, **kw) is unsharded
        finally:
            SharedCaptionEngine.reset()

    def test_head_parallel_engine_matches_unsharded_text(self):
        """Extent-2 model axis over the tiny config's 2 KV heads: the
        head-parallel paged path must caption identically to the unsharded
        engine (attention is embarrassingly parallel over head planes)."""
        from jax.sharding import Mesh

        base = CaptionEngine(VLM_TINY_TEST, max_batch=2)
        base.setup()
        sharded = CaptionEngine(
            VLM_TINY_TEST,
            max_batch=2,
            mesh=Mesh(np.array(jax.devices()[:2]), axis_names=("model",)),
        )
        sharded.setup()
        sharded.params = base.params
        reqs = lambda: [_req(f"m{i}", text=f"scene {i}", max_new=4) for i in range(2)]
        got_base = _drain(base, reqs())
        got_sharded = _drain(sharded, reqs())
        assert got_sharded == got_base


class TestCrossJobInterleave:
    def test_two_owners_active_in_same_step_window(self):
        """Two owners submitting concurrently must INTERLEAVE: decode steps
        exist whose active slots span both owners, each owner gets its own
        results, and per-owner token accounting adds up."""
        eng = CaptionEngine(VLM_TINY_TEST, max_batch=4, async_prep=True)
        eng.setup()
        try:
            results = {}

            def job(tag, n):
                for i in range(n):
                    eng.add_request(
                        _req(f"{tag}-{i}", text=f"{tag} {i}", max_new=12, frames=0,
                             owner=tag)
                    )
                results[tag] = eng.run_until_complete(owner=tag)

            threads = [
                threading.Thread(target=job, args=("jobA", 3)),
                threading.Thread(target=job, args=("jobB", 3)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert sorted(r.request_id for r in results["jobA"]) == [
                f"jobA-{i}" for i in range(3)
            ]
            assert sorted(r.request_id for r in results["jobB"]) == [
                f"jobB-{i}" for i in range(3)
            ]
            assert eng.interleaved_decode_steps > 0
            tokens = eng.owner_decode_tokens
            assert tokens.get("jobA", 0) > 0 and tokens.get("jobB", 0) > 0
            stats = eng.owner_stats()
            assert stats["jobA"]["requests"] == 3
            assert stats["jobB"]["requests"] == 3
        finally:
            eng.shutdown()

    def test_owner_cap_bounds_a_flooding_owner(self):
        """With two active owners the fair-share cap keeps one owner from
        occupying every slot: sync-mode admission of a 6-request flood plus
        one late rival leaves the flood at most ceil(slots/2) in flight."""
        eng = CaptionEngine(VLM_TINY_TEST, max_batch=4, kv_lanes=((128, 4),))
        eng.setup()
        for i in range(6):
            eng.add_request(_req(f"f{i}", text="x", max_new=24, frames=0, owner="flood"))
        eng.add_request(_req("late", text="y", max_new=4, frames=0, owner="late"))
        eng.step()
        inflight = {}
        for s in eng.slots.values():
            inflight[s.request.owner] = inflight.get(s.request.owner, 0) + 1
        for p in eng.pending.values():
            inflight[p.request.owner] = inflight.get(p.request.owner, 0) + 1
        assert inflight.get("flood", 0) <= 2, inflight  # ceil(4 / 2 owners)
        assert inflight.get("late", 0) >= 1, inflight
        got = {r.request_id for r in eng.run_until_complete(owner="flood")}
        assert got == {f"f{i}" for i in range(6)}
        assert {r.request_id for r in eng.run_until_complete(owner="late")} == {"late"}


class TestPagedUpdate:
    """``paged_update`` is the one write of a chunk's K/V into the pool
    (PR 25): the same values in the same cells as the expression it
    replaced, whose update window spanned ``[Hkv, Dh]`` and so cost a
    relayout of the whole pool before every paged kernel call."""

    L, NB, HK, BS, D, NBL = 2, 64, 2, 16, 8, 20

    @staticmethod
    def _old_write(pool, chunk, tables, write_index, layer_index):
        bs = pool.shape[3]
        pos = write_index[:, None] + jnp.arange(chunk.shape[1])[None, :]
        blk = jnp.take_along_axis(tables, pos // bs, axis=1)
        return pool.at[layer_index, blk, :, pos % bs].set(chunk.astype(pool.dtype))

    def _case(self, t, seed=0):
        """Four rows: two live rows that share their first (prefix) block
        and write behind it, from mid-block; two idle rows whose tables
        are all block 0 and whose writes collide there."""
        rng = np.random.default_rng(seed)
        shape = (self.L, self.NB, self.HK, self.BS, self.D)
        pool_k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        pool_v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        own = rng.permutation(np.arange(2, self.NB))[: 2 * (self.NBL - 1)].reshape(2, -1)
        tables = np.zeros((4, self.NBL), np.int32)
        tables[:2, 0] = 1  # the shared prefix block
        tables[:2, 1:] = own
        write_index = np.array([self.BS + 5, self.BS, 0, 0], np.int32)
        k = jnp.asarray(rng.standard_normal((4, t, self.HK, self.D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((4, t, self.HK, self.D)), jnp.float32)
        return pool_k, pool_v, k, v, jnp.asarray(tables), jnp.asarray(write_index)

    @pytest.mark.parametrize("t", [1, 256])
    @pytest.mark.parametrize("which", ["k", "v"])
    def test_same_cells_as_the_old_expression(self, t, which):
        from cosmos_curate_tpu.models.vlm.paged_kv import paged_update

        pool_k, pool_v, k, v, tables, write_index = self._case(t)
        new_k, new_v = paged_update(pool_k, pool_v, k, v, tables, write_index, layer_index=1)
        pool, chunk, new = (pool_k, k, new_k) if which == "k" else (pool_v, v, new_v)
        got = np.asarray(new.astype(jnp.float32))
        want = np.asarray(
            self._old_write(pool, chunk, tables, write_index, 1).astype(jnp.float32)
        )
        # block 0 is the garbage block: colliding writes, undefined winner
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        before = np.asarray(pool.astype(jnp.float32))
        np.testing.assert_array_equal(got[0], before[0])  # the other layer
        # and nothing but the 2 * t cells the two live rows own has changed
        pos = np.asarray(write_index)[:2, None] + np.arange(t)[None, :]
        blk = np.take_along_axis(np.asarray(tables)[:2], pos // self.BS, axis=1)
        written = np.zeros((self.NB, self.BS), bool)
        written[blk, pos % self.BS] = True
        assert written[2:].sum() == 2 * t and not written[:2].any()
        written[0] = True  # the idle rows' garbage
        np.testing.assert_array_equal(
            got[1].swapaxes(1, 2)[~written], before[1].swapaxes(1, 2)[~written]
        )

    @pytest.mark.parametrize("t", [1, 256])
    @pytest.mark.parametrize("widths", ["base", "granite-4.0-h-micro"])
    def test_two_heads_a_row_hold_the_same_cells(self, t, widths):
        """The write into a pool of two 64-wide heads a row (the chunk's
        ``[Hkv, Dh]`` read as ``[Hkv / 2, 128]``, nothing moved) leaves what
        the write into the same pool one head a row leaves, packed."""
        from cosmos_curate_tpu.models.vlm.paged_kv import paged_update
        from cosmos_curate_tpu.ops.paged_attention import heads_per_row, join_rows

        hk, _, d = WIDTHS[widths]
        r = heads_per_row(hk, d)
        rng = np.random.default_rng(t)
        pools = [
            jnp.asarray(rng.standard_normal((self.L, self.NB, hk, self.BS, d)), jnp.bfloat16)
            for _ in range(2)
        ]
        _, _, _, _, tables, write_index = self._case(t)
        k, v = (jnp.asarray(rng.standard_normal((4, t, hk, d)), jnp.float32) for _ in range(2))
        want = paged_update(*pools, k, v, tables, write_index, layer_index=1)
        got = jax.jit(partial(paged_update, layer_index=1))(
            *(join_rows(pool, r) for pool in pools), k, v, tables, write_index
        )
        for g, w in zip(got, want):
            assert g.shape == (self.L, self.NB, hk // 2, self.BS, 128)
            np.testing.assert_array_equal(
                np.asarray(g[:, 1:], np.float32), np.asarray(join_rows(w, r)[:, 1:], np.float32)
            )

    @pytest.mark.parametrize("t", [1, 256])
    @pytest.mark.parametrize("extent", [1, 2])
    def test_head_update_is_the_same_function_under_a_shard_map(self, t, extent):
        """Extent 1 is the docstring's promise (bit-equal to the unsharded
        write); extent 2 gives each shard one head plane of the two."""
        from jax.sharding import Mesh

        from cosmos_curate_tpu.models.vlm.paged_kv import paged_head_update, paged_update

        args = self._case(t, seed=extent)
        mesh = Mesh(np.array(jax.devices()[:extent]), axis_names=("model",))
        want = paged_update(*args, layer_index=1)
        got = jax.jit(partial(paged_head_update, mesh, layer_index=1))(*args)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(g[:, 1:].astype(jnp.float32)), np.asarray(w[:, 1:].astype(jnp.float32))
            )


class TestPoolViews:
    """What the ``gather`` programs and the shared prefix see of a pool that
    holds ``r`` KV heads a row: head planes, whatever ``r``."""

    @pytest.mark.parametrize("widths", sorted(WIDTHS))
    def test_views_round_trip_through_the_pool(self, widths):
        from cosmos_curate_tpu.models.vlm.model import VLMConfig
        from cosmos_curate_tpu.models.vlm.paged_kv import (
            gather_block_views, init_block_pool, scatter_block_views,
        )

        hk, g, d = WIDTHS[widths]
        cfg = VLMConfig(n_layers=2, n_heads=hk * g, n_kv_heads=hk, head_dim=d)
        pool_k, pool_v = init_block_pool(cfg, 12, 16)
        r = hk // pool_k.shape[2]
        assert pool_k.shape == (2, 12, hk // r, 16, r * d)
        assert pool_k.shape[-1] % 128 == 0 or d == 16
        rng = np.random.default_rng(zlib.crc32(widths.encode()))
        tables = jnp.asarray(rng.permutation(np.arange(1, 12))[:6].reshape(2, 3), jnp.int32)
        views = [jnp.asarray(rng.standard_normal((2, 2, hk, 48, d)), jnp.bfloat16) for _ in range(2)]
        pool_k, pool_v = scatter_block_views(pool_k, pool_v, tables, *views)
        assert pool_k.shape == (2, 12, hk // r, 16, r * d)
        for got, want in zip(gather_block_views(pool_k, pool_v, tables, r), views):
            np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
        # token 20 of slot 1, head 3 of 8: block 1 of its table, offset 4, row 3 // r, lanes of head 3 % r
        if hk == 8:
            cell = pool_k[1, tables[1, 1], 3 // r, 4, (3 % r) * d : (3 % r + 1) * d]
            np.testing.assert_array_equal(np.asarray(cell, np.float32), np.asarray(views[0][1, 1, 3, 20], np.float32))
        # untouched blocks stay zero
        free = sorted(set(range(12)) - set(np.asarray(tables).ravel().tolist()))
        assert not np.asarray(pool_k[:, jnp.asarray(free)], np.float32).any()

    @pytest.mark.parametrize("heads", sorted(HEAD_WIDTHS))
    def test_a_prefix_written_into_its_blocks_reads_back_equal(self, heads):
        """``write_prefix_blocks`` (the one device write of a prefix build)
        against what ``prefix_prefill`` returned, read back through the
        views the ``gather`` programs see and through the block ids."""
        from cosmos_curate_tpu.models.vlm.paged_kv import gather_block_views

        cfg, r = _with_heads(heads)
        eng = CaptionEngine(cfg, max_batch=2, kv_lanes=((64, 2),), prefill_chunk=16)
        eng.setup()
        tp, sp, bs = 41, 64, eng.block_size  # two full blocks and a tail of 9
        rng = np.random.default_rng(3)
        emb = jnp.asarray(rng.standard_normal((1, sp, cfg.dim)), jnp.float32)
        pos = jnp.arange(sp, dtype=jnp.int32)[None]
        k, v = eng._prefix_prefill(eng.params, emb, pos, jnp.asarray(tp, jnp.int32))
        k, v = k[:, :, :tp], v[:, :, :tp]
        assert k.shape == (cfg.n_layers, cfg.n_kv_heads, tp, cfg.head_dim)
        ids = [7, 3, 5]
        pool_k, pool_v = eng._write_prefix_blocks(
            eng._pool_k, eng._pool_v, k, v, jnp.asarray(ids, jnp.int32)
        )
        assert pool_k.shape[2:] == (cfg.n_kv_heads // r, bs, r * cfg.head_dim)
        views = gather_block_views(pool_k, pool_v, jnp.asarray([ids], jnp.int32), r)
        for got, want in zip(views, (k, v)):
            np.testing.assert_array_equal(
                np.asarray(got[:, 0, :, :tp], np.float32), np.asarray(want.astype(pool_k.dtype), np.float32)
            )
            assert not np.asarray(got[:, 0, :, tp:], np.float32).any()  # the tail block's padding
