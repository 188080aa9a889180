"""Shared-prefix KV cache + prep/decode overlap tests (tiny config, CPU).

The caption workload's defining property: every request of a (flavor,
prompt_variant) opens with the SAME text prefix. The engine prefills it once
and device-copies the K/V block into each slot at admission — greedy output
must be byte-identical to full prefill (the cache is a pure FLOP saver, not
an approximation), across lane buckets, chunked prefill, and prompt
variants; and the async prep path must overlap vision encoding with decode
without changing outputs.

Engine setups dominate this file's cost (each compiles its program family),
so tests share module-scoped engines and reset counters instead of
rebuilding; greedy decode rows are independent, so per-request outputs are
comparable across engines regardless of batch-mates.
"""

import threading
import time

import numpy as np
import pytest

from cosmos_curate_tpu.models.tokenizer import ByteTokenizer
from cosmos_curate_tpu.models.vlm import (
    CaptionEngine,
    CaptionRequest,
    SamplingConfig,
    VLM_TINY_TEST,
)

TOK = ByteTokenizer()
PREFIX = "system: you are a terse captioner. user:"


def _req(rid, text="describe", prefix=PREFIX, frames=2, max_new=6, **kw):
    return CaptionRequest(
        request_id=rid,
        prefix_ids=TOK.encode(prefix) if prefix else [],
        prompt_ids=TOK.encode(text),
        frames=(
            np.random.default_rng(hash(rid) % 2**31).integers(
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


# The CACHED engine is deliberately the gnarly geometry — short/long KV
# lanes + small prefill chunks — so every parity test also exercises lane
# routing and base-offset chunk placement; the FULL engine is the plain
# single-lane unchunked reference. Greedy rows are independent, so
# per-request outputs must match across the two geometries exactly.
@pytest.fixture(scope="module")
def cached():
    eng = CaptionEngine(
        VLM_TINY_TEST, max_batch=4, kv_lanes=((64, 2), (128, 2)), prefill_chunk=16
    )
    eng.setup()
    return eng


@pytest.fixture(scope="module")
def full():
    eng = CaptionEngine(VLM_TINY_TEST, max_batch=4, enable_prefix_cache=False)
    eng.setup()
    return eng


@pytest.fixture(scope="module")
def async_eng():
    eng = CaptionEngine(
        VLM_TINY_TEST, max_batch=4, async_prep=True, admission_linger_s=0.3
    )
    eng.setup()
    yield eng
    eng.shutdown()


class TestGreedyParity:
    def test_cached_matches_full_prefill(self, cached, full):
        """Byte-identical greedy captions with and without the cache."""
        reqs = lambda: [_req(f"r{i}", text=f"clip number {i}") for i in range(4)]
        assert _drain(cached, reqs()) == _drain(full, reqs())

    def test_parity_across_lane_buckets(self, cached, full):
        """Prefix insertion lands correctly in every lane geometry: a short
        request (short lane) and a long one (long lane) against the
        single-lane reference."""
        reqs = lambda: [
            _req("short", text="hi", max_new=4),
            _req("long", text="w " * 25, max_new=6),
        ]
        assert _drain(cached, reqs()) == _drain(full, reqs())

    def test_parity_across_chunked_prefill(self, cached, full):
        """A prefix-cached CHUNKED suffix (chunks write at base + progress,
        final chunk shifts back) matches unchunked full prefill. An active
        decode forces the chunk path."""
        cached.add_request(_req("warm", text="zz", max_new=24, frames=0))
        cached.step()  # decode active -> the next admit must chunk
        cached.add_request(_req("x", text="c " * 20, max_new=8))
        cached.step()
        assert cached.pending, "long suffix should chunk while decoding"
        chunked = {r.request_id: r.text for r in cached.run_until_complete()}
        want = _drain(full, [_req("x", text="c " * 20, max_new=8)])
        assert chunked["x"] == want["x"]

    @pytest.mark.slow
    def test_parity_mrope_variant(self):
        """Under m-rope (qwen2 vision) the prefix rope components are all
        equal — cached and full prefill must still agree exactly."""
        from cosmos_curate_tpu.models.vlm.model import VLM_QWEN2VL_TINY_TEST

        def run(cache):
            eng = CaptionEngine(
                VLM_QWEN2VL_TINY_TEST, max_batch=2, enable_prefix_cache=cache
            )
            eng.setup()
            return _drain(eng, [_req(f"q{i}", text=f"scene {i}") for i in range(3)])

        assert run(True) == run(False)


class TestPrefillAccounting:
    def test_prefill_tokens_reduced_by_prefix_len(self, cached, full):
        """n requests sharing a Tp-token prefix prefill exactly
        Tp x (n - 1) fewer tokens than the uncached engine."""
        pre = "system: count every prefill token. user:"  # fresh prefix
        tp = len(TOK.encode(pre))
        n = 3
        reqs = lambda: [_req(f"a{i}", prefix=pre, text="go") for i in range(n)]
        cached.reset_stats()
        _drain(cached, reqs())
        full.reset_stats()
        _drain(full, reqs())
        assert cached.prefill_tokens == full.prefill_tokens - tp * (n - 1)
        assert cached.prefix_cache_hits == n - 1
        assert cached.prefix_cache_misses == 1
        assert cached.prefix_tokens_saved == tp * (n - 1)

    def test_short_prefix_not_cached(self, cached):
        cached.reset_stats()
        _drain(cached, [_req("s0", prefix="ab", text="c0")])  # 3 ids < min 4
        assert cached.prefix_cache_hits == 0 and cached.prefix_cache_misses == 0

    def test_share_prefix_false_opts_out(self, cached):
        cached.reset_stats()
        _drain(
            cached,
            [_req(f"o{i}", text=f"c{i}", share_prefix=False) for i in range(2)],
        )
        assert cached.prefix_cache_hits == 0 and cached.prefix_cache_misses == 0


class TestEvictionAndVariants:
    def test_two_variants_no_cross_contamination(self, cached, full):
        """Two prompt_variants through one engine: each prefix keys its own
        entry, outputs match the uncached engine exactly."""
        pa, pb = "system: variant A. user:", "system: variant B, one word. user:"
        reqs = lambda: [
            _req(f"a{i}", prefix=pa, text=f"v{i}") for i in range(2)
        ] + [_req(f"b{i}", prefix=pb, text=f"v{i}") for i in range(2)]
        cached.reset_stats()
        got = _drain(cached, reqs())
        assert got == _drain(full, reqs())
        assert cached.prefix_cache_misses == 2  # one build per variant

    def test_eviction_under_capacity_one(self, cached, full):
        """A capacity-1 LRU with alternating variants evicts and rebuilds —
        correctness must survive the thrash."""
        pa, pb = "system: evict me first. user:", "system: evict me second. user:"
        seq = lambda: [
            _req("e-a0", prefix=pa, text="x"),
            _req("e-b0", prefix=pb, text="x"),
            _req("e-a1", prefix=pa, text="y"),
            _req("e-b1", prefix=pb, text="y"),
        ]
        cached.reset_stats()
        size0 = cached.prefix_cache_size
        cached.prefix_cache_size = 1
        # the public clear: raw dict.clear() would leak the entries' block
        # references in the paged pool's allocator
        cached.clear_prefix_cache()
        try:
            got = {}
            for r in seq():  # serialized so the LRU actually alternates
                got.update(_drain(cached, [r]))
        finally:
            cached.prefix_cache_size = size0
        want = {}
        for r in seq():
            want.update(_drain(full, [r]))
        assert got == want
        assert cached.stats()["prefix_cache_evictions"] >= 2
        assert cached.prefix_cache_misses >= 3  # rebuilds after eviction


class TestPrepDecodeOverlap:
    def test_async_prep_parity_and_linger_packing(self, cached, async_eng):
        """Async prep produces identical outputs, and an idle-engine burst
        admits as a PACKED batch (the linger window) instead of
        head-request-solo."""
        reqs = lambda: [_req(f"r{i}", text=f"clip {i}") for i in range(4)]
        sync = _drain(cached, reqs())
        async_eng.reset_stats()
        assert _drain(async_eng, reqs()) == sync
        # all 4 decoded together: dead-work fraction near 1
        assert async_eng.decode_slot_utilization > 0.9, (
            async_eng.decode_slot_utilization
        )

    def test_decode_progresses_while_next_prep_inflight(self, async_eng):
        """THE overlap property: while request B's vision encode runs in
        the background prep thread, request A must keep decoding."""
        eng = async_eng
        slow_frames_n = 3
        # warm B's encode shape outside the overlap window (A's shapes are
        # warm from the parity test) — the window below must measure
        # scheduling, not XLA compiles
        _drain(eng, [_req("wb", text="warm", frames=slow_frames_n, max_new=2)])
        eng.reset_stats()
        inner = eng._encode_images
        seen_during_slow_prep = []

        def instrumented(params, frames_u8):
            if frames_u8.shape[1] == slow_frames_n:
                # B's encode: sleep past the linger window, then snapshot
                # how far decode got while we were "encoding"
                time.sleep(0.5)
                seen_during_slow_prep.append(eng.stats()["decode_tokens"])
            return inner(params, frames_u8)

        eng._encode_images = instrumented
        try:
            eng.add_request(_req("A", text="first", frames=2, max_new=48))
            eng.add_request(_req("B", text="second", frames=slow_frames_n, max_new=6))
            results = {r.request_id for r in eng.run_until_complete()}
        finally:
            eng._encode_images = inner
        assert results == {"A", "B"}
        assert seen_during_slow_prep, "B's slow encode never ran"
        assert seen_during_slow_prep[0] > 0, (
            "engine idled during B's prep instead of decoding A"
        )

    @pytest.mark.slow
    def test_two_owners_share_async_engine(self, async_eng):
        eng = async_eng
        results = {}

        def stage(name, n):
            for i in range(n):
                eng.add_request(_req(f"{name}-{i}", text=f"{name} {i}", max_new=4))
            results[name] = eng.run_until_complete()

        threads = [
            threading.Thread(target=stage, args=("sa", 4)),
            threading.Thread(target=stage, args=("sb", 3)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(r.request_id for r in results["sa"]) == [
            f"sa-{i}" for i in range(4)
        ]
        assert sorted(r.request_id for r in results["sb"]) == [
            f"sb-{i}" for i in range(3)
        ]
        assert not eng.completed and not eng.slots and not eng.waiting


class TestAsyncLifecycle:
    @pytest.mark.slow
    def test_pre_setup_queue_and_shutdown_reuse(self):
        """Two lifecycle regressions: (a) requests queued BEFORE setup() on
        an async engine must be served once setup starts the prep thread,
        not silently dropped; (b) an engine reused after shutdown() must
        spawn a fresh prep thread (a timed-out shutdown leaves the stop
        flag latched — the replacement thread must not read it and die)."""
        eng = CaptionEngine(VLM_TINY_TEST, max_batch=2, async_prep=True)
        eng.add_request(_req("early", frames=0, max_new=4))
        eng.setup()
        assert [r.request_id for r in eng.run_until_complete()] == ["early"]
        eng.shutdown()
        eng.add_request(_req("later", frames=0, max_new=4))
        try:
            assert [r.request_id for r in eng.run_until_complete()] == ["later"]
        finally:
            eng.shutdown()


class TestVisionReuse:
    def test_refine_reuses_vision_features(self, cached, full):
        """The stage-2 refinement request carrying the SAME frames array
        must not re-run the vision tower, and must produce the same text
        as a follow-up that re-encodes from scratch."""

        def run(eng, reuse: bool):
            eng.reset_stats()
            frames = np.random.default_rng(7).integers(0, 255, (2, 32, 32, 3), np.uint8)
            follow_texts = []

            def on_complete(text, _depth=[0]):
                if _depth[0]:
                    follow_texts.append(text)
                    return None
                _depth[0] += 1
                return CaptionRequest(
                    request_id="w0",
                    prefix_ids=TOK.encode(PREFIX),
                    prompt_ids=TOK.encode("refine: " + text),
                    # same array object -> engine reuses features; a copy
                    # breaks identity -> fresh encode
                    frames=frames if reuse else frames.copy(),
                    sampling=SamplingConfig(max_new_tokens=6),
                    on_complete=on_complete,
                    share_prefix=False,
                )

            eng.add_request(
                CaptionRequest(
                    request_id="w0",
                    prefix_ids=TOK.encode(PREFIX),
                    prompt_ids=TOK.encode("caption this"),
                    frames=frames,
                    sampling=SamplingConfig(max_new_tokens=6),
                    on_complete=on_complete,
                )
            )
            eng.run_until_complete()
            return follow_texts[0], eng.vision_encodes, eng.vision_reuses

        text_reused, encodes_r, reuses_r = run(cached, reuse=True)
        text_fresh, encodes_f, reuses_f = run(full, reuse=False)
        assert text_reused == text_fresh
        assert (encodes_r, reuses_r) == (1, 1)
        assert (encodes_f, reuses_f) == (2, 0)
