"""DevicePipeline: ordering under ragged shape groups, bucket reuse across
drains, donation fallback on CPU, compile-cache knob, and embedding-stage
equivalence with the old synchronous path. All on CPU with tiny shapes."""

import os
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cosmos_curate_tpu.models.batching import next_pow2, pad_batch, pad_to
from cosmos_curate_tpu.models.device_pipeline import (
    DEFAULT_MICRO_BATCH,
    DevicePipeline,
    donate_kwargs,
    donation_supported,
    micro_batch_cap,
    plan_micro_batches,
)


class TestPadBatch:
    def test_pads_to_pow2_with_last_row(self):
        x = np.arange(3 * 2, dtype=np.float32).reshape(3, 2)
        padded, n = pad_batch(x)
        assert n == 3 and padded.shape == (4, 2)
        np.testing.assert_array_equal(padded[3], x[-1])

    def test_pad_rows_are_materialized_copies(self):
        """The broadcast trick must not leak views into the output."""
        x = np.ones((3, 2), np.float32)
        padded, _ = pad_batch(x)
        padded[3] = 7.0
        np.testing.assert_array_equal(x, np.ones((3, 2), np.float32))

    def test_max_pad_to_below_n_returns_unpadded(self):
        """A batch already past the cap passes through untouched — the cap
        bounds pad waste, it never truncates work."""
        x = np.arange(10, dtype=np.float32).reshape(10, 1)
        padded, n = pad_batch(x, max_pad_to=8)
        assert n == 10 and padded.shape == (10, 1)
        np.testing.assert_array_equal(padded, x)

    def test_max_pad_to_equal_n(self):
        x = np.zeros((8, 1), np.float32)
        padded, n = pad_batch(x, max_pad_to=8)
        assert n == 8 and padded.shape == (8, 1)

    def test_max_pad_to_invalid(self):
        with pytest.raises(ValueError):
            pad_batch(np.zeros((2, 1)), max_pad_to=0)

    def test_empty(self):
        padded, n = pad_batch(np.zeros((0, 4)))
        assert n == 0 and padded.shape == (0, 4)

    def test_pad_to_rejects_shrink(self):
        with pytest.raises(ValueError):
            pad_to(np.zeros((4, 1)), 2)


class TestPlan:
    def test_single_bucket_matches_old_pad_batch_shape(self):
        """n <= cap must produce exactly the pow2 bucket the synchronous
        pad_batch path compiled, so warmed shapes carry over."""
        for n in (1, 3, 5, 8, 20, 32):
            plan = plan_micro_batches(n, 32)
            old_target = min(next_pow2(n), 32)
            if n <= 32:
                assert plan == [(0, n, old_target)]

    def test_splits_over_cap(self):
        assert plan_micro_batches(40, 32) == [(0, 32, 32), (32, 40, 8)]
        assert plan_micro_batches(96, 32) == [(0, 32, 32), (32, 64, 32), (64, 96, 32)]
        assert plan_micro_batches(33, 32) == [(0, 32, 32), (32, 33, 1)]

    def test_empty(self):
        assert plan_micro_batches(0, 32) == []

    def test_cap_rounded_down_to_pow2(self):
        """Non-pow2 caps round DOWN: the cap is a per-dispatch memory
        ceiling the planner must not exceed."""
        assert micro_batch_cap(24) == 16
        assert micro_batch_cap(48) == 32
        assert micro_batch_cap(32) == 32
        assert micro_batch_cap(1) == 1
        with pytest.raises(ValueError):
            micro_batch_cap(-1)
        with pytest.raises(ValueError):
            micro_batch_cap(0)

    def test_cap_env(self, monkeypatch):
        monkeypatch.setenv("CURATE_MICRO_BATCH", "16")
        assert micro_batch_cap() == 16
        monkeypatch.delenv("CURATE_MICRO_BATCH")
        assert micro_batch_cap() == DEFAULT_MICRO_BATCH


def _row_mean_fn():
    traces = []

    @jax.jit
    def f(params, x):
        traces.append(x.shape)
        return x.astype(jnp.float32).mean(axis=tuple(range(1, x.ndim))) + params

    return f, traces


class TestPipeline:
    def test_run_matches_sync_path(self):
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/run", f, micro_batch=4)
        x = np.arange(24, dtype=np.float32).reshape(6, 4)
        got = pipe.run(jnp.float32(1.0), x)
        want = np.asarray(f(jnp.float32(1.0), pad_to(x, 8)))[:6]
        np.testing.assert_allclose(got, want)

    def test_ordering_under_ragged_shape_groups(self):
        """Interleaved submissions of DIFFERENT shapes resolve strictly in
        submission order — the contract stage code depends on when it zips
        drained results back onto clips."""
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/ragged", f, micro_batch=8)
        batches = [
            np.full((2, 3), 1.0, np.float32),
            np.full((5, 7), 2.0, np.float32),
            np.full((1, 2), 3.0, np.float32),
            np.full((8, 3), 4.0, np.float32),
        ]
        for b in batches:
            pipe.submit(jnp.float32(0.0), b, n_valid=b.shape[0])
        outs = pipe.drain()
        assert [o.shape[0] for o in outs] == [2, 5, 1, 8]
        for out, b in zip(outs, batches):
            np.testing.assert_allclose(out, b[:, 0])

    def test_bucket_reuse_across_drains(self):
        """The same bucket shapes across drains hit the SAME compiled
        program — the trace-side-effect counter must not grow."""
        f, traces = _row_mean_fn()
        pipe = DevicePipeline("t/reuse", f, micro_batch=4)
        x = np.random.default_rng(0).standard_normal((6, 3)).astype(np.float32)
        pipe.run(jnp.float32(0.0), x)
        n_compiles = len(traces)
        assert n_compiles >= 1
        for _ in range(3):
            pipe.run(jnp.float32(0.0), x)
        assert len(traces) == n_compiles  # no recompiles: buckets reused

    def test_empty_batch(self):
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/empty", f, micro_batch=4)
        out = pipe.run(jnp.float32(0.0), np.zeros((0, 3), np.float32))
        assert out.shape == (0,)

    def test_run_rejects_mismatched_leading_dims(self):
        """A shorter second array would silently pad with repeated rows —
        wrong results; run() must refuse loudly (same class of hardening
        as shard_batch)."""
        @jax.jit
        def f(params, a, b):
            return a + b

        pipe = DevicePipeline("t/mismatch", f, micro_batch=4)
        with pytest.raises(ValueError, match="leading dim"):
            pipe.run(None, np.zeros((4, 2), np.float32), np.zeros((2, 2), np.float32))

    def test_run_refuses_inflight_submissions(self):
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/guard", f, micro_batch=4)
        pipe.submit(jnp.float32(0.0), np.zeros((2, 3), np.float32), n_valid=2)
        with pytest.raises(RuntimeError, match="drain"):
            pipe.run(jnp.float32(0.0), np.zeros((2, 3), np.float32))
        pipe.drain()

    def test_scalar_results_and_postprocess(self):
        @jax.jit
        def stats(x, n):
            return x.sum() / n, x.max()

        pipe = DevicePipeline("t/scalar", stats)
        pipe.submit(np.array([1.0, 2.0, 3.0], np.float32), 3)
        pipe.submit(np.array([5.0, 5.0], np.float32), 2, postprocess=lambda r: r[1])
        first, second = pipe.drain()
        assert float(first[0]) == pytest.approx(2.0)
        assert float(second) == pytest.approx(5.0)

    def test_in_flight_backpressure_bounded(self):
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/depth", f, micro_batch=4, in_flight=2)
        for _ in range(6):
            pipe.submit(jnp.float32(0.0), np.zeros((4, 3), np.float32), n_valid=4)
            assert len(pipe._pending) <= 2
        assert len(pipe.drain()) == 6

    def test_dispatch_timings_recorded(self):
        from cosmos_curate_tpu.observability.stage_timer import (
            dispatch_summaries,
            reset_dispatch_stats,
        )

        reset_dispatch_stats()
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/timing", f, micro_batch=4)
        pipe.run(jnp.float32(0.0), np.zeros((10, 3), np.float32))
        stats = dispatch_summaries()["t/timing"]
        assert stats["dispatches"] == 3  # 4 + 4 + 2
        assert stats["rows"] == 10
        assert stats["padded_rows"] == 10  # 4 + 4 + 2(pow2)
        assert 0.0 <= stats["gap_frac"] <= 1.0
        reset_dispatch_stats()

    def test_failed_postprocess_aborts_whole_burst(self):
        """A failure mid-drain must clear ALL pipeline state: the next
        drain pairing leftover results with new submissions would be
        silent corruption."""
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/abort", f, micro_batch=4)
        pipe.submit(jnp.float32(0.0), np.ones((2, 3), np.float32), n_valid=2)
        pipe.submit(
            jnp.float32(0.0), np.ones((2, 3), np.float32), n_valid=2,
            postprocess=lambda r: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        pipe.submit(jnp.float32(0.0), np.ones((2, 3), np.float32), n_valid=2)
        with pytest.raises(RuntimeError, match="boom"):
            pipe.drain()
        assert pipe.pending == 0  # fully aborted, nothing stale
        # pipeline is reusable after the abort
        pipe.submit(jnp.float32(0.0), np.full((2, 3), 5.0, np.float32), n_valid=2)
        (out,) = pipe.drain()
        np.testing.assert_allclose(out, [5.0, 5.0])

    def test_failed_submit_aborts_in_flight(self):
        """A dispatch failure mid-submit clears earlier in-flight work too:
        a caller that catches per-item and keeps going (transnet over
        videos, SR over clips) must never drain stale results."""

        def f(params, x):
            if x.shape[0] == 3:
                raise RuntimeError("dispatch boom")
            return x * 2

        pipe = DevicePipeline("t/submit-abort", f, micro_batch=4)
        pipe.submit(None, np.ones((2, 3), np.float32), n_valid=2)
        assert pipe.pending == 1
        with pytest.raises(RuntimeError, match="dispatch boom"):
            pipe.submit(None, np.ones((3, 3), np.float32), n_valid=3)
        assert pipe.pending == 0  # earlier submission dropped with it
        assert pipe.drain() == []

    def test_abort_clears_state(self):
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/abort2", f, micro_batch=4)
        pipe.submit(jnp.float32(0.0), np.ones((2, 3), np.float32), n_valid=2)
        assert pipe.pending == 1
        pipe.abort()
        assert pipe.pending == 0
        assert pipe.drain() == []

    def test_micro_batch_zero_rejected(self):
        f, _ = _row_mean_fn()
        with pytest.raises(ValueError):
            DevicePipeline("t/zero", f, micro_batch=0)

    def test_backpressure_releases_device_results(self):
        """Settled results must be read back (device buffers released), not
        parked on device until drain — the HBM bound for long SR bursts."""
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/release", f, micro_batch=4, in_flight=1)
        for i in range(4):
            pipe.submit(jnp.float32(0.0), np.full((2, 3), float(i), np.float32), n_valid=2)
        # with depth=1, at least 3 submissions have settled: their device
        # refs are dropped and host copies held instead
        assert all(s.result is None and s.host is not None for s in pipe._settled)
        outs = pipe.drain()
        for i, out in enumerate(outs):
            np.testing.assert_allclose(out, [float(i), float(i)])


class TestSubmissionTracker:
    def test_pairs_items_with_results_in_order(self):
        f, _ = _row_mean_fn()
        tracker = DevicePipeline("t/trk", f, micro_batch=8).track()
        items = ["a", "b", "c"]
        for i, item in enumerate(items):
            tracker.submit(item, jnp.float32(0.0), np.full((2, 3), float(i), np.float32), n_valid=2)
        assert len(tracker) == 3
        pairs = tracker.drain()
        assert [it for it, _ in pairs] == items
        for i, (_, out) in enumerate(pairs):
            np.testing.assert_allclose(out, [float(i), float(i)])
        assert len(tracker) == 0

    def test_lost_to_abort_hands_back_items(self):
        def f(params, x):
            if x.shape[0] == 3:
                raise RuntimeError("boom")
            return x

        tracker = DevicePipeline("t/trk2", f, micro_batch=8).track()
        tracker.submit("a", None, np.ones((2, 3), np.float32), n_valid=2)
        with pytest.raises(RuntimeError):
            tracker.submit("b", None, np.ones((3, 3), np.float32), n_valid=3)
        assert tracker.lost_to_abort() == ["a"]
        assert tracker.lost_to_abort() == []  # claimed once

    def test_drain_failure_keeps_items_for_claim(self):
        f, _ = _row_mean_fn()
        tracker = DevicePipeline("t/trk3", f, micro_batch=8).track()
        tracker.submit(
            "a", jnp.float32(0.0), np.ones((2, 3), np.float32), n_valid=2,
            postprocess=lambda r: (_ for _ in ()).throw(RuntimeError("pp")),
        )
        with pytest.raises(RuntimeError, match="pp"):
            tracker.drain()
        assert tracker.lost_to_abort() == ["a"]

    def test_dump_and_merge_summaries(self, tmp_path, monkeypatch):
        """Worker-exit dump + parent-side merge (how engine-mode bench
        collects per-dispatch stats from spawned workers)."""
        from cosmos_curate_tpu.observability import stage_timer as st

        st.reset_dispatch_stats()
        f, _ = _row_mean_fn()
        pipe = DevicePipeline("t/dump", f, micro_batch=4)
        pipe.run(jnp.float32(0.0), np.zeros((6, 3), np.float32))
        st._dump_summaries(str(tmp_path))  # what the atexit hook runs
        st.reset_dispatch_stats()
        merged = st.load_dumped_summaries(str(tmp_path))
        assert merged["t/dump"]["dispatches"] == 2  # 4 + 2
        assert merged["t/dump"]["rows"] == 6
        assert 0.0 <= merged["t/dump"]["gap_frac"] <= 1.0


class TestDonation:
    def test_fallback_on_cpu(self):
        """JAX_PLATFORMS=cpu in the test env: donation must degrade to a
        no-op (no donate_argnums), and the pipeline still runs."""
        assert jax.default_backend() == "cpu"
        assert not donation_supported()
        assert donate_kwargs(1) == {}
        f = jax.jit(lambda p, x: x * 2, **donate_kwargs(1))
        pipe = DevicePipeline("t/donate", f, micro_batch=4)
        x = np.ones((3, 2), np.float32)
        np.testing.assert_allclose(pipe.run(None, x), x * 2)


class TestCompileCacheKnob:
    """One cache, placeable from outside: JAX_COMPILATION_CACHE_DIR where
    it is set (and then nothing is set in code), else a fixed directory in
    the checkout."""

    @pytest.fixture
    def jc(self, monkeypatch):
        from cosmos_curate_tpu.utils import jax_cache

        monkeypatch.setattr(jax_cache, "_ENABLED", False)
        monkeypatch.delenv(jax_cache.COMPILE_CACHE_ENV, raising=False)
        return jax_cache

    @pytest.fixture
    def updates(self, monkeypatch):
        """What the code under test sets through jax.config.update."""
        seen = []
        monkeypatch.setattr(jax.config, "update", lambda k, v: seen.append((k, v)))
        return seen

    def test_env_dir_honoured_and_nothing_set_in_code(self, jc, updates, monkeypatch, tmp_path):
        monkeypatch.setenv(jc.JAX_CACHE_DIR_ENV, str(tmp_path / "cc"))
        assert jc.enable_persistent_cache() == str(tmp_path / "cc")
        assert "jax_compilation_cache_dir" not in dict(updates)

    def test_unset_uses_fixed_checkout_path(self, jc, updates, monkeypatch):
        monkeypatch.delenv(jc.JAX_CACHE_DIR_ENV, raising=False)
        repo = Path(__file__).resolve().parents[2]
        assert jc.enable_persistent_cache() == str(repo / ".jax_cache")
        assert dict(updates)["jax_compilation_cache_dir"] == str(repo / ".jax_cache")

    def test_same_path_from_two_processes(self, jc, monkeypatch):
        """No pid, no fingerprint, no temp dir: a second process (another
        cwd) resolves the very same directory, or its entries never hit."""
        import subprocess
        import sys

        monkeypatch.delenv(jc.JAX_CACHE_DIR_ENV, raising=False)
        repo = Path(__file__).resolve().parents[2]
        code = "from cosmos_curate_tpu.utils import jax_cache; print(jax_cache.cache_dir())"
        env = {k: v for k, v in os.environ.items() if k != jc.JAX_CACHE_DIR_ENV}
        env["PYTHONPATH"] = str(repo)
        out = subprocess.run(
            [sys.executable, "-c", code], cwd="/", env=env, capture_output=True,
            text=True, check=True,
        ).stdout.strip()
        assert out == jc.cache_dir() == str(repo / ".jax_cache")

    def test_knob_off(self, jc, updates, monkeypatch, tmp_path):
        monkeypatch.setenv(jc.JAX_CACHE_DIR_ENV, str(tmp_path / "cc"))
        monkeypatch.setenv(jc.COMPILE_CACHE_ENV, "0")
        assert jc.cache_dir() is None
        assert jc.enable_persistent_cache() is None
        assert updates == [("jax_enable_compilation_cache", False)]


class TestEmbeddingStageEquivalence:
    def test_identical_outputs_to_old_sync_path(self):
        """encode_clips through the pipeline must produce the SAME
        embeddings as the old pad_batch + jit + np.asarray path (single
        bucket: bit-identical; multi-bucket: per-sample compute, allclose)."""
        from cosmos_curate_tpu.models.batching import pad_batch as _pad
        from cosmos_curate_tpu.models.embedder import (
            VIDEO_EMBED_TINY_TEST,
            VideoEmbedder,
        )

        m = VideoEmbedder(VIDEO_EMBED_TINY_TEST)
        m.setup()
        clips = np.random.default_rng(7).integers(
            0, 255, (5, 4, 32, 32, 3), np.uint8
        )
        got = m.encode_clips(clips)
        padded, n = _pad(clips)
        want = np.asarray(m._apply(m._params, padded))[:n]
        np.testing.assert_array_equal(got, want)

    def test_multi_bucket_matches_sync(self):
        from cosmos_curate_tpu.models.embedder import (
            VIDEO_EMBED_TINY_TEST,
            VideoEmbedder,
        )
        from cosmos_curate_tpu.models.device_pipeline import DevicePipeline

        m = VideoEmbedder(VIDEO_EMBED_TINY_TEST)
        m.setup()
        m._pipeline = DevicePipeline("embed/test-multi", m._apply, micro_batch=4)
        clips = np.random.default_rng(8).integers(
            0, 255, (6, 4, 32, 32, 3), np.uint8
        )
        got = m.encode_clips(clips)  # buckets: 4 + 2
        want = np.asarray(m._apply(m._params, pad_to(clips, 8)))[:6]
        np.testing.assert_allclose(got, want, atol=1e-5)
