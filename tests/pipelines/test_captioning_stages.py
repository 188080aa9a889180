"""Caption prep + caption stage integration (tiny VLM, synthetic media)."""

import time

import pytest

from cosmos_curate_tpu.core.runner import SequentialRunner
from cosmos_curate_tpu.core.pipeline import run_pipeline
from cosmos_curate_tpu.data.model import FrameExtractionSignature
from cosmos_curate_tpu.models.vlm import VLM_TINY_TEST
from cosmos_curate_tpu.pipelines.video.input_discovery import discover_split_tasks
from cosmos_curate_tpu.pipelines.video.stages.captioning import CaptionPrepStage, CaptionStage
from cosmos_curate_tpu.pipelines.video.stages.clip_extraction import (
    ClipTranscodingStage,
    FixedStrideExtractorStage,
)
from cosmos_curate_tpu.pipelines.video.stages.download import VideoDownloadStage
from cosmos_curate_tpu.pipelines.video.stages.frame_extraction import ClipFrameExtractionStage
from cosmos_curate_tpu.pipelines.video.stages.writer import ClipWriterStage
from tests.fixtures.media import make_scene_video


@pytest.fixture(scope="module")
def captioned_output(tmp_path_factory):
    d = tmp_path_factory.mktemp("cap")
    vids = d / "in"
    vids.mkdir()
    make_scene_video(vids / "v0.mp4", scene_len_frames=48, num_scenes=1)
    sig = FrameExtractionSignature("fps", 4.0)
    out = d / "out"
    stages = [
        VideoDownloadStage(),
        FixedStrideExtractorStage(clip_len_s=1.0, min_clip_len_s=0.5),
        ClipTranscodingStage(num_threads=2),
        ClipFrameExtractionStage(signatures=(sig,), resize_hw=(32, 32)),
        CaptionPrepStage(window_len=24, remainder_threshold=12, frames_per_window=2, extraction=sig),
        CaptionStage(cfg=VLM_TINY_TEST, max_batch=4, max_new_tokens=6),
        ClipWriterStage(str(out)),
    ]
    tasks = discover_split_tasks(str(vids))
    done = run_pipeline(tasks, stages, runner=SequentialRunner())
    return out, done


def test_windows_created_and_captioned(captioned_output):
    out, done = captioned_output
    clips = [c for t in done for c in t.video.clips]
    assert len(clips) == 2  # 2s video, 1s stride
    for clip in clips:
        assert clip.windows, "prep stage must create windows"
        for win in clip.windows:
            assert "default" in win.caption
            assert isinstance(win.caption["default"], str)


def test_caption_metadata_written(captioned_output):
    out, done = captioned_output
    import json

    metas = [json.loads(p.read_text()) for p in (out / "metas" / "v0").glob("*.json")]
    assert metas
    for m in metas:
        assert m["windows"], "windows must be serialized"
        assert all("default" in w["captions"] for w in m["windows"])


def test_tokens_per_second_recorded(captioned_output):
    _, done = captioned_output
    assert all(t.stage_perf.get("caption_tokens_per_s", 0) > 0 for t in done)


def test_phase_breakdown_recorded(captioned_output):
    """The caption stage stamps the engine phase/prefix stats per task and
    folds them into the stage_timer caption aggregates (the flight
    recorder's caption_phases section reads the same source)."""
    from cosmos_curate_tpu.observability.stage_timer import caption_phase_summaries

    _, done = captioned_output
    for t in done:
        assert "caption_prefix_cache_hits" in t.stage_perf
        assert "caption_engine_idle_s" in t.stage_perf
    agg = caption_phase_summaries().get("CaptionStage")
    assert agg is not None and agg["drives"] >= 1
    assert agg["decode_s"] > 0 and agg["wall_s"] > 0
    # every window after the first hits the shared instruction prefix
    assert agg["prefix_cache_hits"] >= 1


def test_the_phase_account_reaches_the_run_report(captioned_output):
    """The engine's counts and its device-queue clock ride the same deltas:
    steps, the programs handed to the device and the seconds inside step()
    with the queue provably empty, in the aggregate and on the report's lines."""
    from cosmos_curate_tpu.observability.flight_recorder import render_report
    from cosmos_curate_tpu.observability.stage_timer import caption_phase_summaries

    agg = caption_phase_summaries()["CaptionStage"]
    assert agg["step_n"] > 0 and agg["decode_dispatch_n"] > 0 and agg["prefill_dispatch_n"] > 0
    assert agg["decode_dispatch_n"] == agg["paged_kernel_steps"]  # a drained drive read every program
    assert agg["programs_per_step"] == round(
        (agg["decode_dispatch_n"] + agg["prefill_dispatch_n"]) / agg["step_n"], 3
    )
    # the dispatch phases' own part (the prep thread's prefix build books its own outside step())
    own = agg["decode_dispatch_exposed_s"] + agg["prefill_dispatch_exposed_s"]
    assert own > 0 and 0 < agg["step_exposed_s"] <= agg["wall_s"]
    text = render_report({"caption_phases": {"CaptionStage": agg}})
    assert f"programs/step {agg['programs_per_step']:.2f}" in text
    assert f"exposed {agg['step_exposed_s']:.2f}s (dispatch's own {own:.2f}s)" in text


def test_prompt_encoded_once_across_windows(monkeypatch):
    """Satellite: _make_request must not re-tokenize the identical prompt
    per window — the encode runs once per stage, then requests copy the
    cached ids."""
    from cosmos_curate_tpu.data.model import Window

    stage = CaptionStage(cfg=VLM_TINY_TEST, max_batch=2, max_new_tokens=4)
    calls = {"n": 0}
    real = stage._model.encode_prompt

    def counting(text, *, has_vision):
        calls["n"] += 1
        return real(text, has_vision=has_vision)

    monkeypatch.setattr(stage._model, "encode_prompt", counting)
    import numpy as np

    reqs = []
    for i in range(5):
        win = Window(start_frame=0, end_frame=8)
        win.frames = np.zeros((2, 32, 32, 3), np.uint8)
        reqs.append(stage._make_request(f"w{i}", win))
    assert calls["n"] == 1
    # requests must not alias the cached id lists
    assert reqs[0].prefix_ids == reqs[1].prefix_ids
    assert reqs[0].prefix_ids is not reqs[1].prefix_ids


def test_flavored_stage_runs_laned_with_high_utilization(
    tmp_path_factory, monkeypatch
):
    """VERDICT r3 #3: the PRODUCTION caption stage (not just the benchmark)
    must construct a laned engine from the flavor's defaults, and the
    utilization-aware admission must keep decode rows busy on a
    mixed-length workload."""
    from tests.models.test_vlm_engine import _write_gpt2_tokenizer_files

    d = tmp_path_factory.mktemp("lane")
    monkeypatch.setenv("CURATE_MODEL_WEIGHTS_DIR", str(d / "w"))
    _write_gpt2_tokenizer_files(d / "w" / "caption-vlm-tpu")
    from cosmos_curate_tpu.models.vlm import SharedCaptionEngine

    SharedCaptionEngine.reset()
    vids = d / "in"
    vids.mkdir()
    make_scene_video(vids / "v0.mp4", scene_len_frames=48, num_scenes=1)
    sig = FrameExtractionSignature("fps", 4.0)
    stages = [
        VideoDownloadStage(),
        FixedStrideExtractorStage(clip_len_s=1.0, min_clip_len_s=0.5),
        ClipTranscodingStage(num_threads=2),
        ClipFrameExtractionStage(signatures=(sig,), resize_hw=(32, 32)),
        CaptionPrepStage(
            window_len=24, remainder_threshold=12, frames_per_window=2, extraction=sig
        ),
        CaptionStage(model_flavor="qwen-chat-tiny-test", max_batch=4, max_new_tokens=6),
    ]
    tasks = discover_split_tasks(str(vids))
    done = run_pipeline(tasks, stages, runner=SequentialRunner())
    engine = stages[-1]._model.engine
    # the flavor's default lanes are live in the production stage
    assert [(l.length, l.n_slots) for l in engine.lanes] == [(192, 4), (256, 2)]
    # every window captioned through the chat template
    for t in done:
        for clip in t.video.clips:
            for win in clip.windows:
                assert "default" in win.caption
    # admission packs active lanes: the decode dead-work fraction stays
    # bounded. With prep/decode overlap the engine starts decoding window 1
    # while later windows are still vision-encoding (prep-bound on CPU), so
    # early steps run partially-filled batches — dead rows traded for wall
    # time. Lane-packing itself is asserted by TestUtilizationAwareRouting.
    assert engine.decode_slot_utilization >= 0.15, engine.decode_slot_utilization
    SharedCaptionEngine.reset()


def test_two_caption_owners_share_engine_and_interleave(tmp_path):
    """Cross-job continuous batching (acceptance): two concurrent
    CaptionStage owners share ONE SharedCaptionEngine, their requests
    interleave in the same decode-step window (both owners hold active
    slots simultaneously), results route back to the right owner, and the
    run report carries per-owner accounting."""
    import threading

    import numpy as np

    from cosmos_curate_tpu.data.model import Clip, SplitPipeTask, Video, VideoMetadata, Window
    from cosmos_curate_tpu.models.vlm import SharedCaptionEngine
    from cosmos_curate_tpu.observability import stage_timer
    from cosmos_curate_tpu.observability.flight_recorder import write_run_report

    SharedCaptionEngine.reset()
    stage_timer.reset_caption_phases()

    def make_tasks(tag: str, n: int):
        tasks = []
        for i in range(n):
            clip = Clip(span=(0.0, 1.0))
            win = Window(start_frame=0, end_frame=8)
            win.frames = np.random.default_rng(i + (1000 if tag == "a" else 2000)).integers(
                0, 255, (2, 32, 32, 3), np.uint8
            )
            clip.windows = [win]
            video = Video(
                path=f"{tag}-{i}.mp4",
                metadata=VideoMetadata(width=32, height=32, fps=8.0, num_frames=8, duration_s=1.0),
                clips=[clip],
            )
            tasks.append(SplitPipeTask(video=video))
        return tasks

    stage_a = CaptionStage(cfg=VLM_TINY_TEST, max_batch=4, max_new_tokens=8)
    stage_b = CaptionStage(cfg=VLM_TINY_TEST, max_batch=4, max_new_tokens=8)
    stage_a.model.setup()
    stage_b.model.setup()
    # ONE engine for both stages: the registry keys on (model, dtype, mesh)
    assert stage_a.model.engine is stage_b.model.engine
    assert stage_a.owner != stage_b.owner
    engine = stage_a.model.engine
    try:
        done = {}

        def drive(stage, tasks, key):
            done[key] = stage.process_data(tasks)

        threads = [
            threading.Thread(target=drive, args=(stage_a, make_tasks("a", 3), "a")),
            threading.Thread(target=drive, args=(stage_b, make_tasks("b", 3), "b")),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # every window captioned, no cross-owner stealing
        for key in ("a", "b"):
            for task in done[key]:
                for clip in task.video.clips:
                    assert clip.windows[0].caption.get("default"), (key, task.video.path)
        # THE interleave assertion: decode steps existed whose active slots
        # spanned both owners
        assert engine.interleaved_decode_steps > 0
        tokens = engine.owner_decode_tokens
        assert tokens.get(stage_a.owner, 0) > 0 and tokens.get(stage_b.owner, 0) > 0
        # per-owner accounting reaches run_report.json
        report = write_run_report(str(tmp_path))
        owners = report["caption_phases"]["CaptionStage"]["owners"]
        assert owners[stage_a.owner]["requests"] == 3
        assert owners[stage_b.owner]["requests"] == 3
        assert owners[stage_a.owner]["decode_tokens"] > 0
    finally:
        SharedCaptionEngine.reset()
        stage_timer.reset_caption_phases()


class _SpansInMemory:
    """A tracing backend that keeps what it is handed."""

    def __init__(self):
        self.spans = []

    def export(self, span):
        self.spans.append(span)

    def close(self):
        pass


def _exported_request_counters() -> dict:
    """`caption_requests_total{boundary}` and `caption_request_seconds_total{interval}` of
    the caption stage, as the process's Prometheus registry holds them now."""
    from prometheus_client import REGISTRY

    from cosmos_curate_tpu.engine.metrics import get_metrics

    assert get_metrics().enabled
    read = lambda name, **labels: REGISTRY.get_sample_value(name, {"stage": "CaptionStage", **labels}) or 0.0
    out = {b: read("caption_requests_total", boundary=b) for b in ("taken", "ready", "admitted", "first", "finished", "dropped")}
    out.update({i: read("caption_request_seconds_total", interval=i) for i in ("queue", "prep", "row_wait", "prefill", "decode")})
    return out


@pytest.mark.parametrize("enabled", [True, False], ids=["tracing_on", "tracing_off"])
def test_a_drive_emits_five_spans_a_result_under_one_request_id(enabled, tmp_path, monkeypatch):
    """A request's life inside the engine, as spans of the repo's one span API:
    five a result, children of the drive's `caption.engine`, sharing the
    request's id; with tracing off, none (and nothing else changes)."""
    import numpy as np

    from cosmos_curate_tpu.data.model import Clip, SplitPipeTask, Video, VideoMetadata, Window
    from cosmos_curate_tpu.models.vlm import SharedCaptionEngine
    from cosmos_curate_tpu.observability import stage_timer, tracing

    SharedCaptionEngine.reset()
    stage_timer.reset_caption_phases()
    tasks = []
    for i in range(3):
        win = Window(start_frame=0, end_frame=8)
        win.frames = np.random.default_rng(i).integers(0, 255, (2, 32, 32, 3), np.uint8)
        clip = Clip(span=(0.0, 1.0))
        clip.windows = [win]
        meta = VideoMetadata(width=32, height=32, fps=8.0, num_frames=8, duration_s=1.0)
        tasks.append(SplitPipeTask(video=Video(path=f"v{i}.mp4", metadata=meta, clips=[clip])))
    stage = CaptionStage(cfg=VLM_TINY_TEST, max_batch=4, max_new_tokens=5)
    stage.model.setup()
    memory = _SpansInMemory()
    try:
        if enabled:
            tracing.enable_tracing(str(tmp_path / "t.ndjson"))
            monkeypatch.setattr(tracing, "_backends", [memory])
        exported0 = _exported_request_counters()
        t0 = time.time()
        stage.process_data(tasks)
        t1 = time.time()
        ids = {f"{t.video.clips[0].uuid}-0" for t in tasks}
        life = [s for s in memory.spans if s.name.startswith("caption.request.")]
        if not enabled:
            assert not memory.spans
        else:
            (drive,) = [s for s in memory.spans if s.name == "caption.engine"]
            assert len(life) == 5 * len(tasks) and {s.attributes["request_id"] for s in life} == ids
            for rid in ids:
                mine = [s for s in life if s.attributes["request_id"] == rid]
                assert [s.name.rsplit(".", 1)[1] for s in mine] == ["queue", "prep", "row_wait", "prefill", "decode"]
                assert all((s.parent_id, s.trace_id) == (drive.span_id, drive.trace_id) for s in mine)
                # end to end on the wall clock, inside the drive, each starting where the last ended
                assert all(a.end_s == pytest.approx(b.start_s, abs=1e-6) for a, b in zip(mine, mine[1:]))
                assert t0 - 0.05 <= mine[0].start_s <= mine[-1].end_s <= t1 + 0.05
                assert all(s.start_s <= s.end_s for s in mine)
                for s in mine:
                    assert s.attributes["lane"] in [l.length for l in stage.model.engine.lanes]
                    assert s.attributes["output_tokens"] >= 1 and s.attributes["prompt_tokens"] > 0
                assert [s.attributes["step"] for s in mine[2:]] == sorted(s.attributes["step"] for s in mine[2:])
        # the operator's side reads the same account: the five means and the time to first token
        agg = stage_timer.caption_phase_summaries()["CaptionStage"]
        assert agg["request_finished_n"] == agg["request_taken_n"] == 3 and agg["request_dropped_n"] == 0
        first_four = ("request_queue_ms", "request_prep_ms", "request_row_wait_ms", "request_prefill_ms")
        assert 0 < agg["request_ttft_ms"] == pytest.approx(sum(agg[k] for k in first_four), abs=0.01)
        assert agg["request_itl_ms"] > 0 and agg["request_decode_gaps"] > 0
        # ...and the exporter's one counter pair: requests past a boundary, seconds in an interval
        exported = {k: v - exported0[k] for k, v in _exported_request_counters().items()}
        assert [exported[b] for b in ("taken", "ready", "admitted", "first", "finished", "dropped")] == [3, 3, 3, 3, 3, 0]
        for interval in ("queue", "prep", "row_wait", "prefill", "decode"):
            assert exported[interval] == pytest.approx(agg[f"request_{interval}_s"], abs=1e-3)
    finally:
        tracing.disable_tracing()
        SharedCaptionEngine.reset()
        stage_timer.reset_caption_phases()
