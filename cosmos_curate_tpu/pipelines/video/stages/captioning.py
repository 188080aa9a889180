"""Captioning stages: CPU prep + TPU engine stage.

Equivalent capability of the reference's captioning path
(cosmos_curate/pipelines/video/captioning/vllm_caption_stage.py:244/413 —
``VllmPrepStage`` windows + model inputs on CPU, ``VllmCaptionStage`` runs
the engine with in-flight batching and two-stage refinement). Same deliberate
CPU/device split here: the prep stage computes caption windows
(windowing_utils ``compute_windows`` semantics) and samples window frames;
the caption stage owns one ``CaptionEngine`` (the chip owner's in-process
pool) and streams every window of every clip through continuous batching.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from cosmos_curate_tpu.core.model import ModelInterface
from cosmos_curate_tpu.core.stage import Resources, Stage
from cosmos_curate_tpu.data.model import FrameExtractionSignature, SplitPipeTask, Window
from cosmos_curate_tpu.models import registry
from cosmos_curate_tpu.models.prompts import REFINEMENT_PROMPT, get_caption_prompt
from cosmos_curate_tpu.models.tokenizer import default_caption_tokenizer
from cosmos_curate_tpu.models.vlm import (
    CaptionEngine,
    CaptionRequest,
    SamplingConfig,
    VLM_BASE,
    VLMConfig,
)
from cosmos_curate_tpu.utils.logging import get_logger
from cosmos_curate_tpu.video.windowing import compute_windows

logger = get_logger(__name__)


class CaptionPrepStage(Stage[SplitPipeTask, SplitPipeTask]):
    """CPU prep: cut clips into caption windows and attach window frames."""

    def __init__(
        self,
        *,
        window_len: int = 256,
        remainder_threshold: int = 128,
        frames_per_window: int = 8,
        extraction: FrameExtractionSignature = FrameExtractionSignature("fps", 2.0),
    ) -> None:
        self.window_len = window_len
        self.remainder_threshold = remainder_threshold
        self.frames_per_window = frames_per_window
        self.extraction = extraction

    @property
    def resources(self) -> Resources:
        return Resources(cpus=3.0)

    def process_data(self, tasks: list[SplitPipeTask]) -> list[SplitPipeTask]:
        key = self.extraction.key()
        for task in tasks:
            for clip in task.video.clips:
                frames = clip.extracted_frames.get(key)
                if frames is None or frames.shape[0] == 0:
                    continue
                # windows are defined over source frames; map to extracted
                # frame indices proportionally
                src_frames = max(
                    1, int(clip.duration_s * task.video.metadata.fps)
                )
                spans = compute_windows(
                    src_frames,
                    window_len=self.window_len,
                    remainder_threshold=self.remainder_threshold,
                )
                n_ext = frames.shape[0]
                clip.windows = []
                for a, b in spans:
                    ea = int(a / src_frames * n_ext)
                    eb = max(ea + 1, int(b / src_frames * n_ext))
                    idx = np.linspace(ea, min(eb, n_ext) - 1, self.frames_per_window)
                    win = Window(start_frame=a, end_frame=b)
                    win.frames = frames[idx.round().astype(int)]
                    # effective sampling rate of the window's frames in
                    # source time (Qwen2.5 temporal m-rope scaling)
                    span_s = (b - a) / max(task.video.metadata.fps, 1e-6)
                    win.frame_fps = self.frames_per_window / max(span_s, 1e-6)
                    clip.windows.append(win)
        return tasks


# Engines are process-level and keyed by (model, dtype, mesh) — see
# models/vlm/shared_engine.py: every caption-family stage (captioning,
# enhancement, semantic filter, per-event) AND every concurrent pipeline in
# the process submits into ONE engine per served model, whose admission
# interleaves their requests (cross-job continuous batching). Each stage
# instance is one engine OWNER: requests carry the stage's unique owner
# tag, so completions route back to the right drive and per-owner fairness
# + accounting have a stable identity.
_OWNER_SEQ = itertools.count()


# The five intervals of a request's life inside the engine (CaptionEngine._stamp),
# each a span `caption.request.<name>` between two stamps of CaptionResult.timing
_REQUEST_SPANS = (
    ("queue", "arrived", "taken"),
    ("prep", "taken", "ready"),
    ("row_wait", "ready", "admitted"),
    ("prefill", "admitted", "first_token"),
    ("decode", "first_token", "finished"),
)


def _emit_request_spans(results: list, stage: str) -> None:
    """Five spans a result, children of the span that is open (the drive's
    `caption.engine`), one `request_id` each: the engine stamped the boundaries
    on the monotonic clock, the span API keeps wall time, so one offset read
    here moves them over. The `*_step` attributes are the ordinals of the
    `engine.step` spans of a device profile (docs/OBSERVABILITY.md)."""
    from cosmos_curate_tpu.observability.tracing import end_span, start_span

    to_wall = time.time() - time.monotonic()
    for res in results:
        t = res.timing
        if not t:
            continue
        for name, opens, closes in _REQUEST_SPANS:
            span = start_span(
                f"caption.request.{name}", stage=stage, request_id=res.request_id,
                lane=t["lane"], prompt_tokens=res.num_prompt_tokens,
                output_tokens=res.num_output_tokens,
            )
            if closes + "_step" in t:
                span.set_attribute("step", t[closes + "_step"])
            span.start_s, span.end_s = to_wall + t[opens], to_wall + t[closes]
            end_span(span)


def _owner_tag(name: str) -> str:
    """A unique, human-readable engine-owner tag for one stage instance."""
    return f"{name}#{next(_OWNER_SEQ)}"


class _CaptionVLM(ModelInterface):
    MODEL_ID = "caption-vlm-tpu"

    def __init__(
        self,
        cfg: VLMConfig,
        max_batch: int,
        model_id: str | None = None,
        require_weights: bool = False,
        hf_chat: bool = False,
        specials: dict[str, int] | None = None,
        kv_lanes: tuple[tuple[int, int], ...] | None = None,
        text_only: bool = False,
        model_chips: int = 1,
        flavor: str | None = None,
        prefill_rows: int | None = None,
    ) -> None:
        self.cfg = cfg
        self.max_batch = max_batch
        self.model_id = model_id or self.MODEL_ID
        self.require_weights = require_weights
        self.hf_chat = hf_chat
        self.specials = specials
        self.kv_lanes = kv_lanes
        self.prefill_rows = prefill_rows  # FlavorSpec.prefill_rows
        self.text_only = text_only
        # chips of this host the engine's ``model`` mesh spans (FlavorSpec.
        # model_chips; 1 = no mesh) and the flavor's name for error messages
        self.model_chips = model_chips
        self.flavor = flavor
        self.engine: CaptionEngine | None = None
        self._tokenizer = None
        # encode_prompt memo: the HF BPE is pure-Python and the caption
        # prompts are loop-invariant across windows/clips/events
        self._prompt_cache: dict[tuple[str, bool], tuple[list[int], list[int]]] = {}

    def __getstate__(self):
        # engines and tokenizers are worker-local (the engine holds device
        # buffers; the tokenizer may load node-staged files)
        state = self.__dict__.copy()
        state["engine"] = None
        state["_tokenizer"] = None
        state["_prompt_cache"] = {}
        return state

    @property
    def model_id_names(self) -> list[str]:
        return [self.model_id]

    @property
    def tokenizer(self):
        """The tokenizer requests for this model MUST be encoded with.

        A converted HF checkpoint's embedding table is indexed by the
        checkpoint's exact token ids, so hf_chat flavors load
        HFVocabTokenizer from the staged ``vocab.json``/``merges.txt``
        (ADVICE r3: encoding such prompts with the repo BPE feeds wrong
        embedding rows and the eos check never fires). Missing tokenizer
        files fail loudly, like ``require_weights`` does for params.
        """
        if self._tokenizer is None:
            if self.hf_chat:
                from cosmos_curate_tpu.models.tokenizer import HFVocabTokenizer

                registry.maybe_pull_tokenizer_files(self.model_id)
                vocab = registry.find_model_file(self.model_id, "vocab.json")
                merges = registry.find_model_file(self.model_id, "merges.txt")
                if vocab is None or merges is None:
                    raise FileNotFoundError(
                        f"{self.model_id} is a converted-checkpoint flavor: "
                        f"stage its tokenizer files (vocab.json + merges.txt) "
                        f"under weights/{self.model_id}/ — encoding with the "
                        f"repo tokenizer would address wrong embedding rows"
                    )
                self._tokenizer = HFVocabTokenizer.from_gpt2_files(
                    vocab, merges, specials=self.specials
                )
            else:
                self._tokenizer = default_caption_tokenizer()
        return self._tokenizer

    def encode_prompt(
        self, user_text: str, *, has_vision: bool
    ) -> tuple[list[int], list[int]]:
        """(prefix_ids, prompt_ids) for a CaptionRequest in this flavor's
        prompt format: the checkpoint's chat template for hf_chat flavors
        (vision embeddings splice between the two); repo-native flavors put
        the instruction text in the PREFIX (before the vision block) — the
        cache-friendly layout: the engine's shared-prefix KV cache prefills
        it once per (flavor, prompt_variant) instead of once per window.
        Memoized — stages call this per window/clip/event with identical
        text."""
        if has_vision and self.text_only:
            raise ValueError(
                f"{self.model_id} is a TEXT-ONLY flavor (no trained vision "
                f"tower): frame-bearing stages (captioning, semantic filter, "
                f"per-event) cannot use it — pick a VL flavor; the LM flavor "
                f"serves enhancement/chat paths"
            )
        key = (user_text, has_vision)
        hit = self._prompt_cache.get(key)
        if hit is None:
            if self.hf_chat:
                from cosmos_curate_tpu.models.vlm.chat import build_qwen_vl_chat

                hit = build_qwen_vl_chat(
                    self.tokenizer,
                    user_text,
                    has_vision=has_vision,
                    specials=self.specials or None,
                )
            else:
                # all text before the vision block: for a text-only request
                # the token sequence is identical either way, and for a
                # vision request the shared instruction prefix becomes
                # positionally cacheable across windows
                hit = self.tokenizer.encode(user_text), []
            if len(self._prompt_cache) < 4096:  # bound memory on unique texts
                self._prompt_cache[key] = hit
        # copies: requests must not alias the cached lists
        return list(hit[0]), list(hit[1])

    def setup(self) -> None:
        from cosmos_curate_tpu.models.vlm import SharedCaptionEngine

        # build the tokenizer BEFORE the engine: a missing staged
        # tokenizer must fail setup, not first inference
        tokenizer = self.tokenizer

        def loader(engine: CaptionEngine):
            # asked before the engine has seeded anything: a checkpoint is
            # restored into the template's structure; where there is none
            # (and the flavor may go without) the engine seeds at setup()
            template = engine.param_template()
            params = registry.load_params(
                self.model_id, lambda seed: template, require=self.require_weights
            )
            return None if params is template else params

        self.engine = SharedCaptionEngine.get(
            self.cfg,
            model_id=self.model_id,
            max_batch=self.max_batch,
            kv_lanes=self.kv_lanes,
            prefill_rows=self.prefill_rows,
            tokenizer=tokenizer,
            loader=loader,
            mesh=self._serving_mesh(),
        )

    def _serving_mesh(self):
        """The ``model`` mesh the flavor is served over, or None for a
        flavor that fits one chip. The stage holds the whole host
        (``entire_tpu_host``), so the chips are this process's to take;
        with too few of them this raises, naming flavor, needed and found."""
        if self.model_chips == 1:
            return None
        from cosmos_curate_tpu.parallel.mesh import model_mesh

        return model_mesh(
            self.model_chips, what=f"caption model {self.flavor or self.model_id!r}"
        )


def resolve_caption_model(
    cfg: VLMConfig | None, model_flavor: str | None, max_batch: int
) -> _CaptionVLM:
    """One resolution rule for every caption-family stage (captioning,
    enhancement, semantic filter, per-event): an explicit flavor selects
    the full serving spec from VLM_FLAVORS — architecture, weight id,
    tokenizer/chat handling, and default KV lanes — and REQUIRES staged
    weights for real-checkpoint flavors (a user asking for qwen25vl-7b
    must not silently get random-init gibberish)."""
    if cfg is not None and model_flavor is not None:
        raise ValueError("pass cfg OR model_flavor, not both")
    if model_flavor is not None:
        from cosmos_curate_tpu.models.vlm.model import vlm_flavor

        spec = vlm_flavor(model_flavor)
        return _CaptionVLM(
            spec.cfg,
            max_batch,
            model_id=spec.model_id,
            require_weights=spec.require_weights,
            hf_chat=spec.hf_chat,
            specials=dict(spec.specials) if spec.specials else None,
            kv_lanes=spec.kv_lanes,
            text_only=spec.text_only,
            model_chips=spec.model_chips,
            flavor=model_flavor,
            prefill_rows=spec.prefill_rows,
        )
    return _CaptionVLM(cfg or VLM_BASE, max_batch)


class CaptionStage(Stage[SplitPipeTask, SplitPipeTask]):
    """TPU stage: continuous-batching captioning of every clip window."""

    def __init__(
        self,
        *,
        prompt_variant: str = "default",
        cfg: VLMConfig | None = None,
        max_batch: int = 8,
        max_new_tokens: int = 128,
        refine: bool = False,
        model_flavor: str | None = None,
        stage_batch_size: int = 32,
    ) -> None:
        self.prompt_variant = prompt_variant
        self.prompt_text = get_caption_prompt(prompt_variant)
        self.max_new_tokens = max_new_tokens
        self.refine = refine
        # this stage's engine-owner identity: requests are tagged with it,
        # completions route back by it, and the shared engine's cross-job
        # fairness + per-owner accounting key on it
        self.owner = _owner_tag(f"caption-{prompt_variant}")
        self._model = resolve_caption_model(cfg, model_flavor, max_batch)
        # a small-context flavor must clamp generation, not refuse requests
        # (half the context stays available for vision + prompt)
        if self.max_new_tokens >= self._model.cfg.max_seq // 2:
            self.max_new_tokens = self._model.cfg.max_seq // 2
        self._refined_ids: set[str] = set()  # stage-2 bookkeeping (not user data)
        # Deep batches feed the continuous batch: with the runner default of
        # one task per process_data call, every window decoded SOLO — the
        # engine never saw a full slot batch and pipeline tok/s sat at ~30%
        # of standalone. Admission still paces itself (waiting/ready queues
        # + background prep), so a deep batch costs queue memory, not stalls.
        self._stage_batch_size = max(1, stage_batch_size)
        # loop-invariant per-request pieces, resolved once per stage (the
        # prompt encode is also memoized model-side; this skips even the
        # memo lookup and the SamplingConfig rebuild per window)
        self._encoded_prompt: tuple[list[int], list[int]] | None = None
        self._sampling = SamplingConfig(max_new_tokens=self.max_new_tokens)

    @property
    def model(self) -> ModelInterface:
        return self._model

    @property
    def resources(self) -> Resources:
        return Resources(cpus=1.0, entire_tpu_host=True)

    @property
    def batch_size(self) -> int:
        return self._stage_batch_size

    def process_data(self, tasks: list[SplitPipeTask]) -> list[SplitPipeTask]:
        from cosmos_curate_tpu.observability import stage_timer
        from cosmos_curate_tpu.observability.tracing import traced_span, tracing_enabled

        engine = self._model.engine
        assert engine is not None, "setup() not called"
        t_start = time.monotonic()
        phases0 = engine.phase_seconds
        stats0 = self._engine_counts(engine)
        windows: dict[str, Window] = {}
        with traced_span("caption.submit", stage=self.name):
            for task in tasks:
                for clip in task.video.clips:
                    for w_i, win in enumerate(clip.windows):
                        if win.frames is None:
                            continue
                        rid = f"{clip.uuid}-{w_i}"
                        windows[rid] = win
                        # non-blocking: the engine preps (vision encode +
                        # embedding) in its background thread while the
                        # run_until_complete loop below decodes — prep of
                        # window N+1 overlaps decode of window N
                        engine.add_request(self._make_request(rid, win))
        if not windows:
            return tasks
        with traced_span("caption.engine", stage=self.name) as span:
            results = engine.run_until_complete(owner=self.owner)
            wall = time.monotonic() - t_start
            phases = self._phase_delta(engine, phases0, stats0, wall)
            phases["requests"] = len(results)
            for k, v in phases.items():
                span.set_attribute(f"caption.{k}", round(v, 4) if isinstance(v, float) else v)
            if tracing_enabled():
                _emit_request_spans(results, self.name)
        stage_timer.record_caption_phases(self.name, phases)
        try:
            from cosmos_curate_tpu.engine.metrics import get_metrics

            get_metrics().observe_caption_owners(engine.owner_stats())
        except Exception:  # metrics must never take down the caption path
            pass
        for res in results:
            win = windows.get(res.request_id)
            if win is None:
                continue
            win.caption[self.prompt_variant] = res.text
        logger.info(
            "captioned %d windows at %.1f output tok/s "
            "(prefill %.2fs decode %.2fs idle %.2fs; prefix hits %d, "
            "%d prefill tokens saved)",
            len(results),
            engine.tokens_per_second,
            phases["prefill_s"],
            phases["decode_s"],
            phases["idle_s"],
            phases["prefix_cache_hits"],
            phases["prefix_tokens_saved"],
        )
        for task in tasks:
            task.stage_perf["caption_tokens_per_s"] = engine.tokens_per_second
            task.stage_perf["caption_prefix_cache_hits"] = phases["prefix_cache_hits"]
            task.stage_perf["caption_engine_idle_s"] = round(phases["idle_s"], 4)
            task.stage_perf["caption_kv_blocks_used"] = engine.kv_blocks_used
            task.stage_perf["caption_prefix_block_refs"] = phases["prefix_block_refs"]
        return tasks

    def _engine_counts(self, engine: CaptionEngine) -> dict:
        """The stage's counters (``stage_timer._CAPTION_COUNT_KEYS``) of those
        ``engine.stats()`` hands out: engine-wide, a drive's deltas like the
        phases (which carry the rest of those keys themselves)."""
        from cosmos_curate_tpu.observability import stage_timer

        stats = engine.stats()
        counts = {k: stats[k] for k in stage_timer._CAPTION_COUNT_KEYS if k in stats}
        # per-OWNER, not engine-wide: under a shared engine another job's
        # tokens decode inside this drive's window, and the run report's
        # owner table must not claim them for this stage
        counts["decode_tokens"] = engine.owner_decode_tokens.get(self.owner, 0)
        return counts

    def _phase_delta(
        self, engine: CaptionEngine, phases0: dict, stats0: dict, wall: float
    ) -> dict:
        """Per-phase/cache deltas over this drive. Counters are engine-wide
        — under a shared engine another stage's concurrent drive bleeds in,
        so treat per-stage attribution as approximate there. ``idle_s`` is
        wall minus device phases: the engine-stall time the overlap rework
        exists to shrink."""
        phases1 = engine.phase_seconds  # seconds, exposed seconds and counts: the whole account
        phases = {k: v - phases0[k] for k, v in phases1.items()}
        now = self._engine_counts(engine)
        counts = {k: now[k] - stats0[k] for k in now}
        busy = phases["prefill_s"] + phases["decode_s"]
        return {
            **phases,
            **counts,
            "wall_s": wall,
            "idle_s": max(0.0, wall - busy),
            # occupancy gauges (absolute, not deltas) + the owner identity
            # for per-owner accounting in the run report
            "owner": self.owner,
            "kv_blocks_total": engine.kv_blocks_total,
            "kv_blocks_peak": engine.kv_blocks_used_peak,
            "kv_blocks_used": engine.kv_blocks_used,
        }

    def _make_request(self, rid: str, win: Window) -> CaptionRequest:
        if self._encoded_prompt is None:
            self._encoded_prompt = self._model.encode_prompt(
                self.prompt_text, has_vision=True
            )
        prefix_ids, prompt_ids = self._encoded_prompt
        sampling = self._sampling
        on_complete = None
        if self.refine:
            def on_complete(text: str, _rid=rid, _win=win) -> CaptionRequest | None:
                if _rid in self._refined_ids:
                    return None
                self._refined_ids.add(_rid)
                pre, ids = self._model.encode_prompt(
                    REFINEMENT_PROMPT + text, has_vision=True
                )
                return CaptionRequest(
                    request_id=_rid,
                    prefix_ids=pre,
                    prompt_ids=ids,
                    frames=_win.frames,
                    frame_fps=_win.frame_fps,
                    sampling=sampling,
                    on_complete=on_complete,
                    # the stage-2 prefix bakes in the window's own caption —
                    # unique per window, so caching it would thrash the
                    # shared-prefix LRU without ever hitting
                    share_prefix=False,
                )
        return CaptionRequest(
            request_id=rid,
            prefix_ids=list(prefix_ids),
            prompt_ids=list(prompt_ids),
            frames=win.frames,
            frame_fps=win.frame_fps,
            sampling=sampling,
            on_complete=on_complete,
            owner=self.owner,
        )
