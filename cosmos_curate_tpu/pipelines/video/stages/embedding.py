"""Embedding stages: per-clip video embeddings on the TPU.

Equivalent capability of the reference's embedding stages
(cosmos_curate/pipelines/video/embedding/internvideo2_stages.py:43/187,
cosmos_embed1_stages.py:43/190 — a CPU frame-prep stage feeding a device
embed stage). The same deliberate CPU/device split: frame prep happens in
``ClipFrameExtractionStage``; this stage batches all clips in a task into
shape-grouped batches that the embedders dispatch through the shared
``DevicePipeline`` (models/device_pipeline.py) — pow2 bucket micro-batches,
double-buffered H2D/compute, readback deferred to the drain — so the MXU
stays fed while the host assembles the next group.
"""

from __future__ import annotations

import numpy as np

from cosmos_curate_tpu.core.model import ModelInterface
from cosmos_curate_tpu.core.stage import Resources, Stage
from cosmos_curate_tpu.data.model import FrameExtractionSignature, SplitPipeTask
from cosmos_curate_tpu.models.clip import CLIPImageEmbeddings
from cosmos_curate_tpu.models.embedder import VIDEO_EMBED_BASE, VideoEmbedConfig, VideoEmbedder
from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Tasks fused per device dispatch.
EMBED_STAGE_TASK_BATCH = 8


class ClipEmbeddingStage(Stage[SplitPipeTask, SplitPipeTask]):
    """variant="video": temporal-transformer video embedding;
    variant="clip": mean of normalized CLIP frame embeddings."""

    def __init__(
        self,
        *,
        variant: str = "video",
        video_cfg: VideoEmbedConfig | None = None,
        clip_variant: str = "clip-vit-b16-tpu",
        extraction: FrameExtractionSignature = FrameExtractionSignature("fps", 2.0),
    ) -> None:
        from cosmos_curate_tpu.models.embedder import VIDEO_EMBED_VARIANTS
        from cosmos_curate_tpu.models.internvideo2 import IV2_VARIANTS, IV2Embedder

        known = ["clip", *VIDEO_EMBED_VARIANTS, *IV2_VARIANTS]
        if variant not in known:
            raise ValueError(f"unknown embedding variant {variant!r}; have {known}")
        self.variant = "clip" if variant == "clip" else "video"
        self.extraction = extraction
        self._model: ModelInterface
        if variant == "clip":
            self._model = CLIPImageEmbeddings(clip_variant)
        elif variant in IV2_VARIANTS:
            cfg, model_id, require = IV2_VARIANTS[variant]
            self._model = IV2Embedder(cfg, model_id=model_id, require_weights=require)
        elif video_cfg is not None:
            self._model = VideoEmbedder(video_cfg)
        else:
            cfg, model_id = VIDEO_EMBED_VARIANTS[variant]
            self._model = VideoEmbedder(cfg, model_id=model_id)

    @property
    def model(self) -> ModelInterface:
        return self._model

    @property
    def resources(self) -> Resources:
        return Resources(cpus=1.0, tpus=1.0)

    @property
    def model_name(self) -> str:
        return self._model.model_id_names[0]

    @property
    def batch_size(self) -> int:
        # several tasks per call: their clips fuse into per-shape device
        # batches below, so the MXU sees e.g. 32 clips instead of 4 per
        # dispatch
        return EMBED_STAGE_TASK_BATCH

    def process_data(self, tasks: list[SplitPipeTask]) -> list[SplitPipeTask]:
        key = self.extraction.key()
        if self.variant == "video":
            self._embed_video_batch([t.video for t in tasks], key)
        else:
            self._embed_clip_mean_batch([t.video for t in tasks], key)
        return tasks

    def _embed_video_batch(self, videos, key: str) -> None:
        """encode_clips over every clip of every task in the batch
        (cross-task batching: per-video batches waste the MXU on short
        videos with few clips). Clips group by spatial shape — a
        mixed-resolution corpus without prep-stage resizing embeds per
        group instead of crashing the whole batch."""
        model: VideoEmbedder = self._model  # type: ignore[assignment]
        groups: dict[tuple, tuple[list, list]] = {}
        for video in videos:
            for clip in video.clips:
                frames = clip.extracted_frames.get(key)
                if frames is None or frames.shape[0] == 0:
                    continue
                idx = model.sample_frame_indices(frames.shape[0])
                batch, targets = groups.setdefault(frames.shape[1:], ([], []))
                batch.append(frames[idx])
                targets.append(clip)
        for batch, targets in groups.values():
            embs = model.encode_clips(np.stack(batch))
            for clip, emb in zip(targets, embs):
                clip.embeddings[self.model_name] = emb

    def _embed_clip_mean_batch(self, videos, key: str) -> None:
        """Mean-of-CLIP-frame embeddings, fused across every clip of every
        task in the batch (same cross-task batching as the video variant),
        grouped by frame shape."""
        model: CLIPImageEmbeddings = self._model  # type: ignore[assignment]
        groups: dict[tuple, tuple[list, list]] = {}
        for video in videos:
            for clip in video.clips:
                frames = clip.extracted_frames.get(key)
                if frames is None or frames.shape[0] == 0:
                    continue
                stacks, targets = groups.setdefault(frames.shape[1:], ([], []))
                stacks.append(frames)
                targets.append(clip)
        for stacks, targets in groups.values():
            embs = model.encode_frames(np.concatenate(stacks))
            offset = 0
            for clip, frames in zip(targets, stacks):
                n = frames.shape[0]
                mean = embs[offset : offset + n].mean(axis=0)
                mean /= np.linalg.norm(mean) + 1e-8
                clip.embeddings[self.model_name] = mean.astype(np.float32)
                offset += n
