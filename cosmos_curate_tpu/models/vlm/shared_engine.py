"""Process-level shared caption engine registry: cross-job continuous
batching.

Equivalent capability of the reference's single vLLM deployment serving
every caption consumer (cosmos_curate/models/vllm_interface.py — one engine
process, many request streams): engines are registered per
``(model, dtype, mesh)``, so every caption-family stage — captioning,
enhancement, semantic filter, per-event — and every CONCURRENT pipeline in
the process (the pipelined runner's pinned caption workers included)
submits into ONE engine per served model. Requests carry an ``owner`` tag
and the engine's admission interleaves owners fairly (Orca-style
iteration-level scheduling across jobs), so two pipelines decode in one
continuous batch instead of each paying for a half-idle private engine —
and weights + the KV block pool exist once per model, not once per
pipeline.

The key deliberately EXCLUDES serving geometry (max_batch, kv_lanes,
block_size): sharing one engine across stages that ask for different batch
sizes is the point, so the first creator's geometry wins and later getters
join it (logged when they asked for something else).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable

from cosmos_curate_tpu.models.vlm.engine import CaptionEngine
from cosmos_curate_tpu.models.vlm.model import VLMConfig
from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass(frozen=True)
class EngineKey:
    """What must match for two callers to share one engine: the served
    checkpoint (model_id — the same architecture under two weight ids must
    NOT share, the second would caption with the first's weights), the
    architecture (cfg), the compute dtype, the device mesh the engine was
    built on, and the SHARDING geometry over that mesh (two engines sharding
    the KV pool over different model-axis extents compile different
    programs and must never collide on one registry slot)."""

    model_id: str
    cfg: VLMConfig
    dtype: str
    mesh: tuple
    geometry: tuple = ()


class SharedCaptionEngine:
    """The process-level registry. All methods are classmethods — there is
    exactly one registry per process, like the device mesh itself."""

    _lock = threading.Lock()
    _engines: "dict[EngineKey, CaptionEngine]" = {}
    # per-key build locks: engine setup + weight loading can take minutes,
    # and must not stall registry reads or a DIFFERENT model's creation
    _building: "dict[EngineKey, threading.Lock]" = {}

    @staticmethod
    def _mesh_fingerprint() -> tuple:
        import jax

        return tuple((d.platform, int(d.id)) for d in jax.devices())

    @staticmethod
    def _mesh_geometry(mesh) -> tuple:
        """Hashable (axis, extent) tuple for a serving mesh (empty when
        unsharded) — matches CaptionEngine.mesh_geometry."""
        if mesh is None:
            return ()
        return tuple((str(n), int(mesh.shape[n])) for n in mesh.axis_names)

    @classmethod
    def key_for(
        cls, cfg: VLMConfig, model_id: str, dtype: str = "bfloat16", mesh: Any = None
    ) -> EngineKey:
        return EngineKey(
            model_id, cfg, dtype, cls._mesh_fingerprint(), cls._mesh_geometry(mesh)
        )

    @classmethod
    def get(
        cls,
        cfg: VLMConfig,
        *,
        model_id: str,
        max_batch: int = 8,
        kv_lanes: tuple | None = None,
        prefill_rows: int | None = None,
        tokenizer: Any = None,
        dtype: str = "bfloat16",
        async_prep: bool = True,
        loader: "Callable[[CaptionEngine], Any] | None" = None,
        mesh: Any = None,
    ) -> CaptionEngine:
        """The shared engine for (model, dtype, mesh, sharding geometry),
        building + setting it up on first use. ``loader`` (called once,
        with the fresh engine, BEFORE its ``setup()``: no seeded tree exists
        yet, and ``engine.param_template()`` has the structure a checkpoint
        restores into) returns the params to serve, or None to serve seeded
        ones — weight loading stays the caller's policy (require_weights
        etc.) without the registry re-running it per stage. ``setup()`` then
        places and narrows what was loaded, or seeds: a flavor whose float32
        tree fits no chip beside its loaded one is never seeded first.
        ``mesh`` selects the head-parallel paged-attention geometry and is
        part of the key: differently sharded engines never share."""
        key = cls.key_for(cfg, model_id, dtype, mesh=mesh)

        def existing() -> "CaptionEngine | None":
            engine = cls._engines.get(key)
            if engine is None:
                return None
            actual = [(l.length, l.n_slots) for l in engine.lanes]
            wanted = (
                sorted((int(a), int(b)) for a, b in kv_lanes)
                if kv_lanes is not None
                else None
            )
            if (wanted is not None and wanted != actual) or (
                wanted is None and max_batch != engine.max_batch
            ):
                logger.info(
                    "sharing caption engine %s: requested geometry "
                    "(max_batch=%s, kv_lanes=%s) differs from the creator's "
                    "lanes %s (geometry is fixed at first creation)",
                    model_id,
                    max_batch,
                    kv_lanes,
                    actual,
                )
            return engine

        with cls._lock:
            engine = existing()
            if engine is not None:
                return engine
            build_lock = cls._building.setdefault(key, threading.Lock())
        # build OUTSIDE the registry lock (setup compiles, loader may pull
        # checkpoints for minutes) — only same-key callers wait
        with build_lock:
            with cls._lock:
                engine = existing()
            if engine is not None:
                return engine
            engine = CaptionEngine(
                cfg,
                max_batch=max_batch,
                tokenizer=tokenizer,
                kv_lanes=kv_lanes,
                max_prefill_rows=prefill_rows,
                # production engines prep in the background so vision
                # encoding of request N+1 overlaps decode of request N
                async_prep=async_prep,
                mesh=mesh,
            )
            if loader is not None:
                engine.params = loader(engine)
            engine.setup()
            with cls._lock:
                cls._engines[key] = engine
                cls._building.pop(key, None)
            return engine

    @classmethod
    def adopt(
        cls, engine: CaptionEngine, *, cfg: VLMConfig, model_id: str,
        dtype: str = "bfloat16",
    ) -> None:
        """Register an externally built engine (benchmarks seed their warm
        engine so the CaptionStage pass shares it instead of doubling
        weight memory). The engine's own mesh decides the geometry slot."""
        with cls._lock:
            key = cls.key_for(cfg, model_id, dtype, mesh=getattr(engine, "mesh", None))
            cls._engines[key] = engine

    @classmethod
    def stats(cls) -> dict:
        """Registry-wide occupancy + per-owner gauges, keyed by model_id —
        the cross-job observability surface."""
        with cls._lock:
            engines = dict(cls._engines)
        out: dict[str, dict] = {}
        for key, engine in engines.items():
            out[key.model_id] = {
                "kv_blocks_used": engine.kv_blocks_used,
                "kv_blocks_total": engine.kv_blocks_total,
                "prefix_block_refs": engine.prefix_block_refs,
                "interleaved_decode_steps": engine.interleaved_decode_steps,
                "owners": engine.owner_stats(),
            }
        return out

    @classmethod
    def reset(cls) -> None:
        """Drop every registered engine (tests). Engines are shut down so
        prep threads stop and prefix-cache block references release."""
        with cls._lock:
            engines = list(cls._engines.values())
            cls._engines.clear()
            cls._building.clear()
        for engine in engines:
            try:
                engine.shutdown()
            except Exception:  # a wedged prep thread must not fail teardown
                logger.exception("engine shutdown failed during registry reset")
