"""Continuous-batching caption engine.

Equivalent capability of the reference's vLLM engine driver
(cosmos_curate/models/vllm_interface.py:390-703 — ``add_request``/``step``
in-flight batching with two-stage caption refinement; async variant
vllm_async_stage.py). TPU-first re-design:

- **paged KV cache**: KV memory is ONE block pool ``[L, n_blocks, Hkv,
  block_size, Dh]`` (models/vlm/paged_kv.py) and every admitted slot
  holds a block *table* instead of a worst-case-length cache row — a
  request reserves ``ceil((prompt + max_new + 1) / block_size)`` blocks, so
  pool occupancy (not slot count) is the admission limit, vLLM
  PagedAttention-style. Prefill/decode programs gather each slot's blocks
  into a contiguous lane-length view (the exact shapes the slot-row engine
  compiled — greedy outputs stay byte-identical), run the unchanged model,
  and scatter the written blocks back. Lanes survive as decode-batch
  shapes: a lane bounds the gathered view length and groups slots into one
  static-shape decode program.
- **continuous batching**: slots join/leave between decode steps; the decode
  step always runs the full slot batch with an active mask (idle rows write
  into the reserved garbage block — dead work, bounded by max_batch, in
  exchange for zero recompiles).
- **tokens/s** is tracked per engine — THE caption-throughput metric
  (reference docs/curator/design/SPEED_OF_LIGHT.md).
- **refcounted shared-prefix blocks**: every caption request in a run opens
  with the same system-prompt/template text (SGLang RadixAttention's core
  insight, Zheng et al. 2024 — and the caption workload is its best case:
  the prefix is identical across ALL requests of a (flavor,
  prompt_variant)). The prefix prefills ONCE into pool blocks that admitted
  requests REFERENCE through their block tables with a refcount — zero
  device copies at admission (the round-7 per-slot ``insert_prefix`` copy is
  gone); copy-on-write duplicates only a partially-filled shared tail
  block. Per-request prefill starts at the prefix boundary with absolute
  rope positions, producing byte-identical greedy output while skipping
  ``len(prefix) x (requests - 1)`` prefill tokens. Evicting a prefix whose
  blocks are still referenced defers the free to the last referencing slot.
- **cross-job continuous batching**: requests carry an ``owner`` and the
  admission loop interleaves owners fairly (least-recently-admitted owner
  first, per-owner in-flight cap), so several concurrent pipelines/stages
  sharing one engine (models/vlm/shared_engine.py) decode in ONE batch
  instead of serializing whole jobs — Orca-style iteration-level
  scheduling across jobs.
- **two kinds of state** (hybrid flavors, ``cfg.ssm_layers``): beside the
  block pool, whose ``L`` then counts the attention layers only, a
  slot-indexed recurrent store ``[Lm, slots + 1, H, P, N]`` float32 (and the
  convolutions' tails) holds the Mamba-2 state of every state-space layer, a
  row a slot, row 0 the garbage row. A row is claimed and released with the
  slot's blocks; admission zeroes it or copies the shared prefix's snapshot
  into it (a prefix entry is blocks PLUS the state at exactly its last
  token); chunked prefill carries it from chunk to chunk; and since a
  recurrence cannot mask what it did not mean to read, padding never
  advances it: a prefill's positions past ``t_valid`` and a decode program's
  idle rows are masked in the recurrence, and a chunked prefill's last chunk
  is padded at its end instead of shifted back over tokens already taken.
- **prep/decode overlap** (``async_prep=True``): a background thread runs
  vision encoding + token embedding for waiting requests while the caller's
  ``step()`` loop decodes, so frame prep of request N+1 hides behind decode
  of request N instead of serializing with it.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from cosmos_curate_tpu.models.batching import next_pow2
from cosmos_curate_tpu.models.tokenizer import ByteTokenizer, default_caption_tokenizer
from cosmos_curate_tpu.models.vlm.model import VLM, VLMConfig, init_cache, init_recurrent_store
from cosmos_curate_tpu.models.vlm.paged_kv import (
    BlockAllocator,
    PoolExhausted,
    gather_block_views,
    init_block_pool,
    scatter_block_views,
)

# full sampling surface (top_p/min_p/penalties/min_tokens) lives in
# models/vlm/sampling.py; re-exported here for the existing import paths
from cosmos_curate_tpu.models.vlm.sampling import SamplingConfig, sample_token
from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass
class CaptionRequest:
    request_id: str
    prompt_ids: list[int]
    frames: np.ndarray | None = None  # uint8 [N, H, W, 3]
    # rate the frames were sampled at (frames/sec of source time); drives
    # Qwen2.5-VL's absolute-time temporal m-rope (None = unscaled)
    frame_fps: float | None = None
    # text tokens embedded BEFORE the vision block (chat templates put the
    # system turn + <|vision_start|> ahead of the image pads); prompt_ids
    # follow the vision block
    prefix_ids: list[int] = field(default_factory=list)
    sampling: SamplingConfig = field(default_factory=SamplingConfig)
    # called with the finished text; may return a follow-up request
    # (two-stage caption refinement, reference vllm_interface.py:543)
    on_complete: Callable[[str], "CaptionRequest | None"] | None = None
    metadata: dict[str, Any] = field(default_factory=dict)
    # set by add_request: which caller's run_until_complete owns this request
    # (several caption-family stages share one engine; see run_until_complete)
    owner: Any = None
    # Whether this request's text prefix may be served from / inserted into
    # the shared-prefix KV cache. Stages set False for one-shot prefixes
    # (the refinement pass bakes the stage-1 caption into its prefix, so
    # caching it would only thrash the LRU).
    share_prefix: bool = True
    # Encoded vision-tower output reused across passes of the SAME frames
    # (the engine fills this after the first encode; a refinement follow-up
    # carrying the identical frames array inherits it automatically).
    vision_features: Any = field(default=None, repr=False)


@dataclass
class _Slot:
    request: CaptionRequest
    position: int  # next cache position to write (== current length)
    # next ROPE position — under m-rope this lags the cache position
    # (vision tokens share t/h/w coordinates; text resumes at max(grid)+1)
    rope_position: int = 0
    generated: list[int] = field(default_factory=list)
    # per-request generator when sampling.seed is set (reproducible
    # captions regardless of batch interleaving); None = engine-shared rng
    rng: np.random.Generator | None = None
    # incrementally decoded output bytes (exact: decode is per-token byte
    # concatenation) — stop-string checks scan a bounded tail of this
    raw: bytearray = field(default_factory=bytearray)
    # prompt+output token counts maintained incrementally for penalties
    # (None when no penalty is configured)
    penalty_counts: dict[int, int] | None = None


def _truncate_at_stop(text: str, stops: tuple[str, ...]) -> str | None:
    """Text before the EARLIEST stop-string match (tuple order must not
    matter), or None when nothing matches."""
    idx = min((i for i in (text.find(s) for s in stops) if i >= 0), default=-1)
    return text[:idx] if idx >= 0 else None


@dataclass
class CaptionResult:
    request_id: str
    text: str
    num_prompt_tokens: int
    num_output_tokens: int
    metadata: dict[str, Any] = field(default_factory=dict)
    owner: Any = None


@dataclass
class _VisionFeatures:
    """One window's encoded vision-tower output, cached on the request so a
    refinement follow-up over the SAME frames skips the tower entirely."""

    embeds: Any  # [T_vis, D] device array
    ds: np.ndarray | None  # qwen3 deepstack levels [L_ds, T_vis, D]
    grid: tuple[int, int, int] | None
    eff_fps: float | None
    n_tokens: int


@dataclass
class _Prepared:
    """A request after host/vision prep, ready for admission.

    ``embeds`` hold only the SUFFIX (everything after the shared text
    prefix) when ``base > 0``: the prefix's K/V come from the shared-prefix
    cache and are device-copied into the slot's cache rows at admission, so
    prefill starts at cache position ``base`` (rope positions stay
    absolute — ``rope`` rows are the suffix slice of the full layout)."""

    request: CaptionRequest
    embeds: np.ndarray  # [T_suffix, D] float32
    t_suffix: int
    rope: np.ndarray  # [T_suffix] or [T_suffix, 3]
    next_rope: int
    ds: np.ndarray | None  # [L_ds, T_suffix, D] deepstack (suffix-aligned)
    base: int = 0  # cached prefix length already in the KV cache
    prefix_key: tuple | None = None

    @property
    def total(self) -> int:
        return self.base + self.t_suffix


@dataclass
class _PrefixEntry:
    """One shared text prefix, prefilled ONCE and resident in pool blocks.

    Admitted requests reference ``blocks[:n_full]`` directly through their
    block tables (refcounted — zero device copies); a partially-filled
    ``tail_block`` (``length % block_size != 0``) is copy-on-write
    duplicated at admission, since the referencing slot's own K/V writes
    would otherwise extend into shared memory."""

    blocks: list[int]  # ceil(length / block_size) pool block ids
    n_full: int  # length // block_size — the directly-shareable prefix
    tail_block: int | None  # blocks[-1] when partially filled, else None
    length: int
    # hybrid flavors: the state-space layers' state after exactly ``length``
    # tokens, (ssm [Lm, H, P, N], conv [Lm, (d_conv - 1) * conv_dim]) — copied
    # into the slot's row of the recurrent store at admission
    state: tuple | None = None


@dataclass
class _BlockClaim:
    """The pool blocks one admitted slot holds: ``shared`` prefix blocks it
    incref'd (freed back to the prefix entry's refcount on release) and
    ``private`` blocks it owns outright (freed on release)."""

    shared: list[int]
    private: list[int]

    @property
    def all_blocks(self) -> list[int]:
        return self.shared + self.private


@dataclass
class _PendingPrefill:
    """A slot whose prompt is being prefilled chunk by chunk.

    Long prompts are admitted in fixed-size chunks interleaved with decode
    steps (vLLM chunked prefill, reference models/vllm_interface.py:543 +
    SPEED_OF_LIGHT.md:116-121): one prefill group no longer stalls every
    in-flight request's decode for its whole duration. The chunk program is
    the same compiled family as bucket prefill (static [N, C, D] shapes,
    per-row write_index), so chunking adds zero recompiles."""

    request: CaptionRequest
    embeds: np.ndarray  # [T, D] prompt embeds (suffix-only when base > 0)
    t_valid: int
    rope_pos: np.ndarray  # [T] or [T, 3]
    next_rope: int
    progress: int = 0  # prompt tokens already written to the cache
    # qwen3 deepstack visual features [L_ds, T, D] (zeros at text
    # positions), chunk-sliced alongside embeds; None otherwise
    ds: np.ndarray | None = None
    # cache offset where this prompt's writes start (= cached shared-prefix
    # length; chunk k writes at base + progress)
    base: int = 0


@dataclass
class _Lane:
    """One decode-batch shape: ``n_slots`` block tables of ``length``
    gathered positions each.

    With the paged pool, a lane no longer OWNS KV memory — blocks come from
    the engine-wide pool and occupancy is the admission limit. What a lane
    still bounds is compiled-program shape: its slots decode as one static
    ``[n_slots, length]`` batch, and ``length`` caps the gathered view (so
    short requests ride cheap short-view programs instead of the worst-case
    gather). ``table`` rows are the slot block tables; free/unused entries
    point at the reserved garbage block 0."""

    length: int
    base: int  # global slot-id offset (lane-local idx + base = public id)
    n_slots: int
    # [n_slots, length // block_size] int32 pool block ids (host-side; a
    # snapshot rides into every prefill/decode program call)
    table: np.ndarray | None = None
    slots: dict = field(default_factory=dict)
    pending: dict = field(default_factory=dict)
    # slot indices claimed by _admit's current grouping pass (released when
    # the group prefill runs)
    reserved: set = field(default_factory=set)
    # slot idx -> _BlockClaim, held from admission until release
    claims: dict = field(default_factory=dict)


# Phases of the engine's two threads, each timed at one site by
# CaptionEngine._phase(): the span "engine.<name>" on the profiler's clock and
# the counter "<name>_s" of phase_seconds on the host's. The roots also report
# their elapsed time (`step_s`, `prep_s`); their self time is `<root>_other_s`.
_PHASE_ROOTS = ("step", "prep")
_PHASES = _PHASE_ROOTS + (
    "lock_wait",
    "admit",
    "prefill_build",
    "prefill_dispatch",
    "prefill_wait",
    "prefill_sample",
    "decode_build",
    "decode_dispatch",
    "decode_wait",
    "decode_sample",
    "vision_encode",
)


def _bytes_per_chip(tree) -> int:
    """Bytes of ``tree`` resident on one device: a partitioned leaf counts
    one shard, a repeated (or unplaced) leaf its whole. Read off the
    arrays' shardings — no device is asked anything."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        shape = sharding.shard_shape(leaf.shape) if sharding is not None else leaf.shape
        total += int(np.prod(shape)) * leaf.dtype.itemsize
    return total


def _init_params(model: VLM, seed: int = 0):
    """``model``'s parameters from ``seed`` (boxed: every leaf carries its
    partition annotation), through the one method that touches every layer."""
    cfg = model.cfg
    size = (
        cfg.qwen_vision.image_size
        if cfg.vision_variant in ("qwen2", "qwen3")
        else cfg.vision.image_size
    )
    return model.init(
        jax.random.PRNGKey(seed),
        jnp.zeros((1, 1, size, size, 3), jnp.uint8),
        jnp.zeros((1, 4), jnp.int32),
        *init_cache(cfg, 1),
        method=model.init_everything,
    )


@lru_cache(maxsize=8)
def _abstract_params(model: VLM):
    """Shape, dtype and partition annotation of every parameter of
    ``model``, nothing made. Tracing a 2B model's init takes seconds, and
    engines that share one tree share one model: traced once a process."""
    return jax.eval_shape(partial(_init_params, model))


def _stored_as(leaf, dtype):
    """``leaf`` in ``dtype``. A leaf of another type is cast (a placed
    array keeps its sharding) and its source DELETED at once, so that a tree
    is narrowed with never more than one leaf held twice, whoever else still
    refers to the wider tree; a leaf already in ``dtype`` is returned as it
    is, so that engines can share one tree."""
    if leaf.dtype == dtype:
        return leaf
    wide = jnp.asarray(leaf)  # the caller's own array, or a host leaf's copy
    stored = wide.astype(dtype)
    wide.delete()
    return stored


class CaptionEngine:
    def __init__(
        self,
        cfg: VLMConfig,
        *,
        max_batch: int = 8,
        params: Any = None,
        tokenizer: ByteTokenizer | None = None,
        prefill_chunk: int = 256,
        kv_lanes: tuple[tuple[int, int], ...] | None = None,
        async_prep: bool = False,
        enable_prefix_cache: bool = True,
        prefix_cache_size: int = 8,
        min_prefix_len: int = 4,
        admission_linger_s: float = 0.05,
        block_size: int = 16,
        kv_pool_blocks: int | None = None,
        owner_inflight_cap: int | None = None,
        paged_attention: str = "auto",
        mesh: Any = None,
    ) -> None:
        """``params`` (here or assigned to ``.params`` later) are CONSUMED,
        as a jitted call consumes a donated argument: the engine serves from
        parameters stored in the type each layer computes in (bfloat16 for
        the matmuls and the embedding table; float32 for norm scales, the MoE
        router and an untied head), so ``setup()`` casts every leaf that
        comes in another type once and deletes its source as it goes. A
        caller that needs its wider tree afterwards hands in a copy. Leaves
        already in their serving type are kept as they are, never deleted:
        ``CaptionEngine(cfg, params=other.params)`` shares one tree."""
        self.cfg = cfg
        self.max_batch = max_batch
        # prompts longer than this prefill in chunks of this size,
        # interleaved with decode steps
        self.prefill_chunk = min(prefill_chunk, cfg.max_seq)
        self.tokenizer = tokenizer or default_caption_tokenizer()
        # which family of programs: "auto"/"kernel" run the paged programs
        # (attention reads the pool through the block table; which
        # implementation is ops/paged_attention.py's decision alone);
        # "gather" builds the gather-view/scatter-back programs over the
        # XLA reference, which the parity tests and the benchmark's
        # `correct` compare against
        if paged_attention not in ("auto", "kernel", "gather"):
            raise ValueError(
                f"paged_attention must be auto|kernel|gather, got {paged_attention!r}"
            )
        self.paged_attention = paged_attention
        self._use_paged = paged_attention != "gather"
        # optional device mesh: threads into the model so the paged path
        # runs head-parallel over parallel/axes.MODEL when the mesh names
        # that axis (KV pool + heads sharded, block tables replicated)
        self.mesh = mesh
        # hybrid flavors keep a second kind of per-request state (module docstring)
        self._recurrent = bool(cfg.ssm_layers)
        if self._recurrent and mesh is not None:
            raise ValueError("the recurrent store is not split over a mesh: serve a hybrid on one chip")
        # parameters stored in the type they are computed in: see VLM.param_dtype
        self.model = VLM(cfg, mesh=mesh, param_dtype=VLM.dtype)
        # the model's abstract parameter tree once setup() has taken it: per
        # leaf the serving dtype and (mesh engines only) the PartitionSpec.
        # From then on whatever is assigned to ``params`` — a handed-in tree,
        # a checkpoint loaded after setup, another engine's tree — is placed
        # over the mesh and stored in those types, never left on one device
        # or in a type every program would have to cast from
        self._param_dtypes: Any = None
        self._param_specs: Any = None
        self.params = params
        self.waiting: list[CaptionRequest] = []
        # (length, n_slots) per decode-batch lane; default = one
        # worst-case-length lane, the round-2 behavior
        spec = kv_lanes or ((cfg.max_seq, max_batch),)
        # every lane length must tile into whole blocks (the gathered view
        # must equal the lane length EXACTLY for shape parity with the
        # slot-row programs): shrink the block size to the largest common
        # divisor when a lane length doesn't tile
        bs = max(1, int(block_size))
        for length, _ in spec:
            bs = math.gcd(bs, int(length))
        if bs != block_size:
            logger.warning(
                "block_size %d does not divide every KV lane length; using %d",
                block_size, bs,
            )
        # both sides of the fallback are surfaced (stats() / bench row) so
        # bench comparisons across block sizes aren't apples-to-oranges
        # when the gcd silently shrank the divisor
        self.block_size_requested = int(block_size)
        self.block_size = bs
        base = 0
        self.lanes: list[_Lane] = []
        for length, n in sorted(spec):
            if length > cfg.max_seq:
                raise ValueError(f"lane length {length} exceeds max_seq {cfg.max_seq}")
            self.lanes.append(
                _Lane(
                    length=length,
                    base=base,
                    n_slots=n,
                    table=np.zeros((n, length // bs), np.int32),
                )
            )
            base += n
        self.prefix_cache_size = prefix_cache_size
        lane_blocks = sum((l.length // bs) * l.n_slots for l in self.lanes)
        if kv_pool_blocks is None:
            # pool capacity = the memory the per-lane rows used to pin, plus
            # headroom for the shared-prefix entries that now live in pool
            # blocks, plus the reserved garbage block 0
            prefix_reserve = (
                prefix_cache_size * max(1, min(256, self.lanes[-1].length) // bs)
                if enable_prefix_cache
                else 0
            )
            kv_pool_blocks = 1 + lane_blocks + prefix_reserve
        # a pool smaller than the lane sum could deadlock a full slot load
        self.kv_pool_blocks = max(int(kv_pool_blocks), 1 + lane_blocks)
        self._allocator = BlockAllocator(self.kv_pool_blocks)
        self._pool_k = None
        self._pool_v = None
        self._kv_pool_bytes_per_chip = 0  # until setup() makes the pool
        # the recurrent store (hybrid flavors; None otherwise): slot ``i`` of
        # lane ``l`` owns row ``1 + l.base + i``
        self._ssm = None
        self._conv = None
        self._recurrent_bytes_per_chip = 0
        self.completed: list[CaptionResult] = []
        self._decode_tokens = 0
        # dead-work accounting: every decode step runs a lane's FULL slot
        # batch (static shapes); rows without an active slot are wasted.
        # utilization = tokens produced / rows executed
        self._decode_rows = 0
        # per-phase accounting (seconds), all of it through _phase(): self
        # time per phase name, plus the elapsed time of the two roots (the
        # stepping thread's `step`, the prep thread's `prep`). Feeds
        # phase_seconds (stage_timer caption phases, the benchmark's
        # per-layer metrics).
        # _stats_lock guards every counter '+=': the prep thread (prep /
        # vision / prefix-build counters) and the step thread (prefill /
        # decode counters) would otherwise lose updates racing on the same
        # attributes — and prefill_tokens is the acceptance metric.
        #
        # CANONICAL LOCK ORDER (checked by `lint --concurrency`):
        #   _lock (== _work_cv)  ->  _prefix_lock  ->  _stats_lock
        # _stats_lock is innermost and leaf-only: never acquire any other
        # engine lock while holding it.
        self._stats_lock = threading.Lock()
        self._phase_s = dict.fromkeys(_PHASES, 0.0)
        self._phase_elapsed_s = dict.fromkeys(_PHASE_ROOTS, 0.0)
        self._phase_open = threading.local()  # .stack: open phases of one thread
        self._prefill_tokens = 0  # prompt tokens pushed through prefill
        self._vision_encodes = 0
        self._vision_reuses = 0
        # shared-prefix KV cache: LRU over prefix token tuples. Entries are
        # small ([L, Hkv, Tp, Dh] per prefix) next to the lane caches.
        self.enable_prefix_cache = enable_prefix_cache
        self.prefix_cache_size = prefix_cache_size
        self.min_prefix_len = min_prefix_len
        self._prefix_cache: "OrderedDict[tuple, _PrefixEntry]" = OrderedDict()  # guarded-by: _prefix_lock
        # Middle of the canonical order: taken AFTER _lock (engine mutation)
        # and BEFORE _stats_lock, never the other way around — see the order
        # note at _stats_lock above.
        self._prefix_lock = threading.Lock()
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_evictions = 0
        self._prefix_tokens_saved = 0
        # paged-KV accounting (all under _stats_lock): cumulative block
        # reservations per admitted request (the kv_bytes_per_request bench
        # field), the worst-case tokens the slot-row engine would have
        # reserved for the same admissions, shared-prefix block references
        # handed out (the zero-copy successor of insert_prefix dispatches),
        # and copy-on-write tail duplications
        self._requests_admitted = 0
        self._kv_blocks_reserved = 0
        self._kv_private_blocks = 0
        self._kv_worstcase_tokens = 0
        self._prefix_block_refs = 0
        self._kv_cow_copies = 0
        self._kv_blocks_used_peak = 0
        # paged-attention accounting (under _stats_lock): decode steps
        # served by the paged programs (no gathered working set — the
        # structural assertion that the per-step copy is gone), bytes of
        # contiguous KV view the gather programs would have materialized
        # and scattered back for the same calls
        self._paged_kernel_steps = 0
        self._kv_gather_bytes_avoided = 0
        # table entries the decode kernel's loop walks (a row's valid
        # length in pages, summed over the rows of every decode program)
        # over the entries the rows' tables span (rows x blocks a lane):
        # the share of the table the kernel touches
        self._paged_decode_pages_walked = 0
        self._paged_decode_pages_spanned = 0
        # recurrent-store accounting (under _stats_lock): rows held at once,
        # admissions served from a prefix's state snapshot, calls of the
        # decode recurrence (one a state-space layer a decode program)
        self._recurrent_rows_used_peak = 0
        self._prefix_state_snapshots = 0
        self._ssm_decode_calls = 0
        # cross-job fairness: least-recently-admitted owner goes first, and
        # no owner may hold more than its in-flight share of the slots
        # (owner_inflight_cap; None = ceil(total slots / active owners))
        self.owner_inflight_cap = owner_inflight_cap
        self._owner_last_admit: dict[Any, int] = {}
        self._owner_last_prep: dict[Any, int] = {}
        self._admit_seq = 0
        self._prep_seq = 0
        self._interleaved_steps = 0
        self._owner_decode_tokens: dict[Any, int] = {}
        self._owner_requests: dict[Any, int] = {}
        # async prep: a background thread runs vision encode + embedding for
        # waiting requests while the caller's step() loop decodes — prep of
        # request N+1 overlaps decode of request N (the caption stage's
        # prep/decode stall was ~70% of its engine budget). Sync mode
        # (default) preps inline at admission: the round-5 behavior,
        # deterministic step() semantics for tests.
        self.async_prep = async_prep
        self._ready: "deque[_Prepared]" = deque()
        self._prep_inflight: CaptionRequest | None = None
        self._prep_thread: threading.Thread | None = None
        self._prep_stop = False
        # admission linger: when EVERY lane is idle and a burst is still
        # prepping, opening a lane for the first ready request decodes it
        # solo (full-batch rows for one token). Hold admission up to this
        # long so fast preps pack a batch; slow preps (vision-heavy real
        # configs) blow the deadline and overlap decode instead.
        self.admission_linger_s = admission_linger_s
        self._linger_until: float | None = None
        self._built = False
        # One engine is shared by every caption-family stage in a pipeline
        # (weights + KV cache are too big to duplicate). Stages run in
        # separate pool threads, and the jitted prefill/decode donate the
        # cache buffers — concurrent steps would be use-after-donate. This
        # lock serializes all engine mutation; completions are owner-tagged
        # so one stage's run cannot steal another stage's results.
        # OUTERMOST in the canonical order (_lock -> _prefix_lock ->
        # _stats_lock): always acquired first, via `with self._lock` or its
        # condition alias `with self._work_cv`.
        self._lock = threading.RLock()
        # signaled when prep lands a ready request / a follow-up is queued;
        # run_until_complete waits on it instead of spinning when the only
        # outstanding work is an in-flight background prep
        self._work_cv = threading.Condition(self._lock)

    @property
    def params(self) -> Any:
        return self._params

    @params.setter
    def params(self, value: Any) -> None:
        if value is not None and self._param_specs is not None:
            from cosmos_curate_tpu.parallel.sharding import place_partitioned

            value = place_partitioned(self.mesh, value, self._param_specs)
        if value is not None and self._param_dtypes is not None:
            value = jax.tree.map(_stored_as, nn.unbox(value), self._param_dtypes)
        self._params = value
        self._param_bytes_per_chip = _bytes_per_chip(value)

    # read-only aggregate views over the lanes (public slot id = lane.base
    # + lane-local index, unique across lanes)
    @property
    def slots(self) -> dict[int, _Slot]:
        return {l.base + i: s for l in self.lanes for i, s in l.slots.items()}

    @property
    def pending(self) -> dict[int, _PendingPrefill]:
        return {l.base + i: p for l in self.lanes for i, p in l.pending.items()}

    def kv_bytes(self) -> int:
        """Total device bytes the KV block pool pins."""
        if self._pool_k is None:
            return 0
        return self._pool_k.nbytes + self._pool_v.nbytes

    # -- setup ----------------------------------------------------------
    def setup(self, seed: int = 0) -> None:
        cfg = self.cfg
        # Place and narrow everything ONCE, here. The serving model's
        # abstract init says where each leaf lives and in which type: the
        # dtype its layer computes in, and on a mesh the model's
        # nn.with_partitioning annotation (so a loaded checkpoint's plain
        # tree places the same way). The block pools go by their KV-head
        # planes: left on the default device, the head-parallel shard_map
        # would re-distribute the whole pool on every step.
        abstract = _abstract_params(self.model)
        self._param_dtypes = jax.tree.map(lambda x: x.dtype, nn.unbox(abstract))
        # seeded parameters are the float32 model's, narrowed like any other
        # tree: a seeded engine serves what handing in ``VLM(cfg).init`` would
        seeded = partial(_init_params, self.model.clone(param_dtype=jnp.float32), seed)
        pool_sharding = None
        if self.mesh is None:
            self.params = seeded() if self._params is None else self._params
        else:
            from jax.sharding import PartitionSpec as P

            from cosmos_curate_tpu.parallel.axes import MODEL
            from cosmos_curate_tpu.parallel.sharding import spec_sharding

            self._param_specs = nn.get_partition_spec(abstract)
            if self._params is None:
                # made split: a flavor served over a mesh need not fit one chip
                shardings = jax.tree.map(
                    lambda spec: spec_sharding(self.mesh, spec),
                    self._param_specs,
                    is_leaf=lambda x: isinstance(x, P),
                )
                self._params = jax.jit(
                    lambda: nn.unbox(seeded()), out_shardings=shardings
                )()
            self.params = self._params  # the setter places and narrows
            pool_sharding = spec_sharding(self.mesh, P(None, None, MODEL, None, None))
        self._pool_k, self._pool_v = init_block_pool(
            cfg, self.kv_pool_blocks, self.block_size, sharding=pool_sharding
        )
        self._kv_pool_bytes_per_chip = _bytes_per_chip((self._pool_k, self._pool_v))
        if self._recurrent:
            self._ssm, self._conv = init_recurrent_store(
                cfg, 1 + sum(l.n_slots for l in self.lanes), dtype=self.model.dtype
            )
            self._recurrent_bytes_per_chip = _bytes_per_chip((self._ssm, self._conv))

        model = self.model
        bs = self.block_size

        @jax.jit
        def encode_images(params, frames_u8):
            return model.apply(params, frames_u8, method=model.encode_images)

        @jax.jit
        def embed_tokens(params, ids):
            return model.apply(params, ids, method=model.embed_tokens)

        mrope = cfg.mrope_section is not None
        # qwen3 deepstack: number of LM layers receiving visual injections
        self._ds_levels = (
            len(cfg.qwen_vision.deepstack_indexes)
            if cfg.vision_variant == "qwen3" and cfg.qwen_vision is not None
            else 0
        )

        @partial(jax.jit, donate_argnums=(1, 2))
        def prefill_batch(params, pool_k, pool_v, tables, embeds, write_index, t_valid, rope_pos, ds=None):
            """Batched prefill through the block tables (replaces the
            round-1 one-request-at-a-time admission — the reference leans
            on vLLM's batched prefill, vllm_interface.py:543). embeds:
            [N, Tb, D] (bucket- or chunk-padded); tables: [N, nbl] block
            ids; write_index/t_valid: [N]; rope_pos: [N, Tb] (or [N, Tb, 3]
            m-rope). write_index > 0 rows are later chunks of a chunked
            prefill, or shared-prefix suffixes starting past their cached
            blocks. Gathers each row's blocks into a contiguous view (the
            slot-row shapes — byte-identical math), writes every row's
            cells in one program, scatters the blocks back, and returns
            each row's logits at its last valid position: [N, V]."""
            ck, cv = gather_block_views(pool_k, pool_v, tables)
            logits, nk, nv = model.apply(
                params,
                embeds,
                ck,
                cv,
                rope_pos,
                write_index,
                write_index + t_valid,
                deepstack=ds,
                logits_at=t_valid - 1,
            )
            pool_k, pool_v = scatter_block_views(pool_k, pool_v, tables, nk, nv)
            return logits[:, 0], pool_k, pool_v

        @partial(jax.jit, donate_argnums=(1, 2))
        def decode_step(params, pool_k, pool_v, tables, tokens, positions, rope_positions):
            """tokens/positions/rope_positions: [n_slots]; one token per
            slot. positions index the gathered view; rope_positions are the
            rotary positions (identical unless m-rope lagged them at
            prefill). tables: [n_slots, nbl] — idle rows point at the
            garbage block, shared prefix blocks scatter back unchanged (the
            paged_kv module docstring's duplicate-write invariant).

            Greedy argmax happens ON DEVICE for the whole batch — per-slot
            host argmaxes were the decode loop's bottleneck (one device
            sync per slot per token)."""
            embeds = model.apply(params, tokens[:, None], method=model.embed_tokens)
            rp = rope_positions[:, None]
            if mrope:
                # decode is always text: all three components equal
                rp = jnp.broadcast_to(rp[..., None], (*rp.shape, 3))
            ck, cv = gather_block_views(pool_k, pool_v, tables)
            logits, nk, nv = model.apply(
                params,
                embeds,
                ck,
                cv,
                rp,
                positions,
                positions + 1,
            )
            pool_k, pool_v = scatter_block_views(pool_k, pool_v, tables, nk, nv)
            step_logits = logits[:, 0]
            greedy = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
            return greedy, step_logits, pool_k, pool_v

        @partial(jax.jit, donate_argnums=(1, 2))
        def prefill_batch_paged(
            params, pool_k, pool_v, tables, embeds, write_index, t_valid, rope_pos, ds=None
        ):
            """prefill_batch without the working set: the model's paged
            forward scatters each row's chunk through its block table and
            attends straight out of the pool (ops/paged_attention.py) — no
            gather_block_views, no scatter_block_views. Same arguments,
            same returns, bit-equal logits on the reference path."""
            logits, pool_k, pool_v = model.apply(
                params,
                embeds,
                pool_k,
                pool_v,
                rope_pos,
                write_index,
                write_index + t_valid,
                tables,
                deepstack=ds,
                logits_at=t_valid - 1,
                method=model.paged_forward,
            )
            return logits[:, 0], pool_k, pool_v

        @partial(jax.jit, donate_argnums=(1, 2))
        def decode_step_paged(params, pool_k, pool_v, tables, tokens, positions, rope_positions):
            """decode_step without the working set — see prefill_batch_paged.
            The per-step O(context) gathered copy and its scatter-back are
            gone; each row writes exactly ONE pool cell."""
            embeds = model.apply(params, tokens[:, None], method=model.embed_tokens)
            rp = rope_positions[:, None]
            if mrope:
                # decode is always text: all three components equal
                rp = jnp.broadcast_to(rp[..., None], (*rp.shape, 3))
            logits, pool_k, pool_v = model.apply(
                params,
                embeds,
                pool_k,
                pool_v,
                rp,
                positions,
                positions + 1,
                tables,
                method=model.paged_forward,
            )
            step_logits = logits[:, 0]
            greedy = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
            return greedy, step_logits, pool_k, pool_v

        @jax.jit
        def prefix_prefill(params, embeds, rope_pos, t_valid):
            """Prefill ONE text prefix into a scratch cache and return its
            K/V block [L, Hkv, Sp, Dh] (sliced to the true length by the
            caller). embeds: [1, Sp, D] (pow2-padded); t_valid: scalar.
            Compiled once per Sp bucket — prefixes are per (flavor,
            prompt_variant), so this runs once per variant, not per
            request."""
            ck, cv = init_cache(cfg, 1, length=embeds.shape[1])
            _logits, nk, nv = model.apply(
                params,
                embeds,
                ck,
                cv,
                rope_pos,
                jnp.zeros((1,), jnp.int32),
                jnp.full((1,), t_valid, jnp.int32),
            )
            return nk[:, 0], nv[:, 0]

        @partial(jax.jit, donate_argnums=(0, 1))
        def write_prefix_blocks(pool_k, pool_v, pk, pv, ids):
            """Store one freshly built prefix K/V ([L, Hkv, Tp, Dh]) into
            its allocated pool blocks ``ids`` ([nb]) — the ONE device write
            per prefix build; admitted requests then reference these blocks
            with zero further copies. Compiled once per Tp (prefixes are
            per (flavor, prompt_variant), so this runs once per variant)."""
            l, hk, tp, dh = pk.shape
            pad = ((0, 0), (0, 0), (0, ids.shape[0] * bs - tp), (0, 0))

            def blocks(x, dtype):  # -> [L, nb, Hkv, bs, Dh]
                return jnp.pad(x.astype(dtype), pad).reshape(l, hk, -1, bs, dh).swapaxes(1, 2)

            pool_k = pool_k.at[:, ids].set(blocks(pk, pool_k.dtype))
            pool_v = pool_v.at[:, ids].set(blocks(pv, pool_v.dtype))
            return pool_k, pool_v

        @partial(jax.jit, donate_argnums=(0, 1))
        def copy_blocks(pool_k, pool_v, src, dst):
            """Copy-on-write: duplicate blocks ``src`` into ``dst`` ([m]
            each) — used ONLY when a request must extend a partially-filled
            shared prefix tail block (one block, not the whole prefix)."""
            pool_k = pool_k.at[:, dst].set(pool_k[:, src])
            pool_v = pool_v.at[:, dst].set(pool_v[:, src])
            return pool_k, pool_v

        self._host_rng = np.random.default_rng(seed)
        self._encode_images = encode_images
        self._embed_tokens = embed_tokens
        self._prefill_batch = prefill_batch_paged if self._use_paged else prefill_batch
        self._decode = decode_step_paged if self._use_paged else decode_step
        self._prefix_prefill = prefix_prefill
        self._write_prefix_blocks = write_prefix_blocks
        self._copy_blocks = copy_blocks
        if self._recurrent:
            self._build_recurrent_programs()
        self._built = True
        if self.async_prep:
            # requests may already be waiting (queued before setup)
            with self._work_cv:
                self._start_prep_thread()
                self._work_cv.notify_all()

    def _build_recurrent_programs(self) -> None:
        """A hybrid's programs: the prefill and decode programs with the
        recurrent store riding along (the store's arguments come after the
        others', its two arrays after the pools in what is returned), the
        shared prefix's build with its state snapshot, and the two ways a
        slot's row starts. They take the place of setup()'s."""
        cfg, model, use_paged = self.cfg, self.model, self._use_paged
        if cfg.mrope_section is not None or self._ds_levels:
            raise ValueError("a hybrid flavor with m-rope or deepstack has no program here")

        def chunk_forward(params, pool_k, pool_v, tables, embeds, rope, write_index, kv_len, **kw):
            """The forward of both: as in setup()'s four programs, the
            recurrent store riding along (``recurrent=`` in ``kw``)."""
            if use_paged:
                return model.apply(
                    params, embeds, pool_k, pool_v, rope, write_index, kv_len, tables,
                    method=model.paged_forward, **kw,
                )
            ck, cv = gather_block_views(pool_k, pool_v, tables)
            logits, nk, nv, ssm, conv = model.apply(
                params, embeds, ck, cv, rope, write_index, kv_len, **kw
            )
            pool_k, pool_v = scatter_block_views(pool_k, pool_v, tables, nk, nv)
            return logits, pool_k, pool_v, ssm, conv

        @partial(jax.jit, donate_argnums=(1, 2, 9, 10))
        def prefill_batch_recurrent(
            params, pool_k, pool_v, tables, embeds, write_index, t_valid, rope_pos, ds, ssm, conv, rows
        ):
            """prefill_batch(_paged) for a hybrid: ``rows`` [N] are the rows'
            rows of the recurrent store (ssm, conv), whose states advance over
            the ``t_valid`` leading positions and no further."""
            logits, pool_k, pool_v, ssm, conv = chunk_forward(
                params, pool_k, pool_v, tables, embeds, rope_pos, write_index,
                write_index + t_valid, deepstack=ds, logits_at=t_valid - 1,
                recurrent=(ssm, conv, rows, t_valid),
            )
            return logits[:, 0], pool_k, pool_v, ssm, conv

        @partial(jax.jit, donate_argnums=(1, 2, 7, 8))
        def decode_step_recurrent(
            params, pool_k, pool_v, tables, tokens, positions, rope_positions, ssm, conv, rows
        ):
            """decode_step(_paged) for a hybrid: idle rows carry store row 0,
            the garbage row, and do not advance it either."""
            embeds = model.apply(params, tokens[:, None], method=model.embed_tokens)
            logits, pool_k, pool_v, ssm, conv = chunk_forward(
                params, pool_k, pool_v, tables, embeds, rope_positions[:, None], positions,
                positions + 1, recurrent=(ssm, conv, rows, (rows > 0).astype(jnp.int32)),
            )
            step_logits = logits[:, 0]
            greedy = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
            return greedy, step_logits, pool_k, pool_v, ssm, conv

        @jax.jit
        def prefix_prefill_recurrent(params, embeds, rope_pos, t_valid):
            """prefix_prefill for a hybrid: the prefix's K/V and, from a zero
            state, the state-space layers' state after exactly ``t_valid``
            tokens (the padding masked): what a request that shares the
            prefix starts from."""
            ck, cv = init_cache(cfg, 1, length=embeds.shape[1])
            ssm, conv = init_recurrent_store(cfg, 1, dtype=model.dtype)
            valid = jnp.full((1,), t_valid, jnp.int32)
            _logits, nk, nv, ssm, conv = model.apply(
                params, embeds, ck, cv, rope_pos, jnp.zeros((1,), jnp.int32), valid,
                recurrent=(ssm, conv, jnp.zeros((1,), jnp.int32), valid),
            )
            return nk[:, 0], nv[:, 0], ssm[:, 0], conv[:, 0]

        @partial(jax.jit, donate_argnums=(0, 1))
        def set_state_row(ssm, conv, row, snap_ssm, snap_conv):
            """A slot's row of the recurrent store starts from a prefix's
            snapshot: the one device copy of a hybrid's shared admission."""
            return ssm.at[:, row].set(snap_ssm), conv.at[:, row].set(snap_conv)

        @partial(jax.jit, donate_argnums=(0, 1))
        def zero_state_row(ssm, conv, row):
            """…or from zeros, never from the last tenant's state."""
            return ssm.at[:, row].set(0.0), conv.at[:, row].set(0)

        self._prefill_batch = prefill_batch_recurrent
        self._decode = decode_step_recurrent
        self._prefix_prefill = prefix_prefill_recurrent
        self._set_state_row = set_state_row
        self._zero_state_row = zero_state_row

    # -- public API -----------------------------------------------------
    @property
    def _max_len(self) -> int:
        return self.lanes[-1].length  # lanes are sorted by length

    def add_request(self, request: CaptionRequest, owner: Any = None) -> None:
        budget = self._max_len - request.sampling.max_new_tokens - 1
        if budget <= 0:
            raise ValueError(
                f"max_new_tokens={request.sampling.max_new_tokens} leaves no "
                f"prompt budget in the longest KV lane ({self._max_len})"
            )
        if any(not s for s in request.sampling.stop):
            # '' in tail is always True — an empty stop string would finish
            # the request after one token with empty text
            raise ValueError("stop strings must be non-empty")
        if request.owner is None:
            request.owner = owner if owner is not None else threading.get_ident()
        with self._work_cv:
            self.waiting.append(request)
            # only a BUILT engine may prep (the thread calls the jitted
            # encoders setup() creates); requests queued before setup()
            # wait — setup() starts the thread for them, and the sync
            # step() path keeps raising 'call setup() first'
            if self.async_prep and self._built:
                self._start_prep_thread()
            self._work_cv.notify_all()

    def _prep_requests(self) -> list[CaptionRequest]:
        """Requests past ``waiting`` but not yet admitted (prepared or
        mid-prep in the background thread). Lock held by caller."""
        reqs = [p.request for p in self._ready]
        if self._prep_inflight is not None:
            reqs.append(self._prep_inflight)
        return reqs

    def has_work(self, owner: Any = None) -> bool:
        with self._lock:
            if owner is None:
                return bool(
                    self.waiting or self._prep_requests() or self.slots or self.pending
                )
            return (
                any(r.owner == owner for r in self.waiting)
                or any(r.owner == owner for r in self._prep_requests())
                or any(s.request.owner == owner for s in self.slots.values())
                or any(p.request.owner == owner for p in self.pending.values())
            )

    def run_until_complete(self, owner: Any = None) -> list[CaptionResult]:
        """Drive the engine until this caller's requests are done.

        ``owner`` defaults to the calling thread's ident — the same default
        ``add_request`` tags requests with — so the existing
        add-then-run-in-one-thread usage is unchanged. Requests queued by
        other owners still ride along in the continuous batch (free
        throughput), but their completions stay queued for *their*
        ``run_until_complete``.
        """
        if owner is None:
            owner = threading.get_ident()
        while True:
            # Lock per step, not across the drain: another stage's
            # add_request must be able to slip in between decode steps so
            # its requests actually join the continuous batch.
            with self._work_cv:
                if not self.has_work(owner):
                    mine = [r for r in self.completed if r.owner == owner]
                    self.completed = [r for r in self.completed if r.owner != owner]
                    # keep THIS owner's entries: the caller reads its
                    # per-owner accounting deltas right after this returns
                    self._prune_owner_state(keep=owner)
                    return mine
                steppable = (
                    bool(self._ready)
                    or (not self.async_prep and bool(self.waiting))
                    or any(l.slots or l.pending for l in self.lanes)
                )
                if not steppable or self._should_linger():
                    # only background prep is outstanding (or admission is
                    # lingering for the burst's prep to pack a batch) —
                    # sleep until it lands instead of spinning empty steps
                    self._work_cv.wait(0.02)
                    continue
                self.step()

    @contextlib.contextmanager
    def _phase(self, name: str):
        """Time one phase, once, on both clocks: a ``TraceAnnotation``
        ``engine.<name>`` (inert unless a profiler session runs) and the
        host's monotonic clock. The counter gets the phase's SELF time —
        elapsed less the elapsed of the phases nested in it on this thread —
        so the phases under a root add up to the root's elapsed time."""
        try:
            stack = self._phase_open.stack
        except AttributeError:
            stack = self._phase_open.stack = []
        stack.append(0.0)  # seconds of the phases nested in this one
        with jax.profiler.TraceAnnotation("engine." + name):
            t0 = time.monotonic()
            try:
                yield
            finally:
                elapsed = time.monotonic() - t0
                nested = stack.pop()
                if stack:
                    stack[-1] += elapsed
                with self._stats_lock:
                    self._phase_s[name] += elapsed - nested
                    if name in self._phase_elapsed_s:
                        self._phase_elapsed_s[name] += elapsed

    @property
    def _decode_time(self) -> float:
        # the decode program call + host sync: same site, both attention paths
        return self._phase_s["decode_dispatch"] + self._phase_s["decode_wait"]

    @property
    def _prefill_time(self) -> float:
        # prefill programs (incl. shared-prefix builds), their host sync and
        # the first-token sampling that ends a prompt
        p = self._phase_s
        return p["prefill_dispatch"] + p["prefill_wait"] + p["prefill_sample"]

    @property
    def tokens_per_second(self) -> float:
        return self._decode_tokens / self._decode_time if self._decode_time > 0 else 0.0

    @property
    def decode_tokens(self) -> int:
        return self._decode_tokens

    @property
    def decode_time_s(self) -> float:
        return self._decode_time

    @property
    def prefill_tokens(self) -> int:
        """Prompt tokens pushed through prefill programs (bucket, chunk,
        and shared-prefix builds; cache-inserted prefix copies are NOT
        prefill). With the shared-prefix cache, n requests sharing a
        Tp-token prefix prefill Tp fewer tokens each after the first."""
        return self._prefill_tokens

    @property
    def prefix_cache_hits(self) -> int:
        return self._prefix_hits

    @property
    def prefix_cache_misses(self) -> int:
        return self._prefix_misses

    @property
    def prefix_cache_evictions(self) -> int:
        return self._prefix_evictions

    @property
    def prefix_tokens_saved(self) -> int:
        """Prefill tokens NOT recomputed thanks to shared-prefix hits."""
        return self._prefix_tokens_saved

    @property
    def vision_encodes(self) -> int:
        return self._vision_encodes

    @property
    def vision_reuses(self) -> int:
        return self._vision_reuses

    # -- paged-KV occupancy and cross-job accounting --------------------
    @property
    def kv_blocks_total(self) -> int:
        """Allocatable pool blocks (admission limit; garbage block excluded)."""
        return self._allocator.capacity

    @property
    def kv_blocks_used(self) -> int:
        return self._allocator.used_blocks

    @property
    def kv_blocks_used_peak(self) -> int:
        """High-water pool occupancy since the last reset_stats()."""
        return self._kv_blocks_used_peak

    @property
    def kv_block_bytes(self) -> int:
        """Device bytes one block pins (K + V across all layers)."""
        cfg = self.cfg
        # bf16 pool: 2 bytes/element, x2 for K and V
        return 2 * 2 * len(cfg.kv_layers) * self.block_size * cfg.n_kv_heads * cfg.head_dim

    @property
    def prefix_block_refs(self) -> int:
        """Cumulative shared-prefix block references handed to admitted
        requests — each one is a whole block of prefix K/V served with ZERO
        device copies (the metric that replaced insert_prefix dispatches)."""
        return self._prefix_block_refs

    @property
    def prefix_copy_dispatches(self) -> int:
        """Whole-prefix device-copy dispatches at admission. Structurally
        zero since the paged pool: admitted requests REFERENCE prefix
        blocks through their tables instead of copying them into slot rows
        (the round-7 jitted insert_prefix path is deleted). Kept as an
        explicit counter so the bench/smoke contract 'zero prefix
        device-copy dispatches' is asserted, not assumed."""
        return 0

    @property
    def kv_cow_copies(self) -> int:
        """Copy-on-write duplications of a partially-filled shared prefix
        tail block (ONE block each — not a prefix copy)."""
        return self._kv_cow_copies

    # -- paged-attention accounting --------------------------------------
    def _gather_view_bytes(self, rows: int, length: int) -> int:
        """Bytes of contiguous KV working set the gather programs would
        materialize for one program call over ``rows`` block tables of
        ``length`` gathered positions (K + V, all layers)."""
        cfg = self.cfg
        itemsize = 2 if self._pool_k is None else self._pool_k.dtype.itemsize
        return 2 * len(cfg.kv_layers) * rows * length * cfg.n_kv_heads * cfg.head_dim * itemsize

    @property
    def paged_kernel_steps(self) -> int:
        """Decode steps served by the paged-attention programs — attention
        read the pool through the block table; NO contiguous working-set
        copy was built or scattered back. Structurally zero under
        ``paged_attention="gather"``; > 0 is the smoke contract that the
        kernel path was actually taken."""
        return self._paged_kernel_steps

    @property
    def kv_gather_bytes_avoided(self) -> int:
        """Cumulative bytes of per-call contiguous KV working set the
        gather programs would have materialized (and scattered back) for
        the prefill/decode calls the paged path served instead."""
        return self._kv_gather_bytes_avoided

    @property
    def decode_attention_s(self) -> float:
        """Tight wall time of decode program calls + host sync, identical
        measurement site for the paged and gather paths — the
        kernel-vs-gather comparison the bench caption_attention section
        reports. (Also contained in phase decode_s, which this mirrors at
        the program-call granularity.)"""
        return self._decode_time

    @property
    def mesh_geometry(self) -> tuple:
        """Hashable (axis, extent) view of the serving mesh (empty when
        unsharded) — part of the SharedCaptionEngine key so differently
        sharded engines never collide."""
        if self.mesh is None:
            return ()
        return tuple(
            (str(name), int(self.mesh.shape[name])) for name in self.mesh.axis_names
        )

    def stats(self) -> dict:
        """One-call snapshot of the serving counters (bench row / smoke
        surface). Includes both sides of the block-size fallback: the
        constructor-requested size and the gcd-shrunk divisor actually
        used, so cross-run bench comparisons can detect a silent shrink."""
        with self._stats_lock:
            return {
                "paged_attention": self.paged_attention,
                "mesh_geometry": self.mesh_geometry,
                # what ONE chip of the mesh holds: a quarter of what is
                # partitioned over model=4, the whole of what is repeated
                "param_bytes_per_chip": self._param_bytes_per_chip,
                "kv_pool_bytes_per_chip": self._kv_pool_bytes_per_chip,
                "kv_block_size": self.block_size,
                "kv_block_size_requested": self.block_size_requested,
                "paged_kernel_steps": self._paged_kernel_steps,
                "paged_decode_pages_walked": self._paged_decode_pages_walked,
                "paged_decode_pages_spanned": self._paged_decode_pages_spanned,
                "kv_gather_bytes_avoided": self._kv_gather_bytes_avoided,
                "decode_attention_s": self._decode_time,
                "decode_tokens": self._decode_tokens,
                "decode_s": self._decode_time,
                "prefill_tokens": self._prefill_tokens,
                "prefill_s": self._prefill_time,
                "kv_blocks_total": self._allocator.capacity,
                "kv_blocks_used": self._allocator.used_blocks,
                "kv_blocks_used_peak": self._kv_blocks_used_peak,
                # the second kind of state (all zero without state-space layers)
                "recurrent_state_bytes_per_chip": self._recurrent_bytes_per_chip,
                "recurrent_rows_total": sum(l.n_slots for l in self.lanes) if self._recurrent else 0,
                "recurrent_rows_used_peak": self._recurrent_rows_used_peak,
                "prefix_state_snapshots": self._prefix_state_snapshots,
                "ssm_decode_calls": self._ssm_decode_calls,
            }

    @property
    def requests_admitted(self) -> int:
        return self._requests_admitted

    @property
    def kv_bytes_reserved_per_request(self) -> float:
        """Mean KV bytes reserved per admitted request (shared references
        counted at full block size — still strictly below the old
        worst-case row whenever prompt + max_new undershoots the lane)."""
        if not self._requests_admitted:
            return 0.0
        return self._kv_blocks_reserved * self.kv_block_bytes / self._requests_admitted

    @property
    def kv_bytes_worstcase_per_request(self) -> float:
        """What the slot-row engine reserved for the same admissions: each
        routed lane's FULL row, regardless of actual request length."""
        if not self._requests_admitted:
            return 0.0
        token_bytes = self.kv_block_bytes / self.block_size
        return self._kv_worstcase_tokens * token_bytes / self._requests_admitted

    @property
    def interleaved_decode_steps(self) -> int:
        """Steps whose active slots spanned 2+ owners — the cross-job
        continuous-batching signal (two pipelines decoding in ONE batch)."""
        return self._interleaved_steps

    @property
    def owner_decode_tokens(self) -> dict:
        with self._stats_lock:
            return dict(self._owner_decode_tokens)

    def owner_stats(self) -> dict:
        """Per-owner queue/in-flight/served gauges, keyed by str(owner) —
        the cross-job accounting surface (metrics exporter + run report)."""
        with self._lock:
            out: dict[str, dict] = {}

            def bucket(owner):
                return out.setdefault(
                    str(owner),
                    {"waiting": 0, "ready": 0, "inflight": 0,
                     "decode_tokens": 0, "requests": 0},
                )

            for r in self.waiting:
                bucket(r.owner)["waiting"] += 1
            if self._prep_inflight is not None:
                bucket(self._prep_inflight.owner)["waiting"] += 1
            for p in self._ready:
                bucket(p.request.owner)["ready"] += 1
            for lane in self.lanes:
                for s in lane.slots.values():
                    bucket(s.request.owner)["inflight"] += 1
                for p in lane.pending.values():
                    bucket(p.request.owner)["inflight"] += 1
            with self._stats_lock:
                for owner, n in self._owner_decode_tokens.items():
                    bucket(owner)["decode_tokens"] = n
                for owner, n in self._owner_requests.items():
                    bucket(owner)["requests"] = n
            return out

    @property
    def phase_seconds(self) -> dict[str, float]:
        """Cumulative per-phase seconds. ``prep`` (host prep incl. the
        vision share), ``vision_encode`` (vision-tower subset of prep),
        ``prefill`` (prefill programs, host sync, first-token sampling) and
        ``decode`` (decode programs + host sync) keep their meaning and are
        derived from the phases of ``_phase()``: ``step_s`` is the time
        inside ``step()``, and ``lock_wait``, ``admit``, ``prefill_*`` and
        ``decode_*`` (``build``, ``dispatch``, ``wait``, ``sample``) and
        ``step_other`` partition it (with ``prep_other`` and
        ``vision_encode`` where prep runs inline; a shared-prefix build on
        the prep thread adds to ``prefill_*`` from outside ``step()``).
        Window minus ``step_s`` is the caller's stall; ``*_wait`` is the
        stepping thread blocked on the device."""
        with self._stats_lock:
            out = {f"{k}_s": v for k, v in self._phase_s.items() if k not in _PHASE_ROOTS}
            for root in _PHASE_ROOTS:
                out[f"{root}_s"] = self._phase_elapsed_s[root]
                out[f"{root}_other_s"] = self._phase_s[root]
            out["prefill_s"] = self._prefill_time
            out["decode_s"] = self._decode_time
        return out

    def reset_stats(self) -> None:
        """Zero the throughput counters (e.g. after benchmark warmup) —
        the counter set and its reset stay in one place. Shared-prefix
        cache CONTENTS survive (only the hit/miss counters reset)."""
        with self._stats_lock:
            self._decode_tokens = 0
            self._decode_rows = 0
            self._phase_s = dict.fromkeys(_PHASES, 0.0)
            self._phase_elapsed_s = dict.fromkeys(_PHASE_ROOTS, 0.0)
            self._prefill_tokens = 0
            self._vision_encodes = 0
            self._vision_reuses = 0
            self._prefix_hits = 0
            self._prefix_misses = 0
            self._prefix_evictions = 0
            self._prefix_tokens_saved = 0
            self._requests_admitted = 0
            self._kv_blocks_reserved = 0
            self._kv_private_blocks = 0
            self._kv_worstcase_tokens = 0
            self._prefix_block_refs = 0
            self._kv_cow_copies = 0
            self._paged_kernel_steps = 0
            self._paged_decode_pages_walked = 0
            self._paged_decode_pages_spanned = 0
            self._kv_gather_bytes_avoided = 0
            self._kv_blocks_used_peak = self._allocator.used_blocks
            self._recurrent_rows_used_peak = (
                sum(len(l.claims) for l in self.lanes) if self._recurrent else 0
            )
            self._prefix_state_snapshots = 0
            self._ssm_decode_calls = 0
            self._interleaved_steps = 0
            self._owner_decode_tokens.clear()
            self._owner_requests.clear()

    def clear_prefix_cache(self) -> None:
        """Drop every cached prefix and release the LRU's block references.
        Blocks still mapped by in-flight slots stay allocated until those
        slots release (deferred free); after a full drain the pool reads
        fully free."""
        with self._lock, self._prefix_lock:
            for entry in self._prefix_cache.values():
                self._allocator.decref(entry.blocks)
            self._prefix_cache.clear()

    def shutdown(self) -> None:
        """Stop the background prep thread and release the prefix cache's
        block references (tests assert the pool is fully free after a
        drained shutdown; long-lived engines just let the daemon thread die
        with the process)."""
        self.clear_prefix_cache()
        with self._work_cv:
            self._prep_stop = True
            self._work_cv.notify_all()
        t = self._prep_thread
        if t is not None:
            t.join(timeout=5)
            if t.is_alive():
                # mid-encode and past the grace: leave the stop flag SET so
                # the thread exits at its next loop check instead of
                # resuming work beside a future replacement thread
                logger.warning("caption prep thread still running after 5s grace")
                return
            self._prep_thread = None
        self._prep_stop = False

    @property
    def decode_slot_utilization(self) -> float:
        """Fraction of executed decode rows that produced a token (the
        static-batch dead-work measure; lanes raise it by keeping batches
        near their occupancy)."""
        return self._decode_tokens / self._decode_rows if self._decode_rows else 0.0

    # -- engine internals ----------------------------------------------
    def step(self) -> None:
        """Admit ready requests, advance chunked prefills, then one decode
        step per active lane — so a long prompt never blocks the in-flight
        batch's decode for more than a chunk's latency.

        Chunk admission is tuned against decode occupancy (the live signal
        behind ``decode_slot_utilization``): chunking exists to protect
        in-flight decode from a long prefill stall, so while NO lane is
        decoding, pending chunks run back to back instead of one per step —
        an idle engine prefills at full speed."""
        if not self._built:
            raise RuntimeError("call setup() first")
        with self._phase("step"), contextlib.ExitStack() as waiting:
            waiting.enter_context(self._phase("lock_wait"))
            with self._work_cv:
                waiting.close()  # the lock is ours: lock_wait ends here
                with self._phase("admit"):
                    self._admit()
                # cross-job signal: this step's active slots span 2+ owners
                # — several jobs are decoding in ONE continuous batch
                step_owners = {
                    s.request.owner for l in self.lanes for s in l.slots.values()
                }
                if len(step_owners) > 1:
                    with self._stats_lock:
                        self._interleaved_steps += 1
                for lane in self.lanes:
                    if lane.pending:
                        self._prefill_chunk_step(lane)
                        while lane.pending and not any(l.slots for l in self.lanes):
                            self._prefill_chunk_step(lane)
                    if lane.slots:
                        self._decode_once(lane)
                self._work_cv.notify_all()  # ready-queue space may have freed

    # -- request prep (sync inline, or the background overlap thread) ---
    def _start_prep_thread(self) -> None:
        if self._prep_thread is not None and self._prep_thread.is_alive():
            return
        # a shutdown() whose join grace expired leaves _prep_stop latched;
        # a fresh thread must not read the stale flag and die instantly
        self._prep_stop = False
        self._prep_thread = threading.Thread(
            target=self._prep_loop, name="caption-prep", daemon=True
        )
        self._prep_thread.start()

    def _prep_ahead_limit(self) -> int:
        # bound host memory for prepared-but-unadmitted embeds: enough to
        # keep every slot fed one wave ahead, no more
        return max(2, 2 * sum(l.n_slots for l in self.lanes))

    def _prep_loop(self) -> None:
        """Background prep: vision encode + token embedding for waiting
        requests, FIFO, overlapping the caller's decode loop. Device
        compute runs OUTSIDE the engine lock — the lock only guards queue
        hops, so a decode step never waits on a vision encode and vice
        versa (device-side serialization is the hardware's business)."""
        while True:
            with self._work_cv:
                while not self._prep_stop and (
                    not self.waiting or len(self._ready) >= self._prep_ahead_limit()
                ):
                    self._work_cv.wait(0.1)
                if self._prep_stop:
                    return
                req = self._pop_waiting_fair()
                self._prep_inflight = req
            prep = self._safe_prepare(req)  # no lock: overlaps decode
            with self._work_cv:
                self._prep_inflight = None
                if prep is not None:
                    self._ready.append(prep)
                self._work_cv.notify_all()

    # every stage instance mints a fresh owner tag, so a long-lived shared
    # engine would otherwise accumulate owner-keyed state forever (and mint
    # unbounded per-owner metric series)
    _OWNER_STATE_CAP = 256

    # holds-lock: _lock
    def _prune_owner_state(self, keep: Any = None) -> None:
        """Bound the owner-keyed maps: once past the cap, drop entries for
        owners with no live work. ``keep`` protects the owner whose drive
        just completed — its stage reads the accounting deltas right after
        (pruning it first would hand the stage a zero/negative delta).
        Lock held by caller."""
        maps = (
            self._owner_last_admit,
            self._owner_last_prep,
            self._owner_decode_tokens,
            self._owner_requests,
        )
        if all(len(m) <= self._OWNER_STATE_CAP for m in maps):
            return
        live = {r.owner for r in self.waiting}
        live.update(p.request.owner for p in self._ready)
        if self._prep_inflight is not None:
            live.add(self._prep_inflight.owner)
        for lane in self.lanes:
            live.update(s.request.owner for s in lane.slots.values())
            live.update(p.request.owner for p in lane.pending.values())
        live.update(r.owner for r in self.completed)
        if keep is not None:
            live.add(keep)
        with self._stats_lock:
            for m in maps:
                if len(m) > self._OWNER_STATE_CAP:
                    for owner in [o for o in m if o not in live]:
                        del m[owner]

    @staticmethod
    def _fair_head(owners_in_order, last_map: dict, inflight: dict, cap: float):
        """(owner, index) of the next fair pick: FIFO within an owner,
        least-recently-served owner first, owners at ``cap`` in-flight
        skipped. ``owners_in_order`` yields each queue item's owner in
        queue order. Returns None when every queued owner is capped."""
        heads: "OrderedDict[Any, int]" = OrderedDict()
        for i, owner in enumerate(owners_in_order):
            if owner not in heads:
                heads[owner] = i
        eligible = [(o, i) for o, i in heads.items() if inflight.get(o, 0) < cap]
        if not eligible:
            return None
        return min(eligible, key=lambda kv: (last_map.get(kv[0], -1), kv[1]))

    def _pop_waiting_fair(self) -> CaptionRequest:
        """Next waiting request: one pipeline's burst cannot push another
        pipeline's requests out of the prep pipeline (cross-job fairness
        starts at prep, since only prepped requests can be admitted).
        Single-owner queues reduce to plain FIFO. Lock held by caller."""
        owner, idx = self._fair_head(
            (r.owner for r in self.waiting), self._owner_last_prep, {}, float("inf")
        )
        self._owner_last_prep[owner] = self._prep_seq
        self._prep_seq += 1
        return self.waiting.pop(idx)

    def _safe_prepare(self, req: CaptionRequest) -> "_Prepared | None":
        with self._phase("prep"):
            try:
                return self._prepare(req)
            except Exception:
                logger.exception("prefill prep failed for %s; dropping", req.request_id)
                return None

    def _should_linger(self) -> bool:
        """True while admission should hold for the in-flight burst's prep:
        every lane idle, ready requests waiting, more of the burst still
        prepping, and the linger deadline not yet blown. Lock held by
        caller."""
        if not self.async_prep or self.admission_linger_s <= 0:
            return False
        if not self._ready or any(l.slots or l.pending for l in self.lanes):
            self._linger_until = None
            return False
        incoming = len(self.waiting) + (1 if self._prep_inflight is not None else 0)
        free = sum(l.n_slots for l in self.lanes)
        if not incoming or len(self._ready) >= free:
            self._linger_until = None
            return False
        now = time.monotonic()
        if self._linger_until is None:
            self._linger_until = now + self.admission_linger_s
        return now < self._linger_until

    def _owner_cap(self, inflight: dict) -> int:
        """Per-owner in-flight slot cap: an explicit ``owner_inflight_cap``,
        or the fair share of the slot budget across owners that currently
        have work. A single owner gets the whole engine (admission-order
        parity with the single-job engine)."""
        if self.owner_inflight_cap is not None:
            return max(1, self.owner_inflight_cap)
        owners = set(inflight)
        owners.update(r.owner for r in self.waiting)
        owners.update(p.request.owner for p in self._ready)
        if self._prep_inflight is not None:
            owners.add(self._prep_inflight.owner)
        total = sum(l.n_slots for l in self.lanes)
        if len(owners) <= 1:
            return total
        return max(1, -(-total // len(owners)))

    # holds-lock: _lock
    def _next_prepared(self, inflight: dict) -> "_Prepared | None":
        """Next admission candidate: FIFO within an owner, least-recently-
        admitted owner first, owners at their in-flight cap skipped — the
        cross-job interleave. Single-owner queues reduce to plain FIFO. In
        sync mode fall through to inline prep of the waiting queue (same
        owner rotation)."""
        cap = self._owner_cap(inflight)
        if self._ready:
            pick = self._fair_head(
                (p.request.owner for p in self._ready),
                self._owner_last_admit,
                inflight,
                cap,
            )
            if pick is None:
                return None  # every queued owner is at its fair share
            prep = self._ready[pick[1]]
            del self._ready[pick[1]]
            return prep
        if not self.async_prep:
            while self.waiting:
                pick = self._fair_head(
                    (r.owner for r in self.waiting),
                    self._owner_last_prep,
                    inflight,
                    cap,
                )
                if pick is None:
                    return None
                owner, idx = pick
                self._owner_last_prep[owner] = self._prep_seq
                self._prep_seq += 1
                prep = self._safe_prepare(self.waiting.pop(idx))
                if prep is not None:
                    return prep
        return None

    def _route(self, need: int) -> _Lane | None:
        """Pick the lane for a request needing ``need`` positions.

        Utilization-aware admission: every decode step runs a lane's FULL
        slot batch (static shapes), so joining a lane that is already
        decoding adds a token to rows that execute anyway — pure win —
        while opening an idle lane pays its whole batch for one request.
        Among lanes that fit and have a free slot, prefer the smallest
        ACTIVE lane; fall back to the smallest idle one. Exception: a
        request that a SHORTER idle lane could serve must not consume the
        LAST free slot of a longer active lane — long-lane slots are
        scarce (e.g. 2 at 4096 for the 7B default) and burning the last
        one on a short request head-of-line-blocks the next long prompt."""
        first_idle = None
        active = None
        active_free = 0
        for lane in self.lanes:  # sorted by length
            occupied = len(lane.slots) + len(lane.pending) + len(lane.reserved)
            if lane.length < need or occupied >= lane.n_slots:
                continue
            if occupied and active is None:
                active = lane
                active_free = lane.n_slots - occupied
            elif not occupied and first_idle is None:
                first_idle = lane
        if active is not None:
            if (
                first_idle is not None
                and first_idle.length < active.length
                and active_free <= 1
            ):
                return first_idle
            return active
        return first_idle

    def _prompt_len_estimate(self, req: CaptionRequest) -> int:
        """Prompt length WITHOUT running the encoders. Routing now sees the
        prepared request's ACTUAL total (prep precedes admission), so this
        is a planning utility: callers sizing a request against the lanes
        (fit_max_new_tokens, capacity tooling) without paying an encode."""
        n = len(req.prefix_ids) + len(req.prompt_ids)
        if req.frames is not None:
            n += self._vision_token_count(req.frames.shape[0])
        return min(n, self._max_len - req.sampling.max_new_tokens - 1)

    # holds-lock: _lock
    def _admit(self) -> None:
        if self._should_linger():
            return
        # per-owner in-flight counts for the fairness cap (updated as this
        # pass admits, so one pass cannot blow past the cap either)
        inflight: dict[Any, int] = {}
        for l in self.lanes:
            for s in l.slots.values():
                inflight[s.request.owner] = inflight.get(s.request.owner, 0) + 1
            for p in l.pending.values():
                inflight[p.request.owner] = inflight.get(p.request.owner, 0) + 1
        groups: dict[tuple[int, int], list[tuple]] = {}
        while True:
            prep = self._next_prepared(inflight)
            if prep is None:
                break
            req = prep.request
            need = prep.total + req.sampling.max_new_tokens + 1
            lane = self._route(min(need, self._max_len))
            if lane is None:
                # head-of-line waits for a slot to free (FIFO); the prep
                # work is kept, not redone
                self._ready.appendleft(prep)
                break
            lane_budget = lane.length - req.sampling.max_new_tokens - 1
            if prep.total > lane_budget:  # routed lane too short after all
                if req.frames is not None:
                    # never slice a vision block (see _fit_frames_to_budget):
                    # re-route on the ACTUAL token count — _prepare
                    # guarantees the total fits the longest lane, so a lane
                    # exists; None only means it is busy, so requeue at the
                    # head and wait instead of dropping a servable request
                    lane2 = self._route(prep.total + req.sampling.max_new_tokens + 1)
                    if lane2 is None:
                        self._ready.appendleft(prep)
                        break
                    logger.info(
                        "%s: multimodal prompt re-routed %d -> %d lane "
                        "(estimate %d, actual %d tokens)",
                        req.request_id, lane.length, lane2.length,
                        lane_budget, prep.total,
                    )
                    lane = lane2
                    lane_budget = lane.length - req.sampling.max_new_tokens - 1
                else:
                    if prep.base:
                        # tail-keep truncation may cut into the prefix
                        # region: fold the prefix back in first
                        prep = self._materialize_full(prep)
                    prep.embeds = prep.embeds[-lane_budget:]
                    prep.rope = prep.rope[-lane_budget:]
                    if prep.ds is not None:
                        prep.ds = prep.ds[:, -lane_budget:]
                    prep.t_suffix = lane_budget
            # The prefix entry must be resident BEFORE placement decisions:
            # when the pool cannot host it (exhausted with nothing
            # evictable), fold the prefix back into the host embeds and
            # admit uncached — recompute beats waiting on cache memory.
            if prep.base:
                entry, _ = self._ensure_prefix(prep.prefix_key, count=False)
                if entry is None:
                    prep = self._materialize_full(prep)
            # Shared-prefix placement feasibility in THIS lane: a bucketed
            # group prefill writes a [bucket]-length chunk at offset base,
            # which must stay inside the lane. Chunked prefill places
            # exactly (its final chunk shifts back), so prefer it when the
            # suffix is chunkable; otherwise fold the prefix back in.
            group_ok = (
                prep.base + min(next_pow2(prep.t_suffix), lane.length) <= lane.length
            )
            if not group_ok and prep.t_suffix <= self.prefill_chunk:
                prep = self._materialize_full(prep)
                group_ok = True
            # Chunk admission tuned against decode occupancy (the live
            # signal behind decode_slot_utilization): chunking protects
            # in-flight decode from a long prefill stall — with no lane
            # decoding there is nothing to protect, so admit the whole
            # prompt as one bucketed prefill and skip the per-step drip.
            decode_active = any(l.slots for l in self.lanes)
            chunked = prep.t_suffix > self.prefill_chunk and (
                decode_active or not group_ok
            )
            if chunked and self._recurrent:
                # a recurrence cannot take a token twice, so a hybrid's last
                # chunk is padded at its end, not shifted back: the padded
                # chunks must fit the lane, else the prompt goes in one bucket
                c = self.prefill_chunk
                if prep.base + -(-prep.t_suffix // c) * c > lane.length:
                    if not group_ok:
                        prep = self._materialize_full(prep)
                    chunked = False
            slot_idx = next(
                i
                for i in range(lane.n_slots)
                if i not in lane.slots
                and i not in lane.pending
                and i not in lane.reserved
            )
            try:
                self._claim_kv(lane, slot_idx, prep, req)
            except PoolExhausted:
                if prep.base and not any(l.claims for l in self.lanes):
                    # nothing in flight will free blocks and eviction
                    # spares the entry this claim references — the
                    # request's OWN prefix entry may be hoarding an idle
                    # pool. Fold the prefix back in and retry uncached: a
                    # lone worst-case request always fits an empty pool
                    # (kv_pool_blocks is floored at the lane sum).
                    self._ready.appendleft(self._materialize_full(prep))
                    continue
                # occupancy-based admission: the BLOCK POOL, not slot
                # count, is the limit — wait for in-flight requests to
                # free blocks (prep kept, not redone)
                self._ready.appendleft(prep)
                break
            except Exception:
                logger.exception(
                    "KV block claim failed for %s; dropping", req.request_id
                )
                continue
            inflight[req.owner] = inflight.get(req.owner, 0) + 1
            self._owner_last_admit[req.owner] = self._admit_seq
            self._admit_seq += 1
            if chunked:
                # long prompt: prefill in chunks interleaved with decode
                lane.pending[slot_idx] = _PendingPrefill(
                    request=req,
                    embeds=prep.embeds,
                    t_valid=prep.t_suffix,
                    rope_pos=prep.rope,
                    next_rope=prep.next_rope,
                    ds=prep.ds,
                    base=prep.base,
                )
                continue
            bucket = min(next_pow2(prep.t_suffix), lane.length)
            groups.setdefault((self.lanes.index(lane), bucket), []).append(
                (
                    slot_idx,
                    req,
                    prep.embeds,
                    prep.t_suffix,
                    prep.rope,
                    prep.next_rope,
                    prep.ds,
                    prep.base,
                )
            )
            # reserve the slot so this loop's later iterations see it taken
            lane.reserved.add(slot_idx)
        for (lane_i, bucket), items in sorted(groups.items()):
            lane = self.lanes[lane_i]
            for slot_idx, *_ in items:  # release the reservations
                lane.reserved.discard(slot_idx)
            try:
                self._prefill_group(lane, bucket, items)
            except Exception:
                if len(items) == 1:
                    logger.exception(
                        "prefill failed for %s; dropping", items[0][1].request_id
                    )
                    self._release_claim(lane, items[0][0])
                    continue
                # isolate the offender: retry each request as its own group
                logger.exception(
                    "batched prefill failed for %d requests; retrying singly",
                    len(items),
                )
                for item in items:
                    try:
                        self._prefill_group(lane, bucket, [item])
                    except Exception:
                        logger.exception(
                            "prefill failed for %s; dropping", item[1].request_id
                        )
                        self._release_claim(lane, item[0])

    def _prepare(self, req: CaptionRequest, allow_prefix: bool = True) -> _Prepared:
        """Vision encode + token embed for one request.

        When the request's text prefix is shareable (``share_prefix``, long
        enough, cache enabled, no truncation needed), only the SUFFIX
        (vision + prompt text) is embedded — the prefix's K/V come from the
        shared-prefix cache and ``base`` marks where suffix prefill starts.
        Rope positions stay absolute over the full [prefix][vision][prompt]
        layout either way, so cached and uncached prefills write identical
        cache contents (greedy parity). Under m-rope the positions come
        from build_mrope_positions; otherwise they are arange."""
        from cosmos_curate_tpu.models.vlm.model import build_mrope_positions

        budget = self._max_len - req.sampling.max_new_tokens - 1
        n_pre = len(req.prefix_ids)
        vis_embeds = None
        ds_vis = None
        grid_merged = None
        eff_fps = None
        if req.frames is not None:
            vf = req.vision_features
            n_text = n_pre + len(req.prompt_ids)
            if vf is not None and n_text + vf.n_tokens <= budget:
                # refinement pass over the SAME frames: reuse the encoded
                # vision features instead of re-running the tower
                vis_embeds, ds_vis = vf.embeds, vf.ds
                grid_merged, eff_fps = vf.grid, vf.eff_fps
                with self._stats_lock:
                    self._vision_reuses += 1
            else:
                frames, eff_fps = self._fit_frames_to_budget(req)
                with self._phase("vision_encode"):
                    vis = self._encode_images(self.params, jnp.asarray(frames)[None])
                    if isinstance(vis, tuple):  # qwen3: (embeds, deepstack levels)
                        vis, ds_levels = vis
                        ds_vis = np.asarray(ds_levels[:, 0], np.float32)  # [L_ds, T_vis, D]
                    vis_embeds = vis[0]
                    jax.block_until_ready(vis_embeds)
                with self._stats_lock:
                    self._vision_encodes += 1
                if self.cfg.vision_variant in ("qwen2", "qwen3"):
                    grid_merged = self.cfg.qwen_vision.merged_grid(frames.shape[0])
                req.vision_features = _VisionFeatures(
                    embeds=vis_embeds,
                    ds=ds_vis,
                    grid=grid_merged,
                    eff_fps=eff_fps,
                    n_tokens=int(vis_embeds.shape[0]),
                )
        n_vis = 0 if vis_embeds is None else int(vis_embeds.shape[0])
        total = n_pre + n_vis + len(req.prompt_ids)
        use_prefix = (
            allow_prefix
            and self.enable_prefix_cache
            and req.share_prefix
            and n_pre >= self.min_prefix_len
            and n_vis + len(req.prompt_ids) > 0  # suffix must be non-empty
            and total <= budget  # tail-keep truncation cuts into the prefix
        )
        parts = []
        if n_pre and not use_prefix:
            pre = jnp.asarray(req.prefix_ids, jnp.int32)
            parts.append(self._embed_tokens(self.params, pre[None])[0])
        if vis_embeds is not None:
            parts.append(vis_embeds)
        if req.prompt_ids:
            ids = jnp.asarray(req.prompt_ids, jnp.int32)
            parts.append(self._embed_tokens(self.params, ids[None])[0])
        embeds = jnp.concatenate(parts, axis=0)
        if self.cfg.mrope_section is not None:
            if grid_merged is None and n_vis:
                # vit-variant vision tokens: treat as a 1 x 1 x n_vis row
                grid_merged = (1, 1, n_vis)
            # Qwen2.5-VL temporal scaling: t_scale = second_per_grid_t *
            # tokens_per_second, second_per_grid_t = temporal_patch_size /
            # sampled fps (HF get_rope_index); Qwen2-VL (tokens_per_second
            # None) keeps the unscaled arange.
            t_scale = 1.0
            qv = self.cfg.qwen_vision
            if (
                qv is not None
                and qv.tokens_per_second
                and eff_fps
                and grid_merged is not None
            ):
                t_scale = qv.tokens_per_second * qv.temporal_patch_size / eff_fps
            rope_pos, next_rope = build_mrope_positions(
                n_pre, grid_merged, len(req.prompt_ids), t_scale
            )
        else:
            rope_pos = np.arange(total, dtype=np.int32)
            next_rope = total
        ds = None
        if ds_vis is not None and self._ds_levels:
            # deepstack buffer: zeros at text positions, the merger levels
            # at the vision span (text-only requests carry ds=None — the
            # prefill buffers read as zeros); suffix-aligned when the
            # prefix is cached
            off = 0 if use_prefix else n_pre
            t_len = (total - n_pre) if use_prefix else total
            ds = np.zeros((self._ds_levels, t_len, embeds.shape[-1]), np.float32)
            ds[:, off : off + ds_vis.shape[1]] = ds_vis
        if use_prefix:
            key = tuple(req.prefix_ids)
            _entry, hit = self._ensure_prefix(key)
            if hit:
                with self._stats_lock:
                    self._prefix_tokens_saved += n_pre
            return _Prepared(
                request=req,
                embeds=np.asarray(embeds, np.float32),
                t_suffix=total - n_pre,
                rope=np.asarray(rope_pos)[n_pre:],
                next_rope=next_rope,
                ds=ds,
                base=n_pre,
                prefix_key=key,
            )
        t_valid = total
        rope_pos = np.asarray(rope_pos)
        if t_valid > budget:
            if req.frames is not None:
                # _fit_frames_to_budget guarantees multimodal prompts fit;
                # slicing here would cut the vision block mid-grid and
                # corrupt the prompt silently
                raise ValueError(
                    f"{req.request_id}: multimodal prompt still over budget "
                    f"after frame reduction ({t_valid} > {budget})"
                )
            # text-only: keep the tail (task instructions usually come
            # last); rope positions stay absolute for the kept tokens
            embeds = embeds[-budget:]
            rope_pos = rope_pos[-budget:]
            if ds is not None:
                ds = ds[:, -budget:]
            t_valid = budget
        return _Prepared(
            request=req,
            embeds=np.asarray(embeds, np.float32),
            t_suffix=t_valid,
            rope=rope_pos,
            next_rope=next_rope,
            ds=ds,
        )

    def _prepare_embeds(self, req: CaptionRequest):
        """Legacy full-layout prep view (no prefix cache): ([T, D] embeds,
        t_valid, [T(,3)] rope positions, next_rope, ds)."""
        p = self._prepare(req, allow_prefix=False)
        return p.embeds, p.t_suffix, p.rope, p.next_rope, p.ds

    def _materialize_full(self, prep: _Prepared) -> _Prepared:
        """Fold the cached prefix back into a prepared request (host-side):
        the fallback when a routed lane cannot place a bucketed suffix at
        offset ``base``, or when tail-keep truncation must see the whole
        layout. Produces the exact uncached prefill inputs."""
        req = prep.request
        n_pre = len(req.prefix_ids)
        pre = jnp.asarray(req.prefix_ids, jnp.int32)
        pre_emb = np.asarray(self._embed_tokens(self.params, pre[None])[0], np.float32)
        t = np.arange(n_pre, dtype=np.int32)
        pre_rope = np.stack([t, t, t], axis=-1) if prep.rope.ndim == 2 else t
        ds = prep.ds
        if ds is not None:
            ds = np.concatenate(
                [np.zeros((ds.shape[0], n_pre, ds.shape[-1]), np.float32), ds], axis=1
            )
        return _Prepared(
            request=req,
            embeds=np.concatenate([pre_emb, prep.embeds], axis=0),
            t_suffix=n_pre + prep.t_suffix,
            rope=np.concatenate([pre_rope, prep.rope], axis=0),
            next_rope=prep.next_rope,
            ds=ds,
        )

    def _ensure_prefix(
        self, key: tuple, count: bool = True
    ) -> "tuple[_PrefixEntry | None, bool]":
        """(entry, was_hit) for one shared text prefix, prefilling it into
        POOL BLOCKS on first use and LRU-inserting the entry. The scratch
        prefill compute runs without the engine lock (it touches no pool
        state, so the prep thread can build a prefix while the decode loop
        runs); only the final block allocation + pool write takes the
        engine lock — lock order is always engine lock -> prefix lock.
        Returns (None, False) when the pool cannot host the entry even
        after evicting idle prefixes: callers serve the prefix uncached.
        ``count=False`` skips the hit counter (the admission-time re-lookup
        must not double-count the prep-time hit); rebuild misses always
        count — an eviction-rebuild is real recompute."""
        with self._prefix_lock:
            entry = self._prefix_cache.get(key)
            if entry is not None:
                self._prefix_cache.move_to_end(key)
                if count:
                    with self._stats_lock:
                        self._prefix_hits += 1
                return entry, True
        if not self.enable_prefix_cache:
            return None, False
        with self._stats_lock:
            self._prefix_misses += 1
        tp = len(key)
        sp = next_pow2(tp)
        with self._phase("prefill_build"):
            emb = np.zeros((1, sp, self.cfg.dim), np.float32)
            emb[0, :tp] = np.asarray(
                self._embed_tokens(self.params, jnp.asarray(key, jnp.int32)[None])[0],
                np.float32,
            )
            pos = np.zeros((1, sp), np.int32)
            pos[0, :tp] = np.arange(tp, dtype=np.int32)
            if self.cfg.mrope_section is not None:
                # text prefix: all three m-rope components equal
                pos = np.broadcast_to(pos[..., None], (1, sp, 3))
        with self._phase("prefill_dispatch"):
            # a hybrid's build also returns its state snapshot (ssm, conv)
            k, v, *state = self._prefix_prefill(
                self.params,
                jnp.asarray(emb),
                jnp.asarray(pos),
                jnp.asarray(tp, jnp.int32),
            )
            k, v = k[:, :, :tp], v[:, :, :tp]
        with self._phase("prefill_wait"):
            jax.block_until_ready(v)
        with self._stats_lock:
            self._prefill_tokens += tp
        bs = self.block_size
        nb = -(-tp // bs)
        with self._lock:
            with self._prefix_lock:
                raced = self._prefix_cache.get(key)
                if raced is not None:  # a concurrent build won: adopt it
                    self._prefix_cache.move_to_end(key)
                    with self._stats_lock:
                        # the outcome is a HIT (the winner's build is
                        # served); reclassify the miss counted up front so
                        # hit-rate stats stay exact under concurrency
                        self._prefix_misses -= 1
                        self._prefix_hits += 1
                    return raced, True
                if not self._allocator.can_alloc(nb):
                    self._evict_prefixes_for(nb)
                if not self._allocator.can_alloc(nb):
                    logger.warning(
                        "prefix cache: pool exhausted; serving %d-token "
                        "prefix uncached", tp,
                    )
                    return None, False
                ids = self._allocator.alloc(nb)
                self._pool_k, self._pool_v = self._write_prefix_blocks(
                    self._pool_k,
                    self._pool_v,
                    k,
                    v,
                    jnp.asarray(ids, jnp.int32),
                )
                entry = _PrefixEntry(
                    blocks=ids,
                    n_full=tp // bs,
                    tail_block=ids[-1] if tp % bs else None,
                    length=tp,
                    state=tuple(state) or None,
                )
                self._prefix_cache[key] = entry
                while len(self._prefix_cache) > self.prefix_cache_size:
                    _k2, evicted = self._prefix_cache.popitem(last=False)
                    # referenced blocks defer their free to the last slot
                    self._allocator.decref(evicted.blocks)
                    with self._stats_lock:
                        self._prefix_evictions += 1
                return entry, False

    # holds-lock: _lock, _prefix_lock
    def _evict_prefixes_for(self, n_blocks: int, exclude: tuple | None = None) -> None:
        """Evict idle LRU prefixes until ``n_blocks`` are allocatable (or
        the cache is empty — referenced blocks free only when their last
        slot releases). ``exclude`` protects the entry a claim in progress
        is about to reference. Engine + prefix locks held by caller."""
        for key in list(self._prefix_cache):
            if self._allocator.can_alloc(n_blocks):
                return
            if key == exclude:
                continue
            evicted = self._prefix_cache.pop(key)
            self._allocator.decref(evicted.blocks)
            with self._stats_lock:
                self._prefix_evictions += 1

    # holds-lock: _lock
    def _claim_kv(
        self, lane: _Lane, slot_idx: int, prep: _Prepared, req: CaptionRequest
    ) -> _BlockClaim:
        """Reserve a request's KV blocks and build its block-table row.

        Shared-prefix full blocks are REFERENCED (incref — zero device
        copies, the successor of the deleted insert_prefix path); a
        partially-filled shared tail block is copy-on-write duplicated into
        the request's first private block; the rest of
        ``ceil(need / block_size)`` blocks are fresh private allocations.
        Raises PoolExhausted when the pool cannot supply the private blocks
        (admission backpressure, not an error). Engine lock held by
        caller."""
        bs = self.block_size
        need = min(prep.total + req.sampling.max_new_tokens + 1, lane.length)
        view_blocks = -(-need // bs)
        shared: list[int] = []
        cow_src: int | None = None
        entry = None
        if prep.base:
            with self._prefix_lock:
                entry = self._prefix_cache.get(prep.prefix_key)
            if entry is None:
                # _admit ensured the entry earlier THIS iteration and holds
                # the engine lock inserts/evictions need — it cannot vanish
                raise RuntimeError(f"prefix entry vanished for {req.request_id}")
            shared = list(entry.blocks[: entry.n_full])
            cow_src = entry.tail_block
        private_needed = view_blocks - len(shared)
        if not self._allocator.can_alloc(private_needed):
            if not any(l.claims for l in self.lanes):
                # nothing in flight will ever free blocks — the pool is
                # held by idle prefix entries. Evict them (sparing the one
                # this claim references) instead of deadlocking admission.
                with self._prefix_lock:
                    self._evict_prefixes_for(
                        private_needed,
                        exclude=prep.prefix_key if prep.base else None,
                    )
            if not self._allocator.can_alloc(private_needed):
                raise PoolExhausted(
                    f"{private_needed} KV blocks needed, "
                    f"{self._allocator.free_blocks} free of {self._allocator.capacity}"
                )
        self._allocator.incref(shared)
        private = self._allocator.alloc(private_needed)
        try:
            if cow_src is not None:
                # the suffix extends INTO the partially-filled shared tail
                # block: copy-on-write one block — the only device copy on
                # the whole admission path
                self._pool_k, self._pool_v = self._copy_blocks(
                    self._pool_k,
                    self._pool_v,
                    jnp.asarray([cow_src], jnp.int32),
                    jnp.asarray([private[0]], jnp.int32),
                )
        except BaseException:
            # a failed CoW dispatch must hand the references back, or the
            # shared pool shrinks permanently on every transient error
            self._allocator.decref(shared + private)
            raise
        row = lane.table[slot_idx]
        row[:] = 0
        row[: len(shared)] = shared
        row[len(shared) : view_blocks] = private
        claim = _BlockClaim(shared=shared, private=private)
        lane.claims[slot_idx] = claim
        if self._recurrent:
            try:
                self._start_state_row(lane, slot_idx, None if entry is None else entry.state)
            except BaseException:
                self._release_claim(lane, slot_idx)
                raise
        with self._stats_lock:
            self._requests_admitted += 1
            self._kv_blocks_reserved += view_blocks
            self._kv_private_blocks += len(private)
            self._kv_worstcase_tokens += lane.length
            self._prefix_block_refs += len(shared)
            if cow_src is not None:
                self._kv_cow_copies += 1
            self._kv_blocks_used_peak = max(
                self._kv_blocks_used_peak, self._allocator.used_blocks
            )
            if self._recurrent:
                self._recurrent_rows_used_peak = max(
                    self._recurrent_rows_used_peak, sum(len(l.claims) for l in self.lanes)
                )
                self._prefix_state_snapshots += entry is not None
            self._owner_requests[req.owner] = (
                self._owner_requests.get(req.owner, 0) + 1
            )
        return claim

    @staticmethod
    def _state_rows(lane: _Lane, slot_indices) -> np.ndarray:
        """Rows of the recurrent store that a lane's slots own (row 0 is the
        garbage row, so slot ``i`` of the lane has row ``1 + base + i``)."""
        return (1 + lane.base + np.asarray(slot_indices)).astype(np.int32)

    # holds-lock: _lock
    def _start_state_row(self, lane: _Lane, slot_idx: int, snapshot: tuple | None) -> None:
        """A freshly claimed slot starts from the shared prefix's state
        snapshot, or from zeros; never from the last tenant's state."""
        row = jnp.asarray(self._state_rows(lane, slot_idx))
        if snapshot is None:
            self._ssm, self._conv = self._zero_state_row(self._ssm, self._conv, row)
        else:
            self._ssm, self._conv = self._set_state_row(self._ssm, self._conv, row, *snapshot)

    # holds-lock: _lock
    def _run_prefill(self, lane: _Lane, slots_arr, tables, embeds, write_index, t_valid, rope, ds):
        """One call of the prefill program over host arrays; a hybrid's
        recurrent store rides along and comes back with the pools."""
        args = (
            self.params, self._pool_k, self._pool_v, jnp.asarray(tables), jnp.asarray(embeds),
            jnp.asarray(write_index), jnp.asarray(t_valid), jnp.asarray(rope),
            None if ds is None else jnp.asarray(ds),
        )
        if not self._recurrent:
            logits, self._pool_k, self._pool_v = self._prefill_batch(*args)
        else:
            logits, self._pool_k, self._pool_v, self._ssm, self._conv = self._prefill_batch(
                *args, self._ssm, self._conv, jnp.asarray(self._state_rows(lane, slots_arr))
            )
        return logits

    def _release_claim(self, lane: _Lane, slot_idx: int) -> None:
        """Return a slot's block references to the pool. Private blocks
        free immediately; shared prefix blocks free only when the LAST
        reference (including the LRU's own) drops — an evicted-but-still-
        referenced prefix frees here, deferred. Engine lock held by
        caller."""
        claim = lane.claims.pop(slot_idx, None)
        if claim is None:
            return
        self._allocator.decref(claim.all_blocks)
        lane.table[slot_idx, :] = 0
        self._work_cv.notify_all()  # pool-blocked admissions may now fit

    def fit_max_new_tokens(
        self,
        requested: int,
        prompt_ids: list[int],
        prefix_ids: list[int] = (),
        n_frames: int = 0,
    ) -> int:
        """The largest ``max_new_tokens`` (≤ requested, ≥ 1) that leaves
        this prompt inside the longest KV lane — callers with fixed prompts
        clamp generation instead of having the vision block rejected."""
        n = len(prefix_ids) + len(prompt_ids)
        if n_frames:
            n += self._vision_token_count(n_frames)
        return max(1, min(requested, self._max_len - n - 1))

    def _vision_token_count(self, n_frames: int) -> int:
        if self.cfg.vision_variant in ("qwen2", "qwen3"):
            return self.cfg.qwen_vision.tokens_out(n_frames)
        return self.cfg.vision_tokens

    def _fit_frames_to_budget(
        self, req: CaptionRequest
    ) -> tuple[np.ndarray | None, float | None]:
        """An over-budget multimodal prompt re-samples FEWER frames instead
        of silently slicing the vision block (VERDICT r3: tail-keep on a
        frames-heavy request dropped leading vision tokens mid-grid,
        producing a grammatically-valid but semantically-corrupt prompt;
        the reference's windowing guarantees prompts fit,
        windowing_utils.py:53). Raises when even one frame cannot fit —
        the caller's text leaves no room for vision.

        Returns (frames, effective_fps): re-sampling spreads fewer frames
        over the SAME source span, so the temporal m-rope scale must use
        the reduced rate, not the request's original frame_fps."""
        frames = req.frames
        if frames is None:
            return None, None
        budget = self._max_len - req.sampling.max_new_tokens - 1
        n_text = len(req.prefix_ids) + len(req.prompt_ids)
        n = frames.shape[0]
        if n_text + self._vision_token_count(n) <= budget:
            return frames, req.frame_fps
        for n2 in range(n - 1, 0, -1):
            if n_text + self._vision_token_count(n2) <= budget:
                idx = np.linspace(0, n - 1, n2).round().astype(int)
                logger.warning(
                    "%s: prompt over budget; re-sampled %d -> %d frames",
                    req.request_id,
                    n,
                    n2,
                )
                eff = req.frame_fps * (n2 / n) if req.frame_fps else None
                return frames[idx], eff
        raise ValueError(
            f"{req.request_id}: text prompt ({n_text} tokens) leaves no room "
            f"for any vision tokens within budget {budget}"
        )

    # holds-lock: _lock
    def _prefill_group(self, lane: _Lane, bucket: int, items: list) -> None:
        """One batched prefill for all requests sharing a length bucket.

        The row count is padded to a power of two by duplicating row 0
        (same table + same content → the duplicate scatter writes identical
        values), so compiled program count stays O(log max_batch x
        log max_seq). Bucket padding may write past a row's reserved
        blocks ([base, base + bucket) can overshoot need): those positions
        map to garbage-block table entries, whose contents are never read
        unmasked."""
        with self._phase("prefill_build"):
            n = len(items)
            n_pad = next_pow2(n)  # bounded by next_pow2(lane.n_slots)
            dim = items[0][2].shape[-1]
            embeds = np.zeros((n_pad, bucket, dim), np.float32)
            slots_arr = np.zeros(n_pad, np.int32)
            t_valids = np.ones(n_pad, np.int32)
            bases = np.zeros(n_pad, np.int32)
            mrope = self.cfg.mrope_section is not None
            rope_shape = (n_pad, bucket, 3) if mrope else (n_pad, bucket)
            rope_buf = np.zeros(rope_shape, np.int32)
            ds_buf = (
                np.zeros((self._ds_levels, n_pad, bucket, dim), np.float32)
                if self._ds_levels
                else None
            )
            for j, (slot_idx, _req, emb, t_valid, rope_pos, _next, ds, base) in enumerate(
                items
            ):
                embeds[j, :t_valid] = np.asarray(emb, np.float32)[:t_valid]
                slots_arr[j] = slot_idx
                t_valids[j] = t_valid
                bases[j] = base  # shared-prefix rows start past their cached K/V
                rope_buf[j, :t_valid] = rope_pos[:t_valid]
                if ds_buf is not None and ds is not None:
                    ds_buf[:, j, :t_valid] = ds[:, :t_valid]
            for j in range(n, n_pad):  # duplicate row 0 into padding
                embeds[j] = embeds[0]
                slots_arr[j] = slots_arr[0]
                t_valids[j] = t_valids[0]
                bases[j] = bases[0]
                rope_buf[j] = rope_buf[0]
                if ds_buf is not None:
                    ds_buf[:, j] = ds_buf[:, 0]
            tables = lane.table[slots_arr]  # [n_pad, nbl]; padding rows = row 0
        with self._phase("prefill_dispatch"):
            logits = self._run_prefill(
                lane, slots_arr, tables, embeds, bases, t_valids, rope_buf, ds_buf
            )
        with self._phase("prefill_wait"):
            logits_np = np.asarray(logits)  # one host sync for the whole group
        with self._phase("prefill_sample"):
            with self._stats_lock:
                self._prefill_tokens += int(sum(it[3] for it in items))
                if self._use_paged:
                    self._kv_gather_bytes_avoided += self._gather_view_bytes(
                        len(tables), lane.length
                    )
            for j, (slot_idx, req, _emb, t_valid, _rope, next_rope, _ds, base) in enumerate(
                items
            ):
                self._start_slot(
                    lane, slot_idx, req, base + t_valid, next_rope, logits_np[j]
                )

    def _start_slot(
        self,
        lane: _Lane,
        slot_idx: int,
        req: CaptionRequest,
        t_valid: int,
        next_rope: int,
        logits_row: np.ndarray,
    ) -> None:
        """Sample the first token from the last-prompt-position logits and
        enter the slot into the continuous decode batch."""
        # seed=None is the unseeded sentinel; any int (incl. 0) pins
        rng = (
            np.random.default_rng(req.sampling.seed)
            if req.sampling.seed is not None
            else None
        )
        counts: dict[int, int] | None = None
        s = req.sampling
        if (
            s.repetition_penalty != 1.0
            or s.presence_penalty != 0.0
            or s.frequency_penalty != 0.0
        ):
            # penalty history covers prompt tokens too (vLLM
            # semantics); maintained incrementally from here on
            counts = {}
            for t in [*req.prefix_ids, *req.prompt_ids]:
                counts[t] = counts.get(t, 0) + 1
        first = sample_token(
            logits_row,
            req.sampling,
            generated=counts,
            num_generated=0,
            eos_id=self.tokenizer.eos_id,
            rng=rng if rng is not None else self._host_rng,
        )
        slot = _Slot(
            request=req,
            position=t_valid,
            rope_position=next_rope,
            generated=[first],
            rng=rng,
            penalty_counts=counts,
        )
        if counts is not None:
            counts[first] = counts.get(first, 0) + 1
        if req.sampling.stop:
            slot.raw += self.tokenizer.decode_bytes([first])
        lane.slots[slot_idx] = slot
        self._maybe_finish(lane, slot_idx, slot)

    # holds-lock: _lock
    def _prefill_chunk_step(self, lane: _Lane) -> None:
        """Advance every pending chunked prefill by one chunk (one batched
        program call); rows finishing their prompt enter the decode batch."""
        C = self.prefill_chunk
        items = list(lane.pending.items())
        if not items:
            return
        with self._phase("prefill_build"):
            n = len(items)
            n_pad = next_pow2(n)  # bounded by next_pow2(lane.n_slots)
            dim = items[0][1].embeds.shape[-1]
            mrope = self.cfg.mrope_section is not None
            embeds = np.zeros((n_pad, C, dim), np.float32)
            slots_arr = np.zeros(n_pad, np.int32)
            write_idx = np.zeros(n_pad, np.int32)
            chunk_valid = np.ones(n_pad, np.int32)
            rope_buf = np.zeros((n_pad, C, 3) if mrope else (n_pad, C), np.int32)
            ds_buf = (
                np.zeros((self._ds_levels, n_pad, C, dim), np.float32)
                if self._ds_levels
                else None
            )
            new_tokens = 0
            for j, (slot_idx, p) in enumerate(items):
                take = min(C, p.t_valid - p.progress)
                start = p.progress
                if take < C and not self._recurrent:
                    # final partial chunk: shift back so the C-length buffer
                    # ends exactly at the prompt end. The overlapped rows
                    # rewrite identical K/V (same embeds, same rope, correct
                    # causal mask), and dynamic_update_slice stays in bounds
                    # for shared-prefix bases > 0 and for lane lengths that are
                    # not a multiple of the chunk size. A recurrence cannot
                    # take a token twice: a hybrid's last chunk starts where
                    # the one before ended and is padded at its end instead
                    # (_admit made sure that it fits the lane).
                    start = p.t_valid - C
                filled = min(C, p.t_valid - start)  # C, but for a hybrid's last chunk
                new_tokens += take
                embeds[j, :filled] = p.embeds[start : start + filled]
                slots_arr[j] = slot_idx
                write_idx[j] = p.base + start
                chunk_valid[j] = C if start < p.progress else take
                rope_buf[j, :filled] = p.rope_pos[start : start + filled]
                if ds_buf is not None and p.ds is not None:
                    ds_buf[:, j, :filled] = p.ds[:, start : start + filled]
            for j in range(n, n_pad):  # duplicate row 0 (identical writes: safe)
                embeds[j] = embeds[0]
                slots_arr[j] = slots_arr[0]
                write_idx[j] = write_idx[0]
                chunk_valid[j] = chunk_valid[0]
                rope_buf[j] = rope_buf[0]
                if ds_buf is not None:
                    ds_buf[:, j] = ds_buf[:, 0]
            tables = lane.table[slots_arr]  # [n_pad, nbl]; padding rows = row 0
        with self._phase("prefill_dispatch"):
            logits = self._run_prefill(
                lane, slots_arr, tables, embeds, write_idx, chunk_valid, rope_buf, ds_buf
            )
        finished = []
        for j, (slot_idx, p) in enumerate(items):
            p.progress += min(C, p.t_valid - p.progress)
            if p.progress >= p.t_valid:
                finished.append((j, slot_idx, p))
        if finished:  # no finished row: nothing to read back, no host sync
            with self._phase("prefill_wait"):
                logits_np = np.asarray(logits)
        with self._phase("prefill_sample"):
            for j, slot_idx, p in finished:
                del lane.pending[slot_idx]
                self._start_slot(
                    lane, slot_idx, p.request, p.base + p.t_valid, p.next_rope,
                    logits_np[j],
                )
            with self._stats_lock:
                self._prefill_tokens += new_tokens
                if self._use_paged:
                    self._kv_gather_bytes_avoided += self._gather_view_bytes(
                        len(tables), lane.length
                    )

    # holds-lock: _lock
    def _decode_once(self, lane: _Lane) -> None:
        with self._phase("decode_build"):
            tokens = np.full(lane.n_slots, self.tokenizer.pad_id, np.int32)
            positions = np.zeros(lane.n_slots, np.int32)
            rope_positions = np.zeros(lane.n_slots, np.int32)
            # The decode program scatters K/V for EVERY row (static shapes, no
            # write mask), so idle rows' write positions must be harmless.
            # Fully-free rows carry an all-garbage block table — their write
            # lands in the reserved garbage block — but a row mid-chunked-
            # prefill holds real prompt K/V: point its write at base +
            # progress, a cell the NEXT chunk overwrites anyway (the shifted
            # final chunk covers [t_valid - C, t_valid), which contains it), so
            # the pad-token garbage can never survive into attention reads.
            for i, p in lane.pending.items():
                positions[i] = p.base + p.progress
            for i, slot in lane.slots.items():
                tokens[i] = slot.generated[-1]
                positions[i] = slot.position
                rope_positions[i] = slot.rope_position
        with self._phase("decode_dispatch"):
            args = (
                self.params,
                self._pool_k,
                self._pool_v,
                jnp.asarray(lane.table),
                jnp.asarray(tokens),
                jnp.asarray(positions),
                jnp.asarray(rope_positions),
            )
            if not self._recurrent:
                greedy, logits, self._pool_k, self._pool_v = self._decode(*args)
            else:
                # rows that decode advance their own state; the others (free,
                # or mid-prefill and holding a real state) carry the garbage
                # row and are masked in the recurrence besides
                rows = np.zeros(lane.n_slots, np.int32)
                active = list(lane.slots)
                rows[active] = self._state_rows(lane, active)
                greedy, logits, self._pool_k, self._pool_v, self._ssm, self._conv = (
                    self._decode(*args, self._ssm, self._conv, jnp.asarray(rows))
                )
        with self._phase("decode_wait"):
            greedy_np = np.asarray(greedy)  # ONE host sync for the whole batch
        with self._phase("decode_sample"):
            with self._stats_lock:
                self._decode_tokens += len(lane.slots)
                self._decode_rows += lane.n_slots
                self._ssm_decode_calls += len(self.cfg.ssm_layers)
                if self._use_paged:
                    self._paged_kernel_steps += 1
                    # a row's kv_len is positions + 1 (decode_step_paged)
                    self._paged_decode_pages_walked += int(
                        (positions // self.block_size + 1).sum()
                    )
                    self._paged_decode_pages_spanned += lane.table.size
                    self._kv_gather_bytes_avoided += self._gather_view_bytes(
                        lane.n_slots, lane.length
                    )
                for slot in lane.slots.values():
                    owner = slot.request.owner
                    self._owner_decode_tokens[owner] = (
                        self._owner_decode_tokens.get(owner, 0) + 1
                    )
            # the device argmax suffices only for pure-greedy rows with no
            # penalties and min_tokens already satisfied
            needs_logits = any(
                s.request.sampling.needs_logits(len(s.generated))
                for s in lane.slots.values()
            )
            logits_np = np.asarray(logits) if needs_logits else None
            for i in list(lane.slots):
                slot = lane.slots[i]
                if slot.request.sampling.needs_logits(len(slot.generated)):
                    nxt = sample_token(
                        logits_np[i],
                        slot.request.sampling,
                        # incrementally maintained prompt+output counts; the
                        # decode loop must not re-unique the history per token
                        generated=slot.penalty_counts,
                        num_generated=len(slot.generated),
                        eos_id=self.tokenizer.eos_id,
                        rng=slot.rng if slot.rng is not None else self._host_rng,
                    )
                else:
                    nxt = int(greedy_np[i])
                slot.generated.append(nxt)
                if slot.penalty_counts is not None:
                    slot.penalty_counts[nxt] = slot.penalty_counts.get(nxt, 0) + 1
                if slot.request.sampling.stop:
                    slot.raw += self.tokenizer.decode_bytes([nxt])
                slot.position += 1
                slot.rope_position += 1
                self._maybe_finish(lane, i, slot)

    def _maybe_finish(self, lane: _Lane, slot_idx: int, slot: _Slot) -> None:
        req = slot.request
        done = (
            slot.generated[-1] == self.tokenizer.eos_id
            or len(slot.generated) >= req.sampling.max_new_tokens
            or slot.position + 1 >= lane.length
        )
        stop_text: str | None = None
        if not done and req.sampling.stop:
            # stop strings match on decoded text (vLLM `stop`); the match
            # and everything after it is dropped. The hot path scans only a
            # bounded tail of the incrementally maintained byte buffer
            # (slot.raw — exact regardless of zero-byte special tokens);
            # the full decode runs once, on a hit.
            longest = max(len(s) for s in req.sampling.stop)
            tail = bytes(slot.raw[-(4 * longest + 8) :]).decode("utf-8", errors="replace")
            if any(s in tail for s in req.sampling.stop):
                stop_text = _truncate_at_stop(
                    bytes(slot.raw).decode("utf-8", errors="replace"), req.sampling.stop
                )
                done = stop_text is not None
        if not done:
            return
        del lane.slots[slot_idx]
        self._release_claim(lane, slot_idx)
        out_ids = [t for t in slot.generated if t != self.tokenizer.eos_id]
        text = stop_text if stop_text is not None else self.tokenizer.decode(out_ids)
        if stop_text is None and req.sampling.stop:
            # a stop string may land in the same step that hit eos/max
            truncated = _truncate_at_stop(text, req.sampling.stop)
            if truncated is not None:
                text = truncated
        result = CaptionResult(
            request_id=req.request_id,
            text=text,
            num_prompt_tokens=len(req.prefix_ids) + len(req.prompt_ids),
            num_output_tokens=len(slot.generated),
            metadata=req.metadata,
            owner=req.owner,
        )
        if req.on_complete is not None:
            follow_up = req.on_complete(text)
            if follow_up is not None:
                if follow_up.owner is None:
                    follow_up.owner = req.owner
                if (
                    follow_up.frames is not None
                    and follow_up.frames is req.frames
                    and follow_up.vision_features is None
                ):
                    # refinement over the SAME frames array: hand the
                    # already-encoded vision features to the follow-up so
                    # the tower doesn't run twice per window
                    follow_up.vision_features = req.vision_features
                self.waiting.append(follow_up)
                self._work_cv.notify_all()  # wake the prep thread
                return  # result superseded by the refinement pass
        self.completed.append(result)
