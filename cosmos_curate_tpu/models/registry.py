"""Model-weights registry and staging.

Equivalent capability of the reference's weights management
(cosmos_curate/configs/all_models.json registry + core/utils/model/
model_utils.py:56-778 download/staging flow): a registry of model ids with
their local weight locations, a per-node staging hook, and loading that is
explicit about provenance.

In this image there is no network egress and no pretrained cache, so
``load_params`` falls back to **seeded random initialization** with a
prominent warning when no weights are staged — architecture, sharding and
throughput are exercised identically; real deployments drop orbax
checkpoints into ``$CURATE_MODEL_WEIGHTS_DIR/<model-id>/``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable

from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)

WEIGHTS_DIR_ENV = "CURATE_MODEL_WEIGHTS_DIR"


class WeightsIntegrityError(RuntimeError):
    """A pulled checkpoint failed its sha256 manifest — never silently
    degraded to random init (corrupted staging must abort, not caption
    a dataset with garbage at full cost)."""
# Remote prefix weights are pulled from on demand (s3:// gs:// az:// or a
# local/NFS path) — the reference's download/staging flow
# (model_utils.py:139 pulls from HF/S3 to node-local disk; here the pull
# rides the SDK-free storage clients).
WEIGHTS_URI_ENV = "CURATE_WEIGHTS_URI"


@dataclass(frozen=True)
class ModelEntry:
    model_id: str
    description: str = ""


_REGISTRY: dict[str, ModelEntry] = {}


def register_model(model_id: str, description: str = "") -> None:
    _REGISTRY[model_id] = ModelEntry(model_id, description)


def registered_models() -> list[str]:
    return sorted(_REGISTRY)


for _mid, _desc in [
    ("transnetv2-tpu", "shot transition detector (Flax DDCNN)"),
    ("clip-vit-l14-tpu", "CLIP ViT-L/14 image embedder (Flax)"),
    ("clip-vit-b16-tpu", "CLIP ViT-B/16 image embedder (Flax)"),
    ("aesthetics-mlp-tpu", "aesthetic score head over CLIP embeddings"),
    ("video-embed-tpu", "temporal-transformer video embedder"),
    ("internvideo2-1b-tpu", "InternVideo2-1B stage2 video embedder (converted checkpoint slot)"),
    ("internvideo2-tiny-test", "InternVideo2 tiny test config"),
    ("caption-vlm-tpu", "vision-language captioning model (Flax)"),
    ("caption-qwen2vl-2b-tpu", "Qwen2-VL-2B-class captioner (converted checkpoint slot)"),
    ("caption-qwen25vl-7b-tpu", "Qwen2.5-VL-7B/CosmosReason-class captioner (converted checkpoint slot)"),
    ("caption-qwen3moe-a3b-tpu", "Qwen3-MoE-A3B-class chat LM, expert-parallel (converted checkpoint slot)"),
    ("caption-qwen3vl-moe-a3b-tpu", "Qwen3-VL-MoE-A3B captioner: deepstack vision + sparse LM (converted checkpoint slot)"),
    ("caption-granite-4.0-h-micro-tpu", "Granite-4.0-H-Micro hybrid (Mamba-2 + attention) text LM (converted checkpoint slot)"),
    ("caption-deepseek-v2-ep8-tpu", "DeepSeek-V2, one chip's share of an 8-way expert-parallel deployment (converted checkpoint slot)"),
    ("caption-trinity-large-ep8-tpu", "Trinity-Large (afmoe), one chip's share of an 8-way expert-parallel deployment (converted checkpoint slot)"),
    ("caption-keye-vl2-a3b-ep8-tpu", "Keye-VL-2.0-30B-A3B's language model (learned sparse attention), one chip's share of an 8-way expert-parallel deployment (checkpoint slot; no converter yet)"),
    ("caption-olmo-hybrid-7b-tpu", "Olmo-Hybrid-7B hybrid (gated delta rule + attention) text LM (checkpoint slot; no converter yet)"),
    ("caption-olmo-hybrid-7b-pp2-tpu", "Olmo-Hybrid-7B, the first of two pipeline stages: 16 layers, table and head (checkpoint slot; no converter yet)"),
    ("caption-solar-open2-ep8-tpu", "Solar-Open2-250B (Kimi Delta Attention + gated attention over sparse experts), one chip's share of the first four-layer stage of an 8-way expert-parallel deployment (checkpoint slot; no converter yet)"),
    ("caption-lfm2-24b-a2b-pp5-tpu", "LFM2-24B-A2B (gated short convolutions + attention over 64 sparse experts, all held), the first of five pipeline stages: 10 layers, table and tied head (checkpoint slot; no converter yet)"),
    ("caption-mellum2-12b-a2.5b-pp4-tpu", "Mellum2-12B-A2.5B-Instruct (window and YaRN full attention over 64 sparse experts, all held), the first of four pipeline stages: 8 layers, table and untied head (checkpoint slot; no converter yet)"),
    ("t5-encoder-tpu", "text encoder for caption embeddings"),
    ("ocr-detector-tpu", "overlay-text region detector (Flax FCN)"),
    ("ocr-recognizer-tpu", "text recognizer CRNN with CTC decoding"),
    ("tracker-siamese-tpu", "learned single-object appearance tracker"),
]:
    register_model(_mid, _desc)


def weights_root() -> Path:
    return Path(os.environ.get(WEIGHTS_DIR_ENV, "/tmp/curate_model_weights"))


# Weights committed with the framework itself (e.g. the synthetically
# trained TransNetV2 checkpoint) — searched after the staging dir so a
# staged real checkpoint always wins.
REPO_WEIGHTS_DIR = Path(__file__).resolve().parent.parent.parent / "weights"


def local_dir_for(model_id: str) -> Path:
    return weights_root() / model_id


def find_checkpoint(model_id: str) -> Path | None:
    return find_model_file(model_id, "params.msgpack")


def find_model_file(model_id: str, filename: str) -> Path | None:
    """A staged/committed auxiliary model file (tokenizer vocab, config,
    ...), staging dir first so a pulled real asset wins over a committed
    test fixture."""
    for root in (weights_root(), REPO_WEIGHTS_DIR):
        p = root / model_id / filename
        if p.exists():
            return p
    return None


# Non-checkpoint files pulled alongside a caption model's weights: converted
# HF checkpoints are unusable without their exact-id tokenizer files
# (GPT-2-format pair for Qwen; tokenizer.json for T5/unigram checkpoints).
TOKENIZER_AUX_FILES = ("vocab.json", "merges.txt", "tokenizer.json")


def stage_weights_on_node(model_ids: list[str]) -> None:
    """Per-node staging hook (reference: one Ray task per node copies weights
    to local SSD, model_utils.py:139). Ensures dirs exist and, when
    ``CURATE_WEIGHTS_URI`` names a remote prefix, pulls each model's
    checkpoint down to node-local disk."""
    for mid in model_ids:
        local_dir_for(mid).mkdir(parents=True, exist_ok=True)
        maybe_pull_remote_weights(mid)


def maybe_pull_remote_weights(model_id: str) -> Path | None:
    """Pull ``{CURATE_WEIGHTS_URI}/{model_id}/params.msgpack`` to the local
    staging dir if it is not already there.

    Fan-out safe: concurrent worker processes on one node serialize on a
    file lock and land the bytes via atomic rename, so every node pays the
    download ONCE regardless of worker count (the reference's one-Ray-task-
    per-node staging property). A ``params.msgpack.sha256`` sidecar, when
    present, is verified before the rename — a truncated or corrupted pull
    never becomes a "staged checkpoint".
    """
    uri = os.environ.get(WEIGHTS_URI_ENV, "").rstrip("/")
    if not uri:
        return None
    dest = local_dir_for(model_id) / "params.msgpack"
    if dest.exists():
        return dest
    from cosmos_curate_tpu.storage.client import get_storage_client
    from cosmos_curate_tpu.utils.file_lock import file_lock

    dest.parent.mkdir(parents=True, exist_ok=True)
    lock_path = dest.parent / ".staging.lock"
    with file_lock(lock_path):
        if dest.exists():  # another worker won the race while we waited
            return dest
        remote = f"{uri}/{model_id}/params.msgpack"
        client = get_storage_client(remote)
        try:
            want = client.read_bytes(f"{remote}.sha256").decode().split()[0]
        except FileNotFoundError:
            want = ""
        import hashlib

        tmp = dest.with_suffix(".msgpack.tmp")
        digest = hashlib.sha256()
        chunk = 32 * 1024 * 1024
        # stream ranged reads through the hash into the temp file: a
        # multi-GB checkpoint never sits fully in RAM (the realistic VLM
        # case this plane exists for)
        read_range = getattr(client, "read_range", None)
        if getattr(client, "size", None) is None:
            read_range = None  # ranged streaming needs the object size too
        size = 0
        try:
            with tmp.open("wb") as fh:
                if read_range is not None:
                    total = client.size(remote)
                    for start in range(0, total, chunk):
                        part = read_range(remote, start, min(start + chunk, total) - 1)
                        digest.update(part)
                        fh.write(part)
                        size += len(part)
                else:
                    data = client.read_bytes(remote)
                    digest.update(data)
                    fh.write(data)
                    size = len(data)
        except FileNotFoundError:
            tmp.unlink(missing_ok=True)
            logger.info("no remote weights at %s", remote)
            return None
        except Exception:
            tmp.unlink(missing_ok=True)
            raise
        if want and digest.hexdigest() != want:
            tmp.unlink(missing_ok=True)
            raise WeightsIntegrityError(
                f"weights integrity check failed for {remote}: "
                f"sha256 {digest.hexdigest()} != manifest {want}"
            )
        tmp.rename(dest)  # atomic: readers never see a partial file
        logger.info("staged %s from %s (%d bytes)", model_id, remote, size)
        return dest


def maybe_pull_tokenizer_files(model_id: str) -> None:
    """Best-effort pull of the tokenizer sidecar files a converted HF
    checkpoint needs. Called only when a converted checkpoint is in play
    (hf_chat caption flavors; T5 after its checkpoint is staged) —
    repo-native flavors must not pay doomed remote GETs on every setup."""
    uri = os.environ.get(WEIGHTS_URI_ENV, "").rstrip("/")
    if not uri:
        return
    from cosmos_curate_tpu.storage.client import get_storage_client

    for name in TOKENIZER_AUX_FILES:
        dest = local_dir_for(model_id) / name
        if dest.exists():
            continue
        remote = f"{uri}/{model_id}/{name}"
        try:
            data = get_storage_client(remote).read_bytes(remote)
        except FileNotFoundError:
            continue
        dest.parent.mkdir(parents=True, exist_ok=True)
        tmp = dest.with_name(dest.name + ".tmp")
        tmp.write_bytes(data)
        tmp.rename(dest)
        logger.info("staged %s for %s", name, model_id)


def load_params(
    model_id: str,
    init_fn: Callable[[int], Any],
    *,
    seed: int = 0,
    require: bool = False,
) -> Any:
    """Load staged weights for ``model_id`` if present, else fall back to
    ``init_fn(seed)`` (random init) with a warning.

    ``require=True`` raises instead of falling back — for callers whose
    behavior would silently invert on random weights (e.g. a filter stage
    that must NOT fail open to discarding every clip).

    Format: flax msgpack (``flax.serialization``) — synchronous and
    self-contained; the tree structure comes from ``init_fn``."""
    from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

    # Every model load precedes that model's compiles; enabling here makes
    # repeat compiles (fresh processes, re-created stage instances) disk hits.
    enable_persistent_cache()
    ckpt = find_checkpoint(model_id)
    if ckpt is None:
        try:
            ckpt = maybe_pull_remote_weights(model_id)
        except WeightsIntegrityError:
            raise  # corruption must abort, not fall back to random init
        except Exception:
            logger.exception("remote weight staging failed for %s", model_id)
            ckpt = None
    if ckpt is not None:
        import flax.serialization

        logger.info("loading %s weights from %s", model_id, ckpt)
        template = init_fn(seed)
        data = ckpt.read_bytes()
        try:
            # canonical format: UNBOXED raw arrays (what converters emit
            # and save_params writes); sharding metadata is re-attached
            # from the init template so pjit layouts survive the roundtrip
            restored = flax.serialization.from_bytes(_unbox_tree(template), data)
            # from_bytes does NOT validate leaf shapes: a checkpoint staged
            # for other model shapes restores "successfully" and then dies
            # deep inside apply (observed: default-config transnet weights
            # loaded into TRANSNET_TINY_TEST). Check here so the mismatch
            # takes the architecture-mismatch path below.
            _assert_shapes_match(_unbox_tree(template), restored, model_id)
            return _rebox_like(template, restored)
        except (ValueError, KeyError, TypeError) as unboxed_err:
            # legacy format: checkpoints written before the unboxed
            # canonicalization serialized Partitioned leaves as
            # {'value': ...} state dicts — restore against the boxed
            # template keeps them loadable (shape-validated like the
            # canonical path: this fallback must not smuggle in a
            # wrong-architecture checkpoint the canonical path rejected)
            try:
                restored = flax.serialization.from_bytes(template, data)
                _assert_shapes_match(
                    _unbox_tree(template), _unbox_tree(restored), model_id
                )
                return restored
            except (ValueError, KeyError, TypeError):
                e = unboxed_err  # report the canonical-format error
            if require:
                raise RuntimeError(
                    f"staged weights at {ckpt} do not match {model_id}'s "
                    f"current architecture: {e}"
                ) from e
            # a checkpoint staged for different model shapes (e.g. an old
            # config) must not hard-crash the pipeline at stage setup
            logger.error(
                "staged weights at %s do not match %s's current architecture "
                "(%s); falling back to random init", ckpt, model_id, e,
            )
            return init_fn(seed)
    elif require:
        raise RuntimeError(
            f"no staged weights for {model_id} under "
            f"{local_dir_for(model_id) / 'params.msgpack'}"
        )
    logger.warning(
        "no staged weights for %s under %s — using seeded random init "
        "(stage a params.msgpack there for real inference)",
        model_id,
        local_dir_for(model_id) / "params.msgpack",
    )
    return init_fn(seed)


# checkpoint digests cached per (path, mtime): hashing a multi-GB
# checkpoint once per process is fine, once per written chunk is not
_PROVENANCE_CACHE: dict[tuple[str, int], str] = {}


def weights_provenance(model_id: str) -> str:
    """Where ``model_id``'s weights would come from RIGHT NOW:
    ``"checkpoint:<sha256-12>"`` when a checkpoint is staged/committed,
    ``"random"`` otherwise (the seeded-init fallback ``load_params`` warns
    about). Downstream consumers use this to refuse noise — e.g. the corpus
    index (dedup/index_store.py) never ingests random-provenance
    embeddings. Only positive results are cached (keyed by path + mtime),
    so weights staged later in-process are picked up."""
    ckpt = find_checkpoint(model_id)
    if ckpt is None:
        return "random"
    try:
        key = (str(ckpt), ckpt.stat().st_mtime_ns)
    except OSError:
        return "random"
    cached = _PROVENANCE_CACHE.get(key)
    if cached is not None:
        return cached
    import hashlib

    digest = hashlib.sha256()
    with ckpt.open("rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 22), b""):
            digest.update(chunk)
    prov = f"checkpoint:{digest.hexdigest()[:12]}"
    _PROVENANCE_CACHE[key] = prov
    return prov


def save_params(model_id: str, params: Any, *, root: Path | str | None = None) -> Path:
    """Write staged weights into the registry location (or under ``root``
    — e.g. the repo's committed weights/ tree). Single source of truth for
    the checkpoint layout: trainers must not re-implement it."""
    import flax.serialization

    base = Path(root) if root is not None else weights_root()
    ckpt = base / model_id / "params.msgpack"
    ckpt.parent.mkdir(parents=True, exist_ok=True)
    # Canonical checkpoint format: unboxed raw arrays. Partitioned sharding
    # boxes are process-local compile metadata, not weights — converters
    # emit raw arrays and load_params re-boxes from the init template.
    # Atomic publish: a trainer killed mid-write (watcher timeouts) must not
    # leave a truncated params.msgpack that later passes exists() checks.
    tmp = ckpt.with_name(ckpt.name + ".tmp")
    tmp.write_bytes(flax.serialization.to_bytes(_unbox_tree(params)))
    tmp.replace(ckpt)
    return ckpt


def _unbox_tree(tree: Any) -> Any:
    """Strip flax AxisMetadata boxes (nn.Partitioned) down to raw arrays."""
    import jax
    from flax import linen as fnn

    return jax.tree_util.tree_map(
        lambda x: x.unbox() if isinstance(x, fnn.Partitioned) else x,
        tree,
        is_leaf=lambda x: isinstance(x, fnn.Partitioned),
    )


def _assert_shapes_match(template: Any, restored: Any, model_id: str) -> None:
    """Raise ValueError naming the first leaf whose shape disagrees with the
    init template (both trees unboxed; same treedef by construction of the
    from_bytes target)."""
    import jax

    t_leaves = jax.tree_util.tree_leaves_with_path(template)
    r_leaves = jax.tree_util.tree_leaves(restored)
    if len(t_leaves) != len(r_leaves):
        raise ValueError(
            f"{model_id} checkpoint has {len(r_leaves)} leaves, "
            f"model expects {len(t_leaves)}"
        )
    for (path, t), r in zip(t_leaves, r_leaves):
        t_shape = getattr(t, "shape", None)
        r_shape = getattr(r, "shape", None)
        if t_shape != r_shape:
            raise ValueError(
                f"{model_id} checkpoint leaf {jax.tree_util.keystr(path)} has "
                f"shape {r_shape}, model expects {t_shape}"
            )


def _rebox_like(template: Any, values: Any) -> Any:
    """Wrap restored raw arrays back into the template's Partitioned boxes
    (positional zip over the flattened trees; structures match because the
    unboxed template produced the restore target)."""
    import jax
    from flax import linen as fnn

    t_leaves, treedef = jax.tree_util.tree_flatten(
        template, is_leaf=lambda x: isinstance(x, fnn.Partitioned)
    )
    v_leaves = jax.tree_util.tree_leaves(values)
    out = [
        t.replace_boxed(v) if isinstance(t, fnn.Partitioned) else v
        for t, v in zip(t_leaves, v_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)
