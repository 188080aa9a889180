"""Semantic dedup pipeline: embeddings parquet → pruned clip set.

Equivalent capability of the reference's dedup pipeline
(cosmos_curate/pipelines/video/dedup_pipeline.py + dedup/: RAFT/NCCL actor
pool + cuML k-means + per-cluster pruning; output layout
docs/curator/reference/VIDEO_PIPELINES.md:196-206). Here the collective
plane is the JAX mesh (dedup/kmeans.py); this module is the IO + orchestration:
read every embeddings parquet under the split output, run semantic_dedup,
write ``dedup/dedup_summary_<eps>.csv`` plus kept/removed id lists.

Fast path: when a persistent corpus index exists (``<input>/index`` or
``index_path`` — built in-pipeline by ``--corpus-index`` runs or via the
``index`` CLI), ``run_dedup`` QUERIES it instead of re-clustering —
O(probed shards) per batch against the whole curated corpus, not
O(N·K·iters) against this run alone (docs/DEDUP.md).
"""

from __future__ import annotations

import io
import os
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from cosmos_curate_tpu.dedup.kmeans import semantic_dedup
from cosmos_curate_tpu.storage.client import get_storage_client, read_bytes
from cosmos_curate_tpu.storage.writers import write_csv, write_json
from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# bounded fan-out for per-chunk parquet fetches (same knob the engine's
# worker fetch pool uses — one convention for storage-read concurrency)
FETCH_THREADS_ENV = "CURATE_WORKER_FETCH_THREADS"


@dataclass
class DedupPipelineArgs:
    input_path: str = ""  # split output root (with embeddings/<model>/)
    output_path: str = ""  # defaults to <input>/dedup
    embedding_model: str = ""  # "" = first found
    eps: float = 0.07
    n_clusters: int = 0  # 0 = sqrt(N)
    max_iters: int = 20
    use_mesh: bool = True
    # corpus-index fast path: query instead of re-cluster when one exists
    use_index: bool = True
    index_path: str = ""  # "" = <input>/index
    nprobe: int = 0  # 0 = index default


def load_embeddings(input_path: str, model: str = "") -> tuple[list[str], np.ndarray, str]:
    """Read all per-chunk embedding parquets under the split output.

    Chunk fetches+decodes fan out through a bounded thread pool
    (``CURATE_WORKER_FETCH_THREADS``, default 4): object-store GETs are
    latency-bound and pyarrow releases the GIL for the decode, so the
    serial per-chunk loop was pure wasted wall time on wide runs."""
    client = get_storage_client(input_path)
    root = f"{input_path.rstrip('/')}/embeddings"
    files = list(client.list_files(root, suffixes=(".parquet",)))
    if model:
        files = [f for f in files if f"/embeddings/{model}/" in f.path]
    if not files:
        raise FileNotFoundError(f"no embedding parquets under {root}")
    found_model = files[0].path.rsplit("/embeddings/", 1)[1].split("/", 1)[0]
    # one embedding space only: mixing models would compare incompatible
    # vectors (or crash on dim mismatch)
    files = [f for f in files if f"/embeddings/{found_model}/" in f.path]

    def _fetch(path: str) -> tuple[list[str], list[np.ndarray], int]:
        import pyarrow.parquet as pq

        data = read_bytes(path)
        table = pq.read_table(io.BytesIO(data))
        return (
            table.column("clip_uuid").to_pylist(),
            [np.asarray(v, np.float32) for v in table.column("embedding").to_pylist()],
            len(data),
        )

    workers = max(1, int(os.environ.get(FETCH_THREADS_ENV, "4") or 4))
    t0 = time.monotonic()
    if len(files) == 1 or workers == 1:
        parts = [_fetch(f.path) for f in files]
    else:
        with ThreadPoolExecutor(
            max_workers=min(workers, len(files)), thread_name_prefix="embed-fetch"
        ) as pool:
            parts = list(pool.map(_fetch, (f.path for f in files)))
    elapsed = time.monotonic() - t0
    ids: list[str] = []
    vecs: list[np.ndarray] = []
    total_bytes = 0
    for chunk_ids, chunk_vecs, nbytes in parts:
        ids.extend(chunk_ids)
        vecs.extend(chunk_vecs)
        total_bytes += nbytes
    try:
        from cosmos_curate_tpu.observability.stage_timer import record_object_plane

        record_object_plane(
            store_reads=len(files), store_read_bytes=total_bytes,
            store_read_wait_s=elapsed,
        )
    except Exception:  # metrics must never take down the load path
        logger.debug("object-plane recording failed", exc_info=True)
    logger.info(
        "loaded %d embeddings from %d parquets (%.1f MB) in %.2fs (%d fetch threads)",
        len(ids), len(files), total_bytes / 1e6, elapsed, min(workers, len(files)),
    )
    return ids, np.stack(vecs), found_model


def _open_index(args: DedupPipelineArgs, mesh, model: str):
    """The corpus index this run should query, or None (absent/disabled/
    incompatible). One embedding space per index: a model mismatch falls
    back to re-clustering instead of comparing incompatible vectors (or
    crashing on a dim mismatch)."""
    if not args.use_index:
        return None
    from cosmos_curate_tpu.dedup.corpus_index import CorpusIndex

    root = (args.index_path or f"{args.input_path.rstrip('/')}/index").rstrip("/")
    try:
        if not CorpusIndex.exists(root):
            return None
        index = CorpusIndex.open(root, mesh=mesh, metrics_name="run_dedup")
    except Exception as e:
        logger.warning("corpus index at %s unusable (%s); re-clustering", root, e)
        return None
    index_model = index.meta.get("model", "")
    if index_model and model and index_model != model:
        logger.warning(
            "corpus index at %s holds %r embeddings but this run used %r; "
            "re-clustering instead", root, index_model, model,
        )
        return None
    return index


def run_dedup(args: DedupPipelineArgs) -> dict:
    t0 = time.monotonic()
    out = (args.output_path or f"{args.input_path.rstrip('/')}/dedup").rstrip("/")
    ids, embeddings, model = load_embeddings(args.input_path, args.embedding_model)
    logger.info("dedup: %d embeddings (%s, dim %d)", len(ids), model, embeddings.shape[1])
    mesh = None
    if args.use_mesh:
        from cosmos_curate_tpu.parallel.mesh import best_effort_mesh

        mesh = best_effort_mesh()
    index = _open_index(args, mesh, model)
    if index is not None:
        # fast path: query the persistent index (which may already contain
        # this very run via in-pipeline fragments — incremental_dedup's
        # keep-first ordering handles self-matches) instead of re-running
        # Lloyd over everything
        from cosmos_curate_tpu.dedup.corpus_index import incremental_dedup

        method = "index_query"
        logger.info(
            "dedup fast path: querying corpus index at %s (%d indexed vectors)",
            index.store.root, index.meta.get("num_vectors", 0),
        )
        result = incremental_dedup(
            index, ids, embeddings, eps=args.eps, nprobe=args.nprobe or None
        )
    else:
        method = "recluster"
        result = semantic_dedup(
            embeddings,
            ids,
            n_clusters=args.n_clusters or None,
            eps=args.eps,
            iters=args.max_iters,
            mesh=mesh,
        )
    rows = [
        {
            "clip_uuid": cid,
            "action": "removed",
            "duplicate_of": result["duplicate_of"].get(cid, ""),
        }
        for cid in result["removed"]
    ] + [{"clip_uuid": cid, "action": "kept", "duplicate_of": ""} for cid in result["kept"]]
    write_csv(
        f"{out}/dedup_summary_{args.eps:g}.csv", rows, ["clip_uuid", "action", "duplicate_of"]
    )
    summary = {
        "embedding_model": model,
        "eps": args.eps,
        "method": method,
        "index_path": index.store.root if index is not None else "",
        "num_embeddings": len(ids),
        "num_kept": len(result["kept"]),
        "num_removed": len(result["removed"]),
        "elapsed_s": time.monotonic() - t0,
    }
    write_json(f"{out}/summary.json", summary)
    logger.info(
        "dedup done: kept %d / removed %d in %.1fs",
        summary["num_kept"], summary["num_removed"], summary["elapsed_s"],
    )
    return summary
