"""Distributed k-means over a device mesh: the semantic-dedup core.

Equivalent capability of the reference's multi-GPU dedup
(cosmos_curate/pipelines/video/dedup/dedup_actor.py:197-237 — cuML
``KMeansMG`` over NCCL bootstrapped by RAFT, raft_actor.py:84-131). The
TPU-native re-design has no NCCL and no actor pool: embeddings are sharded
over the mesh's data axes, centroids are replicated, and each Lloyd
iteration is ONE jitted program — XLA inserts the cross-device ``psum`` for
the centroid sums exactly where the reference ran NCCL all-reduce. The hot
op (points x centroids similarity) is a single large matmul on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@functools.partial(jax.jit, static_argnames=("k", "valid"))
def _init_centroids(data: jax.Array, k: int, seed: int, valid: int) -> jax.Array:
    """Greedy k-means++-style seeding: first centroid sampled from the real
    rows, each next one the point FURTHEST (lowest max cosine similarity)
    from every centroid chosen so far. Uniform sampling of all k seeds made
    the result hinge on the PRNG's whims — two seeds landing in one true
    cluster is a bad local minimum Lloyd never escapes, and which seeds you
    get varies across jax versions/platforms (the tier-1 environment
    sensitivity this replaced). Rows beyond ``valid`` are mesh padding and
    masked out."""
    n = data.shape[0]
    mask = jnp.arange(n) < valid
    i0 = jax.random.choice(
        jax.random.PRNGKey(seed), n, p=mask / jnp.maximum(mask.sum(), 1)
    )
    cents = jnp.zeros((k, data.shape[1]), data.dtype).at[0].set(data[i0])
    best = data @ data[i0]  # max similarity to any chosen centroid

    def body(carry, j):
        cents, best = carry
        idx = jnp.argmin(jnp.where(mask, best, jnp.inf))
        c = data[idx]
        cents = cents.at[j].set(c)
        best = jnp.maximum(best, data @ c)
        return (cents, best), None

    (cents, _), _ = jax.lax.scan(body, (cents, best), jnp.arange(1, k))
    return cents


@jax.jit
def _lloyd_step(data, centroids, valid):
    """One Lloyd iteration. data: [N, D] (rows beyond ``valid`` are padding),
    centroids: [K, D]. Returns (new_centroids, assignments, shift)."""
    sims = data @ centroids.T  # [N, K] — the MXU matmul
    assign = jnp.argmax(sims, axis=1)
    mask = (jnp.arange(data.shape[0]) < valid)[:, None]
    one_hot = jax.nn.one_hot(assign, centroids.shape[0], dtype=data.dtype) * mask
    sums = one_hot.T @ data  # [K, D] — psum inserted here under sharding
    counts = one_hot.sum(axis=0)[:, None]
    new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), centroids)
    norms = jnp.linalg.norm(new, axis=1, keepdims=True)
    new = new / jnp.maximum(norms, 1e-8)
    shift = jnp.linalg.norm(new - centroids, axis=1).max()
    return new, assign, shift


def kmeans_fit(
    embeddings: np.ndarray,
    k: int,
    *,
    iters: int = 20,
    tol: float = 1e-4,
    seed: int = 0,
    mesh=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Fit spherical k-means; returns (centroids [K, D], assignments [N]).

    With ``mesh``, rows shard over its data axes and every iteration's
    centroid reduction rides the mesh collectives; without, single device.
    Embeddings are L2-normalized (cosine geometry, like the reference's
    cosine pruning).
    """
    n, d = embeddings.shape
    k = min(k, n)
    data = embeddings / np.maximum(np.linalg.norm(embeddings, axis=1, keepdims=True), 1e-8)
    valid = n
    # a 1-device mesh adds nothing but sharding overhead — same numerics.
    # A mesh the batch cannot ride is an error, not a reason to use less
    # of the machine quietly.
    if mesh is not None and mesh.size > 1:
        from cosmos_curate_tpu.parallel.sharding import shard_batch

        data, _pad = shard_batch(mesh, data.astype(np.float32))
    else:
        data = jnp.asarray(data, jnp.float32)

    centroids = _init_centroids(data, k, seed, valid)
    assign = None
    for i in range(iters):
        centroids, assign, shift = _lloyd_step(data, centroids, valid)
        if float(shift) < tol:
            logger.info("kmeans converged after %d iters (shift %.2e)", i + 1, float(shift))
            break
    return np.asarray(centroids), np.asarray(assign)[:n]


def semantic_dedup(
    embeddings: np.ndarray,
    ids: list[str],
    *,
    n_clusters: int | None = None,
    eps: float = 0.07,
    iters: int = 20,
    seed: int = 0,
    mesh=None,
) -> dict:
    """SemDeDup-style pruning (public technique; reference drives the same
    shape via cuML): cluster, then within each cluster drop items whose
    max cosine similarity to an already-kept item exceeds ``1 - eps``.

    Returns {"kept": [...], "removed": [...], "duplicate_of": {id: id},
    "assignments": np.ndarray}.
    """
    n = len(ids)
    if n == 0:
        return {"kept": [], "removed": [], "duplicate_of": {}, "assignments": np.zeros(0, int)}
    k = n_clusters or max(1, int(np.sqrt(n)))
    _, assign = kmeans_fit(embeddings, k, iters=iters, seed=seed, mesh=mesh)
    normed = embeddings / np.maximum(np.linalg.norm(embeddings, axis=1, keepdims=True), 1e-8)
    kept: list[str] = []
    removed: list[str] = []
    duplicate_of: dict[str, str] = {}
    threshold = 1.0 - eps
    for c in np.unique(assign):
        members = np.flatnonzero(assign == c)
        sims = normed[members] @ normed[members].T  # small per-cluster block
        kept_local: list[int] = []
        for j, m in enumerate(members):
            dup_idx = next(
                (kl for kl in kept_local if sims[j, kl] > threshold), None
            )
            if dup_idx is None:
                kept_local.append(j)
                kept.append(ids[m])
            else:
                removed.append(ids[m])
                duplicate_of[ids[m]] = ids[members[dup_idx]]
    return {
        "kept": kept,
        "removed": removed,
        "duplicate_of": duplicate_of,
        "assignments": assign,
    }
