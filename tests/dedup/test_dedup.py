"""k-means + semantic dedup tests (incl. mesh-sharded k-means)."""

import numpy as np
import pytest

from cosmos_curate_tpu.dedup.kmeans import kmeans_fit, semantic_dedup


def _clustered_data(rng, n_per=40, centers=None, dim=16, spread=0.05):
    centers = centers if centers is not None else rng.standard_normal((3, dim))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    points = []
    for c in centers:
        points.append(c + spread * rng.standard_normal((n_per, dim)))
    return np.concatenate(points).astype(np.float32), centers


class TestKMeans:
    def test_recovers_clusters(self, rng):
        data, _ = _clustered_data(rng)
        _, assign = kmeans_fit(data, 3, iters=30, seed=1)
        # all points of one true cluster should share a label
        for g in range(3):
            labels = assign[g * 40 : (g + 1) * 40]
            assert len(np.unique(labels)) == 1
        assert len(np.unique(assign)) == 3

    def test_mesh_sharded_matches_single_device(self, rng):
        from cosmos_curate_tpu.parallel.mesh import best_effort_mesh

        data, _ = _clustered_data(rng, n_per=32)
        mesh = best_effort_mesh()
        _, a_single = kmeans_fit(data, 3, iters=30, seed=1)
        _, a_mesh = kmeans_fit(data, 3, iters=30, seed=1, mesh=mesh)
        # same grouping (labels may permute)
        for g in range(3):
            s = a_single[g * 32 : (g + 1) * 32]
            m = a_mesh[g * 32 : (g + 1) * 32]
            assert len(np.unique(s)) == 1
            assert len(np.unique(m)) == 1

    def test_k_clamped_to_n(self):
        data = np.eye(4, dtype=np.float32)
        centroids, assign = kmeans_fit(data, 10, iters=5)
        assert centroids.shape[0] == 4
        assert assign.shape == (4,)

    def test_single_device_mesh_degrades_to_identical_results(self, rng):
        """On a 1-device environment (a CPU box without the suite's forced
        8-device XLA flag) a mesh must add nothing: kmeans_fit(mesh=...)
        takes the single-device path and the result is bit-identical — the
        environment-sensitivity fix asserted directly."""
        import jax
        from jax.sharding import Mesh

        from cosmos_curate_tpu.parallel.axes import MESH_AXES

        data, _ = _clustered_data(rng, n_per=16)
        mesh = Mesh(
            np.array(jax.devices()[:1]).reshape(1, 1, 1, 1), axis_names=MESH_AXES
        )
        assert mesh.size == 1
        c0, a0 = kmeans_fit(data, 3, iters=10, seed=0)
        c1, a1 = kmeans_fit(data, 3, iters=10, seed=0, mesh=mesh)
        np.testing.assert_array_equal(a0, a1)
        np.testing.assert_array_equal(c0, c1)

    def test_mesh_the_batch_cannot_ride_raises(self, rng):
        """A multi-device mesh that cannot take the batch is an error: the
        run must not quietly use less of the machine than it was given."""

        class _BrokenMesh:
            size = 2  # looks multi-device, fails at shard time
            axis_names = ()

        data, _ = _clustered_data(rng, n_per=16)
        with pytest.raises(AttributeError):  # JAX's own, from the device put
            kmeans_fit(data, 3, iters=10, seed=0, mesh=_BrokenMesh())


class TestSemanticDedup:
    def test_exact_duplicates_removed(self, rng):
        base = rng.standard_normal((10, 16)).astype(np.float32)
        data = np.concatenate([base, base + 1e-5])  # 10 near-exact dupes
        ids = [f"c{i}" for i in range(20)]
        result = semantic_dedup(data, ids, eps=0.01, n_clusters=4)
        assert len(result["kept"]) == 10
        assert len(result["removed"]) == 10
        for removed_id, kept_id in result["duplicate_of"].items():
            assert kept_id in result["kept"]
            assert removed_id not in result["kept"]

    def test_distinct_items_survive(self, rng):
        data = np.eye(8, dtype=np.float32)  # orthogonal -> similarity 0
        result = semantic_dedup(data, [f"c{i}" for i in range(8)], eps=0.05)
        assert len(result["kept"]) == 8
        assert result["removed"] == []

    def test_empty(self):
        result = semantic_dedup(np.zeros((0, 4), np.float32), [])
        assert result["kept"] == [] and result["removed"] == []
