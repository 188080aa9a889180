"""Mesh construction: the TPU-native answer to the reference's NCCL planes.

The reference has three communication planes (SURVEY.md §5): Ray object store
between stages, NCCL for dedup k-means, vLLM-internal NCCL for TP. Here every
collective plane is a `jax.sharding.Mesh`: XLA emits ICI collectives within a
slice and DCN collectives across slices — no NCCL anywhere.

Axis names come from the canonical registry (parallel/axes.py):
``dcn`` / ``data`` / ``model`` / ``seq`` — see its docstring for semantics.
``MeshSpec.resolve`` is the device-free half (pure arithmetic over extents),
so build-time checks (analysis/shard_check.py) validate the same logic the
run-time mesh constructors use.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from cosmos_curate_tpu.parallel.axes import DATA, MESH_AXES, MODEL, SEQ


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; -1 axes absorb remaining devices (like reshape)."""

    dcn: int = 1
    data: int = -1
    model: int = 1
    seq: int = 1

    def axis_names(self) -> tuple[str, ...]:
        return MESH_AXES

    def extents(self) -> tuple[int, ...]:
        return (self.dcn, self.data, self.model, self.seq)

    def extent_errors(self) -> list[str]:
        """Structural problems with the declared extents (empty = well
        formed). The single source of this validation: ``resolve`` raises
        on them and shardcheck's ``mesh_tiling_errors`` reports them."""
        dims = self.extents()
        if any(d == 0 or d < -1 for d in dims):
            return [f"mesh axis extents must be positive or -1, got {dims}"]
        if sum(1 for d in dims if d == -1) > 1:
            return ["at most one mesh axis may be -1"]
        return []

    def resolve(self, num_devices: int) -> dict[str, int]:
        """Concrete extent per axis over ``num_devices``, with the single
        -1 axis absorbing the remainder. Raises ``ValueError`` when the
        spec cannot tile the device count — the same arithmetic
        ``best_effort_mesh`` builds with and shardcheck validates
        device-free."""
        for msg in self.extent_errors():
            raise ValueError(msg)
        dims = list(self.extents())
        n_free = sum(1 for d in dims if d == -1)
        n_fixed = int(np.prod([d for d in dims if d > 0]))
        if n_free == 1:
            if num_devices % n_fixed:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes {dims}"
                )
            dims = [d if d > 0 else num_devices // n_fixed for d in dims]
        if int(np.prod(dims)) != num_devices:
            raise ValueError(f"mesh {dims} != {num_devices} devices")
        return dict(zip(self.axis_names(), dims))


def tpu_chip_count() -> int:
    """TPU chips JAX reports — the denominator of per-chip metrics and the
    autoscaler's chip budget. A CPU run has none and counts as one unit;
    a device query that fails raises (a guessed chip count hides the
    device)."""
    import jax

    return max(1, sum(d.platform == "tpu" for d in jax.devices()))


def local_mesh(axis_names: tuple[str, ...] = (DATA, MODEL), shape: tuple[int, ...] | None = None):
    """Mesh over this process's local devices (the ``entire_tpu_host`` worker
    claim). Default: all chips on one ``model`` axis when shape is None and
    one axis name given, else data×model split with model = all chips."""
    import jax
    from jax.sharding import Mesh

    devices = jax.local_devices()
    n = len(devices)
    if shape is None:
        if len(axis_names) == 1:
            shape = (n,)
        elif len(axis_names) == 2:
            shape = (1, n)
        else:
            raise ValueError("provide an explicit shape for >2 axes")
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} != {n} local devices")
    return Mesh(np.array(devices).reshape(shape), axis_names=axis_names)


def seq_mesh(n: int):
    """Mesh over the first ``n`` visible devices on the ``seq`` axis — the
    sequence-parallel plane the windowed SR models shard_map over. Central
    so device selection is not re-derived (and hardcoded) per model; see
    the hardcoded-device-count lint rule."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if n > len(devices):
        raise ValueError(f"seq mesh needs {n} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n]), axis_names=(SEQ,))


def model_mesh(n: int, *, what: str = "a model mesh"):
    """Mesh over the first ``n`` local devices on the ``model`` axis — the
    tensor-parallel plane a caption flavor of ``model_chips=n`` is served
    over (models/vlm/model.py ``FlavorSpec``). One constructor, so the
    caption stage and the chip smoke build the mesh the benchmark's driver
    builds and share one ``SharedCaptionEngine`` key. ``what`` names the
    asker in the error a host with fewer chips gets."""
    import jax
    from jax.sharding import Mesh

    devices = jax.local_devices()
    if n > len(devices):
        raise ValueError(
            f"{what} is served over {n} chips of one host (its parameters are "
            f"split over them); this host has {len(devices)}"
        )
    return Mesh(np.array(devices[:n]), axis_names=(MODEL,))


def best_effort_mesh(spec: MeshSpec | None = None):
    """Build the full (dcn, data, model, seq) mesh over all visible devices,
    resolving -1 axes. Single-host single-chip degenerates to (1,1,1,1)."""
    import jax
    from jax.sharding import Mesh

    spec = spec or MeshSpec()
    devices = jax.devices()
    dims = spec.resolve(len(devices))
    return Mesh(
        np.array(devices).reshape(tuple(dims.values())), axis_names=spec.axis_names()
    )
