"""Sharding helpers: NamedSharding construction and host→device batch placement.

The scaling-book recipe: pick a mesh, annotate shardings on the big tensors,
let XLA insert collectives. These helpers keep annotations terse at stage
call sites, and centralize the host→device transfer (the critical data path
feeding chips from CPU prep stages, SURVEY.md §7 hard part 3).

Axis names come from parallel/axes.py; ``shard_map`` here is the front
door every shard_map call site uses (``jax.shard_map`` with this repo's
``check_vma=False`` default).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from cosmos_curate_tpu.parallel.axes import BATCH_AXES


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map``. Accepts ``jax.sharding.AbstractMesh`` too, so
    specs can be shape-checked under ``jax.eval_shape`` with zero devices —
    the mechanism behind ``cosmos-curate-tpu lint --shard-check``."""
    import jax

    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def named_sharding(mesh, *spec_axes: str | tuple[str, ...] | None):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*spec_axes))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def spec_sharding(mesh, spec):
    """NamedSharding for a PartitionSpec that may name axes ``mesh`` lacks:
    those dims replicate (a model-only mesh still places a spec written for
    the full (dcn, data, model, seq) registry)."""
    from jax.sharding import NamedSharding, PartitionSpec

    def keep(entry):
        if isinstance(entry, tuple):
            return tuple(a for a in entry if a in mesh.axis_names) or None
        return entry if entry in mesh.axis_names else None

    return NamedSharding(mesh, PartitionSpec(*(keep(e) for e in spec)))


def place_partitioned(mesh, params, specs):
    """Device-put a parameter tree over ``mesh`` by ``specs`` — the
    ``nn.get_partition_spec`` tree of the model's ``nn.with_partitioning``
    annotations. ``params`` may be boxed or plain (a loaded checkpoint)."""
    import flax.linen as nn
    import jax

    return jax.tree.map(
        lambda x, spec: jax.device_put(x, spec_sharding(mesh, spec)),
        nn.unbox(params),
        specs,
    )


def batch_sharding(mesh, batch_axes: str | tuple[str, ...] = BATCH_AXES):
    """Sharding for a [B, ...] batch: leading dim over the data axes.
    Axes absent from the mesh are dropped; with none left the batch is
    replicated (the single-axis / model-only mesh fallback)."""
    axes = tuple(a for a in _axes_tuple(batch_axes) if a in mesh.axis_names)
    return named_sharding(mesh, axes if axes else None)


def batch_shard_count(mesh, batch_axes: str | tuple[str, ...] = BATCH_AXES) -> int:
    """How many ways ``batch_sharding`` splits the leading dim on ``mesh``."""
    return int(
        np.prod([mesh.shape[a] for a in _axes_tuple(batch_axes) if a in mesh.axis_names])
    ) or 1


def shard_batch(mesh, tree: Any, batch_axes: str | tuple[str, ...] = BATCH_AXES):
    """Device-put a host pytree of [B, ...] numpy arrays, batch-sharded.

    Pads the batch up to a multiple of the data-axis extent (model code must
    mask or slice off padding; the returned pad count says how much was
    added — ``unshard_batch`` strips it). Every leaf must agree on the
    leading dim: a silently-wrong per-leaf pad is worse than a loud error.
    """
    import jax

    leaves = jax.tree.leaves(tree)
    if not leaves:
        raise ValueError("shard_batch: empty pytree — nothing to shard")
    batch_dims = {getattr(x, "shape", ())[:1] for x in leaves}
    if () in batch_dims:
        raise ValueError("shard_batch: scalar leaf has no batch dimension")
    if len(batch_dims) > 1:
        sizes = sorted(b[0] for b in batch_dims)
        raise ValueError(
            f"shard_batch: leaves disagree on the leading batch dim: {sizes}"
        )
    sharding = batch_sharding(mesh, batch_axes)
    n_shards = batch_shard_count(mesh, batch_axes)

    def _pad(x):
        b = x.shape[0]
        rem = (-b) % n_shards
        if rem:
            pad = np.zeros((rem, *x.shape[1:]), x.dtype)
            x = np.concatenate([x, pad], axis=0)
        return x

    padded = jax.tree.map(_pad, tree)
    pad_count = (-leaves[0].shape[0]) % n_shards
    return jax.device_put(padded, sharding), pad_count


def unshard_batch(tree: Any, pad_count: int) -> Any:
    """Host-side inverse of ``shard_batch``: gather each leaf back to numpy
    and strip the ``pad_count`` padding rows it appended."""
    import jax

    def _cut(x):
        x = np.asarray(x)
        return x[: x.shape[0] - pad_count] if pad_count else x

    return jax.tree.map(_cut, tree)


def _axes_tuple(batch_axes) -> tuple[str, ...]:
    return batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
