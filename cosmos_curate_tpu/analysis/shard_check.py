"""shardcheck: device-free verification of the TPU sharding/shape layer.

Third pillar of curate-lint next to graph_lint (pipeline-graph semantics)
and ast_lint (source hazards). The sharding layer is the whole point of the
TPU port — every NCCL plane became a ``jax.sharding.Mesh`` — yet a typo'd
axis name, a non-divisible batch, or a mis-specced ``shard_map`` otherwise
only fails minutes into a run on real chips. This pass catches all three at
build time, on CPU, with **zero device allocation**:

- every contract's ``PartitionSpec`` axes are checked against the declared
  ``MeshSpec`` (existence, one-use-per-spec, divisibility of the sharded
  dimension by the axis extent — including the ``shard_batch`` padding
  contract, which downgrades batch non-divisibility to a pad-waste
  warning);
- the forward itself runs under ``jax.eval_shape`` over a
  ``jax.sharding.AbstractMesh`` — ``shard_map`` axis names and per-device
  block shapes are verified by JAX's own tracing machinery, no TPUs (or
  even XLA compilation) involved;
- per-device bytes for replicated parameters are estimated from the
  abstract init, warning when a spec would blow the declared HBM budget.

Entry points: :func:`run_shard_check` (library),
``cosmos-curate-tpu lint --shard-check`` (CLI), and
``scripts/run_static_checks.sh`` (the CI gate). The ``run_pipeline``
pre-flight reuses :func:`mesh_tiling_errors` to validate stage-declared
``MeshSpec``\\ s against ``ClusterShape.num_tpu_chips``
(analysis/graph_lint.py). Defaults (mesh, HBM budget) come from
``[tool.curate-lint]`` in pyproject.toml.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Any, Callable, Sequence

from cosmos_curate_tpu.analysis.common import Finding, LintConfig, Severity, load_config
from cosmos_curate_tpu.parallel.axes import BATCH_AXES, MESH_AXES, SEQ
from cosmos_curate_tpu.parallel.mesh import MeshSpec

_SHARD_FILE = "<shard-check>"

# One dimension's sharding: unsharded, one axis, or a multi-axis product.
DimAxes = None | str | tuple[str, ...]


@dataclass(frozen=True)
class AbstractInput:
    """One input operand as (shape, dtype, per-dimension axis spec)."""

    shape: tuple[int, ...]
    dtype: str = "float32"
    spec: tuple[DimAxes, ...] = ()
    name: str = "input"


@dataclass(frozen=True)
class ShardContract:
    """One checkable sharded entry point — a model forward or a
    shard_map'd kernel.

    ``init`` abstractly builds the parameter tree (called under
    ``jax.eval_shape``; used for the HBM estimate and passed to
    ``forward``). ``forward`` is eval_shape'd with ``ShapeDtypeStruct``
    stand-ins for every input; when ``needs_mesh`` it receives an
    ``AbstractMesh`` built from the resolved ``MeshSpec`` as its first
    argument, so the real ``shard_map`` call sites are exercised.
    ``pads_batch`` marks entry points that ride ``shard_batch``'s pad
    contract: a non-divisible leading dim pads instead of failing, so it
    reports as a pad-waste warning rather than an error.
    """

    name: str
    inputs: tuple[AbstractInput, ...]
    forward: Callable[..., Any] | None = None
    init: Callable[[], Any] | None = None
    needs_mesh: bool = False
    pads_batch: bool = False
    where: str = ""  # source pointer shown in findings

    def describe(self) -> str:
        return f"{self.name} ({self.where})" if self.where else self.name


# -- mesh-spec arithmetic (no jax; shared with the run_pipeline pre-flight) --


def parse_mesh_spec(text: str) -> MeshSpec:
    """``"data=2,model=4"`` -> MeshSpec; unnamed axes default to extent 1
    (NOT -1: the lint pass must stay device-free, so nothing is left to
    absorb a discovered device count unless requested with an explicit
    ``axis=-1``)."""
    extents = {a: 1 for a in MESH_AXES}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        key, sep, value = part.partition("=")
        key = key.strip()
        if not sep or key not in MESH_AXES:
            raise ValueError(
                f"bad mesh spec entry '{part}': expected axis=extent with axis "
                f"in {', '.join(MESH_AXES)}"
            )
        try:
            extents[key] = int(value)
        except ValueError as e:
            raise ValueError(f"bad mesh extent in '{part}'") from e
    return MeshSpec(**extents)


def mesh_tiling_errors(spec: MeshSpec, num_chips: int) -> list[str]:
    """Why ``spec`` cannot tile a cluster of ``num_chips`` chips (empty =
    it can). Unlike ``MeshSpec.resolve`` this allows the mesh to cover a
    *subset* of the cluster (a stage's host-local mesh vs. the cluster
    total), so the check is divisibility, not equality."""
    errors = spec.extent_errors()
    if errors:
        return errors
    dims = spec.extents()
    fixed = math.prod(d for d in dims if d > 0)
    if fixed > num_chips:
        errors.append(
            f"mesh {dims} needs {fixed} chip(s) at its fixed axes but the "
            f"cluster declares {num_chips}"
        )
    elif num_chips % fixed:
        errors.append(
            f"mesh {dims} cannot tile {num_chips} chip(s): fixed-axes product "
            f"{fixed} does not divide the chip count"
        )
    return errors


def _resolve_mesh(
    spec: MeshSpec, num_devices: int | None, findings: list[Finding]
) -> dict[str, int] | None:
    """Concrete per-axis extents for the pass. With no device count given,
    the spec must be fully specified (no -1) — device discovery is exactly
    what this pass avoids. A fully-specified mesh may cover a *subset* of
    an explicit ``num_devices`` (a host-local mesh on a larger cluster):
    the requirement is tiling, not equality."""
    extents = spec.extents()
    has_free = any(d == -1 for d in extents)
    if num_devices is None:
        if has_free:
            findings.append(
                Finding(
                    _SHARD_FILE, 0, "shard-mesh-spec",
                    f"mesh {extents} has a -1 axis; pass an explicit "
                    "device count (--devices) or specify every extent",
                )
            )
            return None
        num_devices = math.prod(extents)
    if has_free:
        try:
            return spec.resolve(num_devices)
        except ValueError as e:
            findings.append(Finding(_SHARD_FILE, 0, "shard-mesh-spec", str(e)))
            return None
    errors = mesh_tiling_errors(spec, num_devices)
    if errors:
        findings.extend(
            Finding(_SHARD_FILE, 0, "shard-mesh-spec", msg) for msg in errors
        )
        return None
    return dict(zip(spec.axis_names(), extents))


# -- static spec checks ------------------------------------------------------


def _dim_axes(entry: DimAxes) -> tuple[str, ...]:
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def _check_input_spec(
    contract: ShardContract,
    inp: AbstractInput,
    mesh: dict[str, int],
    findings: list[Finding],
) -> bool:
    """Static half: axis existence, one-use-per-spec, divisibility.
    Returns False when errors make the abstract forward pointless."""
    ok = True
    label = f"{contract.describe()} input '{inp.name}'"
    if len(inp.spec) > len(inp.shape):
        findings.append(
            Finding(
                _SHARD_FILE, 0, "shard-rank-mismatch",
                f"{label}: spec has {len(inp.spec)} entries for a rank-"
                f"{len(inp.shape)} array {inp.shape}",
            )
        )
        return False
    used: set[str] = set()
    for dim, entry in enumerate(inp.spec):
        extent = 1
        for axis in _dim_axes(entry):
            if axis not in mesh:
                findings.append(
                    Finding(
                        _SHARD_FILE, 0, "shard-unknown-axis",
                        f"{label}: dim {dim} sharded over axis '{axis}' which "
                        f"is not in the mesh {dict(mesh)}"
                        + (
                            ""
                            if axis in MESH_AXES
                            else f" (nor the canonical registry: {', '.join(MESH_AXES)})"
                        ),
                    )
                )
                ok = False
                continue
            if axis in used:
                findings.append(
                    Finding(
                        _SHARD_FILE, 0, "shard-duplicate-axis",
                        f"{label}: axis '{axis}' used more than once in one spec",
                    )
                )
                ok = False
            used.add(axis)
            extent *= mesh[axis]
        if extent > 1 and inp.shape[dim] % extent:
            if contract.pads_batch and dim == 0:
                pad = (-inp.shape[dim]) % extent
                findings.append(
                    Finding(
                        _SHARD_FILE, 0, "shard-pad-waste",
                        f"{label}: batch dim {inp.shape[dim]} pads by {pad} row(s) "
                        f"to fill {extent} shard(s) "
                        f"({100.0 * pad / (inp.shape[dim] + pad):.0f}% padding waste)",
                        severity=Severity.WARNING,
                    )
                )
            else:
                findings.append(
                    Finding(
                        _SHARD_FILE, 0, "shard-indivisible",
                        f"{label}: dim {dim} of size {inp.shape[dim]} is not "
                        f"divisible by its sharding extent {extent} "
                        f"({'×'.join(_dim_axes(entry))})",
                    )
                )
                ok = False
    return ok


# -- abstract (eval_shape) checks -------------------------------------------


def _abstract_mesh(mesh: dict[str, int]):
    from jax.sharding import AbstractMesh

    shape_tuple = tuple(mesh.items())
    try:
        return AbstractMesh(shape_tuple)
    except TypeError:
        # newer JAX signature: AbstractMesh(axis_sizes, axis_names)
        return AbstractMesh(tuple(mesh.values()), tuple(mesh.keys()))


def _shape_structs(inputs: Sequence[AbstractInput]):
    import jax
    import jax.numpy as jnp

    return [jax.ShapeDtypeStruct(i.shape, jnp.dtype(i.dtype)) for i in inputs]


def _param_bytes(params: Any) -> int:
    import jax

    return sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params)
        if hasattr(leaf, "shape")
    )


def _check_abstract_flow(
    contract: ShardContract,
    mesh: dict[str, int],
    hbm_gb: float,
    findings: list[Finding],
) -> None:
    """Abstract half: eval_shape the init (HBM estimate) and the forward
    (shape flow + shard_map spec validation via AbstractMesh)."""
    import jax

    params = None
    if contract.init is not None:
        try:
            params = jax.eval_shape(contract.init)
        except Exception as e:
            findings.append(
                Finding(
                    _SHARD_FILE, 0, "shard-shape-flow",
                    f"{contract.describe()}: abstract init failed: "
                    f"{type(e).__name__}: {_trim(e)}",
                )
            )
            return
        if hbm_gb > 0:
            # Params are replicated unless a contract shards them, so the
            # per-device cost is the full tree. Activations are workload-
            # dependent and excluded; this is a floor, not a ceiling.
            per_device = _param_bytes(params)
            if per_device > hbm_gb * 2**30:
                findings.append(
                    Finding(
                        _SHARD_FILE, 0, "shard-hbm-budget",
                        f"{contract.describe()}: replicated params need "
                        f"{per_device / 2**30:.2f} GiB per device, over the "
                        f"declared HBM budget of {hbm_gb:g} GiB — shard them "
                        "(nn.with_partitioning) or shrink the model",
                        severity=Severity.WARNING,
                    )
                )
    if contract.forward is None:
        return
    forward = contract.forward
    if contract.needs_mesh:
        # the mesh is static configuration, not a traced operand: close
        # over it so eval_shape only sees abstract arrays
        amesh = _abstract_mesh(mesh)
        inner = forward
        forward = lambda *arrays: inner(amesh, *arrays)  # noqa: E731
    args: list[Any] = []
    if params is not None:
        args.append(params)
    args.extend(_shape_structs(contract.inputs))
    try:
        jax.eval_shape(forward, *args)
    except Exception as e:
        # jax.shard_map's own complaint about a spec axis the mesh lacks
        absent = re.search(r"_specs\S* refers to ('[^']+')", str(e))
        if isinstance(e, ValueError) and absent:
            findings.append(
                Finding(
                    _SHARD_FILE, 0, "shard-unknown-axis",
                    f"{contract.describe()}: shard_map names axis "
                    f"{absent.group(1)} which is absent from the mesh {dict(mesh)}",
                )
            )
            return
        findings.append(
            Finding(
                _SHARD_FILE, 0, "shard-shape-flow",
                f"{contract.describe()}: abstract forward failed: "
                f"{type(e).__name__}: {_trim(e)}",
            )
        )


def _trim(e: Exception, limit: int = 300) -> str:
    text = " ".join(str(e).split())
    return text if len(text) <= limit else text[: limit - 3] + "..."


# -- the contract registry ---------------------------------------------------


def default_contracts(mesh: dict[str, int]) -> list[ShardContract]:
    """Contracts for the repo's sharded entry points, sized from tiny test
    configs (shape semantics are identical to the production configs; the
    checks scale-invariantly cover axis names and divisibility).

    ``mesh`` lets sequence-parallel contracts pick batch/frame counts that
    exercise the declared ``seq`` extent rather than hardcoding one.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from cosmos_curate_tpu.models.diffusion_sr import DIFF_SR_TINY_TEST, DenoiserUNet, ddim_sample
    from cosmos_curate_tpu.models.super_resolution import SR_TINY_TEST, SRNet
    from cosmos_curate_tpu.parallel.ring_attention import ring_attention
    from cosmos_curate_tpu.parallel.sharding import shard_map
    from cosmos_curate_tpu.parallel.ulysses import ulysses_attention

    seq = max(1, mesh.get(SEQ, 1))
    contracts: list[ShardContract] = []

    # models/super_resolution.py — frames sharded over 'seq' (sp_size > 1)
    sr = SRNet(SR_TINY_TEST)

    def sr_init():
        return sr.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3), jnp.uint8))

    def sr_forward(amesh, params, frames):
        spec = P(SEQ, None, None, None)
        return shard_map(
            lambda p, f: sr.apply(p, f),
            mesh=amesh, in_specs=(P(), spec), out_specs=spec,
        )(params, frames)

    contracts.append(
        ShardContract(
            name="super-resolution-tpu",
            where="models/super_resolution.py",
            inputs=(
                AbstractInput((4 * seq, 16, 16, 3), "uint8", (SEQ,), name="frames"),
            ),
            init=sr_init,
            forward=sr_forward,
            needs_mesh=True,
        )
    )

    # models/diffusion_sr.py — window chunks sharded over 'seq'
    cfg = DIFF_SR_TINY_TEST
    dsr = DenoiserUNet(cfg)
    side = 16 * cfg.scale

    def dsr_init():
        dummy = jnp.zeros((cfg.window, side, side, 3), jnp.float32)
        return dsr.init(jax.random.PRNGKey(0), dummy, dummy, jnp.float32(0.5))

    def dsr_forward(amesh, params, conds, keys):
        def sample_chunks(p, c, k):
            return jax.vmap(lambda ci, ki: ddim_sample(dsr, p, ci, cfg, ki))(c, k)

        return shard_map(
            sample_chunks, mesh=amesh,
            in_specs=(P(), P(SEQ), P(SEQ)), out_specs=P(SEQ),
        )(params, conds, keys)

    contracts.append(
        ShardContract(
            name="diffusion-sr-tpu",
            where="models/diffusion_sr.py",
            inputs=(
                AbstractInput((seq, cfg.window, side, side, 3), "float32", (SEQ,), name="conds"),
                AbstractInput((seq, 2), "uint32", (SEQ,), name="keys"),
            ),
            init=dsr_init,
            forward=dsr_forward,
            needs_mesh=True,
        )
    )

    # parallel/ring_attention.py — sequence sharded over 'seq'
    attn_spec = (None, None, SEQ, None)
    attn_shape = (1, 4, 8 * seq, 8)
    contracts.append(
        ShardContract(
            name="ring-attention",
            where="parallel/ring_attention.py",
            inputs=tuple(
                AbstractInput(attn_shape, "float32", attn_spec, name=n)
                for n in ("q", "k", "v")
            ),
            forward=lambda amesh, q, k, v: ring_attention(q, k, v, amesh),
            needs_mesh=True,
        )
    )

    # parallel/ulysses.py — heads must also divide the 'seq' extent
    ul_shape = (1, 4 * seq, 8 * seq, 8)
    contracts.append(
        ShardContract(
            name="ulysses-attention",
            where="parallel/ulysses.py",
            inputs=tuple(
                AbstractInput(ul_shape, "float32", attn_spec, name=n)
                for n in ("q", "k", "v")
            ),
            forward=lambda amesh, q, k, v: ulysses_attention(q, k, v, amesh),
            needs_mesh=True,
        )
    )

    # parallel/sharding.py — the shard_batch host→device pad contract
    contracts.append(
        ShardContract(
            name="shard-batch",
            where="parallel/sharding.py",
            inputs=(AbstractInput((32, 512), "float32", (BATCH_AXES,), name="batch"),),
            pads_batch=True,
        )
    )

    # dedup/corpus_index.py — the IVF query matmul: queries sharded over the
    # batch axes (shard_batch pad contract), the probed corpus shard
    # replicated; the real shard_map call site is traced abstractly
    from cosmos_curate_tpu.dedup.corpus_index import query_matmul

    contracts.append(
        ShardContract(
            name="ivf-query",
            where="dedup/corpus_index.py",
            inputs=(
                AbstractInput((32, 64), "float32", (BATCH_AXES,), name="queries"),
                AbstractInput((128, 64), "float32", (), name="corpus"),
            ),
            forward=lambda amesh, q, c: query_matmul(amesh, q, c, top_k=4),
            needs_mesh=True,
            pads_batch=True,
        )
    )

    # models/vlm/paged_kv.py — the caption engine's block-table KV gather:
    # slot rows (tables) shard over the batch axes for data-parallel engine
    # replicas, the block pool is replicated; the real shard_map call site
    # is traced abstractly (same [L, NB, Hkv, bs, Dh] pool layout the
    # engine compiles, tiny extents)
    from cosmos_curate_tpu.models.vlm.paged_kv import paged_gather

    pool_shape = (2, 9, 2, 4, 8)  # [L, n_blocks, Hkv, block_size, Dh]
    contracts.append(
        ShardContract(
            name="vlm-paged-gather",
            where="models/vlm/paged_kv.py",
            inputs=(
                AbstractInput(pool_shape, "bfloat16", (), name="pool_k"),
                AbstractInput(pool_shape, "bfloat16", (), name="pool_v"),
                AbstractInput((8, 2), "int32", (BATCH_AXES,), name="tables"),
            ),
            forward=lambda amesh, pk, pv, t: paged_gather(amesh, pk, pv, t),
            needs_mesh=True,
            pads_batch=True,
        )
    )

    # ops/paged_attention.py — head-parallel paged attention: queries, the
    # KV block pools, and the output shard their Hkv dimension over the
    # model axis (tensor parallelism over KV heads); block tables and
    # per-row lengths replicate. The real shard_map call site is traced
    # abstractly on the XLA reference path (use_kernel=False keeps the
    # trace device-free).
    from cosmos_curate_tpu.models.vlm.paged_kv import paged_head_update
    from cosmos_curate_tpu.ops.paged_attention import paged_head_attention

    from cosmos_curate_tpu.parallel.axes import MODEL

    contracts.append(
        ShardContract(
            name="vlm-paged-head-attention",
            where="ops/paged_attention.py",
            inputs=(
                AbstractInput(
                    (8, 1, 2, 4, 8), "bfloat16",
                    (None, None, MODEL, None, None), name="q",
                ),
                AbstractInput(
                    pool_shape, "bfloat16", (None, None, MODEL, None, None),
                    name="pool_k",
                ),
                AbstractInput(
                    pool_shape, "bfloat16", (None, None, MODEL, None, None),
                    name="pool_v",
                ),
                AbstractInput((8, 2), "int32", (), name="tables"),
                AbstractInput((8,), "int32", (), name="write_index"),
                AbstractInput((8,), "int32", (), name="kv_len"),
            ),
            forward=lambda amesh, q, pk, pv, t, wi, kl: paged_head_attention(
                amesh, q, pk, pv, t, wi, kl, use_kernel=False
            ),
            needs_mesh=True,
        )
    )

    # models/vlm/paged_kv.py — the matching head-parallel pool scatter: each
    # model-axis shard writes a chunk's K/V into its own head plane through
    # the replicated block table.
    contracts.append(
        ShardContract(
            name="vlm-paged-head-scatter",
            where="models/vlm/paged_kv.py",
            inputs=(
                AbstractInput(
                    pool_shape, "bfloat16", (None, None, MODEL, None, None),
                    name="pool_k",
                ),
                AbstractInput(
                    pool_shape, "bfloat16", (None, None, MODEL, None, None),
                    name="pool_v",
                ),
                AbstractInput((8, 1, 2, 8), "bfloat16", (None, None, MODEL, None), name="k"),
                AbstractInput((8, 1, 2, 8), "bfloat16", (None, None, MODEL, None), name="v"),
                AbstractInput((8, 2), "int32", (), name="tables"),
                AbstractInput((8,), "int32", (), name="write_index"),
            ),
            forward=lambda amesh, pk, pv, k, v, t, wi: paged_head_update(
                amesh, pk, pv, k, v, t, wi, layer_index=1
            ),
            needs_mesh=True,
        )
    )
    return contracts


# -- entry points ------------------------------------------------------------


def check_contract(
    contract: ShardContract, mesh: dict[str, int], *, hbm_gb: float = 0.0
) -> list[Finding]:
    """All findings for one contract against resolved mesh extents."""
    findings: list[Finding] = []
    static_ok = True
    for inp in contract.inputs:
        static_ok &= _check_input_spec(contract, inp, mesh, findings)
    # A spec that already failed statically would only re-raise the same
    # problem (more opaquely) out of tracing — skip the abstract half.
    if static_ok:
        _check_abstract_flow(contract, mesh, hbm_gb, findings)
    return findings


def run_shard_check(
    mesh_spec: MeshSpec | None = None,
    *,
    num_devices: int | None = None,
    hbm_gb: float | None = None,
    contracts: Sequence[ShardContract] | None = None,
    config: LintConfig | None = None,
) -> list[Finding]:
    """The shape-flow pass: resolve the mesh, then check every contract.

    Defaults come from ``[tool.curate-lint]``: ``shard-mesh`` (e.g.
    ``"data=2,seq=2"``), ``shard-hbm-gb``. Explicit arguments win.
    """
    config = config or load_config()
    if mesh_spec is None:
        mesh_spec = (
            parse_mesh_spec(config.shard_mesh)
            if config.shard_mesh
            else MeshSpec(dcn=1, data=1, model=1, seq=1)
        )
    if hbm_gb is None:
        hbm_gb = config.shard_hbm_gb
    findings: list[Finding] = []
    mesh = _resolve_mesh(mesh_spec, num_devices, findings)
    if mesh is None:
        return findings
    for contract in contracts if contracts is not None else default_contracts(mesh):
        findings.extend(check_contract(contract, mesh, hbm_gb=hbm_gb))
    return findings
