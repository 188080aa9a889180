"""The Pallas kernels compiled for a DESCRIBED TPU v5e, at real widths.

Interpret mode checks a kernel's arithmetic and nothing about its layout:
block shapes the chip's compiler refuses (a K/V tile that slices one head
out of the second-to-last dimension, a reshape across sublane tiles, too
much VMEM) pass every CPU test. The TPU compiler is installed next to the
CPU one and compiles for a chip that is described, not attached, so these
cases cost no chip time: a kernel change that the chip would refuse fails
here. Nothing runs — results are ``chip_smoke.py``'s business.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one chip of a described (not attached) v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it off around these cases
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


# (Hkv, G, D): the default `base` captioner, Qwen2-VL-2B, one chip's share
# of Qwen2.5-VL-7B over model=4 (one KV head, 7 query heads), and the
# Qwen3 MoE flavors (four KV heads at D 128: the widest page the decode
# kernel copies for itself)
WIDTHS = {
    "base": (8, 2, 64),
    "qwen2vl-2b": (2, 6, 128),
    "qwen25vl-7b-shard": (1, 7, 128),
    "qwen3-moe-a3b": (4, 8, 128),
}
B, T, S, BS, LAYERS, POOL_BLOCKS = 8, 256, 1024, 16, 2, 600


def _paged_decode(hk, g, d, arg, rows=B, lane=S):
    from cosmos_curate_tpu.ops.paged_attention import _paged_decode as fn

    pool = arg((LAYERS, POOL_BLOCKS, hk, BS, d), jnp.bfloat16)
    return (
        functools.partial(fn, layer_index=1, sm_scale=d**-0.5, interpret=False),
        (arg((rows, hk, g, d), jnp.bfloat16), pool, pool,
         arg((rows, lane // BS), jnp.int32), arg((rows,), jnp.int32)),
    )


def _paged_prefill(hk, g, d, arg, rows=B, lane=S):
    from cosmos_curate_tpu.ops.paged_attention import _paged_prefill as fn

    pool = arg((LAYERS, POOL_BLOCKS, hk, BS, d), jnp.bfloat16)
    return (
        functools.partial(fn, layer_index=1, sm_scale=d**-0.5, block_q=128, interpret=False),
        (arg((rows, T, hk, g, d), jnp.bfloat16), pool, pool,
         arg((rows, lane // BS), jnp.int32), arg((rows,), jnp.int32), arg((rows,), jnp.int32)),
    )


def _flash(hk, g, d, arg):
    from cosmos_curate_tpu.ops.flash_attention import flash_attention as fn

    # 2049 = InternVideo2's 8x256+1 tokens: the ragged tail pads in-kernel
    x = arg((1, hk * g, 2049, d), jnp.bfloat16)
    return functools.partial(fn, interpret=False), (x, x, x)


KERNELS = {
    "paged_decode": _paged_decode,
    "paged_prefill": _paged_prefill,
    "flash_attention": _flash,
}


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(v5e, kernel, widths):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = KERNELS[kernel](*WIDTHS[widths], arg)
    compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip would
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "kernel,rows,widths",
    [
        *(pytest.param("paged_decode", 4, w, id=f"decode-4-rows-{w}") for w in sorted(WIDTHS)),
        pytest.param("paged_decode", 2, "qwen25vl-7b-shard", id="decode-2-rows-tp4-cell"),
        *(pytest.param("paged_prefill", 1, w, id=f"prefill-1-row-{w}") for w in sorted(WIDTHS)),
    ],
)
def test_paged_kernels_compile_at_the_widest_lane(v5e, kernel, rows, widths):
    """The 4096 lane is the longest block table a cell runs, 256 entries a
    row in scalar memory, at the shapes the cells give the kernels there:
    a decode step over the lane's four rows (two in the tp4 cell), a
    256-token chunk of one row. The compiler refuses a kernel that wants
    more VMEM than a call may have, so compiling is the check (the page
    buffers are sized from the page, not from the table)."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = KERNELS[kernel](*WIDTHS[widths], arg, rows=rows, lane=4096)
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", ["paged_decode", "paged_prefill"])
def test_custom_call_is_named_as_the_benchmark_expects(v5e, kernel):
    """A traced benchmark run finds the paged kernels among the device's
    operations by the instruction name of their ``tpu_custom_call``: the name
    of the jitted function that wraps the ``pallas_call``. Renaming a wrapper
    fails here, on the CPU, and not in a traced run on the chip. (In this
    file, not beside the harness: one file describes the chip, see above.)"""
    from perfbench import trace_reduce
    from perfbench.drivers.caption_engine import KERNELS as TRACE_PATTERNS

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = KERNELS[kernel](*WIDTHS["qwen2vl-2b"], arg)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [
        trace_reduce.instruction(line.strip())  # as the reducer reads an event's name
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert calls, "the compiled program holds no tpu_custom_call"
    assert all(re.search(TRACE_PATTERNS[kernel], name) for name in calls), calls
    others = [rx for key, rx in TRACE_PATTERNS.items() if key != kernel]
    assert not any(re.search(rx, name) for rx in others for name in calls)


def _write_then_attend(t, hk, g, d, layers, arg):
    """The paged branch of ``DecoderLayer`` over ``layers`` layers with the
    matmuls left out: the write of a chunk's K/V through the block table,
    then the paged kernel on the written pools, pools donated."""
    from cosmos_curate_tpu.models.vlm.paged_kv import paged_update
    from cosmos_curate_tpu.ops.paged_attention import _paged_decode, _paged_prefill

    def program(pool_k, pool_v, q, k, v, tables, write_index):
        for layer in range(layers):
            pool_k, pool_v = paged_update(
                pool_k, pool_v, k, v, tables, write_index, layer_index=layer
            )
            if t == 1:
                attn = _paged_decode(
                    q[:, 0], pool_k, pool_v, tables, write_index + 1,
                    layer_index=layer, sm_scale=d**-0.5, interpret=False,
                )[:, None]
            else:
                attn = _paged_prefill(
                    q, pool_k, pool_v, tables, write_index, write_index + t,
                    layer_index=layer, sm_scale=d**-0.5, block_q=128, interpret=False,
                )
            # the next layer's q, k and v depend on this layer's attention
            q, k, v = q + attn, k + attn[:, :, :, 0], v + attn[:, :, :, 1]
        return q, pool_k, pool_v

    rows = B if t == 1 else 1  # a decode step over the lane; a one-row chunk
    pool = arg((layers, POOL_BLOCKS, hk, BS, d), jnp.bfloat16)
    chunk = arg((rows, t, hk, d), jnp.bfloat16)
    args = (pool, pool, arg((rows, t, hk, g, d), jnp.bfloat16), chunk, chunk,
            arg((rows, S // BS), jnp.int32), arg((rows,), jnp.int32))
    return jax.jit(program, donate_argnums=(0, 1)).lower(*args).compile().as_text()


def _pool_copies(hlo, pool_shape):
    """(names of the instructions that copy a pool-shaped array, those of
    them that are an operand of a ``tpu_custom_call``) in a compiled
    program's text."""
    shape = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    copies = {
        line.split("=")[0].strip().lstrip("%")
        for line in hlo.splitlines()
        if re.search(r"= " + re.escape(shape) + r"\{[^}]*\} copy\(", line)
    }
    feeding = [
        name
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        for name in re.findall(r"%([\w.\-]+)", line.split("custom-call(")[1].split(")")[0])
        if name in copies
    ]
    return copies, feeding


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("t", [1, T], ids=["decode", "prefill-T256"])
def test_pool_keeps_the_kernels_layout_through_the_write(v5e, t, widths):
    """The K/V write leaves the pool in the layout the paged kernels' operand
    demands, so no layer pays a whole-pool relayout ``copy`` between its write
    and its kernel (PR 25; before it: ``2 * layers + 2`` copies of the pool a
    program, 70-81% of the device's time). A compile-time property has no
    run-time counter: this is the mechanism's counter, and the ``copy`` row
    of a traced benchmark run is its reading on the chip."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    hk, g, d = WIDTHS[widths]
    counts = []
    for layers in (LAYERS, 2 * LAYERS):
        copies, feeding = _pool_copies(
            _write_then_attend(t, hk, g, d, layers, arg), (layers, POOL_BLOCKS, hk, BS, d)
        )
        assert not feeding, f"{layers} layers: pool copies feed the kernels: {feeding}"
        counts.append(len(copies))
    assert counts[1] <= counts[0], f"pool-shaped copies grow with depth: {counts}"
    assert counts[0] <= 4, counts  # `base`: four at the program's boundary; 2B: none
