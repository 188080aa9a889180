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


# (Hkv, G, D): the default `base` captioner and Granite-4.0-H-Micro (D 64:
# two KV heads a pool row, so the kernels see four heads of 128 lanes),
# Qwen2-VL-2B, one chip's share of Qwen2.5-VL-7B over model=4 (one KV head,
# 7 query heads), the Qwen3 MoE flavors (four KV heads at D 128: the widest
# page the decode kernel copies for itself), and the test-size flavors (D 16
# with two KV heads makes no whole tile: the decode kernel's BlockSpec form,
# which a chip serves too)
WIDTHS = {
    "base": (8, 2, 64),
    "granite-4.0-h-micro": (8, 4, 64),
    "qwen2vl-2b": (2, 6, 128),
    "qwen25vl-7b-shard": (1, 7, 128),
    "qwen3-moe-a3b": (4, 8, 128),
    "tiny-test": (2, 2, 16),
}
B, T, S, BS, LAYERS, POOL_BLOCKS = 8, 256, 1024, 16, 2, 600


def _pool_shape(hk, d, layers=LAYERS):
    """The pool as the engine makes it: ``init_block_pool``'s own packing."""
    from cosmos_curate_tpu.models.vlm.model import VLMConfig
    from cosmos_curate_tpu.models.vlm.paged_kv import init_block_pool

    cfg = VLMConfig(n_layers=layers, n_heads=hk, n_kv_heads=hk, head_dim=d)
    pool_k, _ = jax.eval_shape(lambda: init_block_pool(cfg, POOL_BLOCKS, BS))
    return pool_k.shape


def _paged(t, hk, g, d, arg, rows=B, lane=S):
    """``paged_attention`` as the model calls it, on the chip's side of its
    choice: grouped queries at the model's widths, the pool packed (or not)
    as the engine packs it."""
    from cosmos_curate_tpu.ops.paged_attention import paged_attention as fn

    pool = arg(_pool_shape(hk, d), jnp.bfloat16)
    return (
        functools.partial(fn, layer_index=1, use_kernel=True, interpret=False),
        (arg((rows, t, hk, g, d), jnp.bfloat16), pool, pool,
         arg((rows, lane // BS), jnp.int32), arg((rows,), jnp.int32), arg((rows,), jnp.int32)),
    )


_paged_decode = functools.partial(_paged, 1)
_paged_prefill = functools.partial(_paged, T)


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
    buffers are sized from the page and the block of queries, not from the
    table)."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = KERNELS[kernel](*WIDTHS[widths], arg, rows=rows, lane=4096)
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "kernel,rows,lane,blocks",
    [
        pytest.param("paged_decode", 24, 12288, None, id="decode-24-rows-of-12288"),
        pytest.param("paged_decode", 16, 4096, None, id="decode-16-rows-of-4096"),
        pytest.param("paged_prefill", 4, 12288, None, id="prefill-4-rows-of-12288"),
        pytest.param("paged_prefill", 1, 4096, None, id="prefill-1-row-of-4096"),
        pytest.param("paged_prefill", 4, 12288, 16, id="prefill-4-rows-of-12288-in-blocks-of-16"),
    ],
)
@pytest.mark.parametrize("window", [4096, None], ids=["window-layer", "full-layer"])
def test_windowed_kernels_compile_at_trinitys_widths(v5e, kernel, rows, lane, blocks, window):
    """Trinity-Large's attention (8 KV heads x 6 query heads x 128) at its two
    lanes, the first past 4,096 positions, in the blocks of 128 the engine takes
    for such lanes: 96 table entries a row in scalar memory, a page of 128 keys
    a copy, the window's first-page arithmetic in the loops. And the prefill
    chunk in blocks of 16 too, 768 entries a row: the size the engine's rule was
    written to keep the old one-page-a-grid-step kernel away from, which the
    kernel that walks groups of pages no longer minds (PERF.md PR 39)."""

    from cosmos_curate_tpu.ops.paged_attention import paged_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    from cosmos_curate_tpu.models.vlm.engine import default_block_size

    hk, g, d, t = 8, 6, 128, 1 if kernel == "paged_decode" else T
    bs = blocks or default_block_size(((4096, 16), (12288, 24)))
    pool = arg((4, 12800 // bs, hk, bs, d), jnp.bfloat16)
    fn = functools.partial(paged_attention, layer_index=1, use_kernel=True, interpret=False, window=window)
    args = (
        arg((rows, t, hk, g, d), jnp.bfloat16), pool, pool, arg((rows, lane // bs), jnp.int32),
        arg((rows,), jnp.int32), arg((rows,), jnp.int32),
    )
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
    then the paged kernel on the written pools, pools donated and packed as
    the engine packs them. Returns (compiled text, the pool's shape)."""
    from cosmos_curate_tpu.models.vlm.paged_kv import paged_update
    from cosmos_curate_tpu.ops.paged_attention import paged_attention

    def program(pool_k, pool_v, q, k, v, tables, write_index):
        for layer in range(layers):
            pool_k, pool_v = paged_update(
                pool_k, pool_v, k, v, tables, write_index, layer_index=layer
            )
            attn = paged_attention(
                q, pool_k, pool_v, tables, write_index, write_index + t,
                layer_index=layer, use_kernel=True, interpret=False,
            )
            # the next layer's q, k and v depend on this layer's attention
            q, k, v = q + attn, k + attn[:, :, :, 0], v + attn[:, :, :, 1]
        return q, pool_k, pool_v

    rows = B if t == 1 else 1  # a decode step over the lane; a one-row chunk
    shape = _pool_shape(hk, d, layers)
    pool = arg(shape, jnp.bfloat16)
    chunk = arg((rows, t, hk, d), jnp.bfloat16)
    args = (pool, pool, arg((rows, t, hk, g, d), jnp.bfloat16), chunk, chunk,
            arg((rows, S // BS), jnp.int32), arg((rows,), jnp.int32))
    return jax.jit(program, donate_argnums=(0, 1)).lower(*args).compile().as_text(), shape


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
    of a traced benchmark run is its reading on the chip. At ``D`` = 64 the
    row is a whole tile only because two KV heads share it (PR 34; one head
    a row cost four copies here at the program's boundary and twenty in
    Granite-4.0-H's decode program under memory pressure)."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    hk, g, d = WIDTHS[widths]
    counts = []
    for layers in (LAYERS, 2 * LAYERS):
        copies, feeding = _pool_copies(*_write_then_attend(t, hk, g, d, layers, arg))
        assert not feeding, f"{layers} layers: pool copies feed the kernels: {feeding}"
        counts.append(len(copies))
    if _pool_shape(hk, d)[-1] % 128 == 0:
        # every served width: `base` and Granite two heads a row, the Qwens one
        assert counts == [0, 0], counts
    else:
        # a row under a lane tile (the test-size flavors): XLA keeps the
        # pool compressed at the program's boundary, two copies in and two
        # out, and none a layer
        assert counts[1] <= counts[0] <= 4, f"pool-shaped copies grow with depth: {counts}"


# -- the latent pool (DeepSeek-V2: one row of 640 lanes a token, 128 heads) ---

MLA_HEADS, MLA_WIDTH, MLA_VALUES = 128, 640, 512


def _latent_rows(arg, rows, t, lane, width=MLA_WIDTH):
    from cosmos_curate_tpu.ops.latent_attention import _latent_rows as fn

    return (
        functools.partial(
            fn, layer_index=1, sm_scale=0.1147, v_width=MLA_VALUES, rows_per_table=t, interpret=False
        ),
        (arg((rows * t, MLA_HEADS, width), jnp.bfloat16),
         arg((LAYERS, POOL_BLOCKS, 1, BS, width), jnp.bfloat16),
         arg((rows, lane // BS), jnp.int32), arg((rows * t,), jnp.int32)),
    )


@pytest.mark.parametrize(
    "rows,t,lane,name",
    [
        pytest.param(256, 1, 1024, "mla_decode", id="decode-256-rows-short-lane"),
        pytest.param(8, 1, 4096, "mla_decode", id="decode-8-rows-long-lane"),
        pytest.param(8, 256, 1024, "mla_prefill", id="prefill-8-rows-T256"),
        pytest.param(1, 256, 4096, "mla_prefill", id="prefill-1-row-long-lane"),
    ],
)
def test_latent_kernel_compiles_for_v5e_under_its_trace_name(v5e, rows, t, lane, name):
    """The shapes the DeepSeek-V2 cell gives the kernel: a decode step over a
    lane's rows (256 tables of 64 entries in scalar memory), a prefill group
    of 8 chunks as 2,048 query rows. The custom call carries the name the
    benchmark's trace reduction looks for."""
    from perfbench import trace_reduce

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = _latent_rows(arg, rows, t, lane)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [
        trace_reduce.instruction(line.strip().removeprefix("ROOT "))  # alone, it is the program's root
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert calls and all(re.search(rf"^{name}(\.\d+)?$", c) for c in calls), calls


def test_mosaic_slices_no_latent_row_of_576_lanes(v5e):
    """Why the row is padded to 640: the chip stores a 576-wide array in 640
    lanes anyway, and Mosaic refuses to slice it (PERF.md, PR 33)."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = _latent_rows(arg, 8, 1, 1024, width=576)
    with pytest.raises(ValueError, match="not whole lane tiles"):
        jax.jit(fn).lower(*args)


@pytest.mark.parametrize("t", [1, T], ids=["decode", "prefill-T256"])
def test_latent_pool_keeps_the_kernels_layout_through_the_write(v5e, t):
    """``latent_update`` leaves the one array in the layout the kernel's
    operand demands: no layer pays a pool-shaped ``copy`` between its write
    and its kernel, and none appears with depth."""
    from cosmos_curate_tpu.models.vlm.paged_kv import latent_update
    from cosmos_curate_tpu.ops.latent_attention import _latent_rows as kernel

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    rows = B if t == 1 else 1

    def program(layers, pool, q, chunk, tables, write_index):
        own = (write_index[:, None] + jnp.arange(1, t + 1)[None, :]).reshape(-1)
        for layer in range(layers):
            pool = latent_update(pool, chunk, tables, write_index, layer_index=layer)
            u = kernel(
                q.reshape(rows * t, MLA_HEADS, MLA_WIDTH), pool, tables, own, layer_index=layer,
                sm_scale=0.1147, v_width=MLA_VALUES, rows_per_table=t, interpret=False,
            ).reshape(rows, t, MLA_HEADS, MLA_VALUES)
            # the next layer's queries and rows depend on this layer's attention
            q = q + jnp.pad(u, ((0, 0), (0, 0), (0, 0), (0, MLA_WIDTH - MLA_VALUES)))
            chunk = chunk + u[:, :, 0].sum(axis=-1, keepdims=True)
        return q, pool

    counts = []
    for layers in (LAYERS, 2 * LAYERS):
        shape = (layers, POOL_BLOCKS, 1, BS, MLA_WIDTH)
        hlo = jax.jit(functools.partial(program, layers), donate_argnums=(0,)).lower(
            arg(shape, jnp.bfloat16), arg((rows, t, MLA_HEADS, MLA_WIDTH), jnp.bfloat16),
            arg((rows, t, MLA_WIDTH), jnp.bfloat16), arg((rows, S // BS), jnp.int32),
            arg((rows,), jnp.int32),
        ).compile().as_text()
        copies, feeding = _pool_copies(hlo, shape)
        assert not feeding, f"{layers} layers: pool copies feed the kernel: {feeding}"
        counts.append(len(copies))
    assert counts == [0, 0], counts


@pytest.mark.parametrize(
    "tables,m,k,n,whole",
    [
        (20, 1536, 5120, 3072, False), (20, 1536, 1536, 5120, False), (20, 12288, 5120, 3072, False),
        (64, 8192, 2048, 3072, True), (64, 8192, 1536, 2048, True), (64, 1024, 2048, 3072, True),
        (64, 8192, 2304, 1792, True), (64, 8192, 896, 2304, True),
    ],
    ids=[
        "decode-gate-up", "decode-down", "prefill-gate-up", "lfm2-prefill-gate-up-whole", "lfm2-prefill-down-whole",
        "lfm2-decode-gate-up-whole", "mellum2-prefill-gate-up-whole", "mellum2-prefill-down-whole",
    ],
)
def test_grouped_matmul_compiles_for_v5e_without_copying_the_tables(v5e, tables, m, k, n, whole):
    """DeepSeek-V2's 20 held experts at the cell's row counts, and LFM2's and
    Mellum2's 64, every one held, with K whole in a tile (6 MiB of a table a
    grid step at the most, 14.75 MiB a step): the tiles chosen in ops/grouped_matmul.py fit a
    call's VMEM, the tables go into the kernel as they are stored, and the
    custom call is named ``gmm``."""
    from cosmos_curate_tpu.ops.grouped_matmul import grouped_matmul, tiles

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    assert (tiles(k, n, whole=whole)[1] == k) == (whole or k <= 1024)
    hlo = jax.jit(functools.partial(grouped_matmul, whole=whole, use_kernel=True, interpret=False)).lower(
        arg((m, k), jnp.bfloat16), arg((tables, k, n), jnp.bfloat16), arg((tables,), jnp.int32)
    ).compile().as_text()
    assert re.search(r"%gmm(\.\d+)? = .*custom-call", hlo)
    assert not re.search(rf"= bf16\[{tables}," + f"{k},{n}" + r"\]\{[^}]*\} copy\(", hlo)


@pytest.mark.parametrize("tokens", [256, 1024], ids=["decode-rows", "prefill-rows"])
@pytest.mark.parametrize(
    "preset", ["VLM_DEEPSEEK_V2_EP8", "VLM_TRINITY_LARGE_EP8", "VLM_KEYE_VL2_A3B_EP8", "VLM_SOLAR_OPEN2_EP8"]
)
def test_share_held_expert_layers_lower_for_v5e_to_the_parents_text(v5e, preset, tokens):
    """The four cells whose programs hold a share of their experts, at their
    real widths, lowered (not compiled) for the described v5e: the text,
    Mosaic's serialized kernels in it (printed back without their debug
    locations), is what ``grouped_matmul`` gave before a caller could say
    ``whole`` (PR 59)."""
    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from cosmos_curate_tpu.ops.grouped_matmul import grouped_matmul
    from tests.ops.test_grouped_matmul import moe_layer_text, parent_grouped_matmul

    cfg = getattr(vlm_model, preset)
    kernel = dict(use_kernel=True, interpret=False)
    now = moe_layer_text(cfg, tokens, functools.partial(grouped_matmul, **kernel), sharding=v5e)
    then = moe_layer_text(cfg, tokens, functools.partial(parent_grouped_matmul, **kernel), sharding=v5e)
    assert now == then and now.count("tpu_custom_call") == 2
    # and the check can tell: the same layer saying ``whole`` is another text
    said = moe_layer_text(cfg, tokens, lambda *a, **kw: grouped_matmul(*a, **kw | kernel | {"whole": True}), sharding=v5e)
    assert said != now


@pytest.mark.parametrize("heads_per_step", [16, 32, 64])
def test_ssm_decode_kernel_compiles_for_v5e_under_its_trace_name(v5e, heads_per_step):
    """Granite-4.0-H-Micro's widths, the cell's rows and planes: 64 heads of
    [64, 128] float32, the whole row (2 MiB a buffer, four buffers) or a part
    of it a grid step; the decay a third prefetched array, ``dt x`` as rows
    turned over in the kernel, ``H C`` a float32 product at ``highest`` that
    leaves lane-dense. The custom call carries the name the benchmark's trace
    reduction looks for, and the store is aliased, not copied."""
    from cosmos_curate_tpu.ops.ssm import _ssm_decode, heads_a_step
    from perfbench import trace_reduce

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    rows, layers, h, p, n = 48, 36, 64, 64, 128
    assert heads_a_step(h, p, n) == 64  # what the engine's programs run
    fn = functools.partial(_ssm_decode, heads_per_step=heads_per_step, interpret=False)
    hlo = jax.jit(fn, donate_argnums=(0,)).lower(
        arg((layers, rows + 1, h, p, n)), arg((), jnp.int32), arg((rows,), jnp.int32),
        arg((rows, h)), arg((rows, h, p)), arg((rows, n)), arg((rows, n)),
    ).compile().as_text()
    calls = [
        trace_reduce.instruction(line.strip().removeprefix("ROOT "))
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert calls and all(re.search(r"^_?ssm_decode(\.\d+)?$", c) for c in calls), calls
    assert not re.search(r"f32\[36,49,64,64,128\]\{[^}]*\} copy\(", hlo)


@pytest.mark.parametrize("heads_per_step", [10, 30])
def test_delta_decode_kernel_compiles_for_v5e_under_its_trace_name(v5e, heads_per_step):
    """Olmo-Hybrid's widths, the cell's rows: 30 heads x [96, 192] side by
    side, 5760 lanes; a block of 10 heads is 1920 lanes, walked two heads
    (three tiles) at a time, the whole row 2.1 MiB a buffer. The custom call
    carries the name the benchmark's trace reduction looks for."""
    from cosmos_curate_tpu.ops.delta_rule import _delta_decode
    from perfbench import trace_reduce

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    rows, h, dk, dv = 44, 30, 96, 192
    fn = functools.partial(_delta_decode, heads_per_step=heads_per_step, interpret=False)
    hlo = jax.jit(fn).lower(
        arg((12, rows + 1, dk, h * dv)), arg((), jnp.int32), arg((rows,), jnp.int32),
        arg((rows, h, dk)), arg((rows, h, dk)), arg((rows, h, dv)), arg((rows, h)), arg((rows, h)),
    ).compile().as_text()
    calls = [
        trace_reduce.instruction(line.strip().removeprefix("ROOT "))
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert calls and all(re.search(r"^_?delta_decode(\.\d+)?$", c) for c in calls), calls


@pytest.mark.parametrize("heads_per_step", [16, 32])
def test_channel_decay_decode_kernel_compiles_for_v5e_under_the_same_trace_name(v5e, heads_per_step):
    """Solar-Open2's widths, the cell's rows: 64 heads x [128, 128] side by
    side, 8192 lanes, 4 MiB a row a layer; the decay a third column, ``k | q |
    a`` in ONE ``[128, 3 * heads]`` block (48 or 96 lanes of a 128-lane tile).
    The custom call carries the name the benchmark's trace reduction looks
    for, whichever shape the decay has."""
    from cosmos_curate_tpu.ops.delta_rule import _delta_decode
    from perfbench import trace_reduce

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    rows, h, dk, dv = 256, 64, 128, 128
    fn = functools.partial(_delta_decode, heads_per_step=heads_per_step, interpret=False)
    hlo = jax.jit(fn, donate_argnums=(0,)).lower(
        arg((3, rows + 9, dk, h * dv)), arg((), jnp.int32), arg((rows,), jnp.int32),
        arg((rows, h, dk)), arg((rows, h, dk)), arg((rows, h, dv)), arg((rows, h, dk)), arg((rows, h)),
    ).compile().as_text()
    calls = [
        trace_reduce.instruction(line.strip().removeprefix("ROOT "))
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert calls and all(re.search(r"^_?delta_decode(\.\d+)?$", c) for c in calls), calls


@pytest.mark.parametrize(
    "kernel,rows,lane",
    [
        pytest.param("paged_decode", 40, 1024, id="decode-40-rows"),
        pytest.param("paged_decode", 4, 4096, id="decode-4-rows-long-lane"),
        pytest.param("paged_prefill", 4, 1024, id="prefill-4-rows-T256"),
        pytest.param("paged_prefill", 1, 4096, id="prefill-1-row-long-lane"),
    ],
)
def test_paged_kernels_compile_at_olmo_hybrids_heads(v5e, kernel, rows, lane):
    """30 KV heads of 128 with ONE query head each: the widest page any flavor
    has (a page is 30 x 16 x 128), no power of two, and the only group of 1."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = KERNELS[kernel](30, 1, 128, arg, rows=rows, lane=lane)
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
