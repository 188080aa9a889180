"""The grouped product's tiles (ops/grouped_matmul.py): the rule as a pure
function at every cell's expert shapes, the kernel in interpret mode with K
whole against ``jax.lax.ragged_dot``, and the proof that a flavor which holds
a SHARE of its experts lowers to the program it lowered to before ``whole``
existed."""

from __future__ import annotations

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import model as vlm_model
from cosmos_curate_tpu.ops import grouped_matmul as gmm_ops
from cosmos_curate_tpu.ops.grouped_matmul import _ROWS, _STEP, _side, _step_bytes, grouped_matmul, tiles
from cosmos_curate_tpu.ops.tiling import round_up

# [K, N] of gate_up and of down, as the presets have them (test_tiles_are_the_presets' pins that)
WHOLE = {
    "lfm2-gate-up": (2048, 3072, (128, 2048, 1536)),
    "lfm2-down": (1536, 2048, (128, 1536, 2048)),
    "mellum2-gate-up": (2304, 1792, (128, 2304, 896)),
    "mellum2-down": (896, 2304, (128, 896, 2304)),
}
SHARE_HELD = {
    "deepseek-gate-up": (5120, 3072),
    "deepseek-down": (1536, 5120),
    "trinity-gate-up": (3072, 6144),
    "trinity-down": (3072, 3072),
    "keye-gate-up": (2048, 1536),
    "keye-down": (768, 2048),
    "solar-gate-up": (4096, 2560),
    "solar-down": (1280, 4096),
}
PRESETS = {
    "lfm2": "VLM_LFM2_24B_A2B_PP5", "mellum2": "VLM_MELLUM2_12B_PP4", "deepseek": "VLM_DEEPSEEK_V2_EP8",
    "trinity": "VLM_TRINITY_LARGE_EP8", "keye": "VLM_KEYE_VL2_A3B_EP8", "solar": "VLM_SOLAR_OPEN2_EP8",
}


def parent_grouped_matmul(lhs, rhs, group_sizes, *, use_kernel=None, interpret=None, **_):
    """``grouped_matmul`` as it stood at PR 57, before a caller could say
    ``whole``: what the share-held flavors' programs were lowered from. Kept
    word for word (``**_`` swallows the keyword the caller now passes)."""
    if use_kernel is None:
        use_kernel = gmm_ops._on_tpu()
    if not use_kernel:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32).astype(lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    m = lhs.shape[0]
    m_pad = round_up(m, _ROWS)
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    out = gmm(
        lhs, rhs, group_sizes.astype(jnp.int32), preferred_element_type=lhs.dtype,
        tiling=(_ROWS, _side(rhs.shape[1]), _side(rhs.shape[2])), interpret=interpret,
    )
    return out[:m]


def _script(name):
    path = pathlib.Path(__file__).resolve().parents[2] / "scripts" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


lowering = _script("moe_lowering_check")


def moe_layer_text(cfg, tokens, product, **kw):
    """The lowered text of one ``MoEFFN``, its products through ``product``, the kernels' debug locations out."""
    return lowering.without_debug_info(lowering.moe_layer_text(cfg, tokens, product, **kw))


# -- the rule --------------------------------------------------------------------


@pytest.mark.parametrize("name", WHOLE)
def test_whole_takes_k_whole_and_a_step_inside_the_budget(name):
    k, n, want = WHOLE[name]
    rows, tk, tn = tiles(k, n, whole=True)
    assert (rows, tk, tn) == want
    assert tk == k and n % tn == 0 and tn % 128 == 0
    # two blocks each of a table, of rows and of results in flight, and the float32 accumulator, in a call's 16 MiB
    held = 2 * (tk * tn + rows * tk + rows * tn) * 2 + rows * tn * 4
    assert held == _step_bytes(rows, tk, tn, 2) <= _STEP < 16 << 20
    wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
    assert all(_step_bytes(rows, tk, t, 2) > _STEP for t in wider)  # and no wider side does


@pytest.mark.parametrize("name", SHARE_HELD)
def test_share_held_shapes_keep_their_tiles(name):
    k, n = SHARE_HELD[name]
    assert tiles(k, n) == tiles(k, n, whole=False) == (_ROWS, _side(k), _side(n))
    assert max(_side(k), _side(n)) <= 1024


@pytest.mark.parametrize("family", PRESETS)
def test_tiles_are_the_presets(family):
    """The shapes above are the presets' own, and ``whole`` is what the preset says of its experts."""
    cfg = getattr(vlm_model, PRESETS[family])
    shapes = {f"{family}-gate-up": (cfg.dim, 2 * cfg.moe.hidden), f"{family}-down": (cfg.moe.hidden, cfg.dim)}
    table = WHOLE if cfg.moe.held is None else SHARE_HELD
    assert {name: table[name][:2] for name in shapes} == shapes
    assert (cfg.moe.held is None) == (family in ("lfm2", "mellum2"))


def test_a_width_no_step_holds_whole_falls_back_to_the_cut():
    """K x 128 columns past the budget (no flavor's): both sides cut, as without ``whole``."""
    assert tiles(16384, 1024, whole=True) == tiles(16384, 1024) == (128, 1024, 1024)
    assert tiles(96, 40, whole=True) == (128, 96, 40)  # a test's widths: the sides themselves
    assert tiles(2048, 3072, whole=True, itemsize=4) == (128, 2048, 512)  # float32 tables: a third of the columns


# -- the kernel with K whole -----------------------------------------------------


@pytest.mark.parametrize(
    "sizes",
    [[100, 60, 0, 90], [128, 128, 128, 0], [0, 0, 300, 1], [384, 0, 0, 0]],
    ids=["straddle-empty-short", "on-the-boundaries", "leading-empties", "one-table-three-tiles"],
)
def test_whole_kernel_matches_ragged_dot(sizes):
    """K = 2048 is cut in two by the share-held tiles and taken whole here. The
    first case has a group that straddles a row tile (60 rows from row 100),
    an empty group, and a last group that ends at row 250 of 384."""
    rng = np.random.default_rng(sum(sizes))
    lhs = jnp.asarray(rng.normal(size=(384, 2048)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(4, 2048, 256)) / 45, jnp.bfloat16)
    assert tiles(2048, 256, whole=True) == (128, 2048, 256) != tiles(2048, 256)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    want = grouped_matmul(lhs, rhs, sizes, use_kernel=False)
    got = grouped_matmul(lhs, rhs, sizes, whole=True, use_kernel=True, interpret=True)
    cut = grouped_matmul(lhs, rhs, sizes, use_kernel=True, interpret=True)
    assert got.shape == want.shape == (384, 256) and got.dtype == jnp.bfloat16
    for kernel in (got, cut):
        np.testing.assert_allclose(
            np.asarray(kernel[:live], np.float32), np.asarray(want[:live], np.float32), atol=0.03, rtol=2e-2
        )


def test_whole_kernel_pads_rows_short_of_a_tile():
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.normal(size=(200, 256)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(3, 256, 128)) / 16, jnp.bfloat16)
    sizes = jnp.asarray([70, 0, 130], jnp.int32)
    want = grouped_matmul(lhs, rhs, sizes, use_kernel=False)
    got = grouped_matmul(lhs, rhs, sizes, whole=True, use_kernel=True, interpret=True)
    assert got.shape == (200, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.03, rtol=2e-2)


# -- what the layer says, and what it leaves alone -------------------------------


@pytest.mark.parametrize(
    "preset,whole",
    [("VLM_LFM2_MOE_TINY_TEST", True), ("VLM_MELLUM2_TINY_TEST", True), ("VLM_DEEPSEEK_V2_TINY_TEST", False),
     ("VLM_TRINITY_TINY_TEST", False), ("VLM_KEYE_TINY_TEST", False), ("VLM_SOLAR_OPEN2_TINY_TEST", False)],
)
def test_the_layer_says_whole_where_its_config_holds_every_expert(preset, whole):
    cfg = getattr(vlm_model, preset)
    said = []

    def spy(lhs, rhs, sizes, **kw):
        said.append(kw)
        return grouped_matmul(lhs, rhs, sizes, **kw)

    moe_layer_text(cfg, 16, spy)
    assert said == [{"whole": whole}] * 2 and (cfg.moe.held is None) == whole


@pytest.mark.parametrize(
    "preset", ["VLM_DEEPSEEK_V2_TINY_TEST", "VLM_TRINITY_TINY_TEST", "VLM_KEYE_TINY_TEST", "VLM_SOLAR_OPEN2_TINY_TEST"]
)
def test_share_held_layers_lower_to_the_parents_text(preset):
    """Through the kernel (interpret mode on the CPU): the program of a flavor
    that holds a share of its experts is, character for character, what the
    product of PR 57 gave. ``python scripts/moe_lowering_check.py`` asks the same of a
    checkout of the parent, at the real shapes too, against the described v5e."""
    cfg = getattr(vlm_model, preset)
    kernel = dict(use_kernel=True, interpret=True)
    now = moe_layer_text(cfg, 48, functools.partial(grouped_matmul, **kernel))
    then = moe_layer_text(cfg, 48, functools.partial(parent_grouped_matmul, **kernel))
    assert now == then and "ragged_dot" not in now


def test_the_probe_script_rehearses_on_the_cpu(capsys):
    """``scripts/gmm_tiles_probe.py --rehearse``: the control flow at a tiny
    size in interpret mode (no time it prints means anything)."""
    probe = _script("gmm_tiles_probe")
    assert probe.visits([100, 60, 0, 90]) == (4, 3) and probe.visits([128, 128, 0]) == (2, 2)
    assert probe.k_whole(2304, 1792, 6 << 20) == (128, 2304, 896)
    assert probe.main(["--rehearse", "--calls", "2", "--rules", "cut", "whole", "whole-6m"]) == 0
    out = capsys.readouterr().out
    assert out.count("us a call") == 12 and out.count("  layer ") == 2 and "of 819" in out


def test_the_lowering_check_reads_a_kernels_body_without_its_locations():
    """A text with no kernel in it is itself; the check's cases name presets the model has."""
    assert lowering.without_debug_info("module { }") == "module { }"
    assert all(hasattr(vlm_model, preset) for preset in lowering.TINY + lowering.REAL)
    held = {p: getattr(vlm_model, p).moe.held is not None for p in lowering.TINY + lowering.REAL}
    assert held == {p: any(family in p for family in lowering.SHARE_HELD) for p in held}
