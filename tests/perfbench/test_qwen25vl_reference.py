"""The plain Qwen2.5-VL reference (perfbench/reference/qwen25vl.py) against the
program at test size: the caption engine on a ``model=4`` mesh (four of the
eight virtual devices), windowed vision tower, three-component m-rope, chunked
prefill and decoding through the paged pool. The comparison itself is
``chip_smoke.reference_logit_errors``, the code the four-chip phase runs.

The tolerance is the rehearsal's (configs/qwen25vl-7b-tp4.json): at width 64 a
bfloat16 rounding is a larger share of a logit than at 3584; 0.008-0.020 is
what the right reference reads here, 0.06 the bound. Two cases compute the
reference wrong on purpose and must read above it: a test that cannot fail
shows nothing.
"""

import numpy as np
import pytest

import chip_smoke
from perfbench.reference import qwen25vl as ref

TOL = 0.06
N_STEPS = 8
FPS = 2.0  # the program then scales t by tokens_per_second * temporal_patch / fps = 2


QK_GAIN = 1.5


def _perturbed(params):
    """Biases start at zero and norm scales at one: move them, or a wrong
    bias or scale would pass. A freshly initialised decoder attends almost
    evenly, so that a wrong rotation changes little: its query and key
    matrices are scaled up, which makes attention depend on the positions."""
    import jax
    import jax.numpy as jnp

    def move(path, x):
        names = [getattr(k, "key", None) for k in path]
        if x.ndim == 1:
            return x + 0.05 * jnp.cos(jnp.arange(x.size, dtype=x.dtype)).reshape(x.shape)
        if names[-1] == "kernel" and names[-2] in ("q", "k") and str(names[-3]).startswith("layer_"):
            return x * QK_GAIN
        return x

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def run():
    """One engine on the mesh, one text request prefilled whole, one window
    request (frames behind a prefix) prefilled in chunks of 16 beside it."""
    from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from cosmos_curate_tpu.parallel.mesh import model_mesh
    from perfbench.drivers.caption_engine import make_params

    flavor = vlm_flavor("qwen25vl-tiny-test")
    cfg, mesh = flavor.cfg, model_mesh(flavor.model_chips)
    engine = CaptionEngine(
        cfg, kv_lanes=flavor.kv_lanes, prefill_chunk=16, mesh=mesh,
        params=_perturbed(make_params(cfg, 3, mesh)),
    )
    engine.setup()
    first, steps = chip_smoke._capture_first_logits(engine), chip_smoke._capture_decode_logits(engine)
    rng = np.random.default_rng(5)
    size = cfg.qwen_vision.image_size

    def ids(n):
        return rng.integers(cfg.vocab // 2, cfg.vocab, n).tolist()

    text = CaptionRequest(
        request_id="check-text", prompt_ids=ids(70), sampling=SamplingConfig(max_new_tokens=40)
    )
    window = CaptionRequest(
        request_id="check-window", prefix_ids=ids(8), prompt_ids=ids(12), frame_fps=FPS,
        frames=rng.integers(0, 255, (4, size, size, 3), np.uint8),
        sampling=SamplingConfig(max_new_tokens=N_STEPS + 1),
    )
    engine.add_request(text)
    while not engine.slots:
        engine.step()
    engine.add_request(window)
    done = sorted(r.request_id for r in engine.run_until_complete())
    assert done == ["check-text", "check-window"]
    assert engine.stats()["paged_kernel_steps"] > 0 and engine.mesh_geometry == (("model", 4),)
    yield engine, first, steps, {"text": text, "window": window}
    engine.shutdown()


def _errors(run, kind, **wrong):
    engine, first, steps, requests = run
    t_scale = 2.0 * 2 / FPS if kind == "window" else 1.0
    return chip_smoke.reference_logit_errors(
        engine, first, steps, requests[kind], n_steps=N_STEPS, t_scale=t_scale, **wrong
    )


@pytest.mark.parametrize("kind", ["window", "text"])
def test_engine_on_the_mesh_agrees_with_the_reference_at_every_step(run, kind):
    errs = _errors(run, kind)
    assert len(errs) == 1 + N_STEPS  # first-step logits, then 8 steps through the paged pool
    assert max(errs) <= TOL, errs


def test_a_windowed_block_computed_as_full_attention_is_seen(run):
    depth = run[0].cfg.qwen_vision.depth
    errs = _errors(run, "window", vision_wrong={"fullatt_blocks": tuple(range(depth))})
    assert max(errs) > TOL, errs


def test_swapped_mrope_sections_are_seen(run):
    t, h, w = run[0].cfg.mrope_section
    errs = _errors(run, "window", decoder_wrong={"sections": (w, h, t)})
    assert max(errs) > TOL, errs


def test_an_unscaled_temporal_position_is_seen(run):
    engine, first, steps, requests = run
    errs = chip_smoke.reference_logit_errors(
        engine, first, steps, requests["window"], n_steps=N_STEPS, t_scale=1.0
    )
    assert max(errs) > max(_errors(run, "window")), errs


@pytest.mark.parametrize("n_frames", [4, 3], ids=["4-frames", "3-frames-last-repeated"])
def test_vision_tower_alone(run, n_frames):
    """A windowed block and a full block both present; an odd frame count is
    padded by the last frame on both sides."""
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm.model import VLM

    engine = run[0]
    cfg = engine.cfg
    size = cfg.qwen_vision.image_size
    frames = np.random.default_rng(n_frames).integers(0, 255, (n_frames, size, size, 3), np.uint8)
    model = VLM(cfg)
    got = np.asarray(
        model.apply(engine.params, jnp.asarray(frames)[None], method=model.encode_images)[0], np.float32
    )
    vk = ref.vision_kwargs(cfg)
    assert set(range(vk["depth"])) - set(vk["fullatt_blocks"]) and vk["fullatt_blocks"]
    want = np.asarray(ref.vision_tower(engine.params, frames, **vk))
    assert got.shape == want.shape == (cfg.qwen_vision.tokens_out(n_frames), cfg.dim)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale <= TOL
    wrong = np.asarray(
        ref.vision_tower(engine.params, frames, **dict(vk, fullatt_blocks=tuple(range(vk["depth"]))))
    )
    assert np.abs(got - wrong).max() / scale > TOL


@pytest.mark.parametrize(
    "rows, cols, merge, patch, window_px, sizes",
    [(10, 10, 2, 4, 16, {4, 8, 16}), (16, 16, 2, 14, 112, {64}), (6, 14, 2, 14, 112, {48, 36}),
     (10, 6, 2, 4, 24, {36, 24})],
    ids=["tiny-cut-windows", "7b-at-224px", "wide-cut", "three-unit-windows"],
)
def test_windows_are_the_sets_the_program_attends_in(rows, cols, merge, patch, window_px, sizes):
    """``window_of_patch`` numbers row-major patches; the program permutes
    merge-ordered tokens window-major (HF ``get_window_index``). Same sets."""
    from cosmos_curate_tpu.models.vlm.vision_qwen import QwenVisionConfig, window_partition

    cfg = QwenVisionConfig(patch_size=patch, spatial_merge_size=merge, window_size=window_px, variant="qwen2_5")
    token_perm, seg, _units = window_partition(cfg, (1, rows, cols))
    # a program token q is (R, C, dy, dx) in merge order: its row-major patch
    q = np.asarray(token_perm)
    unit, inside = np.divmod(q, merge * merge)
    big_r, big_c = np.divmod(unit, cols // merge)
    dy, dx = np.divmod(inside, merge)
    row_major = (big_r * merge + dy) * cols + big_c * merge + dx
    theirs = {frozenset(row_major[seg == s].tolist()) for s in np.unique(seg)}
    mine = ref.window_of_patch(rows, cols, merge=merge, patch=patch, window_px=window_px)
    ours = {frozenset(np.nonzero(mine == w)[0].tolist()) for w in np.unique(mine)}
    assert ours == theirs
    assert {len(s) for s in ours} == sizes  # patches a window: whole ones and those the edge cuts


@pytest.mark.parametrize(
    "before, grid, after, t_scale",
    [(5, None, 0, 1.0), (0, (2, 5, 5), 7, 1.0), (8, (2, 5, 5), 12, 2.0), (3, (16, 8, 8), 96, 1.0),
     (4, (3, 2, 6), 5, 0.5), (6, (1, 4, 4), 0, 2.0)],
    ids=["text", "vision-first", "scaled-t", "window-32f", "slow-t", "no-text-after"],
)
def test_positions_agree_with_the_program(before, grid, after, t_scale):
    from cosmos_curate_tpu.models.vlm.model import build_mrope_positions

    theirs, nxt = build_mrope_positions(before, grid, after, t_scale)
    mine = ref.mrope_positions(before, grid, after, t_scale)
    np.testing.assert_array_equal(mine, theirs)
    more = ref.continue_positions(mine, 3)
    np.testing.assert_array_equal(more[-3:], np.repeat(np.arange(nxt, nxt + 3)[:, None], 3, axis=1))


def test_reference_shares_no_code_with_the_program():
    import inspect

    source = inspect.getsource(ref)
    assert "cosmos_curate_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
