"""Qwen2-VL vision tower + full multimodal conversion parity.

HF models are randomly initialized from tiny configs (no downloads):
numeric agreement proves the Flax architecture, the m-rope positions, and
the weight mapping are exact, so loading a real Qwen2-VL checkpoint is the
same code path with real weights (reference serves these checkpoints via
vLLM, cosmos_curate/models/vllm_qwen.py:122-260).
"""

from dataclasses import replace

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")

import jax
import jax.numpy as jnp

from cosmos_curate_tpu.models.vlm import vision_qwen
from cosmos_curate_tpu.models.vlm.vision_qwen import (
    QWEN2_VL_2B_VISION,
    QWEN3_VISION_TINY_TEST,
    QWEN25_VISION_TINY_TEST,
    QWEN25_VL_7B_VISION,
    QWEN_VISION_TINY_TEST,
    QwenVisionConfig,
    QwenVisionTower,
    frames_to_patches,
)

HF_VISION_KW = dict(
    depth=2,
    embed_dim=32,
    num_heads=4,
    hidden_size=48,
    mlp_ratio=2,
    patch_size=4,
    temporal_patch_size=2,
    spatial_merge_size=2,
    in_channels=3,
)


def _hf_vision_config():
    from transformers.models.qwen2_vl.configuration_qwen2_vl import Qwen2VLVisionConfig

    return Qwen2VLVisionConfig(**HF_VISION_KW)


class TestVisionTowerParity:
    @pytest.fixture(scope="class")
    def pair(self):
        import torch

        from transformers.models.qwen2_vl.modeling_qwen2_vl import (
            Qwen2VisionTransformerPretrainedModel,
        )

        from cosmos_curate_tpu.models.convert_qwen import (
            convert_qwen2_vision,
            qwen2_vision_config,
        )

        hf_cfg = _hf_vision_config()
        torch.manual_seed(11)
        hf = Qwen2VisionTransformerPretrainedModel(hf_cfg).eval()
        ours_cfg = qwen2_vision_config(hf_cfg, image_size=16)
        sd = {f"visual.{k}": v for k, v in hf.state_dict().items()}
        vision_params, report = convert_qwen2_vision(sd, hf_cfg.depth)
        tower = QwenVisionTower(ours_cfg, dtype=jnp.float32)
        return hf, tower, ours_cfg, vision_params, report

    def test_every_vision_tensor_mapped(self, pair):
        hf, _, _, _, report = pair
        assert not report.unmapped, report.unmapped
        assert set(report.mapped) == {f"visual.{k}" for k in hf.state_dict()}

    @pytest.mark.parametrize("grid", [(1, 4, 4), (2, 4, 4)])
    def test_output_matches_hf(self, pair, grid):
        import torch

        hf, tower, cfg, vision_params, _ = pair
        t, h, w = grid
        s = t * h * w
        patches = np.random.default_rng(3).normal(size=(s, cfg.patch_dim)).astype(np.float32)
        with torch.no_grad():
            want = hf(
                torch.from_numpy(patches), grid_thw=torch.tensor([[t, h, w]])
            ).numpy()
        got = tower.apply(vision_params, jnp.asarray(patches)[None], grid)[0]
        assert got.shape == want.shape == (s // 4, cfg.hidden_size)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-3)


class TestPatchExtraction:
    def test_matches_hf_processor(self):
        """frames_to_patches emits exactly the HF Qwen2VLImageProcessor's
        patch vectors (order AND values) for a fixed-size input."""
        from transformers.models.qwen2_vl.image_processing_qwen2_vl import (
            Qwen2VLImageProcessor,
        )

        cfg = QwenVisionConfig(
            depth=1, embed_dim=32, num_heads=4, hidden_size=32, patch_size=14, image_size=28
        )
        rng = np.random.default_rng(5)
        frame = rng.integers(0, 256, (28, 28, 3), np.uint8)
        proc = Qwen2VLImageProcessor(
            min_pixels=28 * 28, max_pixels=28 * 28, patch_size=14, merge_size=2
        )
        out = proc(images=[frame], return_tensors="np")
        want = out["pixel_values"]  # [S, patch_dim]
        assert tuple(out["image_grid_thw"][0]) == (1, 2, 2)
        got, grid = frames_to_patches(jnp.asarray(frame)[None, None], cfg)
        assert grid == (1, 2, 2)
        np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-3, rtol=1e-4)


class TestFullMultimodalParity:
    @pytest.fixture(scope="class")
    def pair(self):
        import torch

        from cosmos_curate_tpu.models.convert_qwen import (
            convert_qwen2_vl,
            qwen2_lm_config,
            qwen2_vision_config,
        )
        from cosmos_curate_tpu.models.vlm.model import VLM

        cfg = transformers.Qwen2VLConfig(
            vocab_size=128,
            hidden_size=48,
            intermediate_size=96,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=128,
            rope_theta=10000.0,
            rope_scaling={"type": "mrope", "mrope_section": [2, 2, 2]},
            tie_word_embeddings=True,
            attention_dropout=0.0,
            vision_config=dict(HF_VISION_KW, hidden_size=48),
            image_token_id=125,
            video_token_id=126,
            vision_start_token_id=123,
            vision_end_token_id=124,
        )
        torch.manual_seed(13)
        hf = transformers.Qwen2VLForConditionalGeneration(cfg).eval()
        v_cfg = qwen2_vision_config(hf.config.vision_config, image_size=16)
        ours_cfg = qwen2_lm_config(
            hf.config,
            max_seq=64,
            vision_variant="qwen2",
            qwen_vision=v_cfg,
        )
        assert ours_cfg.mrope_section == (2, 2, 2)
        lm_params, vision_params, report = convert_qwen2_vl(
            hf.state_dict(), cfg.num_hidden_layers, cfg.vision_config.depth
        )
        model = VLM(ours_cfg, dtype=jnp.float32)
        return hf, model, ours_cfg, lm_params, vision_params, report

    def test_checkpoint_converts_completely(self, pair):
        hf, _, _, _, _, report = pair
        assert report.vision_skipped == []
        assert not report.unmapped, report.unmapped
        assert set(report.mapped) >= set(hf.state_dict())

    def test_multimodal_logits_match(self, pair):
        import torch

        from cosmos_curate_tpu.models.convert_qwen import (
            merge_lm_params,
            merge_vision_params,
        )
        from cosmos_curate_tpu.models.vlm.model import build_mrope_positions, init_cache

        hf, model, cfg, lm_params, vision_params, _ = pair
        grid = (1, 4, 4)
        t, h, w = grid
        s = t * h * w
        n_merged = s // 4
        rng = np.random.default_rng(17)
        patches = rng.normal(size=(s, cfg.qwen_vision.patch_dim)).astype(np.float32)
        text = rng.integers(0, 120, 6).astype(np.int64)

        # HF layout: [vision_start][image pads][vision_end][text...]
        input_ids = np.concatenate(
            [[123], np.full(n_merged, 125), [124], text]
        ).astype(np.int64)
        with torch.no_grad():
            want = hf(
                input_ids=torch.from_numpy(input_ids)[None],
                pixel_values=torch.from_numpy(patches),
                image_grid_thw=torch.tensor([[t, h, w]]),
            ).logits[0].numpy()

        # ours: same layout via prefix/suffix token embeds + vision embeds
        ck, cv = init_cache(cfg, 1, dtype=jnp.float32)
        size = cfg.qwen_vision.image_size
        init_tree = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 2, size, size, 3), jnp.uint8),
            jnp.zeros((1, 4), jnp.int32),
            ck,
            cv,
            method=model.init_everything,
        )
        params = merge_vision_params(merge_lm_params(init_tree, lm_params), vision_params)

        vis = model.apply(
            params,
            jnp.asarray(patches)[None],
            grid,
            method=lambda m, p, g: m.vision_tower(p, g),
        )
        pre = model.apply(params, jnp.asarray([[123]], jnp.int32), method=model.embed_tokens)
        post_ids = np.concatenate([[124], text]).astype(np.int32)
        post = model.apply(params, jnp.asarray(post_ids)[None], method=model.embed_tokens)
        embeds = jnp.concatenate([pre, vis, post], axis=1)
        merged_grid = (t, h // 2, w // 2)
        rope_pos, _ = build_mrope_positions(1, merged_grid, len(post_ids))
        total = embeds.shape[1]
        logits, _, _ = model.apply(
            params,
            embeds,
            ck,
            cv,
            jnp.asarray(rope_pos)[None],
            jnp.zeros((1,), jnp.int32),
            jnp.full((1,), total, jnp.int32),
        )
        np.testing.assert_allclose(np.asarray(logits[0]), want, atol=5e-4, rtol=1e-3)


class TestQwen25VisionParity:
    """Qwen2.5-VL vision tower (windowed attention, RMSNorm, SwiGLU —
    also CosmosReason's vision architecture, reference vllm_qwen.py)."""

    @pytest.fixture(scope="class")
    def pair(self):
        import torch

        from transformers.models.qwen2_5_vl.configuration_qwen2_5_vl import (
            Qwen2_5_VLVisionConfig,
        )
        from transformers.models.qwen2_5_vl.modeling_qwen2_5_vl import (
            Qwen2_5_VisionTransformerPretrainedModel,
        )

        from cosmos_curate_tpu.models.convert_qwen import (
            convert_qwen2_vision,
            qwen2_vision_config,
        )

        hf_cfg = Qwen2_5_VLVisionConfig(
            depth=4,
            hidden_size=32,
            num_heads=4,
            intermediate_size=64,
            out_hidden_size=48,
            patch_size=4,
            temporal_patch_size=2,
            spatial_merge_size=2,
            # window = 16px -> 2x2 merged tokens per window; full attention
            # only at block 2, so windows are genuinely exercised
            window_size=16,
            fullatt_block_indexes=[2],
        )
        torch.manual_seed(23)
        hf = Qwen2_5_VisionTransformerPretrainedModel(hf_cfg).eval()
        ours_cfg = qwen2_vision_config(hf_cfg, image_size=32)
        assert ours_cfg.variant == "qwen2_5"
        sd = {f"visual.{k}": v for k, v in hf.state_dict().items()}
        vision_params, report = convert_qwen2_vision(sd, hf_cfg.depth)
        tower = QwenVisionTower(ours_cfg, dtype=jnp.float32)
        return hf, tower, ours_cfg, vision_params, report

    def test_every_tensor_mapped(self, pair):
        hf, _, _, _, report = pair
        assert not report.unmapped, report.unmapped
        assert set(report.mapped) == {f"visual.{k}" for k in hf.state_dict()}

    @pytest.mark.parametrize("grid", [(1, 8, 8), (2, 8, 8), (1, 6, 6)])
    def test_output_matches_hf(self, pair, grid):
        """Grids larger than (and not divisible by) the window size —
        the permutation, padding, and per-block mask switching all bite."""
        import torch

        hf, tower, cfg, vision_params, _ = pair
        t, h, w = grid
        s = t * h * w
        patches = np.random.default_rng(29).normal(size=(s, cfg.patch_dim)).astype(np.float32)
        with torch.no_grad():
            want = hf(
                torch.from_numpy(patches), grid_thw=torch.tensor([[t, h, w]])
            ).numpy()
        got = tower.apply(vision_params, jnp.asarray(patches)[None], grid)[0]
        assert got.shape == want.shape == (s // 4, cfg.hidden_size)
        np.testing.assert_allclose(np.asarray(got), want, atol=3e-4, rtol=1e-3)


class TestQwen25FullParity:
    """Full Qwen2.5-VL checkpoint conversion: untied lm_head + windowed
    vision tower + m-rope, numerically against HF end to end."""

    @pytest.fixture(scope="class")
    def pair(self):
        import torch

        from cosmos_curate_tpu.models.convert_qwen import (
            convert_qwen2_vl,
            qwen2_lm_config,
            qwen2_vision_config,
        )
        from cosmos_curate_tpu.models.vlm.model import VLM

        cfg = transformers.Qwen2_5_VLConfig(
            vocab_size=128,
            hidden_size=48,
            intermediate_size=96,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=128,
            rope_theta=10000.0,
            rope_scaling={"type": "mrope", "mrope_section": [2, 2, 2]},
            tie_word_embeddings=False,
            attention_dropout=0.0,
            vision_config=dict(
                depth=3,
                hidden_size=32,
                num_heads=4,
                intermediate_size=64,
                out_hidden_size=48,
                patch_size=4,
                temporal_patch_size=2,
                spatial_merge_size=2,
                window_size=16,
                fullatt_block_indexes=[1],
            ),
            image_token_id=125,
            video_token_id=126,
            vision_start_token_id=123,
            vision_end_token_id=124,
        )
        torch.manual_seed(31)
        hf = transformers.Qwen2_5_VLForConditionalGeneration(cfg).eval()
        v_cfg = qwen2_vision_config(hf.config.vision_config, image_size=32)
        ours_cfg = qwen2_lm_config(
            hf.config, max_seq=128, vision_variant="qwen2", qwen_vision=v_cfg
        )
        assert not ours_cfg.tied_embeddings
        lm_params, vision_params, report = convert_qwen2_vl(
            hf.state_dict(), cfg.num_hidden_layers, cfg.vision_config.depth
        )
        model = VLM(ours_cfg, dtype=jnp.float32)
        return hf, model, ours_cfg, lm_params, vision_params, report

    def test_converts_completely(self, pair):
        hf, _, _, _, _, report = pair
        assert report.vision_skipped == []
        assert not report.unmapped, report.unmapped
        assert set(report.mapped) >= set(hf.state_dict())

    def test_multimodal_logits_match(self, pair):
        import torch

        from cosmos_curate_tpu.models.convert_qwen import (
            merge_lm_params,
            merge_vision_params,
        )
        from cosmos_curate_tpu.models.vlm.model import build_mrope_positions, init_cache

        hf, model, cfg, lm_params, vision_params, _ = pair
        grid = (1, 8, 8)  # bigger than the 2x2-merged-token window
        t, h, w = grid
        s = t * h * w
        n_merged = s // 4
        rng = np.random.default_rng(37)
        patches = rng.normal(size=(s, cfg.qwen_vision.patch_dim)).astype(np.float32)
        text = rng.integers(0, 120, 5).astype(np.int64)
        input_ids = np.concatenate(
            [[123], np.full(n_merged, 125), [124], text]
        ).astype(np.int64)
        with torch.no_grad():
            want = hf(
                input_ids=torch.from_numpy(input_ids)[None],
                pixel_values=torch.from_numpy(patches),
                image_grid_thw=torch.tensor([[t, h, w]]),
            ).logits[0].numpy()

        ck, cv = init_cache(cfg, 1, dtype=jnp.float32)
        size = cfg.qwen_vision.image_size
        init_tree = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 2, size, size, 3), jnp.uint8),
            jnp.zeros((1, 4), jnp.int32),
            ck,
            cv,
            method=model.init_everything,
        )
        params = merge_vision_params(merge_lm_params(init_tree, lm_params), vision_params)
        vis = model.apply(
            params,
            jnp.asarray(patches)[None],
            grid,
            method=lambda m, p, g: m.vision_tower(p, g),
        )
        pre = model.apply(params, jnp.asarray([[123]], jnp.int32), method=model.embed_tokens)
        post_ids = np.concatenate([[124], text]).astype(np.int32)
        post = model.apply(params, jnp.asarray(post_ids)[None], method=model.embed_tokens)
        embeds = jnp.concatenate([pre, vis, post], axis=1)
        rope_pos, _ = build_mrope_positions(1, (t, h // 2, w // 2), len(post_ids))
        total = embeds.shape[1]
        logits, _, _ = model.apply(
            params,
            embeds,
            ck,
            cv,
            jnp.asarray(rope_pos)[None],
            jnp.zeros((1,), jnp.int32),
            jnp.full((1,), total, jnp.int32),
        )
        np.testing.assert_allclose(np.asarray(logits[0]), want, atol=7e-4, rtol=1e-3)


class TestMRopeTemporalScaling:
    """Qwen2.5-VL scales the temporal m-rope component to absolute time
    (ADVICE r3): parity of build_mrope_positions(t_scale) with HF
    Qwen2_5_VLModel.get_rope_index on a video prompt."""

    # integer seconds-per-grid only: transformers 4.57 casts
    # second_per_grid_t to the int64 range dtype before multiplying
    # (truncating 0.5 -> 0) — a regression vs the original Qwen float
    # computation ("interval = tokens_per_second * temporal_patch_size /
    # fps ... 25 * 2 / 1 = 50", HF docstring). We implement the float
    # semantics (floor applied at the END, test below), so HF parity can
    # only be asserted where both agree.
    @pytest.mark.parametrize("second_per_grid_t", [1.0, 2.0, 5.0])
    def test_video_positions_match_hf(self, second_per_grid_t):
        import torch
        from transformers.models.qwen2_5_vl.configuration_qwen2_5_vl import (
            Qwen2_5_VLConfig,
        )
        from transformers.models.qwen2_5_vl.modeling_qwen2_5_vl import (
            Qwen2_5_VLModel,
        )

        from cosmos_curate_tpu.models.vlm.model import build_mrope_positions

        cfg = Qwen2_5_VLConfig(
            hidden_size=32,
            intermediate_size=64,
            num_hidden_layers=1,
            num_attention_heads=4,
            num_key_value_heads=2,
            vocab_size=160,
            vision_start_token_id=123,
            image_token_id=125,
            video_token_id=126,
            vision_config=dict(
                depth=1,
                hidden_size=16,
                intermediate_size=32,
                num_heads=2,
                patch_size=8,
                spatial_merge_size=2,
                tokens_per_second=2.0,
                out_hidden_size=32,
            ),
            rope_scaling={"type": "mrope", "mrope_section": [2, 1, 1]},
        )
        hf = Qwen2_5_VLModel(cfg)
        gt, gh, gw = 3, 4, 4  # pre-merge grid
        mh, mw = gh // 2, gw // 2
        n_vis = gt * mh * mw
        n_before, n_after = 4, 3
        input_ids = torch.tensor(
            [[*range(10, 10 + n_before - 1), 123, *([126] * n_vis), *range(40, 40 + n_after)]]
        )
        pos, _ = hf.get_rope_index(
            input_ids=input_ids,
            image_grid_thw=None,
            video_grid_thw=torch.tensor([[gt, gh, gw]]),
            second_per_grid_ts=torch.tensor([second_per_grid_t]),
            attention_mask=torch.ones_like(input_ids),
        )
        want = pos[:, 0].numpy().T  # [T, 3]

        t_scale = 2.0 * second_per_grid_t
        ours, next_pos = build_mrope_positions(n_before, (gt, mh, mw), n_after, t_scale)
        np.testing.assert_array_equal(ours, want)
        assert next_pos == want.max() + 1

    def test_fractional_scale_floors_at_the_end(self):
        from cosmos_curate_tpu.models.vlm.model import build_mrope_positions

        # t_scale 1.5 over grid_t=3: temporal ids floor(0,1.5,3.0)=0,1,3
        # (the original Qwen float semantics; HF 4.57's int cast would
        # give 0,1,2)
        ours, next_pos = build_mrope_positions(2, (3, 1, 1), 1, 1.5)
        assert list(ours[2:5, 0]) == [2, 3, 5]
        assert list(ours[2:5, 1]) == [2, 2, 2]
        # text resumes at abs-t-max 5 + 1 = 6; one trailing token -> 7
        assert next_pos == 7

    def test_unit_scale_matches_qwen2_behavior(self):
        from cosmos_curate_tpu.models.vlm.model import build_mrope_positions

        a, na = build_mrope_positions(3, (2, 2, 2), 4)
        b, nb = build_mrope_positions(3, (2, 2, 2), 4, 1.0)
        np.testing.assert_array_equal(a, b)
        assert na == nb


class TestQwen3VisionParity:
    """Qwen3-VL deepstack vision tower (learned interpolated pos embed,
    LayerNorm blocks with gelu-tanh MLP, multi-level deepstack mergers —
    the tower behind the reference's Qwen3-VL MoE captioners)."""

    @pytest.fixture(scope="class")
    def pair(self):
        import torch
        from transformers.models.qwen3_vl_moe.configuration_qwen3_vl_moe import (
            Qwen3VLMoeVisionConfig,
        )
        from transformers.models.qwen3_vl_moe.modeling_qwen3_vl_moe import (
            Qwen3VLMoeVisionModel,
        )

        from cosmos_curate_tpu.models.convert_qwen import (
            convert_qwen3_vision,
            qwen3_vision_config,
        )

        hf_cfg = Qwen3VLMoeVisionConfig(
            depth=3,
            hidden_size=32,
            intermediate_size=64,
            num_heads=4,
            patch_size=8,
            temporal_patch_size=2,
            spatial_merge_size=2,
            out_hidden_size=64,
            # 4x4 learned grid under a 6x6 patch grid: linspace(0,3,6) is
            # FRACTIONAL, so the bilinear 4-neighbor weights are actually
            # exercised (an even division would collapse them to one-hot)
            num_position_embeddings=16,
            deepstack_visual_indexes=[0, 1],
        )
        torch.manual_seed(5)
        hf = Qwen3VLMoeVisionModel(hf_cfg).eval()
        ours_cfg = qwen3_vision_config(hf_cfg, image_size=48)
        params, report = convert_qwen3_vision(hf.state_dict(), ours_cfg)
        return hf, ours_cfg, params, report

    def test_conversion_complete(self, pair):
        _, _, _, report = pair
        assert not report.unmapped, report.unmapped

    def test_tower_and_deepstack_match(self, pair):
        import torch

        from cosmos_curate_tpu.models.vlm.vision_qwen import (
            QwenVisionTower,
            frames_to_patches,
        )

        hf, cfg, params, _ = pair
        rng = np.random.default_rng(9)
        frames = rng.integers(0, 255, (1, 4, 48, 48, 3), np.uint8)
        patches, grid = frames_to_patches(jnp.asarray(frames), cfg)
        with torch.no_grad():
            want, want_ds = hf(
                torch.from_numpy(np.asarray(patches))[0],
                grid_thw=torch.tensor([list(grid)]),
            )
        tower = QwenVisionTower(cfg, dtype=jnp.float32)
        got, got_ds = tower.apply(params, patches, grid)
        np.testing.assert_allclose(
            np.asarray(got[0]), want.numpy(), atol=2e-4, rtol=1e-3
        )
        assert got_ds.shape[0] == len(want_ds) == 2
        for lvl in range(2):
            np.testing.assert_allclose(
                np.asarray(got_ds[lvl, 0]), want_ds[lvl].numpy(), atol=2e-4, rtol=1e-3
            )


# Qwen2.5-VL's own window of 4 x 4 merge units (112 px / 2 / 14) at test
# widths: (1, 6, 14) cuts it to runs of 48 and 36 tokens
_WINDOWS_OF_FOUR_UNITS = replace(QWEN25_VISION_TINY_TEST, window_size=32)


class TestSegmentAttention:
    """Every block attends inside the static runs the tower hands it (HF's
    cu_seqlens segments) and nothing of shape [S, S] is left in the program."""

    @pytest.mark.parametrize(
        "cfg, grid",
        [
            pytest.param(QWEN_VISION_TINY_TEST, (2, 4, 4), id="qwen2"),
            pytest.param(QWEN25_VISION_TINY_TEST, (2, 8, 8), id="qwen2_5-whole-windows-of-16"),
            pytest.param(QWEN25_VISION_TINY_TEST, (1, 6, 6), id="qwen2_5-cut-16-8-8-4"),
            pytest.param(QWEN25_VISION_TINY_TEST, (1, 6, 14), id="qwen2_5-cut-16x3-8x4-4"),
            pytest.param(_WINDOWS_OF_FOUR_UNITS, (1, 6, 14), id="qwen2_5-cut-48-36"),
            pytest.param(QWEN3_VISION_TINY_TEST, (2, 4, 4), id="qwen3"),
        ],
    )
    def test_equals_the_dense_masked_product(self, monkeypatch, cfg, grid):
        s = grid[0] * grid[1] * grid[2]
        calls = []
        inner = vision_qwen._segment_attention

        def spy(q, k, v, seg_lens):
            out = inner(q, k, v, seg_lens)
            calls.append((q, k, v, np.asarray(seg_lens), out))
            return out

        monkeypatch.setattr(vision_qwen, "_segment_attention", spy)
        tower = QwenVisionTower(cfg, dtype=jnp.float32)
        patches = jax.random.normal(jax.random.PRNGKey(31), (2, s, cfg.patch_dim))
        params = tower.init(jax.random.PRNGKey(37), patches, grid)
        # seeded biases and norm scales, so that no block is near the identity
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(41), len(leaves))
        params = jax.tree.unflatten(
            tree, [p + 0.3 * jax.random.normal(k, p.shape) for p, k in zip(leaves, keys)]
        )
        calls.clear()
        tower.apply(params, patches, grid)
        assert len(calls) == cfg.depth

        # the masks HF's cu_seqlens stand for, written out densely
        frame = np.arange(s) // (grid[1] * grid[2])
        full = frame[:, None] == frame[None, :]
        windowed_blocks = set()
        if cfg.variant == "qwen2_5":
            _perm, seg, _units = vision_qwen.window_partition(cfg, grid)
            window = seg[:, None] == seg[None, :]
            windowed_blocks = set(range(cfg.depth)) - set(cfg.fullatt_block_indexes)
            assert windowed_blocks and set(cfg.fullatt_block_indexes)
        for i, (q, k, v, seg_lens, got) in enumerate(calls):
            mask = window if i in windowed_blocks else full
            assert seg_lens.sum() == s
            assert q.dtype == jnp.float32 and q.shape == (2, s, cfg.num_heads, cfg.head_dim)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q * cfg.head_dim**-0.5, k)
            probs = jax.nn.softmax(jnp.where(mask[None, None], logits, -1e30), axis=-1)
            want = np.asarray(jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(2, s, -1))
            # the same float32 sums in another order: 1e-5 of the output's scale
            # (an element near zero is a difference of larger terms)
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=1e-5, atol=1e-5 * np.abs(want).max()
            )

    @pytest.mark.parametrize(
        "cfg",
        [
            pytest.param(replace(QWEN2_VL_2B_VISION, depth=2), id="qwen2vl-2b"),
            # one windowed block (64 runs of 64), one full (16 runs of 256)
            pytest.param(
                replace(QWEN25_VL_7B_VISION, depth=2, fullatt_block_indexes=(1,)), id="qwen25vl-7b"
            ),
        ],
    )
    def test_no_s_by_s_value_at_the_cells_grid(self, cfg):
        """The towers of both windows-32f cells, widths as published and
        depth cut to 2, lowered (not run) at 32 frames of 224 px."""
        grid = cfg.grid(32)
        assert grid == (16, 16, 16)
        tower = QwenVisionTower(cfg, dtype=jnp.bfloat16)
        patches = jax.ShapeDtypeStruct((1, 4096, cfg.patch_dim), jnp.bfloat16)
        params = jax.eval_shape(lambda p: tower.init(jax.random.PRNGKey(0), p, grid), patches)
        text = jax.jit(lambda prm, p: tower.apply(prm, p, grid)).lower(params, patches).as_text()
        assert "4096x1280x" in text  # the program is the tower's, at the cells' size
        assert "4096x4096" not in text
