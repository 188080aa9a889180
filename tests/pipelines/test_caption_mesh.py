"""The caption stage builds the ``model`` mesh its flavor is served over:
``FlavorSpec.model_chips`` travels with the checkpoint choice, and
``_CaptionVLM.setup()`` hands ``SharedCaptionEngine.get`` the mesh, with no
argument beyond the flavor. Four of the eight virtual devices stand for the
chips of a v5e host."""

import dataclasses

import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import SharedCaptionEngine
from cosmos_curate_tpu.models.vlm.model import (
    VLM_FLAVORS,
    VLM_QWEN25VL_TINY_TEST,
    FlavorSpec,
    vlm_flavor,
)
from cosmos_curate_tpu.pipelines.video.stages.captioning import CaptionStage

MESH_FLAVORS = {"qwen25vl-7b": 4, "qwen25vl-tiny-test": 4}


@pytest.fixture(autouse=True)
def _fresh_registry():
    SharedCaptionEngine.reset()
    yield
    SharedCaptionEngine.reset()


@pytest.mark.parametrize("name", sorted(VLM_FLAVORS))
def test_every_flavor_declares_its_chips(name):
    spec = vlm_flavor(name)
    assert spec.model_chips == MESH_FLAVORS.get(name, 1)
    assert spec.cfg.n_kv_heads % spec.model_chips == 0


@pytest.mark.parametrize(
    "n_kv_heads, chips", [(2, 4), (4, 3), (4, 8), (4, 0)],
    ids=["2-heads-over-4", "4-heads-over-3", "4-heads-over-8", "no-chip"],
)
def test_a_degree_that_does_not_divide_the_kv_heads_is_refused_where_declared(n_kv_heads, chips):
    cfg = dataclasses.replace(VLM_QWEN25VL_TINY_TEST, n_kv_heads=n_kv_heads)
    with pytest.raises(ValueError, match=rf"model_chips={chips} does not divide n_kv_heads={n_kv_heads}"):
        FlavorSpec(cfg, "caption-vlm-tpu", model_chips=chips)


def test_stage_of_a_four_chip_flavor_builds_the_mesh_engine_the_driver_builds():
    import jax
    from jax.sharding import Mesh

    from cosmos_curate_tpu.parallel.axes import MODEL

    stage = CaptionStage(model_flavor="qwen25vl-tiny-test")
    stage.model.setup()
    engine = stage.model.engine
    assert engine.mesh_geometry == (("model", 4),)
    # perfbench/drivers/caption_engine.py: Mesh(np.array(devices), (MODEL,)) of the cell's 4 chips
    drivers = Mesh(np.array(jax.devices()[:4]), (MODEL,))
    assert list(engine.mesh.devices.flat) == list(drivers.devices.flat)
    spec = vlm_flavor("qwen25vl-tiny-test")
    key = SharedCaptionEngine.key_for(spec.cfg, spec.model_id, mesh=drivers)
    assert key.geometry == engine.mesh_geometry
    assert SharedCaptionEngine._engines[key] is engine
    assert [(l.length, l.n_slots) for l in engine.lanes] == sorted(spec.kv_lanes)


def test_stats_say_what_one_chip_holds():
    import jax

    stage = CaptionStage(model_flavor="qwen25vl-tiny-test")
    stage.model.setup()
    engine = stage.model.engine
    stats = engine.stats()
    leaves = jax.tree.leaves(engine.params)
    whole = sum(x.nbytes for x in leaves)
    split = sum(x.nbytes for x in leaves if not x.sharding.is_fully_replicated)
    assert 0 < split < whole  # norm scales and row-parallel biases are repeated
    assert stats["param_bytes_per_chip"] == split // 4 + (whole - split)
    assert stats["kv_pool_bytes_per_chip"] * 4 == engine.kv_bytes() > 0
    # and what the chip's memory really holds, by its shards
    on_first = sum(s.data.nbytes for x in leaves for s in x.addressable_shards if s.device == jax.devices()[0])
    assert on_first == stats["param_bytes_per_chip"]


@pytest.mark.parametrize("name", ["tiny-test", "qwen3moe-tiny-test"])
def test_stage_of_a_one_chip_flavor_builds_no_mesh(name):
    stage = CaptionStage(model_flavor=name)
    assert stage.model.model_chips == 1
    stage.model.setup()
    engine = stage.model.engine
    assert engine.mesh is None and engine.mesh_geometry == ()
    stats = engine.stats()
    assert stats["param_bytes_per_chip"] == sum(
        x.nbytes for x in __import__("jax").tree.leaves(engine.params)
    )
    assert stats["kv_pool_bytes_per_chip"] == engine.kv_bytes()


def test_cfg_without_flavor_builds_no_mesh():
    stage = CaptionStage(cfg=VLM_QWEN25VL_TINY_TEST)
    assert stage.model.model_chips == 1 and stage.model._serving_mesh() is None


@pytest.mark.parametrize("found", [1, 2, 3])
def test_too_few_chips_is_an_error_at_setup_that_names_flavor_needed_and_found(monkeypatch, found):
    import jax

    chips = jax.local_devices()[:found]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: chips)
    stage = CaptionStage(model_flavor="qwen25vl-tiny-test")
    with pytest.raises(
        ValueError,
        match=rf"caption model 'qwen25vl-tiny-test' is served over 4 chips .* this host has {found}",
    ):
        stage.model.setup()
    assert stage.model.engine is None and not SharedCaptionEngine._engines


def _family():
    from cosmos_curate_tpu.pipelines.video.stages.enhance_caption import EnhanceCaptionStage
    from cosmos_curate_tpu.pipelines.video.stages.per_event_caption import PerEventCaptionStage
    from cosmos_curate_tpu.pipelines.video.stages.semantic_filter import SemanticFilterStage

    return [CaptionStage, EnhanceCaptionStage, PerEventCaptionStage, SemanticFilterStage]


@pytest.mark.parametrize("stage_cls", _family(), ids=lambda c: c.__name__)
def test_every_caption_family_stage_carries_the_degree(stage_cls):
    """They share the engine through ``resolve_caption_model`` and have no
    mesh code of their own."""
    model = stage_cls(model_flavor="qwen25vl-tiny-test")._model
    assert (model.model_chips, model.flavor) == (4, "qwen25vl-tiny-test")
    assert model._serving_mesh().shape == {"model": 4}
    assert stage_cls(model_flavor="tiny-test")._model._serving_mesh() is None


@pytest.mark.parametrize("n", [1, 4, 8])
def test_model_mesh_takes_the_first_local_devices(n):
    import jax

    from cosmos_curate_tpu.parallel.mesh import model_mesh

    mesh = model_mesh(n)
    assert mesh.axis_names == ("model",) and list(mesh.devices.flat) == jax.local_devices()[:n]


def test_model_mesh_refuses_more_than_there_are():
    from cosmos_curate_tpu.parallel.mesh import model_mesh

    with pytest.raises(ValueError, match="a model mesh is served over 9 chips .* this host has 8"):
        model_mesh(9)
