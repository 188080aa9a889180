"""The caption engine serves from parameters stored in the type they are
computed in: the model says which (``VLM.param_dtype``), the engine narrows a
handed-in tree once, at ``setup()``, and the arithmetic is the float32 tree's
own (the programs rounded the same leaves to bfloat16 at every call)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cosmos_curate_tpu.models.layers import dense
from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM,
    VLM_MOE_TINY_TEST,
    VLM_QWEN2VL_TINY_TEST,
    VLM_QWEN25VL_TINY_TEST,
    init_cache,
)
from cosmos_curate_tpu.parallel.axes import MODEL
from cosmos_curate_tpu.parallel.sharding import spec_sharding

CONFIGS = {
    "qwen2vl-tied": VLM_QWEN2VL_TINY_TEST,
    "qwen25vl-untied": VLM_QWEN25VL_TINY_TEST,
    "qwen3moe": VLM_MOE_TINY_TEST,
}
# leaves whose layer computes in float32: they are never stored narrower
FLOAT32_LEAVES = ("scale", "router", "lm_head", "pos_embed")
LANES = dict(max_batch=2, kv_lanes=((128, 2),))

each_config = pytest.mark.parametrize("cfg", list(CONFIGS.values()), ids=list(CONFIGS))


def _image_size(cfg):
    return cfg.qwen_vision.image_size if cfg.qwen_vision else cfg.vision.image_size


def _float32_tree(cfg, seed=0):
    """What ``VLM(cfg)`` inits, plain: the tree the benchmark's ``make_params``,
    the converters and the loaders build."""
    tree = nn.unbox(_init_params(VLM(cfg), seed))
    assert {x.dtype for x in jax.tree.leaves(tree)} == {jnp.dtype(jnp.float32)}
    return tree


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _serving_dtypes(cfg):
    abstract = jax.eval_shape(lambda: _init_params(VLM(cfg, param_dtype=VLM.dtype)))
    return jax.tree.map(lambda x: x.dtype, nn.unbox(abstract))


def _request(cfg, rid="r"):
    size = _image_size(cfg)
    frames = np.random.default_rng(1).integers(0, 255, (2, size, size, 3), np.uint8)
    return CaptionRequest(
        request_id=rid,
        prompt_ids=[int(t) for t in np.random.default_rng(2).integers(3, 250, 21)],
        frames=frames,
        sampling=SamplingConfig(max_new_tokens=8, min_tokens=8),
    )


def _first_logits_and_tokens(engine, req):
    """(first-step logits, the greedy tokens) of one request."""
    seen = {}
    start_slot = engine._start_slot

    def spy(lane, slot_idx, request, t_valid, next_rope, logits_row):
        seen["logits"] = np.array(logits_row, np.float32)
        start_slot(lane, slot_idx, request, t_valid, next_rope, logits_row)
        seen["slot"] = lane.slots[slot_idx]

    engine._start_slot = spy
    try:
        engine.add_request(req)
        (done,) = engine.run_until_complete()
    finally:
        engine._start_slot = start_slot
    assert done.num_output_tokens == 8
    return seen["logits"], list(seen["slot"].generated)


@each_config
def test_every_leaf_is_stored_in_the_type_its_layer_computes_in(cfg):
    handed = _float32_tree(cfg)
    engine = CaptionEngine(cfg, params=handed, **LANES)
    engine.setup()
    want = _serving_dtypes(cfg)
    got = jax.tree.map(lambda x: x.dtype, engine.params)
    assert got == want
    by_path = {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_leaves_with_path(engine.params)}
    kept = {p for p, x in by_path.items() if x.dtype == jnp.float32}
    assert kept and all(x.dtype in (jnp.float32, jnp.bfloat16) for x in by_path.values())
    # what is consumed in float32 is stored in float32; every decoder matmul is narrow
    assert {p for p in by_path if any(name in p for name in FLOAT32_LEAVES)} <= kept
    decoder_matmuls = [p for p in by_path if "['layer_" in p and p.endswith("['kernel']") and "router" not in p]
    assert decoder_matmuls and not set(decoder_matmuls) & kept
    assert by_path["['params']['embed']['embedding']"].dtype == jnp.bfloat16
    if not cfg.tied_embeddings:
        assert by_path["['params']['lm_head']['kernel']"].dtype == jnp.float32
    if cfg.moe is not None:
        assert by_path["['params']['layer_0']['moe']['gate_up']"].dtype == jnp.bfloat16
        assert by_path["['params']['layer_0']['moe']['router']['kernel']"].dtype == jnp.float32
    assert engine.stats()["param_bytes_per_chip"] == sum(x.nbytes for x in by_path.values())
    # the wider sources were released as each leaf was cast; what was kept is the handed-in array
    for (path, source), stored in zip(
        jax.tree_util.tree_leaves_with_path(handed), jax.tree.leaves(engine.params)
    ):
        assert source.is_deleted() == (stored.dtype != jnp.float32), jax.tree_util.keystr(path)
        assert source.is_deleted() or stored is source


@each_config
def test_logits_and_greedy_tokens_are_the_float32_trees_own(cfg):
    tree = _float32_tree(cfg)
    narrowed = CaptionEngine(cfg, params=_copy(tree), **LANES)
    narrowed.setup()
    # the model's whole forward pass (vision tower, embedding, decoder, head):
    # the serving model on the narrowed tree against VLM(cfg) on the float32 tree
    size = _image_size(cfg)
    frames = jnp.asarray(np.random.default_rng(3).integers(0, 255, (1, 2, size, size, 3), np.uint8))
    ids = jnp.asarray(np.random.default_rng(4).integers(3, 250, (1, 9)), jnp.int32)

    def forward(model, params):
        return model.apply(params, frames, ids, *init_cache(cfg, 1), method=model.init_everything)

    want = forward(VLM(cfg), tree)
    got = forward(narrowed.model, narrowed.params)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert w.dtype == g.dtype
        np.testing.assert_array_equal(np.asarray(w, np.float32), np.asarray(g, np.float32))
    # the engine's own programs on the float32 tree (the arithmetic before the
    # engine narrowed: every program cast these leaves itself), put past the setter
    wide = CaptionEngine(cfg, **LANES)
    wide.setup()
    wide._params = tree
    want_logits, want_tokens = _first_logits_and_tokens(wide, _request(cfg))
    got_logits, got_tokens = _first_logits_and_tokens(narrowed, _request(cfg))
    np.testing.assert_array_equal(got_logits, want_logits)
    assert got_tokens == want_tokens and len(got_tokens) == 8


@each_config
def test_a_second_engine_shares_the_first_engines_arrays(cfg):
    first = CaptionEngine(cfg, params=_float32_tree(cfg), **LANES)
    first.setup()
    second = CaptionEngine(cfg, params=first.params, paged_attention="gather", **LANES)
    second.setup()
    for a, b in zip(jax.tree.leaves(first.params), jax.tree.leaves(second.params), strict=True):
        assert a is b and not a.is_deleted()
    assert second.stats()["param_bytes_per_chip"] == first.stats()["param_bytes_per_chip"]
    # and assigned after setup, as SharedCaptionEngine's loader assigns
    third = CaptionEngine(cfg, **LANES)
    third.setup()
    third.params = first.params
    assert all(
        a is b for a, b in zip(jax.tree.leaves(first.params), jax.tree.leaves(third.params), strict=True)
    )
    assert _first_logits_and_tokens(first, _request(cfg))[1] == _first_logits_and_tokens(third, _request(cfg))[1]


@each_config
def test_a_narrowed_leaf_keeps_its_partition_spec(cfg):
    # four of the eight virtual devices for a v5e host's chips, as in
    # tests/pipelines/test_caption_mesh.py (two where there are two KV heads)
    mesh = Mesh(np.array(jax.devices()[: min(4, cfg.n_kv_heads)]), (MODEL,))
    specs = nn.get_partition_spec(jax.eval_shape(lambda: _init_params(VLM(cfg, mesh=mesh))))
    specs = nn.unbox(specs)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    shardings = jax.tree.map(lambda s: spec_sharding(mesh, s), specs, is_leaf=is_spec)
    # made split, as the benchmark's make_params and the engine's own seeded init make it
    handed = jax.jit(lambda: _float32_tree(cfg), out_shardings=shardings)()
    on_host = jax.tree.map(np.asarray, handed)
    engine = CaptionEngine(cfg, params=handed, mesh=mesh, **LANES)
    engine.setup()
    want_dtypes = _serving_dtypes(cfg)
    split = 0
    for source, stored, sharding, dtype in zip(
        jax.tree.leaves(handed), jax.tree.leaves(engine.params),
        jax.tree.leaves(shardings), jax.tree.leaves(want_dtypes), strict=True,
    ):
        assert stored.dtype == dtype
        assert stored.sharding.is_equivalent_to(sharding, stored.ndim)
        assert source.is_deleted() == (dtype != jnp.float32)
        split += not stored.sharding.is_fully_replicated
    assert split  # the matmuls, the table (and the expert tables) are split
    on_first = sum(
        s.data.nbytes for x in jax.tree.leaves(engine.params)
        for s in x.addressable_shards if s.device == mesh.devices.flat[0]
    )
    assert engine.stats()["param_bytes_per_chip"] == on_first
    # a tree from the host (a loaded checkpoint) lands the same way
    other = CaptionEngine(cfg, mesh=mesh, **LANES)
    other.setup()
    other.params = on_host
    for a, b in zip(jax.tree.leaves(engine.params), jax.tree.leaves(other.params), strict=True):
        assert a.dtype == b.dtype and a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert len(_first_logits_and_tokens(engine, _request(cfg))[1]) == 8


@each_config
def test_a_seeded_engine_serves_what_handing_in_the_float32_init_would(cfg):
    seeded = CaptionEngine(cfg, **LANES)
    seeded.setup(seed=3)
    handed = CaptionEngine(cfg, params=_float32_tree(cfg, seed=3), **LANES)
    handed.setup()
    for a, b in zip(jax.tree.leaves(seeded.params), jax.tree.leaves(handed.params), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_host_leaves_of_a_loaded_checkpoint_are_narrowed_and_left_alone():
    cfg = VLM_QWEN2VL_TINY_TEST
    loaded = jax.tree.map(np.asarray, _float32_tree(cfg))
    engine = CaptionEngine(cfg, **LANES)
    engine.setup()
    engine.params = loaded
    assert jax.tree.map(lambda x: x.dtype, engine.params) == _serving_dtypes(cfg)
    assert all(isinstance(x, np.ndarray) and x.dtype == np.float32 for x in jax.tree.leaves(loaded))


@each_config
def test_the_model_inits_float32_unless_told(cfg):
    assert VLM(cfg).param_dtype == jnp.float32
    abstract = jax.eval_shape(lambda: _init_params(VLM(cfg)))
    assert {x.dtype for x in jax.tree.leaves(abstract)} == {jnp.dtype(jnp.float32)}


@pytest.mark.parametrize("param_dtype", [None, jnp.bfloat16], ids=["default", "bfloat16"])
def test_dense_stores_what_it_is_told_and_float32_by_default(param_dtype):
    kw = {} if param_dtype is None else {"param_dtype": param_dtype}
    layer = dense(8, "out", **kw)
    params = nn.unbox(layer.init(jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.bfloat16)))
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype(param_dtype or jnp.float32)}
    assert layer.apply(params, jnp.ones((2, 4), jnp.bfloat16)).dtype == jnp.bfloat16
