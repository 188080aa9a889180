"""ops/delta_rule.py: the chunked prefill scan and the Pallas decode kernel
(interpret mode) against the recurrence itself, token by token, in plain XLA,
and all three against the installed ``transformers``' own two functions for the
same rule (``models/qwen3_next``): a third, independent, opinion."""

import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.ops import delta_rule as dr

H, DK, DV = 4, 16, 24  # dv no multiple of dk, and 4 x 24 lanes make no tile


def _inputs(seed, b, t, beta=(0.0, 2.0), common=0.5):
    """q and k l2-normed a head as the mixer hands them in (``common``: a
    shared component, as a silu's positive mean gives a layer's keys), q
    scaled; g <= 0; beta in ``beta``."""
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.normal(size=shape) + common
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(b, t, H, DK) * DK**-0.5
    g = -rng.uniform(0.001, 0.5, (b, t, H))
    arrays = (rng.normal(size=(b, H, DK, DV)), q, unit(b, t, H, DK), rng.normal(size=(b, t, H, DV)), g,
              rng.uniform(*beta, (b, t, H)))
    return tuple(jnp.asarray(x, jnp.float32) for x in arrays)


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=tol * float(jnp.abs(want).max()))


@pytest.mark.parametrize(
    "t,chunk,beta",
    [
        pytest.param(128, 64, (0.0, 2.0), id="two-whole-chunks"),
        pytest.param(150, 64, (0.0, 2.0), id="ragged-tail-of-22"),
        pytest.param(37, 64, (0.0, 2.0), id="shorter-than-a-chunk"),
        pytest.param(70, 8, (0.0, 2.0), id="chunks-of-8-under-the-16-block"),
        pytest.param(96, 32, (0.0, 2.0), id="chunks-of-32-one-merge"),
        pytest.param(45, 12, (0.0, 2.0), id="chunks-of-12-no-power-of-two"),
        pytest.param(192, 64, (1.9, 2.0), id="beta-near-2"),
    ],
)
def test_chunked_scan_agrees_with_the_recurrence(t, chunk, beta):
    """Float32 at highest precision throughout, so the two agree to float32's
    own rounding, across chunk boundaries and from a carried state; a tail no
    multiple of the chunk is padded with positions that advance nothing."""
    state, *rest = _inputs(t, 2, t, beta=beta)
    o_ref, s_ref = dr.delta_scan_reference(state, *rest)
    o, s = dr.delta_chunk_scan(state, *rest, chunk=chunk)
    _close(o, o_ref)
    _close(s, s_ref)


def test_unit_lower_inverse_by_blocks():
    """The hardest case the rule can hand in: identical keys at beta = 2 make
    every entry under the diagonal 2; the inverse by blocks stays exact where
    the product over all 64 would pass through terms of 1e27."""
    for n in (5, 16, 24, 64):
        m = jnp.eye(n) + 2.0 * jnp.tril(jnp.ones((n, n)), -1)
        np.testing.assert_allclose(np.asarray(dr._unit_lower_inverse(m) @ m), np.eye(n), atol=1e-3)
    rng = np.random.default_rng(0)
    m = jnp.eye(64) + jnp.tril(jnp.asarray(rng.uniform(-0.5, 0.5, (3, 64, 64)), jnp.float32), -1)
    np.testing.assert_allclose(np.asarray(dr._unit_lower_inverse(m) @ m), np.broadcast_to(np.eye(64), m.shape), atol=1e-5)


@pytest.mark.parametrize("scan", ["recurrence", "chunked"])
def test_positions_with_beta_and_g_zero_leave_the_state_where_it_was(scan):
    """What the mixer hands in for padding: a row whose last 11 positions have
    beta = 0 and g = 0 ends in the state its first 10 positions left."""
    state, q, k, v, g, beta = _inputs(3, 2, 21)
    run = dr.delta_scan_reference if scan == "recurrence" else lambda *a: dr.delta_chunk_scan(*a, chunk=8)
    _, s_short = run(state, q[:, :10], k[:, :10], v[:, :10], g[:, :10], beta[:, :10])
    _, s_masked = run(state, q, k, v, g.at[:, 10:].set(0.0), beta.at[:, 10:].set(0.0))
    _close(s_masked, s_short, 1e-6)


@pytest.mark.parametrize("heads_per_step", [1, 2, 4])
def test_decode_kernel_agrees_with_the_xla_step(heads_per_step):
    """Interpret mode: rows of a store, two of them idle rows that share the
    garbage row 0 with beta = 0 and g = 0; the store updated in place at
    ``[layer, rows]`` and nowhere else."""
    rng = np.random.default_rng(heads_per_step)
    store = jnp.asarray(rng.normal(size=(3, 7, DK, H * DV)), jnp.float32)
    rows = jnp.asarray([2, 0, 6, 0, 1], jnp.int32)
    _, q, k, v, g, beta = _inputs(9, 5, 1)
    live = (rows > 0)[:, None]
    q, k, v, g, beta = q[:, 0], k[:, 0], v[:, 0], g[:, 0] * live, beta[:, 0] * live
    o_ref, s_ref = dr.delta_decode(store, 1, rows, q, k, v, g, beta, use_kernel=False)
    o, s = dr.delta_decode(
        store, 1, rows, q, k, v, g, beta, use_kernel=True, interpret=True, heads_per_step=heads_per_step
    )
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6, atol=1e-6)
    untouched = np.asarray(s).copy()
    untouched[1, [1, 2, 6]] = np.asarray(store)[1, [1, 2, 6]]
    np.testing.assert_array_equal(untouched, np.asarray(store))  # row 0 too: it advances nothing


def test_decode_kernel_walks_whole_tiles_of_heads():
    """At Olmo-Hybrid's dv = 192 two heads' lanes make three 128-lane tiles,
    so the kernel walks a block two heads at a time (a head's column spread
    over its own 192 lanes); six heads in blocks of four and two."""
    rng = np.random.default_rng(5)
    h, dk, dv = 6, 8, 192
    store = jnp.asarray(rng.normal(size=(1, 3, dk, h * dv)), jnp.float32)
    rows = jnp.asarray([1, 2], jnp.int32)
    q, k = (jnp.asarray(rng.normal(size=(2, h, dk)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, h, dv)), jnp.float32)
    g, beta = -jnp.asarray(rng.uniform(0, 1, (2, h)), jnp.float32), jnp.asarray(rng.uniform(0, 2, (2, h)), jnp.float32)
    o_ref, s_ref = dr.delta_decode(store, 0, rows, q, k, v, g, beta, use_kernel=False)
    for hb in (2, 6):
        o, s = dr.delta_decode(store, 0, rows, q, k, v, g, beta, use_kernel=True, interpret=True, heads_per_step=hb)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5, atol=1e-5)


def test_the_store_keeps_the_heads_side_by_side():
    state = jnp.arange(2 * H * DK * DV, dtype=jnp.float32).reshape(2, H, DK, DV)
    packed = dr.pack_state(state)
    assert packed.shape == (2, DK, H * DV)
    np.testing.assert_array_equal(np.asarray(packed[0, 3, DV : 2 * DV]), np.asarray(state[0, 1, 3]))
    np.testing.assert_array_equal(np.asarray(dr.unpack_state(packed, H)), np.asarray(state))


def test_prefill_and_decode_default_to_the_recurrence_off_the_tpu():
    """``use_kernel=None`` is decided in ops/delta_rule.py alone: on the CPU
    both operations are the XLA recurrence, bit for bit."""
    state, q, k, v, g, beta = _inputs(4, 2, 12)
    store = jnp.zeros((1, 3, DK, H * DV), jnp.float32).at[0, 1:].set(dr.pack_state(state))
    rows = jnp.asarray([1, 2], jnp.int32)
    for a, b in zip(
        dr.delta_prefill(store, 0, rows, q, k, v, g, beta, chunk=8)
        + dr.delta_decode(store, 0, rows, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]),
        dr.delta_prefill(store, 0, rows, q, k, v, g, beta, chunk=8, use_kernel=False)
        + dr.delta_decode(store, 0, rows, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], use_kernel=False),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("ours", ["recurrence", "chunked", "kernel"])
def test_agrees_with_transformers_own_gated_delta_rule(ours):
    """``torch_recurrent_gated_delta_rule`` / ``torch_chunk_gated_delta_rule``
    (transformers' qwen3_next module) on the same inputs from the same carried
    state; they scale q themselves, so they are handed it unscaled."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.qwen3_next.modeling_qwen3_next")
    state, q, k, v, g, beta = _inputs(11, 2, 100)
    as_torch = [torch.tensor(np.asarray(x)) for x in (q * DK**0.5, k, v, g, beta)]
    o_rec, s_rec = hf.torch_recurrent_gated_delta_rule(*as_torch, torch.tensor(np.asarray(state)), True)
    o_chunk, s_chunk = hf.torch_chunk_gated_delta_rule(
        *as_torch, chunk_size=64, initial_state=torch.tensor(np.asarray(state)), output_final_state=True
    )
    if ours == "kernel":  # token by token through a store, in interpret mode
        store = jnp.zeros((1, 3, DK, H * DV), jnp.float32).at[0, 1:].set(dr.pack_state(state))
        rows, os = jnp.asarray([1, 2], jnp.int32), []
        for i in range(q.shape[1]):
            o, store = dr.delta_decode(
                store, 0, rows, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], use_kernel=True, interpret=True
            )
            os.append(o)
        o, s = jnp.stack(os, axis=1), dr.unpack_state(store[0, rows], H)
    elif ours == "chunked":
        o, s = dr.delta_chunk_scan(state, q, k, v, g, beta, chunk=64)
    else:
        o, s = dr.delta_scan_reference(state, q, k, v, g, beta)
    for theirs_o, theirs_s in ((o_rec, s_rec), (o_chunk, s_chunk)):
        _close(o, theirs_o.numpy())
        _close(s, theirs_s.numpy())
