"""ops/ssm.py: the chunked SSD prefill scan and the Pallas decode kernel
(interpret mode) against the recurrence itself, token by token, in plain XLA."""

import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.ops import ssm

H, P, N = 8, 16, 32


def _inputs(seed, b, t):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    dt = jnp.asarray(rng.uniform(0.001, 0.3, (b, t, H)), jnp.float32)
    a = -jnp.arange(1, H + 1, dtype=jnp.float32)
    return normal(b, H, P, N), normal(b, t, H, P), dt, a, normal(b, t, N), normal(b, t, N), jnp.ones(H) * 0.5


@pytest.mark.parametrize("t,chunk", [(32, 8), (32, 32), (21, 8), (5, 8), (64, 16)])
def test_ssd_scan_agrees_with_the_recurrence(t, chunk):
    """Whole steps, one step, a ragged tail (padded with dt = 0) and a chunk
    longer than the sequence. The within-step products take bfloat16
    operands, so ``y`` agrees to a few 2^-8 of its largest value; the state
    handed from step to step is float32 at highest precision throughout."""
    state, x, dt, a, b, c, d = _inputs(t, 2, t)
    y_ref, s_ref = ssm.ssm_scan_reference(state, x, dt, a, b, c, d)
    y, s = ssm.ssd_chunk_scan(state, x, dt, a, b, c, d, chunk=chunk)
    assert float(jnp.abs(y - y_ref).max()) < 0.02 * float(jnp.abs(y_ref).max())
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=0, atol=1e-5 * float(jnp.abs(s_ref).max()))


@pytest.mark.parametrize("scan", ["recurrence", "ssd"])
def test_positions_with_dt_zero_leave_the_state_where_it_was(scan):
    """What the mixer hands in for padding: a row whose last 11 positions
    have dt = 0 ends in the state its first 10 positions left."""
    state, x, dt, a, b, c, d = _inputs(3, 2, 21)
    masked = dt.at[:, 10:].set(0.0)
    run = ssm.ssm_scan_reference if scan == "recurrence" else lambda *v: ssm.ssd_chunk_scan(*v, chunk=8)
    _, s_short = run(state, x[:, :10], dt[:, :10], a, b[:, :10], c[:, :10], d)
    _, s_masked = run(state, x, masked, a, b, c, d)
    np.testing.assert_allclose(np.asarray(s_masked), np.asarray(s_short), rtol=0, atol=1e-6)


@pytest.mark.parametrize("heads_per_step", [2, 4, 8])
def test_decode_kernel_agrees_with_the_xla_step(heads_per_step):
    """Interpret mode: rows of a store, two of them idle rows that share the
    garbage row 0 with dt = 0; the store updated in place at ``[layer,
    rows]`` and nowhere else."""
    rng = np.random.default_rng(heads_per_step)
    store = jnp.asarray(rng.normal(size=(3, 7, H, P, N)), jnp.float32)
    rows = jnp.asarray([2, 0, 6, 0, 1], jnp.int32)
    _, x, dt, a, b, c, d = _inputs(9, 5, 1)
    x, dt, b, c = x[:, 0], dt[:, 0] * (rows > 0)[:, None], b[:, 0], c[:, 0]
    y_ref, s_ref = ssm.ssm_decode(store, 1, rows, x, dt, a, b, c, d, use_kernel=False)
    y, s = ssm.ssm_decode(
        store, 1, rows, x, dt, a, b, c, d, use_kernel=True, interpret=True, heads_per_step=heads_per_step
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6, atol=1e-6)
    untouched = np.asarray(s).copy()
    untouched[1, [1, 2, 6]] = np.asarray(store)[1, [1, 2, 6]]
    np.testing.assert_array_equal(untouched, np.asarray(store))  # row 0 too: dt = 0 there


def test_prefill_and_decode_default_to_the_recurrence_off_the_tpu():
    """``use_kernel=None`` is decided in ops/ssm.py alone: on the CPU both
    operations are the XLA recurrence, bit for bit."""
    state, x, dt, a, b, c, d = _inputs(4, 2, 12)
    store = jnp.zeros((1, 3, H, P, N), jnp.float32).at[0, 1:].set(state)
    rows = jnp.asarray([1, 2], jnp.int32)
    y0, s0 = ssm.ssm_prefill(store, 0, rows, x, dt, a, b, c, d, chunk=8)
    y1, s1 = ssm.ssm_prefill(store, 0, rows, x, dt, a, b, c, d, chunk=8, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    yd0, sd0 = ssm.ssm_decode(store, 0, rows, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
    yd1, sd1 = ssm.ssm_decode(store, 0, rows, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(yd0), np.asarray(yd1))
    np.testing.assert_array_equal(np.asarray(sd0), np.asarray(sd1))
