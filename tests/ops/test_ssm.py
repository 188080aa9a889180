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


GRANITE = (64, 64, 128)  # Granite-4.0-H's heads, head_dim, d_state


def _decode_inputs(seed, b, h, p, n):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (b, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 8.0, (h,)), jnp.float32)
    b_ = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, n)) / n**0.5, jnp.float32)  # y of order 1, as the tolerances assume
    return x, dt, a, b_, c, jnp.asarray(rng.normal(size=(h,)), jnp.float32)


@pytest.mark.parametrize("heads_per_step", [None, 2, 4, 8, 16, 32, 64])
def test_decode_kernel_at_granites_head_shape(heads_per_step):
    """64 heads of [64, 128], 3 rows (one of them idle on the garbage row), 2
    layers: the derived heads a step (None) and every explicit one, the whole
    row a step among them, against the XLA step at the tolerances above."""
    h, p, n = GRANITE
    rng = np.random.default_rng(7)
    store = jnp.asarray(rng.normal(size=(2, 4, h, p, n)), jnp.float32)
    rows = jnp.asarray([3, 0, 1], jnp.int32)
    x, dt, a, b, c, d = _decode_inputs(11, 3, h, p, n)
    dt = dt * (rows > 0)[:, None]
    y_ref, s_ref = ssm.ssm_decode(store, 1, rows, x, dt, a, b, c, d, use_kernel=False)
    y, s = ssm.ssm_decode(
        store, 1, rows, x, dt, a, b, c, d, use_kernel=True, interpret=True, heads_per_step=heads_per_step
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s)[0], np.asarray(store)[0])  # the other layer
    np.testing.assert_array_equal(np.asarray(s)[1, [0, 2]], np.asarray(store)[1, [0, 2]])  # garbage row, unvisited row


def test_decode_kernel_over_24_chained_steps_with_rows_permuted():
    """One store through 24 decode steps, the batch rows pointing at other
    store rows every step (and one of them idle in turn): ``y`` at every step
    and the store after the last within 1e-5 of the XLA step's."""
    h, p, n = 8, 16, 128
    rng = np.random.default_rng(24)
    store = ref = jnp.asarray(rng.normal(size=(2, 6, h, p, n)), jnp.float32)
    for step in range(24):
        rows = jnp.asarray(rng.permutation(5)[:4] + 1, jnp.int32).at[step % 4].set(0)
        x, dt, a, b, c, d = _decode_inputs(100 + step, 4, h, p, n)
        dt = dt * (rows > 0)[:, None]
        layer = step % 2
        y_ref, ref = ssm.ssm_decode(ref, layer, rows, x, dt, a, b, c, d, use_kernel=False)
        y, store = ssm.ssm_decode(store, layer, rows, x, dt, a, b, c, d, use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(store), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_all_rows_idle_on_the_garbage_row_leave_the_store_bit_for_bit():
    """What a lane with no request hands in: every row at 0 with dt = 0. The
    kernel visits the garbage row once a batch row and writes back what it read."""
    h, p, n = 8, 16, 128
    rng = np.random.default_rng(5)
    store = jnp.asarray(rng.normal(size=(2, 4, h, p, n)), jnp.float32)
    x, dt, a, b, c, d = _decode_inputs(6, 5, h, p, n)
    rows = jnp.zeros(5, jnp.int32)
    y, s = ssm.ssm_decode(store, 1, rows, x, dt * 0.0, a, b, c, d, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(store))
    y_ref, _ = ssm.ssm_decode(store, 1, rows, x, dt * 0.0, a, b, c, d, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("h", [8, 24, 64, 128])
def test_derived_heads_a_step_divides_the_heads_and_fits_the_kernels_vmem(h):
    """The state block comes in and goes out, each double-buffered: four
    buffers of ``hb x P x N`` float32 under what the kernel asks the compiler
    for, with room for the small blocks; an explicit cap is a cap."""
    _, p, n = GRANITE
    hb = ssm.heads_a_step(h, p, n)
    assert h % hb == 0 and 1 <= hb <= h
    assert 4 * hb * p * n * 4 <= 0.75 * ssm._VMEM_BYTES
    assert ssm.heads_a_step(h, p, n, at_most=4) == 4
    assert ssm.heads_a_step(h, p, n, at_most=10**6) == h


def test_the_probe_script_rehearses_on_the_cpu(capsys):
    """``scripts/ssm_decode_probe.py --rehearse``: the control flow at a tiny
    size in interpret mode (no time it prints means anything)."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[2] / "scripts" / "ssm_decode_probe.py"
    spec = importlib.util.spec_from_file_location("ssm_decode_probe", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    assert probe.main(["--rehearse", "--rows", "3", "--heads-per-step", "2", "8"]) == 0
    out = capsys.readouterr().out
    assert out.count("us a call") == 2 and "of 819 GB/s" in out
