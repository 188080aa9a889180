"""ops/delta_rule.py under a decay a CHANNEL (Kimi Delta Attention: ``Diag(a_t)``
where the gated delta rule has a scalar): the step, the scan, the chunked form
and the Pallas decode kernel (interpret mode) against the recurrence written
out token by token in numpy; a scalar decay broadcast over ``dk`` against the
scalar rule, to the last bit; padding; and STRONG decay, where the textbook
factoring of the in-chunk products overflows float32."""

import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.ops import delta_rule as dr

H, DK, DV = 4, 16, 24  # dv no multiple of dk, and 4 x 24 lanes make no tile


def _inputs(seed, b, t, *, decay=(0.001, 0.5), beta=(0.0, 2.0), common=0.5, dims=(H, DK, DV)):
    """As tests/ops/test_delta_rule.py's, with ``g`` ``[B, T, H, dk]``: a token's
    decay a channel, its logarithm uniform in ``-decay``."""
    h, dk, dv = dims
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.normal(size=shape) + common
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(b, t, h, dk) * dk**-0.5
    g = -rng.uniform(*decay, (b, t, h, dk))
    arrays = (rng.normal(size=(b, h, dk, dv)), q, unit(b, t, h, dk), rng.normal(size=(b, t, h, dv)), g,
              rng.uniform(*beta, (b, t, h)))
    return tuple(jnp.asarray(x, jnp.float32) for x in arrays)


def _recurrence(state, q, k, v, g, beta):
    """The rule itself, float64, a token and a head at a time:
    ``S' = Diag(exp(g)) S; S = S' + k (x) beta (v - S'^T k); o = S^T q``."""
    state, q, k, v, g, beta = (np.asarray(x, np.float64) for x in (state, q, k, v, g, beta))
    b, t, h, _ = k.shape
    out = np.zeros((b, t, h, v.shape[-1]))
    state = state.copy()
    for i in range(b):
        for j in range(h):
            s = state[i, j]
            for n in range(t):
                s = np.exp(g[i, n, j])[:, None] * s
                s = s + np.outer(k[i, n, j], beta[i, n, j] * (v[i, n, j] - s.T @ k[i, n, j]))
                out[i, n, j] = s.T @ q[i, n, j]
            state[i, j] = s
    return out, state


def _close(got, want, tol=2e-5):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol * max(float(np.abs(want).max()), 1e-30))


def test_step_and_scan_are_the_recurrence_written_out():
    state, *rest = _inputs(1, 2, 9)
    o_want, s_want = _recurrence(state, *rest)
    o, s = dr.delta_scan_reference(state, *rest)
    _close(o, o_want)
    _close(s, s_want)
    o1, s1 = dr.delta_step_reference(state, *(x[:, 0] for x in rest))
    _close(o1, o_want[:, 0])
    _close(s1, _recurrence(state, *(x[:, :1] for x in rest))[1])


@pytest.mark.parametrize("fn", ["step", "scan"])
def test_a_scalar_decay_broadcast_over_dk_is_the_scalar_rule_to_the_last_bit(fn):
    """ONE rule: the decay's shape is the only difference, so the same numbers
    in both shapes give the same bits."""
    state, q, k, v, g, beta = _inputs(2, 2, 13)
    scalar = g[..., 0]
    wide = jnp.broadcast_to(scalar[..., None], g.shape)
    if fn == "step":
        a = dr.delta_step_reference(state, q[:, 0], k[:, 0], v[:, 0], scalar[:, 0], beta[:, 0])
        b = dr.delta_step_reference(state, q[:, 0], k[:, 0], v[:, 0], wide[:, 0], beta[:, 0])
    else:
        a = dr.delta_scan_reference(state, q, k, v, scalar, beta)
        b = dr.delta_scan_reference(state, q, k, v, wide, beta)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize(
    "t,chunk,beta",
    [
        pytest.param(128, 64, (0.0, 2.0), id="two-whole-chunks-of-four-sub-blocks"),
        pytest.param(150, 64, (0.0, 2.0), id="ragged-tail-of-22"),
        pytest.param(37, 64, (0.0, 2.0), id="shorter-than-a-chunk-rounded-to-48"),
        pytest.param(70, 8, (0.0, 2.0), id="chunks-of-8-under-a-sub-block"),
        pytest.param(96, 32, (0.0, 2.0), id="chunks-of-32-two-sub-blocks"),
        pytest.param(11, 64, (0.0, 2.0), id="shorter-than-a-sub-block"),
        pytest.param(192, 64, (1.9, 2.0), id="beta-near-2"),
    ],
)
def test_chunked_scan_agrees_with_the_recurrence(t, chunk, beta):
    state, *rest = _inputs(t, 2, t, beta=beta)
    o_want, s_want = _recurrence(state, *rest)
    o, s = dr.delta_chunk_scan(state, *rest, chunk=chunk)
    _close(o, o_want)
    _close(s, s_want)
    # and the scalar rule's chunked form on the same numbers, where the decay is one a head
    scalar = rest[3][..., 0]
    o2, s2 = dr.delta_chunk_scan(state, *rest[:3], jnp.broadcast_to(scalar[..., None], rest[3].shape), rest[4], chunk=chunk)
    o3, s3 = dr.delta_chunk_scan(state, *rest[:3], scalar, rest[4], chunk=chunk)
    _close(o2, o3)
    _close(s2, s3)


@pytest.mark.parametrize(
    "decay",
    [
        pytest.param((60.0, 110.0), id="A-16-softplus-at-5"),
        pytest.param((1.0, 4.0), id="e-2.5-a-token"),
        pytest.param((0.0, 110.0), id="channels-that-keep-beside-channels-that-forget"),
    ],
)
def test_strong_decay_neither_overflows_nor_drifts(decay):
    """``exp(-cs_s)`` of the textbook factoring is ``inf`` here (cs reaches
    -160 to -7,000 inside 64 tokens; float32 ends at e^88): the sub-block form
    never takes ``exp`` of a positive number."""
    state, *rest = _inputs(7, 2, 130, decay=decay)
    cs = np.cumsum(np.asarray(rest[3])[:, :64], axis=1)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-cs.astype(np.float32))).all()  # what the factoring would form
    o_want, s_want = _recurrence(state, *rest)
    for o, s in (dr.delta_chunk_scan(state, *rest, chunk=64), dr.delta_scan_reference(state, *rest)):
        assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
        np.testing.assert_allclose(np.asarray(o), o_want, rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), s_want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("scan", ["recurrence", "chunked", "kernel"])
def test_positions_with_beta_and_g_zero_leave_the_state_where_it_was(scan):
    """What the mixer hands in for padding: ``beta = 0`` and ``g = 0`` over all
    of a head's channels advance nothing."""
    state, q, k, v, g, beta = _inputs(3, 2, 21)
    masked_g, masked_beta = g.at[:, 10:].set(0.0), beta.at[:, 10:].set(0.0)
    if scan == "kernel":
        store = jnp.stack([dr.pack_state(jnp.concatenate([state, state]))] * 2)  # [2, 4, dk, H * dv]
        rows = jnp.asarray([1, 2])
        _, after = dr.delta_decode(
            store, 1, rows, q[:, 12], k[:, 12], v[:, 12], masked_g[:, 12], masked_beta[:, 12],
            use_kernel=True, interpret=True, heads_per_step=2,
        )
        np.testing.assert_array_equal(np.asarray(after), np.asarray(store))
        return
    run = dr.delta_scan_reference if scan == "recurrence" else lambda *a: dr.delta_chunk_scan(*a, chunk=8)
    _, s_short = run(state, q[:, :10], k[:, :10], v[:, :10], g[:, :10], beta[:, :10])
    _, s_masked = run(state, q, k, v, masked_g, masked_beta)
    _close(s_masked, s_short, 1e-6)


@pytest.mark.parametrize(
    "dims,heads_per_step",
    [
        pytest.param((4, 16, 24), 1, id="a-head-a-step"),
        pytest.param((4, 16, 24), 4, id="lanes-that-make-no-tile"),
        pytest.param((4, 16, 64), 4, id="two-heads-a-tile"),
        pytest.param((8, 16, 128), 4, id="a-head-a-tile-two-groups"),
        pytest.param((8, 16, 128), None, id="the-rule's-own-heads-a-step"),
    ],
)
def test_decode_kernel_agrees_with_the_recurrence(dims, heads_per_step):
    """Rows out of order, one row idle on the garbage row, a layer that is not
    the first: the kernel's third column (``k | q | a`` in one block) against
    the recurrence, and every row it was not given left as it was."""
    h, dk, dv = dims
    state, q, k, v, g, beta = _inputs(11, 4, 1, dims=dims)
    rng = np.random.default_rng(5)
    store = jnp.asarray(rng.normal(size=(3, 7, dk, h * dv)), jnp.float32)
    rows = jnp.asarray([5, 0, 2, 6])
    live = (rows > 0)[:, None]
    g1, beta1 = g[:, 0] * live[..., None], beta[:, 0] * live
    o, after = dr.delta_decode(
        store, 1, rows, q[:, 0], k[:, 0], v[:, 0], g1, beta1, use_kernel=True, interpret=True,
        heads_per_step=heads_per_step,
    )
    before = dr.unpack_state(store[1, rows], h)
    o_want, s_want = _recurrence(before, q, k, v, g1[:, None], beta1[:, None])
    _close(o[np.asarray(rows) > 0], o_want[np.asarray(rows) > 0, 0])
    _close(dr.unpack_state(after[1, rows], h), s_want)
    untouched = np.ones(7, bool)
    untouched[np.asarray(rows)[np.asarray(rows) > 0]] = False
    np.testing.assert_array_equal(np.asarray(after[1, untouched]), np.asarray(store[1, untouched]))
    np.testing.assert_array_equal(np.asarray(after)[[0, 2]], np.asarray(store)[[0, 2]])
    # the XLA step the engine's other programs run
    o_x, after_x = dr.delta_decode(store, 1, rows, q[:, 0], k[:, 0], v[:, 0], g1, beta1, use_kernel=False)
    _close(o, o_x)
    _close(after, after_x)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_prefill_through_the_store_then_decode(use_kernel):
    """``delta_prefill`` reads and writes the rows' states in the store (the
    chunked form on the kernel side, the recurrence on the other), a decode
    step goes on from there: against the recurrence over all the tokens."""
    state, q, k, v, g, beta = _inputs(13, 2, 40)
    rows = jnp.asarray([2, 1])
    store = jnp.zeros((2, 3, DK, H * DV), jnp.float32).at[0, rows].set(dr.pack_state(state))
    o, store = dr.delta_prefill(store, 0, rows, q[:, :39], k[:, :39], v[:, :39], g[:, :39], beta[:, :39], chunk=32,
                                use_kernel=use_kernel)
    o_last, store = dr.delta_decode(store, 0, rows, q[:, 39], k[:, 39], v[:, 39], g[:, 39], beta[:, 39],
                                    use_kernel=use_kernel, interpret=True)
    o_want, s_want = _recurrence(state, q, k, v, g, beta)
    _close(o, o_want[:, :39])
    _close(o_last, o_want[:, 39])
    _close(dr.unpack_state(store[0, rows], H), s_want)
    np.testing.assert_array_equal(np.asarray(store[1]), 0.0)


def test_heads_a_grid_step_follow_the_state_a_head():
    """A megabyte of state a grid step: 10 of Olmo-Hybrid's 30 heads of [96,
    192] (what PR 44 measured best), 16 of Solar-Open2's 64 of [128, 128]."""
    assert dr.heads_a_step(30, 96, 192) == 10 and dr.heads_a_step(64, 128, 128) == 16
    assert dr.heads_a_step(4, 16, 24) == 4 and dr.heads_a_step(30, 96, 192, at_most=8) == 6
