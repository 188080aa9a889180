"""Times ``_ssm_decode`` alone on the chip, at Granite-4.0-H-Micro's widths (64
heads of [64, 128], 36 state-space layers).

    chiprun -- python scripts/ssm_decode_probe.py [--rows 8 48] [--heads-per-step 16 32 64]
    chiprun -- python scripts/ssm_decode_probe.py --rows 48 --heads-per-step 64 --same-row

For each (rows, heads a grid step): ``--calls`` calls (16) chained inside ONE
jitted program (each call's ``x`` depends on the last call's ``y``; one call
from the host costs 0.7 ms of dispatch, and what is left of it, 1/16 of a
dispatch a call, is IN the number printed: about 45 us at 16 calls, 15 at
48), the store handed in as an ARGUMENT and donated, over the layers' planes
in turn; prints microseconds a call and the logical bytes of
``perfbench/roofline/ssm_bytes.py`` a second against 819 GB/s, and checks the
kernel against the XLA step on the way. Beside each reading, ``copy``: a
kernel of the same grid and the same aliased state blocks that writes back
what it read and does nothing else, which is what the DMA alone allows this
grid. ``--same-row`` points every row at the garbage row of one plane: with
the whole row a grid step no block moves between steps, and the time is the
kernel's own work. ``--rehearse`` runs the control flow at a tiny size on the
CPU (interpret mode; no time means anything).

What PR 55 read with it (the builder's chip runs, one v5e, 48 rows, us a call
in a program of 16 calls / of 48 calls; the cell's trace reads a call 64 us
shorter than the 16-call figure):

    the kernel as PR 30 wrote it (decay and dt x as [P, hb] columns, a lane of
    them sliced out and spread a head a register; y a lane reduction a head,
    placed by a where):            457 / 419  us,  54 / 59% of 819 GB/s
    that, the y contraction out:   369 / -    us   (a control)
    that, the two spreads out:     364 / -    us   (a control)
    copy (the DMA alone):          371 / 327  us,  67 / 76%
    the kernel now:                370 / 336  us,  67 / 74%

With no block moving (``--same-row``, 64 heads a step; the DMA of such a step
takes 6.8 us): the old kernel 8.7 us a grid step, without ``y`` 2.7, without
the spreads 1.8, the kernel now 3.8. Either control alone fits under the DMA;
reductions and spreads together (one cross-lane unit serves both) outlast it.
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _copy_call(store, layer, rows, *, hb, interpret):
    """The decode kernel's grid and state blocks, and a body that only copies."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, h, p, n = store.shape

    def body(layer_ref, rows_ref, state_ref, out_ref):
        out_ref[...] = state_ref[...]

    spec = pl.BlockSpec((None, None, hb, p, n), lambda i, j, layer, rows: (layer[0], rows[i], j, 0, 0))
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows.shape[0], h // hb), in_specs=[spec], out_specs=spec
        ),
        out_shape=jax.ShapeDtypeStruct(store.shape, store.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32), store)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, nargs="+", default=[8, 48])
    p.add_argument("--heads-per-step", type=int, nargs="+", default=None, help="default: what ops/ssm.py derives")
    p.add_argument("--calls", type=int, default=16)
    p.add_argument("--same-row", action="store_true", help="every row the garbage row of one plane")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.ops import ssm
    from perfbench.roofline import ssm_bytes

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU: nothing here is measured without one")
        return 1
    layers, h, hd, n = (2, 8, 16, 128) if args.rehearse else (36, 64, 64, 128)
    interpret = args.rehearse or None
    steps = args.heads_per_step or [ssm.heads_a_step(h, hd, n)]
    print(f"device {jax.devices()[0].device_kind!r}; store [{layers}, rows + 1, {h}, {hd}, {n}] float32; {args.calls} calls a program")
    for rows in args.rows:
        keys = jax.random.split(jax.random.PRNGKey(rows), 6)
        x = jax.random.normal(keys[0], (rows, h, hd), jnp.float32)
        dt = jax.random.uniform(keys[1], (rows, h), jnp.float32, 0.001, 0.3)
        a = -jnp.arange(1, h + 1, dtype=jnp.float32) / 8
        b = jax.random.normal(keys[2], (rows, n), jnp.float32)
        c = jax.random.normal(keys[3], (rows, n), jnp.float32) / n**0.5
        d = jnp.ones((h,), jnp.float32)
        slots = jnp.zeros(rows, jnp.int32) if args.same_row else jnp.arange(1, rows + 1, dtype=jnp.int32)
        plane = (lambda i: 0) if args.same_row else (lambda i: i % layers)
        small = jax.random.normal(keys[4], (1, rows + 1, h, hd, n), jnp.float32)  # the check's own store
        want_y, want_s = ssm.ssm_decode(small, 0, slots, x, dt, a, b, c, d, use_kernel=False)
        moved = ssm_bytes.ssm_decode_bytes(rows, n_layers=1, n_heads=h, head_dim=hd, d_state=n)
        store = jax.random.normal(keys[5], (layers, rows + 1, h, hd, n), jnp.float32)
        for hb in steps:
            if h % hb:
                continue
            got_y, got_s = ssm.ssm_decode(
                small, 0, slots, x, dt, a, b, c, d, use_kernel=True, heads_per_step=hb, interpret=interpret
            )
            # rows that collide on the garbage row have no one answer: no check with --same-row
            err_y = float("nan") if args.same_row else float(jnp.abs(got_y - want_y).max() / jnp.abs(want_y).max())
            err_s = float("nan") if args.same_row else float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max())
            del got_s

            def kernel(store, x):
                y = jnp.zeros_like(x)
                for i in range(args.calls):
                    y, store = ssm.ssm_decode(
                        store, plane(i), slots, x + 1e-3 * y, dt, a, b, c, d, use_kernel=True,
                        heads_per_step=hb, interpret=interpret,
                    )
                return y, store

            def copy(store, x):
                for i in range(args.calls):
                    store = _copy_call(store, plane(i), slots, hb=hb, interpret=bool(interpret))
                return x, store

            per_call = {}
            for name, chained in (("kernel", kernel), ("copy", copy)):
                run = jax.jit(chained, donate_argnums=(0,))
                y, store = run(store, x)
                jax.block_until_ready(y)
                best = float("inf")
                for _ in range(1 if args.rehearse else 5):
                    t0 = time.perf_counter()
                    y, store = run(store, x)
                    jax.block_until_ready((y, store))
                    best = min(best, time.perf_counter() - t0)
                per_call[name] = best / args.calls
            t = per_call["kernel"]
            print(
                f"rows {rows:3d} heads/step {hb:2d}: {t * 1e6:8.1f} us a call, {t * 1e6 / (rows * h // hb):6.2f} a grid step, "
                f"{moved / 1e6:7.2f} MB logical, {100 * moved / t / 819e9:5.1f}% of 819 GB/s; "
                f"copy {per_call['copy'] * 1e6:8.1f} us; vs the XLA step: y {err_y:.2e}, state {err_s:.2e}"
            )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
