"""Times ``_delta_decode`` alone on the chip, at Olmo-Hybrid's widths (30 heads of
[96, 192]) or Solar-Open2's (``--widths solar``: 64 heads of [128, 128]), the
decay a scalar a head or, with ``--channel-decay``, a vector over ``dk`` (the
kernel's third column).

    chiprun -- python scripts/delta_decode_probe.py [--rows 8 44] [--heads-per-step 6 10 30]
    chiprun -- python scripts/delta_decode_probe.py --widths solar --channel-decay --rows 128 --heads-per-step 8 16 32

For each (rows, heads a grid step): 16 calls chained inside ONE jitted program
(each call's ``v`` depends on the last call's ``o``; one call from the host
costs 0.7 ms of dispatch), the store handed in as an ARGUMENT and donated, over
twelve layers' planes in turn; prints microseconds a call and the logical
bytes of ``perfbench/roofline/delta_bytes.py`` a second against 819 GB/s, and
checks the kernel against the XLA step on the way. ``--rehearse`` runs the
control flow at a tiny size on the CPU (interpret mode; no time means anything).
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, nargs="+", default=[8, 44])
    p.add_argument("--heads-per-step", type=int, nargs="+", default=[6, 10, 30])
    p.add_argument("--widths", choices=("olmo", "solar"), default="olmo")
    p.add_argument("--channel-decay", action="store_true", help="the decay a vector over dk")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.ops import delta_rule as dr
    from perfbench.roofline import delta_bytes

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU: nothing here is measured without one")
        return 1
    layers, h, dk, dv = (2, 4, 16, 24) if args.rehearse else (3, 64, 128, 128) if args.widths == "solar" else (12, 30, 96, 192)
    calls = 16
    print(f"device {jax.devices()[0].device_kind!r}; store [{layers}, rows + 1, {dk}, {h * dv}] float32")
    rng = np.random.default_rng(0)
    for rows in args.rows:
        store = jnp.asarray(rng.normal(size=(layers, rows + 1, dk, h * dv)), jnp.float32)
        q, k = (jnp.asarray(rng.normal(size=(rows, h, dk)) / dk**0.5, jnp.float32) for _ in range(2))
        v = jnp.asarray(rng.normal(size=(rows, h, dv)), jnp.float32)
        g = -jnp.asarray(rng.uniform(0.0, 1.0, (rows, h, dk) if args.channel_decay else (rows, h)), jnp.float32)
        beta = jnp.asarray(rng.uniform(0.0, 2.0, (rows, h)), jnp.float32)
        slots = jnp.arange(1, rows + 1, dtype=jnp.int32)
        want_o, want_s = dr.delta_decode(store, 1, slots, q, k, v, g, beta, use_kernel=False)
        for hb in args.heads_per_step:
            if h % hb or (not args.rehearse and (hb * dv) % 128):
                continue
            got_o, got_s = dr.delta_decode(
                store, 1, slots, q, k, v, g, beta, use_kernel=True, heads_per_step=hb, interpret=args.rehearse or None
            )
            err_o = float(jnp.abs(got_o - want_o).max() / jnp.abs(want_o).max())
            err_s = float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max())

            def chained(store, v):
                o = jnp.zeros_like(v)
                for i in range(calls):
                    o, store = dr.delta_decode(
                        store, i % layers, slots, q, k, v + 1e-3 * o, g, beta, use_kernel=True,
                        heads_per_step=hb, interpret=args.rehearse or None,
                    )
                return o, store

            run = jax.jit(chained, donate_argnums=(0,))
            o, work = run(store + 0.0, v)
            jax.block_until_ready(o)
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                o, work = run(work, v)
                jax.block_until_ready(o)
                best = min(best, time.perf_counter() - t0)
            per_call = best / calls
            moved = delta_bytes.delta_decode_bytes(rows, n_layers=1, n_heads=h, key_dim=dk, value_dim=dv)
            moved += 4 * rows * h * (dk - 1) if args.channel_decay else 0  # the decay a column, not a scalar
            print(
                f"rows {rows:3d} heads/step {hb:2d}: {per_call * 1e6:9.1f} us a call, {moved / 1e6:7.2f} MB logical, "
                f"{moved / per_call / 1e9:7.1f} GB/s = {100 * moved / per_call / 819e9:5.1f}% of 819 GB/s; "
                f"vs the XLA step: o {err_o:.2e}, state {err_s:.2e}"
            )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
