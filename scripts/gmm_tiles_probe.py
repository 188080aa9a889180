"""Times the grouped product of a sparse expert layer alone on the chip, by
shape and by tiles: a call of ``gmm`` and a whole layer (gate_up, activation,
down), at the row counts and expert shapes of the cells that run it.

    chiprun -- python scripts/gmm_tiles_probe.py
    chiprun -- python scripts/gmm_tiles_probe.py --shapes lfm2-prefill-8 deepseek-decode --rules cut whole whole-6m

A shape is a program's rows: ``tokens x top_k`` assignments routed at random
over the flavor's experts, sorted by expert as ``MoEFFN._sorted_experts`` sorts
them, the held experts' first. A rule gives the tiles: ``cut`` is
``ops/grouped_matmul.py::tiles`` without ``whole`` (what a flavor that holds a
share of its experts runs), ``whole`` is the same with it (what LFM2 and
Mellum2 run), and ``whole-<n>m`` is K whole with a block of a table of up to
``n`` MiB: not in the program, here to ask what another budget would buy. For each product and rule: the visits (grid steps over row tiles
that hold rows; ``gmm`` fetches a table's block anew at a visit unless the
last visit named the same block, which K cut in two never does), visits a
touched table, microseconds a call and the bytes of ONE read of the touched
tables a second against 819 GB/s. ``--calls`` calls (16) are chained in ONE
jitted program (each call's rows take 128 numbers of the last call's result,
in place; a call from the host costs 0.7 ms of dispatch, and 1/16 of one is in
the number printed). ``--rehearse`` runs the control flow at a tiny size on the
CPU (interpret mode; no time means anything).

What PR 58's builder read with the probe this one replaces (one v5e, us a call,
share of 819 GB/s; the logs went with the PR, PERF.md section 6 PR 59 has the
rest), and what sent PR 59 to K whole:

    lfm2-prefill-8 (8,192 rows, 128 a table, 127 visits for 64 tables)
      gate_up [64, 2048, 3072]  (128, 1024, 1024)  2,522 us  39.0%
                                (128, 2048,  768)  1,898 us  51.8%
      down    [64, 1536, 2048]  (128,  768, 1024)  1,358 us  36.2%
                                (128, 1536, 1024)  1,031 us  47.7%
    rows moved to a row-tile boundary (64 visits) bought the kernel more
    (gate_up 1,587 us at 256 rows a tile) and the layer nothing (-0.6%): three
    times the rows to gather, gate and scatter.
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

# name: (tokens a program, top_k, experts, held, dim, expert hidden); held == experts: ``whole``
SHAPES = {
    "lfm2-prefill-8": (2048, 4, 64, 64, 2048, 1536),
    "lfm2-prefill-4": (1024, 4, 64, 64, 2048, 1536),
    "lfm2-prefill-2": (512, 4, 64, 64, 2048, 1536),
    "lfm2-decode": (256, 4, 64, 64, 2048, 1536),
    "mellum2-prefill-4": (1024, 8, 64, 64, 2304, 896),
    "mellum2-decode-24": (24, 8, 64, 64, 2304, 896),
    "deepseek-decode": (256, 6, 160, 20, 5120, 1536),
    "deepseek-prefill-3": (768, 6, 160, 20, 5120, 1536),
    "trinity-prefill-4": (1024, 4, 256, 32, 3072, 3072),
    "solar-prefill": (1024, 8, 320, 40, 4096, 1280),
    "keye-prefill": (1024, 8, 128, 16, 2048, 768),
}
REHEARSAL = {"tiny-whole": (64, 2, 4, 4, 256, 128), "tiny-share": (64, 2, 8, 4, 256, 128)}
HBM = 819e9  # bytes a second, one v5e (perfbench/roofline has the table the cells use)


def k_whole(k: int, n: int, budget: int, itemsize: int = 2) -> tuple[int, int, int]:
    """K whole and the widest multiple of 128 that divides ``n`` inside ``budget`` bytes a block."""
    sides = [t for t in range(n, 0, -128) if n % t == 0 and t % 128 == 0] or [n]
    return 128, k, next((t for t in sides if k * t * itemsize <= budget), sides[-1])


def rule(name: str):
    from cosmos_curate_tpu.ops.grouped_matmul import tiles

    if name in ("cut", "whole"):
        return lambda k, n: tiles(k, n, whole=name == "whole")
    if name.startswith("whole-") and name.endswith("m"):
        return lambda k, n: k_whole(k, n, int(float(name[6:-1]) * 2**20))
    raise SystemExit(f"no rule {name!r}: cut, whole, whole-<MiB>m")


def visits(sizes, rows: int = 128) -> tuple[int, int]:
    """(grid steps over row tiles that hold rows, tables touched) for groups of ``sizes`` rows from row 0."""
    steps = touched = start = 0
    for size in map(int, sizes):
        if size:
            steps += (start + size - 1) // rows - start // rows + 1
            touched += 1
        start += size
    return steps, touched


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--shapes", nargs="+", default=None, help=f"default: the whole-held ones; of {sorted(SHAPES)}")
    p.add_argument("--rules", nargs="+", default=["cut", "whole"])
    p.add_argument("--calls", type=int, default=16)
    p.add_argument("--seed", type=int, default=59)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU: nothing here is measured without one")
        return 1
    table = REHEARSAL if args.rehearse else SHAPES
    names = args.shapes or [name for name, s in table.items() if args.rehearse or s[2] == s[3]]
    interpret = bool(args.rehearse)
    print(f"device: {jax.devices()[0].device_kind} x {jax.device_count()}; {args.calls} calls a program; seed {args.seed}")

    def product(lhs, rhs, sizes, tiling):
        return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tiling, interpret=interpret)

    def timed(step, x, *operands):
        """Seconds a call of ``step(x, *operands) -> [M, >=128]``, chained ``--calls`` times in one program."""

        def chained(x, *operands):
            for _ in range(args.calls):
                x = x.at[:1, :128].add(1e-3 * step(x, *operands)[:1, :128])
            return x

        run = jax.jit(chained, donate_argnums=(0,))
        x = jax.block_until_ready(run(jnp.copy(x), *operands))  # the caller keeps its rows
        best = float("inf")
        for _ in range(1 if args.rehearse else 5):
            t0 = time.perf_counter()
            x = jax.block_until_ready(run(x, *operands))
            best = min(best, time.perf_counter() - t0)
        return best / args.calls, x

    for name in names:
        tokens, top_k, experts, held, dim, hidden = table[name]
        rng = np.random.default_rng(args.seed)
        choice = np.argsort(rng.random((tokens, experts)), axis=1)[:, :top_k].reshape(-1)
        expert = np.where(choice < held, choice, held)  # absent experts' assignments last, in no table's group
        sizes = np.bincount(expert, minlength=held + 1)[:held]
        m = tokens * top_k
        m_pad = -(-m // 128) * 128
        steps, touched = visits(sizes)
        print(
            f"{name}: {tokens} tokens x top-{top_k} = {m} assignments, {int(sizes.sum())} on the {held} of {experts} "
            f"tables held, {touched} touched, {steps} visits ({steps / max(touched, 1):.2f} a touched table)"
        )
        keys = jax.random.split(jax.random.key(args.seed), 3)
        rows = jax.random.normal(keys[0], (m_pad, dim), jnp.bfloat16)
        tables = {
            "gate_up": jax.random.normal(keys[1], (held, dim, 2 * hidden), jnp.bfloat16) * dim**-0.5,
            "down": jax.random.normal(keys[2], (held, hidden, dim), jnp.bfloat16) * hidden**-0.5,
        }
        group_sizes = jnp.asarray(sizes, jnp.int32)
        live = int(sizes.sum())
        layer_us = {}
        for rule_name in args.rules:
            tiling = {which: rule(rule_name)(*t.shape[1:]) for which, t in tables.items()}
            for which, t in tables.items():
                x = rows if which == "gate_up" else jax.random.normal(keys[0], (m_pad, hidden), jnp.bfloat16)
                want = jax.lax.ragged_dot(x, t, group_sizes, preferred_element_type=jnp.float32)[:live]
                got = product(x, t, group_sizes, tiling[which])[:live].astype(jnp.float32)
                err = float(jnp.abs(got - want).max() / jnp.abs(want).max()) if live else 0.0
                sec, _ = timed(lambda x, t, g, tl=tiling[which]: product(x, t, g, tl), x, t, group_sizes)
                moved = touched * t.shape[1] * t.shape[2] * t.dtype.itemsize
                print(
                    f"  {which:7s} {list(t.shape)} {rule_name:9s} tiles {tiling[which]}  {sec * 1e6:8.1f} us a call  "
                    f"{sec * 1e6 / max(steps, 1):6.2f} us a visit  {moved / sec / 1e9:6.1f} GB/s of one read "
                    f"({100 * moved / sec / HBM:4.1f}% of 819)  vs ragged_dot {err:.1e}"
                )

            def layer(x, gate_up, down, g, tl=tiling):
                gate, up = jnp.split(product(x, gate_up, g, tl["gate_up"]), 2, axis=-1)
                return product(jax.nn.silu(gate) * up, down, g, tl["down"])

            sec, _ = timed(layer, rows, tables["gate_up"], tables["down"], group_sizes)
            layer_us[rule_name] = sec * 1e6
        first = layer_us[args.rules[0]]
        print(
            f"  layer   {name}: "
            + "  ".join(f"{r} {us:8.1f} us ({100 * (us / first - 1):+.1f}%)" for r, us in layer_us.items())
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
