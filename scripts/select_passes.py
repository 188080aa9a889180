"""What the indexer's threshold kernel costs, and how many passes it makes.

One v5e, one layer, Keye's shapes: a prefill chunk's 256 queries of one row of
a 32,768 lane at three contexts, and a decode step's one query a row (12 rows
of that lane, 4 of the 8,192 lane), a top-k of 2,048, seeded float32 scores
(standard normal, ``-inf`` where a query may not choose). For each shape:
``_sparse_select`` ms a call (16 calls chained inside one jitted program, each
call's ``n_live`` depending on the last one's answer, four repeats), the value
passes its blocks of queries ran and how many ran the tie search
(``select_threshold(with_passes=True)``), whether ``(tau, p_star)`` are
``select_threshold_reference``'s, and how many leading bits the highest and
lowest live key of a block share (what a search started at their first
differing bit would save). ``--parent DIR`` times the kernel of another
checkout's ``ops/sparse_attention.py`` beside it (PERF.md, PR 42).
``--prefill-probe`` adds one line: ``_sparse_prefill`` for a 256-token chunk of
one row at 16,384 positions (4 KV heads x 8 x 128, blocks of 128) beside the
time its ``4 x 32 x 256 x 16,384 x 128`` operations take at the chip's peak.

    chiprun -- python scripts/select_passes.py --parent _checkout/parent
    JAX_PLATFORMS=cpu python scripts/select_passes.py --rehearse
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

CALLS = 16  # chained inside one program: one call from the host costs 0.7 ms of dispatch
MXU_PEAK = 197e12  # one v5e, bfloat16 operations a second (PERF.md section 3)


def cases(rehearse: bool):
    """(top-k, [(name, lane, [(write_index, kv_len)] a row, queries a row)])."""
    if rehearse:
        return 12, [("chunk of 16 at 60", 64, [(44, 60)], 16), ("decode x3", 64, [(49, 50), (0, 0), (63, 64)], 1)]
    six = [9984, 14080, 18176, 22272, 26368, 30464]
    chunk = [(f"chunk of 256 at {c}", 32768, [(c - 256, c)], 256) for c in (9984, 18176, 30464)]
    steps = [
        ("decode x12, lane 32,768", 32768, [(c - 1, c) for c in six * 2], 1),
        ("decode x4, lane 8,192", 8192, [(c - 1, c) for c in [1728, 5824] * 2], 1),
    ]
    return 2048, chunk + steps


def load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="another checkout of this repo: its kernel is timed beside this one's")
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes, interpret mode: the control flow, no time")
    ap.add_argument("--prefill-probe", action="store_true", help="also time _sparse_prefill at 16k against its MXU bound")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=4)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.ops import sparse_attention as sa

    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print(f"no TPU here ({device}): a time from this device would mean nothing; --rehearse runs the control flow")
        return 1
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    kernels = {"change": sa}
    if args.parent:
        kernels["parent"] = load(
            pathlib.Path(args.parent) / "cosmos_curate_tpu" / "ops" / "sparse_attention.py", "parent_sparse_attention"
        )
    k, shapes = cases(args.rehearse)
    block = sa._SELECT_ROWS  # the queries a grid step of the kernel holds
    rng = np.random.default_rng(args.seed)

    def took(program, *operands):
        """ms a call of the ``CALLS`` a program chains (as text and as a number), and its last output."""
        jax.block_until_ready(program(*operands))
        repeats = 1 if args.rehearse else args.repeats
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = program(*operands)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / repeats / CALLS * 1e3
        return ("ran" if args.rehearse else f"{ms:.4f} ms"), ms, out

    if args.prefill_probe:
        hk, g, d, bs, t, context = (2, 2, 16, 4, 8, 32) if args.rehearse else (4, 8, 128, 128, 256, 16384)
        nbl = context // bs
        pool_k, pool_v = (
            jax.random.normal(jax.random.key(args.seed + i), (1, nbl + 1, hk, bs, d), jnp.bfloat16) for i in (1, 2)
        )
        tables = jnp.asarray(rng.permutation(nbl)[None] + 1, jnp.int32)
        q = jnp.asarray(rng.standard_normal((1, t, hk, g, d), np.float32), jnp.bfloat16)
        write, kv_len = jnp.asarray([context - t], jnp.int32), jnp.asarray([context], jnp.int32)
        chosen = jnp.asarray(rng.random((1, t, context)) < 0.125) & sa._seen(write, kv_len, t, context)
        attend = functools.partial(sa.sparse_prefill_attention, layer_index=0, use_kernel=True, interpret=args.rehearse)

        def chain_prefill(q, pool_k, pool_v, tables, write, kv_len, chosen):  # the pools as ARGUMENTS, never constants
            out = attend(q, pool_k, pool_v, tables, write, kv_len, chosen)
            for _ in range(CALLS - 1):  # a zero XLA cannot prove zero: the next call waits for this one
                nudge = jnp.any(jnp.isnan(out.astype(jnp.float32))).astype(q.dtype)
                out = attend(q + nudge, pool_k, pool_v, tables, write, kv_len, chosen)
            return out

        text, ms, _ = took(jax.jit(chain_prefill), q, pool_k, pool_v, tables, write, kv_len, chosen)
        flop = 4 * hk * g * t * context * d
        bound_ms = flop / MXU_PEAK * 1e3
        share = "" if args.rehearse else f": {100 * bound_ms / ms:.1f}% of the bound"
        print(
            f"_sparse_prefill, {t} queries of one row at {context}: {text} a call; {flop / 1e9:.1f} GFLOP, "
            f"{bound_ms:.4f} ms at the MXU's peak{share}",
            flush=True,
        )

    def select_ms(module, scores, live):
        select = functools.partial(module.select_threshold, use_kernel=True, interpret=args.rehearse)

        def chain(scores, live):
            out = select(scores, k, live)
            for _ in range(CALLS - 1):  # the same zero, on the bound of the work: the scores stay where they are
                out = select(scores, k, live + jnp.any(out[0] == out[1] + 1).astype(jnp.int32))
            return out

        text, _, out = took(jax.jit(chain), scores, live)
        return text, out

    for name, lane, rows, t in shapes:
        write = np.array([w for w, _ in rows])[:, None, None]
        kv_len = np.array([n for _, n in rows])[:, None, None]
        pos = np.arange(lane)[None, None]
        seen = (pos <= write + np.arange(t)[None, :, None]) & (pos < kv_len)
        scores = jnp.asarray(np.where(seen, rng.standard_normal(seen.shape, np.float32) + 0.0, -np.inf), jnp.float32)
        live = jnp.asarray(seen.sum(-1), jnp.int32)
        line = [f"{name}:"]
        chooses = np.asarray(live) > k
        want = [np.asarray(x)[chooses] for x in sa.select_threshold_reference(scores, k)]
        same = True
        for side, module in kernels.items():
            text, numbers = select_ms(module, scores, live)
            line.append(f"{side} {text}")
            same &= all((np.asarray(got)[chooses] == ref).all() for got, ref in zip(numbers, want))
        passes, tied = (
            np.asarray(x).reshape(-1)
            for x in sa.select_threshold(scores, k, live, use_kernel=True, interpret=args.rehearse, with_passes=True)[2:]
        )
        # a block's account stands in each of its rows (rows as the kernel lays them): read the first
        flat = chooses.reshape(-1)
        first = np.arange(0, flat.size, block)
        first = first[[flat[i : i + block].any() for i in first]]  # the blocks that search
        key = np.asarray(sa.order_key(scores)).astype(np.int64) + 2**31  # the unsigned order
        top, low = np.where(seen, key, 0).max(-1).reshape(-1), np.where(seen, key, 2**32).min(-1).reshape(-1)
        shared = [
            32 - int(top[i : i + block][flat[i : i + block]].max() ^ low[i : i + block][flat[i : i + block]].min()).bit_length()
            for i in first
        ]
        line.append(
            f"blocks that search {first.size} of {-(-flat.size // block)}, value passes a block "
            f"{passes[first].mean():.2f} ({passes[first].min()}-{passes[first].max()}), tie blocks {int(tied[first].sum())}, "
            f"leading bits a block's live keys share {min(shared)}-{max(shared)}, "
            f"(tau, p_star) the sort's where a query chooses: {same}"
        )
        print("  ".join(line), flush=True)
        if not same:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
