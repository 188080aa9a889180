"""Where a flavor's compiled decode (or prefill) program copies whole arrays.

Builds the caption engine of a one-chip flavor at full size with seeded
parameters, compiles ONE program of it (a lane's decode program, or a
prefill program of ``--prefill`` tokens a row) and prints every ``copy``
and every layout change XLA's memory-pressure pass put in
(``*.remat_compressed`` / ``*.remat_uncompressed``), grouped by the shape
they produce: the reading of ``tests/ops/test_tpu_compile.py``'s pool-copy
count on a whole program (PERF.md, PR 34). Nothing is measured.

    chiprun -- python scripts/pool_copies.py --flavor granite-4.0-h-micro
    JAX_PLATFORMS=cpu python scripts/pool_copies.py --describe ...

``--describe`` compiles for a DESCRIBED v5e chip without one (the engine is
built on the CPU, its programs lowered for the described device): what the
chip's compiler makes of the program, at no chip time. The whole text goes to
``chiprun_out/hlo/``.
"""

from __future__ import annotations

import argparse
import collections
import os
import pathlib
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(?[a-z0-9]+\[[^=]*?) ([\w\-]+)\(")
_ITEMSIZE = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "f16": 2, "s8": 1, "u8": 1, "pred": 1}


def _bytes(shape: str) -> int:
    m = re.match(r"\(?([a-z0-9]+)\[([\d,]*)\]", shape)
    if not m or m.group(1) not in _ITEMSIZE:
        return 0
    n = 1
    for s in filter(None, m.group(2).split(",")):
        n *= int(s)
    return n * _ITEMSIZE[m.group(1)]


def whole_array_copies(hlo: str) -> dict:
    """{(kind, shape): count} over the compiled text's ``copy`` instructions
    and remat layout changes; a shape is written as XLA prints it, layout
    and all, so a padded and a compressed form of one array stay apart."""
    found = collections.Counter()
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, opcode = m.groups()
        remat = re.search(r"remat_(un)?compressed", name)
        if opcode in ("copy", "copy-start") or remat:
            found[(remat.group(0) if remat else opcode, shape.strip())] += 1
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--flavor", default="granite-4.0-h-micro")
    ap.add_argument("--lane", type=int, default=0, help="index into the flavor's lanes")
    ap.add_argument("--prefill", type=int, default=0, help="tokens a row: the prefill program instead")
    ap.add_argument("--rows", type=int, default=1, help="rows of the prefill program")
    ap.add_argument("--describe", action="store_true", help="compile for a described v5e, no chip")
    ap.add_argument("--min-mib", type=float, default=1.0, help="leave out smaller arrays")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from perfbench.drivers.caption_engine_hybrid import make_params

    flavor = vlm_flavor(args.flavor)
    if flavor.model_chips != 1:
        raise SystemExit(f"{args.flavor} is served over {flavor.model_chips} chips: one-chip flavors only")
    cfg = flavor.cfg
    engine = CaptionEngine(
        cfg, kv_lanes=flavor.kv_lanes, params=make_params(cfg, args.seed),
        max_prefill_rows=flavor.prefill_rows,
    )
    engine.setup(args.seed)
    lane = engine.lanes[args.lane]

    target = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        target = SingleDeviceSharding(topo.devices[0])
        # the ops pick their kernels by asking for the platform: answer with
        # the described chip while the program is lowered
        jax.devices = lambda *a, **k: [topo.devices[0]]
        from cosmos_curate_tpu.ops import latent_attention, ssm

        ssm._on_tpu = latent_attention._on_tpu = lambda: True

    def abstract(x):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=target), x)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=target)

    pools = abstract((engine._pool_k, engine._pool_v))
    nbl = lane.length // engine.block_size
    store = abstract((engine._ssm, engine._conv)) if engine._recurrent else ()
    if args.prefill:
        n, t = args.rows, args.prefill
        rope = ints(n, t, 3) if cfg.mrope_section is not None else ints(n, t)
        embeds = jax.ShapeDtypeStruct((n, t, cfg.dim), jnp.float32, sharding=target)
        call = (abstract(engine.params), *pools, ints(n, nbl), embeds, ints(n), ints(n), rope)
        call += (None, *store, ints(n)) if store else ()
        program, what = engine._prefill_batch, f"prefill.{n}x{t}"
    else:
        n = lane.n_slots
        call = (abstract(engine.params), *pools, ints(n, nbl), ints(n), ints(n), ints(n))
        call += (*store, ints(n)) if store else ()
        call += (abstract(engine._expert_held),) if engine._counts_experts else ()
        program, what = engine._decode, f"decode.{n}"
    compiled = program.lower(*call).compile()
    hlo = compiled.as_text()
    out = pathlib.Path("chiprun_out/hlo")
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{args.flavor}.{what}.lane{lane.length}{'.described' if args.describe else ''}.txt"
    path.write_text(hlo)

    print(f"{args.flavor} {what}, lane {lane.length}: pools {engine._pool_k.shape}, "
          f"{engine._pool_k.nbytes / 2**20:.1f} MiB each; compiled text at {path}")
    print(f"memory: {compiled.memory_analysis()}")
    total = 0
    rows = sorted(whole_array_copies(hlo).items(), key=lambda kv: -kv[1] * _bytes(kv[0][1]))
    for (kind, shape), count in rows:
        mib = _bytes(shape) / 2**20
        if mib >= args.min_mib:
            total += count
            print(f"  {count:3d} x {kind:20s} {shape}  {mib:8.1f} MiB each")
    print(f"{total} copies or layout changes of arrays of {args.min_mib} MiB and over")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
