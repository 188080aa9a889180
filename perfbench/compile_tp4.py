"""Scratch script, no chip needed: compiles the four-chip cell's decode and
prefill programs at full Qwen2.5-VL-7B width and depth for a *described*
``v5e:2x2`` and prints ``memory_analysis()`` per device. Run it before the
first four-chip call: what the chip's compiler refuses here costs no chip time.

    JAX_PLATFORMS=cpu python3 -m perfbench.compile_tp4 [--config qwen25vl-7b-tp4]

A compile that passes is not a chip run: nothing here is a measurement.
The two program bodies restate ``CaptionEngine.setup``'s
``prefill_batch_paged`` and ``decode_step_paged`` (the engine builds them as
closures over pools that need attached devices). The program picks its Pallas
kernels by asking ``jax.devices()``, which sees the CPU here, so the script
answers that question with the described devices while it lowers.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@contextlib.contextmanager
def described_devices(devices):
    import jax

    real = jax.devices
    jax.devices = lambda *a, **k: list(devices)
    try:
        yield
    finally:
        jax.devices = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen25vl-7b-tp4")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cosmos_curate_tpu.models.vlm.model import VLM, init_cache, vlm_flavor
    from cosmos_curate_tpu.parallel.axes import MODEL
    from cosmos_curate_tpu.parallel.sharding import spec_sharding
    from perfbench.catalog import _read_json, HERE

    conf = _read_json(HERE / "configs" / f"{args.config}.json")
    flavor = vlm_flavor(conf["flavor"])
    cfg, lanes = flavor.cfg, flavor.kv_lanes
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    n = conf["mesh"]["model"]
    mesh = Mesh(np.array(topo.devices[:n]), (MODEL,))
    model = VLM(cfg, mesh=mesh)
    size = cfg.qwen_vision.image_size
    bs = int(conf["serving"]["block_size"])

    def boxed(key):
        return model.init(
            key, jnp.zeros((1, 1, size, size, 3), jnp.uint8), jnp.zeros((1, 4), jnp.int32),
            *init_cache(cfg, 1), method=model.init_everything,
        )

    shapes = jax.eval_shape(boxed, jax.random.PRNGKey(0))
    specs = nn.get_partition_spec(shapes)
    params = jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=spec_sharding(mesh, spec)),
        nn.unbox(shapes), specs,
    )
    per_chip = sum(
        int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
    print(f"{args.config}: {sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) / 1e9:.3f} B "
          f"parameters, {per_chip / 2**30:.2f} GiB a chip over {dict(mesh.shape)}")
    lane_blocks = sum((length // bs) * slots for length, slots in lanes)
    n_blocks = 1 + lane_blocks + 8 * (256 // bs)
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, n_blocks, cfg.n_kv_heads, bs, cfg.head_dim), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, MODEL, None, None)),
    )
    rep = NamedSharding(mesh, P())

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def prefill(params, pool_k, pool_v, tables, embeds, write_index, t_valid, rope_pos):
        logits, pool_k, pool_v = model.apply(
            params, embeds, pool_k, pool_v, rope_pos, write_index, write_index + t_valid, tables,
            deepstack=None, logits_at=t_valid - 1, method=model.paged_forward,
        )
        return logits[:, 0], pool_k, pool_v

    def decode(params, pool_k, pool_v, tables, tokens, positions, rope_positions):
        embeds = model.apply(params, tokens[:, None], method=model.embed_tokens)
        rp = rope_positions[:, None]
        rp = jnp.broadcast_to(rp[..., None], (*rp.shape, 3))
        logits, pool_k, pool_v = model.apply(
            params, embeds, pool_k, pool_v, rp, positions, positions + 1, tables,
            method=model.paged_forward,
        )
        step = logits[:, 0]
        return jnp.argmax(step, axis=-1).astype(jnp.int32), step, pool_k, pool_v

    length, slots = lanes[-1]
    nbl = length // bs
    programs = [("decode", decode, slots, None)] + [
        (f"prefill rows {rows} T {t}", prefill, rows, t)
        for rows in (1, slots) for t in (int(conf["serving"]["prefill_chunk"]), 2048)
    ]
    ok = True
    with described_devices(topo.devices):
        for name, fn, rows, t in programs:
            if t is None:
                a = (params, pool, pool, arg((rows, nbl), jnp.int32), arg((rows,), jnp.int32),
                     arg((rows,), jnp.int32), arg((rows,), jnp.int32))
            else:
                a = (params, pool, pool, arg((rows, nbl), jnp.int32), arg((rows, t, cfg.dim), jnp.float32),
                     arg((rows,), jnp.int32), arg((rows,), jnp.int32), arg((rows, t, 3), jnp.int32))
            t0 = time.monotonic()
            compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*a).compile()
            text = compiled.as_text()
            mem = compiled.memory_analysis()
            holds = {op: op in text for op in ("all-reduce", "tpu_custom_call")}
            total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            print(
                f"{name} (lane {length}): compiled in {time.monotonic() - t0:.0f} s; per device: "
                f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
                f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, outputs {mem.output_size_in_bytes / 2**30:.2f} GiB "
                f"(aliased {mem.alias_size_in_bytes / 2**30:.2f}), total {total / 2**30:.2f} GiB; holds {holds}",
                flush=True,
            )
            ok &= all(holds.values()) and total < 15.75 * 2**30
    print("every program compiled, holds its kernel and collectives, and fits 16 GB" if ok else "NOT OK")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
