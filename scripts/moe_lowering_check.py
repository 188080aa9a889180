"""Do two checkouts lower a sparse expert layer to the same program?

    git archive <parent> | tar -x -C _checkout/parent
    python scripts/moe_lowering_check.py [--parent _checkout/parent]

Lowers ONE ``MoEFFN`` (``models/vlm/model.py``), its grouped products through
the Pallas kernel, from this checkout and from ``--parent``, and compares the
lowered texts by their SHA-256: the tiny test presets through the kernel in
interpret mode on the CPU, the cells' presets at their real widths for a
DESCRIBED TPU v5e (lowered, not compiled: StableHLO with Mosaic's serialized
kernel in it, block shapes and all). Needs no chip.

One thing in that text is NOT the program: Mosaic serializes each kernel WITH
its debug locations, the files and lines of the Python stack that traced it
(``.../ops/grouped_matmul.py``, ``.../models/vlm/model.py``, this script), so
the raw text of two checkouts differs as soon as one lies elsewhere or has a
line more above a call, whatever the kernels do. (JAX's compile cache keys that
raw body: a checkout moved, or a line added to ``model.py``, recompiles every
program that holds a kernel.) ``without_debug_info`` prints each kernel body
back without its locations; what is compared is that text, and the report says
beside it whether the raw texts happen to match too. Exits 1 unless

  - every flavor that holds a SHARE of its experts (DeepSeek, Trinity, Keye,
    Solar) lowers to the parent's text, character for character, and
  - the check can see tiles at all: a real preset that holds EVERY expert (LFM2,
    Mellum2; ``grouped_matmul(..., whole=True)`` since PR 59) must NOT lower to
    the text of a parent whose ``grouped_matmul`` has no ``whole``. Against a
    parent that has it every text must match.

Each checkout is lowered in a process of its own (``--emit --root DIR``), so
neither imports the other's modules. ``tests/ops/test_grouped_matmul.py`` and
``tests/ops/test_tpu_compile.py`` ask the same of a word-for-word copy of PR
57's ``grouped_matmul``; this asks it of the parent's files.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import pathlib
import re
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
SHARE_HELD = ("DEEPSEEK_V2", "TRINITY", "KEYE", "SOLAR_OPEN2")
TINY = [
    "VLM_DEEPSEEK_V2_TINY_TEST", "VLM_TRINITY_TINY_TEST", "VLM_KEYE_TINY_TEST", "VLM_SOLAR_OPEN2_TINY_TEST",
    "VLM_LFM2_MOE_TINY_TEST", "VLM_MELLUM2_TINY_TEST",
]
REAL = [
    "VLM_DEEPSEEK_V2_EP8", "VLM_TRINITY_LARGE_EP8", "VLM_KEYE_VL2_A3B_EP8", "VLM_SOLAR_OPEN2_EP8",
    "VLM_LFM2_24B_A2B_PP5", "VLM_MELLUM2_12B_PP4",
]
ROWS = {"tiny": (48,), "v5e": (256, 1024)}  # tokens a program: a decode step's and a prefill group's


def moe_layer_text(cfg, tokens, product, *, sharding=None):
    """The lowered text of one ``MoEFFN`` of ``cfg`` over ``tokens`` rows, its
    grouped products going through ``product`` (which replaces
    ``ops.grouped_matmul.grouped_matmul`` while the layer is traced)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from cosmos_curate_tpu.ops import grouped_matmul as gmm_ops

    layer = vlm_model.MoEFFN(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, tokens, cfg.dim), jnp.bfloat16, sharding=sharding)
    params = jax.eval_shape(lambda: nn.unbox(layer.init(jax.random.key(0), jnp.zeros(x.shape, x.dtype))))
    if sharding is not None:
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), params)
    saved = gmm_ops.grouped_matmul
    gmm_ops.grouped_matmul = product
    try:
        return jax.jit(layer.apply).lower(params, x).as_text()
    finally:
        gmm_ops.grouped_matmul = saved


def without_debug_info(text: str) -> str:
    """``text`` with the serialized body of every ``tpu_custom_call`` replaced by
    its assembly stripped of debug locations (no body: ``text`` itself)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    from jax._src.lib.mlir import passmanager as pm

    def plain(found):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # the serialized form is its own dialect, ``stable_mosaic``
        with ctx:
            kernel = ir.Module.parse(base64.b64decode(found.group(2)))
            pm.PassManager.parse("builtin.module(strip-debuginfo)").run(kernel.operation)
            return found.group(1) + kernel.operation.get_asm(enable_debug_info=False) + found.group(3)

    return re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)', plain, text)


def emit(root: pathlib.Path) -> dict:
    """For the checkout at ``root``: ``cases``, ``{case: [sha256 of the lowered
    text without debug locations, of the raw text]}``, and ``knows_whole``,
    whether its ``grouped_matmul`` takes the keyword."""
    sys.path.insert(0, str(root))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import inspect

    from jax.sharding import SingleDeviceSharding

    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from cosmos_curate_tpu.ops.grouped_matmul import grouped_matmul

    assert pathlib.Path(vlm_model.__file__).resolve().is_relative_to(root.resolve()), vlm_model.__file__
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        v5e = SingleDeviceSharding(topo.devices[0])
    except Exception as e:  # no TPU compiler in this installation
        print(f"no v5e can be described here, the real shapes are not lowered: {e}", file=sys.stderr)
        v5e = None
    found = {}
    for where, presets, sharding in (("tiny", TINY, None), ("v5e", REAL, v5e)):
        if where == "v5e" and v5e is None:
            continue
        for preset in presets:
            cfg = getattr(vlm_model, preset, None)
            if cfg is None:
                continue
            for tokens in ROWS[where]:
                through_kernel = lambda *a, **kw: grouped_matmul(*a, **kw, use_kernel=True, interpret=sharding is None)
                text = moe_layer_text(cfg, tokens, through_kernel, sharding=sharding)
                assert "ragged_dot" not in text and (sharding is None or text.count("tpu_custom_call") == 2), preset
                found[f"{where}:{preset}:{tokens}"] = [
                    hashlib.sha256(t.encode()).hexdigest() for t in (without_debug_info(text), text)
                ]
    return {"knows_whole": "whole" in inspect.signature(grouped_matmul).parameters, "cases": found}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--parent", type=pathlib.Path, default=ROOT / "_checkout" / "parent")
    p.add_argument("--emit", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--root", type=pathlib.Path, default=ROOT, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.emit:
        print(json.dumps(emit(args.root)))
        return 0
    if not (args.parent / "cosmos_curate_tpu").is_dir():
        print(f"no checkout at {args.parent}: git archive <parent> | tar -x -C {args.parent}")
        return 2
    sides = {}
    for side, root in (("parent", args.parent), ("this", ROOT)):
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"} | {"JAX_PLATFORMS": "cpu"}
        proc = subprocess.run(
            [sys.executable, __file__, "--emit", "--root", str(root)], cwd=root, env=env, capture_output=True, text=True
        )
        if proc.returncode:
            print(proc.stderr[-4000:])
            return 2
        sides[side] = json.loads(proc.stdout.strip().splitlines()[-1])
    wrong = []
    every = sides["parent"]["knows_whole"]  # a parent with ``whole``: every text must match
    for case, (digest, raw) in sides["this"]["cases"].items():
        where, preset, _ = case.split(":")
        then, raw_then = sides["parent"]["cases"].get(case, (None, None))
        same = digest == then
        share_held = any(family in preset for family in SHARE_HELD)
        want_same = share_held or every or where == "tiny"  # a test's widths are never cut: one text either way
        verdict = "same text" if same else "ANOTHER text" if then else "not in the parent"
        verdict += " (raw too)" if same and raw == raw_then else " (raw: the kernels' locations differ)" if same else ""
        ok = then is not None and same == want_same
        note = "" if ok else "   <-- " + ("must be the parent's" if want_same else "the check cannot see tiles")
        wrong += [] if ok else [case]
        print(f"{case:44s} {digest[:16]}  parent {str(then)[:16]}  {verdict}{note}")
    if not any(case.startswith("v5e:") for case in sides["this"]["cases"]):
        print("the real shapes were not lowered (no v5e could be described): nothing is shown of the cells' programs")
        return 1
    print("FAILED: " + ", ".join(wrong) if wrong else "ok: the share-held flavors' layers lower to the parent's text")
    return 1 if wrong else 0


if __name__ == "__main__":
    raise SystemExit(main())
