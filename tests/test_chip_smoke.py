"""chip_smoke.py off the chip: it must refuse to run, and its four-chip
control flow must hold on four virtual CPU devices (the device check
stubbed HERE — the script itself has no way around it)."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True,
        text=True,
        timeout=600,
    )


def test_refuses_to_run_without_a_tpu():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert proc.stdout == ""  # no phase ran, no result printed
    message = [l for l in proc.stderr.splitlines() if l.startswith("chip_smoke:")]
    assert len(message) == 1 and "JAX found no TPU (platform='cpu')" in message[0]


# the sharded phase at the tiny test widths, 4 KV heads so that every
# device of a model=4 mesh gets a head plane
_STUBBED = """
import dataclasses, sys
import jax
import chip_smoke
from cosmos_curate_tpu.models.vlm.model import VLM_QWEN2VL_TINY_TEST

cfg = dataclasses.replace(VLM_QWEN2VL_TINY_TEST, n_heads=8, n_kv_heads=4)
chip_smoke.require_tpu = lambda: jax.devices()[0]
def run_sharded():
    chip_smoke.phase_sharded(cfg, ((64, 2),), totals=(40,), n_frames=2, max_new=2)
    # the 7B's deployment at test size, as the stage builds it, against float32
    engine = chip_smoke.phase_reference(
        "qwen25vl-tiny-test", n_frames=4, n_prefix=8, n_prompt=12, n_text=70, tol=0.06,
        prefill_chunk=16,
    )
    chip_smoke.phase_collective_names(engine)
    engine.shutdown()

chip_smoke.run_sharded = run_sharded
sys.exit(chip_smoke.main(["--chips", "4"]))
"""


def test_chips4_runs_only_the_sharded_paths_on_four_virtual_devices():
    proc = _run(
        ["-c", _STUBBED], XLA_FLAGS="--xla_force_host_platform_device_count=4"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    assert any("1/4 on each device" in l for l in lines)  # the KV pool was spread
    assert any("k-means over mesh" in l for l in lines)
    for request in ("check-window", "check-text"):  # first step + 8 decode steps each
        assert any(f"reference: {request} vs the float32 reference" in l for l in lines)
    assert any("prefilled in chunks of 16" in l for l in lines)
    named = next(l for l in lines if "collectives: the decode program's, by origin" in l)
    assert "'tp_reduce.attn_out': 2" in named and "'tp_reduce.mlp_down': 2" in named
    assert not any(phase in l for l in lines for phase in ("kernels:", "split:", "caption-2b:"))
