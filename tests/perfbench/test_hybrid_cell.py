"""The hybrid cell's own pieces of the yardstick: its operation and byte counts
against a hand count, its three readers on a hand-made record (and None where
there is nothing to read), its configuration file against the flavor, the
device trace's name for its kernel, and a rehearsal of its control flow."""

import json
import os
import re
import subprocess
import sys

import pytest

from perfbench import catalog
from perfbench.roofline import ssm_bytes

CELL = "granite-4.0-h-micro.text-rewrite"
SHAPE = dict(n_layers=36, n_heads=64, head_dim=64, d_state=128)


def _reader(name):
    return catalog.load_module("layer_metrics", name)


def test_ssm_decode_bytes_and_flops_against_a_hand_count():
    # one row, one layer: the 64 x 64 x 128 float32 state once each way is
    # 2 x 2 MiB; x and y 2 x 16 KiB, dt 256 B, B and C 2 x 512 B
    one = ssm_bytes.ssm_decode_bytes(1, **dict(SHAPE, n_layers=1))
    assert one == 2 * 2**21 + 2 * 2**14 + 256 + 1024
    assert ssm_bytes.ssm_decode_bytes(48, **SHAPE) == 48 * 36 * one
    # ISSUE 30's arithmetic: 48 rows read and write 6.75 GiB of state a step
    assert ssm_bytes.ssm_decode_bytes(48, **SHAPE) / 2**30 == pytest.approx(6.75, rel=0.01)
    assert ssm_bytes.ssm_decode_flops(1, **dict(SHAPE, n_layers=1)) == 5 * 2**19 + 2 * 2**12


def test_the_three_readers_on_a_hand_made_record():
    class Trace:
        busy_s_by_chip = [4.0]

    steps = [[600] * 40, [600] * 8]  # two decode programs: 40 and 8 decoding rows
    run = {
        "trace": Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 12 * 2**30},
        "ssm_trace": {"kernel_s": {"ssm_decode": 0.02}, "kernel_calls": {"ssm_decode": 72}},
        "slice": {"decode_lengths": steps, "ssm_shape": SHAPE},
        "recurrent": {"recurrent_state_bytes_per_chip": 3 * 2**30},
    }
    moved = ssm_bytes.ssm_decode_bytes(48, **SHAPE)
    assert _reader("kernel.ssm_decode_hbm_share").read(run) == pytest.approx(100 * moved / 819e9 / 0.02)
    assert _reader("kernel.ssm_time_share").read(run) == pytest.approx(0.5)
    assert _reader("engine.recurrent_state_gib").read(run) == 3.0


@pytest.mark.parametrize(
    "name", ["kernel.ssm_decode_hbm_share", "kernel.ssm_time_share", "engine.recurrent_state_gib"]
)
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """What the parent commit's runs and the other cells' records look like:
    a trace, a slice, and no state-space summary, shape or counter."""

    class Trace:
        busy_s_by_chip = [4.0]

    run = {
        "trace": Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {"decode_lengths": [[100]], "kv_shape": {}},
    }
    assert _reader(name).read(run) is None
    assert _reader(name).read(dict(run, ssm_trace=None, recurrent={})) is None


def test_the_cell_reports_the_new_metrics_and_the_old_cells_do_not():
    new = {"kernel.ssm_decode_hbm_share", "kernel.ssm_time_share", "engine.recurrent_state_gib"}
    assert new <= set(catalog.load_cell(CELL).per_layer)
    assert not new & set(catalog.load_cell("qwen2vl-2b.text-rewrite").per_layer)
    # the pair that separates architecture from traffic: one traffic file
    assert catalog.load_cell(CELL).traffic == catalog.load_cell("qwen2vl-2b.text-rewrite").traffic


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from perfbench.drivers.caption_engine_hybrid import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes)
    assert flavor.text_only and flavor.require_weights and flavor.model_chips == 1
    assert conf["layer_types"].count("attention") == 4 and len(flavor.cfg.ssm_layers) == 36
    with pytest.raises(ValueError, match="mamba_d_state"):
        check_config_file(dict(conf, mamba_d_state=64), flavor.cfg, flavor.kv_lanes)
    assert set(conf["assumed"]) >= {"head_dim", "ssm_state_dtype", "conv_state_dtype", "kv_cache_dtype", "weights"}
    for key, why in conf["check"].items():
        if key.endswith("_why"):
            assert "PLACEHOLDER" not in why and key[:-4] in conf["check"]


def test_the_trace_names_the_state_space_kernel_and_no_paged_pattern_takes_it():
    """As ``test_custom_call_is_named_as_the_benchmark_expects`` for the paged
    kernels: the reducer finds ``_ssm_decode`` by the instruction name of its
    ``tpu_custom_call``, compiled here for a described v5e at the cell's sizes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from cosmos_curate_tpu.ops import ssm
    from perfbench import trace_reduce
    from perfbench.drivers.caption_engine import KERNELS
    from perfbench.drivers.caption_engine_hybrid import SSM_KERNELS

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(store, rows, x, dt, a, b, c, d):
        return ssm.ssm_decode(store, 1, rows, x, dt, a, b, c, d, use_kernel=True, interpret=False)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        hlo = jax.jit(step, donate_argnums=(0,)).lower(
            arg((2, 9, 64, 64, 128)), arg((8,), jnp.int32), arg((8, 64, 64)), arg((8, 64)),
            arg((64,)), arg((8, 128)), arg((8, 128)), arg((64,)),
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    calls = [
        trace_reduce.instruction(line.strip())
        for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert calls and all(re.search(SSM_KERNELS["ssm_decode"], name) for name in calls), calls
    assert not any(re.search(rx, name) for rx in KERNELS.values() for name in calls)
    # the aliased store is updated in place: no copy of it in the program
    assert not re.search(r"f32\[2,9,64,64,128\]\{[^}]*\} copy\(", hlo)


def test_rehearsal_of_the_cells_control_flow_on_the_cpu():
    """Set-up, warmers, every comparison of ``correct``, ramp, window and
    result line at the tiny preset (about 50 s, most of it compiles)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", "2147483777",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=catalog.CHECKOUT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    # how many requests end in two seconds is the host's business, not the test's
    assert line["correct"] is True and line["failed"] == 0
    assert "state snapshot" in proc.stdout and "XLA path" in proc.stdout
    assert line["metrics"] == {}  # a rehearsal writes no device number
