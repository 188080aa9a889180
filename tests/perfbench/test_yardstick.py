"""The yardstick itself: the plain references against the program's models on
the tiny presets, operation and byte counts against a hand count, the trace
reduction on hand-made traces, and the refusal to run without a TPU."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import catalog, trace_reduce
from perfbench.roofline import ops_bytes
from perfbench.trace_reduce import Line, Plane


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_qwen2_reference_agrees_with_the_program(tied):
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm.model import VLM, VLM_QWEN2VL_TINY_TEST, init_cache
    from perfbench.drivers.caption_engine import make_params
    from perfbench.reference import qwen2_decoder as ref

    cfg = dataclasses.replace(VLM_QWEN2VL_TINY_TEST, qkv_bias=True, tied_embeddings=tied)
    params = make_params(cfg, 1, None)
    # biases start at zero: give them values, or a wrong bias would pass
    params = jax.tree.map(
        lambda x: x + 0.02 * jnp.cos(jnp.arange(x.size, dtype=x.dtype)).reshape(x.shape) if x.ndim == 1 else x,
        params,
    )
    n = 40
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, n), jnp.int32)
    want = ref.last_logits(params, ids, **ref.model_kwargs(cfg))
    model = VLM(cfg)
    pos = jnp.broadcast_to(jnp.arange(n)[None, :, None], (1, n, 3))
    logits, _, _ = model.apply(
        params, model.apply(params, ids[None], method=model.embed_tokens), *init_cache(cfg, 1),
        pos, jnp.zeros((1,), jnp.int32), jnp.full((1,), n, jnp.int32),
    )
    err = float(jnp.abs(logits[0, n - 1] - want).max() / jnp.abs(want).max())
    # the program computes in bfloat16 activations: a few 2^-8 over 2 layers
    assert err < 0.05, err


def test_clip_reference_agrees_with_the_program():
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vit import VIT_TINY_TEST, ViT, preprocess_frames
    from perfbench.drivers.split_pipeline import make_weights
    from perfbench.reference import clip_vit

    cfg = dataclasses.replace(VIT_TINY_TEST, act="quick_gelu", ln_eps=1e-5, preprocess="clip")
    params = make_weights(cfg, 3)
    frames = jnp.asarray(np.random.default_rng(0).integers(0, 255, (3, 32, 32, 3), np.uint8))
    pooled, _ = ViT(cfg).apply(params, preprocess_frames(frames, image_size=32, mode="clip"))
    pooled = np.asarray(pooled, np.float32)
    pooled /= np.linalg.norm(pooled, axis=-1, keepdims=True)
    sizes = dict(patch=cfg.patch_size, layers=cfg.layers, heads=cfg.heads, ln_eps=cfg.ln_eps)
    want = np.asarray(clip_vit.frame_embeddings(params, frames, **sizes))
    assert np.abs(pooled - want).max() < 0.01
    clip = np.asarray(clip_vit.clip_embedding(params, frames, **sizes))
    assert abs(np.linalg.norm(clip) - 1.0) < 1e-5


def test_paged_decode_bytes_against_a_hand_count():
    # one step, two rows at 17 and 32 valid positions, 16-token pages: 2 + 2 pages.
    # 4 pages x 16 positions x 2 KV heads x 128 x 2 bytes x (K and V) x 28 layers
    want = 4 * 16 * 2 * 128 * 2 * 2 * 28
    got = ops_bytes.paged_decode_kv_bytes(
        [17, 32, 0], n_layers=28, n_kv_heads=2, head_dim=128, block_size=16
    )
    assert got == want == 1_835_008
    flops = ops_bytes.paged_decode_flops([17, 32], n_layers=28, n_heads=12, head_dim=128)
    assert flops == 49 * 12 * 128 * 4 * 28
    share, bound = ops_bytes.roofline_share(
        flops=flops, bytes_moved=got, seconds=1e-4, peaks=catalog.peaks("TPU v5 lite")
    )
    assert bound == "memory" and share == pytest.approx(got / 819e9 / 1e-4)


def _trace():
    """Two chips, 100 us slice. Chip 0: ops at 10-30, 20-40 (overlap), 60-70,
    an all-reduce at 80-90. Host: slice span 0-100, engine.step 0-50, feed 50-60."""
    us = 1000
    # named as a TPU trace names them: by the whole HLO line
    ops0 = [("%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p), kind=kLoop", 10 * us, 20 * us),
            ('%_paged_decode.3 = bf16[4,2,16,128]{3,2,1,0} custom-call(s32[4]{0} %a), '
             'custom_call_target="tpu_custom_call"', 20 * us, 20 * us),
            ("%fusion.2.clone = f32[8]{0} fusion(f32[8]{0} %q)", 60 * us, 10 * us),
            ("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %fusion.2)", 80 * us, 10 * us)]
    ops1 = [("fusion.1", 0, 50 * us)]
    host = [(trace_reduce.SLICE_SPAN, 0, 100 * us), ("engine.step", 0, 50 * us), ("feed", 50 * us, 10 * us)]
    return [
        Plane("/device:TPU:0", [Line("XLA Ops", ops0), Line("Steps", [("1", 0, 100 * us)])]),
        Plane("/device:TPU:1", [Line("XLA Ops", ops1)]),
        Plane("/host:CPU", [Line("main", host)]),
    ]


def test_trace_reduction_on_a_hand_made_trace():
    s = trace_reduce.reduce(
        _trace(), kernels={"paged_decode": "^_?paged_decode"}, host_spans=("engine.step", "feed")
    )
    assert s.window_s == pytest.approx(100e-6) and s.chips == 2
    assert s.busy_s_by_chip == pytest.approx([50e-6, 50e-6])  # 10-40, 60-70, 80-90 | 0-50
    assert s.idle_share == pytest.approx(0.5)
    assert s.kernel_s == pytest.approx({"paged_decode": 20e-6}) and s.kernel_calls == {"paged_decode": 1}
    assert s.collective_s == pytest.approx(10e-6)
    assert s.op_s == pytest.approx({"fusion": 30e-6, "paged_decode": 20e-6, "all-reduce": 10e-6})
    # chip 0 idles 0-10, 40-60, 70-80, 90-100: 20 us under engine.step, 10 under feed
    assert s.gap_s == pytest.approx({"engine.step": 20e-6, "feed": 10e-6, "unattributed": 20e-6})
    top = s.breakdown()
    assert top["device_ops"][0] == ["fusion", pytest.approx(30e-6)]
    assert top["idle_gaps"][0][0] in ("engine.step", "unattributed")


def test_trace_reduction_refuses_a_missing_kernel_and_knows_no_cpu_device():
    with pytest.raises(LookupError, match="paged_prefill"):
        trace_reduce.reduce(_trace(), kernels={"paged_prefill": "^_?paged_prefill"})
    assert trace_reduce.reduce([Plane("/host:CPU", [])], kernels={}) is None
    assert trace_reduce.category("%fusion.12.3 = f32[2]{0} fusion(f32[2]{0} %copy.4)") == "fusion"
    assert trace_reduce.instruction("%copy-start.2 = (s32[4]{0}) copy-start(%t)") == "copy-start.2"
    assert trace_reduce.category("jit_decode_step_paged(17768756795752491165)") == "jit_decode_step_paged(17768756795752491165)"


def test_recorded_cut_round_trips(tmp_path):
    path = tmp_path / "cut.json"
    kept = trace_reduce.record(
        _trace(), path, start_ns=0, end_ns=100_000, keep=lambda p, l, n: l.name != "Steps"
    )
    assert kept == 8
    again = trace_reduce.reduce(
        trace_reduce.load_recorded(path), kernels={"paged_decode": "^_paged_decode"}, host_spans=("feed",)
    )
    assert again.busy_s == pytest.approx(50e-6) and again.gap_s["feed"] == pytest.approx(10e-6)


def test_the_run_refuses_a_platform_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "qwen2vl-2b.text-rewrite",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=catalog.CHECKOUT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "found no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_an_unknown_workload_is_refused_before_jax_is_touched():
    with pytest.raises(KeyError, match="unknown workload"):
        catalog.load_cell("no-such.cell")
    assert "jax" not in catalog.__dict__


def test_result_line_has_the_contracts_keys_only():
    from perfbench import run as run_module

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 123}

    cell = catalog.load_cell("qwen2vl-2b.text-rewrite")
    record = {
        "correct": True, "attempted": 7, "failed": 0, "devices": [Dev()],
        "end_to_end": {"output_tok_per_s": 412.5, "setup_s": 50.25}, "trace": None,
    }
    line = run_module.result_line(cell, record, trace=False, rehearse=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["metrics"] == {
        "output_tok_per_s": {"value": 412.5, "unit": "tokens/s"},
        "setup_s": {"value": 50.25, "unit": "s"},
    }
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 123}
    json.dumps(line)


def test_trace_reduction_reproduces_a_recorded_tpu_trace():
    """12 ms cut from this benchmark's first traced run on a TPU v5e (PR 22,
    qwen2vl-2b.windows-32f), events named as the chip names them. The numbers
    were worked out apart from trace_reduce (a plain loop over the intervals)."""
    planes = trace_reduce.load_recorded(catalog.HERE / "testdata" / "trace_cut.windows-32f.json")
    s = trace_reduce.reduce(
        planes, kernels={"paged_decode": r"^_?paged_decode"}, host_spans=("engine.step", "feed", "collect")
    )
    assert s.chips == 1 and s.events == 190  # of 191: one has no duration
    assert s.window_s == pytest.approx(9515431e-9)  # no slice span in the cut: first op to last
    assert s.busy_s == pytest.approx(9515212e-9)
    assert s.idle_share == pytest.approx(219 / 9515431, rel=1e-6)
    assert s.kernel_calls == {"paged_decode": 1}
    assert s.kernel_s["paged_decode"] == pytest.approx(404121e-9)
    # the whole-pool relayout copies around the kernel: 71% of this cut
    assert s.op_s["copy"] == pytest.approx(6789195e-9)
    assert s.collective_s == 0.0
    # the chip's 219 ns of gaps all lie inside the harness's engine.step span
    assert s.gap_s == pytest.approx({"engine.step": 219e-9, "feed": 0.0, "collect": 0.0, "unattributed": 0.0}, abs=1e-12)
    assert s.breakdown()["device_ops"][0][0] == "copy"


def test_layer_readers_on_a_hand_made_run():
    s = trace_reduce.reduce(
        _trace(), kernels={"paged_decode": "^_?paged_decode", "paged_prefill": "^fusion.2"},
        host_spans=("engine.step",),
    )
    kv = dict(n_layers=28, n_kv_heads=2, head_dim=128, block_size=16, dtype_bytes=2)
    run = {
        "trace": s, "window_s": 40.0, "compiles_in_window": 0,
        "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 10 * 2**30},
        "stats_delta": {"decode_tokens": 1000, "decode_s": 25.0},
        "phase_delta": {"prefill_s": 2.0, "vision_encode_s": 4.0},
        "slice": {"decode_lengths": [[17, 32]], "kv_shape": kv,
                  "attention_shape": dict(n_layers=28, n_heads=12, head_dim=128)},
    }

    def read(name):
        return catalog.load_module("layer_metrics", name).read(run)

    assert read("engine.decode_ms_per_token") == pytest.approx(25.0)
    assert read("engine.prefill_share") == pytest.approx(5.0)
    assert read("vision.encode_share") == pytest.approx(10.0)
    assert read("device.idle_share") == pytest.approx(50.0)
    assert read("device.peak_mem_gib") == pytest.approx(10.0)
    assert read("device.compiles_in_window") == 0.0
    assert read("collective.time_share") == pytest.approx(100 * 10 / 50)
    assert read("kernel.paged_attention_time_share") == pytest.approx(100 * 30 / 50)
    # 1,835,008 bytes (the hand count above) at 819 GB/s over the kernel's 20 us
    assert read("kernel.paged_decode_hbm_share") == pytest.approx(100 * 1_835_008 / 819e9 / 20e-6)
