"""Accelerator health gate (reference gpu_start_helper capability)."""

from __future__ import annotations

import os

import pytest

from cosmos_curate_tpu.utils import health


def test_cpu_pinned_env_short_circuits(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert health.accelerator_health_gate(attempts=1) is False


def test_retries_then_raises(monkeypatch):
    """The gate passes or raises: a chip that never answers is an error,
    never a quiet CPU run."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    calls = []
    monkeypatch.setattr(health, "probe_accelerator", lambda timeout_s=0: calls.append(1) or False)
    monkeypatch.setattr(health.time, "sleep", lambda s: None)
    with pytest.raises(RuntimeError, match="accelerator unhealthy after 3 probes"):
        health.accelerator_health_gate(attempts=3, backoff_s=0)
    assert len(calls) == 3


def test_recovers_mid_retries(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    answers = iter([False, True])
    monkeypatch.setattr(health, "probe_accelerator", lambda timeout_s=0: next(answers))
    monkeypatch.setattr(health.time, "sleep", lambda s: None)
    assert health.accelerator_health_gate(attempts=3, backoff_s=0) is True


def test_split_gate_raises_and_leaves_the_platform_alone(monkeypatch, tmp_path):
    """CURATE_HEALTH_GATE=on in front of a split run: an unhealthy chip
    aborts the run up front; JAX_PLATFORMS is never flipped to the CPU."""
    from cosmos_curate_tpu.pipelines.video.split import SplitPipelineArgs, run_split

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("CURATE_HEALTH_GATE", "on")
    monkeypatch.setattr(health, "probe_accelerator", lambda timeout_s=0: False)
    monkeypatch.setattr(health.time, "sleep", lambda s: None)
    args = SplitPipelineArgs(input_path=str(tmp_path), output_path=str(tmp_path / "out"))
    with pytest.raises(RuntimeError, match="accelerator unhealthy"):
        run_split(args)
    assert "JAX_PLATFORMS" not in os.environ


def test_probe_subprocess_times_out_cleanly():
    """A device that does not answer must surface as False after the
    timeout, never hang the prober. Simulated with a tiny timeout: even a
    healthy import can't finish in 0.2s, so the TimeoutExpired path runs."""
    assert health.probe_accelerator(timeout_s=0.2) is False
