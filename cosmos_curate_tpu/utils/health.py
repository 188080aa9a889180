"""Accelerator health gate: retrying TPU liveness probe.

Equivalent capability of the reference's GPU start helper
(cosmos_curate/core/utils/infra/gpu_start_helper.py — a retrying health
gate that blocks pipeline start until the accelerator answers, instead of
letting the first model call crash a worker mid-run).

The gate either passes or raises: it never degrades a run to the CPU. The
probe runs in a subprocess with a timeout, so a device that does not answer
cannot hang the prober. A chip belongs to one process at a time — the probe
child exits before the caller touches JAX, and the gate must run BEFORE
this process initializes JAX (afterwards the child cannot reach the chip).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def probe_accelerator(timeout_s: float = 120.0) -> bool:
    """One subprocess probe: does ``jax.devices()`` answer with a non-CPU
    backend within the timeout?"""
    code = (
        "import jax, sys; d = jax.devices(); "
        "sys.exit(0 if d and d[0].platform != 'cpu' else 1)"
    )
    try:
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, timeout=timeout_s
        )
        return r.returncode == 0
    except subprocess.TimeoutExpired:
        return False


def accelerator_health_gate(
    *,
    attempts: int = 3,
    probe_timeout_s: float = 120.0,
    backoff_s: float = 30.0,
) -> bool:
    """Retrying gate: True once the accelerator answers, RuntimeError when
    it never does — a TPU entry point fails with one clear message up front
    rather than crashing a worker later. Returns False without probing only
    when the caller pinned the CPU itself (``JAX_PLATFORMS=cpu``): nothing
    to gate."""
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        return False
    for i in range(attempts):
        if probe_accelerator(probe_timeout_s):
            if i:
                logger.info("accelerator answered on probe %d/%d", i + 1, attempts)
            return True
        if i + 1 < attempts:
            logger.warning(
                "accelerator probe %d/%d failed; retrying in %.0fs",
                i + 1, attempts, backoff_s,
            )
            time.sleep(backoff_s)
    raise RuntimeError(
        f"accelerator unhealthy after {attempts} probes x {probe_timeout_s:.0f}s "
        "— set JAX_PLATFORMS=cpu to run on the CPU on purpose"
    )
