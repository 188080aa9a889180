"""The three per-layer metrics read off the engine's step phases, each
against a hand-made run record. No JAX here."""

import pytest

from perfbench import catalog

PHASES = {
    "step_s": 9.9, "prefill_wait_s": 1.0, "decode_wait_s": 8.0,
    "decode_build_s": 0.05, "decode_dispatch_s": 0.2, "decode_sample_s": 0.15,
}
RUN = {"window_s": 10.0, "phase_delta": PHASES, "stats_delta": {"paged_kernel_steps": 100}}
WANT = {
    "engine.device_wait_share": 90.0,  # (1 + 8) / 10
    "engine.decode_host_ms_per_program": 4.0,  # 0.4 s / 100 programs
    "engine.stall_share": 1.0,  # 1 - 9.9 / 10
}
# what the parent's program reports: the old four keys and none of the new
OLD_PHASES = {"prep_s": 1.0, "vision_encode_s": 0.5, "prefill_s": 1.0, "decode_s": 8.0}
ZERO_DENOMINATOR = {
    "engine.device_wait_share": dict(RUN, window_s=0.0),
    "engine.decode_host_ms_per_program": dict(RUN, stats_delta={"paged_kernel_steps": 0}),
    "engine.stall_share": dict(RUN, window_s=0.0),
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_value(name):
    assert catalog.load_module("layer_metrics", name).read(RUN) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize(
    "run",
    [
        {"window_s": 10.0},
        {"window_s": 10.0, "phase_delta": None, "stats_delta": None},
        dict(RUN, phase_delta=OLD_PHASES),
    ],
    ids=["no_deltas", "deltas_none", "parent_program"],
)
def test_nothing_to_read(name, run):
    assert catalog.load_module("layer_metrics", name).read(run) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_zero_denominator(name):
    assert catalog.load_module("layer_metrics", name).read(ZERO_DENOMINATOR[name]) is None


def test_host_ms_needs_the_program_count():
    reader = catalog.load_module("layer_metrics", "engine.decode_host_ms_per_program")
    assert reader.read({"window_s": 10.0, "phase_delta": PHASES}) is None
