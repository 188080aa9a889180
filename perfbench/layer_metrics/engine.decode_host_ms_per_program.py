"""Milliseconds of host work around one decode program, the wait for its
result left out: delta (``decode_build_s`` + ``decode_dispatch_s`` +
``decode_sample_s``) / delta ``paged_kernel_steps`` (one a decode program).
Buffers, host-to-device puts and dispatch before it; sampling and finishing
after it. None from a program that does not time these phases."""

UNIT, LAYER, MOVES, SOURCE = "ms/program", "caption engine", "output_tok_per_s", "program_span"

KEYS = ("decode_build_s", "decode_dispatch_s", "decode_sample_s")


def read(run):
    d = run.get("phase_delta") or {}
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    if not programs or any(k not in d for k in KEYS):
        return None
    return 1e3 * sum(d[k] for k in KEYS) / programs
