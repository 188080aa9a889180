"""HBM-roofline share of the gated short convolutions in the DECODE programs of
the traced slice: what the mixers have to move (roofline/lfm2_bytes.py: ``W_in``,
``W_out`` and the taps once a program a conv layer; a live row's normed input
in, its output out and its tails read and written) over the device time of the
instructions under the scope ``mixer.short_conv`` in those programs, against
the chip's HBM bandwidth. A decode program's two products are up to 256 rows
against 33.5 MB of weights a layer: the bytes bound them, at 256 rows by a
tenth only (0.39 ms of bytes, 0.35 ms of operations: the chip's ridge), so this
IS the mixer's roofline share there (the prefill programs' products are
compute-bound; their time is in ``kernel.short_conv_time_share``). An idle
row's traffic is not counted, which lowers the share. Nothing to read where the
driver records no such scope."""

from perfbench.catalog import peaks
from perfbench.roofline import lfm2_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"

SCOPE = "mixer.short_conv"


def read(run):
    piece, scopes = run.get("slice") or {}, run.get("scope_s") or {}
    spent = scopes.get(("decode", SCOPE))
    steps = piece.get("decode_lengths")
    if not spent or not steps or "conv_shape" not in piece:
        return None
    live = sum(len(step) for step in steps)
    moved = lfm2_bytes.short_conv_weight_bytes(len(steps), **piece["conv_shape"]) + lfm2_bytes.short_conv_token_bytes(
        live, live, **piece["conv_shape"]
    )
    return 100.0 * moved / spent / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
