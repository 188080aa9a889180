"""Roofline share of a decode step's chosen-set attention: the K/V bytes of
``min(context, 2048)`` positions a row a layer for every decode program of the
traced slice (``roofline/sparse_bytes.py``), at the chip's peak HBM bandwidth,
over the device time the decode programs spend under the scope ``attn.sparse``
(the gather out of the pools and the attention over what it brought). Nothing
to read where the driver records no such scope."""

from perfbench.catalog import peaks
from perfbench.roofline import sparse_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    scopes, piece = run.get("scope_s"), run.get("slice") or {}
    shape, steps = piece.get("sparse_shape"), piece.get("decode_lengths")
    spent = (scopes or {}).get(("decode", "attn.sparse"))
    if not shape or not steps or not spent:
        return None
    kv = {k: shape[k] for k in ("n_layers", "top_k", "n_kv_heads", "head_dim", "dtype_bytes")}
    moved = sum(sparse_bytes.chosen_decode_kv_bytes(step, **kv) for step in steps)
    return 100.0 * moved / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / spent
