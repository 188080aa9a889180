"""Roofline share of the index scores: for every decode and prefill program of
the traced slice, the index-key bytes of the live positions its rows see (read
once a layer) at the chip's HBM bandwidth and ``2 x 16 x 64`` operations a
(query, position) pair at its bfloat16 peak, whichever bound is the larger
(``roofline/sparse_bytes.py``: from the work, 128 B a key, not the 256 B row it
is stored in), over the device time under the scope ``attn.index_score``.
Nothing to read where the driver records no such scope."""

from perfbench.catalog import peaks
from perfbench.roofline import sparse_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    scopes, piece = run.get("scope_s"), run.get("slice") or {}
    shape = piece.get("sparse_shape")
    spent = sum(s for (_kind, scope), s in (scopes or {}).items() if scope == "attn.index_score")
    if not shape or not spent:
        return None
    peak = peaks(run["device"]["kind"])
    ops = {k: shape[k] for k in ("n_layers", "index_heads", "index_dim")}
    kv = {k: shape[k] for k in ("n_layers", "index_dim")}
    least = 0.0
    for lengths in piece.get("decode_lengths") or []:  # one query a live row
        live = sum(int(n) for n in lengths if n > 0)
        least += max(
            sparse_bytes.index_score_flops(live, **ops) / peak["flops_bf16"],
            sparse_bytes.index_score_bytes(live, **kv) / peak["hbm_bytes_per_s"],
        )
    for rows in piece.get("prefill_rows") or []:  # (write offset, valid queries) a live row
        pairs = sum(sparse_bytes.prefill_pairs(w, v) for w, v in rows)
        live = sum(w + v for w, v in rows)
        least += max(
            sparse_bytes.index_score_flops(pairs, **ops) / peak["flops_bf16"],
            sparse_bytes.index_score_bytes(live, **kv) / peak["hbm_bytes_per_s"],
        )
    return 100.0 * least / spent
