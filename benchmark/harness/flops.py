"""Operations and bytes the ALGORITHM needs, from shapes alone.

Model FLOPs count what forward and backward require and nothing else:
attention is charged for the (query, key) pairs a causal, windowed model
attends, the embedding lookup is not a matmul, and recomputed operations
(remat) never count.  ``bench.py::model_flops_per_step`` charged the full
S x S square; this does not.
"""

from __future__ import annotations


def attended_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one causal sequence: query ``i`` attends keys
    ``max(0, i - window + 1) .. i`` (``window <= 0``: all of ``0 .. i``)."""
    if window <= 0 or window >= seq:
        return seq * (seq + 1) // 2
    # the first `window` queries see 1..window keys, the rest see `window`
    return window * (window + 1) // 2 + (seq - window) * window


def heads(cfg: dict) -> tuple:
    """(query heads, KV heads, head size); HF configs may leave out the
    last two: KV heads default to the query heads, the head size to
    hidden / heads."""
    h = cfg["num_attention_heads"]
    return (h, cfg.get("num_key_value_heads", h),
            cfg.get("head_dim") or cfg["hidden_size"] // h)


def matmul_params(cfg: dict) -> dict:
    """Parameters that sit in a matmul, per layer and in the head."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = heads(cfg)
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return {"layer": layer, "head": d * cfg["vocab_size"]}


def model_flops_per_token(cfg: dict, seq: int) -> dict:
    """Required forward+backward FLOPs per trained token (a multiply-add is
    two), split into matmul and attention parts."""
    mp = matmul_params(cfg)
    layers = cfg["num_hidden_layers"]
    matmul = 6.0 * (layers * mp["layer"] + mp["head"])
    # scores and values: 2 matmuls of head_dim per pair and head, 2 FLOPs
    # per multiply-add, x3 for forward + backward
    pairs = attended_pairs(seq, cfg.get("sliding_window") or 0)
    h, _, hd = heads(cfg)
    attn = 3.0 * 2 * 2 * h * hd * pairs * layers / seq
    return {"matmul": matmul, "attention": attn, "total": matmul + attn}


def flash_least_seconds(cfg: dict, batch: int, seq: int, peaks: dict,
                        shards: int = 1) -> dict:
    """Least time one device could take for the flash forward and backward
    of ONE layer at this batch: the larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s, and which of the two binds.

    FLOPs: forward 2 matmuls per attended pair, backward 5 (dq kernel: s,
    dp, dq; dkv kernel: s, dp, dv, dk — the algorithm needs s and dp once,
    so 2 + 5, not the 2 + 7 the two-kernel split executes).  Bytes: q, k,
    v, o read or written once forward; q, k, v, o, do read and dq, dk, dv
    written backward, bf16; lse and delta are small and left out.
    ``shards``: devices the batch x heads are divided over."""
    h, kv, hd = heads(cfg)
    pairs = attended_pairs(seq, cfg.get("sliding_window") or 0)
    flops = (2 + 5) * 2.0 * h * hd * pairs * batch / shards
    q_bytes = 2.0 * batch * seq * h * hd
    kv_bytes = 2.0 * batch * seq * kv * hd
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes
    bwd_bytes = 4 * q_bytes + 4 * kv_bytes
    nbytes = (fwd_bytes + bwd_bytes) / shards
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flops, "bytes": nbytes}
