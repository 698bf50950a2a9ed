"""The work a window needs, from the model's shapes and the engine's
counts: operations and HBM bytes, whatever implements them.

What is counted is what the served tokens *need*: prompt tokens
computed (not prefix-cache hits, not bucket padding), decode slot-steps
at their live context lengths, each weight read once per program call,
and live KV pages (not the block table's full width). The utilization
built on these numbers (and the kernel rooflines, once the trace can
tell the kernels apart) therefore reads the same work whatever a later
change does to execute it.
"""
from __future__ import annotations

BF16 = 2


def dims(arch: dict) -> dict:
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hq, hkv, hd = (arch["num_attention_heads"],
                   arch["num_key_value_heads"], arch["head_dim"])
    layer = d * (hq + 2 * hkv) * hd + hq * hd * d + d * 2 * f + f * d
    return {"d": d, "L": arch["num_hidden_layers"], "hq": hq, "hkv": hkv,
            "hd": hd, "layer_params": layer, "vocab": arch["vocab_size"],
            "head_params": d * arch["vocab_size"]}


def _kv_bytes_per_token(m: dict) -> int:
    return m["L"] * 2 * m["hkv"] * m["hd"] * BF16


def decode_step(m: dict, lengths, page_size: int) -> dict:
    """One lockstep decode step over the active slots, each attending
    ``length`` keys (its context including the new token)."""
    n = len(lengths)
    mm_flops = 2 * n * (m["L"] * m["layer_params"] + m["head_params"])
    mm_bytes = BF16 * (m["L"] * m["layer_params"] + m["head_params"]) \
        + BF16 * n * m["L"] * (4 * m["d"])
    ctx = sum(lengths)
    pages = sum(-(-ln // page_size) for ln in lengths)
    attn_flops = 4 * m["L"] * m["hq"] * m["hd"] * ctx
    attn_bytes = pages * page_size * _kv_bytes_per_token(m)
    return {"mm_flops": mm_flops, "mm_bytes": mm_bytes,
            "attn_flops": attn_flops, "attn_bytes": attn_bytes}


def prefill_panel(m: dict, tokens: int, prefix: int) -> dict:
    """``tokens`` prompt rows computed after ``prefix`` cached ones;
    logits for the last row only."""
    mm_flops = 2 * tokens * m["L"] * m["layer_params"] \
        + 2 * m["head_params"]
    mm_bytes = BF16 * (m["L"] * m["layer_params"] + m["head_params"]) \
        + BF16 * tokens * m["L"] * 4 * m["d"]
    pairs = tokens * prefix + tokens * (tokens + 1) // 2
    attn_flops = 4 * m["L"] * m["hq"] * m["hd"] * pairs
    attn_bytes = (prefix + tokens) * _kv_bytes_per_token(m)
    return {"mm_flops": mm_flops, "mm_bytes": mm_bytes,
            "attn_flops": attn_flops, "attn_bytes": attn_bytes}


def flops(w: dict) -> float:
    return float(w["mm_flops"] + w["attn_flops"])
