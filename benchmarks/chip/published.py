"""The check that a configuration file and the program's config agree.

A family's ``check(cfg, arch)`` calls :func:`check` with the published
keys it shares with the program (:func:`attention_keys` for a block of
attention and one dense MLP) and its own conditions on the rest.
"""
from __future__ import annotations


def attention_keys(cfg) -> dict:
    """HF-style key -> the program's value, for a block of grouped-query
    attention and one dense MLP of width ``intermediate_size``."""
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "vocab_size": cfg.vocab,
            "num_hidden_layers": cfg.n_layers,
            "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings}


def check(cfg, arch: dict, same: dict, ok: bool):
    """Raise where a published key differs from the program's value in
    ``same``, where ``ok`` (the family's own conditions) is false, or
    where the program does not serve bfloat16 weights, which is what
    ``weights.make`` draws: the program must run what the file states."""
    bad = {k: (arch[k], v) for k, v in same.items() if arch[k] != v}
    if bad or not ok or cfg.dtype != "bfloat16":
        raise ValueError(f"{arch['name']}: file and program differ: {bad}")
