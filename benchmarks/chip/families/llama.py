"""The llama block family: pre-norm RMSNorm decoder blocks, rotary
positions with the half-split rotation, grouped-query attention, a
SwiGLU MLP and an untied lm_head (deepseek-llm, internlm2).

Everything the harness needs to know about this layout is here: the
benchmark's own weights (:func:`shapes`), the program's parameter tree
over them (:func:`program_params`), the check that the configuration
file and the program agree (:func:`check`), the float32 reference
(:func:`logits`) and the work a window needs (:func:`dims`,
:func:`decode_step`, :func:`prefill_panel`).

Weights, in the order they are drawn:

  embed        (Vp, d)                 token embedding
  attn_norm    (L, d)                  pre-attention RMS gain
  wqkv         (L, d, (Hq + 2 Hkv) hd) q | k | v output columns
  wo           (L, Hq hd, d)
  mlp_norm     (L, d)                  pre-MLP RMS gain
  w_gate_up    (L, d, 2 f)             gate | up output columns
  w_down       (L, f, d)
  final_norm   (d,)
  lm_head      (d, Vp)

``Vp`` is the vocabulary rounded up to the program's padding; the
reference reads only the first ``V`` rows and columns.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import published
from reference import _attend, _by_rows, _mm, _rms, _rope, padded_tokens
from weights import padded_vocab
# the generic counts fit this layout: each weight read once per call, K
# and V of hkv heads cached per layer
from work import decode_step, prefill_panel  # noqa: F401

LAYER_KEYS = ("attn_norm", "wqkv", "wo", "mlp_norm", "w_gate_up", "w_down")


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------


def shapes(arch: dict) -> dict:
    """name -> (shape, std, mean) for an HF-style config dict."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hq, hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd, n = arch["head_dim"], arch["num_hidden_layers"]
    vp = padded_vocab(arch["vocab_size"])
    qkv = (hq + 2 * hkv) * hd
    return {
        "embed": ((vp, d), 1.0, 0.0),
        # gains away from 1, so that a dropped gain shows
        "attn_norm": ((n, d), 0.2, 1.0),
        "wqkv": ((n, d, qkv), 1 / math.sqrt(d), 0.0),
        "wo": ((n, hq * hd, d), 1 / math.sqrt(hq * hd), 0.0),
        "mlp_norm": ((n, d), 0.2, 1.0),
        "w_gate_up": ((n, d, 2 * f), 1 / math.sqrt(d), 0.0),
        "w_down": ((n, f, d), 1 / math.sqrt(f), 0.0),
        "final_norm": ((d,), 0.2, 1.0),
        "lm_head": ((d, vp), 1 / math.sqrt(d), 0.0),
    }


def program_params(w: dict, arch: dict) -> dict:
    """The program's parameter tree (``repro.models.lm`` layout for a
    dense, untied, single-stage model) over the same arrays."""
    block = {"norm1": {"g": w["attn_norm"]},
             "attn": {"wqkv": w["wqkv"], "wo": w["wo"]},
             "norm2": {"g": w["mlp_norm"]},
             "ffn": {"wgi": w["w_gate_up"], "wo": w["w_down"]}}
    return {"embed": w["embed"],
            "stages": [{"stacked": {"0": block}, "shared": {}}],
            "final_norm": {"g": w["final_norm"]},
            "lm_head": w["lm_head"]}


def check(cfg, arch: dict):
    """Raise where the file's published keys and the program's config
    differ: the program must run what the file states."""
    published.check(cfg, arch, published.attention_keys(cfg),
                    cfg.act == "silu" and cfg.norm == "rms"
                    and cfg.family == "dense")


# ----------------------------------------------------------------------
# the float32 reference
# ----------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("arch", "mode"))
def _layer(x, lw, *, arch, mode):
    hq, hkv, hd, eps, theta = arch
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, lw["attn_norm"], eps)
    qkv = _by_rows(lambda r: _mm(r, lw["wqkv"], mode), h)
    q = _rope(qkv[:, :hq * hd].reshape(s, hq, hd), pos, theta)
    k = _rope(qkv[:, hq * hd:(hq + hkv) * hd].reshape(s, hkv, hd), pos,
              theta)
    v = qkv[:, (hq + hkv) * hd:].reshape(s, hkv, hd)
    o = _attend(q, k, v)
    x = x + _by_rows(lambda r: _mm(r, lw["wo"], mode), o)
    h = _rms(x, lw["mlp_norm"], eps)

    def mlp(r):
        gu = _mm(r, lw["w_gate_up"], mode)
        f = gu.shape[-1] // 2
        return _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], lw["w_down"], mode)

    return x + _by_rows(mlp, h)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "mode"))
def _head(x, pos, final_norm, lm_head, *, vocab, eps, mode):
    xs = _rms(x[pos], final_norm, eps)
    return _mm(xs, lm_head[:, :vocab], mode)


def arch_key(arch: dict) -> tuple:
    return (arch["num_attention_heads"], arch["num_key_value_heads"],
            arch["head_dim"], float(arch["rms_norm_eps"]),
            float(arch["rope_theta"]))


def logits(w: dict, arch: dict, tokens: np.ndarray, positions: np.ndarray,
           mode=None) -> jax.Array:
    """Next-token logits (len(positions), vocab) of the sequence
    ``tokens`` at ``positions``, in float32 (or in the control's
    precision), one layer at a time."""
    t = padded_tokens(tokens)
    key = arch_key(arch)
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(t)].astype(jnp.float32)
        for layer in range(arch["num_hidden_layers"]):
            lw = {k: w[k][layer] for k in LAYER_KEYS}
            x = _layer(x, lw, arch=key, mode=mode)
        return _head(x, jnp.asarray(positions, jnp.int32), w["final_norm"],
                     w["lm_head"], vocab=arch["vocab_size"],
                     eps=float(arch["rms_norm_eps"]), mode=mode)


# ----------------------------------------------------------------------
# the work a window needs (``work.py`` counts it from these sizes)
# ----------------------------------------------------------------------


def dims(arch: dict) -> dict:
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hq, hkv, hd = (arch["num_attention_heads"],
                   arch["num_key_value_heads"], arch["head_dim"])
    layer = d * (hq + 2 * hkv) * hd + hq * hd * d + d * 2 * f + f * d
    return {"d": d, "L": arch["num_hidden_layers"], "hq": hq, "hkv": hkv,
            "hd": hd, "layer_params": layer, "vocab": arch["vocab_size"],
            "head_params": d * arch["vocab_size"]}
