"""A second block family, for the self-tests: a GPT-BigCode-style block
as the program serves the registry's granite-20b (pre-norm LayerNorm
with a bias, rotary positions with the half-split rotation, one kv head
under every query head, a GELU MLP with the tanh approximation, and the
lm_head tied to the embedding).

``tiny.make_root`` writes it as ``families/granite.py`` of a throwaway
checkout, beside a configuration that names it: the harness must take
a family that the llama family cannot describe from new files alone.

It mirrors what the program does with its ``granite-20b`` entry, not
the published architecture: granite-20b-code-base is GPT-BigCode, with
learned absolute positions and ``layer_norm_epsilon`` 1e-5, where the
program applies rotary positions and an eps of 1e-6. It serves to test
the seam; a family for the published model follows its model card, and
where the program departs from it the comparison shows the gap.

Weights, in the order they are drawn:

  embed        (Vp, d)                 token embedding, also the lm_head
  ln1_g, ln1_b (L, d)                  pre-attention LayerNorm
  wqkv         (L, d, (Hq + 2 Hkv) hd) q | k | v output columns
  wo           (L, Hq hd, d)
  ln2_g, ln2_b (L, d)                  pre-MLP LayerNorm
  w_in         (L, d, f)
  w_out        (L, f, d)
  final_g, final_b (d,)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import published
from reference import _attend, _by_rows, _mm, _rope, padded_tokens
from weights import padded_vocab
# the generic counts fit this layout too
from work import decode_step, prefill_panel  # noqa: F401

LAYER_KEYS = ("ln1_g", "ln1_b", "wqkv", "wo", "ln2_g", "ln2_b", "w_in",
              "w_out")


def shapes(arch: dict) -> dict:
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hq, hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd, n = arch["head_dim"], arch["num_hidden_layers"]
    vp = padded_vocab(arch["vocab_size"])
    return {
        # the lm_head's scale: a unit-variance embedding would put each
        # token's own row far on top, and no control could flip one
        "embed": ((vp, d), 1 / math.sqrt(d), 0.0),
        # gains away from 1 and biases away from 0, so that a dropped
        # one shows
        "ln1_g": ((n, d), 0.2, 1.0),
        "ln1_b": ((n, d), 0.2, 0.0),
        "wqkv": ((n, d, (hq + 2 * hkv) * hd), 1 / math.sqrt(d), 0.0),
        "wo": ((n, hq * hd, d), 1 / math.sqrt(hq * hd), 0.0),
        "ln2_g": ((n, d), 0.2, 1.0),
        "ln2_b": ((n, d), 0.2, 0.0),
        "w_in": ((n, d, f), 1 / math.sqrt(d), 0.0),
        "w_out": ((n, f, d), 1 / math.sqrt(f), 0.0),
        "final_g": ((d,), 0.2, 1.0),
        "final_b": ((d,), 0.2, 0.0),
    }


def program_params(w: dict, arch: dict) -> dict:
    """``repro.models.lm``'s tree for a dense, tied, single-stage model
    with LayerNorm and a GELU MLP."""
    block = {"norm1": {"g": w["ln1_g"], "b": w["ln1_b"]},
             "attn": {"wqkv": w["wqkv"], "wo": w["wo"]},
             "norm2": {"g": w["ln2_g"], "b": w["ln2_b"]},
             "ffn": {"wi": w["w_in"], "wo": w["w_out"]}}
    return {"embed": w["embed"],
            "stages": [{"stacked": {"0": block}, "shared": {}}],
            "final_norm": {"g": w["final_g"], "b": w["final_b"]}}


def check(cfg, arch: dict):
    published.check(cfg, arch, published.attention_keys(cfg),
                    arch["hidden_act"] == "gelu_pytorch_tanh"
                    and cfg.act == "gelu" and cfg.norm == "layer"
                    and cfg.family == "dense")


def _ln(x, g, b, eps):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("arch", "mode"))
def _layer(x, lw, *, arch, mode):
    hq, hkv, hd, eps, theta = arch
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _ln(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _by_rows(lambda r: _mm(r, lw["wqkv"], mode), h)
    q = _rope(qkv[:, :hq * hd].reshape(s, hq, hd), pos, theta)
    k = _rope(qkv[:, hq * hd:(hq + hkv) * hd].reshape(s, hkv, hd), pos,
              theta)
    v = qkv[:, (hq + hkv) * hd:].reshape(s, hkv, hd)
    x = x + _by_rows(lambda r: _mm(r, lw["wo"], mode), _attend(q, k, v))
    h = _ln(x, lw["ln2_g"], lw["ln2_b"], eps)
    return x + _by_rows(lambda r: _mm(jax.nn.gelu(
        _mm(r, lw["w_in"], mode), approximate=True), lw["w_out"], mode), h)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "mode"))
def _head(x, pos, g, b, embed, *, vocab, eps, mode):
    return _mm(_ln(x[pos], g, b, eps), embed[:vocab].T, mode)


def logits(w: dict, arch: dict, tokens: np.ndarray, positions: np.ndarray,
           mode=None) -> jax.Array:
    t = padded_tokens(tokens)
    eps = float(arch["layer_norm_epsilon"])
    key = (arch["num_attention_heads"], arch["num_key_value_heads"],
           arch["head_dim"], eps, float(arch["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(t)].astype(jnp.float32)
        for layer in range(arch["num_hidden_layers"]):
            x = _layer(x, {k: w[k][layer] for k in LAYER_KEYS}, arch=key,
                       mode=mode)
        return _head(x, jnp.asarray(positions, jnp.int32), w["final_g"],
                     w["final_b"], w["embed"], vocab=arch["vocab_size"],
                     eps=eps, mode=mode)


def dims(arch: dict) -> dict:
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hq, hkv, hd = (arch["num_attention_heads"],
                   arch["num_key_value_heads"], arch["head_dim"])
    layer = d * (hq + 2 * hkv) * hd + hq * hd * d + 2 * d * f
    return {"d": d, "L": arch["num_hidden_layers"], "hq": hq, "hkv": hkv,
            "hd": hd, "layer_params": layer,
            "head_params": d * arch["vocab_size"]}
