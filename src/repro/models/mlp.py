"""Feed-forward layers (the FC layers that dominate the paper's Fig. 2).

Variants: GELU MLP (2 mats), SwiGLU / GeGLU (3 mats), RWKV channel-mix
(relu^2 + receptance gate). All matmuls go through the row-wise primitive.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import partitioning as part
from repro.core.types import GATED_ACTS as GATED, ModelConfig
from repro.kernels import ops
from repro.models.initializers import normal


def init(key, cfg: ModelConfig, stack: Optional[int], dtype,
         d_ff: Optional[int] = None):
    """Gated variants store the gate|up pair PRE-FUSED as one ``wgi``
    (d, 2*d_ff) leaf (DESIGN.md §5) — gate columns first, up columns
    second — so the gated kernel streams both halves straight from the
    stored panel. Non-gated MLPs keep the single ``wi`` leaf."""
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    lead = () if stack is None else (stack,)
    llead = () if stack is None else ("layers",)
    ks = jax.random.split(key, 3)

    def w(k, din, dout):
        return normal(k, lead + (din, dout), dtype, 1 / math.sqrt(din))

    if cfg.act in GATED:
        params = {"wgi": w(ks[0], d, 2 * f), "wo": w(ks[1], f, d)}
        specs = {"wgi": llead + ("embed", "ffn"),
                 "wo": llead + ("ffn", "embed")}
    else:
        params = {"wi": w(ks[0], d, f), "wo": w(ks[1], f, d)}
        specs = {"wi": llead + ("embed", "ffn"),
                 "wo": llead + ("ffn", "embed")}
    return params, specs


def apply(params, x, *, cfg: ModelConfig, norm=None, residual=None):
    """``norm``/``residual`` select the fused pipeline (DESIGN.md §3):
    the pre-norm runs as the first kernel's prologue, gated variants
    stream the stored wg|wi panel through ONE kernel whose epilogue
    computes ``act(g) * h``, and the residual add rides the output
    projection's epilogue. With both None this is the seed's per-op
    composition (the stored panel sliced back into wg and wi)."""
    act = {"silu": "silu", "geglu": "gelu", "gelu": "gelu",
           "relu": "relu"}[cfg.act]
    if cfg.act in GATED:
        if norm is not None:
            h = ops.gate_up_proj(x, params["wgi"], activation=act,
                                 norm=norm)
        else:
            from repro.core import quant
            wgi = quant.resolve_weight(params["wgi"], x.dtype)
            f = wgi.shape[-1] // 2
            g = ops.matmul(x, wgi[..., :f], activation=act)
            h = ops.matmul(x, wgi[..., f:]) * g
    else:
        h = ops.matmul(x, params["wi"], activation=act, norm=norm)
    if part.tp_axis() is None:
        return ops.matmul(h, params["wo"], residual=residual)
    # TP serving: wo is row-sharded over the hidden dim — psum the
    # partial product over the mesh axis before the residual rides on
    y = part.tp_reduce(ops.matmul(h, params["wo"]))
    return y if residual is None else y + residual


# ---------------------------- RWKV channel-mix -------------------------


def init_cmix(key, cfg: ModelConfig, stack: Optional[int], dtype):
    d, f = cfg.d_model, cfg.d_ff
    lead = () if stack is None else (stack,)
    llead = () if stack is None else ("layers",)
    ks = jax.random.split(key, 4)

    def w(k, din, dout):
        return normal(k, lead + (din, dout), dtype, 1 / math.sqrt(din))

    params = {"wk": w(ks[0], d, f), "wv": w(ks[1], f, d),
              "wr": w(ks[2], d, d),
              "mu_k": jnp.full(lead + (d,), 0.5, dtype),
              "mu_r": jnp.full(lead + (d,), 0.5, dtype)}
    specs = {"wk": llead + ("embed", "ffn"), "wv": llead + ("ffn", "embed"),
             "wr": llead + ("embed", "embed"),
             "mu_k": llead + (None,), "mu_r": llead + (None,)}
    return params, specs


def apply_cmix(params, x, x_prev):
    """RWKV6 channel-mix. x: (B,S,d); x_prev: token-shifted x."""
    xk = x + (x_prev - x) * params["mu_k"].astype(x.dtype)
    xr = x + (x_prev - x) * params["mu_r"].astype(x.dtype)
    k = ops.matmul(xk, params["wk"], activation="relu2")
    r = jax.nn.sigmoid(ops.matmul(xr, params["wr"]).astype(jnp.float32))
    v = ops.matmul(k, params["wv"])
    return (r * v.astype(jnp.float32)).astype(x.dtype)
