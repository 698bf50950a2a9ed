"""Mamba2 (SSD) mixer — zamba2's backbone.

The selective-scan recurrence has no dot-product-primitive form (noted
in DESIGN.md §5): the paper's row-wise technique applies to the in/out
projections only. The scan itself uses the SSD *chunked* formulation —
intra-chunk attention-like term + inter-chunk state passing — which maps
onto TPU as dense (L x L)-per-head matmuls, scanned over chunks.

Recurrence (per head h, head dim P, state dim N, scalar decay):
    S_t = exp(dt_t * a_h) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D_h x_t
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.types import ModelConfig
from repro.kernels import ops
from repro.models.initializers import normal


class MambaState(NamedTuple):
    conv: jnp.ndarray   # (B, d_conv-1, conv_dim) rolling conv inputs
    ssm: jnp.ndarray    # (B, H, P, N) state


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    conv_dim = d_in + 2 * s.d_state
    return s, d_in, n_heads, conv_dim


def init(key, cfg: ModelConfig, stack: Optional[int], dtype):
    s, d_in, n_heads, conv_dim = _dims(cfg)
    d = cfg.d_model
    lead = () if stack is None else (stack,)
    llead = () if stack is None else ("layers",)
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_in + 2 * s.d_state + n_heads

    def w(k, din, dout):
        return normal(k, lead + (din, dout), dtype, 1 / math.sqrt(din))

    params = {
        "in_proj": w(ks[0], d, proj_out),
        "out_proj": w(ks[1], d_in, d),
        "conv_w": normal(ks[2], lead + (s.d_conv, conv_dim), dtype, 0.1),
        "conv_b": jnp.zeros(lead + (conv_dim,), dtype),
        "A_log": jnp.zeros(lead + (n_heads,), jnp.float32),
        "dt_bias": jnp.zeros(lead + (n_heads,), jnp.float32),
        "D": jnp.ones(lead + (n_heads,), jnp.float32),
        "norm_g": jnp.ones(lead + (d_in,), dtype),
    }
    specs = {
        "in_proj": llead + ("embed", "ffn"),
        "out_proj": llead + ("ffn", "embed"),
        "conv_w": llead + (None, "ffn"), "conv_b": llead + ("ffn",),
        "A_log": llead + (None,), "dt_bias": llead + (None,),
        "D": llead + (None,), "norm_g": llead + ("ffn",),
    }
    return params, specs


def _split(cfg, zxbcdt):
    s, d_in, n_heads, _ = _dims(cfg)
    z, x, bc, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + 2 * s.d_state], axis=-1)
    return z, x, bc, dt


def _conv(x, w, b, state=None):
    """Causal depthwise conv. x: (B,S,C); w: (K,C). state: (B,K-1,C)."""
    k = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    new_state = xp[:, -(k - 1):] if k > 1 else None
    return out + b, new_state


def ssd_chunked(xh, dt, a, B, C, *, chunk: int = 128, s0=None):
    """Chunked SSD scan.

    xh: (Bb, S, H, P); dt: (Bb, S, H); a: (H,) negative;
    B, C: (Bb, S, N). Returns (y, final_state (Bb,H,P,N)).
    """
    bb, sl, h, p = xh.shape
    n = B.shape[-1]
    chunk = min(chunk, sl)
    pad = (-sl) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    nc = (sl + pad) // chunk
    xc = xh.reshape(bb, nc, chunk, h, p).transpose(1, 0, 2, 3, 4)
    dtc = dt.reshape(bb, nc, chunk, h).transpose(1, 0, 2, 3)
    Bc = B.reshape(bb, nc, chunk, n).transpose(1, 0, 2, 3)
    Cc = C.reshape(bb, nc, chunk, n).transpose(1, 0, 2, 3)

    if s0 is None:
        s0 = jnp.zeros((bb, h, p, n), jnp.float32)

    def step(S, inp):
        xk, dk, Bk, Ck = inp                      # (Bb,L,H,P),(Bb,L,H),...
        lam = dk * a                              # (Bb,L,H) log decays <=0
        cs = jnp.cumsum(lam, axis=1)              # inclusive cumsum
        # intra-chunk: M[b,h,i,j] = exp(cs_i - cs_j) dt_j (C_i . B_j), j<=i
        logd = cs[:, :, None, :] - cs[:, None, :, :]      # (Bb,i,j,H)
        mask = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
        logd = jnp.where(mask[None, :, :, None], logd, -jnp.inf)
        cb = jnp.einsum("bin,bjn->bij", Ck, Bk)           # (Bb,i,j)
        M = jnp.exp(logd) * cb[..., None] * dk[:, None, :, :]
        y = jnp.einsum("bijh,bjhp->bihp", M, xk)
        # inter-chunk: y_i += exp(cs_i) * C_i . S^T
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bhpn,bin->bihp", S, Ck)
        # state update: S' = exp(cs_L) S + sum_j exp(cs_L - cs_j) dt_j x_j B_j
        tail = jnp.exp(cs[:, -1:, :] - cs)                # (Bb,L,H)
        S_new = (jnp.exp(cs[:, -1])[:, :, None, None] * S
                 + jnp.einsum("bjh,bjhp,bjn->bhpn", tail * dk, xk, Bk))
        return S_new, y

    # backward recomputes intra-chunk tensors from boundary states (the
    # scan-AD default stacks every chunk's decay/score products in HBM)
    step = jax.checkpoint(step, prevent_cse=False)
    S_fin, ys = jax.lax.scan(step, s0, (xc, dtc, Bc, Cc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bb, nc * chunk, h, p)
    return y[:, :sl], S_fin


def ssd_ref(xh, dt, a, B, C, s0=None):
    """Naive per-step scan oracle."""
    bb, sl, h, p = xh.shape
    n = B.shape[-1]
    S = jnp.zeros((bb, h, p, n), jnp.float32) if s0 is None else s0

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        decay = jnp.exp(dtt * a)                  # (Bb,H)
        S = (S * decay[:, :, None, None]
             + jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, Bt))
        y = jnp.einsum("bhpn,bn->bhp", S, Ct)
        return S, y

    xs = (xh.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
          B.transpose(1, 0, 2), C.transpose(1, 0, 2))
    S, ys = jax.lax.scan(step, S, xs)
    return ys.transpose(1, 0, 2, 3), S


def apply(params, x, *, cfg: ModelConfig, state: Optional[MambaState] = None,
          chunk: Optional[int] = None):
    """Full-sequence forward. x: (B,S,d). Returns (out, final_state)."""
    s, d_in, n_heads, conv_dim = _dims(cfg)
    bsz, sl, _ = x.shape
    zxbcdt = ops.matmul(x, params["in_proj"])
    z, xi, bc, dt = _split(cfg, zxbcdt)
    conv_in = jnp.concatenate([xi, bc], axis=-1)
    conv_state = state.conv if state is not None else None
    conv_out, new_conv = _conv(conv_in, params["conv_w"].astype(jnp.float32),
                               params["conv_b"].astype(jnp.float32),
                               conv_state)
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32))
    xi = conv_out[..., :d_in]
    B = conv_out[..., d_in:d_in + s.d_state]
    C = conv_out[..., d_in + s.d_state:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"])                 # (B,S,H)
    a = -jnp.exp(params["A_log"])                             # (H,)
    xh = xi.reshape(bsz, sl, n_heads, s.head_dim)
    y, s_fin = ssd_chunked(xh, dt, a, B, C, chunk=chunk or s.chunk,
                           s0=state.ssm if state is not None else None)
    y = y + params["D"][None, None, :, None] * xh
    y = y.reshape(bsz, sl, d_in)
    # gated RMSNorm then out-projection
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = ops.layernorm(y.astype(x.dtype), params["norm_g"], kind="rms")
    out = ops.matmul(y, params["out_proj"])
    new_state = MambaState(conv=new_conv.astype(x.dtype), ssm=s_fin)
    return out, new_state


def init_state(cfg: ModelConfig, batch: int, dtype) -> MambaState:
    s, d_in, n_heads, conv_dim = _dims(cfg)
    return MambaState(
        conv=jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        ssm=jnp.zeros((batch, n_heads, s.head_dim, s.d_state), jnp.float32))


def state_specs():
    return MambaState(conv=("batch", None, "ffn"),
                      ssm=("batch", None, None, None))
