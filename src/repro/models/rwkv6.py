"""RWKV6 "Finch" time-mix — attention-free, data-dependent per-channel decay.

The WKV recurrence has no dot-product-primitive form, so the paper's
row-wise technique applies only to the R/K/V/G/O projections (>=80% of
FLOPs; see DESIGN.md §5). The recurrence itself runs chunkwise:

    y_t = sum_c r_t[c] * (S_{t-1}[c,:] + u[c] k_t[c] v_t)
    S_t[c,:] = w_t[c] * S_{t-1}[c,:] + k_t[c] * v_t
    w_t = exp(-exp(w0 + lora(x_t)))          (data-dependent decay)

Chunked numerics: per-step log decays are clamped to [-CLAMP, -1e-6].
With chunk=16 and CLAMP=3.5 the largest intermediate factor is
exp(16*3.5) ~ 2e24 (fp32-safe) while anything the clamp affects has
decayed below fp32 epsilon — semantically lossless.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.types import ModelConfig
from repro.kernels import ops
from repro.models.initializers import normal

CHUNK = 16
CLAMP = 3.5
# Backward recomputes intra-chunk tensors from the chunk-boundary WKV
# states instead of materializing every chunk's rd/kd/A products (the
# scan-AD default stacks them: ~6 GB f32 per layer at 4k tokens).
# See EXPERIMENTS.md §Perf (rwkv6 train_4k iteration 1).
BOUNDARY_RECOMPUTE = True


class RWKVState(NamedTuple):
    x_prev_t: jnp.ndarray   # (B, d) last input of time-mix
    x_prev_c: jnp.ndarray   # (B, d) last input of channel-mix
    wkv: jnp.ndarray        # (B, H, hd, hd) recurrence state


def init(key, cfg: ModelConfig, stack: Optional[int], dtype):
    r = cfg.rwkv
    d = cfg.d_model
    h = d // r.head_dim
    lead = () if stack is None else (stack,)
    llead = () if stack is None else ("layers",)
    ks = jax.random.split(key, 8)

    def w(k, din, dout, scale=1.0):
        return normal(k, lead + (din, dout), dtype, scale / math.sqrt(din))

    params = {
        "wr": w(ks[0], d, d), "wk": w(ks[1], d, d), "wv": w(ks[2], d, d),
        "wg": w(ks[3], d, d), "wo": w(ks[4], d, d),
        "w0": jnp.full(lead + (d,), -2.0, jnp.float32),
        "w_lora_a": w(ks[5], d, r.decay_lora, 0.1),
        "w_lora_b": (jnp.zeros(lead + (r.decay_lora, d), jnp.float32)
                     ).astype(dtype),
        "u": normal(ks[6], lead + (h, r.head_dim), jnp.float32, 0.1),
        "mu": (0.5 * jnp.ones(lead + (5, d), jnp.float32)).astype(dtype),
        "ln_g": jnp.ones(lead + (d,), dtype),
        "ln_b": jnp.zeros(lead + (d,), dtype),
    }
    specs = {
        "wr": llead + ("embed", "qkv"), "wk": llead + ("embed", "qkv"),
        "wv": llead + ("embed", "qkv"), "wg": llead + ("embed", "qkv"),
        "wo": llead + ("qkv", "embed"),
        "w0": llead + (None,), "w_lora_a": llead + ("embed", None),
        "w_lora_b": llead + (None, "embed"), "u": llead + (None, None),
        "mu": llead + (None, None), "ln_g": llead + (None,),
        "ln_b": llead + (None,),
    }
    return params, specs


def wkv_chunked(r, k, v, lw, u, *, chunk: int = CHUNK, s0=None):
    """Chunked WKV6. r,k,v: (B,S,H,P); lw: (B,S,H,P) log decay (<0);
    u: (H,P). Returns (y (B,S,H,P), final state (B,H,P,P))."""
    b, sl, h, p = r.shape
    chunk = min(chunk, sl)
    pad = (-sl) % chunk
    if pad:
        z = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, z), jnp.pad(k, z), jnp.pad(v, z)
        lw = jnp.pad(lw, z)  # pad with 0 log-decay; ok, tokens unused
    nc = (sl + pad) // chunk

    def resh(x):
        return x.reshape(b, nc, chunk, h, p).transpose(1, 0, 2, 3, 4)

    rc, kc, vc, lwc = resh(r), resh(k), resh(v), resh(lw)
    if s0 is None:
        s0 = jnp.zeros((b, h, p, p), jnp.float32)

    idx = jnp.arange(chunk)
    strict = idx[:, None] > idx[None, :]          # j < i

    def step(S, inp):
        rk, kk, vk, lwk = inp                     # (B,L,H,P)
        cs = jnp.cumsum(lwk, axis=1)              # inclusive
        cs_prev = cs - lwk                        # exclusive: sum_{t<i}
        # intra: A[i,j] = sum_c r_i[c] k_j[c] exp(cs_prev_i - cs_j), j<i
        rd = rk * jnp.exp(cs_prev)                # (B,L,H,P)
        kd = kk * jnp.exp(-cs)
        A = jnp.einsum("bihp,bjhp->bhij", rd, kd)
        A = jnp.where(strict[None, None], A, 0.0)
        # diagonal bonus term: (r_i . u k_i)
        diag = jnp.einsum("bihp,hp,bihp->bih", rk, u, kk)
        y = (jnp.einsum("bhij,bjhp->bihp", A, vk)
             + diag[..., None] * vk)
        # inter: y_i += sum_c r_i[c] exp(cs_prev_i[c]) S[c,:]
        y = y + jnp.einsum("bihp,bhpq->bihq", rd, S)
        # state: S' = diag(exp(cs_L)) S + sum_j exp(cs_L - cs_j) k_j v_j
        tail = jnp.exp(cs[:, -1:] - cs)           # (B,L,H,P)
        S_new = (jnp.exp(cs[:, -1])[..., None] * S
                 + jnp.einsum("bjhp,bjhq->bhpq", tail * kk, vk))
        return S_new, y

    if BOUNDARY_RECOMPUTE:
        step = jax.checkpoint(step, prevent_cse=False)
    S_fin, ys = jax.lax.scan(step, s0, (rc, kc, vc, lwc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, nc * chunk, h, p)
    return y[:, :sl], S_fin


def wkv_ref(r, k, v, lw, u, s0=None):
    """Naive per-step oracle."""
    b, sl, h, p = r.shape
    S = jnp.zeros((b, h, p, p), jnp.float32) if s0 is None else s0

    def step(S, inp):
        rt, kt, vt, lwt = inp                     # (B,H,P)
        kv = jnp.einsum("bhp,bhq->bhpq", kt, vt)
        y = jnp.einsum("bhp,bhpq->bhq", rt, S + u[..., None] * kv)
        S = jnp.exp(lwt)[..., None] * S + kv
        return S, y

    xs = tuple(x.transpose(1, 0, 2, 3) for x in (r, k, v, lw))
    S, ys = jax.lax.scan(step, S, xs)
    return ys.transpose(1, 0, 2, 3), S


def _token_shift(x, x_prev_last):
    """x_{t-1} stream: shift right; position 0 uses carried state."""
    return jnp.concatenate([x_prev_last[:, None], x[:, :-1]], axis=1)


def apply(params, x, *, cfg: ModelConfig, state: Optional[dict] = None):
    """Time-mix forward. x: (B,S,d); state: {'x_prev_t': (B,d),
    'wkv': (B,H,P,P)} or None. Returns (out, (new_x_prev, new_wkv))."""
    rr = cfg.rwkv
    b, sl, d = x.shape
    h, p = d // rr.head_dim, rr.head_dim
    x_last = (state["x_prev_t"] if state is not None
              else jnp.zeros_like(x[:, 0]))
    xp = _token_shift(x, x_last)
    mu = params["mu"].astype(x.dtype)             # (5, d)
    xr = x + (xp - x) * mu[0]
    xk = x + (xp - x) * mu[1]
    xv = x + (xp - x) * mu[2]
    xg = x + (xp - x) * mu[3]
    xw = x + (xp - x) * mu[4]
    r = ops.matmul(xr, params["wr"]).reshape(b, sl, h, p).astype(jnp.float32)
    k = ops.matmul(xk, params["wk"]).reshape(b, sl, h, p).astype(jnp.float32)
    v = ops.matmul(xv, params["wv"]).reshape(b, sl, h, p).astype(jnp.float32)
    g = ops.matmul(xg, params["wg"])
    # data-dependent decay (the Finch contribution)
    lora = jnp.tanh(ops.matmul(xw, params["w_lora_a"],
                               out_dtype=jnp.float32))
    wlog = params["w0"] + ops.matmul(
        lora.astype(x.dtype), params["w_lora_b"], out_dtype=jnp.float32)
    lw = -jnp.exp(wlog).reshape(b, sl, h, p)
    lw = jnp.clip(lw, -CLAMP, -1e-6)
    s0 = state["wkv"] if state is not None else None
    y, s_fin = ops.wkv(r, k, v, lw, params["u"], s0=s0)
    y = y.reshape(b, sl, d).astype(x.dtype)
    y = ops.layernorm(y, params["ln_g"], params["ln_b"], kind="layer")
    y = (y.astype(jnp.float32)
         * jax.nn.silu(g.astype(jnp.float32))).astype(x.dtype)
    out = ops.matmul(y, params["wo"])
    return out, (x[:, -1], s_fin)
