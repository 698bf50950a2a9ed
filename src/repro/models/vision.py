"""Vision transformers (Swin / ViT) — the paper's own target workload.

Exercises the row-wise kernels end-to-end exactly as the ASIC does:
patch-embed conv -> the same matmul primitive (Sec. IV-C), FC layers ->
row-wise matmul (Sec. IV-D), W-MSA -> Q-stationary attention within 7x7
windows (Sec. IV-E). Used by the vision example and the paper-table
benchmarks.

With pipeline fusion on (the default, see DESIGN.md §3) a block runs as
four dense-pipeline kernel launches — [ln1-prologue + qkv],
[proj + residual], [ln2-prologue + mlp1 + gelu], [mlp2 + residual] —
plus the flash window-attention kernel, which takes the
relative-position bias (and shift mask) as an additive score-bias
operand instead of materializing dense 49x49 score matrices. With
fusion off the seed's per-op composition (separate norm kernels, dense
windowed scores, XLA residual adds) is preserved as the before/after
baseline.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.swin_t import SwinConfig, ViTConfig
from repro.core import runtime
from repro.kernels import ops
from repro.models.initializers import normal


def _w(key, din, dout, dtype):
    return normal(key, (din, dout), dtype, 1 / math.sqrt(din))


def _window_partition(x, w):
    b, h, wd, c = x.shape
    x = x.reshape(b, h // w, w, wd // w, w, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, w * w, c)


def _window_reverse(xw, w, h, wd):
    b = xw.shape[0] // ((h // w) * (wd // w))
    x = xw.reshape(b, h // w, wd // w, w, w, -1)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, wd, -1)


def _rel_pos_index(w: int):
    coords = jnp.stack(jnp.meshgrid(jnp.arange(w), jnp.arange(w),
                                    indexing="ij"), 0).reshape(2, -1)
    rel = coords[:, :, None] - coords[:, None, :]
    rel = rel + (w - 1)
    return rel[0] * (2 * w - 1) + rel[1]          # (w*w, w*w)


def _shift_mask(h, wd, w, shift):
    """Attention mask for shifted windows (standard Swin)."""
    img = jnp.zeros((1, h, wd, 1))
    cnt = 0
    slices = (slice(0, -w), slice(-w, -shift), slice(-shift, None))
    for hs in slices:
        for ws in slices:
            img = img.at[:, hs, ws, :].set(cnt)
            cnt += 1
    mw = _window_partition(img, w).reshape(-1, w * w)
    diff = mw[:, :, None] - mw[:, None, :]
    return jnp.where(diff == 0, 0.0, -1e9)        # (nW, w*w, w*w)


def init_swin(key, cfg: SwinConfig, dtype=jnp.float32):
    ks = iter(jax.random.split(key, 256))
    d = cfg.embed_dim
    params = {
        "patch_w": _w(next(ks), cfg.patch * cfg.patch * cfg.in_chans, d,
                      dtype),
        "patch_b": jnp.zeros((d,), dtype),
        "stages": [],
        "norm_g": None, "norm_b": None,
    }
    c = d
    for si, (depth, heads) in enumerate(zip(cfg.depths, cfg.num_heads)):
        stage = {"blocks": []}
        for _bi in range(depth):
            blk = {
                "ln1_g": jnp.ones((c,), dtype), "ln1_b": jnp.zeros((c,), dtype),
                "qkv": _w(next(ks), c, 3 * c, dtype),
                "qkv_b": jnp.zeros((3 * c,), dtype),
                "proj": _w(next(ks), c, c, dtype),
                "proj_b": jnp.zeros((c,), dtype),
                "ln2_g": jnp.ones((c,), dtype), "ln2_b": jnp.zeros((c,), dtype),
                "mlp1": _w(next(ks), c, int(cfg.mlp_ratio * c), dtype),
                "mlp1_b": jnp.zeros((int(cfg.mlp_ratio * c),), dtype),
                "mlp2": _w(next(ks), int(cfg.mlp_ratio * c), c, dtype),
                "mlp2_b": jnp.zeros((c,), dtype),
                "rel_bias": normal(next(ks),
                                   ((2 * cfg.window - 1) ** 2, heads),
                                   dtype, 0.02),
            }
            stage["blocks"].append(blk)
        if si < len(cfg.depths) - 1:
            stage["merge"] = _w(next(ks), 4 * c, 2 * c, dtype)
            c *= 2
        params["stages"].append(stage)
    params["norm_g"] = jnp.ones((c,), dtype)
    params["norm_b"] = jnp.zeros((c,), dtype)
    params["head"] = _w(next(ks), c, cfg.num_classes, dtype)
    params["head_b"] = jnp.zeros((cfg.num_classes,), dtype)
    return params


def _rel_bias(blk, rel_idx, heads, shift, mask):
    """Additive score bias (nb, heads, t, t): the relative-position
    table gathered per window geometry, plus the shift mask per
    window position when the block is shifted."""
    t = rel_idx.shape[0]
    rel = jnp.take(blk["rel_bias"], rel_idx.reshape(-1), axis=0)
    bias = rel.reshape(t, t, heads).transpose(2, 0, 1)[None]   # (1,h,t,t)
    if shift:
        bias = bias + mask[:, None]                 # (nW_img, h, t, t)
    return bias


def _wmsa(blk, x, heads, w, shift, rel_idx, mask):
    """Seed per-op window attention: dense 49x49 scores, separate
    norm/residual launches handled by the caller. Kept as the
    pipeline-fusion-off baseline."""
    b, h, wd, c = x.shape
    hd = c // heads
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    xw = _window_partition(x, w)                   # (B*nW, w*w, C)
    qkv = ops.matmul(xw, blk["qkv"], bias=blk["qkv_b"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    nw, t, _ = q.shape

    def heads_of(z):
        return z.reshape(nw, t, heads, hd).transpose(0, 2, 1, 3)

    q, k, v = heads_of(q), heads_of(k), heads_of(v)
    s = jnp.einsum("nhqd,nhkd->nhqk", q, k) * hd ** -0.5
    bias = jnp.take(blk["rel_bias"], rel_idx.reshape(-1), axis=0)
    s = s + bias.reshape(t, t, heads).transpose(2, 0, 1)[None]
    if shift:
        n_img = (h // w) * (wd // w)
        s = s.reshape(-1, n_img, heads, t, t) + mask[None, :, None]
        s = s.reshape(nw, heads, t, t)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhqk,nhkd->nhqd", p, v)
    o = o.transpose(0, 2, 1, 3).reshape(nw, t, c)
    o = ops.matmul(o, blk["proj"], bias=blk["proj_b"])
    x = _window_reverse(o, w, h, wd)
    if shift:
        x = jnp.roll(x, (shift, shift), axis=(1, 2))
    return x


def _swin_block_fused(blk, x, heads, w, shift, rel_idx, mask):
    """One Swin block as the fused pipeline: [ln1-prologue + qkv],
    flash window attention with the bias operand, [proj + residual],
    [ln2-prologue + mlp1 + gelu], [mlp2 + residual]."""
    b, h, wd, c = x.shape
    hd = c // heads
    xr = jnp.roll(x, (-shift, -shift), axis=(1, 2)) if shift else x
    xw = _window_partition(xr, w)                  # (B*nW, t, C)
    nw, t, _ = xw.shape
    # Swin stores qkv pre-fused since the seed — the LM params adopted
    # the same layout in PR 4, and both now route through ops.qkv_proj.
    q, k, v = ops.qkv_proj(xw, blk["qkv"], (c, c, c), bias=blk["qkv_b"],
                           norm=ops.NormSpec("layer", blk["ln1_g"],
                                             blk["ln1_b"]))

    def heads_of(z):
        return z.reshape(nw, t, heads, hd).transpose(0, 2, 1, 3)

    bias = _rel_bias(blk, rel_idx, heads, shift, mask)
    o = ops.attention(heads_of(q), heads_of(k), heads_of(v),
                      causal=False, bias=bias)
    o = o.transpose(0, 2, 1, 3).reshape(nw, t, c)
    # residual add in window layout == image layout (pure permutation)
    o = ops.matmul(o, blk["proj"], bias=blk["proj_b"], residual=xw)
    xr = _window_reverse(o, w, h, wd)
    x = jnp.roll(xr, (shift, shift), axis=(1, 2)) if shift else xr

    xf = x.reshape(-1, c)
    hdn = ops.matmul(xf, blk["mlp1"], bias=blk["mlp1_b"],
                     activation="gelu",
                     norm=ops.NormSpec("layer", blk["ln2_g"],
                                       blk["ln2_b"]))
    return ops.matmul(hdn, blk["mlp2"], bias=blk["mlp2_b"],
                      residual=xf).reshape(x.shape)


def swin_forward(params, images, cfg: SwinConfig):
    """images: (B, H, W, 3) -> logits (B, classes)."""
    w = cfg.window
    x = ops.patch_embed(images, params["patch_w"], params["patch_b"],
                        patch=cfg.patch)          # (B, H/4, W/4, D)
    rel_idx = _rel_pos_index(w)
    fuse = runtime.pipeline_fusion()
    for si, (_depth, heads) in enumerate(zip(cfg.depths, cfg.num_heads)):
        stage = params["stages"][si]
        b, h, wd, c = x.shape
        mask = _shift_mask(h, wd, w, w // 2) if h > w else None
        for bi, blk in enumerate(stage["blocks"]):
            shift = (w // 2) if (bi % 2 == 1 and h > w) else 0
            if fuse:
                x = _swin_block_fused(blk, x, heads, w, shift, rel_idx,
                                      mask)
                continue
            res = x
            xn = ops.layernorm(x.reshape(-1, c), blk["ln1_g"],
                               blk["ln1_b"]).reshape(x.shape)
            x = res + _wmsa(blk, xn, heads, w, shift, rel_idx, mask)
            res = x
            xn = ops.layernorm(x.reshape(-1, c), blk["ln2_g"],
                               blk["ln2_b"]).reshape(x.shape)
            hdn = ops.matmul(xn, blk["mlp1"], bias=blk["mlp1_b"],
                             activation="gelu")
            x = res + ops.matmul(hdn, blk["mlp2"], bias=blk["mlp2_b"])
        if "merge" in stage:
            b, h, wd, c = x.shape
            x = x.reshape(b, h // 2, 2, wd // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, wd // 2,
                                                      4 * c)
            x = ops.matmul(x, stage["merge"])
    b, h, wd, c = x.shape
    x = ops.layernorm(x.reshape(-1, c), params["norm_g"],
                      params["norm_b"]).reshape(b, h * wd, c)
    x = jnp.mean(x, axis=1)
    return ops.matmul(x, params["head"], bias=params["head_b"],
                      out_dtype=jnp.float32)


# ------------------------------- ViT ----------------------------------


def init_vit(key, cfg: ViTConfig, dtype=jnp.float32):
    ks = iter(jax.random.split(key, 128))
    d = cfg.embed_dim
    tokens = (cfg.img_size // cfg.patch) ** 2
    params = {
        "patch_w": _w(next(ks), cfg.patch * cfg.patch * cfg.in_chans, d,
                      dtype),
        "patch_b": jnp.zeros((d,), dtype),
        "cls": jnp.zeros((1, 1, d), dtype),
        "pos": normal(next(ks), (1, tokens + 1, d), dtype, 0.02),
        "blocks": [],
    }
    for _ in range(cfg.depth):
        blk = {
            "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "qkv": _w(next(ks), d, 3 * d, dtype),
            "proj": _w(next(ks), d, d, dtype),
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
            "mlp1": _w(next(ks), d, int(cfg.mlp_ratio * d), dtype),
            "mlp2": _w(next(ks), int(cfg.mlp_ratio * d), d, dtype),
        }
        params["blocks"].append(blk)
    params["norm_g"] = jnp.ones((d,), dtype)
    params["norm_b"] = jnp.zeros((d,), dtype)
    params["head"] = _w(next(ks), d, cfg.num_classes, dtype)
    return params


def vit_forward(params, images, cfg: ViTConfig):
    x = ops.patch_embed(images, params["patch_w"], params["patch_b"],
                        patch=cfg.patch)
    b = x.shape[0]
    d = cfg.embed_dim
    x = x.reshape(b, -1, d)
    x = jnp.concatenate([jnp.broadcast_to(params["cls"], (b, 1, d)), x], 1)
    x = x + params["pos"].astype(x.dtype)
    heads = cfg.num_heads
    hd = d // heads
    fuse = runtime.pipeline_fusion()
    for blk in params["blocks"]:
        def hsplit(z):
            return z.reshape(b, -1, heads, hd).transpose(0, 2, 1, 3)

        if fuse:
            qkv = ops.matmul(x, blk["qkv"],
                             norm=ops.NormSpec("layer", blk["ln1_g"],
                                               blk["ln1_b"]))
            q, k, v = jnp.split(qkv, 3, axis=-1)
            o = ops.attention(hsplit(q), hsplit(k), hsplit(v),
                              causal=False)
            o = o.transpose(0, 2, 1, 3).reshape(b, -1, d)
            x = ops.matmul(o, blk["proj"], residual=x)
            h = ops.matmul(x, blk["mlp1"], activation="gelu",
                           norm=ops.NormSpec("layer", blk["ln2_g"],
                                             blk["ln2_b"]))
            x = ops.matmul(h, blk["mlp2"], residual=x)
            continue
        xn = ops.layernorm(x, blk["ln1_g"], blk["ln1_b"])
        qkv = ops.matmul(xn, blk["qkv"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        o = ops.attention(hsplit(q), hsplit(k), hsplit(v), causal=False)
        o = o.transpose(0, 2, 1, 3).reshape(b, -1, d)
        x = x + ops.matmul(o, blk["proj"])
        xn = ops.layernorm(x, blk["ln2_g"], blk["ln2_b"])
        h = ops.matmul(xn, blk["mlp1"], activation="gelu")
        x = x + ops.matmul(h, blk["mlp2"])
    x = ops.layernorm(x, params["norm_g"], params["norm_b"])
    return ops.matmul(x[:, 0], params["head"], out_dtype=jnp.float32)
