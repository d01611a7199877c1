"""MMDiT denoiser of Stable Diffusion 3 (arXiv:2403.03206; diffusers'
``SD3Transformer2DModel`` without qk-norm or dual attention), with the
patch-parallel interface of :mod:`repro.models.diffusion.dit`.

Two token streams run side by side: the image rows and the prompt's
context tokens. Each block modulates both from ``silu(temb)`` (adaLN-zero
per stream), joins them in one softmax over image keys ⊕ context keys, and
gives each stream its own out-projection and tanh-GELU MLP. The last block
is ``context_pre_only``: its context stream only feeds keys and values.

Under displaced patch parallelism (DESIGN.md §18) a patch evaluation
computes its own image rows and the WHOLE context stream; joint attention
reads this patch's fresh image K/V for its rows, the published (stale) image
K/V for every other row, and its own fresh context K/V. Only image K/V is
returned for publishing: context K/V is never stale and never buffered, so
the buffers have :func:`dit.buffer_shape`, as DiT's.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.diffusion import DiTConfig
from repro.models import layers
from repro.models.diffusion import dit


class TextCond(NamedTuple):
    """The prompt of a call: the text encoders' tokens ``context`` [B, L,
    cond_dim] and their pooled vector ``pooled`` [B, pooled_dim]."""
    context: jnp.ndarray
    pooled: jnp.ndarray


# ----------------------------------------------------------------------
# params
# ----------------------------------------------------------------------

def _stream_shapes(D: int, F: int, prefix: str = "") -> dict:
    """Attention projections and MLP of one stream (biases throughout)."""
    return {f"{prefix}qkv": (D, 3 * D), f"{prefix}qkv_b": (3 * D,),
            f"{prefix}wo": (D, D), f"{prefix}wo_b": (D,),
            f"{prefix}w1": (D, F), f"{prefix}b1": (F,),
            f"{prefix}w2": (F, D), f"{prefix}b2": (D,)}


def param_shapes(cfg: DiTConfig) -> dict:
    """{name: shape}. ``blocks`` stacks the L - 1 joint blocks on a leading
    axis; ``last`` is the context_pre_only block: its context stream has an
    AdaLayerNormContinuous (scale, shift) and q/k/v projections only."""
    D, L = cfg.d_model, cfg.n_layers
    F = int(cfg.mlp_ratio * D)
    tok = cfg.token_dim
    joint = {"mod_w": (D, 6 * D), "mod_b": (6 * D,), **_stream_shapes(D, F),
             "cmod_w": (D, 6 * D), "cmod_b": (6 * D,),
             **_stream_shapes(D, F, "c")}
    last = {"mod_w": (D, 6 * D), "mod_b": (6 * D,), **_stream_shapes(D, F),
            "cmod_w": (D, 2 * D), "cmod_b": (2 * D,),
            "cqkv": (D, 3 * D), "cqkv_b": (3 * D,)}
    return {"patch_embed": (tok, D), "patch_bias": (D,),
            "t_w1": (256, D), "t_b1": (D,), "t_w2": (D, D), "t_b2": (D,),
            "y_w1": (cfg.pooled_dim, D), "y_b1": (D,), "y_w2": (D, D),
            "y_b2": (D,),
            "ctx_embed": (cfg.cond_dim, D), "ctx_bias": (D,),
            "blocks": {k: (L - 1,) + s for k, s in joint.items()},
            "last": last,
            "final_mod_w": (D, 2 * D), "final_mod_b": (2 * D,),
            "final_proj": (D, tok), "final_bias": (tok,)}


_ZERO_INIT = ("mod_w", "cmod_w", "final_mod_w", "final_proj")


def init_params(key, cfg: DiTConfig):
    """Untrained params in the layout of :func:`param_shapes`: fan-in
    projections, zero biases, and adaLN-zero modulation and output head
    (as DiT's)."""
    dt = jnp.dtype(cfg.param_dtype)
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        ndim = len(shape) - (path[0].key == "blocks")    # per block
        if ndim == 1 or path[-1].key in _ZERO_INIT:
            out.append(jnp.zeros(shape, dt))
        else:
            out.append(layers.dense_init(k, shape, dt))
    return jax.tree.unflatten(treedef, out)


# ----------------------------------------------------------------------
# embeddings
# ----------------------------------------------------------------------

def _sincos(pos, dim: int) -> np.ndarray:
    omega = 1.0 / 10_000 ** (np.arange(dim // 2, dtype=np.float64)
                             / (dim / 2.0))
    out = pos.astype(np.float64)[:, None] * omega[None]
    return np.concatenate([np.sin(out), np.cos(out)], axis=1)


@functools.lru_cache(maxsize=None)
def pos_table(side: int, max_size: int, dim: int) -> np.ndarray:
    """SD3's position table [side * side, dim] float32: the 2-D sin-cos table
    of a ``max_size`` square whose coordinates span ``side`` units (diffusers'
    ``base_size``), cropped to its centre ``side`` square. The first half of
    the channels encodes the column, the second the row."""
    top = (max_size - side) // 2
    c = (np.arange(max_size, dtype=np.float32)
         / np.float32(max_size / side))[top:top + side]
    e = _sincos(c, dim // 2)                             # [side, dim/2]
    grid = np.concatenate(
        [np.broadcast_to(e[None, :], (side, side, dim // 2)),
         np.broadcast_to(e[:, None], (side, side, dim // 2))], axis=-1)
    return grid.reshape(side * side, dim).astype(np.float32)


def _linear(x, w, b):
    return x @ w + b


def _mlp2(x, w1, b1, w2, b2):
    """Linear -> SiLU -> Linear (the timestep and pooled-text embedders)."""
    return _linear(jax.nn.silu(_linear(x, w1, b1)), w2, b2)


def _ff(x, w1, b1, w2, b2):
    """The blocks' MLP: Linear -> tanh-GELU -> Linear."""
    return _linear(jax.nn.gelu(_linear(x, w1, b1), approximate=True), w2, b2)


def cond_vector(params, t, pooled, B: int):
    """silu(temb): temb = TimestepEmbedding(sincos_256(t)) +
    TextProjection(pooled); every adaLN of the model reads it."""
    t = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (B,))
    temb = _mlp2(layers.sinusoidal_embedding(t, 256), params["t_w1"],
                 params["t_b1"], params["t_w2"], params["t_b2"])
    y = _mlp2(pooled, params["y_w1"], params["y_b1"], params["y_w2"],
              params["y_b2"])
    return jax.nn.silu(temb + y)


# ----------------------------------------------------------------------
# the joint block
# ----------------------------------------------------------------------

def joint_attention(q, k, v, cq, ck, cv):
    """One softmax over image keys ⊕ context keys, for image queries ⊕
    context queries (``cq`` None: image queries only, the last block).
    Returns (image rows' output, context output or None)."""
    Nl = q.shape[1]
    qs = q if cq is None else jnp.concatenate([q, cq.astype(q.dtype)], 1)
    out = layers.attend(qs, jnp.concatenate([k, ck.astype(k.dtype)], 1),
                        jnp.concatenate([v, cv.astype(v.dtype)], 1))
    return out[:, :Nl], (None if cq is None else out[:, Nl:])


def context_update(bp, ctx, catt, cmod):
    """The context stream after joint attention (every block but the last):
    gated out-projection residual, then the modulated MLP's."""
    _, _, g1, sh2, sc2, g2 = cmod
    B, Lc, D = ctx.shape
    ctx = ctx + g1[:, None] * _linear(catt.reshape(B, Lc, D), bp["cwo"],
                                      bp["cwo_b"])
    mlp = _ff(dit._modulate(dit._ln(ctx), sh2, sc2), bp["cw1"], bp["cb1"],
              bp["cw2"], bp["cb2"])
    return ctx + g2[:, None] * mlp


def _block(cfg: DiTConfig, c, tok_start, last: bool):
    """The joint block as a scan body over ((x, ctx), (params[, bk, bv]))."""
    D, H = cfg.d_model, cfg.n_heads
    hd = D // H

    def block(carry, scanned):
        x, ctx = carry
        bp, *bufs = scanned
        B, Nl, Lc = x.shape[0], x.shape[1], ctx.shape[1]
        mod = _linear(c.astype(x.dtype), bp["mod_w"], bp["mod_b"])
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        xn = dit._modulate(dit._ln(x), sh1, sc1)
        qkv = _linear(xn, bp["qkv"], bp["qkv_b"]).reshape(B, Nl, 3, H, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        cm = _linear(c.astype(ctx.dtype), bp["cmod_w"], bp["cmod_b"])
        if last:
            # AdaLayerNormContinuous: (scale, shift), in that order; the
            # context queries' output would be discarded, so only K/V
            sc, sh = jnp.split(cm, 2, axis=-1)
            cn = dit._modulate(dit._ln(ctx), sh, sc)
            ckv = _linear(cn, bp["cqkv"][:, D:], bp["cqkv_b"][D:])
            ckv = ckv.reshape(B, Lc, 2, H, hd)
            cq, ck, cv = None, ckv[:, :, 0], ckv[:, :, 1]
        else:
            cmod = jnp.split(cm, 6, axis=-1)
            cn = dit._modulate(dit._ln(ctx), cmod[0], cmod[1])
            cqkv = _linear(cn, bp["cqkv"], bp["cqkv_b"]).reshape(
                B, Lc, 3, H, hd)
            cq, ck, cv = cqkv[:, :, 0], cqkv[:, :, 1], cqkv[:, :, 2]
        if not bufs:
            K, V = k, v                          # every image row fresh
        else:
            bk, bv = bufs
            K = jax.lax.dynamic_update_slice_in_dim(bk, k.astype(bk.dtype),
                                                    tok_start, axis=1)
            V = jax.lax.dynamic_update_slice_in_dim(bv, v.astype(bv.dtype),
                                                    tok_start, axis=1)
        att, catt = joint_attention(q, K, V, cq, ck, cv)
        x = x + g1[:, None] * _linear(att.reshape(B, Nl, D), bp["wo"],
                                      bp["wo_b"])
        x = x + g2[:, None] * _ff(dit._modulate(dit._ln(x), sh2, sc2),
                                  bp["w1"], bp["b1"], bp["w2"], bp["b2"])
        if not last:
            ctx = context_update(bp, ctx, catt, cmod)
        return (x, ctx), (k, v)

    return block


def forward_patch(params, cfg: DiTConfig, x_rows, t, cond: TextCond,
                  row_start, buffers: Optional[Tuple] = None,
                  return_kv: bool = True):
    """Velocity for a row-patch, with the whole context stream.

    x_rows: [B, rows_local, W, C] latent slab (full width).
    cond: :class:`TextCond` (context [B, L, cond_dim], pooled [B, P]).
    buffers: None (the patch's image rows are all the image keys: exact
             when the patch is the whole image) or (buf_k, buf_v) each
             [L, B, N_img, H, hd], the published image K/V; this patch's
             own rows are overwritten fresh before attending.
    Returns (v_rows [B, rows_local, W, C], (fresh_k, fresh_v)
    [L, B, Nl, H, hd] of this patch's image rows, or None).
    """
    if cfg.use_pallas_attention:
        # no Pallas body for a fresh key prefix yet: the joint read takes
        # the jnp path, recorded at trace time
        from repro.kernels import ops as kops
        kops.record_kernel_miss("joint-attn-unsupported")
    p, wp = cfg.patch_size, cfg.tokens_per_side
    rows_tok = x_rows.shape[1] // p
    B = x_rows.shape[0]
    tok = dit.patchify(x_rows, p)                        # [B, Nl, p*p*C]
    Nl = tok.shape[1]
    tok_start = row_start * wp
    pe = jax.lax.dynamic_slice_in_dim(
        jnp.asarray(pos_table(wp, cfg.pos_embed_max_size, cfg.d_model)),
        tok_start, Nl, axis=0)
    x = _linear(tok, params["patch_embed"], params["patch_bias"]) \
        + pe.astype(tok.dtype)
    ctx = _linear(cond.context, params["ctx_embed"], params["ctx_bias"])
    c = cond_vector(params, t, cond.pooled, B)           # [B, D]
    L = cfg.n_layers
    bufs = buffers or ()
    first = (params["blocks"],) + tuple(b[:L - 1] for b in bufs)
    (x, ctx), kvs = jax.lax.scan(_block(cfg, c, tok_start, False),
                                 (x, ctx), first)
    last = (params["last"],) + tuple(b[L - 1] for b in bufs)
    (x, _), kv_last = _block(cfg, c, tok_start, True)((x, ctx), last)
    fm = _linear(c.astype(x.dtype), params["final_mod_w"],
                 params["final_mod_b"])
    sc, sh = jnp.split(fm, 2, axis=-1)
    out = _linear(dit._modulate(dit._ln(x), sh, sc), params["final_proj"],
                  params["final_bias"])
    vel = dit.unpatchify(out, p, rows_tok, wp, cfg.channels)
    if not return_kv:
        return vel, None
    return vel, tuple(jnp.concatenate([a, b[None]], 0)
                      for a, b in zip(kvs, kv_last))
