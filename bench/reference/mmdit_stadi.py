"""Plain reference of Stable Diffusion 3 Medium's MMDiT sampling under a
STADI schedule.

Written from the published descriptions alone, in straightforward
``jax.numpy`` at float32 with ``highest`` matmul precision, and importing
nothing of the system under test:

- SD3's MMDiT (arXiv:2403.03206; diffusers ``SD3Transformer2DModel``, no
  qk-norm): a 2x2 patch embedding with bias plus the 2-D sin-cos table of a
  ``pos_embed_max_size`` square grid spanning ``latent / patch`` units,
  cropped to its centre; ``temb = TimestepEmbedding(sincos_256(t)) +
  TextProjection(pooled)``; the context through a linear embedder; joint
  blocks with an adaLN-zero per stream (LayerNorm without affine, eps 1e-6),
  q/k/v with bias per stream, one softmax over image ⊕ context keys, an
  out-projection and a tanh-GELU MLP per stream; the last block
  ``context_pre_only`` (AdaLayerNormContinuous on the context, no context
  out-projection or MLP); an AdaLayerNormContinuous head predicting the
  velocity.
- Rectified-flow Euler sampling, ``x <- x + (sigma_to - sigma_from) * v``,
  on diffusers' ``FlowMatchEulerDiscreteScheduler`` grid (1000 train steps,
  the config's shift); the model's timestep is ``1000 * sigma``.
- STADI (arXiv:2509.04719) Algorithm 1 as in ``dit_stadi``, whose Eq. 4/5
  plan it reuses. A patch evaluation computes its own image rows and the
  whole context stream; joint attention reads this patch's fresh image K/V
  for its rows, the image K/V published at the last boundary for every other
  row, and its own fresh context K/V. Only image K/V is published.

The weights use the parameter layout the system under test reads (checked
against it by the harness); their values, and each prompt's text-encoder
outputs, are drawn here from the seed. ``precision="fp8"`` is the control:
every matmul input is rounded to float8 e4m3 with a per-tensor scale.
Attention runs over blocks of queries, so that the scores of 4429 tokens
fit on one chip.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

T_TRAIN = 1000
CLIP_TOKENS = 77            # CLIP-L ⊕ CLIP-G tokens ahead of T5's in SD3
Q_BLOCK = 512               # queries per attention block


def _load_dit_stadi():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dit_stadi.py")
    spec = importlib.util.spec_from_file_location("bench_ref_dit_stadi", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dit = _load_dit_stadi()
stadi_plan = _dit.stadi_plan
_q8 = _dit._q8
_ein = _dit._ein
_ln = _dit._ln


# ---------------------------------------------------------------- weights

def _stream(D: int, F: int, p: str = "") -> dict:
    return {f"{p}qkv": (D, 3 * D), f"{p}qkv_b": (3 * D,), f"{p}wo": (D, D),
            f"{p}wo_b": (D,), f"{p}w1": (D, F), f"{p}b1": (F,),
            f"{p}w2": (F, D), f"{p}b2": (D,)}


def weight_shapes(sizes: dict) -> dict:
    """{name: shape}: ``blocks`` stacks the L - 1 joint blocks, ``last`` is
    the context_pre_only block."""
    D, L = sizes["d_model"], sizes["n_layers"]
    F = int(sizes["mlp_ratio"] * D)
    tok = sizes["channels"] * sizes["patch_size"] ** 2
    joint = {"mod_w": (D, 6 * D), "mod_b": (6 * D,), **_stream(D, F),
             "cmod_w": (D, 6 * D), "cmod_b": (6 * D,), **_stream(D, F, "c")}
    last = {"mod_w": (D, 6 * D), "mod_b": (6 * D,), **_stream(D, F),
            "cmod_w": (D, 2 * D), "cmod_b": (2 * D,), "cqkv": (D, 3 * D),
            "cqkv_b": (3 * D,)}
    return {"patch_embed": (tok, D), "patch_bias": (D,),
            "t_w1": (256, D), "t_b1": (D,), "t_w2": (D, D), "t_b2": (D,),
            "y_w1": (sizes["pooled_dim"], D), "y_b1": (D,), "y_w2": (D, D),
            "y_b2": (D,), "ctx_embed": (sizes["cond_dim"], D),
            "ctx_bias": (D,),
            "blocks": {k: (L - 1,) + s for k, s in joint.items()},
            "last": last, "final_mod_w": (D, 2 * D), "final_mod_b": (2 * D,),
            "final_proj": (D, tok), "final_bias": (tok,)}


def _std(name: str, shape, sizes: dict) -> float:
    """Fan-in scaling for projections (attention and MLP outputs not
    shrunk: the gates scale them), 0.02 for biases, and adaLN modulation
    and a head large enough that the timestep, attention and so the stale
    K/V move the image."""
    if name.endswith("mod_b"):
        return 0.1
    if name.endswith("mod_w"):
        return 1.5 * shape[-2] ** -0.5
    if len(shape) - name.startswith("blocks/") == 1:
        return 0.02
    return shape[-2] ** -0.5


def make_weights(key, sizes: dict):
    """All leaves from one key, normal with ``_std``, in the parameter
    dtype. Call under ``jax.jit`` (``sizes`` static) to make them on the
    device in one program."""
    shapes = weight_shapes(sizes)
    dt = jnp.dtype(sizes["param_dtype"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = "/".join(p.key for p in path)
        s = _std(name, shape, sizes)
        out.append((s * jax.random.normal(k, shape, jnp.float32)).astype(dt))
    return jax.tree.unflatten(treedef, out)


def prompt(sizes: dict, cls: int):
    """Prompt ``cls`` of the pool: the text encoders' outputs, seeded by the
    id alone. context [L, cond_dim] float32: the first min(77, L // 4)
    (CLIP) rows zero past channel ``pooled_dim``, as SD3 pads CLIP's 2048
    channels to T5's 4096; pooled [pooled_dim]."""
    rng = np.random.default_rng([15, int(cls)])
    L, Dc, P = sizes["cond_seq_len"], sizes["cond_dim"], sizes["pooled_dim"]
    ctx = rng.standard_normal((L, Dc), dtype=np.float32)
    ctx[:min(CLIP_TOKENS, L // 4), P:] = 0.0
    return ctx, rng.standard_normal((P,), dtype=np.float32)


# ---------------------------------------------------------------- model

def _pos_table(side: int, max_size: int, dim: int):
    """diffusers ``get_2d_sincos_pos_embed(dim, max_size, base_size=side)``
    cropped to the centre ``side`` square: channels [sincos(column),
    sincos(row)], coordinates ``arange(max_size) / (max_size / side)``."""
    grid = np.arange(max_size, dtype=np.float32) / np.float32(max_size / side)
    gw, gh = np.meshgrid(grid, grid)            # [row, col]

    def one(pos, d):
        omega = 1.0 / 10_000 ** (np.arange(d // 2, dtype=np.float64) / (d / 2))
        out = pos.reshape(-1).astype(np.float64)[:, None] * omega[None]
        return np.concatenate([np.sin(out), np.cos(out)], axis=1)
    table = np.concatenate([one(gw, dim // 2), one(gh, dim // 2)], axis=1)
    top = (max_size - side) // 2
    table = table.reshape(max_size, max_size, dim)[top:top + side,
                                                    top:top + side]
    return table.reshape(side * side, dim).astype(np.float32)


def _lin(x, w, b, fp8):
    return _ein("...i,io->...o", x, w, fp8) + b.astype(jnp.float32)


def _attend(q, k, v, fp8):
    """softmax(q k^T / sqrt(hd)) v over blocks of Q_BLOCK queries; q [n, H,
    hd], k and v [m, H, hd]."""
    n, H, hd = q.shape
    blk = min(Q_BLOCK, n)
    nb = -(-n // blk)
    qb = jnp.pad(q, ((0, nb * blk - n), (0, 0), (0, 0))).reshape(nb, blk, H,
                                                                 hd)

    def one(qc):
        probs = jax.nn.softmax(_ein("nhd,mhd->hnm", qc, k, fp8)
                               / math.sqrt(hd), axis=-1)
        return _ein("hnm,mhd->nhd", probs, v, fp8)
    return jax.lax.map(one, qb).reshape(nb * blk, H, hd)[:n]


@functools.partial(jax.jit, static_argnames=("sizes", "row0", "fp8"))
def patch_forward(w, sizes, x_rows, t, ctx, pooled, row0: int, kbuf, vbuf,
                  fp8: bool = False):
    """Velocity for latent rows ``[row0*p, row0*p + x_rows.shape[0])`` of one
    image, with the whole context stream, attending over the whole image's
    K/V (the buffers' values with this patch's own tokens fresh) and the
    fresh context K/V. ``kbuf=None`` is the full-image forward. Returns (v,
    (k, v)) with the fresh image K/V of this patch, [L, Nl, H, hd]."""
    sz = dict(sizes)
    p, C, D, H = sz["patch_size"], sz["channels"], sz["d_model"], sz["n_heads"]
    hd = D // H
    wp = sz["latent_size"] // p
    rows = x_rows.shape[0] // p
    Nl, Lc = rows * wp, ctx.shape[0]
    lo = row0 * wp
    tok = x_rows.reshape(rows, p, wp, p, C).transpose(0, 2, 1, 3, 4)
    tok = tok.reshape(Nl, p * p * C).astype(jnp.float32)
    pe = _pos_table(wp, sz["pos_embed_max_size"], D)[lo:lo + Nl]
    h = _lin(tok, w["patch_embed"], w["patch_bias"], fp8) + pe
    c = _lin(jnp.asarray(ctx, jnp.float32), w["ctx_embed"], w["ctx_bias"],
             fp8)
    half = 128
    freqs = np.exp(-math.log(10_000.0) * np.arange(half) / half)
    ang = jnp.asarray(t, jnp.float32) * freqs.astype(np.float32)
    tf = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])
    temb = _lin(jax.nn.silu(_lin(tf, w["t_w1"], w["t_b1"], fp8)), w["t_w2"],
                w["t_b2"], fp8)
    yemb = _lin(jax.nn.silu(_lin(jnp.asarray(pooled, jnp.float32), w["y_w1"],
                                 w["y_b1"], fp8)), w["y_w2"], w["y_b2"], fp8)
    cond = jax.nn.silu(temb + yemb)                                # [D]

    def mlp(x, bw, pre):
        u = _lin(x, bw[pre + "w1"], bw[pre + "b1"], fp8)
        return _lin(jax.nn.gelu(u, approximate=True), bw[pre + "w2"],
                    bw[pre + "b2"], fp8)

    def block(carry, xs, last=False):
        h, c = carry
        bw, *bufs = xs
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(
            _lin(cond, bw["mod_w"], bw["mod_b"], fp8), 6)
        qkv = _lin(_ln(h) * (1 + sc1) + sh1, bw["qkv"], bw["qkv_b"], fp8)
        q, k, v = jnp.split(qkv.reshape(Nl, 3, H, hd), 3, axis=1)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]
        if last:
            csc, csh = jnp.split(_lin(cond, bw["cmod_w"], bw["cmod_b"], fp8),
                                 2)
            cn = _ln(c) * (1 + csc) + csh
        else:
            cm = jnp.split(_lin(cond, bw["cmod_w"], bw["cmod_b"], fp8), 6)
            cn = _ln(c) * (1 + cm[1]) + cm[0]
        cqkv = _lin(cn, bw["cqkv"], bw["cqkv_b"], fp8).reshape(Lc, 3, H, hd)
        K, V = k, v
        if bufs:
            K = bufs[0].astype(jnp.float32).at[lo:lo + Nl].set(k)
            V = bufs[1].astype(jnp.float32).at[lo:lo + Nl].set(v)
        out = _attend(jnp.concatenate([q, cqkv[:, 0]]),
                      jnp.concatenate([K, cqkv[:, 1]]),
                      jnp.concatenate([V, cqkv[:, 2]]), fp8)
        att, catt = out[:Nl].reshape(Nl, D), out[Nl:].reshape(Lc, D)
        h = h + g1 * _lin(att, bw["wo"], bw["wo_b"], fp8)
        h = h + g2 * mlp(_ln(h) * (1 + sc2) + sh2, bw, "")
        if not last:          # the last block discards the context output
            c = c + cm[2] * _lin(catt, bw["cwo"], bw["cwo_b"], fp8)
            c = c + cm[5] * mlp(_ln(c) * (1 + cm[4]) + cm[3], bw, "c")
        return (h, c), (k, v)

    L = sz["n_layers"]
    bufs = () if kbuf is None else (kbuf, vbuf)
    (h, c), kv = jax.lax.scan(block, (h, c), (w["blocks"],)
                              + tuple(b[:L - 1] for b in bufs))
    (h, _), kv_last = block((h, c), (w["last"],)
                            + tuple(b[L - 1] for b in bufs), last=True)
    sc, sh = jnp.split(_lin(cond, w["final_mod_w"], w["final_mod_b"], fp8), 2)
    out = _lin(_ln(h) * (1 + sc) + sh, w["final_proj"], w["final_bias"], fp8)
    vel = out.reshape(rows, wp, p, p, C).transpose(0, 2, 1, 3, 4)
    kv = tuple(jnp.concatenate([a, b[None]]) for a, b in zip(kv, kv_last))
    return vel.reshape(rows * p, wp * p, C), kv


# ---------------------------------------------------------------- sampling

def sigmas(M: int, shift: float):
    """FlowMatchEulerDiscreteScheduler(1000, shift).set_timesteps(M): M
    sigmas from 1 to the training grid's smallest, shifted, then 0."""
    sh = lambda s: shift * s / (1 + (shift - 1) * s)
    s = sh(np.linspace(1.0, sh(1.0 / T_TRAIN), M))
    return np.append(s, 0.0).astype(np.float32)


def generate(w, sizes: dict, stadi: dict, x_T, cls: int,
             precision: str = "f32"):
    """One image: x_T [1, H, W, C] (or [H, W, C]) and prompt ``cls`` -> x_0
    [H, W, C], float32, under the STADI schedule that ``stadi``
    (occupancies, m_base, m_warmup) describes. ``precision`` "f32" is the
    reference, "fp8" the control."""
    fp8 = precision == "fp8"
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    key = tuple(sorted(sizes.items()))
    p = sizes["patch_size"]
    wp = sizes["latent_size"] // p
    M, Mw = stadi["m_base"], stadi["m_warmup"]
    speeds = [1.0 - o for o in stadi["occupancies"]]
    _, ratios, rows = stadi_plan(speeds, M, Mw, wp)
    sg = sigmas(M, sizes["flow_shift"])
    ts = np.float32(T_TRAIN) * sg
    ctx, pooled = (jnp.asarray(a) for a in prompt(sizes, cls))
    x = jnp.asarray(x_T, jnp.float32).reshape(sizes["latent_size"],
                                                sizes["latent_size"],
                                                sizes["channels"])
    euler = lambda x, v, a, b: x + (sg[b] - sg[a]) * v
    kbuf = vbuf = None
    for m in range(Mw):
        v, (kbuf, vbuf) = patch_forward(w, key, x, ts[m], ctx, pooled, 0,
                                        None, None, fp8=fp8)
        x = euler(x, v, m, m + 1)
    R = math.lcm(*[r for r in ratios if r])
    starts = np.cumsum([0] + rows[:-1])
    for m0 in range(Mw, M, R):
        slabs, pending = {}, {}
        for i, (r, n) in enumerate(zip(ratios, rows)):
            if not r or not n:
                continue
            lo = int(starts[i])
            xl = x[lo * p:(lo + n) * p]
            for s in range(R // r):
                a, b = m0 + s * r, m0 + (s + 1) * r
                v, kv = patch_forward(w, key, xl, ts[a], ctx, pooled, lo,
                                      kbuf, vbuf, fp8=fp8)
                xl = euler(xl, v, a, b)
                if s == 0:
                    pending[i] = (lo * wp, kv)
            slabs[i] = (lo, xl)
        for lo, xl in slabs.values():
            x = x.at[lo * p:lo * p + xl.shape[0]].set(xl)
        for tok0, (k, v) in pending.values():
            kbuf = kbuf.at[:, tok0:tok0 + k.shape[1]].set(k)
            vbuf = vbuf.at[:, tok0:tok0 + v.shape[1]].set(v)
    return x
