"""Plain reference of class-conditional DiT sampling under a STADI schedule.

Written from the published descriptions alone, in straightforward
``jax.numpy`` at float32 with ``highest`` matmul precision, and importing
nothing of the system under test:

- DiT (arXiv:2212.09748): patchify, fixed 2-D sin-cos position embedding,
  sinusoidal timestep MLP plus a class embedding, adaLN-zero blocks
  (LayerNorm without affine, shift/scale/gate from the conditioning vector),
  an eps-only linear head.
- DDIM / DPM-Solver-1 (STADI paper, Lemma 1) over ``round(linspace(T, 0,
  M + 1))`` on a linear beta schedule (1e-4 .. 2e-2, T = 1000).
- STADI (arXiv:2509.04719) Algorithm 1: ``m_warmup`` synchronous full-image
  steps; then intervals of ``lcm(ratios)`` fine steps in which worker ``i``
  denoises its row slab ``lcm / r_i`` times against the K/V published at the
  last boundary (its own rows fresh, DistriFusion style), publishes the K/V
  of its first substep, and at the boundary the slabs and the published K/V
  are gathered ("sync" exchange). Steps by Eq. 4 (two tiers, ratios 1 and
  2), patch rows by Eq. 5 (largest remainder).

The weights use the parameter layout the system under test reads (names and
shapes are checked against it by the harness); their values are drawn here
from the seed. ``precision="fp8"`` is the correctness control: every matmul
input is rounded to float8 e4m3 with a per-tensor scale.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

T_TRAIN = 1000
BETA_MIN, BETA_MAX = 1e-4, 2e-2


# ---------------------------------------------------------------- weights

def weight_shapes(sizes: dict) -> dict:
    """{name: shape}; block leaves carry a leading layer axis."""
    D, L = sizes["d_model"], sizes["n_layers"]
    F = int(sizes["mlp_ratio"] * D)
    tok = sizes["channels"] * sizes["patch_size"] ** 2
    blocks = {"qkv": (L, D, 3 * D), "wo": (L, D, D), "w1": (L, D, F),
              "w2": (L, F, D), "mod_w": (L, D, 6 * D), "mod_b": (L, 6 * D)}
    return {"patch_embed": (tok, D), "patch_bias": (D,), "t_w1": (256, D),
            "t_w2": (D, D), "cond_embed": (sizes["n_classes"], D),
            "blocks": blocks, "final_mod_w": (D, 2 * D),
            "final_mod_b": (2 * D,), "final_proj": (D, tok)}


def _stds(sizes: dict) -> dict:
    """Standard deviation of each leaf: fan-in scaling for projections,
    residual projections shrunk by sqrt(2 L), and small non-zero values for
    the adaLN modulation and the head (zero there would make attention, and
    so the stale K/V, irrelevant to the output)."""
    D, L = sizes["d_model"], sizes["n_layers"]
    F = int(sizes["mlp_ratio"] * D)
    tok = sizes["channels"] * sizes["patch_size"] ** 2
    return {"patch_embed": tok ** -0.5, "patch_bias": 0.02,
            "t_w1": 256 ** -0.5, "t_w2": D ** -0.5, "cond_embed": 0.02,
            "blocks": {"qkv": D ** -0.5, "wo": (2 * L * D) ** -0.5,
                       "w1": D ** -0.5, "w2": (2 * L * F) ** -0.5,
                       "mod_w": 0.02, "mod_b": 0.02},
            "final_mod_w": 0.02, "final_mod_b": 0.02, "final_proj": 0.05}


def make_weights(key, sizes: dict):
    """All leaves from one key, normal with ``_stds``, in the parameter
    dtype. Call under ``jax.jit`` (``sizes`` static) to make them on the
    device in one program."""
    shapes, stds = weight_shapes(sizes), _stds(sizes)
    dt = jnp.dtype(sizes["param_dtype"])
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    std_leaves = jax.tree.leaves(stds)
    out = [(s * jax.random.normal(k, shape, jnp.float32)).astype(dt)
           for k, shape, s in zip(keys, leaves, std_leaves)]
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------- plan

def stadi_plan(speeds, m_base: int, m_warmup: int, p_total: int,
               a: float = 0.75, b: float = 0.25):
    """Eq. 4 (tiers 1 and 2) then Eq. 5. Returns (steps, ratios, rows)."""
    vmax = max(speeds)
    F = m_base - m_warmup
    steps, ratios = [], []
    for v in speeds:
        if v <= b * vmax:
            steps.append(0)
            ratios.append(0)
        elif v > a * vmax:
            steps.append(m_base)
            ratios.append(1)
        else:
            steps.append(m_warmup + F // 2)
            ratios.append(2)
    rate = [v / m if m else 0.0 for v, m in zip(speeds, steps)]
    ideal = [r / sum(rate) * p_total for r in rate]
    rows = [math.floor(x) for x in ideal]
    rows = [max(n, 1) if r > 0 else 0 for n, r in zip(rows, rate)]
    order = sorted(range(len(ideal)), key=lambda i: ideal[i] - rows[i],
                   reverse=True)
    for i in order:
        if sum(rows) >= p_total:
            break
        if rate[i] > 0:
            rows[i] += 1
    if sum(rows) != p_total:
        raise ValueError(f"row allocation {rows} does not cover {p_total}")
    return steps, ratios, rows


# ---------------------------------------------------------------- model

def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale (the control)."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6)


def _pos_embed(side: int, dim: int):
    def one(n, d):
        omega = np.exp(-math.log(10_000.0) * np.arange(d // 2) / (d // 2))
        ang = np.arange(n)[:, None] * omega[None]
        return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    e = one(side, dim // 2)
    grid = np.concatenate([np.broadcast_to(e[:, None], (side, side, dim // 2)),
                           np.broadcast_to(e[None, :], (side, side, dim // 2))],
                          axis=-1)
    return grid.reshape(side * side, dim).astype(np.float32)


def _cond(w, t, cls, fp8):
    half = 128
    freqs = np.exp(-math.log(10_000.0) * np.arange(half) / half)
    ang = jnp.asarray(t, jnp.float32) * freqs.astype(np.float32)
    temb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])[None]      # [1, 256]
    h = jax.nn.silu(_ein("bi,io->bo", temb, w["t_w1"], fp8))
    h = _ein("bi,io->bo", h, w["t_w2"], fp8)
    return jax.nn.silu(h + w["cond_embed"][cls].astype(jnp.float32)[None])


@functools.partial(jax.jit, static_argnames=("sizes", "row0", "fp8"))
def patch_forward(w, sizes, x_rows, t, cls, row0: int, kbuf, vbuf,
                  fp8: bool = False):
    """eps for latent rows ``[row0*p, row0*p + x_rows.shape[0])`` of one
    image, attending over the whole image's K/V: the buffers' values, with
    this patch's own tokens replaced by fresh ones. ``kbuf=None`` is the
    full-image forward (every token fresh). Returns (eps, (k, v)) with the
    fresh K/V of this patch, [L, Nl, H, hd]."""
    sz = dict(sizes)
    p, C, D, H = sz["patch_size"], sz["channels"], sz["d_model"], sz["n_heads"]
    hd = D // H
    wp = sz["latent_size"] // p
    rows = x_rows.shape[0] // p
    Nl = rows * wp
    tok = x_rows.reshape(rows, p, wp, p, C).transpose(0, 2, 1, 3, 4)
    tok = tok.reshape(Nl, p * p * C).astype(jnp.float32)
    pe = _pos_embed(wp, D)[row0 * wp: row0 * wp + Nl]
    h = (_ein("ni,io->no", tok, w["patch_embed"], fp8)
         + w["patch_bias"].astype(jnp.float32) + pe)
    c = _cond(w, t, cls, fp8)                                       # [1, D]
    lo = row0 * wp

    def block(h, xs):
        if kbuf is None:
            bw = xs
        else:
            bw, kb, vb = xs
        mod = _ein("bi,io->bo", c, bw["mod_w"], fp8)[0] \
            + bw["mod_b"].astype(jnp.float32)
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6)
        xn = _ln(h) * (1 + sc1) + sh1
        qkv = _ein("ni,io->no", xn, bw["qkv"], fp8).reshape(Nl, 3, H, hd)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        if kbuf is None:
            K, V = k, v
        else:
            K = kb.astype(jnp.float32).at[lo:lo + Nl].set(k)
            V = vb.astype(jnp.float32).at[lo:lo + Nl].set(v)
        logits = _ein("nhd,mhd->hnm", q, K, fp8) / math.sqrt(hd)
        probs = jax.nn.softmax(logits, axis=-1)
        att = _ein("hnm,mhd->nhd", probs, V, fp8).reshape(Nl, D)
        h = h + g1 * _ein("ni,io->no", att, bw["wo"], fp8)
        xn = _ln(h) * (1 + sc2) + sh2
        mlp = _ein("nf,fo->no", jax.nn.gelu(_ein("ni,if->nf", xn, bw["w1"], fp8)),
                   bw["w2"], fp8)
        return h + g2 * mlp, (k, v)

    xs = w["blocks"] if kbuf is None else (w["blocks"], kbuf, vbuf)
    h, kv = jax.lax.scan(block, h, xs)
    mod = _ein("bi,io->bo", c, w["final_mod_w"], fp8)[0] \
        + w["final_mod_b"].astype(jnp.float32)
    sh, sc = jnp.split(mod, 2)
    out = _ein("ni,io->no", _ln(h) * (1 + sc) + sh, w["final_proj"], fp8)
    eps = out.reshape(rows, wp, p, p, C).transpose(0, 2, 1, 3, 4)
    return eps.reshape(rows * p, wp * p, C), kv


# ---------------------------------------------------------------- sampling

def _schedule():
    betas = np.concatenate([[0.0], np.linspace(BETA_MIN, BETA_MAX, T_TRAIN)])
    return np.cumprod(1.0 - betas)


def _ddim(x, eps, ab, t_from, t_to):
    a_f, a_t = math.sqrt(ab[t_from]), math.sqrt(ab[t_to])
    s_f, s_t = math.sqrt(1 - ab[t_from]), math.sqrt(1 - ab[t_to])
    return (a_t / a_f) * x - (a_t * s_f / a_f - s_t) * eps


def generate(w, sizes: dict, stadi: dict, x_T, cls: int,
             precision: str = "f32"):
    """One image: x_T [1, H, W, C] (or [H, W, C]) -> x_0 [H, W, C], float32,
    under the STADI schedule that ``stadi`` (occupancies, m_base, m_warmup)
    describes. ``precision`` "f32" is the reference, "fp8" the control."""
    fp8 = precision == "fp8"
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    key = tuple(sorted(sizes.items()))
    p = sizes["patch_size"]
    wp = sizes["latent_size"] // p
    M, Mw = stadi["m_base"], stadi["m_warmup"]
    speeds = [1.0 - o for o in stadi["occupancies"]]
    _, ratios, rows = stadi_plan(speeds, M, Mw, wp)
    ab = _schedule()
    ts = [int(t) for t in np.round(np.linspace(T_TRAIN, 0, M + 1))]
    x = jnp.asarray(x_T, jnp.float32).reshape(sizes["latent_size"],
                                                sizes["latent_size"],
                                                sizes["channels"])
    cls = jnp.asarray(cls, jnp.int32)
    kbuf = vbuf = None
    for m in range(Mw):
        eps, (kbuf, vbuf) = patch_forward(w, key, x, ts[m], cls, 0, None,
                                          None, fp8=fp8)
        x = _ddim(x, eps, ab, ts[m], ts[m + 1])
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
                t_from, t_to = ts[m0 + s * r], ts[m0 + (s + 1) * r]
                eps, kv = patch_forward(w, key, xl, t_from, cls, lo, kbuf,
                                        vbuf, fp8=fp8)
                xl = _ddim(xl, eps, ab, t_from, t_to)
                if s == 0:
                    pending[i] = (lo * wp, kv)
            slabs[i] = (lo, xl)
        for lo, xl in slabs.values():
            x = x.at[lo * p:lo * p + xl.shape[0]].set(xl)
        for tok0, (k, v) in pending.values():
            kbuf = kbuf.at[:, tok0:tok0 + k.shape[1]].set(k)
            vbuf = vbuf.at[:, tok0:tok0 + v.shape[1]].set(v)
    return x
