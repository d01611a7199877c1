"""Flash attention (tiled online-softmax) Pallas TPU kernel.

Grid (B, H, nq, nk); the innermost kv-block axis is sequential ("arbitrary")
and accumulates into VMEM scratch (running max m, denominator l, weighted
accumulator acc) — the standard TPU flash pattern. BlockSpecs keep one
(bq x hd) Q tile + one (bk x hd) K/V tile in VMEM; MXU-aligned tile sizes
(multiples of 128 where shapes allow) are chosen in ops.py.

Supports causal and sliding-window masks (window > 0 => keys in
(q_pos - window, q_pos]).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # [bq]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool, window: int = 0,
                         scale: float = None, bq: int = 128, bk: int = 128,
                         interpret: bool = True):
    """q: [B,H,S,hd]; k,v: [B,H,T,hd] (kv heads already broadcast). Returns
    [B,H,S,hd]. S % bq == 0 and T % bk == 0 (ops.py pads)."""
    B, H, S, hd = q.shape
    T = k.shape[2]
    nq, nk = S // bq, T // bk
    scale = scale if scale is not None else hd ** -0.5

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
