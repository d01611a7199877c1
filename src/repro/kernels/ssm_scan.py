"""Chunked selective-SSM scan (Mamba recurrence) Pallas TPU kernel.

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * x_t) B_t^T
    y_t = <h_t, C_t> + D * x_t

Grid (B, n_dblocks, n_chunks): the chunk axis is sequential ("arbitrary")
with the running state h [dblk, N] carried in VMEM scratch across chunks —
HBM traffic is O(S * dblk) instead of O(S * dblk * N) for a naive
materialized-state scan, and each chunk's inner recurrence runs entirely in
VMEM/VREGs. dblk is lane-aligned (multiple of 128) in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, dskip_ref, o_ref,
                h_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)          # [chunk, dblk]
    dt = dt_ref[0].astype(jnp.float32)        # [chunk, dblk]
    bt = b_ref[0].astype(jnp.float32)         # [chunk, N]
    ct = c_ref[0].astype(jnp.float32)         # [chunk, N]
    a = a_ref[...].astype(jnp.float32)        # [dblk, N]
    dskip = dskip_ref[...].astype(jnp.float32)  # [dblk]

    def step(t, carry):
        h, ys = carry
        da = jnp.exp(dt[t][:, None] * a)                       # [dblk, N]
        h = da * h + (dt[t] * x[t])[:, None] * bt[t][None, :]  # [dblk, N]
        y = jnp.sum(h * ct[t][None, :], axis=1) + dskip * x[t]
        ys = jax.lax.dynamic_update_index_in_dim(ys, y, t, 0)
        return h, ys

    h0 = h_ref[...]
    ys0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, step, (h0, ys0))
    h_ref[...] = h
    o_ref[0] = ys.astype(o_ref.dtype)


def ssm_scan_chunked(x, dt, b_t, c_t, a, d_skip, *, chunk: int = 64,
                     dblk: int = 128, interpret: bool = True):
    """x, dt: [B,S,Di]; b_t, c_t: [B,S,N]; a: [Di,N]; d_skip: [Di].
    Returns y [B,S,Di]. S % chunk == 0, Di % dblk == 0 (ops.py pads)."""
    B, S, Di = x.shape
    N = b_t.shape[-1]
    n_chunks = S // chunk
    nd = Di // dblk

    kernel = functools.partial(_ssm_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(B, nd, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dblk), lambda bb, d, c: (bb, c, d)),
            pl.BlockSpec((1, chunk, dblk), lambda bb, d, c: (bb, c, d)),
            pl.BlockSpec((1, chunk, N), lambda bb, d, c: (bb, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda bb, d, c: (bb, c, 0)),
            pl.BlockSpec((dblk, N), lambda bb, d, c: (d, 0)),
            pl.BlockSpec((dblk,), lambda bb, d, c: (d,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dblk), lambda bb, d, c: (bb, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, Di), x.dtype),
        scratch_shapes=[pltpu.VMEM((dblk, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, b_t, c_t, a, d_skip)
