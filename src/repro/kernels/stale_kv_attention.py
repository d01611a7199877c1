"""Stale-KV patch attention — the DistriFusion/STADI hot loop as a TPU kernel.

Q comes from the LOCAL fresh patch (Nl tokens); keys/values for the whole
image come from the stale buffer EXCEPT the local region, which must use the
fresh K/V computed this step. The naive formulation first materializes
  full_kv = dynamic_update_slice(stale, fresh)        (2x KV HBM traffic)
then runs attention. This kernel fuses the region-select into the flash
loop: for kv-block j it loads BOTH the stale block and the (clamped) fresh
block and selects per-block — tok_start and Nl are multiples of the block
size, so every block is purely fresh or purely stale and the select is a
no-op branch on the MXU path. Bidirectional (diffusion attention: no mask).

TPU adaptation note (DESIGN.md §2): DistriFusion implements this as a CUDA
attention call over a buffer patched by an async NCCL broadcast; on TPU the
freshness-select moves INTO the kernel so the buffer is never rewritten in
HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _stale_kernel(qf_ref, kf_ref, vf_ref, ks_ref, vs_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, bq, bk, nk,
                  start_block, n_local_blocks):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = qf_ref[0, 0].astype(jnp.float32)
    is_local = (ik >= start_block) & (ik < start_block + n_local_blocks)
    k = jnp.where(is_local, kf_ref[0, 0], ks_ref[0, 0]).astype(jnp.float32)
    v = jnp.where(is_local, vf_ref[0, 0], vs_ref[0, 0]).astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    _online_softmax_update(s, v, acc_ref, m_ref, l_ref)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _online_softmax_update(s, v, acc_ref, m_ref, l_ref):
    """One flash-attention block update of the (acc, m, l) scratch state."""
    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_cur


def stale_kv_attention_bhsd(q_fresh, k_fresh, v_fresh, k_stale, v_stale,
                            tok_start: int, *, scale=None,
                            bq: int = 128, bk: int = 128,
                            interpret: bool = True):
    """q_fresh/k_fresh/v_fresh: [B,H,Nl,hd] (local patch);
    k_stale/v_stale: [B,H,N,hd] (full-image stale buffer);
    tok_start: local patch offset in the token stream (multiple of bk; Nl too).
    Returns [B,H,Nl,hd].
    """
    B, H, Nl, hd = q_fresh.shape
    N = k_stale.shape[2]
    assert tok_start % bk == 0 and Nl % bk == 0 and N % bk == 0, \
        (tok_start, Nl, N, bk)
    nq, nk = Nl // bq, N // bk
    start_block = tok_start // bk
    n_local = Nl // bk
    scale = scale if scale is not None else hd ** -0.5

    def fresh_kv_index(b, h, i, j):
        # clamp j into the local block range so OOB loads read a valid block
        jj = jnp.clip(j - start_block, 0, n_local - 1)
        return (b, h, jj, 0)

    kernel = functools.partial(_stale_kernel, scale=scale, bq=bq, bk=bk,
                               nk=nk, start_block=start_block,
                               n_local_blocks=n_local)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), fresh_kv_index),
            pl.BlockSpec((1, 1, bk, hd), fresh_kv_index),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Nl, hd), q_fresh.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_fresh, k_fresh, v_fresh, k_stale, v_stale)


# ----------------------------------------------------------------------
# padded layout: traced offsets via scalar prefetch (the shard_map form)
# ----------------------------------------------------------------------

def _padded_kernel(scal_ref, qf_ref, kf_ref, vf_ref, ks_ref, vs_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, bq, bk, nk, n_tokens):
    """Stale-KV flash body with a PER-TOKEN freshness select and an
    in-kernel key mask. ``scal_ref`` holds the traced layout scalars
    ``[tok_start, valid_tokens]``: context token t reads the fresh block
    when ``tok_start <= t < tok_start + valid_tokens`` and the stale
    buffer otherwise; tokens ``>= n_tokens`` (scratch padding) are masked
    out of the softmax. This is exactly the mask-blend +
    dynamic_update_slice + masked-attend reference path of
    ``dit.block_stack``'s SPMD branch, fused so the buffer is never
    rewritten in HBM."""
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    tok_start = scal_ref[0]
    valid = scal_ref[1]
    q = qf_ref[0, 0].astype(jnp.float32)
    toks = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)[:, 0]
    rel = toks - tok_start
    is_fresh = (rel >= 0) & (rel < valid)
    k = jnp.where(is_fresh[:, None], kf_ref[0, 0].astype(jnp.float32),
                  ks_ref[0, 0].astype(jnp.float32))
    v = jnp.where(is_fresh[:, None], vf_ref[0, 0].astype(jnp.float32),
                  vs_ref[0, 0].astype(jnp.float32))
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    s = jnp.where((toks < n_tokens)[None, :], s, NEG_INF)
    _online_softmax_update(s, v, acc_ref, m_ref, l_ref)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def stale_kv_attention_padded_bhsd(q_fresh, k_fresh, v_fresh, k_stale,
                                   v_stale, tok_start, valid_tokens, *,
                                   n_tokens: int, scale=None, bq: int = 8,
                                   bk: int = 8, interpret: bool = True):
    """Padded-layout stale-KV attention for the shard_map executors.

    q_fresh/k_fresh/v_fresh: [B,H,Nl_max,hd] — the local slab padded to the
    MAX patch size; rows >= valid_tokens are scratch (their outputs are
    computed and discarded by the caller, exactly like the reference path).
    k_stale/v_stale: [B,H,Npad,hd] — the whole-image stale buffer,
    scratch-padded to n_tokens + Nl_max.
    tok_start/valid_tokens: TRACED scalars (per-device offsets under
    shard_map), carried as a scalar-prefetch argument so the fresh-block
    index map can still be block-aligned. CONTRACT: tok_start is a multiple
    of bk at runtime (token starts are row_start * tokens_per_side and bk
    divides tokens_per_side — asserted by the caller's tile choice, not
    checkable on a traced value).
    n_tokens: static count of REAL context tokens (key mask threshold).
    Returns [B,H,Nl_max,hd].
    """
    B, H, Nlm, hd = q_fresh.shape
    Np = k_stale.shape[2]
    assert Nlm % bq == 0 and Nlm % bk == 0 and Np % bk == 0, (Nlm, Np, bq, bk)
    nq, nk = Nlm // bq, Np // bk
    nlb = Nlm // bk
    scale = scale if scale is not None else hd ** -0.5

    def fresh_ix(b, h, i, j, scal):
        # clamp j into the local block range so OOB loads read a valid block
        jj = jnp.clip(j - scal[0] // bk, 0, nlb - 1)
        return (b, h, jj, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j, s: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), fresh_ix),
            pl.BlockSpec((1, 1, bk, hd), fresh_ix),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, s: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, s: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j, s: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
    )
    kernel = functools.partial(_padded_kernel, scale=scale, bq=bq, bk=bk,
                               nk=nk, n_tokens=n_tokens)
    scal = jnp.stack([jnp.asarray(tok_start, jnp.int32),
                      jnp.asarray(valid_tokens, jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Nlm, hd), q_fresh.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(scal, q_fresh, k_fresh, v_fresh, k_stale, v_stale)


# ----------------------------------------------------------------------
# guided body: branch-stacked CFG with in-kernel uncond freshness masking
# ----------------------------------------------------------------------

def _guided_kernel(scal_ref, qf_ref, kf_ref, vf_ref, ks_ref, vs_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, bq, bk, nk, n_tokens):
    """Branch-dimensioned padded body (grid axis 0 = guidance branch).
    Branch 0 (conditional) blends its fresh K/V like ``_padded_kernel``;
    branch 1 (unconditional) blends only when ``scal[2]`` (uncond_fresh)
    is 1 — with 0 it attends the pure-stale buffer, the in-kernel form of
    interleaved guidance's "don't recompute the uncond slice" reuse
    (DESIGN.md §12): the caller can skip the uncond blend/publish work
    entirely and the branch still reads a consistent context."""
    g = pl.program_id(0)
    ik = pl.program_id(4)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    tok_start = scal_ref[0]
    valid = jnp.where(g == 0, scal_ref[1], scal_ref[1] * scal_ref[2])
    q = qf_ref[0, 0, 0].astype(jnp.float32)
    toks = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)[:, 0]
    rel = toks - tok_start
    is_fresh = (rel >= 0) & (rel < valid)
    k = jnp.where(is_fresh[:, None], kf_ref[0, 0, 0].astype(jnp.float32),
                  ks_ref[0, 0, 0].astype(jnp.float32))
    v = jnp.where(is_fresh[:, None], vf_ref[0, 0, 0].astype(jnp.float32),
                  vs_ref[0, 0, 0].astype(jnp.float32))
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    s = jnp.where((toks < n_tokens)[None, :], s, NEG_INF)
    _online_softmax_update(s, v, acc_ref, m_ref, l_ref)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def stale_kv_attention_guided_bhsd(q_fresh, k_fresh, v_fresh, k_stale,
                                   v_stale, tok_start, valid_tokens,
                                   uncond_fresh, *, n_tokens: int,
                                   scale=None, bq: int = 8, bk: int = 8,
                                   interpret: bool = True):
    """Branch-stacked guided stale-KV attention: one kernel launch for both
    CFG branches instead of a vmapped pair.

    All tensor operands carry a leading branch axis of 2 (0 = conditional,
    1 = unconditional): q/k/v fresh [2,B,H,Nl_max,hd], stale
    [2,B,H,Npad,hd]. ``uncond_fresh`` (traced 0/1) gates the uncond
    branch's freshness blend in-kernel — 0 reproduces the interleaved-
    guidance reuse interval where the uncond forward was skipped and its
    published buffer must be read as-is. Other scalars as
    :func:`stale_kv_attention_padded_bhsd`. Returns [2,B,H,Nl_max,hd].
    """
    G, B, H, Nlm, hd = q_fresh.shape
    assert G == 2, G
    Np = k_stale.shape[3]
    assert Nlm % bq == 0 and Nlm % bk == 0 and Np % bk == 0, (Nlm, Np, bq, bk)
    nq, nk = Nlm // bq, Np // bk
    nlb = Nlm // bk
    scale = scale if scale is not None else hd ** -0.5

    def fresh_ix(g, b, h, i, j, scal):
        jj = jnp.clip(j - scal[0] // bk, 0, nlb - 1)
        return (g, b, h, jj, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd),
                         lambda g, b, h, i, j, s: (g, b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bk, hd), fresh_ix),
            pl.BlockSpec((1, 1, 1, bk, hd), fresh_ix),
            pl.BlockSpec((1, 1, 1, bk, hd),
                         lambda g, b, h, i, j, s: (g, b, h, j, 0)),
            pl.BlockSpec((1, 1, 1, bk, hd),
                         lambda g, b, h, i, j, s: (g, b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bq, hd),
                               lambda g, b, h, i, j, s: (g, b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
    )
    kernel = functools.partial(_guided_kernel, scale=scale, bq=bq, bk=bk,
                               nk=nk, n_tokens=n_tokens)
    scal = jnp.stack([jnp.asarray(tok_start, jnp.int32),
                      jnp.asarray(valid_tokens, jnp.int32),
                      jnp.asarray(uncond_fresh, jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, B, H, Nlm, hd), q_fresh.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(scal, q_fresh, k_fresh, v_fresh, k_stale, v_stale)


# ----------------------------------------------------------------------
# per-hop LSE body: the flash-style ring attention segment attend
# ----------------------------------------------------------------------

def _lse_kernel(scal_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, bk, nk):
    """Masked flash attention over ONE ring segment, returning both the
    normalized partial output and its log-sum-exp so the caller can merge
    segments across ring hops without ever materializing the assembled
    context (DESIGN.md §15): final = sum_s o_s * exp(lse_s - M) /
    sum_s exp(lse_s - M). ``scal[0]`` is the traced number of valid
    (unmasked) leading keys in this segment."""
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = scal_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    toks = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)[:, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    s = jnp.where((toks < valid)[None, :], s, NEG_INF)
    _online_softmax_update(s, v, acc_ref, m_ref, l_ref)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        # a fully-masked segment keeps m at NEG_INF => lse ~ NEG_INF and
        # the caller's exp(lse - M) weight underflows to exactly 0
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def lse_attention_bhsd(q, k, v, valid_len, *, scale=None, bq: int = 8,
                       bk: int = 8, interpret: bool = True):
    """q: [B,H,S,hd]; k/v: [B,H,T,hd]; valid_len: traced count of real
    leading keys (rest masked). Returns (out [B,H,S,hd], lse [B,H,S]) in
    fp32 lse — the per-hop partial of flash-style ring attention.
    """
    B, H, S, hd = q.shape
    T = k.shape[2]
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    nq, nk = S // bq, T // bk
    scale = scale if scale is not None else hd ** -0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j, s: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, s: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, s: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j, s: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j, s: (b, h, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
    )
    kernel = functools.partial(_lse_kernel, scale=scale, bk=bk, nk=nk)
    scal = jnp.asarray(valid_len, jnp.int32)[None]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, H, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(scal, q, k, v)
