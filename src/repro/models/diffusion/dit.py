"""DiT denoiser (arXiv:2212.09748) with first-class patch-parallel support.

Tokens are row-major over the latent grid; a *patch* is a contiguous range of
token ROWS (STADI's allocatable unit, P_total = tokens_per_side rows).

``forward_patch`` computes eps for a local row range while attending over
full-image K/V assembled from (fresh local) ⊕ (stale remote) buffers — the
DistriFusion mechanism that STADI schedules. With ``buffers=None`` and the
full row range it degenerates to exact single-device inference ("Origin").
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.diffusion import DiTConfig
from repro.models import layers


# ----------------------------------------------------------------------
# patchify helpers
# ----------------------------------------------------------------------

def patchify(x, patch: int):
    """[B,H,W,C] -> [B, (H/p)*(W/p), p*p*C], row-major token grid."""
    B, H, W, C = x.shape
    hp, wp = H // patch, W // patch
    x = x.reshape(B, hp, patch, wp, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, hp * wp, patch * patch * C)


def unpatchify(tok, patch: int, hp: int, wp: int, channels: int):
    B = tok.shape[0]
    x = tok.reshape(B, hp, wp, patch, patch, channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, hp * patch, wp * patch, channels)


def pos_embed_2d(hp: int, wp: int, dim: int):
    """Fixed 2D sin-cos positional embedding [hp*wp, dim]."""
    def _1d(n, d):
        pos = jnp.arange(n, dtype=jnp.float32)
        omega = jnp.exp(-math.log(10_000.0) * jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
        out = pos[:, None] * omega[None]
        return jnp.concatenate([jnp.sin(out), jnp.cos(out)], axis=-1)   # [n, d]

    eh = _1d(hp, dim // 2)                     # [hp, dim/2]
    ew = _1d(wp, dim // 2)                     # [wp, dim/2]
    grid = jnp.concatenate([
        jnp.broadcast_to(eh[:, None], (hp, wp, dim // 2)),
        jnp.broadcast_to(ew[None, :], (hp, wp, dim // 2)),
    ], axis=-1)
    return grid.reshape(hp * wp, dim)


# ----------------------------------------------------------------------
# params
# ----------------------------------------------------------------------

def init_params(key, cfg: DiTConfig):
    if cfg.family == "mmdit":
        from repro.models.diffusion import mmdit
        return mmdit.init_params(key, cfg)
    dt = jnp.dtype(cfg.param_dtype)
    D, L = cfg.d_model, cfg.n_layers
    F = int(cfg.mlp_ratio * D)
    ks = jax.random.split(key, 8)

    def init_block(k):
        # km was never consumed pre-§17, so drawing the cross-attention
        # params from it leaves every existing draw bitwise untouched —
        # the cond_seq_len=0 degeneracy guarantee starts here
        kq, ko, k1, k2, km = jax.random.split(k, 5)
        blk = {
            "qkv": layers.dense_init(kq, (D, 3 * D), dt),
            "wo": layers.dense_init(ko, (D, D), dt, scale=1.0 / math.sqrt(2 * L * D)),
            "w1": layers.dense_init(k1, (D, F), dt),
            "w2": layers.dense_init(k2, (F, D), dt, scale=1.0 / math.sqrt(2 * L * F)),
            "mod_w": jnp.zeros((D, 6 * D), dt),          # adaLN-zero init
            "mod_b": jnp.zeros((6 * D,), dt),
        }
        if cfg.cross_attn:
            # prompt cross-attention (DESIGN.md §17): queries from the
            # hidden states, K/V projected from the cond_dim prompt tokens;
            # the out-projection follows the adaLN-zero idiom (exact zero —
            # an untrained model ignores the prompt entirely)
            kx1, kx2 = jax.random.split(km, 2)
            blk["xq"] = layers.dense_init(kx1, (D, D), dt)
            blk["xkv"] = layers.dense_init(kx2, (cfg.cond_dim, 2 * D), dt)
            blk["xo"] = jnp.zeros((D, D), dt)
        return blk

    blocks = jax.vmap(init_block)(jax.random.split(ks[0], L))
    out = {
        "patch_embed": layers.dense_init(ks[1], (cfg.token_dim, D), dt),
        "patch_bias": jnp.zeros((D,), dt),
        "t_w1": layers.dense_init(ks[2], (256, D), dt),
        "t_w2": layers.dense_init(ks[3], (D, D), dt),
        "cond_embed": layers.embed_init(ks[4], (cfg.n_classes, D), dt),
        "blocks": blocks,
        "final_mod_w": jnp.zeros((D, 2 * D), dt),
        "final_mod_b": jnp.zeros((2 * D,), dt),
        "final_proj": jnp.zeros((D, cfg.token_dim), dt),  # zero-init output
    }
    if cfg.cross_attn:
        # mean-pooled prompt tokens feed the adaLN conditioning vector
        # (ks[5] was never consumed pre-§17 — see init_block)
        out["ctx_pool"] = layers.dense_init(ks[5], (cfg.cond_dim, D), dt)
    return out


def nondegenerate_params(params, seed: int = 7):
    """Untrained params are adaLN-zero: modulation gates and the output head
    are exactly zero, so eps ignores attention (and hence the stale-KV
    buffers) entirely. Tests and benchmarks that probe staleness replace
    those zeros with small deterministic values so remote K/V genuinely
    influences the trajectory. Returns a modified copy; each replaced leaf
    keeps its dtype, so a bf16 model stays bf16."""
    def draw(key, scale, like):
        return (scale * jax.random.normal(key, like.shape)).astype(like.dtype)

    params = dict(params)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    blk = dict(params["blocks"])
    blk["mod_w"] = draw(ks[0], 0.02, blk["mod_w"])
    blk["mod_b"] = draw(ks[1], 0.02, blk["mod_b"])
    params["blocks"] = blk
    params["final_mod_w"] = draw(ks[2], 0.02, params["final_mod_w"])
    params["final_proj"] = draw(ks[3], 0.05, params["final_proj"])
    if "xo" in blk:
        # prompt cross-attention out-projection is adaLN-zero too; give it
        # a deterministic value so prompts genuinely steer the trajectory.
        # Drawn from a distinct key stream so class-conditional params stay
        # bitwise what they were pre-§17.
        blk["xo"] = draw(jax.random.PRNGKey(seed + 101), 0.05, blk["xo"])
    return params


def _stale_kernel_attend(q, k_fresh, v_fresh, k_stale, v_stale,
                         tok_start: int, blk: int):
    """Fused freshness-select attention via the Pallas stale-KV kernel
    (repro.kernels.stale_kv_attention): the per-block fresh/stale select
    happens inside the flash loop, so the stale buffer is never rewritten
    in HBM — the kernelized form of the dynamic_update_slice + attend
    reference path below. Layout [B,Nl,H,hd] <-> kernel's [B,H,Nl,hd]."""
    from repro.kernels import ops as kops
    from repro.kernels import stale_kv_attention as ska
    to = lambda a: jnp.moveaxis(a.astype(q.dtype), 2, 1)
    out = ska.stale_kv_attention_bhsd(
        to(q), to(k_fresh), to(v_fresh), to(k_stale), to(v_stale),
        tok_start, bq=blk, bk=blk, interpret=kops._interpret())
    return jnp.moveaxis(out, 1, 2)


def _stale_kernel_attend_padded(q, k_fresh, v_fresh, k_stale, v_stale,
                                tok_start, valid_tokens, n_tokens: int,
                                blk: int):
    """Padded-layout kernel dispatch (the shard_map form): traced
    tok_start/valid_tokens ride as scalar-prefetch arguments and the
    scratch tail of the stale buffer is masked in-kernel — the fused form
    of the mask-blend + dynamic_update_slice + masked-attend SPMD branch
    below."""
    from repro.kernels import ops as kops
    from repro.kernels import stale_kv_attention as ska
    to = lambda a: jnp.moveaxis(a.astype(q.dtype), 2, 1)
    out = ska.stale_kv_attention_padded_bhsd(
        to(q), to(k_fresh), to(v_fresh), to(k_stale), to(v_stale),
        tok_start, valid_tokens, n_tokens=n_tokens, bq=blk, bk=blk,
        interpret=kops._interpret())
    return jnp.moveaxis(out, 1, 2)


def _pallas_block(cfg, tok_start, Nl: int, N: int,
                  valid_tokens, enable):
    """Select the stale-KV attention body for this layout: ("off", 0) =
    reference path, else (mode, tile) with mode "static" (compile-time
    tok_start, full blend — the emulated/pipefuse interpreters) or
    "padded" (traced tok_start / valid_tokens scratch padding via
    scalar-prefetch — the shard_map executors). ``enable`` stage masking
    needs no kernel support: the disabled-block identity is applied by
    ``block_stack``'s outer ``jnp.where`` AFTER attention, so both kernel
    bodies run under it unchanged.

    Static layouts need tok_start/Nl/N to share a power-of-two tile >= 8;
    padded layouts tile by the largest power-of-two divisor of
    tokens_per_side (token starts/counts are row multiples of it, which
    keeps the traced offsets block-aligned). Every decision is recorded in
    the kernel-path counters (repro.kernels.ops) AT TRACE TIME — misses
    only when the kernel was requested."""
    if not cfg.use_pallas_attention:
        return ("off", 0)
    from repro.kernels import ops as kops
    if valid_tokens is None and isinstance(tok_start, int):
        g = (math.gcd(math.gcd(Nl, N), tok_start) if tok_start
             else math.gcd(Nl, N))
        blk = min(g & (-g), 128)         # largest power-of-two divisor
        if blk >= 8:
            kops.record_kernel_hit("stale_kv.static")
            return ("static", blk)
        kops.record_kernel_miss("tile-too-small")
        return ("off", 0)
    wp = cfg.tokens_per_side
    blk = min(wp & (-wp), 128)
    if blk < 8:
        kops.record_kernel_miss("tile-too-small")
        return ("off", 0)
    if Nl % blk or N % blk:
        kops.record_kernel_miss("padding-misaligned")
        return ("off", 0)
    kops.record_kernel_hit("stale_kv.padded")
    return ("padded", blk)


def _modulate(x, shift, scale):
    return x * (1 + scale[:, None]) + shift[:, None]


def _ln(x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def _cond_vector(params, cfg, t, cond, B, frame=None):
    t = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (B,))
    temb = layers.sinusoidal_embedding(t, 256)
    if frame is not None:
        # multi-frame conditioning (DESIGN.md §16): a sinusoidal frame-index
        # embedding is summed into the timestep features BEFORE the shared
        # MLP, so frames of one video are distinguishable without new
        # params. ``frame`` may be traced (one compile covers every frame).
        # Frame 0 — the anchor frame — passes None and is conditioned
        # exactly like an image, keeping its trajectory bitwise the image
        # path.
        fr = jnp.broadcast_to(jnp.asarray(frame, jnp.float32), (B,))
        temb = temb + layers.sinusoidal_embedding(fr, 256)
    temb = jax.nn.silu(temb.astype(params["t_w1"].dtype) @ params["t_w1"]) @ params["t_w2"]
    if cond is None:
        cemb = 0.0
    elif getattr(cond, "ndim", 0) >= 2:
        # prompt tokens (DESIGN.md §17): cond [B, L, cond_dim + 1], last
        # channel the validity mask. The masked mean of the real tokens
        # feeds the adaLN conditioning vector through ctx_pool; the CFG
        # null branch (all-zero tokens AND mask) pools to exactly 0.0 —
        # the token-space image of the NULL_COND zero embedding below.
        toks, w = cond[..., :-1], cond[..., -1:]
        pooled = jnp.sum(toks * w, axis=1) \
            / jnp.maximum(jnp.sum(w, axis=1), 1.0)
        # broadcast-multiply-reduce instead of ``pooled @ ctx_pool``: a
        # [1, Dc] x [Dc, D] matmul lowers to a gemv standalone but a gemm
        # under the serving engine's lane vmap, and the two accumulate in
        # different orders — this form is batch-shape-invariant, keeping
        # prompt lanes bitwise identical to single-request generate
        pooled = pooled.astype(params["ctx_pool"].dtype)
        cemb = jnp.sum(pooled[..., :, None] * params["ctx_pool"], axis=-2)
    else:
        # class ids >= 0 gather their embedding; the reserved NULL_COND (-1)
        # id selects the zero (unconditional) embedding — the traced-data
        # null branch classifier-free guidance evaluates (DESIGN.md §12)
        idx = jnp.broadcast_to(jnp.asarray(cond, jnp.int32), (B,))
        gathered = params["cond_embed"][jnp.clip(idx, 0)]
        cemb = jnp.where((idx >= 0)[:, None], gathered,
                         jnp.zeros_like(gathered))
    return jax.nn.silu(temb + cemb)                      # [B, D]


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def embed_patch(params, cfg: DiTConfig, x_rows, t, cond, row_start,
                frame=None):
    """Pre-block embedding of a row-patch: patchify + patch embed + 2D pos
    embed + conditioning vector. Returns (h [B,Nl,D], c [B,D])."""
    B = x_rows.shape[0]
    p = cfg.patch_size
    wp = cfg.tokens_per_side
    tok = patchify(x_rows, p)                            # [B, Nl, token_dim]
    Nl = tok.shape[1]
    D = cfg.d_model
    # pad the pos-embed table so padded tail tokens can't shift a clamped
    # dynamic_slice back over the valid region
    pe_full = jnp.concatenate([pos_embed_2d(wp, wp, D),
                               jnp.zeros((Nl, D))], axis=0)
    pe = jax.lax.dynamic_slice_in_dim(pe_full, row_start * wp, Nl, axis=0)
    h = tok @ params["patch_embed"] + params["patch_bias"] + pe.astype(tok.dtype)
    c = _cond_vector(params, cfg, t, cond, B, frame=frame)   # [B, D]
    return h, c


def block_stack(blocks, cfg: DiTConfig, h, c, tok_start,
                buffers: Optional[Tuple] = None, return_kv: bool = True,
                valid_tokens: Optional[jnp.ndarray] = None, enable=None,
                attend_fn=None, ctx_tokens: Optional[int] = None,
                prompt_ctx: Optional[Tuple] = None):
    """Run a contiguous stack of DiT blocks over hidden states ``h``.

    The ONE place the block math lives: ``forward_patch`` runs the whole
    depth through it, and the displaced patch pipeline (DESIGN.md §11) runs
    each stage's slice through it, so stage-segmented numerics can never
    drift from the monolithic forward.

    blocks:  pytree of per-block params, leading axis = block count
    buffers: None (local-only attention) or (buf_k, buf_v) each
             [n_blocks, B, N_total, H, hd] — the stale/displaced K/V context
             for these blocks; own region overwritten fresh before attending
    enable:  optional [n_blocks] bool — a disabled block is an exact
             identity (SPMD stage padding); None compiles with no masking at
             all, preserving the monolithic forward bitwise
    attend_fn: optional replacement for the buffered attention read,
             called as ``attend_fn(q, full_k, full_v, key_mask)`` with the
             freshness-blended whole-image context — the hook the
             sequence-parallel executor (DESIGN.md §13) uses to route the
             read through Ulysses all-to-all + ring hops without touching
             the block math. None preserves the dense read bitwise.
    ctx_tokens: scratch-padded layouts only (``valid_tokens`` set) — number
             of REAL context tokens in the buffers before the scratch tail.
             None = ``cfg.n_tokens`` (the pre-frames behavior); the
             multi-frame SPMD path (DESIGN.md §16) passes ``2 * n_tokens``
             for its (own frame ⊕ previous frame) concatenated context.
    prompt_ctx: prompt conditioning (DESIGN.md §17) — (tokens [B,Lc,Dc],
             key_mask [B,1,1,Lc] bool) cross-attended by every block
             between self-attention and the MLP. None (the
             cond_seq_len=0 degeneracy) traces ZERO extra ops, keeping
             the class-conditional path bitwise.
    Returns (h', kvs) with kvs [n_blocks, B, Nl, H, hd] pairs (or None).
    """
    B, Nl, D = h.shape[0], h.shape[1], cfg.d_model
    H = cfg.n_heads
    hd = D // H
    pallas_mode, pallas_blk = (
        _pallas_block(cfg, tok_start, Nl, buffers[0].shape[2],
                      valid_tokens, enable)
        if buffers is not None and attend_fn is None else ("off", 0))
    if prompt_ctx is not None and cfg.use_pallas_attention:
        # the prompt read runs the reference attend: no Pallas cross-attn
        # body yet (self-attention above still takes the kernel) — recorded
        # at trace time so kernel_stats surfaces the gap honestly
        from repro.kernels import ops as kops
        kops.record_kernel_miss("cross-attn-unsupported")
    # Padded kernel contract: real tokens = cfg.n_tokens when the buffers
    # carry the SPMD scratch tail, else the whole buffer; a local slab with
    # no valid_tokens is entirely fresh.
    if pallas_mode == "padded":
        n_real = ((ctx_tokens or cfg.n_tokens)
                  if valid_tokens is not None else buffers[0].shape[2])
        valid_arg = valid_tokens if valid_tokens is not None else Nl

    def block(x, scanned):
        if enable is not None:
            scanned, on = scanned
        if buffers is None:
            bp = scanned
        else:
            bp, bk, bv = scanned
        mod = c.astype(x.dtype) @ bp["mod_w"] + bp["mod_b"]
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        xn = _modulate(_ln(x), sh1, sc1)
        qkv = (xn @ bp["qkv"]).reshape(B, Nl, 3, H, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if buffers is None:
            att = layers.attend(q, k, v)                 # local-only (exact if full)
        elif pallas_mode == "static":
            # fused freshness-select flash kernel: no HBM buffer rewrite
            att = _stale_kernel_attend(q, k, v, bk, bv, tok_start,
                                       pallas_blk)
        elif pallas_mode == "padded":
            # shard_map form of the same fusion: traced tok_start and the
            # valid_tokens scratch mask ride into the kernel as
            # scalar-prefetch operands, so the blend + dynamic_update_slice
            # + masked attend below collapses into one flash loop.
            att = _stale_kernel_attend_padded(q, k, v, bk, bv, tok_start,
                                              valid_arg, n_real, pallas_blk)
        else:
            # SPMD path: buffers are scratch-padded to N + Nl tokens so the
            # read-modify-write below never clamps; the padded tail of the
            # local slab is blended back to the buffer's current values so it
            # cannot overwrite a neighbour's stale region, and scratch keys
            # are masked out of the softmax.
            ku, vu, key_mask = k, v, None
            if valid_tokens is not None:
                mask = (jnp.arange(Nl) < valid_tokens)[None, :, None, None]
                cur_k = jax.lax.dynamic_slice_in_dim(bk, tok_start, Nl, axis=1)
                cur_v = jax.lax.dynamic_slice_in_dim(bv, tok_start, Nl, axis=1)
                ku = jnp.where(mask, k.astype(bk.dtype), cur_k)
                vu = jnp.where(mask, v.astype(bv.dtype), cur_v)
                key_mask = (jnp.arange(bk.shape[1])
                            < (ctx_tokens or cfg.n_tokens))[None, None, None, :]
            full_k = jax.lax.dynamic_update_slice_in_dim(bk, ku.astype(bk.dtype), tok_start, axis=1)
            full_v = jax.lax.dynamic_update_slice_in_dim(bv, vu.astype(bv.dtype), tok_start, axis=1)
            if attend_fn is not None:
                att = attend_fn(q, full_k, full_v, key_mask)
            else:
                att = layers.attend(q, full_k, full_v, mask=key_mask)
        x2 = x + g1[:, None] * (att.reshape(B, Nl, D) @ bp["wo"])
        if prompt_ctx is not None:
            # prompt cross-attention (DESIGN.md §17): every latent token
            # reads the prompt sequence. The CFG null branch (all-zero
            # tokens) projects to zero V, so its read contributes exactly
            # 0.0 — NULL_COND semantics in token space.
            ck, cmask = prompt_ctx
            xq = (_ln(x2) @ bp["xq"]).reshape(B, Nl, H, hd)
            xkv = (ck.astype(x.dtype) @ bp["xkv"]).reshape(
                B, ck.shape[1], 2, H, hd)
            xatt = layers.attend(xq, xkv[:, :, 0], xkv[:, :, 1], mask=cmask)
            x2 = x2 + xatt.reshape(B, Nl, D) @ bp["xo"]
        xn = _modulate(_ln(x2), sh2, sc2)
        hmid = jax.nn.gelu(xn @ bp["w1"]) @ bp["w2"]
        x2 = x2 + g2[:, None] * hmid
        if enable is not None:           # padded stage slot: exact identity
            x2 = jnp.where(on, x2, x)
        return x2, ((k, v) if return_kv else None)

    scanned = blocks if buffers is None else (blocks,) + tuple(buffers)
    if enable is not None:
        scanned = (scanned, enable)
    return jax.lax.scan(block, h, scanned)


def final_head(params, cfg: DiTConfig, h, c, rows_tok: int):
    """adaLN-zero output head: hidden states -> eps rows."""
    mod = c.astype(h.dtype) @ params["final_mod_w"] + params["final_mod_b"]
    sh, sc = jnp.split(mod, 2, axis=-1)
    out = _modulate(_ln(h), sh, sc) @ params["final_proj"]
    return unpatchify(out, cfg.patch_size, rows_tok, cfg.tokens_per_side,
                      cfg.channels)


def forward_patch(params, cfg: DiTConfig, x_rows, t, cond,
                  row_start: int, buffers: Optional[Tuple] = None,
                  return_kv: bool = True, valid_tokens: Optional[jnp.ndarray] = None,
                  attend_fn=None, frame=None, ctx_tokens=None):
    """Denoise a row-patch with stale remote K/V.

    x_rows: [B, rows_local, W, C] latent slab (full width).
    buffers: None (local-only attention: exact when patch == full image)
             or (buf_k, buf_v) each [L, B, N_total, H, hd] — stale K/V for the
             WHOLE image; the local region is overwritten with fresh values
             before attending (DistriFusion semantics). N_total may exceed
             the image token count: the multi-frame path (DESIGN.md §16)
             passes a 2N-token (own frame ⊕ previous frame) concatenation
             and the block math is oblivious — the fresh overwrite lands in
             the first N tokens and attention reads the whole context.
    row_start: first token-row of this patch (for positional embeddings);
               may be a traced int (SPMD path with per-device offsets).
    valid_tokens: SPMD path — number of REAL local tokens (rest is padding to
               the max patch size); padded tokens never pollute the buffer.
    frame: None (image; bitwise-unchanged path) or the latent frame index —
               may be traced — summed into the conditioning vector.

    Returns (eps_rows [B, rows_local, W, C], (fresh_k, fresh_v) [L,B,Nl,H,hd]).

    An MMDiT config (``cfg.family == "mmdit"``) runs
    :func:`repro.models.diffusion.mmdit.forward_patch` instead, on the
    image-only buffers: it has no padded, sequence-sharded or frame form.
    """
    if cfg.family == "mmdit":
        if any(a is not None
               for a in (valid_tokens, attend_fn, frame, ctx_tokens)):
            raise ValueError("the mmdit family runs unpadded, unsharded "
                             "single-frame patches only")
        from repro.models.diffusion import mmdit
        return mmdit.forward_patch(params, cfg, x_rows, t, cond, row_start,
                                   buffers=buffers, return_kv=return_kv)
    rows_tok = x_rows.shape[1] // cfg.patch_size         # token rows in patch
    h, c = embed_patch(params, cfg, x_rows, t, cond, row_start, frame=frame)
    tok_start = row_start * cfg.tokens_per_side
    prompt_ctx = None
    if getattr(cond, "ndim", 0) >= 3:
        # prompt-token cond [B, L, cond_dim + 1] (DESIGN.md §17): split off
        # the trailing validity-mask channel into the cross-attention key
        # mask. cond.ndim is static under jit, so the class-conditional
        # trace (int cond) carries zero extra ops.
        if not cfg.cross_attn:
            raise ValueError(
                "prompt-token cond needs DiTConfig.cross_attn=True "
                "(see DiTConfig.text_conditioned())")
        ck = cond[..., :-1]
        cmask = (cond[..., -1] > 0.5)[:, None, None, :]
        prompt_ctx = (ck, cmask)
    h, kvs = block_stack(params["blocks"], cfg, h, c, tok_start,
                         buffers=buffers, return_kv=return_kv,
                         valid_tokens=valid_tokens, attend_fn=attend_fn,
                         ctx_tokens=ctx_tokens, prompt_ctx=prompt_ctx)
    eps = final_head(params, cfg, h, c, rows_tok)
    return eps, kvs


def forward(params, cfg: DiTConfig, x, t, cond=None, frame=None):
    """Full-image denoiser: [B,H,W,C] -> eps [B,H,W,C] (the Origin path)."""
    eps, _ = forward_patch(params, cfg, x, t, cond, 0, buffers=None,
                           return_kv=False, frame=frame)
    return eps


def null_like(cond) -> jnp.ndarray:
    """The unconditional branch for a cond of either kind: all-zero prompt
    tokens (empty sequence — mask channel included) for token conds
    [B, L, Dc+1], the reserved NULL_COND id for class conds [B]."""
    from repro.core.guidance import NULL_COND
    cond = jnp.asarray(cond)
    if cond.ndim >= 2:
        return jnp.zeros_like(cond)
    return jnp.full_like(cond.astype(jnp.int32), NULL_COND)


def guidance_conds(cond) -> jnp.ndarray:
    """Branch-stacked conds: row 0 = conditional, row 1 = the unconditional
    branch. [2, B] class ids for class conds; [2, B, L, Dc+1] for prompt
    tokens (row 1 the all-zero empty sequence — see text_encoder.null_cond)."""
    from repro.core.guidance import NULL_COND
    cond = jnp.asarray(cond)
    if cond.ndim >= 2:
        return jnp.stack([cond, jnp.zeros_like(cond)])
    cond = cond.astype(jnp.int32)
    return jnp.stack([cond, jnp.full_like(cond, NULL_COND)])


def forward_cfg(params, cfg: DiTConfig, x, t, cond, scale):
    """Fused-batch classifier-free guidance reference (DESIGN.md §12): one
    branch-vmapped dispatch evaluates the conditional and unconditional
    forwards, combined as ``eps_u + scale * (eps_c - eps_u)``. This is the
    CFG analogue of :func:`forward` ("Origin"): exact, single-device, and
    the bitwise reference every guided schedule path is tested against."""
    from repro.core.sampler import cfg_combine
    eps2 = jax.vmap(lambda c: forward(params, cfg, x, t, c))(
        guidance_conds(cond))
    return cfg_combine(eps2[0], eps2[1], scale)


def buffer_shape(cfg: DiTConfig, batch: int):
    D, H = cfg.d_model, cfg.n_heads
    return (cfg.n_layers, batch, cfg.n_tokens, H, D // H)


def init_buffers(cfg: DiTConfig, batch: int, dtype=None):
    dt = dtype or jnp.dtype(cfg.dtype)
    shape = buffer_shape(cfg, batch)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)
