"""Useful floating-point operations of MMDiT sampling, counted from shapes
with the conventions of ``bench/flops.py``: two operations per
multiply-accumulate, no padding. A patch evaluation of ``nq`` image query
tokens runs the image stream's projections and MLP on its own tokens, the
whole context stream (``cond_seq_len`` tokens) every time, and joint
attention of its queries against the ``n`` image keys (stale ones
included) plus the context keys. The last block's context stream computes
only keys and values: its queries' output is discarded, so neither they
nor their attention count.
"""
from __future__ import annotations

from bench import flops


def eval_flops(sizes: dict, nq: int) -> dict:
    """One denoiser evaluation of ``nq`` image query tokens of one image,
    split into the image stream's projections and MLP, the context stream
    (its embedder, projections and MLP), joint attention, and the rest
    (patch embed, output head, timestep and pooled-text MLPs, adaLN
    modulation)."""
    D, L = sizes["d_model"], sizes["n_layers"]
    F = int(sizes["mlp_ratio"] * D)
    n = (sizes["latent_size"] // sizes["patch_size"]) ** 2
    Lc, Dc, P = sizes["cond_seq_len"], sizes["cond_dim"], sizes["pooled_dim"]
    tok = sizes["channels"] * sizes["patch_size"] ** 2
    block = 3 * D * D + D * D + 2 * D * F       # q/k/v, out, MLP
    image = 2 * nq * L * block
    context = 2 * Lc * ((L - 1) * block + 2 * D * D) + 2 * Lc * Dc * D
    attention = 2 * 2 * (n + Lc) * D * (L * nq + (L - 1) * Lc)
    other = (2 * nq * tok * D * 2               # patch embed + output head
             + 2 * (256 * D + D * D)            # timestep MLP
             + 2 * (P * D + D * D)              # pooled-text MLP
             + 2 * D * 6 * D * (2 * L - 1)      # adaLN-zero, both streams
             + 2 * D * 2 * D * 2)               # last context adaLN, head
    return {"image": image, "context": context, "attention": attention,
            "other": other}


def image_flops(sizes: dict, m_warmup: int, m_base: int, ratios,
                rows) -> int:
    """One image under a STADI plan: ``m_warmup`` full-image evaluations,
    then each worker's ``(m_base - m_warmup) / ratio`` evaluations of its
    ``rows`` token rows, each with the whole context stream."""
    wp = sizes["latent_size"] // sizes["patch_size"]
    out = m_warmup * flops.total(eval_flops(sizes, wp * wp))
    for r, nrows in zip(ratios, rows):
        if r and nrows:
            out += (m_base - m_warmup) // r * flops.total(
                eval_flops(sizes, nrows * wp))
    return out


def mfu_percent(run):
    """Useful FLOPs of the images the window completed over the window's
    seconds times the chip's bf16 peak, in %. None without a peak (a run
    off the chip)."""
    if run.peak is None:
        return None
    m_base, m_warmup, ratios, rows = run.driver.plan()
    per_image = image_flops(run.sizes, m_warmup, m_base, ratios, rows)
    done = run.driver.images_in_window()
    return 100.0 * done * per_image / (run.driver.window_s()
                                       * run.peak["bf16_flops_per_s"])
