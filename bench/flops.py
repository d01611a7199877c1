"""Useful floating-point operations of DiT sampling, counted from shapes.

Two operations per multiply-accumulate. A patch evaluation of ``nq`` query
tokens runs every projection and MLP on its own tokens and attends them
against the whole image's ``n`` keys (stale K/V included). Padded lanes and
padded rows are never counted, so the count is the same whatever program
or kernel computes it.
"""
from __future__ import annotations


def eval_flops(sizes: dict, nq: int) -> dict:
    """One denoiser evaluation of ``nq`` query tokens of one image, split
    into block projections and MLP, attention, and the rest (patch embed,
    output head, timestep MLP and adaLN modulation)."""
    D, L = sizes["d_model"], sizes["n_layers"]
    F = int(sizes["mlp_ratio"] * D)
    n = (sizes["latent_size"] // sizes["patch_size"]) ** 2
    tok = sizes["channels"] * sizes["patch_size"] ** 2
    proj_mlp = 2 * nq * L * (3 * D * D + D * D + 2 * D * F)
    attention = 2 * L * 2 * nq * n * D          # q.k^T and p.v
    other = (2 * nq * tok * D * 2               # patch embed + output head
             + 2 * (256 * D + D * D)            # timestep MLP
             + 2 * D * 6 * D * L + 2 * D * 2 * D)   # adaLN modulation
    return {"proj_mlp": proj_mlp, "attention": attention, "other": other}


def total(parts: dict) -> int:
    return sum(parts.values())


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


def image_flops(sizes: dict, m_warmup: int, m_base: int, ratios, rows) -> int:
    """One image under a STADI plan: ``m_warmup`` full-image evaluations,
    then each worker's ``(m_base - m_warmup) / ratio`` evaluations of its
    ``rows`` token rows."""
    wp = sizes["latent_size"] // sizes["patch_size"]
    out = m_warmup * total(eval_flops(sizes, wp * wp))
    for r, nrows in zip(ratios, rows):
        if r and nrows:
            out += (m_base - m_warmup) // r * total(eval_flops(sizes, nrows * wp))
    return out
