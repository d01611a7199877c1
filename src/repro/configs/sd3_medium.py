"""Stable Diffusion 3 Medium's MMDiT (arXiv:2403.03206), at its published
widths and depth: ``SD3Transformer2DModel`` of
stabilityai/stable-diffusion-3-medium-diffusers (transformer/config.json).

A 128x128x16 latent (1024 px) in 2x2 patches gives 4096 image tokens; the
prompt is 77 CLIP tokens (CLIP-L ⊕ CLIP-G, 2048 channels zero-padded to
4096) followed by 256 T5-XXL tokens, 333 x 4096, plus a 2048-channel pooled
CLIP vector. 24 dual-stream joint blocks of 24 heads of 64 (d_model 1536),
the last one context_pre_only; rectified-flow sampling with shift 3.0.
"""
from repro.configs.diffusion import DiTConfig

CONFIG = DiTConfig(
    arch_id="sd3-medium",
    family="mmdit",
    source="arXiv:2403.03206 (SD3); stable-diffusion-3-medium-diffusers",
    latent_size=128,
    channels=16,
    patch_size=2,
    n_layers=24,
    d_model=1536,
    n_heads=24,
    mlp_ratio=4.0,
    cond_seq_len=333,
    cond_dim=4096,
    pooled_dim=2048,
    pos_embed_max_size=192,
    flow_shift=3.0,
    n_classes=1000,
    param_dtype="bfloat16",
    dtype="bfloat16",
)
