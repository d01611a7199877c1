"""Diffusion-model (denoiser) configs for the STADI wing."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    arch_id: str = "tiny-dit"
    family: str = "dit"
    source: str = "arXiv:2212.09748 (DiT)"
    # latent grid
    latent_size: int = 32            # H = W (latent resolution)
    channels: int = 4                # latent channels
    patch_size: int = 2              # patchify
    # transformer
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    mlp_ratio: float = 4.0
    cond_dim: int = 64               # class/prompt conditioning embedding dim
    n_classes: int = 16              # synthetic conditioning vocabulary
    # prompt conditioning (DESIGN.md §17): cond_seq_len > 0 declares the
    # workload prompt-conditioned — the frozen text encoder
    # (repro.models.text_encoder) emits [B, L <= cond_seq_len, cond_dim]
    # prompt tokens (plus a trailing validity-mask channel) and cross_attn
    # interleaves a prompt cross-attention read into every DiT block.
    # Defaults (0 / False) keep the class-conditional path BITWISE: no new
    # params are drawn and no new ops are traced.
    cond_seq_len: int = 0
    cross_attn: bool = False
    # MMDiT (family "mmdit", DESIGN.md §18; SD3, arXiv:2403.03206): the
    # prompt joins self-attention as a second token stream of cond_seq_len
    # tokens of cond_dim channels, and a pooled prompt vector of pooled_dim
    # channels joins the timestep in the adaLN conditioning. The position
    # table is a pos_embed_max_size square cropped to its centre, and
    # flow_shift is the rectified-flow sampler's timestep shift.
    pooled_dim: int = 0
    pos_embed_max_size: int = 0
    flow_shift: float = 1.0
    # numerics
    param_dtype: str = "float32"
    dtype: str = "float32"
    # run the Pallas stale-KV attention kernel (repro.kernels.
    # stale_kv_attention) for buffered patch attention instead of the
    # reference rewrite-then-attend path; interpret mode off-TPU. Falls
    # back to the reference when the patch layout misses the kernel's tile
    # constraints (traced offsets, SPMD padding, indivisible block sizes).
    use_pallas_attention: bool = False

    @property
    def tokens_per_side(self) -> int:
        return self.latent_size // self.patch_size

    @property
    def n_tokens(self) -> int:
        return self.tokens_per_side ** 2

    @property
    def token_dim(self) -> int:
        return self.channels * self.patch_size ** 2

    def replace(self, **kw) -> "DiTConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "DiTConfig":
        return self.replace(n_layers=2, d_model=128, n_heads=4, latent_size=16)

    def text_conditioned(self, cond_seq_len: int = 32) -> "DiTConfig":
        """Prompt-conditioned variant (DESIGN.md §17): enables the per-block
        prompt cross-attention and declares the max prompt-token bucket."""
        return self.replace(cond_seq_len=cond_seq_len, cross_attn=True)


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    arch_id: str = "tiny-unet"
    family: str = "unet"
    source: str = "arXiv:2307.01952 (SDXL; scaled-down)"
    image_size: int = 32
    channels: int = 3
    base_width: int = 32
    channel_mults: tuple = (1, 2, 2)
    attn_levels: tuple = (2,)        # attention at these downsample levels
    n_res_blocks: int = 1
    cond_dim: int = 64
    n_classes: int = 16
    param_dtype: str = "float32"
    dtype: str = "float32"

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)
