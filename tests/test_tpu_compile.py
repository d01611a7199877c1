"""Compile the main path's kernels at sdxl-dit widths for a TPU v5e that is
described, not attached: the TPU compiler refuses what interpret mode lets
through (unaligned slices, too much VMEM, programs that do not fit HBM).

Nothing runs, so these tests say nothing about results or times. The v5e
topology is described inside a module fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
file. Keep every such compile in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config

# v5e has 16 GiB of HBM per chip
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    """The v5e:2x2 topology, with JAX's persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without that chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:   # else libtpu logs to /tmp
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return get_config("sdxl-dit").replace(use_pallas_attention=True)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _bytes(m) -> int:
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_static_stale_kv_kernel_tile_128(one_chip, cfg):
    """The emulated path's kernel: the second worker's slab of the
    two-speed plan (26 patch rows at token 2432), tile 128, f32 activations
    as the bf16-weight model produces them."""
    from repro.kernels import stale_kv_attention as ska
    H, hd, N = cfg.n_heads, cfg.d_model // cfg.n_heads, cfg.n_tokens
    Nl, tok_start = 26 * cfg.tokens_per_side, 38 * cfg.tokens_per_side
    loc = jax.ShapeDtypeStruct((1, H, Nl, hd), jnp.float32, sharding=one_chip)
    ctx = jax.ShapeDtypeStruct((1, H, N, hd), jnp.float32, sharding=one_chip)
    fn = functools.partial(ska.stale_kv_attention_bhsd, tok_start=tok_start,
                           bq=128, bk=128, interpret=False)
    compiled = _compile(fn, loc, loc, loc, ctx, ctx)
    assert "tpu_custom_call" in compiled.as_text()


def test_padded_stale_kv_kernel_tile_64(one_chip, cfg):
    """The shard_map path's kernel: slabs padded to the four-worker plan's
    largest patch (17 rows), traced offsets, tile 64."""
    from repro.kernels import stale_kv_attention as ska
    H, hd, N = cfg.n_heads, cfg.d_model // cfg.n_heads, cfg.n_tokens
    Nlm = 17 * cfg.tokens_per_side
    loc = jax.ShapeDtypeStruct((1, H, Nlm, hd), jnp.float32,
                               sharding=one_chip)
    ctx = jax.ShapeDtypeStruct((1, H, N + Nlm, hd), jnp.float32,
                               sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    fn = functools.partial(ska.stale_kv_attention_padded_bhsd, n_tokens=N,
                           bq=64, bk=64, interpret=False)
    compiled = _compile(fn, loc, loc, loc, ctx, ctx, scalar, scalar)
    assert "tpu_custom_call" in compiled.as_text()


def test_cfg_epilogue(one_chip, cfg):
    """The fused CFG epilogue over one sdxl-dit latent (128x128x4 = 512
    rows of 128 lanes), with a traced guidance scale."""
    from repro.kernels import cfg_epilogue
    rows = cfg.latent_size * cfg.latent_size * cfg.channels // 128
    eps = jax.ShapeDtypeStruct((rows, 128), jnp.float32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    fn = functools.partial(cfg_epilogue.cfg_epilogue_2d, interpret=False)
    compiled = _compile(fn, eps, eps, scale)
    assert "tpu_custom_call" in compiled.as_text()


def test_forward_patch_with_buffers(one_chip, cfg, monkeypatch):
    """A whole 28-layer sdxl-dit ``forward_patch`` over the first worker's
    slab (38 patch rows) with whole-image stale K/V buffers: the program
    the emulated path runs once per worker and step. ``ops._interpret``
    asks ``jax.default_backend()``, which is the CPU here; it is forced off
    so the program holds the compiled kernel and not the interpreter."""
    from repro.kernels import ops
    from repro.models.diffusion import dit
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: dit.init_params(k, cfg), jax.random.PRNGKey(0)))
    buf = jax.ShapeDtypeStruct(dit.buffer_shape(cfg, 1), jnp.float32,
                               sharding=one_chip)
    x_rows = jax.ShapeDtypeStruct(
        (1, 38 * cfg.patch_size, cfg.latent_size, cfg.channels), jnp.float32,
        sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    cond = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    before = ops.kernel_stats_snapshot()

    def step(params, x_rows, t, cond, buf_k, buf_v):
        return dit.forward_patch(params, cfg, x_rows, t, cond, 0,
                                 buffers=(buf_k, buf_v))

    compiled = _compile(step, params, x_rows, t, cond, buf, buf)
    stats = ops.kernel_stats_delta(before, ops.kernel_stats_snapshot())
    assert stats["hits"].get("stale_kv.static", 0) > 0 and not stats["misses"]
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled.memory_analysis()) < V5E_HBM_BYTES
