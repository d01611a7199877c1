"""Sharding rule tests on a 512-placeholder mesh structure (no device state:
uses Mesh of abstract shape via jax.sharding.AbstractMesh)."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.sharding import specs as sh

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs_for(arch):
    cfg = get_config(arch).replace(param_dtype="bfloat16", dtype="bfloat16")
    from repro.models import build_model
    params_s = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return cfg, params_s, sh.param_specs(params_s, MESH, cfg)


def test_dense_rules_llama():
    cfg, params_s, specs = _specs_for("llama3-405b")
    b = specs["blocks"]
    assert b["attn"]["wq"] == P(None, "data", "model")      # 128 heads: sharded
    # kv heads (8) don't divide model axis (16): replicated output dim
    assert b["attn"]["wk"] == P(None, "data", None)
    assert b["attn"]["wv"] == P(None, "data", None)
    assert b["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["embed"] == P("model", "data")
    assert specs["ln_f"] == P()


def test_gemma_small_heads_fully_replicated_attention():
    cfg, params_s, specs = _specs_for("gemma-2b")
    b = specs["blocks"]
    # 8 q heads and 1 kv head on a 16-wide axis: head-dim must never split
    assert b["attn"]["wq"] == P(None, "data", None)
    assert b["attn"]["wk"] == P(None, "data", None)
    assert b["attn"]["wo"] == P(None, None, "data")
    # MLP stays tensor-parallel (16384 % 16 == 0)
    assert b["mlp"]["w_up"] == P(None, "data", "model")


def test_moe_expert_parallel():
    cfg, params_s, specs = _specs_for("olmoe-1b-7b")
    e = specs["blocks"]["moe"]["experts"]
    assert e["w_gate"] == P(None, "model", "data", None)    # experts on model
    assert e["w_down"] == P(None, "model", None, "data")
    assert specs["blocks"]["moe"]["router"] == P(None, "data", None)


def test_guard_drops_nondivisible():
    # vocab 50304 not divisible by 16? 50304/16 = 3144 ok; check odd dim
    spec = sh._guard(("model", "data"), (10, 32), MESH)
    assert spec == P(None, "data")                          # 10 % 16 != 0


def test_batch_specs_multi_pod():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), np.int32)}
    s = sh.batch_specs(batch, MESH3)
    assert s["tokens"] == P(("pod", "data"), None)
    tiny = {"tokens": jax.ShapeDtypeStruct((1, 64), np.int32)}
    s = sh.batch_specs(tiny, MESH3)
    assert s["tokens"] == P(None, None)                     # batch 1: replicated


def test_cache_specs_kv_vs_seq():
    # kv=16 divides model: shard kv heads
    c = {"k": jax.ShapeDtypeStruct((16, 128, 32768, 16, 64), np.float32)}
    assert sh.cache_specs(c, MESH)["k"] == P(None, "data", None, "model", None)
    # kv=8 doesn't: shard sequence instead
    c = {"k": jax.ShapeDtypeStruct((126, 128, 32768, 8, 128), np.float32)}
    assert sh.cache_specs(c, MESH)["k"] == P(None, "data", "model", None, None)


def test_xlstm_heterogeneous_blocks_get_specs():
    cfg, params_s, specs = _specs_for("xlstm-125m")
    assert isinstance(specs["blocks"], list) and len(specs["blocks"]) == 12
    # mLSTM block (idx 0) and sLSTM block (idx 3) both resolve
    assert specs["blocks"][0]["w_up"] == P("data", "model")
    assert specs["blocks"][3]["w_x"] == P("data", "model")
