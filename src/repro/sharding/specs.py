"""Logical-axis sharding rules -> PartitionSpecs.

Production mesh axes (launch/mesh.py): ``(data=16, model=16)`` single-pod,
``(pod=2, data=16, model=16)`` multi-pod. Logical mapping (DESIGN.md §5):

  batch                  -> ('pod','data') when divisible, else replicated
  heads / d_ff / experts / vocab-partition dims -> 'model'  (tensor/expert par.)
  d_model on weight matrices                    -> 'data'   (FSDP-style, so
                                                  405B-class weights fit)
  layer-stack dim / norms / biases / small dims -> replicated
  KV-cache: kv-head dim over 'model' if divisible, else sequence dim

Rules key off parameter *path names* (the naming conventions of
repro.models.*) + ndim, so new modules compose for free.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def _div(n: int, mesh: Mesh, axis) -> bool:
    """Is dim n evenly divisible by the (possibly tuple) mesh axis?"""
    if axis is None:
        return True
    sz = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        sz *= _axis_size(mesh, a)
    return sz <= n and n % sz == 0


def _guard(spec: Sequence, shape, mesh: Mesh) -> P:
    """Drop axis assignments that don't divide the dim (GSPMD could pad, but
    even sharding keeps memory analysis honest)."""
    out = []
    for dim, ax in zip(shape, spec):
        out.append(ax if _div(dim, mesh, ax) else None)
    return P(*out)


# per-leaf-name rules: rightmost dims (left-padded with None for stacking)
_RULES = {
    # embeddings / unembedding
    "embed": ("model", "data"),
    "head": ("data", "model"),
    "cond_embed": (None, "data"),
    "meta": (None, "data"),
    # attention
    "wq": ("data", "model"),
    "wk": ("data", "model"),
    "wv": ("data", "model"),
    "wo": ("model", "data"),
    "qkv": ("data", "model"),
    # dense mlp
    "w_gate": ("data", "model"),
    "w_up": ("data", "model"),
    "w_down": ("model", "data"),
    "w1": ("data", "model"),
    "w2": ("model", "data"),
    # moe
    "router": ("data", None),
    # xlstm / mamba
    "w_in": ("data", "model"),
    "w_x": ("data", "model"),
    "r_h": ("model", None, None),
    "conv": (None, "model"),
    "w_bc": ("model", None),
    "w_dt1": ("model", None),
    "w_dt2": (None, "model"),
    "w_if": ("model", None),
    # dit
    "patch_embed": (None, "data"),
    "mod_w": ("data", "model"),
    "t_w1": (None, "data"),
    "t_w2": ("data", None),
    "final_proj": ("data", None),
}

# moe expert stacks: [L, E, D, F]-style; expert dim -> 'model'
_EXPERT_RULES = {
    "w_gate": ("model", "data", None),
    "w_up": ("model", "data", None),
    "w_down": ("model", None, "data"),
}


def _leaf_spec(path, leaf, mesh: Mesh, cfg=None) -> P:
    names = [p.key for p in path if hasattr(p, "key")]
    name = names[-1] if names else ""
    in_experts = "experts" in names
    rules = _EXPERT_RULES if (in_experts and name in _EXPERT_RULES) else _RULES
    rule = rules.get(name)
    shape = np.shape(leaf)
    if rule is None or len(shape) < len(rule):
        return P()                                  # norms, biases, scalars
    spec = (None,) * (len(shape) - len(rule)) + tuple(rule)
    # GQA/MQA head-count-aware attention sharding: sharding a projection's
    # (heads*hd) dim over 'model' when the head count does not divide the
    # model axis shards head_dim ITSELF, making every attention score
    # contraction a partial sum that GSPMD resolves with a full [B,H,S,T]
    # fp32 all-reduce PER LAYER (measured on gemma-2b prefill_32k, §Perf).
    # Standard fix: replicate those projections across 'model' (head-dim
    # must never split). Applies to q (n_heads) and k/v (n_kv_heads).
    if cfg is not None and not in_experts and name in ("wq", "wk", "wv", "wo"):
        ms = _axis_size(mesh, "model")
        heads = cfg.n_heads if name in ("wq", "wo") else cfg.n_kv_heads
        if heads % ms:
            if name == "wo":               # input dim is heads*hd
                spec = spec[:-2] + (None, spec[-1])
            else:                          # output dim is heads*hd
                spec = spec[:-1] + (None,)
    return _guard(spec, shape, mesh)


def param_specs(params: Any, mesh: Mesh, cfg=None):
    """Pytree of PartitionSpec matching ``params`` (works on shape structs).

    cfg (optional ArchConfig) enables architecture-aware rules (GQA KV
    replication)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path, leaf, mesh, cfg), params)


# ----------------------------------------------------------------------
# activations
# ----------------------------------------------------------------------

def batch_axes(mesh: Mesh):
    # bare string, not a 1-tuple: the specs compare equal to P("data")
    return ("pod", "data") if "pod" in mesh.shape else "data"


def batch_specs(batch: Any, mesh: Mesh, *, seq_axis: Optional[str] = None):
    """Shard the leading batch dim over ('pod','data') when divisible.
    ``seq_axis='model'`` additionally shards dim 1 (sequence parallelism for
    long prefill)."""
    ba = batch_axes(mesh)

    def spec(leaf):
        shape = np.shape(leaf)
        if not shape:
            return P()
        dims = [ba if _div(shape[0], mesh, ba) else None]
        if len(shape) > 1:
            dims.append(seq_axis if (seq_axis and _div(shape[1], mesh, seq_axis)) else None)
        dims += [None] * (len(shape) - len(dims))
        return P(*dims)

    return jax.tree.map(spec, batch)


def cache_specs(cache: Any, mesh: Mesh):
    """KV caches [L,B,T,K,hd]: batch->('pod','data'); kv-heads->'model' when
    divisible else sequence->'model'. SSM states [.., B, ...]: batch only.
    """
    ba = batch_axes(mesh)

    def spec(leaf):
        shape = np.shape(leaf)
        if len(shape) == 5:                         # [L,B,T,K,hd]
            L, B, T, K, hd = shape
            b_ax = ba if _div(B, mesh, ba) else None
            if _div(K, mesh, "model"):
                return P(None, b_ax, None, "model", None)
            if _div(T, mesh, "model"):
                return P(None, b_ax, "model", None, None)
            return P(None, b_ax, None, None, None)
        if len(shape) == 0:
            return P()
        # ssm/conv states: [L,B,...] or [B,...]; find a batch-like dim
        dims = [None] * len(shape)
        for i, d in enumerate(shape[:2]):
            if _div(d, mesh, ba) and d > 1:
                dims[i] = ba
                break
        # shard the widest remaining dim over model if divisible
        rest = [(d, i) for i, d in enumerate(shape) if dims[i] is None]
        if rest:
            d, i = max(rest)
            if _div(d, mesh, "model") and d >= _axis_size(mesh, "model"):
                dims[i] = "model"
        return P(*dims)

    return jax.tree.map(spec, cache)


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------

def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, specs: Any):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
