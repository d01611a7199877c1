"""Uneven-tensor collectives (paper §V-A "All-Gather for uneven sized
tensors"), SPMD-native.

The paper implements two asynchronous workarounds for NCCL's lack of uneven
all_gather: (1) pad every rank's tensor to the max size, all_gather, unpad;
(2) emulate all_gather with per-source broadcasts. We implement both on
``shard_map`` collectives: (1) pad + ``jax.lax.all_gather``; (2) a ring of
``jax.lax.ppermute`` rounds (the SPMD analogue of N broadcasts). Both are
verified equivalent in tests; XLA's async scheduling provides the
compute/communication overlap the paper gets from CUDA streams.

These run inside ``shard_map`` bodies — callers pass the mesh axis name.

This module also owns the :class:`BoundaryExchange` policy registry
(DESIGN.md §10): the strategy deciding, per interval boundary, whether the
latent/KV exchange happens synchronously ("full"), is skipped against stale
buffers ("skip", DistriFusion-style stale-async with a corrective refresh
cadence), or is replaced by local extrapolation of the remote slabs
("predict", Reuse-then-Predict). The schedule IR (:mod:`repro.core.events`)
consults the policy when lowering; executors only ever see the resulting
per-boundary kind.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp


def pad_to(x, rows: int, axis: int = 0):
    pad = rows - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def uneven_all_gather_padded(x_local, sizes: Sequence[int], axis_name: str,
                             axis: int = 0):
    """Strategy 1: pad to max -> all_gather -> concat valid prefixes.

    x_local: this rank's slab, shape[axis] == sizes[my_rank] (static per rank
    is impossible in SPMD, so every rank's local slab is ALREADY padded to
    max(sizes) by the caller; sizes are static Python ints).
    Returns the full concatenation [sum(sizes), ...] on every rank.
    """
    n = len(sizes)
    mx = max(sizes)
    assert x_local.shape[axis] == mx, (x_local.shape, mx)
    gathered = jax.lax.all_gather(x_local, axis_name, tiled=False)  # [N, mx, ...]
    parts = [jax.lax.index_in_dim(gathered, i, 0, keepdims=False) for i in range(n)]
    parts = [jax.lax.slice_in_dim(p, 0, sizes[i], axis=axis) for i, p in enumerate(parts)]
    return jnp.concatenate(parts, axis=axis)


def uneven_all_gather_broadcast(x_local, sizes: Sequence[int], axis_name: str,
                                axis: int = 0):
    """Strategy 2: N-1 ppermute ring rounds (broadcast emulation).

    Same contract as the padded variant (local slab padded to max(sizes)).
    """
    n = len(sizes)
    mx = max(sizes)
    assert x_local.shape[axis] == mx
    received: List = [None] * n
    idx = jax.lax.axis_index(axis_name)
    buf = x_local
    # round r: every rank holds the slab of rank (idx - r) mod n
    for r in range(n):
        # slab currently held originates from rank (idx - r); build the full
        # output with a select over static source ids per position
        received[r] = buf
        if r < n - 1:
            buf = jax.lax.ppermute(buf, axis_name,
                                   [(s, (s + 1) % n) for s in range(n)])
    # received[r] on this rank = slab of rank (idx - r) mod n; reorder to
    # global order using one-hot masks (static unroll over n)
    parts = []
    for src in range(n):
        acc = jnp.zeros_like(x_local)
        for r in range(n):
            # on ranks where (idx - r) % n == src, received[r] is src's slab
            hit = ((idx - r) % n) == src
            acc = jnp.where(hit, received[r], acc)
        parts.append(jax.lax.slice_in_dim(acc, 0, sizes[src], axis=axis))
    return jnp.concatenate(parts, axis=axis)


def stage_handoff(h, axis_name: str, n_stages: int):
    """Point-to-point pipeline handoff (DESIGN.md §11): stage ``s``'s tensor
    moves to stage ``s + 1`` via a single ``ppermute`` — the SPMD analogue
    of a NCCL send/recv pair, NOT a collective: only adjacent stages
    exchange bytes. Stage 0 receives zeros (it has no upstream; the final
    stage's output is broadcast back for the replicated DDIM update
    instead of re-entering here)."""
    return jax.lax.ppermute(h, axis_name,
                            [(s, s + 1) for s in range(n_stages - 1)])


def ring_all_reduce_bytes(n: int, nbytes: int) -> float:
    """Analytic bytes-on-wire per rank for ring all-reduce (simulator)."""
    return 2.0 * (n - 1) / n * nbytes


def ring_hop_rows(segments: Sequence[int]) -> int:
    """Modeled wire rows per rank for ONE ring hop of sequence-parallel
    attention (DESIGN.md §13): every rank forwards one K/V segment to its
    ring neighbor per hop, and uneven speed-proportional segments travel
    padded to max(segments) — the same padded-collective convention as
    :func:`uneven_all_gather_rows`. A single segment (or none) hops
    nothing."""
    active = [s for s in segments if s > 0]
    if len(active) <= 1:
        return 0
    return max(active)


def uneven_all_gather_rows(sizes: Sequence[int]) -> int:
    """Modeled wire rows per rank for the padded uneven all-gather: each of
    the N participating ranks receives N-1 remote slabs padded to
    max(sizes). A single participant (or none) exchanges nothing — the
    simulator must not charge the full-image bytes at every boundary when
    each worker only contributes its own slab."""
    active = [s for s in sizes if s > 0]
    if len(active) <= 1:
        return 0
    return (len(active) - 1) * max(active)


# ----------------------------------------------------------------------
# boundary-exchange policies (DESIGN.md §10)
# ----------------------------------------------------------------------

#: per-boundary verdicts a policy may emit
EXCHANGE_KINDS = ("full", "skip", "predict")


@dataclasses.dataclass(frozen=True)
class BoundaryExchange:
    """Decides the exchange kind at each 0-based interval boundary.

    ``refresh_every`` = E means one corrective FULL refresh every E
    boundaries (so E-1 of every E boundaries are degraded); E = 1 is fully
    synchronous. The final boundary of a run is always forced to "full" by
    the IR regardless of the policy (the image must assemble).
    """
    name: str
    refresh_every: int = 1
    degraded_kind: str = "full"          # what non-refresh boundaries emit

    def __post_init__(self):
        if self.refresh_every < 1:
            raise ValueError(f"refresh_every must be >= 1, got "
                             f"{self.refresh_every}")
        if self.degraded_kind not in EXCHANGE_KINDS:
            raise ValueError(f"unknown exchange kind {self.degraded_kind!r}")

    def kind(self, boundary_index: int) -> str:
        if (boundary_index + 1) % self.refresh_every == 0:
            return "full"
        return self.degraded_kind


EXCHANGES: Dict[str, Callable[[int], BoundaryExchange]] = {}


def register_exchange(name: str):
    def deco(factory):
        EXCHANGES[name] = factory
        return factory
    return deco


def get_exchange(name: str, refresh_every: int = 2) -> BoundaryExchange:
    """Look up a boundary-exchange policy by registry name.

    ``refresh_every`` parameterizes the degraded policies (ignored by
    "sync"): stale_async/predictive skip/predict on ``refresh_every - 1``
    of every ``refresh_every`` boundaries.
    """
    try:
        factory = EXCHANGES[name]
    except KeyError:
        raise KeyError(f"unknown exchange policy {name!r}; registered: "
                       f"{sorted(EXCHANGES)}") from None
    return factory(refresh_every)


@register_exchange("sync")
def _sync(refresh_every: int) -> BoundaryExchange:
    """Today's behavior: blocking latent all-gather + KV merge, every
    boundary. Bitwise-identical numerics to the pre-policy engine."""
    return BoundaryExchange("sync", refresh_every=1)


@register_exchange("stale_async")
def _stale_async(refresh_every: int) -> BoundaryExchange:
    """DistriFusion-style: skip the boundary exchange on E-1 of every E
    boundaries; workers denoise against neighbor slabs up to E intervals
    stale, with a corrective full refresh every E-th boundary."""
    return BoundaryExchange("stale_async", refresh_every=refresh_every,
                            degraded_kind="skip")


@register_exchange("predictive")
def _predictive(refresh_every: int) -> BoundaryExchange:
    """Reuse-then-Predict: on non-refresh boundaries, linearly extrapolate
    the remote K/V slabs from the last two fully-exchanged versions (falls
    back to stale reuse until two refreshes have landed)."""
    return BoundaryExchange("predictive", refresh_every=refresh_every,
                            degraded_kind="predict")


@register_exchange("ring")
def _ring(refresh_every: int) -> BoundaryExchange:
    """Sequence-parallel ring staging (DESIGN.md §13): per-hop staged K/V.

    Between full refreshes the cross-worker boundary is skipped — exactly
    the stale_async verdict — while WITHIN each worker the ring hops of
    every attention keep forwarding fresh per-segment K/V, so ring hops
    carry stale *neighbors* precisely the way DistriFusion halos do. The
    per-boundary kinds are therefore the existing "skip"/"full" grammar
    (nothing new for executors to interpret); what "ring" adds is the
    per-hop staging the seq-aware executors and the ring-contention cost
    model key off the IR's :class:`~repro.core.events.SeqShard` events.
    This is also why stale_async/predictive compose naturally with the
    sequence axis: the ring is orthogonal to the cross-worker verdict."""
    return BoundaryExchange("ring", refresh_every=refresh_every,
                            degraded_kind="skip")
