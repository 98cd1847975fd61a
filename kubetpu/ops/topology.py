"""Slice-alignment kernels over the dense topology coordinates.

All functions are pure jnp over ``(P,)`` assignment vectors and ``(N,)``
coordinate columns — zero per-pod Python. The central trick: with dense
slice ids in ``[0, S]`` (``S`` = unlabeled bucket) a gang's per-slice
member counts are ONE scatter-add, and from those counts both alignment
(same-slice concentration, Σ c_s²) and the cross-slice cut (pairs of
gang members split across slices, G² − Σ c_s² up to a factor 2) fall
out without materializing any (P, P) pairwise matrix.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def slice_counts(
    assignments: jnp.ndarray,
    pod_valid: jnp.ndarray,
    slice_id: jnp.ndarray,
    num_slices: int,
) -> jnp.ndarray:
    """(S+1,) int32 — assigned pods per slice (last bucket = unlabeled).

    ``assignments`` is the engine's (P,) node index (-1 unassigned);
    unassigned/padded pods land in the unlabeled bucket with weight 0.
    """
    assigned = (assignments >= 0) & pod_valid
    # clip the -1 sentinel before the gather; its weight is already 0
    node = jnp.clip(assignments, 0, slice_id.shape[0] - 1)
    sl = jnp.where(assigned, slice_id[node], num_slices)
    return (
        jnp.zeros(num_slices + 1, dtype=jnp.int32)
        .at[sl]
        .add(assigned.astype(jnp.int32))
    )


def alignment_score(
    assignments: jnp.ndarray,
    pod_valid: jnp.ndarray,
    slice_id: jnp.ndarray,
    num_slices: int,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """``(alignment, cut, slices_used)`` for one candidate placement.

    alignment = Σ_s c_s² over LABELED slices — maximal when the whole
    gang shares one slice; cut = G_labeled² − alignment ∝ cross-slice
    member pairs (the DCN traffic proxy); slices_used counts labeled
    slices the gang touches (the fragmentation footprint). All int32
    scalars, comparable across vmapped candidates.
    """
    counts = slice_counts(assignments, pod_valid, slice_id, num_slices)
    labeled = counts[:num_slices] if num_slices else counts[:0]
    align = jnp.sum(labeled * labeled).astype(jnp.int32)
    g = jnp.sum(labeled).astype(jnp.int32)
    cut = g * g - align
    used = jnp.sum((labeled > 0).astype(jnp.int32))
    return align, cut, used


def slice_occupancy(
    requested: jnp.ndarray,
    node_valid: jnp.ndarray,
    slice_id: jnp.ndarray,
    num_slices: int,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Per-slice occupancy from the node resource rows.

    Returns ``(active, sizes)``: (S+1,) bool — slice has ANY requested
    resource on a valid node — and (S+1,) int32 valid-node counts. The
    packing objective reads these to price "opening" a fully-free slice
    (fragmentation) vs landing in an already-active one (alignment).
    """
    busy = (jnp.sum(requested, axis=1) > 0) & node_valid
    busy_per = (
        jnp.zeros(num_slices + 1, dtype=jnp.int32)
        .at[slice_id]
        .add(busy.astype(jnp.int32))
    )
    sizes = (
        jnp.zeros(num_slices + 1, dtype=jnp.int32)
        .at[slice_id]
        .add(node_valid.astype(jnp.int32))
    )
    return busy_per > 0, sizes


@partial(jax.jit, static_argnames=("num_slices",))
def free_slices(
    requested: jnp.ndarray,
    node_valid: jnp.ndarray,
    slice_id: jnp.ndarray,
    num_slices: int,
) -> jnp.ndarray:
    """int32 — labeled slices with ≥1 valid node and ZERO requested
    resources anywhere (a result's ``slices_free_at_steady_state``)."""
    active, sizes = slice_occupancy(requested, node_valid, slice_id, num_slices)
    labeled_active = active[:num_slices] if num_slices else active[:0]
    labeled_sizes = sizes[:num_slices] if num_slices else sizes[:0]
    return jnp.sum(((~labeled_active) & (labeled_sizes > 0)).astype(jnp.int32))
