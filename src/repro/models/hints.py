"""Activation sharding hints: pin the batch/TP layout inside model code.

GSPMD propagation from the jit in_shardings alone is not reliable through
embedding gathers, scans, and remat (§Perf iteration 1 found the batch
axis silently replicated mid-graph, turning TP matmuls into full-batch
f32 all-reduces). These hints pin the residual-stream layout at every
layer boundary. They are exact no-ops when no mesh is active (unit tests,
single-device examples) and filter axis names against the ambient mesh
(``jax.set_mesh``), so the same model code runs everywhere.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P


def _ambient_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def _filter(mesh, entry: Any, dim: int) -> Any:
    if entry is None:
        return None
    cand = entry if isinstance(entry, tuple) else (entry,)
    cand = tuple(a for a in cand if a in mesh.axis_names)

    def div(c):
        n = 1
        for a in c:
            n *= mesh.shape[a]
        return n

    while cand and dim % div(cand) != 0:
        cand = cand[:-1]
    if not cand:
        return None
    return cand if len(cand) > 1 else cand[0]


def shard_hint(x: jax.Array, *entries: Any) -> jax.Array:
    """with_sharding_constraint(x, P(*entries)) against the ambient mesh;
    silently drops absent/non-dividing axes; no-op without a mesh."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    ent = list(entries) + [None] * (x.ndim - len(entries))
    spec = P(*[_filter(mesh, e, d) for e, d in zip(ent, x.shape)])
    return jax.lax.with_sharding_constraint(x, spec)


DP = ("pod", "data")  # batch axes


def hint_batch(x: jax.Array) -> jax.Array:
    """Residual stream (B, S, d): batch on the data axes."""
    return shard_hint(x, DP)


def hint_batch_seq(x: jax.Array) -> jax.Array:
    """Sequence-parallel residual (B, S, d): batch on data, seq on model.
    Norms/elementwise run model-sharded; GSPMD turns the TP boundary
    all-reduces into reduce-scatter + all-gather pairs (§Perf)."""
    return shard_hint(x, DP, "model")


def hint_logits(x: jax.Array) -> jax.Array:
    """(B, S, V) or (B, 1, V): batch on data, vocab on model."""
    return shard_hint(x, DP, None, "model")
