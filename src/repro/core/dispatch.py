"""Unified accumulation-policy execution: one entry point for every
quantized dot product in the framework.

``pqs_dot(x, w, ...)`` runs any of the six accumulation policies

    wide | clip | wrap | sorted | sorted_tiled | sorted_tiled_seq

on either execution backend:

  - ``jnp``    — the pure-jnp reference semantics (core.overflow /
                 core.sorted_accum), exact on any platform;
  - ``pallas`` — the TPU kernels (kernels/ops.py), interpret-mode on CPU,
                 compiled on TPU.

The backend is selected automatically by platform (TPU -> pallas,
otherwise jnp) with an explicit override, and the two are bit-identical
for every policy (tests/test_dispatch.py sweeps the matrix). Arbitrary
shapes are handled here once — K is zero-padded to the policy's required
length (a whole number of k_tile tiles, or a power of two for the global
sort) for BOTH backends, so order-sensitive policies see the same
permutation; M is batch-chunked to bound the (chunk, N, K) partial
products tensor of the jnp backend.

The optional census output classifies natural-order overflow behavior
(persistent vs transient, paper Fig 2a) from the same partial products
the jnp backend accumulates — the analysis path no longer re-derives
them.

``qtensor_dot`` + ``integer_lin`` put the serving stack on this path:
inside the context, every ``models.layers.lin`` whose weight is a
QTensor executes as a true integer dot product under the configured
policy instead of dequantize-then-float-matmul.

Sparse storage: ``pqs_dot(..., storage="nm")`` accepts N:M-compressed
weights (``core.qtensor.SparseQTensor`` or a raw (values, indices)
pair) and runs every policy directly on the compressed form —
bit-identical, census included, to decompressing first (see
``kernels.ops.nm_policy_matmul``). This is the P of PQS composed with
the Q+S: pruning shortens the effective dot-product length the narrow
accumulator sees, and the compressed slabs cut weight HBM traffic by
~n_keep/m on the serving path.

Distributed execution: ``pqs_dot(..., mesh=...)`` runs the same dot
under ``shard_map`` on a named mesh — output channels (N) sharded on
the tensor-parallel axis, rows (M) on the data axes, and the full K
accumulation performed *inside* each shard under the configured policy,
so every output element is produced by exactly the single-device
routine and results stay bit-identical at any mesh shape. Specs are
``sanitize``-degraded (non-dividing axes dropped), so ragged shapes
lower everywhere.

K-sharded accumulation: ``pqs_dot(..., k_shards=S)`` (and its mesh form
``mesh= + k_axis=``) partitions the REDUCTION axis instead of keeping
it whole: each shard accumulates its contiguous, policy-padded K/S
slice under the configured policy with the unchanged kernel bodies, and
the per-shard partials merge up the shared static combine tree
(``core.sorted_accum.combine_schedule`` / ``tree_combine``) with
stepwise saturation. On a mesh the tree runs as log2(S) pairwise
``ppermute`` exchanges along ``k_axis`` — one (M, N) int32 register per
step instead of all-gathering all S partials — and
``defer_combine=True`` exposes the exchange as an async-dispatchable
tail (``PendingCombine``) so independent compute overlaps it. The
census counts every shard's local dot and reports combine-step
overflows separately (``Census.n_combine``). This is what carries a
single dot past the sort kernels' per-device ``ops.MAX_STREAM_K``
bound: per-device K footprint is K/S.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.overflow import (
    Census,
    accumulate,
    census,
    kshard_partials,
    nm_partial_products,
    partial_products,
)
from repro.core.pruning import nm_decompress_jax
from repro.core.quant import qrange
from repro.core.sorted_accum import (
    combine_schedule,
    combine_step,
    tree_combine,
)
from repro.kernels import ops

POLICIES = ops.POLICIES  # derived from the kernel modules — one list
BACKENDS = ("jnp", "pallas")
STORAGES = ("dense", "nm")

# Cap on the HBM tile-sum + permutation statistic of the two-pass
# sorted_tiled kernel (per M-chunk: 2 * 4 * N * K/k_tile bytes/row);
# pqs_dot defaults batch_chunk to stay under it.
_SORT_STATS_BUDGET = 256 * 1024 * 1024


def default_backend() -> str:
    """pallas on real TPUs (compiled kernels); jnp reference elsewhere.

    Interpret-mode pallas is semantically identical but far slower than
    jnp on CPU, so it is opt-in via backend="pallas"."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _validate(policy: str, backend: Optional[str], acc_bits: int,
              k_tile: int, storage: str = "dense") -> None:
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; expected {POLICIES}")
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    if storage not in STORAGES:
        raise ValueError(f"unknown storage {storage!r}; expected {STORAGES}")
    if not 2 <= acc_bits <= 30:
        raise ValueError(f"acc_bits={acc_bits} outside the int32-carrier "
                         "range [2, 30]")
    if policy in ("sorted_tiled", "sorted_tiled_seq") and (
        k_tile <= 0 or k_tile & (k_tile - 1)
    ):
        raise ValueError(f"k_tile must be a power of 2, got {k_tile}")


def _unpack_nm(w: Any, m_group: Optional[int]):
    """(values, indices, m_group, logical K) from a storage="nm" weight.

    Accepts a ``core.qtensor.SparseQTensor`` (m_group/k_dim ride along)
    or a bare ``(values, indices)`` pair plus an explicit ``m_group``.
    """
    from repro.core.qtensor import SparseQTensor

    if isinstance(w, SparseQTensor):
        if w.values.ndim != 3:
            raise ValueError(
                "pqs_dot needs an unstacked (out, G, n_keep) SparseQTensor; "
                f"got values {w.values.shape} (slice the layer axis first)"
            )
        return w.values, w.indices, w.m_group, w.k_dim
    if isinstance(w, (tuple, list)) and len(w) == 2:
        values, indices = w
        if m_group is None:
            raise ValueError(
                "storage='nm' with a bare (values, indices) pair needs an "
                "explicit m_group="
            )
        if values.ndim != 3 or values.shape != indices.shape:
            raise ValueError(
                f"expected matching (N, G, n_keep) slabs, got "
                f"{values.shape} / {indices.shape}"
            )
        return values, indices, m_group, values.shape[1] * m_group
    raise ValueError(
        "storage='nm' expects w to be a SparseQTensor or a "
        f"(values, indices) pair, got {type(w).__name__}"
    )


def _local_dot(
    x2: jax.Array,  # (M, Kp) — K already padded by the shared rule
    w: Any,  # (N, Kp) dense, or (values, indices) compressed slabs
    *,
    acc_bits: int,
    policy: str,
    k_tile: int,
    rounds: int,
    backend: str,
    interpret: Optional[bool],
    block_m: Optional[int],
    block_n: Optional[int],
    sort_impl: str,
    batch_chunk: Optional[int],
    with_census: bool,
    storage: str = "dense",
    m_group: Optional[int] = None,
    nm_impl: Optional[str] = None,
    certified: bool = False,
) -> tuple[jax.Array, Optional[Census]]:
    """Single-device policy matmul on pre-padded operands (+census).

    storage="nm": ``w`` is the compressed (values, indices) pair. The
    jnp backend decompresses to the dense reference semantics (padded
    to the same Kp the dense path would use — zero columns are inert);
    the pallas backend runs ``ops.nm_policy_matmul`` directly on the
    compressed slabs (``nm_impl`` selecting expand vs fused gather —
    bit-identical either way). The census is computed from the
    KEPT-ONLY partial products (``overflow.nm_partial_products``) for
    both backends and both impls — bit-identical counts at n_keep/m of
    the unrolled memory.

    certified=True: a `core.certify` proof says no partial sum can reach
    the acc_bits caps, so the stepwise saturate bookkeeping is dead code
    — the jnp backend accumulates wide (bit-identical to the narrow
    result by the proof), the pallas backend takes the kernels'
    census-free route (``ops.policy_matmul(census=False)``).
    """
    if certified:
        with_census = False
    jnp_policy = "wide" if certified else policy
    m = x2.shape[0]
    chunk = m if (batch_chunk is None or batch_chunk >= m) else batch_chunk
    outs = []
    tot: Optional[Census] = None
    wd = None
    if storage == "nm" and backend == "jnp":
        values, indices = w
        wd = nm_decompress_jax(values, indices, m_group)  # (N, G*m)
        kp = ops.padded_k(wd.shape[-1], policy, k_tile)
        if kp != wd.shape[-1]:
            wd = jnp.pad(wd, ((0, 0), (0, kp - wd.shape[-1])))
    for i in range(0, m, max(chunk, 1)):
        xc = x2[i : i + chunk]
        prods = None
        if storage == "nm" and backend == "jnp":
            xcp = jnp.pad(
                xc, ((0, 0), (0, wd.shape[-1] - xc.shape[-1]))
            ) if wd.shape[-1] != xc.shape[-1] else xc
            prods = partial_products(wd, xcp)  # (c, N, Kp)
            outs.append(
                accumulate(prods, acc_bits, jnp_policy, k_tile, rounds))
        elif storage == "nm":
            outs.append(
                ops.nm_policy_matmul(
                    xc, w[0], w[1], m_group=m_group, policy=policy,
                    acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
                    bm=block_m, bn=block_n, sort_impl=sort_impl,
                    nm_impl=nm_impl, interpret=interpret,
                    census=not certified,
                )
            )
        elif backend == "jnp":
            prods = partial_products(w, xc)  # (c, N, Kp)
            outs.append(
                accumulate(prods, acc_bits, jnp_policy, k_tile, rounds))
        else:
            outs.append(
                ops.policy_matmul(
                    xc, w, policy=policy, acc_bits=acc_bits, k_tile=k_tile,
                    rounds=rounds, bm=block_m, bn=block_n,
                    sort_impl=sort_impl, interpret=interpret,
                    census=not certified,
                )
            )
        if with_census:
            if prods is None:
                # backends that already materialized a cube reuse it
                # (zero products are census-inert); the nm pallas path,
                # which never builds one, pays only the kept-only gather
                prods = (
                    nm_partial_products(w[0], w[1], xc, m_group)
                    if storage == "nm"
                    else partial_products(w, xc)
                )
            tot = _merge_census(tot, census(prods, acc_bits))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out, tot


def _merge_census(tot: Optional[Census], c: Census) -> Census:
    return c if tot is None else Census(*(a + b for a, b in zip(tot, c)))


@dataclasses.dataclass
class PendingCombine:
    """A K-sharded dot whose cross-shard combine is still pending.

    The async-dispatchable tail of ``pqs_dot(..., defer_combine=True)``:
    ``partials`` holds every shard's policy-accumulated int32 register —
    (M, N, S) on a single device, or a global (S, M, N) array laid out
    along ``k_axis`` on a mesh, where each member owns exactly its own
    register (O(1) per-member footprint, never the gathered S). Nothing
    has crossed the interconnect yet.

    ``combine()`` merges the registers up the shared static combine tree
    (``core.sorted_accum.combine_schedule``) and returns what the
    non-deferred call would have — ``out`` or ``(out, Census)`` — bit
    for bit. Because dispatching the exchange is separated from
    consuming its result, a caller tracing both phases into one jitted
    step lets XLA's latency-hiding scheduler run the log2(S) ppermute
    steps concurrently with any compute that does not depend on the
    combined value: issue pass 1 of the next dot, then combine the
    previous one (double-buffered partials in the serving step).
    """

    partials: Any
    _finish: Any  # partials -> out | (out, Census)

    def combine(self):
        """Run the combine tail; returns ``out`` or ``(out, Census)``."""
        return self._finish(self.partials)


def _kshard_dot(
    x2: jax.Array,  # (M, k_shards * k_local) — pre-padded by pqs_dot
    w: Any,  # (N, k_shards * k_local) dense, or pre-padded nm slabs
    *,
    k_shards: int,
    with_census: bool,
    acc_bits: int,
    policy: str,
    k_tile: int,
    rounds: int,
    backend: str,
    interpret: Optional[bool],
    block_m: Optional[int],
    block_n: Optional[int],
    sort_impl: str,
    batch_chunk: Optional[int],
    storage: str = "dense",
    m_group: Optional[int] = None,
    nm_impl: Optional[str] = None,
    certified: bool = False,
    defer: bool = False,
):
    """Single-device hierarchical K-sharded dot (and the mesh oracle).

    K (pre-padded into ``k_shards`` equal, policy-padded contiguous
    slices) is partitioned; every shard accumulates its local slice
    under the unmodified policy — the jnp backend through
    ``overflow.kshard_partials``, the pallas backend through the
    per-shard kernel entry points (``ops.partial_policy_matmul`` /
    ``ops.nm_partial_policy_matmul``) — and the per-shard partials merge
    up the shared static combine tree
    (``core.sorted_accum.tree_combine``).

    Census: every shard's local dot is an examined dot (n_dots =
    k_shards * M * N; per-shard natural-order classification), and
    combine-step overflows are reported separately in ``n_combine`` —
    the total census is exactly sum(per-shard) + combine steps.

    certified=True: per-shard partials AND every combine step are subset
    sums of the row's products, so the certificate covers the whole
    hierarchy — shards and the combine run census-free/saturation-free.

    defer=True returns a ``PendingCombine`` over the stacked (M, N, S)
    registers instead; its finish runs ``tree_combine`` and yields
    ``(out, census)`` exactly as the eager path would.
    """
    if certified:
        with_census = False
    jnp_policy = "wide" if certified else policy
    m = x2.shape[0]
    n = (w[0] if storage == "nm" else w).shape[0]
    chunk = m if (batch_chunk is None or batch_chunk >= m) else batch_chunk
    wd = None
    if storage == "nm" and backend == "jnp":
        # G is pre-padded to a k_shards multiple, so the decompressed
        # matrix is (N, kp) and shard slices fall on group boundaries
        wd = nm_decompress_jax(w[0], w[1], m_group)
    parts_all = []
    tot: Optional[Census] = None
    for i in range(0, m, max(chunk, 1)):
        xc = x2[i : i + chunk]
        prods = None
        if backend == "jnp":
            prods = partial_products(wd if storage == "nm" else w, xc)
            parts = kshard_partials(
                prods, acc_bits, jnp_policy, k_shards, k_tile, rounds
            )
        elif storage == "nm":
            parts = ops.nm_partial_policy_matmul(
                xc, w[0], w[1], m_group=m_group, k_shards=k_shards,
                policy=policy, acc_bits=acc_bits, k_tile=k_tile,
                rounds=rounds, bm=block_m, bn=block_n,
                sort_impl=sort_impl, nm_impl=nm_impl,
                interpret=interpret, census=not certified,
            )
        else:
            parts = ops.partial_policy_matmul(
                xc, w, k_shards=k_shards, policy=policy,
                acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
                bm=block_m, bn=block_n, sort_impl=sort_impl,
                interpret=interpret, census=not certified,
            )
        parts_all.append(parts)
        if with_census:
            if prods is None:
                prods = (
                    nm_partial_products(w[0], w[1], xc, m_group)
                    if storage == "nm"
                    else partial_products(w, xc)
                )
            sh = prods.reshape(
                xc.shape[0], n, k_shards, prods.shape[-1] // k_shards
            )
            tot = _merge_census(tot, census(sh, acc_bits))
    parts = (
        parts_all[0] if len(parts_all) == 1
        else jnp.concatenate(parts_all, axis=0)
    )

    def finish(p):
        out, novf = tree_combine(p, acc_bits, jnp_policy)
        t = tot
        if with_census:
            t = t._replace(
                n_combine=t.n_combine + jnp.sum(novf).astype(jnp.int32)
            )
        return out, t

    if defer:
        return PendingCombine(parts, finish)
    return finish(parts)


def _exchange_combine(
    val: jax.Array, k_axis: str, k_size: int, acc_bits: int, policy: str
) -> tuple[jax.Array, jax.Array]:
    """Pairwise-exchange combine along ``k_axis`` (inside shard_map).

    Walks ``core.sorted_accum.combine_schedule(k_size)``: log2(S)
    ``ppermute`` steps, each exchanging this member's (M, N) int32
    register with the level's partner and merging through
    ``combine_step``. Every member ends holding the root of the same
    balanced tree ``tree_combine`` computes locally (the two realize one
    schedule — that is the bit-identity argument), with per-member
    interconnect volume of log2(S) registers instead of the S an
    all-gather moves. Non-power-of-two axis sizes fall back to
    all-gather + ``tree_combine`` — still bit-identical, the gathered
    vector just walks the identical tree on every member.

    Returns ``(combined, novf_local)``: the combined registers
    (replicated along ``k_axis``) and this member's share of the
    combine-overflow count. Every tree merge is computed redundantly by
    all members of its block, so it is counted only on the block's
    lowest-index member — ``psum`` over ``k_axis`` then reconstructs
    exactly ``tree_combine``'s per-tree count.
    """
    if k_size & (k_size - 1):
        parts = jnp.moveaxis(jax.lax.all_gather(val, k_axis), 0, -1)
        out, novf = tree_combine(parts, acc_bits, policy)
        keep = jax.lax.axis_index(k_axis) == 0
        return out, jnp.where(keep, novf, 0)
    idx = jax.lax.axis_index(k_axis)
    novf = jnp.zeros(val.shape, jnp.int32)
    for level, perm in enumerate(combine_schedule(k_size)):
        other = jax.lax.ppermute(val, k_axis, perm)
        val, hit = combine_step(val, other, acc_bits, policy)
        own = idx % (1 << (level + 1)) == 0
        novf = novf + jnp.where(own, hit.astype(jnp.int32), 0)
    return val, novf


def _sharded_dot(
    x2: jax.Array,  # (M, Kp)
    w: jax.Array,  # (N, Kp)
    mesh,
    m_axes: Optional[tuple[str, ...]],
    n_axis: str,
    with_census: bool,
    k_axis: Optional[str] = None,
    defer: bool = False,
    **kw,
):
    """shard_map wrapper: M on the data axes, N on the TP axis, K whole
    per shard — or, with ``k_axis``, K partitioned across that mesh axis.

    Without ``k_axis`` every shard runs the unmodified single-device
    routine over its (M_shard, N_shard) block with the FULL (padded) K
    axis resident, so the narrow-accumulation order — and therefore the
    result — is bit-identical to the single-device reference. Specs
    degrade through ``sanitize`` when a dimension does not divide its
    axes, so any shape lowers (at worst fully replicated).

    With ``k_axis`` each device accumulates its contiguous K/S slice
    under the policy (still the unmodified local routine) and the
    per-shard registers merge through the pairwise exchange
    (``_exchange_combine``): log2(S) ``ppermute`` steps along the K
    axis, one (M, N) int32 register each, realizing the same static
    combine schedule as the single-device ``k_shards=S`` hierarchy —
    bit-identical to it, at O(1) resident partials per member. The
    census is psummed over the K axis too (every shard's dot is an
    examined dot), and the per-member combine-count shares are psummed
    over ``k_axis`` as well to reconstruct the exact per-tree total.

    Either way the (M, N) blocks are then all-gathered, so every member
    returns the whole result: the float ops a model runs on it never
    split a sum across devices (a split sum rounds differently), and a
    meshed model computes bit-identically to one device.

    ``defer=True`` splits the dot into two shard_maps: phase 1 returns
    the global (S, M, N) register array laid out on ``k_axis`` wrapped
    in a ``PendingCombine``; its finish runs the exchange. Tracing both
    phases into one jitted step lets XLA overlap the exchange with any
    compute independent of the combined value.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import data_axes
    from repro.launch.sharding import sanitize

    if m_axes is None:
        m_axes = data_axes(mesh)
    m_axes = tuple(
        a for a in m_axes if a in mesh.axis_names and a != k_axis
    )
    x_spec = sanitize(mesh, P(m_axes if m_axes else None, k_axis), x2.shape)
    n_entry = n_axis if n_axis in mesh.axis_names else None
    if isinstance(w, tuple):  # compressed (values, indices): N rows shard
        vspec = sanitize(mesh, P(n_entry, k_axis, None), w[0].shape)
        w_spec = (vspec, vspec)
        w_row = vspec[0]
        w_k = vspec[1]
    else:
        w_spec = sanitize(mesh, P(n_entry, k_axis), w.shape)
        w_row = w_spec[0]
        w_k = w_spec[1]
    if k_axis is not None and (x_spec[1] != k_axis or w_k != k_axis):
        # cannot happen: pqs_dot pads K (and G) to k_shards multiples,
        # so sanitize never drops the K entry — guard the invariant the
        # combine below depends on rather than silently mis-combining
        raise AssertionError(
            f"K axis {k_axis!r} was degraded from the operand specs "
            f"({x_spec}, {w_spec}) despite pre-padding"
        )
    out_spec = P()  # whole result on every member (``gather`` below)

    def gather(out):
        if x_spec[0] is not None:
            out = jax.lax.all_gather(out, x_spec[0], axis=0, tiled=True)
        if w_row is not None:
            out = jax.lax.all_gather(out, w_row, axis=1, tiled=True)
        return out
    # census counters must be summed only over axes that actually
    # partition the dots; replicated axes would multiply-count
    used: list[str] = []
    for entry in (x_spec[0], w_row):
        if entry is not None:
            used.extend(entry if isinstance(entry, tuple) else (entry,))
    k_size = int(mesh.shape[k_axis]) if k_axis is not None else 1
    acc_bits = kw["acc_bits"]
    combine_policy = "wide" if kw.get("certified") else kw["policy"]
    cns_specs = Census(P(), P(), P(), P(), P())

    if not defer:

        def body(xl, wl):
            out, cns = _local_dot(xl, wl, with_census=with_census, **kw)
            novf = None
            if k_axis is not None:
                out, novf = _exchange_combine(
                    out, k_axis, k_size, acc_bits, combine_policy
                )
            if with_census:
                axes = tuple(used) + (
                    (k_axis,) if k_axis is not None else ()
                )
                if axes:
                    cns = jax.tree_util.tree_map(
                        lambda a: jax.lax.psum(a, axes), cns
                    )
                if novf is not None:
                    nc = jnp.sum(novf).astype(jnp.int32)
                    nc = jax.lax.psum(nc, tuple(used) + (k_axis,))
                    cns = cns._replace(n_combine=cns.n_combine + nc)
            out = gather(out)
            return (out, cns) if with_census else out

        out_specs = (out_spec, cns_specs) if with_census else out_spec
        return shard_map(
            body, mesh=mesh, in_specs=(x_spec, w_spec), out_specs=out_specs,
            check_vma=False,
        )(x2, w)

    # deferred: phase 1 materializes each member's register as its slot
    # of a global (S, M, N) array laid out along k_axis; phase 2 — the
    # exchange — dispatches when the caller consumes the PendingCombine
    part_spec = P(k_axis, x_spec[0], w_row)

    def body1(xl, wl):
        out, cns = _local_dot(xl, wl, with_census=with_census, **kw)
        if with_census:
            axes = tuple(used) + (k_axis,)
            cns = jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, axes), cns
            )
            return out[None], cns
        return out[None]

    out_specs1 = (part_spec, cns_specs) if with_census else part_spec
    res1 = shard_map(
        body1, mesh=mesh, in_specs=(x_spec, w_spec), out_specs=out_specs1,
        check_vma=False,
    )(x2, w)
    parts, cns1 = res1 if with_census else (res1, None)

    def body2(pl):
        out, novf = _exchange_combine(
            pl[0], k_axis, k_size, acc_bits, combine_policy
        )
        nc = jnp.sum(novf).astype(jnp.int32)
        nc = jax.lax.psum(nc, tuple(used) + (k_axis,))
        return gather(out), nc

    combine_fn = shard_map(
        body2, mesh=mesh, in_specs=(part_spec,), out_specs=(out_spec, P()),
        check_vma=False,
    )

    def finish(p):
        out, nc = combine_fn(p)
        t = cns1
        if with_census:
            t = t._replace(n_combine=t.n_combine + nc)
        return out, t

    return PendingCombine(parts, finish)


def pqs_dot(
    x: jax.Array,  # (..., K) integer carrier (int8 or int32 holding int8)
    w: Any,  # (N, K) integer carrier; rows = output channels — or, with
    # storage="nm", a SparseQTensor / (values, indices) compressed pair
    *,
    acc_bits: int = 16,
    policy: str = "wide",
    k_tile: int = 256,
    rounds: int = 1,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    sort_impl: str = "auto",
    batch_chunk: Optional[int] = None,
    with_census: bool = False,
    mesh=None,
    m_axes: Optional[tuple[str, ...]] = None,
    n_axis: str = "model",
    k_shards: Optional[int] = None,
    k_axis: Optional[str] = None,
    storage: str = "dense",
    m_group: Optional[int] = None,
    nm_impl: Optional[str] = None,
    certified: bool = False,
    defer_combine: bool = False,
):
    """Quantized dot products with simulated narrow accumulation.

    Returns (..., N) int32 — each element a dot product accumulated into
    an acc_bits register under ``policy``. With ``with_census=True``
    returns ``(out, Census)`` where the census classifies natural-order
    overflows of the same dot products (persistent / transient, Fig 2a).

    Any M/N/K works: padding and batch chunking happen here, not at call
    sites. ``backend`` overrides the platform default; both backends are
    bit-identical per policy. ``block_m``/``block_n`` default to the
    measured-autotune winner when REPRO_PQS_AUTOTUNE is enabled, else
    the per-platform table in ``kernels.ops`` (env-overridable).
    ``sort_impl`` picks the Pallas kernel for the global-sort policies:
    ``auto`` (one-pass K-resident up to ``ops.MAX_RESIDENT_K``, two-pass
    streaming above), ``onepass``, or ``twopass``.

    ``storage="nm"`` composes every policy with N:M compressed weight
    storage: ``w`` is a ``core.qtensor.SparseQTensor`` (or a bare
    ``(values, indices)`` pair plus ``m_group=``) and the pallas backend
    runs the policy directly on the compressed slabs
    (``kernels.ops.nm_policy_matmul`` — G is padded instead of K); the
    jnp backend decompresses to the dense reference. ``nm_impl``
    (default ``REPRO_PQS_NM_IMPL``, then ``auto``) selects the Pallas
    implementation: ``expand`` (one-hot expand to dense in VMEM, the
    oracle) or ``gather`` (contract only the kept products — n_keep/m
    of the FLOPs); ``auto`` picks gather wherever it saves work.
    Results — census included (counted over the KEPT partial products
    only) — are bit-identical to ``nm_decompress`` followed by this
    function on the dense matrix, for either implementation.

    With ``mesh`` (a ``jax.sharding.Mesh``), the dot executes under
    ``shard_map``: M sharded over ``m_axes`` (default: the mesh's data
    axes), N over ``n_axis`` ("model"), K accumulated whole inside each
    shard — bit-identical to the single-device result (compressed
    weights shard their N rows the same way), and returned whole on
    every member.

    ``k_shards=S`` (without a mesh) partitions K into S contiguous,
    equal, policy-padded slices accumulated independently under the
    policy, then merged up the shared static combine tree
    (``core.sorted_accum.combine_schedule`` / ``tree_combine`` —
    stepwise saturation; the census reports combine-step overflows
    separately in ``Census.n_combine``, and every shard's local dot
    counts as an examined dot). With ``mesh`` + ``k_axis`` the same
    hierarchy runs distributed: K is partitioned across that mesh axis,
    each device accumulates only its K/S slice (per-device K footprint
    drops by S — past ``ops.MAX_STREAM_K`` total K for the compiled
    sort kernels), and the per-shard registers merge through log2(S)
    pairwise ``ppermute`` exchanges realizing the identical schedule —
    bit-identical to ``k_shards=S`` on one device, at one (M, N)
    register per exchange instead of an S-partial all-gather. Note the
    hierarchy intentionally changes the accumulation ORDER vs the
    full-K dot for the saturating policies (docs/accumulation.md,
    "K-sharded accumulation"); ``wide``/``wrap`` are exactly
    order-invariant.

    ``defer_combine=True`` (K-sharded paths only) returns a
    ``PendingCombine`` instead of the result: the per-shard registers
    with the cross-shard exchange still pending. ``.combine()`` yields
    exactly what the eager call would have returned; dispatching both
    phases inside one jitted step lets XLA overlap the exchange with
    independent compute (see ``PendingCombine``).

    ``certified=True`` declares that a `core.certify.Certificate` proves
    no partial sum of these operands can reach the acc_bits caps — the
    stepwise saturate/census bookkeeping is then provably dead code and
    is skipped (kernels take the census-free wide-safe route; the jnp
    backend accumulates wide). By the subset-sum bound the result is
    bit-identical to the censused narrow path under every policy,
    k-sharding and storage included. The caller is responsible for the
    proof actually covering (weights, act range, acc_bits); serving
    checks it per site via ``IntegerLinConfig.certificate``. Mutually
    exclusive with ``with_census`` — a certified dot has no census.
    """
    _validate(policy, backend, acc_bits, k_tile, storage)
    if certified and with_census:
        raise ValueError(
            "certified=True removes the census from the path entirely; "
            "with_census=True contradicts it"
        )
    if nm_impl is not None:
        if storage != "nm":
            raise ValueError("nm_impl= is only meaningful with storage='nm'")
        if nm_impl not in ops.NM_IMPLS:
            raise ValueError(
                f"nm_impl must be one of {ops.NM_IMPLS}, got {nm_impl!r}")
    if k_axis is not None:
        if mesh is None:
            raise ValueError("k_axis= needs mesh= (the axis lives on it)")
        if k_axis not in mesh.axis_names:
            raise ValueError(
                f"k_axis={k_axis!r} not on the mesh {mesh.axis_names}")
        if k_axis == n_axis:
            raise ValueError(
                f"k_axis and n_axis must differ, both are {k_axis!r}")
        if k_shards is None:
            k_shards = mesh.shape[k_axis]
        elif int(k_shards) != mesh.shape[k_axis]:
            raise ValueError(
                f"k_shards={k_shards} != mesh.shape[{k_axis!r}]="
                f"{mesh.shape[k_axis]}")
    elif k_shards is not None and mesh is not None:
        raise ValueError(
            "k_shards on a mesh needs k_axis= naming the mesh axis the "
            "K shards live on")
    k_shards = 1 if k_shards is None else int(k_shards)
    if k_shards < 1:
        raise ValueError(f"k_shards must be >= 1, got {k_shards}")
    backend = backend or default_backend()
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)

    if storage == "nm":
        values, indices, m_group, k_logical = _unpack_nm(w, m_group)
        n = values.shape[0]
        k_dense = values.shape[1] * m_group
        if k not in (k_logical, k_dense):
            raise ValueError(
                f"contraction mismatch: x has K={k} but the compressed "
                f"weights cover {k_logical} (logical) / {k_dense} (padded)"
            )
        if policy in ("sorted_tiled", "sorted_tiled_seq") and (
            k_tile % m_group != 0
        ):
            raise ValueError(
                f"tiled policies on storage='nm' need k_tile % m_group == "
                f"0 (tile boundaries must align with the compressed "
                f"groups); got k_tile={k_tile}, m_group={m_group}"
            )
        if k_shards > 1:
            # shard K in units of whole groups: pad G so every shard
            # holds g_local groups whose span is a policy-padded length
            # (padded groups expand to zero columns — inert everywhere)
            g = values.shape[1]
            k_local = ops.padded_k(
                -(-g // k_shards) * m_group, policy, k_tile)
            if k_local % m_group:
                raise ValueError(
                    f"k_shards={k_shards} with storage='nm' and policy="
                    f"{policy!r} needs the per-shard padded K ({k_local}) "
                    f"divisible by m_group={m_group}"
                )
            gp = k_shards * (k_local // m_group)
            if gp != g:
                pad3 = ((0, 0), (0, gp - g), (0, 0))
                values = jnp.pad(values, pad3)
                indices = jnp.pad(indices, pad3)
            kp = gp * m_group
            if x2.shape[-1] != kp:
                x2 = jnp.pad(x2, ((0, 0), (0, kp - x2.shape[-1])))
        else:
            if k_dense != k:
                x2 = jnp.pad(x2, ((0, 0), (0, k_dense - k)))
            kp = ops.padded_k(k_dense, policy, k_tile)
        w = (values, indices)
    else:
        if x.shape[-1] != w.shape[-1]:
            raise ValueError(f"contraction mismatch: {x.shape} vs {w.shape}")
        n = w.shape[0]
        # one K-padding rule for both backends: order-sensitive policies
        # must see the same (padded) permutation domain to be bit-identical
        if k_shards > 1:
            # every shard sees the same policy-padded local length, so
            # per-shard kernels and the jnp oracle share one permutation
            # domain — and the mesh path's equal-block partitioning
            # slices at exactly these boundaries
            kp = k_shards * ops.padded_k(-(-k // k_shards), policy, k_tile)
        else:
            kp = ops.padded_k(k, policy, k_tile)
        if kp != k:
            x2 = jnp.pad(x2, ((0, 0), (0, kp - k)))
            w = jnp.pad(w, ((0, 0), (0, kp - k)))

    if (batch_chunk is None and backend == "pallas"
            and policy == "sorted_tiled" and sort_impl != "onepass"):
        # the two-pass kernel's pass 1 materializes (chunk, N, K/k_tile)
        # int32 tile sums (+ a same-shape permutation) in HBM; chunk M so
        # that statistic stays bounded instead of scaling with the full
        # batch. Chunking M is exact — every dot is element-independent.
        # (K-sharded: the statistic exists per shard at K_local/k_tile.)
        per_row = 2 * 4 * n * max(kp // k_shards // k_tile, 1)
        batch_chunk = max(_SORT_STATS_BUDGET // per_row, 1)

    kw = dict(
        acc_bits=acc_bits, policy=policy, k_tile=k_tile, rounds=rounds,
        backend=backend, interpret=interpret, block_m=block_m,
        block_n=block_n, sort_impl=sort_impl, batch_chunk=batch_chunk,
        storage=storage, m_group=m_group if storage == "nm" else None,
        nm_impl=nm_impl if storage == "nm" else None, certified=certified,
    )
    # the kernels' device time reads, on a profile, under this scope
    scope = jax.named_scope(
        f"pqs_dot.{policy}" + (".certified" if certified else ""))
    if defer_combine:
        if mesh is not None and k_axis is not None:
            with scope:
                pending = _sharded_dot(
                    x2, w, mesh, m_axes, n_axis, with_census, k_axis=k_axis,
                    defer=True, **kw
                )
        elif mesh is None and k_shards > 1:
            with scope:
                pending = _kshard_dot(
                    x2, w, k_shards=k_shards, with_census=with_census,
                    defer=True, **kw
                )
        else:
            raise ValueError(
                "defer_combine=True needs a K-sharded dot "
                "(k_shards > 1, or mesh= with k_axis=)"
            )

        def finish_full(p):
            o, tot = pending._finish(p)
            o = o.reshape(*lead, n)
            return (o, tot) if with_census else o

        return PendingCombine(pending.partials, finish_full)

    with scope:
        if mesh is not None:
            res = _sharded_dot(
                x2, w, mesh, m_axes, n_axis, with_census, k_axis=k_axis,
                **kw
            )
            out, tot = res if with_census else (res, None)
        elif k_shards > 1:
            out, tot = _kshard_dot(
                x2, w, k_shards=k_shards, with_census=with_census, **kw
            )
        else:
            out, tot = _local_dot(x2, w, with_census=with_census, **kw)
    out = out.reshape(*lead, n)
    if with_census:
        return out, tot
    return out


# ---------------------------------------------------------------------------
# integer execution of QTensor projections (serving path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class IntegerLinConfig:
    """How ``models.layers.lin`` should execute QTensor weights.

    ``mesh`` (+ ``m_axes``/``n_axis``) distributes every integer
    projection via the sharded ``pqs_dot`` path. ``use_static_acts``
    selects the calibrated static activation QParams a QTensor carries
    (``QTensor.act_qparams``, see ``core.qtensor.attach_act_qparams``)
    over the dynamic per-call absmax reduction whenever present.

    ``k_shards`` opts long-K projections into hierarchical K-sharded
    accumulation (per-shard policy partials + the shared static combine
    tree): only layers whose contraction dim is >= ``k_shard_min_k``
    take the hierarchy — shorter projections keep the bit-identical
    full-K path. With a mesh, ``k_axis`` names the mesh axis the K
    shards live on (K-sharded weight placement:
    ``launch.sharding.params_shardings`` with the same
    ``k_axis``/``k_shard_min_k``). ``overlap_combine`` dispatches each
    K-sharded projection through the deferred two-phase path
    (``pqs_dot(defer_combine=True)`` + immediate ``combine()``): bit
    for bit the same result, but the pass-1 registers and the exchange
    tail lower as separate collectives, so XLA's latency-hiding
    scheduler can overlap one site's log2(S) exchange with another
    site's pass-1 compute inside the same jitted serving step
    (double-buffered partials; see docs/accumulation.md).

    ``certificate`` (a ``core.certify.Certificate``) turns on the
    certified serving fast path: sites whose proof reaches this config's
    effective (acc_bits, act_bits) dispatch census-free and
    saturation-free (``pqs_dot(certified=True)``) and are invisible to
    any ``census_monitor`` — bit-identical to the censused path by the
    certificate's subset-sum bound. Sites without a covering proof keep
    the full census + degradation behavior. The engine verifies the
    certificate's weight hashes against the served params at
    construction (``ServingEngine``).
    """

    policy: str = "sorted_tiled_seq"
    acc_bits: int = 16
    k_tile: int = 256
    rounds: int = 1
    act_bits: int = 8
    backend: Optional[str] = None  # None = platform default
    mesh: Any = None  # jax.sharding.Mesh -> distributed pqs_dot
    m_axes: Optional[tuple[str, ...]] = None  # default: mesh data axes
    n_axis: str = "model"
    use_static_acts: bool = True
    k_shards: Optional[int] = None  # K-sharded accumulation (opt-in)
    k_axis: Optional[str] = None  # mesh axis carrying the K shards
    k_shard_min_k: int = 0  # only layers with K >= this take the hierarchy
    overlap_combine: bool = False  # deferred two-phase K-shard combine
    nm_impl: Optional[str] = None  # sparse kernel impl: expand|gather|auto
    # per-site overrides, ((site, value), ...) — the census-degradation
    # hot-swap path: one saturating layer widens without touching the rest
    site_policies: tuple = ()
    site_acc_bits: tuple = ()
    certificate: Any = None  # core.certify.Certificate -> certified path

    def policy_for(self, site: Optional[str]) -> str:
        return dict(self.site_policies).get(site, self.policy)

    def acc_bits_for(self, site: Optional[str]) -> int:
        return dict(self.site_acc_bits).get(site, self.acc_bits)

    def certified_for(self, site: Optional[str], act_bits: int) -> bool:
        """Does the attached certificate prove this site safe as served?"""
        return (
            self.certificate is not None
            and site is not None
            and self.certificate.covers(
                site, self.acc_bits_for(site), act_bits
            )
        )

    def with_site_policy(self, site: str, policy: str) -> "IntegerLinConfig":
        over = dict(self.site_policies)
        over[site] = policy
        return dataclasses.replace(
            self, site_policies=tuple(sorted(over.items()))
        )

    def with_site_acc_bits(self, site: str, bits: int) -> "IntegerLinConfig":
        over = dict(self.site_acc_bits)
        over[site] = int(bits)
        return dataclasses.replace(
            self, site_acc_bits=tuple(sorted(over.items()))
        )

    def without_site(self, site: str) -> "IntegerLinConfig":
        """Drop every per-site override for ``site`` (un-degrade path)."""
        return dataclasses.replace(
            self,
            site_policies=tuple(
                (s, p) for s, p in self.site_policies if s != site
            ),
            site_acc_bits=tuple(
                (s, b) for s, b in self.site_acc_bits if s != site
            ),
        )


_INT_LIN: list[IntegerLinConfig] = []


def integer_lin_config() -> Optional[IntegerLinConfig]:
    return _INT_LIN[-1] if _INT_LIN else None


@contextlib.contextmanager
def integer_lin(cfg: Optional[IntegerLinConfig] = None, **kw):
    """Enable true integer dot products for QTensor projections.

    Inside the context (including jit *tracing* that happens inside it),
    ``lin(x, QTensor)`` quantizes activations dynamically and runs
    ``pqs_dot`` under the configured policy instead of dequantizing the
    weights to float.
    """
    _INT_LIN.append(cfg or IntegerLinConfig(**kw))
    try:
        yield _INT_LIN[-1]
    finally:
        _INT_LIN.pop()


_CALIBRATION: list = []


def calibration_store():
    """Active ``core.quant.ActCalibrator``, or None outside calibration."""
    return _CALIBRATION[-1] if _CALIBRATION else None


@contextlib.contextmanager
def calibration(store):
    """Collect activation ranges at QTensor projection sites.

    Inside the context, ``models.layers.lin`` reports each QTensor
    input's (min, max) to ``store`` (an ``ActCalibrator``) through
    ``jax.debug.callback`` — the execution stays the float dequant path,
    and the callback fires at runtime even from inside scanned layer
    loops. Freeze the result with ``store.freeze()`` +
    ``core.qtensor.attach_act_qparams``.
    """
    _CALIBRATION.append(store)
    try:
        yield store
    finally:
        _CALIBRATION.pop()


class CensusMonitor:
    """Per-site overflow-census accumulator (the runtime guardrail input).

    ``qtensor_dot`` reports, for every named projection site executed
    under a ``census_monitor`` context, the number of dot products and
    the number of overflow events (persistent-or-transient + combine)
    via ``jax.debug.callback`` — counts land here at runtime, including
    from inside jitted/scanned decode steps. ``wide``-policy sites
    report zero events by construction, so a degraded layer's rate
    measurably drops to 0.0. The serving engine drains this window by
    window (``ServingEngine._check_census``).
    """

    def __init__(self):
        self._dots: dict[str, int] = {}
        self._events: dict[str, int] = {}

    def observe(self, site, n_dots, n_events) -> None:
        site = str(site)
        self._dots[site] = self._dots.get(site, 0) + int(n_dots)
        self._events[site] = self._events.get(site, 0) + int(n_events)

    def totals(self) -> dict[str, tuple[int, int]]:
        return {s: (self._dots[s], self._events[s]) for s in self._dots}

    def rates(self) -> dict[str, float]:
        return {
            s: (self._events[s] / self._dots[s] if self._dots[s] else 0.0)
            for s in self._dots
        }

    def drain(self) -> dict[str, tuple[int, int]]:
        out = self.totals()
        self._dots.clear()
        self._events.clear()
        return out


_CENSUS_MON: list[CensusMonitor] = []


def census_monitor_store() -> Optional[CensusMonitor]:
    """Active ``CensusMonitor``, or None when monitoring is off."""
    return _CENSUS_MON[-1] if _CENSUS_MON else None


@contextlib.contextmanager
def census_monitor(mon: Optional[CensusMonitor] = None):
    """Count overflow events per projection site inside the context.

    Like ``calibration``, the context must wrap *tracing*: sites traced
    inside it carry the census callback permanently (for that jitted
    function), sites traced outside never report. Costs one extra
    census reduction per projection — serving enables it only when a
    ``CensusWatch`` is configured.
    """
    mon = mon or CensusMonitor()
    _CENSUS_MON.append(mon)
    try:
        yield mon
    finally:
        _CENSUS_MON.pop()


@dataclasses.dataclass(frozen=True)
class QATQuantConfig:
    """Accumulator-aware QAT at float linear sites (``a2q_qat`` context).

    Inside the context every named ``models.layers.lin`` whose weight is
    still a float 2-D matrix (with min(shape) >= ``min_dim``) runs
    `core.a2q.a2q_fake_quant`: per-channel quantize + accumulator
    projection + dequantize under a straight-through estimator, against
    the sign-split bound for (``acc_bits``, ``act_bits``). Gradients see
    the projected weights, so training co-adapts to the certifiable
    region — the "train" of train→certify→serve.

    ``census_rows`` > 0 adds the overflow census as a *training signal*:
    a stop-gradient sample of that many activation rows is quantized and
    pushed through `core.overflow.census` against the projected integer
    weights, reported per site to any active ``census_monitor`` — the
    same plumbing serving uses, so the QAT signal and the serving watch
    read identically.
    """

    weight_bits: int = 8
    acc_bits: int = 16
    act_bits: int = 8
    min_dim: int = 16
    census_rows: int = 4


_A2Q_QAT: list[QATQuantConfig] = []


def a2q_qat_config() -> Optional[QATQuantConfig]:
    """Active QAT config, or None outside ``a2q_qat``."""
    return _A2Q_QAT[-1] if _A2Q_QAT else None


@contextlib.contextmanager
def a2q_qat(cfg: Optional[QATQuantConfig] = None, **kw):
    """Enable accumulator-aware fake quantization for float lin weights.

    Like ``integer_lin``/``census_monitor``, the context must wrap
    *tracing*: jitted train steps traced inside it carry the STE
    projection (and census callbacks) permanently.
    """
    _A2Q_QAT.append(cfg or QATQuantConfig(**kw))
    try:
        yield _A2Q_QAT[-1]
    finally:
        _A2Q_QAT.pop()


def a2q_qat_lin(
    x: jax.Array, w: jax.Array, qcfg: QATQuantConfig,
    site: Optional[str] = None,
) -> jax.Array:
    """x (..., in) @ w (in, out) with A2Q-projected fake-quant weights."""
    from repro.core.a2q import a2q_fake_quant, a2q_quantize_project

    w_fq = a2q_fake_quant(
        w.T.astype(jnp.float32), qcfg.weight_bits, qcfg.acc_bits,
        act_bits=qcfg.act_bits,
    ).T
    mon = census_monitor_store()
    if mon is not None and site is not None and qcfg.census_rows > 0:
        wq, _ = a2q_quantize_project(
            w.T.astype(jnp.float32), qcfg.weight_bits, qcfg.acc_bits,
            act_bits=qcfg.act_bits,
        )
        xs = jax.lax.stop_gradient(
            x.reshape(-1, x.shape[-1])[: qcfg.census_rows]
        ).astype(jnp.float32)
        qmax = 2 ** (qcfg.act_bits - 1) - 1
        s_x = jnp.maximum(jnp.max(jnp.abs(xs)), 1e-8) / qmax
        xq = jnp.clip(
            jnp.round(xs / s_x), -qmax - 1, qmax
        ).astype(jnp.int32)
        cns = census(partial_products(wq, xq), qcfg.acc_bits)
        jax.debug.callback(
            functools.partial(mon.observe, site), cns.n_dots, cns.n_any
        )
    return (x.astype(jnp.float32) @ w_fq).astype(x.dtype)


def qtensor_dot(
    x: jax.Array, qt, cfg: IntegerLinConfig, site: Optional[str] = None
) -> jax.Array:
    """x (..., in) float @ QTensor (in, out) as an integer PQS dot.

    Activation quantization is dynamic symmetric per-tensor (absmax at
    act_bits) unless the QTensor carries calibrated static
    ``act_qparams`` and ``cfg.use_static_acts`` — then the frozen
    scale/offset is used and decode skips the data-dependent absmax
    reduction entirely (paper §2.1 setup). The integer matmul
    accumulates under cfg.policy at cfg.acc_bits (sharded over
    ``cfg.mesh`` when set); output is rescaled by the activation scale
    and the QTensor's per-channel weight scales.
    """
    from repro.core.qtensor import SparseQTensor

    sparse = isinstance(qt, SparseQTensor)
    if sparse:
        wq, storage = qt, "nm"  # compressed slabs flow straight through
    else:
        wq, storage = qt.values.T.astype(jnp.int32), "dense"  # (out, in)
    aq = getattr(qt, "act_qparams", None)
    if cfg.use_static_acts and aq is not None:
        qmin, qmax = qrange(aq.bits)
        s_x = aq.scale.astype(jnp.float32)
        xq = jnp.clip(
            jnp.round(x.astype(jnp.float32) / s_x) + aq.offset, qmin, qmax
        ).astype(jnp.int32)
    else:
        qmax = 2 ** (cfg.act_bits - 1) - 1
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
        s_x = (amax / qmax).astype(jnp.float32)
        xq = jnp.clip(
            jnp.round(x.astype(jnp.float32) / s_x), -qmax - 1, qmax
        ).astype(jnp.int32)
    ks, ka = cfg.k_shards, cfg.k_axis
    if (ks is not None or ka is not None) and (
        x.shape[-1] < cfg.k_shard_min_k
    ):
        # short-K layers keep the full-K path — also when the shard
        # count is implied by the mesh axis (k_axis= with k_shards=None)
        ks, ka = None, None
    policy = cfg.policy_for(site)
    acc_bits = cfg.acc_bits_for(site)
    # the activation code range actually admissible on this path — the
    # quantity the certificate's bound was taken over
    act_bits_used = int(aq.bits) if (cfg.use_static_acts and aq is not None) \
        else cfg.act_bits
    certified = cfg.certified_for(site, act_bits_used)
    mon = census_monitor_store()
    want_census = (
        mon is not None and site is not None and policy != "wide"
        and not certified
    )
    kshard_active = (
        (cfg.mesh is not None and ka is not None)
        or (cfg.mesh is None and ks is not None and int(ks) > 1)
    )
    defer = bool(cfg.overlap_combine) and kshard_active
    res = pqs_dot(
        xq, wq, acc_bits=acc_bits,
        policy=policy, k_tile=cfg.k_tile, rounds=cfg.rounds,
        backend=cfg.backend, mesh=cfg.mesh, m_axes=cfg.m_axes,
        n_axis=cfg.n_axis, k_shards=ks,
        k_axis=ka if cfg.mesh is not None else None, storage=storage,
        nm_impl=cfg.nm_impl if sparse else None,
        with_census=want_census, certified=certified,
        defer_combine=defer,
    )
    if defer:
        # two-phase dispatch: the exchange tail lowers as its own
        # collective, overlappable with independent compute traced into
        # the same step — the result is bit-identical either way
        res = res.combine()
    if want_census:
        z, cns = res
        jax.debug.callback(
            functools.partial(mon.observe, site),
            cns.n_dots, cns.n_any + cns.n_combine,
        )
    else:
        z = res
        if mon is not None and site is not None and not certified:
            # wide accumulates in int32 — overflow-free by construction;
            # report the dots so a degraded site's rate reads 0.0
            # (certified sites report nothing at all: CensusWatch must
            # never see them, they are provably overflow-free)
            jax.debug.callback(
                functools.partial(mon.observe, site), z.size, 0
            )
    if cfg.use_static_acts and aq is not None and not aq.symmetric:
        # Eq. (3) offset correction — precomputed at freeze time
        # (qtensor.attach_act_qparams), a per-weight constant
        z = z - qt.act_corr
    zf = z.astype(jnp.float32) * (s_x * qt.scale)
    return zf.astype(x.dtype)
