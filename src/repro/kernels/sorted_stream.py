"""Two-pass K-streaming kernels for the global-permutation sort policies.

The legacy ``sorted_matmul.sort_matmul`` keeps the whole (bm, bn, K)
partial-product cube VMEM-resident, which caps compiled calls at
``kernels.ops.MAX_RESIDENT_K``. The kernels here replace the cube with
the operand *slabs* (int8, 4x narrower than the int32 products and bn x
smaller than the cube) plus an O(k_tile) working set, lifting the K
ceiling from 4096 to ``kernels.ops.MAX_STREAM_K`` (65536 by default):

``sorted_tiled`` — two genuine passes over K:

  pass 1  ``tile_sums_matmul``: stream k_tiles through the grid (MXU dot
          per tile) into a (M, N, K/k_tile) tile-sum statistic. Sorting
          a tile never changes its sum, so these raw-product sums equal
          the oracle's post-sort sums exactly (int32 addition is
          associative; k_tile * 127^2 is far below 2^31).
  pairing ``core.sorted_accum.pair_permutation`` over the tile sums —
          literally the oracle's rank-and-interleave rule, evaluated
          once outside the kernels on the small (M, N, n_tiles) array.
  pass 2  ``paired_accum_matmul``: revisit K in *paired* order. The
          pairing is per output element (each (m, n) dot ranks its own
          tile sums), so a permutation-driven BlockSpec index map —
          which is necessarily uniform across the (bm, bn) block —
          cannot realize it. Instead the int8 operand slabs stay
          resident, and each pair slot gathers its two k_tiles per
          element (``take_along_axis`` over the K axis), bitonic-sorts
          them intra-tile, element-interleaves (a0, b0, a1, b1, ...)
          and saturating-accumulates stepwise. Only the (bm, bn,
          2*k_tile) interleaved pair is ever materialized as products.

``sorted`` — the order is one split/sort/pair stage over the *whole* K
axis per element, so the product cube genuinely must exist to be
sorted; ``chunked_sort_matmul`` bounds it by chunking the bn axis
inside the kernel ((bm, bc, K) live at a time, bc chosen so the chunk
stays under ``CUBE_BUDGET`` bytes) while the int8 slabs stay resident.

VMEM budget (pass 2, defaults bm=8, bn=128, k_tile=256, K=32768):
x slab 8*32Ki = 256 KiB int8, w slab 128*32Ki = 4 MiB int8, perm block
8*128*128*4 = 512 KiB, working pair 8*128*512*4 = 2 MiB — ~7 MiB total
vs the 128 MiB cube the one-pass kernel would need.

HBM budget: the tile-sum statistic and its permutation are
(M, N, K/k_tile) int32 each — per-M-row cost 8 * N * K/k_tile bytes.
``core.dispatch.pqs_dot`` bounds it by chunking M (its
``_SORT_STATS_BUDGET``); direct callers of ``stream_sort_matmul`` with
large M*N should chunk M themselves.

Semantics are bit-exact with ``core.overflow.accumulate`` (the jnp
oracle) and with the legacy one-pass ``sort_matmul`` where that still
runs; ``tests/test_sorted_stream.py`` sweeps both, including K well
above ``MAX_RESIDENT_K``. Mosaic lowering of the per-element gather on
real TPUs is untested (same standing caveat as the in-kernel argsort of
the one-pass kernel); interpret mode is exact everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sorted_accum import pair_permutation
from repro.kernels.bitonic import sorted_order_bitonic
from repro.kernels.nm_spmm import (
    _next_pow2,
    expand_nm_slab,
    gather_nm_products,
    pad_last_pow2,
)
from repro.kernels.sorted_matmul import SORT_POLICIES, _stepwise

# Largest (bm, bc, K) int32 product chunk chunked_sort_matmul keeps live
# while sorting (the bitonic network roughly doubles it with temporaries).
CUBE_BUDGET = 4 * 1024 * 1024


def _tile_sums_kernel(x_ref, w_ref, o_ref):
    xb = x_ref[...].astype(jnp.int32)  # (bm, k_tile)
    wb = w_ref[...].astype(jnp.int32)  # (bn, k_tile)
    o_ref[:, :, 0] = jax.lax.dot_general(
        xb, wb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )


@functools.partial(
    jax.jit, static_argnames=("k_tile", "bm", "bn", "interpret")
)
def tile_sums_matmul(
    x: jax.Array,  # (M, K) int
    w: jax.Array,  # (N, K) int
    *,
    k_tile: int = 256,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pass 1: per-element per-k_tile partial sums, (M, N, K/k_tile) int32.

    One MXU dot per (i, j, t) grid step — K streams through the grid, so
    VMEM holds only the (bm, k_tile) / (bn, k_tile) slabs plus a
    (bm, bn, 1) output block.
    """
    m, k = x.shape
    n, k2 = w.shape
    assert k == k2 and k % k_tile == 0, (x.shape, w.shape, k_tile)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    n_tiles = k // k_tile
    return pl.pallas_call(
        _tile_sums_kernel,
        grid=(m // bm, n // bn, n_tiles),
        in_specs=[
            pl.BlockSpec((bm, k_tile), lambda i, j, t: (i, t)),
            pl.BlockSpec((bn, k_tile), lambda i, j, t: (j, t)),
        ],
        out_specs=pl.BlockSpec((bm, bn, 1), lambda i, j, t: (i, j, t)),
        out_shape=jax.ShapeDtypeStruct((m, n, n_tiles), jnp.int32),
        interpret=interpret,
    )(x, w)


def _nm_tile_sums_kernel(x_ref, v_ref, i_ref, o_ref, *, m_group: int):
    xb = x_ref[...].astype(jnp.int32)  # (bm, k_tile)
    wb = expand_nm_slab(v_ref[...], i_ref[...], m_group)  # (bn, k_tile)
    o_ref[:, :, 0] = jax.lax.dot_general(
        xb, wb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )


@functools.partial(
    jax.jit, static_argnames=("m_group", "k_tile", "bm", "bn", "interpret")
)
def nm_tile_sums_matmul(
    x: jax.Array,  # (M, K) int, K = G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    m_group: int = 16,
    k_tile: int = 256,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pass-1 hook for compressed storage: per-k_tile partial sums,
    (M, N, K/k_tile) int32, streamed from the COMPRESSED slabs.

    Sorting a tile never changes its sum and pruned positions are zero,
    so the kept-only dot per tile equals the dense tile sum exactly —
    the pairing permutation downstream is therefore identical to the
    dense pipeline's while HBM traffic for weights drops by ~n_keep/m
    (the paper's pruning payoff, measured in `pqs_dot(with_census=True)`
    overflow counts as shorter effective K per tile).
    """
    m, k = x.shape
    n, g, n_keep = values.shape
    assert k == g * m_group and k % k_tile == 0, (x.shape, values.shape,
                                                 m_group, k_tile)
    assert k_tile % m_group == 0, (k_tile, m_group)
    bg = k_tile // m_group
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    n_tiles = k // k_tile
    kern = functools.partial(_nm_tile_sums_kernel, m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, n_tiles),
        in_specs=[
            pl.BlockSpec((bm, k_tile), lambda i, j, t: (i, t)),
            pl.BlockSpec((bn, bg, n_keep), lambda i, j, t: (j, t, 0)),
            pl.BlockSpec((bn, bg, n_keep), lambda i, j, t: (j, t, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn, 1), lambda i, j, t: (i, j, t)),
        out_shape=jax.ShapeDtypeStruct((m, n, n_tiles), jnp.int32),
        interpret=interpret,
    )(x, values, indices)


def _gather_tile(xb, wb, tile_idx, k_tile):
    """Products of one k_tile per element: (bm, bn) tile indices ->
    (bm, bn, k_tile) int32. xb is (bm, K), wb is (bn, K)."""
    ks = tile_idx[:, :, None] * k_tile + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, k_tile), 2
    )  # (bm, bn, k_tile) absolute K offsets
    xg = jnp.take_along_axis(xb[:, None, :], ks, axis=-1)
    wg = jnp.take_along_axis(wb[None, :, :], ks, axis=-1)
    return xg * wg


def _paired_body(xb, wb, pm, o_ref, acc_bits: int, k_tile: int,
                 rounds: int):
    """Shared pass-2 body: accumulate K in per-element paired order.

    xb (bm, K) / wb (bn, K) int32 slabs, pm (bm, bn, n_tiles) pairing —
    the dense and nm kernels differ only in how wb reaches VMEM."""
    n_tiles = pm.shape[-1]
    bm, bn = xb.shape[0], wb.shape[0]

    def slot(s, acc):
        pa = _gather_tile(xb, wb, pm[:, :, 2 * s], k_tile)
        pb = _gather_tile(xb, wb, pm[:, :, 2 * s + 1], k_tile)
        pa = sorted_order_bitonic(pa, rounds)
        pb = sorted_order_bitonic(pb, rounds)
        inter = jnp.stack([pa, pb], axis=-1).reshape(bm, bn, 2 * k_tile)
        return _stepwise(jnp.moveaxis(inter, -1, 0), acc, acc_bits,
                         saturate=True)

    acc = jax.lax.fori_loop(
        0, n_tiles // 2, slot, jnp.zeros_like(o_ref)
    )
    if n_tiles % 2:  # unpaired leftover tile rides last, un-interleaved
        tail = _gather_tile(xb, wb, pm[:, :, n_tiles - 1], k_tile)
        tail = jnp.moveaxis(sorted_order_bitonic(tail, rounds), -1, 0)
        acc = _stepwise(tail, acc, acc_bits, saturate=True)
    o_ref[...] = acc


def _paired_kernel(x_ref, w_ref, p_ref, o_ref, *, acc_bits: int,
                   k_tile: int, rounds: int):
    xb = x_ref[...].astype(jnp.int32)  # (bm, K) slab
    wb = w_ref[...].astype(jnp.int32)  # (bn, K) slab
    pm = p_ref[...]  # (bm, bn, n_tiles) per-element pairing permutation
    _paired_body(xb, wb, pm, o_ref, acc_bits, k_tile, rounds)


def _nm_paired_kernel(x_ref, v_ref, i_ref, p_ref, o_ref, *, acc_bits: int,
                      k_tile: int, rounds: int, m_group: int):
    """Pass 2 fed by the compressed slab: HBM streams (bn, G, n_keep)
    values+indices instead of the (bn, K) dense rows; the one-hot expand
    rebuilds the dense slab in VMEM (bit-identical — pruned positions
    expand to zero) and the paired gather proceeds unchanged."""
    xb = x_ref[...].astype(jnp.int32)  # (bm, K) slab
    wb = expand_nm_slab(v_ref[...], i_ref[...], m_group)  # (bn, G*m)
    pm = p_ref[...]
    _paired_body(xb, wb, pm, o_ref, acc_bits, k_tile, rounds)


@functools.partial(
    jax.jit,
    static_argnames=("acc_bits", "k_tile", "rounds", "bm", "bn",
                     "interpret"),
)
def paired_accum_matmul(
    x: jax.Array,  # (M, K) int
    w: jax.Array,  # (N, K) int
    perm: jax.Array,  # (M, N, K/k_tile) int32 pairing permutation
    *,
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pass 2: accumulate K in per-element paired order, (M, N) int32."""
    m, k = x.shape
    n = w.shape[0]
    assert perm.shape == (m, n, k // k_tile), (perm.shape, (m, n, k, k_tile))
    assert k_tile & (k_tile - 1) == 0 and k % k_tile == 0, (k, k_tile)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    n_tiles = k // k_tile
    kern = functools.partial(_paired_kernel, acc_bits=acc_bits,
                             k_tile=k_tile, rounds=rounds)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, bn, n_tiles), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, w, perm)


@functools.partial(
    jax.jit,
    static_argnames=("acc_bits", "k_tile", "rounds", "m_group", "bm", "bn",
                     "interpret"),
)
def nm_paired_accum_matmul(
    x: jax.Array,  # (M, K) int, K = G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    perm: jax.Array,  # (M, N, K/k_tile) int32 pairing permutation
    *,
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pass 2 on compressed storage: per-element paired accumulation."""
    m, k = x.shape
    n, g, n_keep = values.shape
    assert k == g * m_group, (x.shape, values.shape, m_group)
    assert perm.shape == (m, n, k // k_tile), (perm.shape, (m, n, k, k_tile))
    assert k_tile & (k_tile - 1) == 0 and k % k_tile == 0, (k, k_tile)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    n_tiles = k // k_tile
    kern = functools.partial(_nm_paired_kernel, acc_bits=acc_bits,
                             k_tile=k_tile, rounds=rounds, m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bm, bn, n_tiles), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, values, indices, perm)


def _sort_chunk_body(xb, wb, o_ref, c, bc, acc_bits: int, rounds: int):
    """Sort-and-accumulate one (bm, bc, K) cube chunk into o_ref's c-th
    column slice — shared by the dense and N:M compressed kernels (they
    differ only in how the (bc, K) weight chunk reaches VMEM)."""
    prods = xb[:, None, :] * wb[None, :, :]  # (bm, bc, K) live chunk
    ordered = sorted_order_bitonic(prods, rounds)
    o_ref[:, pl.ds(c * bc, bc)] = _stepwise(
        jnp.moveaxis(ordered, -1, 0), jnp.zeros((xb.shape[0], bc), jnp.int32),
        acc_bits, saturate=True,
    )


def _chunked_sort_kernel(x_ref, w_ref, o_ref, *, acc_bits: int, bc: int,
                         rounds: int):
    xb = x_ref[...].astype(jnp.int32)  # (bm, K) slab

    def chunk(c, _):
        wb = w_ref[pl.ds(c * bc, bc), :].astype(jnp.int32)  # (bc, K)
        _sort_chunk_body(xb, wb, o_ref, c, bc, acc_bits, rounds)
        return 0

    n_chunks = o_ref.shape[1] // bc
    jax.lax.fori_loop(0, n_chunks, chunk, 0)


@functools.partial(
    jax.jit,
    static_argnames=("acc_bits", "rounds", "bm", "bn", "bc", "interpret"),
)
def chunked_sort_matmul(
    x: jax.Array,  # (M, K) int, K a power of two
    w: jax.Array,  # (N, K) int
    *,
    acc_bits: int = 16,
    rounds: int = 1,
    bm: int = 8,
    bn: int = 128,
    bc: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Full-K ``sorted`` policy with a bn-chunked product cube.

    The global sort needs all K products of an element live at once, but
    only for ``bc`` output channels at a time: (bm, bc, K) int32 must fit
    ``CUBE_BUDGET``; the (bm, K)/(bn, K) int8 slabs are what scale with K.
    """
    m, k = x.shape
    n = w.shape[0]
    assert k & (k - 1) == 0, f"K must be a power of 2, got {k}"
    assert m % bm == 0 and n % bn == 0 and bn % bc == 0, (m, n, bm, bn, bc)
    kern = functools.partial(_chunked_sort_kernel, acc_bits=acc_bits,
                             bc=bc, rounds=rounds)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, w)


def _nm_chunked_sort_kernel(x_ref, v_ref, i_ref, o_ref, *, acc_bits: int,
                            bc: int, rounds: int, m_group: int):
    """``sorted`` on compressed storage: expand only the bc-row slice of
    the compressed slab per chunk, so the live int32 working set stays
    (bm, bc, K) + (bc, K) — the dense kernel's budget."""
    xb = x_ref[...].astype(jnp.int32)  # (bm, kp) slab (pre-padded)
    kp = xb.shape[1]

    def chunk(c, _):
        vc = v_ref[pl.ds(c * bc, bc), :, :]  # (bc, G, n_keep)
        ic = i_ref[pl.ds(c * bc, bc), :, :]
        wb = expand_nm_slab(vc, ic, m_group)  # (bc, G*m)
        if kp > wb.shape[1]:
            wb = jnp.pad(wb, ((0, 0), (0, kp - wb.shape[1])))
        _sort_chunk_body(xb, wb, o_ref, c, bc, acc_bits, rounds)
        return 0

    n_chunks = o_ref.shape[1] // bc
    jax.lax.fori_loop(0, n_chunks, chunk, 0)


@functools.partial(
    jax.jit,
    static_argnames=("acc_bits", "rounds", "m_group", "bm", "bn", "bc",
                     "interpret"),
)
def nm_chunked_sort_matmul(
    x: jax.Array,  # (M, kp) int, kp a power of two >= G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    acc_bits: int = 16,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    bc: int = 8,
    interpret: bool = False,
) -> jax.Array:
    m, kp = x.shape
    n, g, n_keep = values.shape
    assert g * m_group <= kp, (values.shape, m_group, kp)
    assert kp & (kp - 1) == 0, f"K must be a power of 2, got {kp}"
    assert m % bm == 0 and n % bn == 0 and bn % bc == 0, (m, n, bm, bn, bc)
    kern = functools.partial(_nm_chunked_sort_kernel, acc_bits=acc_bits,
                             bc=bc, rounds=rounds, m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, values, indices)


def _sort_chunk(bm: int, bn: int, k: int) -> int:
    """Largest bc dividing bn with the (bm, bc, K) int32 chunk in budget."""
    for bc in range(bn, 1, -1):
        if bn % bc == 0 and bm * bc * k * 4 <= CUBE_BUDGET:
            return bc
    return 1


def stream_sort_matmul(
    x: jax.Array,  # (M, K) int — M, N multiples of bm, bn; K pre-padded
    w: jax.Array,  # (N, K) int
    *,
    policy: str = "sorted",
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Streaming entry point for ``sorted`` | ``sorted_tiled``.

    Same contract as ``sorted_matmul.sort_matmul`` (callers zero-pad; the
    padding rules are identical) but with slab-bounded VMEM, so
    ``kernels.ops.policy_matmul`` routes K above ``MAX_RESIDENT_K`` here.
    """
    assert policy in SORT_POLICIES, policy
    if policy == "sorted":
        return chunked_sort_matmul(
            x, w, acc_bits=acc_bits, rounds=rounds, bm=bm, bn=bn,
            bc=_sort_chunk(bm, bn, x.shape[1]), interpret=interpret,
        )
    sums = tile_sums_matmul(x, w, k_tile=k_tile, bm=bm, bn=bn,
                            interpret=interpret)
    perm = jax.jit(pair_permutation)(sums)
    return paired_accum_matmul(
        x, w, perm, acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
        bm=bm, bn=bn, interpret=interpret,
    )


def nm_stream_sort_matmul(
    x: jax.Array,  # (M, kp) int — pre-padded like stream_sort_matmul's x
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    policy: str = "sorted",
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Streaming global-sort entry point for N:M compressed storage.

    Same contract as ``stream_sort_matmul`` but the weight operand stays
    compressed end-to-end: pass 1 computes tile sums straight from the
    compressed slabs (``nm_tile_sums_matmul``), the pairing permutation
    is the shared ``pair_permutation``, and pass 2 / the chunked cube
    expand in VMEM only. Bit-identical to decompress-then-dense.
    """
    assert policy in SORT_POLICIES, policy
    if policy == "sorted":
        return nm_chunked_sort_matmul(
            x, values, indices, acc_bits=acc_bits, rounds=rounds,
            m_group=m_group, bm=bm, bn=bn,
            bc=_sort_chunk(bm, bn, x.shape[1]), interpret=interpret,
        )
    sums = nm_tile_sums_matmul(x, values, indices, m_group=m_group,
                               k_tile=k_tile, bm=bm, bn=bn,
                               interpret=interpret)
    perm = jax.jit(pair_permutation)(sums)
    return nm_paired_accum_matmul(
        x, values, indices, perm, acc_bits=acc_bits, k_tile=k_tile,
        rounds=rounds, m_group=m_group, bm=bm, bn=bn, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# fused activation-gather variants: never rebuild the dense slab in VMEM
# ---------------------------------------------------------------------------


def _nm_gather_tile_sums_kernel(x_ref, v_ref, i_ref, o_ref, *,
                                m_group: int):
    """Pass 1 from kept products only: sum of the gathered (bm, bn,
    bg*n_keep) products per tile == the dense tile sum exactly (pruned
    positions contribute zero to any sum), so the downstream pairing
    permutation is identical to both the dense and expand pipelines'."""
    xb = x_ref[...].astype(jnp.int32)  # (bm, k_tile)
    prods = gather_nm_products(xb, v_ref[...], i_ref[...], m_group)
    o_ref[:, :, 0] = jnp.sum(prods, axis=-1)


@functools.partial(
    jax.jit, static_argnames=("m_group", "k_tile", "bm", "bn", "interpret")
)
def nm_gather_tile_sums(
    x: jax.Array,  # (M, K) int, K = G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    m_group: int = 16,
    k_tile: int = 256,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Gather twin of ``nm_tile_sums_matmul``: per-k_tile sums from
    n_keep/m of the products (a VPU gather-multiply-reduce instead of
    the expand path's dense MXU dot)."""
    m, k = x.shape
    n, g, n_keep = values.shape
    assert k == g * m_group and k % k_tile == 0, (x.shape, values.shape,
                                                 m_group, k_tile)
    assert k_tile % m_group == 0, (k_tile, m_group)
    bg = k_tile // m_group
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    n_tiles = k // k_tile
    kern = functools.partial(_nm_gather_tile_sums_kernel, m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, n_tiles),
        in_specs=[
            pl.BlockSpec((bm, k_tile), lambda i, j, t: (i, t)),
            pl.BlockSpec((bn, bg, n_keep), lambda i, j, t: (j, t, 0)),
            pl.BlockSpec((bn, bg, n_keep), lambda i, j, t: (j, t, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn, 1), lambda i, j, t: (i, j, t)),
        out_shape=jax.ShapeDtypeStruct((m, n, n_tiles), jnp.int32),
        interpret=interpret,
    )(x, values, indices)


def _nm_gather_paired_kernel(x_ref, v_ref, i_ref, p_ref, o_ref, *,
                             acc_bits: int, k_tile: int, rounds: int,
                             m_group: int):
    """Pass 2 on kept products: each pair slot gathers its two
    *compressed* tiles (lc = (k_tile/m)*n_keep kept entries each, the
    per-element tile indices addressing the flattened (bn, G*n_keep)
    slab), pow2-pads, sorts, interleaves and stepwise-accumulates.

    Bit-exact vs the expand path because each sorted padded kept tile is
    the sorted dense tile's nonzero-covering prefix (positives descend /
    negatives ascend identically; the dense tail past the kept count is
    all zeros) and interleaved zero pairs are stepwise-inert.
    """
    xb = x_ref[...].astype(jnp.int32)  # (bm, kp) slab
    vals = v_ref[...]  # (bn, G, n_keep)
    idx = i_ref[...]
    pm = p_ref[...]  # (bm, bn, n_tiles)
    bn, g, n_keep = vals.shape
    bm = xb.shape[0]
    n_tiles = pm.shape[-1]
    lc = (k_tile // m_group) * n_keep  # kept entries per compressed tile
    base = jax.lax.broadcasted_iota(
        jnp.int32, (bn, g, n_keep), 1) * m_group
    posd = (idx.astype(jnp.int32) + base).reshape(bn, g * n_keep)
    vflat = vals.reshape(bn, g * n_keep).astype(jnp.int32)

    def ctile(t_idx):
        """(bm, bn) tile indices -> pow2-padded (bm, bn, lp) kept
        products of that k_tile."""
        cs = t_idx[:, :, None] * lc + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, lc), 2
        )  # (bm, bn, lc) offsets into the flat compressed axis
        cs = jnp.broadcast_to(cs, (bm, bn, lc))
        wg = jnp.take_along_axis(
            jnp.broadcast_to(vflat[None], (bm, bn, g * n_keep)), cs, axis=-1)
        pg = jnp.take_along_axis(
            jnp.broadcast_to(posd[None], (bm, bn, g * n_keep)), cs, axis=-1)
        xg = jnp.take_along_axis(
            jnp.broadcast_to(xb[:, None, :], (bm, bn, xb.shape[1])),
            pg, axis=-1)
        return pad_last_pow2(xg * wg)

    lp = _next_pow2(lc)

    def slot(s, acc):
        pa = sorted_order_bitonic(ctile(pm[:, :, 2 * s]), rounds)
        pb = sorted_order_bitonic(ctile(pm[:, :, 2 * s + 1]), rounds)
        inter = jnp.stack([pa, pb], axis=-1).reshape(bm, bn, 2 * lp)
        return _stepwise(jnp.moveaxis(inter, -1, 0), acc, acc_bits,
                         saturate=True)

    acc = jax.lax.fori_loop(0, n_tiles // 2, slot, jnp.zeros_like(o_ref))
    if n_tiles % 2:  # unpaired leftover tile rides last, un-interleaved
        tail = sorted_order_bitonic(ctile(pm[:, :, n_tiles - 1]), rounds)
        acc = _stepwise(jnp.moveaxis(tail, -1, 0), acc, acc_bits,
                        saturate=True)
    o_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("acc_bits", "k_tile", "rounds", "m_group", "bm", "bn",
                     "interpret"),
)
def nm_gather_paired_accum_matmul(
    x: jax.Array,  # (M, K) int, K = G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    perm: jax.Array,  # (M, N, K/k_tile) int32 pairing permutation
    *,
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Gather twin of ``nm_paired_accum_matmul``: the working pair is
    (bm, bn, 2*next_pow2((k_tile/m)*n_keep)) int32 — n_keep/m of the
    expand path's (bm, bn, 2*k_tile) — and no dense slab is rebuilt."""
    m, k = x.shape
    n, g, n_keep = values.shape
    assert k == g * m_group, (x.shape, values.shape, m_group)
    assert perm.shape == (m, n, k // k_tile), (perm.shape, (m, n, k, k_tile))
    assert k_tile & (k_tile - 1) == 0 and k % k_tile == 0, (k, k_tile)
    assert k_tile % m_group == 0, (k_tile, m_group)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    n_tiles = k // k_tile
    kern = functools.partial(_nm_gather_paired_kernel, acc_bits=acc_bits,
                             k_tile=k_tile, rounds=rounds, m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bm, bn, n_tiles), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, values, indices, perm)


def _nm_gather_chunked_sort_kernel(x_ref, v_ref, i_ref, o_ref, *,
                                   acc_bits: int, bc: int, rounds: int,
                                   m_group: int):
    """``sorted`` on kept products: per bc-chunk, gather the chunk rows'
    kept products ((bm, bc, G*n_keep) instead of (bm, bc, kp)), pow2-pad,
    sort, stepwise-accumulate. The sorted kept stream is the sorted
    dense stream's nonzero-covering prefix, so saturation matches."""
    xb = x_ref[...].astype(jnp.int32)  # (bm, kp) slab (pre-padded)

    def chunk(c, _):
        vc = v_ref[pl.ds(c * bc, bc), :, :]  # (bc, G, n_keep)
        ic = i_ref[pl.ds(c * bc, bc), :, :]
        prods = gather_nm_products(xb, vc, ic, m_group)
        ordered = sorted_order_bitonic(pad_last_pow2(prods), rounds)
        o_ref[:, pl.ds(c * bc, bc)] = _stepwise(
            jnp.moveaxis(ordered, -1, 0),
            jnp.zeros((xb.shape[0], bc), jnp.int32), acc_bits, saturate=True,
        )
        return 0

    n_chunks = o_ref.shape[1] // bc
    jax.lax.fori_loop(0, n_chunks, chunk, 0)


@functools.partial(
    jax.jit,
    static_argnames=("acc_bits", "rounds", "m_group", "bm", "bn", "bc",
                     "interpret"),
)
def nm_gather_chunked_sort_matmul(
    x: jax.Array,  # (M, kp) int, kp a power of two >= G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    acc_bits: int = 16,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    bc: int = 8,
    interpret: bool = False,
) -> jax.Array:
    m, kp = x.shape
    n, g, n_keep = values.shape
    assert g * m_group <= kp, (values.shape, m_group, kp)
    assert kp & (kp - 1) == 0, f"K must be a power of 2, got {kp}"
    assert m % bm == 0 and n % bn == 0 and bn % bc == 0, (m, n, bm, bn, bc)
    kern = functools.partial(_nm_gather_chunked_sort_kernel,
                             acc_bits=acc_bits, bc=bc, rounds=rounds,
                             m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, values, indices)


def nm_gather_stream_sort_matmul(
    x: jax.Array,  # (M, kp) int — pre-padded like stream_sort_matmul's x
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    policy: str = "sorted",
    acc_bits: int = 16,
    k_tile: int = 256,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Gather twin of ``nm_stream_sort_matmul``: same contract, but no
    kernel ever rebuilds a dense weight slab — pass 1 sums gathered kept
    products, pass 2 / the chunked cube sort only kept products. The
    chunked ``sorted`` cube budget is sized by the *compressed* length,
    so bc (channels sorted at once) grows by ~m/n_keep."""
    assert policy in SORT_POLICIES, policy
    if policy == "sorted":
        _, g, n_keep = values.shape
        return nm_gather_chunked_sort_matmul(
            x, values, indices, acc_bits=acc_bits, rounds=rounds,
            m_group=m_group, bm=bm, bn=bn,
            bc=_sort_chunk(bm, bn, _next_pow2(g * n_keep)),
            interpret=interpret,
        )
    sums = nm_gather_tile_sums(x, values, indices, m_group=m_group,
                               k_tile=k_tile, bm=bm, bn=bn,
                               interpret=interpret)
    perm = jax.jit(pair_permutation)(sums)
    return nm_gather_paired_accum_matmul(
        x, values, indices, perm, acc_bits=acc_bits, k_tile=k_tile,
        rounds=rounds, m_group=m_group, bm=bm, bn=bn, interpret=interpret,
    )
