"""N:M compressed-weight matmul kernels: expand-in-VMEM and fused gather.

Weights pruned to keep n of every m along K are stored compressed:
    values  (N, K//m, n_keep) int8
    indices (N, K//m, n_keep) int32   (position of each kept value in its
                                       m-group; padded groups use idx 0,
                                       value 0)
The kernels stream the *compressed* form from HBM — an m/n_keep bandwidth
saving, which is the term that matters for decode (DESIGN.md §2).

Two implementations of every policy x sparse-storage composition
(selected by ``kernels.ops.nm_policy_matmul`` via ``nm_impl`` /
``REPRO_PQS_NM_IMPL``):

expand (``nm_seq_policy_matmul`` / ``nm_sort_matmul``) — expand the
  compressed slab to a dense block in VMEM (no gathers) and feed the
  exact dense ``sorted_matmul`` kernel bodies. The K-streaming kernel
  streams K-major (n_keep, bg, bn) slabs and expands them with selects
  (``expand_nm_block``), the form that compiles for TPU; the
  interpret-only sort kernels use the one-hot ``expand_nm_slab``.
  Saves bytes, not FLOPs:
  the contraction still runs over the full dense K. The expanded slab is
  bit-identical to the dense weight block (pruned positions expand to
  zero, and zero partial products are sign-neutral and additively inert
  through sort, saturation, and wraparound), so this path is the
  bit-exactness ORACLE for the gather path below.

gather (``nm_gather_seq_policy_matmul`` / ``nm_gather_sort_matmul``) —
  never build the dense block: per m-group, gather the n_keep KEPT
  activation entries through the index slab (``gather_nm_products``) and
  contract only the (bm, bn, G*n_keep) kept products — n_keep/m of the
  dense work, which is the PQS paper's actual pruning payoff (2:4 ⇒ ~2x
  fewer products formed and accumulated). Bit-exactness relies on the
  zero-product prefix property: the dense product stream of a dot equals
  its kept-product stream plus zeros at the pruned positions, and zeros
  are inert through every policy stage (a bitonic pairwise round maps a
  stream-with-extra-zeros to the same output with the zeros still inert,
  so per-tile/global sorted orders agree on their nonzero prefix; clip
  keeps the register in range so clip(acc+0) == acc; wrap is a mod
  identity on in-range values). The bitonic network needs a power-of-two
  length, so gathered tiles pad L = bg*n_keep up to next_pow2(L) <=
  bg*m — still at most the dense tile, usually far below it.

Expansion cost is n_keep*m selects per weight; the gather is one
dynamic-index load per kept product (same per-element ``take_along_axis``
idiom as ``sorted_stream._gather_tile``). The gather kernels do not lower
to Mosaic: they run in interpret mode only, and ``kernels.ops`` refuses
them on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pruning import nm_onehot_expand
from repro.core.sorted_accum import tiled_sorted_order
from repro.kernels.bitonic import sorted_order_bitonic
from repro.kernels.sorted_matmul import (
    SEQ_POLICIES,
    SORT_POLICIES,
    _seq_body,
    _sort_body,
    _stepwise,
)


def expand_nm_slab(vals: jax.Array, idx: jax.Array, m_group: int
                   ) -> jax.Array:
    """(bn, bg, n_keep) compressed slab -> dense (bn, bg*m_group) int32.

    Delegates to ``core.pruning.nm_onehot_expand`` — the single
    definition of compressed->dense shared with the jnp decompress
    oracle, so both storage backends realize identical dense blocks.
    Padded slots (value 0, index 0) and zero-padded groups expand to
    zeros, equal to the dense weight block exactly.
    """
    return nm_onehot_expand(vals.astype(jnp.int32), idx, m_group)


# ---------------------------------------------------------------------------
# policy x sparse-storage composition kernels
# ---------------------------------------------------------------------------


def expand_nm_block(vals: jax.Array, idx: jax.Array, m_group: int
                    ) -> jax.Array:
    """K-major compressed block (n_keep, bg, bn) -> dense (bg*m, bn) int8.

    Rows come out p-major: row ``p*bg + g`` holds K offset ``g*m + p`` of
    the block (``nm_seq_policy_matmul`` permutes the activation columns
    the same way). Each dense position receives at most one kept value
    (padded slots carry value 0), so the select-sum is exact — the same
    expansion as ``core.pruning.nm_onehot_expand``, written as (bg, bn)
    selects that the TPU compiler lowers, with no lane-merging reshape.
    """
    v, i = vals.astype(jnp.int32), idx.astype(jnp.int32)
    rows = [
        functools.reduce(jnp.add, [jnp.where(i[j] == p, v[j], 0)
                                   for j in range(v.shape[0])])
        for p in range(m_group)
    ]
    return jnp.concatenate(rows, axis=0).astype(jnp.int8)


def _nm_seq_kernel(x_ref, v_ref, i_ref, o_ref, *, policy: str,
                   acc_bits: int, rounds: int, k_tile: int, m_group: int,
                   interpret: bool):
    """``sorted_matmul._seq_body`` fed by the in-VMEM expand block."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bg = v_ref.shape[1]
    cols = [p * bg + g for g in range(bg) for p in range(m_group)]
    _seq_body(x_ref[...], expand_nm_block(v_ref[...], i_ref[...], m_group),
              o_ref, policy=policy, acc_bits=acc_bits, rounds=rounds,
              k_tile=k_tile, interpret=interpret, cols=cols)


@functools.partial(
    jax.jit,
    static_argnames=("policy", "acc_bits", "rounds", "m_group", "bm", "bn",
                     "bg", "k_tile", "interpret"),
)
def nm_seq_policy_matmul(
    x: jax.Array,  # (M, K) int8, K = G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    policy: str = "clip",
    acc_bits: int = 16,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    bg: int = 16,
    k_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """K-streaming policies on compressed storage: wide|clip|wrap|
    sorted_tiled_seq. A grid step covers bk = bg * m_group dense K
    columns; for sorted_tiled_seq k_tile (a power of two) must divide bk,
    so tile boundaries coincide with the dense kernel's.

    The slabs stream K-major as (n_keep, G, N) so a block's N rides the
    lanes, and the activations' columns are permuted p-major within each
    block to match ``expand_nm_block``'s rows: the wide dot is one MXU
    matmul, and the order-sensitive policies still visit K naturally."""
    m, k = x.shape
    n, g, n_keep = values.shape
    assert k == g * m_group, (x.shape, values.shape, m_group)
    assert x.dtype == jnp.int8, x.dtype
    assert policy in SEQ_POLICIES, policy
    bk = bg * m_group
    if policy == "sorted_tiled_seq":
        assert k_tile & (k_tile - 1) == 0 and bk % k_tile == 0, (bk, k_tile)
    assert m % bm == 0 and n % bn == 0 and g % bg == 0, (m, n, g, bm, bn, bg)
    grid = (m // bm, n // bn, g // bg)
    xp = x.reshape(m, g // bg, bg, m_group).swapaxes(-1, -2).reshape(m, k)
    vt = values.transpose(2, 1, 0).astype(jnp.int32)
    it = indices.transpose(2, 1, 0).astype(jnp.int32)
    kern = functools.partial(_nm_seq_kernel, policy=policy,
                             acc_bits=acc_bits, rounds=rounds,
                             k_tile=k_tile, m_group=m_group,
                             interpret=interpret)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(xp, vt, it)


def _nm_sort_kernel(x_ref, v_ref, i_ref, o_ref, *, policy: str,
                    acc_bits: int, k_tile: int, rounds: int, m_group: int):
    """``sorted_matmul._sort_body`` with the w slab expanded in VMEM.

    x arrives pre-padded to the dense padded K (kp); the expanded slab
    covers G*m <= kp columns and is zero-extended to kp in-kernel (the
    ``sorted`` power-of-two pad) — zeros sort inertly, so the product
    cube equals the dense kernel's exactly.
    """
    xb = x_ref[...].astype(jnp.int32)  # (bm, kp)
    wb = expand_nm_slab(v_ref[...], i_ref[...], m_group)  # (bn, G*m)
    kp = xb.shape[1]
    if kp > wb.shape[1]:
        wb = jnp.pad(wb, ((0, 0), (0, kp - wb.shape[1])))
    _sort_body(xb, wb, o_ref, policy=policy, acc_bits=acc_bits,
               k_tile=k_tile, rounds=rounds)


@functools.partial(
    jax.jit,
    static_argnames=("policy", "acc_bits", "k_tile", "rounds", "m_group",
                     "bm", "bn", "interpret"),
)
def nm_sort_matmul(
    x: jax.Array,  # (M, kp) int — pre-padded to the dense padded K
    values: jax.Array,  # (N, G, n_keep) int8, G*m_group <= kp
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
    """Global-permutation policies on compressed storage (one-pass,
    full-K-resident — same contract as ``sorted_matmul.sort_matmul``)."""
    m, kp = x.shape
    n, g, n_keep = values.shape
    assert g * m_group <= kp, (values.shape, m_group, kp)
    assert policy in SORT_POLICIES, policy
    if policy == "sorted":
        assert kp & (kp - 1) == 0, f"K must be a power of 2, got {kp}"
    else:
        assert k_tile & (k_tile - 1) == 0 and kp % k_tile == 0, (kp, k_tile)
        assert g * m_group == kp, "tiled policies pre-pad G to kp/m groups"
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    grid = (m // bm, n // bn)
    kern = functools.partial(_nm_sort_kernel, policy=policy,
                             acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
                             m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, values, indices)


# ---------------------------------------------------------------------------
# fused activation-gather kernels: contract ONLY the kept products
# ---------------------------------------------------------------------------


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pad_last_pow2(a: jax.Array) -> jax.Array:
    """Zero-pad the last axis up to a power of two (bitonic-sortable).

    Zero products are sign-neutral and additively inert, so the pad is
    exact through sort, saturation, and wraparound.
    """
    n = a.shape[-1]
    p = _next_pow2(n)
    if p == n:
        return a
    widths = [(0, 0)] * (a.ndim - 1) + [(0, p - n)]
    return jnp.pad(a, widths)


def gather_nm_products(xb: jax.Array, vals: jax.Array, idx: jax.Array,
                       m_group: int) -> jax.Array:
    """Kept-only partial products via activation gather.

    xb (bm, Kblk >= bg*m_group) int32, vals/idx (bn, bg, n_keep) ->
    (bm, bn, bg*n_keep) int32: product j of row pair (i, o) is
    xb[i, g*m_group + idx[o, g, j]] * vals[o, g, j]. Compared to
    expand-then-dense this forms n_keep/m_group of the products — the
    pruned positions' zero products are never materialized.

    Correctness needs no tail/pad masking by construction: ``nm_compress``
    guarantees indices lie in [0, m_group) (so every gathered position is
    inside the zero-padded xb block) and that padded slots — group
    padding, ragged-K tail positions — carry value 0, making their
    products zero and inert.
    """
    bn, bg, n_keep = vals.shape
    base = jax.lax.broadcasted_iota(
        jnp.int32, (bn, bg, n_keep), 1) * m_group
    pos = (idx.astype(jnp.int32) + base).reshape(bn, bg * n_keep)
    vflat = vals.reshape(bn, bg * n_keep).astype(jnp.int32)
    bm = xb.shape[0]
    xg = jnp.take_along_axis(
        xb[:, None, :],
        jnp.broadcast_to(pos[None, :, :], (bm, bn, bg * n_keep)),
        axis=-1,
    )
    return xg * vflat[None, :, :]


def _nm_gather_seq_kernel(x_ref, v_ref, i_ref, o_ref, *, policy: str,
                          acc_bits: int, rounds: int, m_group: int):
    """K-streaming policies on the gathered kept products only.

    Parity with ``_nm_seq_kernel`` (and hence the dense ``_seq_body``):
    wide sums the same nonzero multiset (int32 addition is exact and
    order-free); clip/wrap accumulate the kept products in the same
    ascending-position order the dense stream visits its nonzeros
    (``nm_compress`` stores indices ascending), and the skipped zero
    products are stepwise-inert; sorted_tiled_seq sorts the pow2-padded
    kept tile, whose ordered stream is the dense ordered tile's nonzero
    prefix (the pairwise-round prefix property) followed by zeros.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    prods = gather_nm_products(x_ref[...].astype(jnp.int32), v_ref[...],
                               i_ref[...], m_group)
    if policy == "wide":
        o_ref[...] += jnp.sum(prods, axis=-1)
        return
    if policy == "sorted_tiled_seq":
        prods = sorted_order_bitonic(pad_last_pow2(prods), rounds)
    o_ref[...] = _stepwise(jnp.moveaxis(prods, -1, 0), o_ref[...],
                           acc_bits, saturate=(policy != "wrap"))


@functools.partial(
    jax.jit,
    static_argnames=("policy", "acc_bits", "rounds", "m_group", "bm", "bn",
                     "bg", "interpret"),
)
def nm_gather_seq_policy_matmul(
    x: jax.Array,  # (M, K) int carrier, K = G * m_group
    values: jax.Array,  # (N, G, n_keep) int8
    indices: jax.Array,  # (N, G, n_keep) int32
    *,
    policy: str = "clip",
    acc_bits: int = 16,
    rounds: int = 1,
    m_group: int = 16,
    bm: int = 8,
    bn: int = 128,
    bg: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Gather twin of ``nm_seq_policy_matmul``: same grid/specs/contract,
    but each step contracts bg*n_keep gathered products instead of
    bg*m_group expanded ones. For sorted_tiled_seq, ``bg * m_group`` IS
    the paper's k_tile (power of two, same constraint as the expand
    kernel, which also bounds the pow2 pad of the gathered tile)."""
    m, k = x.shape
    n, g, n_keep = values.shape
    assert k == g * m_group, (x.shape, values.shape, m_group)
    assert policy in SEQ_POLICIES, policy
    if policy == "sorted_tiled_seq":
        bk = bg * m_group
        assert bk & (bk - 1) == 0, f"bg*m_group must be a power of 2: {bk}"
    assert m % bm == 0 and n % bn == 0 and g % bg == 0, (m, n, g, bm, bn, bg)
    grid = (m // bm, n // bn, g // bg)
    kern = functools.partial(_nm_gather_seq_kernel, policy=policy,
                             acc_bits=acc_bits, rounds=rounds,
                             m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bg * m_group), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bg, n_keep), lambda i, j, kk: (j, kk, 0)),
            pl.BlockSpec((bn, bg, n_keep), lambda i, j, kk: (j, kk, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, values, indices)


def _nm_gather_sort_kernel(x_ref, v_ref, i_ref, o_ref, *, policy: str,
                           acc_bits: int, k_tile: int, rounds: int,
                           m_group: int):
    """Global-permutation policies on the gathered kept products.

    ``sorted``: one bitonic stage over the pow2-padded kept stream —
    its ordered stream is the dense ordered stream's prefix (zeros past
    the kept count on both sides), so stepwise saturation matches.
    ``sorted_tiled``: the kept products regroup into n_tiles compressed
    tiles of lc = (k_tile/m)*n_keep products, each pow2-padded; tile
    sums equal the dense tile sums exactly (zeros add nothing), so
    ``tiled_sorted_order`` realizes the SAME pairing permutation, and
    each interleaved pair stream is the dense pair stream with its
    inert zeros dropped.
    """
    xb = x_ref[...].astype(jnp.int32)  # (bm, kp)
    prods = gather_nm_products(xb, v_ref[...], i_ref[...], m_group)
    if policy == "sorted":
        ordered = sorted_order_bitonic(pad_last_pow2(prods), rounds)
    else:  # sorted_tiled: caller guarantees g * m_group == kp
        bm_, bn_, total = prods.shape
        n_keep = v_ref.shape[-1]
        lc = (k_tile // m_group) * n_keep
        tiles = pad_last_pow2(prods.reshape(bm_, bn_, total // lc, lc))
        lp = tiles.shape[-1]
        ordered = tiled_sorted_order(
            tiles.reshape(bm_, bn_, -1), lp, rounds,
            order_fn=sorted_order_bitonic,
        )
    o_ref[...] = _stepwise(jnp.moveaxis(ordered, -1, 0),
                           jnp.zeros_like(o_ref), acc_bits, saturate=True)


@functools.partial(
    jax.jit,
    static_argnames=("policy", "acc_bits", "k_tile", "rounds", "m_group",
                     "bm", "bn", "interpret"),
)
def nm_gather_sort_matmul(
    x: jax.Array,  # (M, kp) int — pre-padded to the dense padded K
    values: jax.Array,  # (N, G, n_keep) int8, G*m_group <= kp
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
    """Gather twin of ``nm_sort_matmul`` (one-pass, kept products
    resident: (bm, bn, next_pow2(G*n_keep)) int32 instead of
    (bm, bn, kp) — n_keep/m of the dense cube)."""
    m, kp = x.shape
    n, g, n_keep = values.shape
    assert g * m_group <= kp, (values.shape, m_group, kp)
    assert policy in SORT_POLICIES, policy
    if policy == "sorted":
        assert kp & (kp - 1) == 0, f"K must be a power of 2, got {kp}"
    else:
        assert k_tile & (k_tile - 1) == 0 and kp % k_tile == 0, (kp, k_tile)
        assert g * m_group == kp, "tiled policies pre-pad G to kp/m groups"
        assert k_tile % m_group == 0, (k_tile, m_group)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    grid = (m // bm, n // bn)
    kern = functools.partial(_nm_gather_sort_kernel, policy=policy,
                             acc_bits=acc_bits, k_tile=k_tile, rounds=rounds,
                             m_group=m_group)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bn, g, n_keep), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, values, indices)
