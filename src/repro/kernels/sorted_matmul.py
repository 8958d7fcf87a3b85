"""PQS accumulation-policy matmul kernels (the paper's core, TPU-adapted).

Computes Z = X Wᵀ in int8 with a *simulated narrow accumulator* under
every accumulation policy of ``core.overflow``:

  wide             — int32 MXU accumulation (the conventional baseline)
  clip             — natural order, saturating add at every step
  wrap             — natural order, two's-complement wraparound at p bits
  sorted_tiled_seq — per-k_tile split/sort/pairwise-add rounds on a
                     bitonic network (kernels/bitonic.py), tiles in
                     natural order, stepwise saturation (paper §6: "tile
                     size k=256 still eliminates 99% of transients")
  sorted           — one full-K sorting stage, then stepwise saturation
  sorted_tiled     — per-tile sort + sum-ranked tile pairing/interleave
                     (this repo's beyond-paper refinement)

``seq_policy_matmul`` streams K through the grid (k innermost, output
block revisited — the blocked-matmul-compatible form) and compiles for
TPU: int8 operands feed the MXU dot, and the order-sensitive policies
build the (bk, bm, bn) partial-product cube with K on the leading axis,
so the sort and the stepwise walk only ever index that axis statically.
The sort itself is vectorized over the (bm, bn) output block on the VPU.

``sort_matmul`` keeps the full K axis VMEM-resident because its
accumulation order is a global permutation of K. It is the *legacy
one-pass* form of the global-permutation policies (the whole padded K
as a (bm, bn, K) cube, pairing by gather): ``kernels/ops.policy_matmul``
uses it up to ``ops.MAX_RESIDENT_K`` and routes larger K to the two-pass
streaming pipeline in ``kernels/sorted_stream.py``. Neither compiles for
TPU; both run in interpret mode only (``ops`` refuses them on a chip).

Semantics are bit-exact with the pure-jnp oracles (``ref.py`` /
``core.overflow.accumulate``): stepwise saturation, not cumsum-then-clip,
so a mid-tile excursion clips exactly like MCU saturation arithmetic
would. ``sorted_tiled``'s pairing permutation is literally
``core.sorted_accum.tiled_sorted_order`` with the bitonic sort plugged
in, so both backends share one definition of the order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import qrange
from repro.core.sorted_accum import tiled_sorted_order
from repro.kernels.bitonic import sorted_order_bitonic

SEQ_POLICIES = ("wide", "clip", "wrap", "sorted_tiled_seq")
SORT_POLICIES = ("sorted", "sorted_tiled")


# Longest K run ``_stepwise`` unrolls. Compiled kernels index their K
# slices statically (Mosaic has no dynamic_slice) and stream at most one
# block of K per grid step; only the interpret-mode global-sort kernels
# hand it whole-K cubes, which loop instead.
_UNROLL = 1024


def _stepwise(ordered: jax.Array, init: jax.Array, acc_bits: int,
              saturate: bool) -> jax.Array:
    """Accumulate (k, bm, bn) values — K on the LEADING axis — into
    (bm, bn) p-bit registers, one saturating/wrapping add per step
    (mirrors monotone_accumulate)."""
    qmin, qmax = qrange(acc_bits)
    mask = 2**acc_bits - 1

    def step(acc, p):
        nxt = acc + p
        if saturate:
            return jnp.clip(nxt, qmin, qmax)
        return ((nxt - qmin) & mask) + qmin  # two's-complement wrap

    if ordered.shape[0] <= _UNROLL:
        for t in range(ordered.shape[0]):
            init = step(init, ordered[t])
        return init
    return jax.lax.fori_loop(
        0, ordered.shape[0], lambda t, acc: step(acc, ordered[t]), init)


def int_dot(a: jax.Array, b: jax.Array, interpret: bool) -> jax.Array:
    """(m, k) x (k, n) int8 -> int32. Compiled, the int8 operands feed the
    MXU (Mosaic has no int32 x int32 dot); interpreted, they widen to
    int32 first, because XLA:CPU miscompiles some small int8 dots."""
    if interpret:
        a, b = a.astype(jnp.int32), b.astype(jnp.int32)
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _seq_body(xb, wt, o_ref, *, policy: str, acc_bits: int, rounds: int,
              k_tile: int, interpret: bool, cols=None):
    """One K-streaming grid step on int8 blocks xb (bm, bk) / wt (bk, bn).
    THE single definition of the seq-policy semantics — the dense kernel
    and the N:M compressed kernel (kernels/nm_spmm.py) differ only in how
    wt reaches VMEM, so a semantics change here cannot desynchronize the
    two storage forms.

    ``cols[t]`` is the column of xb (and row of wt) holding natural K
    offset t of the block (identity when None); only the order-sensitive
    policies read it. The partial products are built one K slice at a
    time as (bm, bn) tiles stacked on the leading axis, so the sort and
    the stepwise walk index that axis statically; a block holds
    ``bk // k_tile`` independent sort tiles.
    """
    if policy == "wide":
        o_ref[...] += int_dot(xb, wt, interpret)
        return
    x32, w32 = xb.astype(jnp.int32), wt.astype(jnp.int32)
    cols = range(xb.shape[1]) if cols is None else cols
    prods = jnp.stack([x32[:, c:c + 1] * w32[c:c + 1, :] for c in cols])
    acc = o_ref[...]
    if policy == "sorted_tiled_seq":
        for s in range(0, prods.shape[0], k_tile):
            tile = sorted_order_bitonic(prods[s:s + k_tile], rounds, axis=0)
            acc = _stepwise(tile, acc, acc_bits, saturate=True)
    else:
        acc = _stepwise(prods, acc, acc_bits, saturate=(policy != "wrap"))
    o_ref[...] = acc


def _seq_kernel(x_ref, w_ref, o_ref, *, policy: str, acc_bits: int,
                rounds: int, k_tile: int, interpret: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _seq_body(x_ref[...], w_ref[...], o_ref, policy=policy,
              acc_bits=acc_bits, rounds=rounds, k_tile=k_tile,
              interpret=interpret)


def _sort_body(xb, wb, o_ref, *, policy: str, acc_bits: int, k_tile: int,
               rounds: int):
    """Full-K-resident global-sort step on int32 slabs xb (bm, K) / wb
    (bn, K) — shared by the dense and N:M compressed kernels."""
    prods = xb[:, None, :] * wb[None, :, :]  # (bm, bn, K)
    if policy == "sorted":
        ordered = sorted_order_bitonic(prods, rounds)
    else:  # sorted_tiled: shared pairing permutation, bitonic intra-tile
        ordered = tiled_sorted_order(prods, k_tile, rounds,
                                     order_fn=sorted_order_bitonic)
    o_ref[...] = _stepwise(jnp.moveaxis(ordered, -1, 0),
                           jnp.zeros_like(o_ref), acc_bits, saturate=True)


def _sort_kernel(x_ref, w_ref, o_ref, *, policy: str, acc_bits: int,
                 k_tile: int, rounds: int):
    _sort_body(x_ref[...].astype(jnp.int32), w_ref[...].astype(jnp.int32),
               o_ref, policy=policy, acc_bits=acc_bits, k_tile=k_tile,
               rounds=rounds)


@functools.partial(
    jax.jit,
    static_argnames=("policy", "acc_bits", "rounds", "bm", "bn", "bk",
                     "k_tile", "interpret"),
)
def seq_policy_matmul(
    x: jax.Array,  # (M, K) int8 activations
    w: jax.Array,  # (N, K) int8 weights (rows = output channels)
    *,
    policy: str = "clip",
    acc_bits: int = 16,
    rounds: int = 1,
    bm: int = 8,
    bn: int = 128,
    bk: int = 256,
    k_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """K-streaming policies: wide | clip | wrap | sorted_tiled_seq.

    The weights stream K-major, (bk, bn) blocks of w.T, so a K slice of
    the block is a sublane row. For sorted_tiled_seq the sort never
    sees across a k_tile boundary; k_tile must be a power of two that
    divides bk (a 128-lane block holds several smaller tiles).
    """
    m, k = x.shape
    n, k2 = w.shape
    assert k == k2, (x.shape, w.shape)
    assert x.dtype == w.dtype == jnp.int8, (x.dtype, w.dtype)
    assert policy in SEQ_POLICIES, policy
    if policy == "sorted_tiled_seq":
        assert k_tile & (k_tile - 1) == 0 and bk % k_tile == 0, (bk, k_tile)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)
    kern = functools.partial(_seq_kernel, policy=policy, acc_bits=acc_bits,
                             rounds=rounds, k_tile=k_tile,
                             interpret=interpret)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, w.T)


@functools.partial(
    jax.jit,
    static_argnames=("policy", "acc_bits", "k_tile", "rounds", "bm", "bn",
                     "interpret"),
)
def sort_matmul(
    x: jax.Array,  # (M, K) int
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
    """Global-permutation policies: sorted | sorted_tiled (full K resident).

    ``sorted`` requires K to be a power of two (one bitonic stage over the
    whole axis); ``sorted_tiled`` requires K % k_tile == 0 with k_tile a
    power of two. Callers (kernels/ops.py) zero-pad — zeros are
    sign-neutral and additively inert through sort and saturation.
    """
    m, k = x.shape
    n, k2 = w.shape
    assert k == k2, (x.shape, w.shape)
    assert policy in SORT_POLICIES, policy
    if policy == "sorted":
        assert k & (k - 1) == 0, f"K must be a power of 2, got {k}"
    else:
        assert k_tile & (k_tile - 1) == 0 and k % k_tile == 0, (k, k_tile)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    grid = (m // bm, n // bn)
    kern = functools.partial(_sort_kernel, policy=policy, acc_bits=acc_bits,
                             k_tile=k_tile, rounds=rounds)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, w)
