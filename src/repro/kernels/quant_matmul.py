"""Baseline dense int8 x int8 -> int32 matmul Pallas kernel.

The conventional quantized matmul PQS improves on: partial products
accumulate into a WIDE int32 register (what the MXU natively provides).
Grid (M/bm, N/bn, K/bk) with the K axis innermost; the output block is
revisited across K steps and accumulated in place (standard Pallas
reduction pattern). Block shapes default to MXU-aligned 128x128 tiles
with a 512-deep K slab: VMEM footprint =
bm*bk + bk*bn (int8) + bm*bn (int32) ~= 192 KiB, well inside v5e VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sorted_matmul import int_dot


def _kernel(x_ref, w_ref, o_ref, *, interpret: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += int_dot(x_ref[...], w_ref[...], interpret)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def quant_matmul(
    x: jax.Array,  # (M, K) int8
    w: jax.Array,  # (K, N) int8
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, interpret=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, w)
