"""Bitonic sorting network as vectorized compare-exchanges (TPU/VPU-native).

A sorting *network* (paper §6: "sorting networks such as the bitonic
algorithm are popular for sorting arrays in hardware") has no
data-dependent control flow, which makes it the natural TPU mapping for the
paper's sort stage: log2(n)*(log2(n)+1)/2 stages of elementwise
min/max.

The network runs on the LEADING axis. Inside a Pallas kernel the sorted
axis is K and the trailing (bm, bn) dims are the output block, so every
element of a K slice is one (8, 128) tile: a partner exchange at stride j
is a reshape of leading dims to (..., 2, j, bm, bn) and a static pick of
the two halves, and the direction of each k-block comes from splitting
the leading axis once more — no gathers, no ``rev``, no lane-axis
indexing, nothing Mosaic refuses. The same code is the jnp oracle's
network (``bitonic_sort`` sorts any axis by moving it to the front).

A pairwise round (``pairwise_round_bitonic``) runs the network ONCE: the
positives descending are the ascending sort read backwards, and the
negatives ascending are the same sort read forwards, so one sorted tile
and its reversal give both sides of every pair. Masking each sign with a
sentinel and sorting twice, as the jnp oracle does, gives the same values
with twice the comparators.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _stages(n: int) -> list[tuple[int, int]]:
    """Static (block k, stride j) schedule for a full bitonic sort of n."""
    out = []
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            out.append((k, j))
            j //= 2
        k *= 2
    return out


def _exchange(x: jax.Array, k: int, j: int, ascending: bool) -> jax.Array:
    """One network stage on axis 0: partners i and i^j; k-blocks whose
    index has bit k clear sort ``ascending``, the others the other way."""
    n, rest = x.shape[0], x.shape[1:]

    def pair(y, up):  # y (b, 2, j, *rest): halves are the partners
        a, b = y[:, 0], y[:, 1]
        lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
        return jnp.stack([lo, hi] if up else [hi, lo], axis=1)

    if k >= n:  # a single k-block: one direction
        return pair(x.reshape(n // (2 * j), 2, j, *rest),
                    ascending).reshape(x.shape)
    xr = x.reshape(n // (2 * k), 2, k // (2 * j), 2, j, *rest)
    halves = [
        pair(xr[:, d].reshape(-1, 2, j, *rest), ascending == (d == 0))
        .reshape(n // (2 * k), k // (2 * j), 2, j, *rest)
        for d in (0, 1)
    ]
    return jnp.stack(halves, axis=1).reshape(x.shape)


def bitonic_sort(x: jax.Array, ascending: bool = True,
                 axis: int = -1) -> jax.Array:
    """Sort ``axis`` (length must be a power of two)."""
    axis = axis % x.ndim
    n = x.shape[axis]
    if n & (n - 1):
        raise ValueError(f"bitonic length must be a power of 2, got {n}")
    if axis:
        x = jnp.moveaxis(x, axis, 0)
    for k, j in _stages(n):
        x = _exchange(x, k, j, ascending)
    return jnp.moveaxis(x, 0, axis) if axis else x


def _reverse(x: jax.Array) -> jax.Array:
    """Axis 0 reversed (length a power of two): swapping the two halves of
    every block, at every block size, maps index i to i ^ (n-1) = n-1-i.
    Static reshapes and picks only, like ``_exchange``."""
    n, rest = x.shape[0], x.shape[1:]
    j = n // 2
    while j >= 1:
        y = x.reshape(n // (2 * j), 2, j, *rest)
        x = jnp.stack([y[:, 1], y[:, 0]], axis=1).reshape(x.shape)
        j //= 2
    return x


def pairwise_round_bitonic(prods: jax.Array, axis: int = -1) -> jax.Array:
    """One split/sort/pairwise-add round (paper Alg. 1 body) built on the
    sorting network — semantically identical to
    ``core.sorted_accum.pairwise_round`` (tested bit-exact) but expressed
    entirely in reshape/stack/min/max, so it runs inside Pallas kernels.

    One ascending sort serves both signs. With ``s`` sorted ascending
    (length n), the positives in descending order are ``s[n-1-i]`` while
    that is > 0, and the negatives in ascending order are ``s[i]`` while
    that is < 0, so

        out[i] = max(s[n-1-i], 0) + min(s[i], 0)

    is the oracle's ``pos_sorted[i] + neg_sorted[i]`` element for element:
    the zeros, the unpaired leftovers and the pair positions all land
    where the two-sort sentinel form puts them. The reversal is
    ``_reverse`` (Mosaic has no ``rev``).
    """
    s = bitonic_sort(jnp.moveaxis(prods, axis, 0), ascending=True, axis=0)
    out = jnp.maximum(_reverse(s), 0) + jnp.minimum(s, 0)
    return jnp.moveaxis(out, 0, axis)


def sorted_order_bitonic(prods: jax.Array, rounds: int = 1,
                         axis: int = -1) -> jax.Array:
    out = prods
    for _ in range(rounds):
        out = pairwise_round_bitonic(out, axis)
    return out
