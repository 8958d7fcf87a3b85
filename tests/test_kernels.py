"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings
from _hypothesis_shim import strategies as st

from repro.core.overflow import accumulate, partial_products
from repro.core.pruning import nm_prune_mask
from repro.kernels import ops, ref
from repro.kernels.bitonic import (
    bitonic_sort,
    pairwise_round_bitonic,
    sorted_order_bitonic,
)
from repro.kernels.sorted_matmul import seq_policy_matmul
from repro.core.sorted_accum import pairwise_round, sorted_order


@pytest.mark.parametrize("n", [2, 8, 64, 256])
def test_bitonic_matches_sort(n, rng):
    x = jnp.asarray(rng.integers(-(2**28), 2**28, (6, n)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(bitonic_sort(x)), np.sort(np.asarray(x), -1)
    )
    np.testing.assert_array_equal(
        np.asarray(bitonic_sort(x, ascending=False)),
        np.sort(np.asarray(x), -1)[..., ::-1],
    )


def test_bitonic_with_duplicates():
    x = jnp.asarray([[3, 3, 1, 1, 2, 2, 0, 0]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(bitonic_sort(x))[0], [0, 0, 1, 1, 2, 2, 3, 3]
    )


def test_bitonic_rejects_non_pow2():
    with pytest.raises(ValueError):
        bitonic_sort(jnp.zeros((2, 12), jnp.int32))


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_property_pairwise_bitonic_equals_core(seed):
    r = np.random.default_rng(seed)
    p = jnp.asarray(r.integers(-(2**20), 2**20, (3, 64)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(pairwise_round(p)), np.asarray(pairwise_round_bitonic(p))
    )
    np.testing.assert_array_equal(
        np.asarray(sorted_order(p, 2)), np.asarray(sorted_order_bitonic(p, 2))
    )


def _tile(kind: str, n: int) -> np.ndarray:
    """(4, n) partial products; the int8 extremes are 16384 =
    (-128)^2 and -16256 = -128 * 127."""
    r = np.random.default_rng(n)
    if kind == "all_positive":
        return r.integers(1, 16385, (4, n))
    if kind == "all_negative":
        return r.integers(-16256, 0, (4, n))
    if kind == "all_zero":
        return np.zeros((4, n), np.int64)
    if kind == "one_nonzero":
        t = np.zeros((4, n), np.int64)
        t[np.arange(4), r.integers(0, n, 4)] = [16384, -16256, 7, -1]
        return t
    if kind == "ties":
        return r.choice([-3, -1, 0, 1, 3], (4, n))
    return r.choice([16384, -16256], (4, n))  # int8_extremes


@pytest.mark.parametrize("axis", [0, -1])
@pytest.mark.parametrize("n", [2, 64, 256])
@pytest.mark.parametrize("kind", ["all_positive", "all_negative", "all_zero",
                                  "one_nonzero", "ties", "int8_extremes"])
def test_pairwise_bitonic_edge_tiles_match_core(kind, n, axis):
    """The one-sort round and its rounds 1-3 equal the two-sort oracle bit
    for bit, in the kernel's layout (sort axis leading) and the last-axis
    one, on tiles whose sign counts sit at the extremes."""
    p = jnp.asarray(_tile(kind, n), jnp.int32)
    to = (lambda a: jnp.moveaxis(a, -1, 0)) if axis == 0 else (lambda a: a)
    back = (lambda a: jnp.moveaxis(a, 0, -1)) if axis == 0 else (lambda a: a)
    np.testing.assert_array_equal(
        np.asarray(back(pairwise_round_bitonic(to(p), axis=axis))),
        np.asarray(pairwise_round(p)))
    for rounds in (1, 2, 3):
        np.testing.assert_array_equal(
            np.asarray(back(sorted_order_bitonic(to(p), rounds, axis=axis))),
            np.asarray(sorted_order(p, rounds)))


def test_seq_kernel_saturating_matches_oracle():
    """sorted_tiled_seq in interpret mode at a 16-bit accumulator, on
    operands whose dots leave its range (so saturation fires), equals
    the jnp oracle."""
    r = np.random.default_rng(16)
    x = r.integers(90, 128, (8, 256))
    w = r.integers(-128, 128, (128, 256))
    w[:64, :128] = np.abs(w[:64, :128])  # sign-aligned first tiles
    x, w = jnp.asarray(x, jnp.int8), jnp.asarray(w, jnp.int8)
    got = np.asarray(seq_policy_matmul(
        x, w, policy="sorted_tiled_seq", acc_bits=16, bk=128, k_tile=64,
        interpret=True))
    want = np.asarray(accumulate(partial_products(w, x), 16,
                                 "sorted_tiled_seq", k_tile=64, rounds=1))
    wide = np.asarray(x, np.int64) @ np.asarray(w, np.int64).T
    assert (got != wide).any()
    assert (got == 2**15 - 1).any() and (got == -(2**15)).any()
    np.testing.assert_array_equal(got, want)


def _elements(jaxpr, prim: str) -> int:
    """Output elements of every ``prim`` equation, nested jaxprs included."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == prim:
            total += sum(int(np.prod(v.aval.shape)) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _elements(sub, prim)
    return total


def test_pairwise_bitonic_sorts_once():
    """One 64-wide bitonic network (21 stages of 32 compare-exchanges) plus
    the pairing (max of the reversed tile with 0, min of the tile with 0)
    over a (64, 8, 128) tile: a second sort would double the network."""
    tile = jax.ShapeDtypeStruct((64, 8, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda t: pairwise_round_bitonic(t, axis=0))(tile)
    per_column = 21 * 32 + 64
    assert _elements(jaxpr.jaxpr, "min") == per_column * 8 * 128
    assert _elements(jaxpr.jaxpr, "max") == per_column * 8 * 128


def test_next_pow2():
    """next_pow2(1) must be 1 — a K=1 dot is already bitonic-sortable;
    padding it to 2 over-padded every K=1 `sorted` dot."""
    assert ops.next_pow2(1) == 1
    assert ops.next_pow2(2) == 2
    assert ops.next_pow2(3) == 4
    assert ops.next_pow2(4) == 4
    assert ops.next_pow2(4097) == 8192
    for n in range(1, 300):
        p = ops.next_pow2(n)
        assert p >= n and p & (p - 1) == 0 and (p == 1 or p // 2 < n), n


def test_padded_k():
    # sorted: one bitonic stage over the whole axis -> power of two
    assert ops.padded_k(1, "sorted", 256) == 1
    assert ops.padded_k(300, "sorted", 256) == 512
    assert ops.padded_k(4096, "sorted", 256) == 4096
    # tiled policies: whole number of k_tile tiles
    assert ops.padded_k(300, "sorted_tiled", 256) == 512
    assert ops.padded_k(300, "sorted_tiled_seq", 64) == 320
    assert ops.padded_k(256, "sorted_tiled", 256) == 256
    # unsorted policies: no K padding at all
    for policy in ("wide", "clip", "wrap"):
        assert ops.padded_k(300, policy, 256) == 300


def test_pad_to(rng):
    x = jnp.asarray(rng.integers(-5, 5, (5, 6)), jnp.int32)
    same = ops._pad_to(x, 3, 1)
    assert same is x  # already a multiple: no copy
    p0 = ops._pad_to(x, 4, 0)
    assert p0.shape == (8, 6)
    np.testing.assert_array_equal(np.asarray(p0[:5]), np.asarray(x))
    assert int(jnp.abs(p0[5:]).sum()) == 0
    p1 = ops._pad_to(x, 4, 1)
    assert p1.shape == (5, 8) and int(jnp.abs(p1[:, 6:]).sum()) == 0


def test_env_blocks_forms(monkeypatch):
    monkeypatch.delenv("REPRO_PQS_BLOCKS", raising=False)
    assert ops.env_blocks("clip") is None
    monkeypatch.setenv("REPRO_PQS_BLOCKS", "16,64")
    assert ops.env_blocks("clip") == (16, 64)
    assert ops.env_blocks("wide") == (16, 64)  # bare form: every policy
    monkeypatch.setenv("REPRO_PQS_BLOCKS", "sorted:8,128;wide:128,128")
    assert ops.env_blocks("sorted") == (8, 128)
    assert ops.env_blocks("wide") == (128, 128)
    assert ops.env_blocks("clip") is None  # no entry -> fall through
    # mixed: bare entry is the default for policies without their own
    monkeypatch.setenv("REPRO_PQS_BLOCKS", "16,64;sorted:8,128")
    assert ops.env_blocks("sorted") == (8, 128)
    assert ops.env_blocks("clip") == (16, 64)
    assert ops.default_blocks("clip") == (16, 64)  # flows into defaults


@pytest.mark.parametrize("bad", ["8", "8,x", "1,2,3", "bogus:1,2",
                                 "sorted:1", "sorted=8,128"])
def test_env_blocks_malformed(monkeypatch, bad):
    monkeypatch.setenv("REPRO_PQS_BLOCKS", bad)
    with pytest.raises(ValueError, match="REPRO_PQS_BLOCKS"):
        ops.env_blocks("clip")


@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [(16, 64, 16, 8, 8, 32), (32, 128, 24, 16, 8, 64), (7, 50, 9, 8, 8, 32)],
)
def test_quant_matmul_sweep(m, k, n, bm, bn, bk, rng):
    x = jnp.asarray(rng.integers(-127, 127, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8)
    out = ops.quant_matmul(x, w, bm=bm, bn=bn, bk=bk)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.quant_matmul_ref(x, w))
    )


@pytest.mark.parametrize("acc_bits", [12, 16, 20])
@pytest.mark.parametrize("rounds", [1, 2])
def test_sorted_matmul_sweep(acc_bits, rounds, rng):
    x = jnp.asarray(rng.integers(0, 127, (8, 64)), jnp.int8)  # post-ReLU
    w = jnp.asarray(rng.integers(-127, 127, (12, 64)), jnp.int8)
    out = ops.sorted_matmul(
        x, w, acc_bits=acc_bits, rounds=rounds, bm=4, bn=4, bk=32
    )
    expect = ref.sorted_matmul_ref(
        x, w, acc_bits=acc_bits, rounds=rounds, k_tile=32
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_sorted_matmul_ragged_padding(rng):
    """Zero padding must be inert through sort + saturation."""
    x = jnp.asarray(rng.integers(-50, 50, (5, 48)), jnp.int8)
    w = jnp.asarray(rng.integers(-50, 50, (6, 48)), jnp.int8)
    out = ops.sorted_matmul(x, w, acc_bits=18, bm=4, bn=4, bk=16)
    expect = ref.sorted_matmul_ref(x, w, acc_bits=18, rounds=1, k_tile=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_clip_matmul_matches_ref(rng):
    x = jnp.asarray(rng.integers(0, 127, (6, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 127, (10, 64)), jnp.int8)
    out = ops.clip_matmul(x, w, acc_bits=14, bm=2, bn=2, bk=32)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.clip_matmul_ref(x, w, acc_bits=14))
    )


def test_sorted_resolves_transients_where_clip_fails(rng):
    """End-to-end kernel-level PQS claim: with a narrow accumulator the
    sorted kernel recovers the exact (wide) result on dot products whose
    natural order transiently overflows."""
    x = jnp.asarray(rng.integers(0, 127, (16, 128)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 127, (32, 128)), jnp.int8)
    wide = np.asarray(ref.quant_matmul_ref(x, jnp.asarray(np.asarray(w).T)))
    bits = 18
    qmin, qmax = -(2**17), 2**17 - 1
    fits = (wide >= qmin) & (wide <= qmax)
    srt = np.asarray(ops.sorted_matmul(x, w, acc_bits=bits, bm=8, bn=8, bk=128))
    clp = np.asarray(ops.clip_matmul(x, w, acc_bits=bits, bm=8, bn=8, bk=128))
    exact_sorted = (srt == wide)[fits].mean()
    exact_clip = (clp == wide)[fits].mean()
    assert exact_sorted >= exact_clip
    assert exact_sorted > 0.999  # sorting eliminates ~all transients


@pytest.mark.parametrize("n_keep,m_group", [(4, 16), (8, 16), (2, 8)])
def test_nm_spmm_sweep(n_keep, m_group, rng):
    n, k = 16, 128
    wd = rng.integers(-127, 127, (n, k)).astype(np.int8)
    mask = np.asarray(nm_prune_mask(jnp.asarray(wd, jnp.float32), n_keep, m_group))
    wd = (wd * mask).astype(np.int8)
    vals, idx = ops.compress_nm_weights(wd, n_keep, m_group)
    x = jnp.asarray(rng.integers(-127, 127, (12, k)), jnp.int8)
    out = ops.nm_spmm(x, vals, idx, m_group=m_group, bm=4, bn=8, bg=2)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(ref.quant_matmul_ref(x, jnp.asarray(wd.T))),
    )


def test_nm_spmm_bandwidth_model():
    """The compressed form streams n_keep/m of the dense weight bytes —
    the decode-bandwidth saving in DESIGN.md §2 (plus small index cost)."""
    n, k, n_keep, m = 128, 1024, 4, 16
    dense_bytes = n * k  # int8
    vals_bytes = n * (k // m) * n_keep
    idx_bytes = n * (k // m) * n_keep  # int8-packable positions (< m = 16)
    assert vals_bytes == dense_bytes * n_keep / m
    assert (vals_bytes + idx_bytes) <= dense_bytes / 2


@pytest.mark.parametrize("policy", ["sorted", "sorted_tiled"])
def test_global_sort_kernels_refused_when_compiled(policy):
    """The global-sort kernels have no compiled TPU form: a compiled call
    raises instead of falling back to jnp or the interpreter."""
    x = jnp.ones((8, 256), jnp.int8)
    w = jnp.ones((128, 256), jnp.int8)
    with pytest.raises(ValueError, match="no compiled TPU kernel"):
        ops.policy_matmul(x, w, policy=policy, interpret=False)
    vals, idx = ops.compress_nm_weights(np.ones((128, 256), np.int8), 4, 4)
    with pytest.raises(ValueError, match="no compiled TPU kernel"):
        ops.nm_policy_matmul(x, vals, idx, m_group=4, policy=policy,
                             interpret=False)


def test_nm_gather_never_chosen_when_compiled(monkeypatch):
    """``auto`` takes the gather kernels only in interpret mode; asking
    for them on a compiled path is refused."""
    monkeypatch.delenv("REPRO_PQS_NM_IMPL", raising=False)
    assert ops.resolve_nm_impl("sorted_tiled_seq", 64, 2, 4) == "gather"
    assert ops.resolve_nm_impl("sorted_tiled_seq", 64, 2, 4,
                               compiled=True) == "expand"
    with pytest.raises(ValueError, match="does not compile"):
        ops.resolve_nm_impl("clip", 64, 2, 4, "gather", compiled=True)


def test_kernel_layer_imports_on_its_own():
    """``repro.kernels.ops`` loads first in a fresh process: no import
    cycle through ``repro.core``."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src),
               JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", "import repro.kernels.ops"],
                   check=True, env=env, timeout=120)
