"""Policy x sparse-storage composition matrix (``pqs_dot(storage="nm")``).

The contract: every accumulation policy, run directly on N:M-compressed
weights, is BIT-IDENTICAL — census included — to ``nm_decompress``
followed by the dense ``pqs_dot``, on both backends, for every
(n_keep, m) the paper's experiments sweep, at K up to 8192 (the
two-pass streaming kernels), and under a sharded mesh.

The sharded case needs forced host devices (scripts/ci.sh runs this
module inside its multi-device shard next to test_sharded_dispatch.py);
in the single-device suite it self-skips.
"""

import os

# opt-in, and only effective before the first jax backend init (same
# contract as tests/test_sharded_dispatch.py)
if os.environ.get("REPRO_FORCE_MULTIDEVICE") and (
    "--xla_force_host_platform_device_count"
    not in os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core.dispatch import IntegerLinConfig, pqs_dot, qtensor_dot  # noqa: E402
from repro.core.pruning import (  # noqa: E402
    nm_compress,
    nm_decompress,
    nm_prune_mask,
)
from repro.core.qtensor import (  # noqa: E402
    SparseQTensor,
    nm_compress_tree,
    qtensor_nm_compress,
    quantize_weight,
)

POLICIES = ("wide", "clip", "wrap", "sorted", "sorted_tiled",
            "sorted_tiled_seq")
NM_SHAPES = ((2, 4), (4, 8), (4, 16))  # (n_keep, m) — the paper's sweep
CENSUS_FIELDS = ("n_dots", "n_persistent", "n_transient", "n_any")


def _compressed(n, k, n_keep, m, seed=0):
    """(values, indices, dense) with dense = the decompress oracle."""
    rng = np.random.default_rng(seed)
    wd = rng.integers(-127, 127, (n, k)).astype(np.int8)
    mask = np.asarray(nm_prune_mask(jnp.asarray(wd, jnp.float32), n_keep, m))
    wd = (wd * mask).astype(np.int8)
    vals, idx = nm_compress(wd, n_keep, m)
    dense = nm_decompress(vals, idx, m, k=k)
    np.testing.assert_array_equal(dense, wd)  # compression is lossless
    return (jnp.asarray(vals, jnp.int8), jnp.asarray(idx, jnp.int32),
            jnp.asarray(dense))


def _x(m_rows, k, seed=0):
    rng = np.random.default_rng(seed + 100)
    return jnp.asarray(rng.integers(-127, 127, (m_rows, k)), jnp.int8)


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("n_keep,m", NM_SHAPES)
def test_nm_parity_matrix(policy, n_keep, m):
    """All six policies x all (n_keep, m): compressed == decompressed,
    on the jnp AND pallas backends."""
    M, K, N = 5, 96, 9  # ragged M/N on purpose — padding is dispatch's job
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=n_keep * 31 + m)
    x = _x(M, K, seed=m)
    ref = pqs_dot(x, dense, acc_bits=14, policy=policy, k_tile=32,
                  backend="jnp")
    for backend, kw in (("jnp", {}), ("pallas",
                                      dict(block_m=4, block_n=8))):
        out = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=14,
                      policy=policy, k_tile=32, backend=backend, **kw)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref),
            err_msg=f"{policy} {n_keep}:{m} backend={backend}",
        )


@pytest.mark.parametrize("policy", ("clip", "sorted_tiled"))
def test_nm_census_parity(policy):
    """The kept-only census equals the dense census bit for bit: zero
    partial products never change a running sum's range status."""
    n_keep, m = 4, 16
    M, K, N = 6, 128, 10
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=7)
    x = _x(M, K, seed=3)
    _, ref = pqs_dot(x, dense, acc_bits=14, policy=policy, k_tile=32,
                     backend="jnp", with_census=True)
    for backend, kw in (("jnp", {}), ("pallas",
                                      dict(block_m=4, block_n=8))):
        _, out = pqs_dot(x, (vals, idx), storage="nm", m_group=m,
                         acc_bits=14, policy=policy, k_tile=32,
                         backend=backend, with_census=True, **kw)
        for field in CENSUS_FIELDS:
            assert int(getattr(out, field)) == int(getattr(ref, field)), (
                policy,
                backend,
                field,
            )


def test_nm_census_drops_with_sparsity():
    """The paper's pruning payoff, measured: at a fixed accumulator
    width, keeping fewer of every m produces no MORE censused overflow
    events (shorter effective dot products overflow less)."""
    K, N, M = 256, 12, 8
    x = _x(M, K, seed=5)
    prev = None
    for n_keep in (16, 8, 4, 2):
        vals, idx, _ = _compressed(N, K, n_keep, 16, seed=9)
        _, c = pqs_dot(x, (vals, idx), storage="nm", m_group=16,
                       acc_bits=12, policy="clip", backend="jnp",
                       with_census=True)
        if prev is not None:
            assert int(c.n_any) <= prev
        prev = int(c.n_any)


@pytest.mark.slow
def test_nm_parity_large_k():
    """K = 8192: the two-pass streaming sort kernels (tile sums computed
    from the compressed slabs) and the chunked-cube ``sorted`` path."""
    n_keep, m = 4, 16
    M, K, N = 2, 8192, 4
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=11)
    x = _x(M, K, seed=11)
    for policy in POLICIES:
        ref = pqs_dot(x, dense, acc_bits=16, policy=policy, k_tile=256,
                      backend="jnp")
        out = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=16,
                      policy=policy, k_tile=256, backend="pallas",
                      block_m=2, block_n=4)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=policy)


def test_nm_default_blocks_resolve():
    """No explicit blocks: the ``nm:`` kernel-family entries in the
    block table / env override resolve and the result stays exact."""
    vals, idx, dense = _compressed(6, 64, 2, 8, seed=13)
    x = _x(4, 64, seed=13)
    ref = pqs_dot(x, dense, acc_bits=16, policy="clip", backend="jnp")
    out = pqs_dot(x, (vals, idx), storage="nm", m_group=8, acc_bits=16,
                  policy="clip", backend="pallas")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_nm_ragged_k_through_sparse_qtensor(rng):
    """K not divisible by m: the tail group pads inside the compressed
    form and the logical k_dim drives the x-padding."""
    w = jnp.asarray(rng.normal(size=(50, 24)), jnp.float32) * 0.1
    qt = quantize_weight(w, bits=8)  # unpruned: dense-as-sparse below
    sq = qtensor_nm_compress(qt, 16, 16)  # n_keep == m, K=50 has a tail
    assert sq.k_dim == 50 and sq.values.shape == (24, 4, 16)
    np.testing.assert_array_equal(
        np.asarray(qt.dequant(jnp.float32)),
        np.asarray(sq.dequant(jnp.float32)),
    )
    x = jnp.asarray(rng.normal(size=(3, 50)), jnp.float32)
    cfg = IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24,
                           k_tile=64, backend="jnp")
    np.testing.assert_array_equal(
        np.asarray(qtensor_dot(x, qt, cfg)),
        np.asarray(qtensor_dot(x, sq, cfg)),
    )


def test_nm_validation_errors():
    vals, idx, _ = _compressed(4, 32, 2, 8)
    x = _x(2, 32)
    with pytest.raises(ValueError, match="storage"):
        pqs_dot(x, (vals, idx), storage="csr", m_group=8)
    with pytest.raises(ValueError, match="m_group"):
        pqs_dot(x, (vals, idx), storage="nm")  # bare pair needs m_group
    with pytest.raises(ValueError, match="k_tile"):
        pqs_dot(x, (vals, idx), storage="nm", m_group=8,
                policy="sorted_tiled", k_tile=4)  # 4 % 8 != 0
    with pytest.raises(ValueError, match="contraction"):
        pqs_dot(_x(2, 48), (vals, idx), storage="nm", m_group=8)
    with pytest.raises(ValueError, match="SparseQTensor"):
        pqs_dot(x, "bogus", storage="nm", m_group=8)


def test_nm_compress_tree_rejects_bad_args(rng):
    """Argument typos must raise, not silently return a dense tree."""
    from repro.core.qtensor import quantize_tree

    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    tree = quantize_tree({"wq": w}, bits=8, n_keep=4, m=16,
                         min_size=1, min_dim=8)
    with pytest.raises(ValueError, match="n_keep"):
        nm_compress_tree(tree, 17, 16)
    with pytest.raises(ValueError, match="m_group"):
        nm_compress_tree(tree, 4, 0)
    # valid args but a pattern no leaf matches: raise, don't silently
    # return an all-dense tree
    with pytest.raises(ValueError, match="no QTensor leaf"):
        nm_compress_tree(tree, 2, 16)  # tree is 4:16-pruned, not 2:16


def test_nm_integer_serving_engine_end_to_end():
    """A pruned-then-quantized model serves integer decode steps from
    compressed storage, token-identical to the dense-QTensor engine."""
    from repro.configs import get_config
    from repro.core.qtensor import quantize_tree
    from repro.models.model import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_tree(params, bits=8, n_keep=4, m=16,
                            min_size=1 << 10, min_dim=16)
    sparams = nm_compress_tree(qparams, 4, 16)
    assert any(
        isinstance(leaf, SparseQTensor)
        for leaf in jax.tree_util.tree_leaves(
            sparams, is_leaf=lambda l: isinstance(l, SparseQTensor))
    )
    il = IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24,
                          k_tile=64, backend="jnp")

    def run(p):
        rng = np.random.default_rng(1)
        reqs = [
            Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 5).astype(
                        np.int32),
                    max_new_tokens=3)
            for i in range(2)
        ]
        eng = ServingEngine(model, p, num_slots=2, max_len=16, int_lin=il)
        eng.drain(reqs)
        return [r.output for r in reqs]

    assert run(qparams) == run(sparams)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs REPRO_FORCE_MULTIDEVICE (see ci.sh shard)")
@pytest.mark.parametrize("policy", POLICIES)
def test_nm_sharded_bit_identical(policy):
    """Compressed weights shard their N rows over the mesh and stay
    bit-identical to the single-device dense reference."""
    n_keep, m = 4, 16
    M, K, N = 5, 128, 6  # N=6 does not divide the model axis -> degrade
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=17)
    x = _x(M, K, seed=17)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ref = pqs_dot(x, dense, acc_bits=14, policy=policy, k_tile=32,
                  backend="jnp")
    out = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=14,
                  policy=policy, k_tile=32, backend="jnp", mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out),
                                  err_msg=policy)


# ---------------------------------------------------------------------------
# fused activation-gather implementation (nm_impl="gather")
# ---------------------------------------------------------------------------


def _compressed_ragged(n, k, n_keep, m, seed=0):
    """``_compressed`` for K % m != 0: pad for the prune mask, slice
    back, let ``nm_compress`` zero-pad the tail group."""
    rng = np.random.default_rng(seed)
    wd = rng.integers(-127, 127, (n, k)).astype(np.int8)
    kp = k + ((-k) % m)
    wp = np.pad(wd, ((0, 0), (0, kp - k)))
    mask = np.asarray(nm_prune_mask(jnp.asarray(wp, jnp.float32), n_keep, m))
    wd = (wp * mask).astype(np.int8)[:, :k]
    vals, idx = nm_compress(wd, n_keep, m)
    dense = nm_decompress(vals, idx, m, k=k)
    np.testing.assert_array_equal(dense, wd)
    return (jnp.asarray(vals, jnp.int8), jnp.asarray(idx, jnp.int32),
            jnp.asarray(dense))


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("n_keep,m", NM_SHAPES)
def test_nm_gather_expand_bit_identity(policy, n_keep, m):
    """The fused gather kernels are bit-identical — census included — to
    the expand oracle for every policy x (n_keep, m), at the same
    dense-parity shapes the expand matrix sweeps."""
    M, K, N = 5, 96, 9
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=n_keep * 13 + m)
    x = _x(M, K, seed=m + 1)
    ref, cref = pqs_dot(x, dense, acc_bits=14, policy=policy, k_tile=32,
                        backend="jnp", with_census=True)
    outs = {}
    for impl in ("expand", "gather"):
        out, c = pqs_dot(x, (vals, idx), storage="nm", m_group=m,
                         acc_bits=14, policy=policy, k_tile=32,
                         backend="pallas", block_m=4, block_n=8,
                         nm_impl=impl, with_census=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref),
            err_msg=f"{policy} {n_keep}:{m} impl={impl}",
        )
        for field in CENSUS_FIELDS:
            assert int(getattr(c, field)) == int(getattr(cref, field)), (
                policy, impl, field)
        outs[impl] = np.asarray(out)
    np.testing.assert_array_equal(outs["expand"], outs["gather"])


@pytest.mark.slow
def test_nm_gather_parity_large_k():
    """K = 8192 through the gather twins of the two-pass streaming sort
    kernels and the chunked-cube ``sorted`` path."""
    n_keep, m = 4, 16
    M, K, N = 2, 8192, 4
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=23)
    x = _x(M, K, seed=23)
    for policy in POLICIES:
        ref = pqs_dot(x, dense, acc_bits=16, policy=policy, k_tile=256,
                      backend="jnp")
        out = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=16,
                      policy=policy, k_tile=256, backend="pallas",
                      block_m=2, block_n=4, nm_impl="gather")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=policy)


def test_nm_gather_ragged_tail():
    """K % m != 0: the compress-time zero-pad invariant (no per-call
    tail mask in the gather kernel) keeps ragged K exact."""
    n_keep, m = 4, 16
    M, K, N = 4, 100, 6  # G = 7, tail group covers positions 96..111
    vals, idx, dense = _compressed_ragged(N, K, n_keep, m, seed=29)
    sq = SparseQTensor(values=vals, indices=idx, scale=jnp.ones((N,)),
                       m_group=m, k_dim=K)
    x = _x(M, K, seed=29)
    for policy in ("clip", "sorted_tiled_seq", "sorted"):
        ref = pqs_dot(x, dense, acc_bits=14, policy=policy, k_tile=32,
                      backend="jnp")
        out = pqs_dot(x, sq, storage="nm", acc_bits=14,
                      policy=policy, k_tile=32, backend="pallas",
                      block_m=4, block_n=8, nm_impl="gather")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=policy)


def test_nm_impl_env_knob(monkeypatch):
    """REPRO_PQS_NM_IMPL routes when no explicit nm_impl is passed, and
    malformed values raise loudly."""
    from repro.kernels import ops

    vals, idx, dense = _compressed(6, 128, 2, 8, seed=31)
    x = _x(4, 128, seed=31)
    ref = pqs_dot(x, dense, acc_bits=14, policy="clip", backend="jnp")
    for env in ("expand", "gather"):
        monkeypatch.setenv("REPRO_PQS_NM_IMPL", env)
        out = pqs_dot(x, (vals, idx), storage="nm", m_group=8, acc_bits=14,
                      policy="clip", backend="pallas", block_m=4, block_n=8)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=env)
    monkeypatch.setenv("REPRO_PQS_NM_IMPL", "bogus")
    with pytest.raises(ValueError, match="REPRO_PQS_NM_IMPL"):
        ops.resolve_nm_impl("clip", 16, 2, 8)
    monkeypatch.delenv("REPRO_PQS_NM_IMPL")
    with pytest.raises(ValueError, match="nm_impl"):
        pqs_dot(x, (vals, idx), storage="nm", m_group=8, policy="clip",
                backend="pallas", nm_impl="bogus")
    with pytest.raises(ValueError, match="storage"):
        pqs_dot(x, dense, policy="clip", nm_impl="gather")  # dense w


def test_nm_impl_auto_heuristics():
    """``auto`` picks gather only where it can save work: real sparsity
    (n_keep < m), a policy with skippable work, enough groups."""
    from repro.kernels import ops

    assert ops.resolve_nm_impl("clip", 64, 4, 8) == "gather"
    assert ops.resolve_nm_impl("sorted", 64, 2, 4) == "gather"
    assert ops.resolve_nm_impl("wide", 64, 4, 8) == "expand"  # MXU dot
    assert ops.resolve_nm_impl("clip", 64, 8, 8) == "expand"  # dense-as-nm
    small = ops.GATHER_MIN_G - 1
    assert ops.resolve_nm_impl("clip", small, 4, 8) == "expand"  # tiny G
    # explicit choice always wins over the heuristics
    assert ops.resolve_nm_impl("wide", small, 8, 8, "gather") == "gather"
    assert ops.resolve_nm_impl("clip", 64, 4, 8, "expand") == "expand"


def test_nm_gather_kshard_composition():
    """k_shards > 1 on compressed storage: gather partials compose with
    the hierarchical combine bit-identically to expand partials."""
    n_keep, m = 4, 16
    M, K, N = 4, 512, 6
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=37)
    x = _x(M, K, seed=37)
    for policy in POLICIES:
        ref = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=14,
                      policy=policy, k_tile=32, backend="pallas",
                      block_m=4, block_n=8, k_shards=4, nm_impl="expand")
        out = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=14,
                      policy=policy, k_tile=32, backend="pallas",
                      block_m=4, block_n=8, k_shards=4, nm_impl="gather")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=policy)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs REPRO_FORCE_MULTIDEVICE (see ci.sh shard)")
def test_nm_gather_sharded_k_axis():
    """mesh + k_axis with gather kernels inside every K shard — the
    REPRO_FORCE_MULTIDEVICE composition case from the issue."""
    n_keep, m = 4, 16
    M, K, N = 4, 512, 6
    vals, idx, dense = _compressed(N, K, n_keep, m, seed=41)
    x = _x(M, K, seed=41)
    mesh = jax.make_mesh((2, 2, 2), ("data", "model", "kdim"),
                         axis_types=(AxisType.Auto,) * 3)
    for policy in ("clip", "sorted_tiled"):
        ref = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=14,
                      policy=policy, k_tile=32, backend="jnp",
                      k_shards=2)
        out = pqs_dot(x, (vals, idx), storage="nm", m_group=m, acc_bits=14,
                      policy=policy, k_tile=32, backend="pallas",
                      block_m=4, block_n=8, mesh=mesh, k_axis="kdim",
                      nm_impl="gather")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=policy)


# ---------------------------------------------------------------------------
# nm_compress canonical-form invariant (ragged-tail fast path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K", (96, 100))  # K % m == 0 and the ragged tail
def test_nm_compress_canonical_both_branches(K):
    """Both branches of the ceil-G packer satisfy the canonical-form
    invariant the gather kernels rely on (no per-call tail mask)."""
    from repro.core.pruning import nm_assert_canonical

    n_keep, m = 4, 8
    vals, idx, dense = _compressed_ragged(6, K, n_keep, m, seed=43)
    vals, idx = np.asarray(vals), np.asarray(idx)
    nm_assert_canonical(vals, idx, m, k=K)
    np.testing.assert_array_equal(nm_decompress(vals, idx, m, k=K),
                                  np.asarray(dense))


def test_nm_assert_canonical_catches_violations():
    from repro.core.pruning import nm_assert_canonical

    vals, idx, _ = _compressed_ragged(4, 100, 4, 8, seed=47)
    vals = np.asarray(vals).copy()
    idx = np.asarray(idx).copy()
    nm_assert_canonical(vals, idx, 8, k=100)
    bad_v, bad_i = vals.copy(), idx.copy()
    bad_v[0, -1, -1], bad_i[0, -1, -1] = 5, 7  # dense pos 103 >= k=100
    with pytest.raises(AssertionError, match="tail positions"):
        nm_assert_canonical(bad_v, bad_i, 8, k=100)
    desc = idx.copy()
    desc[0, 0] = desc[0, 0][::-1]
    with pytest.raises(AssertionError, match="ascend"):
        nm_assert_canonical(vals, desc, 8)
    with pytest.raises(AssertionError, match="out of range"):
        nm_assert_canonical(vals, idx + 8, 8)
    # zero-padded groups (index 0 repeated, value 0) ARE canonical —
    # exactly what ops' G-padding produces
    zv = np.zeros((4, 2, 4), vals.dtype)
    zi = np.zeros((4, 2, 4), idx.dtype)
    nm_assert_canonical(np.concatenate([vals, zv], 1),
                        np.concatenate([idx, zi], 1), 8)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs REPRO_FORCE_MULTIDEVICE (see ci.sh shard)")
def test_nm_sharded_census_counts_once():
    vals, idx, dense = _compressed(10, 200, 4, 8, seed=19)
    x = _x(6, 200, seed=19)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    _, ref = pqs_dot(x, dense, acc_bits=16, policy="clip", backend="jnp",
                     with_census=True)
    _, out = pqs_dot(x, (vals, idx), storage="nm", m_group=8, acc_bits=16,
                     policy="clip", backend="jnp", mesh=mesh,
                     with_census=True)
    for field in CENSUS_FIELDS:
        assert int(getattr(out, field)) == int(getattr(ref, field)), field
