"""Sharded ``pqs_dot``: multi-device CPU mesh vs single-device reference.

Run with forced host devices (scripts/ci.sh does this as its own shard):

    REPRO_FORCE_MULTIDEVICE=1 python -m pytest tests/test_sharded_dispatch.py

The contract: for every accumulation policy and every sharding layout
(data-only, model-only, full 2-D, degraded/non-dividing), the mesh
execution is BIT-IDENTICAL to the single-device reference — each shard
accumulates its (M_shard, N_shard) block over the whole K axis with the
unmodified single-device routine, so distribution never changes the
narrow-accumulation order. Inside the normal single-device suite this
module self-skips (forcing 8 host devices there would change every
other test's topology).
"""

import os

# opt-in, and only effective before the first jax backend init — the
# flag must not leak a 2-device-topology into the single-device suite.
# A numeric value > 1 forces that many host devices (scripts/ci.sh uses
# 8); "1" or a non-numeric truthy value keeps the historical 8.
if os.environ.get("REPRO_FORCE_MULTIDEVICE") and (
    "--xla_force_host_platform_device_count"
    not in os.environ.get("XLA_FLAGS", "")
):
    _v = os.environ["REPRO_FORCE_MULTIDEVICE"]
    _n = int(_v) if _v.isdigit() and int(_v) > 1 else 8
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", "")
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

if len(jax.devices()) < 2:
    pytest.skip(
        "needs a multi-device backend (XLA_FLAGS="
        "--xla_force_host_platform_device_count=8 before jax init)",
        allow_module_level=True,
    )

from repro.core.dispatch import IntegerLinConfig, pqs_dot  # noqa: E402
from repro.core.qtensor import QTensor, quantize_tree  # noqa: E402

POLICIES = ("wide", "clip", "wrap", "sorted", "sorted_tiled",
            "sorted_tiled_seq")
# ragged shapes on purpose: M=5 does not divide the 4-way data axis and
# N=6 does not divide the 2-way model axis -> sanitize degradation path
SHAPES = ((8, 300, 6), (5, 128, 16), (4, 96, 8))


def _mesh(data, model):
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _xw(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 127, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 127, (n, k)), jnp.int8)
    return x, w


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("mesh_shape", [(4, 2), (8, 1), (1, 8), (2, 2)])
def test_sharded_bit_identical(policy, mesh_shape):
    mesh = _mesh(*mesh_shape)
    for i, (m, k, n) in enumerate(SHAPES):
        x, w = _xw(m, k, n, seed=i)
        ref = pqs_dot(x, w, acc_bits=14, policy=policy, k_tile=64,
                      backend="jnp")
        out = pqs_dot(x, w, acc_bits=14, policy=policy, k_tile=64,
                      backend="jnp", mesh=mesh)
        np.testing.assert_array_equal(
            np.asarray(ref), np.asarray(out),
            err_msg=f"{policy} mesh={mesh_shape} shape={(m, k, n)}",
        )


def test_sharded_pallas_backend():
    """The interpret-mode Pallas kernels also run inside shard_map."""
    mesh = _mesh(4, 2)
    x, w = _xw(8, 128, 16, seed=3)
    ref = pqs_dot(x, w, acc_bits=14, policy="sorted_tiled_seq", k_tile=64,
                  backend="jnp")
    out = pqs_dot(x, w, acc_bits=14, policy="sorted_tiled_seq", k_tile=64,
                  backend="pallas", block_m=4, block_n=8, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_sharded_census_counts_once():
    """Census counters psum only over the partitioning axes — a dot is
    never double-counted by replicated shards."""
    mesh = _mesh(4, 2)
    x, w = _xw(6, 200, 10, seed=5)
    _, ref = pqs_dot(x, w, acc_bits=16, policy="clip", backend="jnp",
                     with_census=True)
    _, out = pqs_dot(x, w, acc_bits=16, policy="clip", backend="jnp",
                     mesh=mesh, with_census=True)
    for field in ("n_dots", "n_persistent", "n_transient", "n_any"):
        assert int(getattr(out, field)) == int(getattr(ref, field)), field


def test_sharded_under_jit_and_leading_dims():
    mesh = _mesh(2, 4)
    x, w = _xw(12, 96, 8, seed=9)
    x3 = x.reshape(2, 6, 96)
    ref = pqs_dot(x, w, acc_bits=16, policy="sorted", backend="jnp")
    f = jax.jit(lambda a, b: pqs_dot(a, b, acc_bits=16, policy="sorted",
                                     backend="jnp", mesh=mesh))
    out = f(x3, w)
    assert out.shape == (2, 6, 8)
    np.testing.assert_array_equal(np.asarray(out).reshape(12, 8),
                                  np.asarray(ref))


CENSUS_FIELDS = ("n_dots", "n_persistent", "n_transient", "n_any",
                 "n_combine")


def _mesh3(data, model, k):
    return jax.make_mesh((data, model, k), ("data", "model", "k"),
                         axis_types=(AxisType.Auto,) * 3)


@pytest.mark.parametrize("policy", POLICIES)
def test_kshard_mesh_matches_oracle(policy):
    """K partitioned across a mesh axis: each device accumulates its
    K/S slice, partials all-gather and tree-combine — bit-identical to
    the single-device k_shards=S hierarchy, census (incl. combine
    steps) equal. M/N shard alongside on their own axes."""
    mesh = _mesh3(2, 2, 2)
    for i, (m, k, n) in enumerate(((3, 500, 5), (2, 96, 4))):
        x, w = _xw(m, k, n, seed=40 + i)
        ref, cr = pqs_dot(x, w, acc_bits=14, policy=policy, k_tile=32,
                          backend="jnp", k_shards=2, with_census=True)
        out, co = pqs_dot(x, w, acc_bits=14, policy=policy, k_tile=32,
                          backend="jnp", mesh=mesh, k_axis="k",
                          with_census=True)
        np.testing.assert_array_equal(
            np.asarray(ref), np.asarray(out),
            err_msg=f"{policy} shape={(m, k, n)}",
        )
        for field in CENSUS_FIELDS:
            assert int(getattr(cr, field)) == int(getattr(co, field)), (
                policy, field)


@pytest.mark.parametrize("policy", POLICIES)
def test_kshard_mesh_nm_storage(policy):
    """The K-shard sweep on N:M compressed storage: compressed slabs
    shard whole groups over the K axis, identical to the single-device
    nm hierarchy (which itself equals decompress-then-dense at aligned
    boundaries — tests/test_property_parity.py)."""
    from repro.core.pruning import nm_compress, nm_prune_mask

    mesh = _mesh3(2, 2, 2)
    n_keep, mg = 4, 16
    m, k, n = 3, 192, 4
    rng = np.random.default_rng(7)
    wd = rng.integers(-127, 127, (n, k)).astype(np.int8)
    mask = np.asarray(
        nm_prune_mask(jnp.asarray(wd, jnp.float32), n_keep, mg))
    wd = (wd * mask).astype(np.int8)
    vals, idx = nm_compress(wd, n_keep, mg)
    vals, idx = jnp.asarray(vals, jnp.int8), jnp.asarray(idx, jnp.int32)
    x = jnp.asarray(rng.integers(-127, 127, (m, k)), jnp.int8)
    kw = dict(storage="nm", m_group=mg, acc_bits=14, policy=policy,
              k_tile=32, backend="jnp", with_census=True)
    ref, cr = pqs_dot(x, (vals, idx), k_shards=2, **kw)
    out, co = pqs_dot(x, (vals, idx), mesh=mesh, k_axis="k", **kw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out),
                                  err_msg=policy)
    for field in CENSUS_FIELDS:
        assert int(getattr(cr, field)) == int(getattr(co, field)), (
            policy, field)


@pytest.mark.parametrize("policy", POLICIES)
def test_kshard_mesh_long_k_past_stream_bound(policy):
    """The acceptance case: total K = 2 x MAX_STREAM_K — past what any
    single compiled sort kernel may stream — split across the K axis so
    each device holds exactly MAX_STREAM_K. Bit-identical to the
    hierarchical jnp oracle, combine census reported."""
    from repro.kernels.ops import MAX_STREAM_K

    mesh = _mesh3(1, 2, 2)
    k = 2 * MAX_STREAM_K
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.integers(-127, 127, (2, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 127, (4, k)), jnp.int8)
    ref, cr = pqs_dot(x, w, acc_bits=20, policy=policy, k_tile=256,
                      backend="jnp", k_shards=2, with_census=True)
    out, co = pqs_dot(x, w, acc_bits=20, policy=policy, k_tile=256,
                      backend="jnp", mesh=mesh, k_axis="k",
                      with_census=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out),
                                  err_msg=policy)
    for field in CENSUS_FIELDS:
        assert int(getattr(cr, field)) == int(getattr(co, field)), (
            policy, field)


def test_kshard_mesh_long_k_nm_storage():
    """Long-K acceptance on compressed storage (one policy end-to-end:
    sorted_tiled_seq, the production default)."""
    from repro.core.pruning import nm_compress, nm_prune_mask
    from repro.kernels.ops import MAX_STREAM_K

    mesh = _mesh3(1, 2, 2)
    n_keep, mg = 4, 16
    k = 2 * MAX_STREAM_K
    rng = np.random.default_rng(17)
    wd = rng.integers(-127, 127, (2, k)).astype(np.int8)
    mask = np.asarray(
        nm_prune_mask(jnp.asarray(wd, jnp.float32), n_keep, mg))
    wd = (wd * mask).astype(np.int8)
    vals, idx = nm_compress(wd, n_keep, mg)
    vals, idx = jnp.asarray(vals, jnp.int8), jnp.asarray(idx, jnp.int32)
    x = jnp.asarray(rng.integers(-127, 127, (2, k)), jnp.int8)
    kw = dict(storage="nm", m_group=mg, acc_bits=20,
              policy="sorted_tiled_seq", k_tile=256, backend="jnp",
              with_census=True)
    ref, cr = pqs_dot(x, (vals, idx), k_shards=2, **kw)
    out, co = pqs_dot(x, (vals, idx), mesh=mesh, k_axis="k", **kw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    for field in CENSUS_FIELDS:
        assert int(getattr(cr, field)) == int(getattr(co, field)), field


@pytest.mark.parametrize("policy", POLICIES)
def test_kshard_mesh_four_way_butterfly(policy):
    """S=4: the exchange really is a multi-level butterfly (two ppermute
    rounds), still bit-identical to the single-device hierarchy with the
    exact census decomposition."""
    mesh = _mesh3(1, 2, 4)
    x, w = _xw(3, 448, 4, seed=51)
    kw = dict(acc_bits=14, policy=policy, k_tile=32, backend="jnp",
              with_census=True)
    ref, cr = pqs_dot(x, w, k_shards=4, **kw)
    out, co = pqs_dot(x, w, mesh=mesh, k_axis="k", **kw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out),
                                  err_msg=policy)
    for field in CENSUS_FIELDS:
        assert int(getattr(cr, field)) == int(getattr(co, field)), (
            policy, field)


@pytest.mark.parametrize("policy", ("wide", "clip", "sorted_tiled_seq"))
def test_defer_combine_matches_eager(policy):
    """defer_combine=True: the PendingCombine's .combine() reproduces
    the eager K-sharded result exactly — census included — on both the
    mesh-less hierarchy and the mesh exchange, in and out of jit."""
    mesh = _mesh3(1, 2, 4)
    x, w = _xw(3, 448, 4, seed=61)
    kw = dict(acc_bits=14, policy=policy, k_tile=32, backend="jnp",
              with_census=True)
    ref, cr = pqs_dot(x, w, k_shards=4, **kw)

    for extra in (dict(k_shards=4), dict(mesh=mesh, k_axis="k")):
        out, co = pqs_dot(x, w, defer_combine=True, **extra, **kw).combine()
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out),
                                      err_msg=f"{policy} {extra.keys()}")
        for field in CENSUS_FIELDS:
            assert int(getattr(cr, field)) == int(getattr(co, field)), (
                policy, field)

    # both phases trace into one jitted computation — the overlap form
    f = jax.jit(
        lambda a, b: pqs_dot(
            a, b, mesh=mesh, k_axis="k", defer_combine=True,
            acc_bits=14, policy=policy, k_tile=32, backend="jnp",
        ).combine()
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(f(x, w)))


def test_defer_combine_needs_k_sharding():
    x, w = _xw(2, 64, 3, seed=1)
    with pytest.raises(ValueError, match="K-sharded"):
        pqs_dot(x, w, defer_combine=True, backend="jnp")


def test_overlap_combine_engine_bit_identical():
    """IntegerLinConfig(overlap_combine=True): the engine's K-sharded
    decode routes through the deferred two-phase combine and stays
    bit-identical to the eager path."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_tree(params, bits=8, min_size=1 << 10, min_dim=16)

    def run(overlap):
        il = IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24,
                              k_tile=64, backend="jnp", k_shards=2,
                              k_axis="k", k_shard_min_k=64,
                              overlap_combine=overlap)
        rng = np.random.default_rng(4)
        reqs = [
            Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
                    max_new_tokens=3)
            for i in range(3)
        ]
        eng = ServingEngine(model, qparams, num_slots=2, max_len=16,
                            int_lin=il, mesh=_mesh3(2, 2, 2))
        eng.drain(reqs)
        return [r.output for r in reqs]

    assert run(False) == run(True)


def test_cache_pool_sharded_decode_bit_identical():
    """cache_shardings(serve_mode=True) on a real 8-device mesh: the
    paged KV pool page-sharded over the data axis (each member owns a
    page shard) decodes bit-identically to serve_mode=False's
    replicated pool under the same mesh placement. serve_mode only
    toggles the pool-axis spec, and that axis is pure indirection
    (gather/scatter through the page table, no arithmetic) — so page
    sharding must never change a bit. (The head_dim "model" entry,
    common to both modes, is excluded from the contract: re-tiling a
    float contraction may legally reassociate.)"""
    from repro.configs import get_config
    from repro.launch.sharding import cache_shardings, place_tree
    from repro.models.model import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = _mesh(4, 2)

    def run(serve_mode):
        rng = np.random.default_rng(3)
        reqs = [
            Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 4).astype(np.int32),
                    max_new_tokens=3)
            for i in range(3)
        ]
        eng = ServingEngine(model, params, num_slots=2, max_len=16,
                            page_size=8, num_pages=8)
        sh = cache_shardings(mesh, eng.caches, serve_mode=serve_mode)
        specs = [
            s.spec for s in jax.tree_util.tree_leaves(
                sh, is_leaf=lambda l: hasattr(l, "spec"))
        ]
        if serve_mode:  # the pool axis really is split over "data"
            assert any("data" in str(sp) for sp in specs), (
                "serve_mode placed no pool shard")
        else:
            assert not any("data" in str(sp) for sp in specs)
        eng.caches = place_tree(eng.caches, sh)
        eng.drain(reqs)
        return [list(r.output) for r in reqs]

    assert run(False) == run(True)


def test_kshard_mesh_validation():
    x, w = _xw(2, 64, 3, seed=1)
    mesh = _mesh(4, 2)
    with pytest.raises(ValueError, match="k_axis"):
        pqs_dot(x, w, mesh=mesh, k_shards=2)  # mesh needs a named K axis
    with pytest.raises(ValueError, match="not on the mesh"):
        pqs_dot(x, w, mesh=mesh, k_axis="k")
    mesh3 = _mesh3(2, 2, 2)
    with pytest.raises(ValueError, match="k_shards"):
        pqs_dot(x, w, mesh=mesh3, k_axis="k", k_shards=4)  # axis is 2-way


def test_kshard_integer_serving_engine():
    """End-to-end: the engine's integer decode with long-K projections
    opted into K-sharding on the serving mesh reproduces the
    single-device K-sharded outputs (and the full-K outputs of layers
    below the threshold are untouched by construction)."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_tree(params, bits=8, min_size=1 << 10, min_dim=16)

    def run(mesh, k_axis):
        il = IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24,
                              k_tile=64, backend="jnp", k_shards=2,
                              k_axis=k_axis, k_shard_min_k=64)
        rng = np.random.default_rng(2)
        reqs = [
            Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
                    max_new_tokens=3)
            for i in range(3)
        ]
        eng = ServingEngine(model, qparams, num_slots=2, max_len=16,
                            int_lin=il, mesh=mesh)
        eng.drain(reqs)
        return [r.output for r in reqs]

    assert run(None, None) == run(_mesh3(2, 2, 2), "k")


def test_kshard_min_k_gate_applies_with_axis_only():
    """k_shard_min_k must gate the hierarchy even when the shard count
    is implied by the mesh axis (k_axis= with k_shards=None): short-K
    projections keep the bit-identical full-K path."""
    from repro.core.dispatch import qtensor_dot
    from repro.core.qtensor import quantize_weight

    rng = np.random.default_rng(21)
    w = jnp.asarray(rng.normal(size=(64, 24)), jnp.float32) * 0.1
    x = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    qt = quantize_weight(w, bits=8)
    mesh = _mesh3(2, 2, 2)
    base = dict(policy="sorted_tiled_seq", acc_bits=12, k_tile=16,
                backend="jnp", mesh=mesh)
    full = qtensor_dot(x, qt, IntegerLinConfig(**base))
    gated = qtensor_dot(x, qt, IntegerLinConfig(
        k_axis="k", k_shard_min_k=4096, **base))
    np.testing.assert_array_equal(np.asarray(full), np.asarray(gated))
    # sanity: below the threshold the hierarchy actually engages (a
    # 12-bit register saturates differently under the combine tree)
    sharded = qtensor_dot(x, qt, IntegerLinConfig(
        k_axis="k", k_shard_min_k=0, **base))
    assert sharded.shape == full.shape


def test_kshard_param_placement():
    """params_shardings(k_axis=) puts long-K QTensor leaves' input dim
    on the K axis (serve mode) so the K-sharded dot finds its weight
    shards resident; short-K leaves keep the plain rule."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import params_shardings

    mesh = _mesh3(2, 2, 2)
    params = {
        "attn": {
            "wq": QTensor(jnp.zeros((256, 128), jnp.int8),
                          jnp.zeros((128,)), None),
            "small": QTensor(jnp.zeros((64, 128), jnp.int8),
                             jnp.zeros((128,)), None),
        },
    }
    sh = params_shardings(mesh, params, serve_mode=True, k_axis="k",
                          k_shard_min_k=256)
    assert sh["attn"]["wq"].values.spec == P("k", "model")
    assert sh["attn"]["small"].values.spec == P(None, "model")
    # scales stay on the out entry either way
    assert sh["attn"]["wq"].scale.spec == P("model")


def test_qtensor_param_shardings_on_mesh():
    """QTensor pytrees shard values+scales together through the rules."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import params_shardings

    mesh = _mesh(4, 2)
    params = {
        "layers": {
            "attn": {
                "wq": QTensor(jnp.zeros((4, 128, 256), jnp.int8),
                              jnp.zeros((4, 256)),
                              None),
                "wo": QTensor(jnp.zeros((4, 256, 128), jnp.int8),
                              jnp.zeros((4, 128)),
                              None),
            }
        },
        "norm": jnp.zeros((128,)),
    }
    sh = params_shardings(mesh, params)
    wq = sh["layers"]["attn"]["wq"]
    assert isinstance(wq, QTensor)
    assert wq.values.spec == P(None, "data", "model")
    # scale follows the values' output-channel entry
    assert wq.scale.spec == P(None, "model")
    # out-type projections reverse -> scale rides the data axes
    wo = sh["layers"]["attn"]["wo"]
    assert wo.values.spec == P(None, "model", "data")
    assert wo.scale.spec == P(None, "data")


def test_integer_serving_engine_on_mesh():
    """End-to-end: quantized engine decode with the integer projections
    distributed over the mesh reproduces the single-device outputs."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_tree(params, bits=8, min_size=1 << 10, min_dim=16)
    il = IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24, k_tile=64,
                          backend="jnp")

    def run(mesh):
        rng = np.random.default_rng(1)
        reqs = [
            Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
                    max_new_tokens=3)
            for i in range(3)
        ]
        eng = ServingEngine(model, qparams, num_slots=2, max_len=16,
                            int_lin=il, mesh=mesh)
        eng.drain(reqs)
        return [r.output for r in reqs]

    assert run(None) == run(_mesh(4, 2))
