"""The main-path Pallas kernels and one whole decode step compile for a
TPU v5e at qwen2-1.5b widths.

Nothing runs: each test lowers with ``interpret=False`` and compiles
against a *described* v5e:2x2 topology (no chip attached), which raises
whatever the chip's compiler would refuse — interpret-mode parity tests
cannot see a block that breaks the (8, 128) tiling or a primitive Mosaic
does not lower. The topology is described only inside the fixture below:
only one process at a time may load the TPU library, so describing it at
import time would break collection under several test workers.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# qwen2-1.5b (configs/qwen2_1_5b.py): d_model 1536, kv width 256, d_ff 8960
D, KV, FF = 1536, 256, 8960


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# (policy, M, K, N, k_tile): decode M=8 and prefill M=256 rows
DENSE = [
    ("wide", 8, D, FF, 64),
    ("clip", 8, FF, D, 64),
    ("wrap", 256, D, KV, 64),
    ("sorted_tiled_seq", 8, FF, D, 64),
    ("sorted_tiled_seq", 256, D, FF, 128),
]


@pytest.mark.parametrize("policy,m,k,n,k_tile", DENSE)
def test_dense_policy_kernel_compiles(one_chip, policy, m, k, n, k_tile):
    bm, bn = ops.default_blocks(policy, "tpu")
    fn = functools.partial(ops.policy_matmul, policy=policy, acc_bits=16,
                           k_tile=k_tile, bm=bm, bn=bn, interpret=False)
    c = _compile(one_chip, fn, ((m, k), jnp.int8), ((n, k), jnp.int8))
    assert _kernels(c) == 1


@pytest.mark.parametrize("policy,m,k,n", [
    ("wide", 8, D, FF),
    ("sorted_tiled_seq", 256, FF, D),
])
def test_nm_expand_kernel_compiles(one_chip, policy, m, k, n):
    """2:4 compressed weights; ``auto`` must pick the expand kernel."""
    bm, bn = ops.default_blocks("nm:" + policy, "tpu")
    fn = functools.partial(ops.nm_policy_matmul, m_group=4, policy=policy,
                           acc_bits=16, k_tile=64, bm=bm, bn=bn,
                           interpret=False)
    g = k // 4
    c = _compile(one_chip, fn, ((m, k), jnp.int8), ((n, g, 2), jnp.int8),
                 ((n, g, 2), jnp.int32))
    assert _kernels(c) == 1


def test_quant_matmul_compiles(one_chip):
    fn = functools.partial(ops.quant_matmul, interpret=False)
    c = _compile(one_chip, fn, ((256, D), jnp.int8), ((D, D), jnp.int8))
    assert _kernels(c) == 1


@pytest.fixture(scope="module")
def qwen2_step(one_chip):
    """One jitted decode step of qwen2-1.5b at published widths (28
    layers, int8 weights, 8 slots x 512 tokens of int8 paged KV) through
    the engine's own step function, compiled once for the tests below."""
    from repro.configs import get_config
    from repro.core.dispatch import IntegerLinConfig
    from repro.core.qtensor import quantize_tree
    from repro.models.model import build_model
    from repro.serving import ServingEngine

    with pytest.MonkeyPatch.context() as mp:
        # steer the platform-keyed choices (default backend, interpret
        # mode, block table) to what they are on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        model = build_model(get_config("qwen2-1.5b"))
        params = jax.eval_shape(
            lambda key: quantize_tree(model.init(key), bits=8),
            jax.random.PRNGKey(0))
        engine = ServingEngine(
            model, params, num_slots=8, max_len=512, page_size=16,
            cache_dtype="int8",
            int_lin=IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24,
                                     k_tile=64),
        )

        def on_chip(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

        return engine._step.lower(
            jax.tree_util.tree_map(on_chip, params),
            on_chip(jax.ShapeDtypeStruct((8, 1), jnp.int32)),
            jax.tree_util.tree_map(on_chip, engine.caches),
            on_chip(jax.ShapeDtypeStruct((8,), jnp.bool_)),
        ).compile()


def test_qwen2_decode_step_compiles_and_fits(qwen2_step):
    """Every projection of the scanned layer is a compiled kernel, and the
    program fits one chip's 16 GB."""
    c = qwen2_step
    assert _kernels(c) == 7  # q, k, v, o, gate, up, down
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, mem


def test_qwen2_decode_step_names_its_work(qwen2_step):
    """What a profile of the step is read by: the module is still
    ``jit_step``, each kernel keeps the name the roofline reader matches
    and sits under its policy's scope, and every instruction the step's
    code traced carries one of the model's scopes (instructions the
    compiler adds, such as async copies and buffer allocations, carry no
    op_name at all)."""
    from chipbench.cell import reader
    from chipbench.spans import hlo_op_names, innermost_scope

    kernel = reader("metrics", "pqs_dot_roofline").KERNEL
    text = qwen2_step.as_text()
    assert text.startswith("HloModule jit_step,")
    kernels = [ln.strip() for ln in text.splitlines()
               if "tpu_custom_call" in ln]
    assert len(kernels) == 7
    names = hlo_op_names(text)
    for k in kernels:
        assert kernel.match(k), k
        assert innermost_scope(names[k[1:].split(" ")[0]]) == \
            "pqs_dot.sorted_tiled_seq"
    traced = {op: on for op, on in names.items()
              if on.startswith("jit(step)/")}
    assert len(traced) > 100
    unscoped = {op: on for op, on in traced.items()
                if not innermost_scope(on)}
    assert not unscoped
