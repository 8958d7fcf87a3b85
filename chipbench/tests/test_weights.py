"""The reference makes, layer by layer, the weights the harness hands the
program in one call: the int8 values bit for bit, the f32 scales, norms
and biases to the last unit of rounding (XLA may fuse their few
arithmetic steps differently inside and outside one program)."""

from __future__ import annotations

import jax
import numpy as np

from chipbench import weights
from chipbench.weights import Dims

TINY = Dims(layers=3, d=32, heads=4, kv_heads=2, head_dim=8, ff=48,
            vocab=64, tied=False, qkv_bias=True, qk_norm=True,
            rope_theta=1e4, eps=1e-6)


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == np.int8:
        np.testing.assert_array_equal(a, b)
    else:
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_layer_by_layer_equals_the_stacked_call():
    root = weights.root_key(2**40 + 3)
    layers, glob = jax.jit(weights.all_weights, static_argnums=1)(root,
                                                                  TINY)
    for i in range(TINY.layers):
        one = weights.layer_weights(weights.layer_key(root, i), TINY)
        for name, leaf in one.items():
            got = jax.tree_util.tree_map(lambda a: np.asarray(a)[i],
                                         layers[name])
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(leaf)):
                _same(a, b)
    again = weights.global_weights(root, TINY)
    for a, b in zip(jax.tree_util.tree_leaves(glob),
                    jax.tree_util.tree_leaves(again)):
        _same(a, b)


def test_seeds_give_other_weights_and_int8_stays_symmetric():
    a = weights.global_weights(weights.root_key(1), TINY)["lm_head"][0]
    b = weights.global_weights(weights.root_key(2), TINY)["lm_head"][0]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(a).min()) >= -127
