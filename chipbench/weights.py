"""Seeded weights of a dense Qwen-style decoder, made on the device.

The weights are the model: every projection is int8 values with one f32
scale per output channel (``W[i, o] = q[i, o] * s[o]``), norms and biases
are f32. The harness hands them to the program in the program's layout
(``program.py``); the plain reference (``reference.py``) makes the same
values again from the same seed, one layer at a time, and never reads
what the program holds.

Names follow the published (Hugging Face) checkpoints. A projection is
stored ``(in, out)``, so ``x @ W`` applies it.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

# std of an integer drawn uniformly from [-127, 127]
_INT8_STD = 127 / math.sqrt(3)

LAYER_LEAVES = (
    "input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
    "q_bias", "k_bias", "v_bias", "q_norm", "k_norm",
    "post_attention_layernorm", "gate_proj", "up_proj", "down_proj",
)
GLOBAL_LEAVES = ("embed_tokens", "norm", "lm_head")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes a configuration file states, in its own key names."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    qk_norm: bool
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(
            layers=c["num_hidden_layers"], d=c["hidden_size"],
            heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim",
                           c["hidden_size"] // c["num_attention_heads"]),
            ff=c["intermediate_size"], vocab=c["vocab_size"],
            tied=c["tie_word_embeddings"], qkv_bias=c["attention_bias"],
            qk_norm=c["qk_norm"], rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
        )

    def projections(self) -> dict[str, tuple[int, int]]:
        """(in, out) of each projection of one layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {
            "q_proj": (self.d, q), "k_proj": (self.d, kv),
            "v_proj": (self.d, kv), "o_proj": (q, self.d),
            "gate_proj": (self.d, self.ff), "up_proj": (self.d, self.ff),
            "down_proj": (self.ff, self.d),
        }


def root_key(seed: int) -> jax.Array:
    """A key from any whole number, past 32 bits too. The seed is data,
    not a constant of the programs that take the key: a new seed compiles
    nothing."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _int8(key, shape) -> jax.Array:
    bits = jax.random.bits(key, shape, jnp.uint8)
    return jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8), -127)


def _projection(key, fan_in: int, shape) -> tuple[jax.Array, jax.Array]:
    """int8 values and per-output-channel scales; the float weight has a
    standard deviation near 1/sqrt(fan_in)."""
    kq, ks = jax.random.split(key)
    scale = (0.75 + 0.5 * jax.random.uniform(ks, shape[-1:])) / (
        _INT8_STD * math.sqrt(fan_in))
    return _int8(kq, shape), scale


def _around(key, shape, centre: float, spread: float) -> jax.Array:
    return centre + spread * jax.random.uniform(key, shape, minval=-1.0,
                                                maxval=1.0)


def layer_weights(key, dims: Dims) -> dict:
    """One layer: projections as (values, scales), the rest f32."""
    k = {n: jax.random.fold_in(key, i) for i, n in enumerate(LAYER_LEAVES)}
    w = {
        name: _projection(k[name], fi, (fi, fo))
        for name, (fi, fo) in dims.projections().items()
    }
    w["input_layernorm"] = _around(k["input_layernorm"], (dims.d,), 1.0, 0.1)
    w["post_attention_layernorm"] = _around(
        k["post_attention_layernorm"], (dims.d,), 1.0, 0.1)
    if dims.qkv_bias:
        for name in ("q", "k", "v"):
            n = dims.projections()[f"{name}_proj"][1]
            w[f"{name}_bias"] = _around(k[f"{name}_bias"], (n,), 0.0, 0.1)
    if dims.qk_norm:
        for name in ("q_norm", "k_norm"):
            w[name] = _around(k[name], (dims.head_dim,), 1.0, 0.1)
    return w


def layer_key(root: jax.Array, i) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root, 0), i)


def global_weights(root: jax.Array, dims: Dims) -> dict:
    key = jax.random.fold_in(root, 1)
    k = {n: jax.random.fold_in(key, i) for i, n in enumerate(GLOBAL_LEAVES)}
    w = {
        "embed_tokens": _projection(k["embed_tokens"], dims.d,
                                    (dims.vocab, dims.d)),
        "norm": _around(k["norm"], (dims.d,), 1.0, 0.1),
    }
    if not dims.tied:
        w["lm_head"] = _projection(k["lm_head"], dims.d, (dims.d, dims.vocab))
    return w


def all_weights(root: jax.Array, dims: Dims) -> tuple[dict, dict]:
    """(layer-stacked weights, global weights), traceable in one jit.

    Layers are made one at a time inside the program (``lax.map``), so
    the temporaries of a whole stack never exist at once.
    """
    keys = jax.vmap(lambda i: layer_key(root, i))(jnp.arange(dims.layers))
    layers = jax.lax.map(lambda kk: layer_weights(kk, dims), keys)
    return layers, global_weights(root, dims)


def dequant(values_scale, dtype=jnp.float32) -> jax.Array:
    q, s = values_scale
    return q.astype(dtype) * s.astype(dtype)
