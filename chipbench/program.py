"""The system under test, as the harness drives it.

The only module of the benchmark that imports the program (``repro``):
it maps a configuration file onto the program's model, hands it the
seeded weights (``weights.py``) in its own parameter layout, and builds
the serving engine with the cell's deployment and accumulation setting.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs import get_config  # noqa: E402
from repro.core.dispatch import IntegerLinConfig  # noqa: E402
from repro.core.qtensor import QTensor  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402,F401
from repro.models.model import build_model  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402,F401

from chipbench.weights import Dims, all_weights, root_key  # noqa: E402

# configuration-file key -> the program's ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm", "rope_theta": "rope_theta",
}


def model_config(config: dict):
    """The program's config for ``config["arch"]`` with the file's sizes.

    Every size the file states must be the program's own, except those
    the file lists under ``reduced``, which are set from the file.
    """
    base = get_config(config["arch"])
    sizes = config["config"]
    over = {}
    for key, field in _FIELDS.items():
        if key not in sizes:
            continue
        want, have = sizes[key], getattr(base, field)
        if key == "head_dim" and have is None:
            have = base.resolved_head_dim
        if want != have:
            if key not in config["reduced"]:
                raise ValueError(
                    f"{config['arch']}: {key}={want} in the configuration "
                    f"file, {field}={have} in the program, and {key} is "
                    "not listed under reduced")
            over[field] = want
    if sizes["hidden_act"] != base.activation or base.norm != "rmsnorm":
        raise ValueError(f"{config['arch']}: not a SiLU/RMSNorm decoder")
    return dataclasses.replace(base, **over)


def _program_tree(layers: dict, glob: dict, dims: Dims) -> dict:
    """Weights in the program's layout: QTensor (values, scales) per
    projection, norms stored as (weight - 1), biases as they are."""
    qt = lambda vs: QTensor(vs[0], vs[1])  # noqa: E731
    attn = {n: qt(layers[f"{n[1]}_proj"]) for n in ("wq", "wk", "wv", "wo")}
    if dims.qkv_bias:
        attn.update({f"b{n}": layers[f"{n}_bias"] for n in "qkv"})
    if dims.qk_norm:
        attn["q_norm"] = layers["q_norm"] - 1.0
        attn["k_norm"] = layers["k_norm"] - 1.0
    tree = {
        "layers": {
            "ln1": layers["input_layernorm"] - 1.0,
            "attn": attn,
            "ln2": layers["post_attention_layernorm"] - 1.0,
            "mlp": {"w_gate": qt(layers["gate_proj"]),
                    "w_up": qt(layers["up_proj"]),
                    "w_out": qt(layers["down_proj"])},
        },
        "ln_f": glob["norm"] - 1.0,
        "embed": qt(glob["embed_tokens"]),
    }
    if not dims.tied:
        tree["head"] = qt(glob["lm_head"])
    return tree


@functools.partial(jax.jit, static_argnums=(1,))
def _make_params(root, dims: Dims):
    return _program_tree(*all_weights(root, dims), dims)


def make_params(model, dims: Dims, seed: int):
    """The served parameters, made on the device in one jitted call.

    Checked against the program's own init, leaf for leaf: each of its
    float matrices is an int8 ``QTensor`` of that shape here, every other
    leaf has its shape and type."""
    params = _make_params(root_key(seed), dims)
    want = jax.eval_shape(model.init, jax.random.key(0))
    is_qt = lambda x: isinstance(x, QTensor)  # noqa: E731
    got_s = jax.tree_util.tree_structure(params, is_leaf=is_qt)
    if got_s != jax.tree_util.tree_structure(want):
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{got_s}\nvs\n{jax.tree_util.tree_structure(want)}")
    for g, w in zip(jax.tree_util.tree_leaves(params, is_leaf=is_qt),
                    jax.tree_util.tree_leaves(want)):
        shape, dtype = ((g.values.shape, jnp.dtype(jnp.int8)) if is_qt(g)
                        else (g.shape, w.dtype))
        if shape != w.shape or (g.values if is_qt(g) else g).dtype != dtype:
            raise ValueError(f"leaf {shape} where the program has {w.shape} "
                             f"{w.dtype}")
    return params


def make_engine(model, params, serving: dict, accum: dict) -> ServingEngine:
    """A paged int8-KV engine whose integer projections run ``pqs_dot``
    under the cell's accumulation setting, on the platform's default
    backend."""
    return ServingEngine(
        model, params, num_slots=serving["slots"],
        max_len=serving["max_len"], page_size=serving["page_size"],
        cache_dtype=serving["kv_cache"],
        int_lin=IntegerLinConfig(**accum),
    )


def set_accum(engine: ServingEngine, accum: dict) -> None:
    """Serve on under another accumulation setting: the engine re-jits
    its steps with it, as it does when census degradation changes a
    site's policy."""
    engine.int_lin = IntegerLinConfig(**accum)
    engine._build_step_fns()


def build(config: dict, seed: int):
    cfg = model_config(config)
    model = build_model(cfg)
    dims = Dims.from_config(config["config"])
    return model, make_params(model, dims, seed)

