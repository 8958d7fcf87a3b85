"""build_model(cfg): one API over every architecture family.

Returns a ``Model`` bundle of pure functions:

    init(key)                      -> params
    loss(params, batch)            -> scalar (train objective)
    forward(params, batch)         -> logits (train/prefill shapes)
    init_caches(params, batch, L)  -> decode caches (+ encdec cross-KV)
    decode(params, token, caches)  -> (logits, new_caches)
    param_count(params)            -> int

Batch dicts (produced by data/ and launch/input_specs):
    dense/moe:  {"tokens" (B,S) i32, "labels" (B,S) i32}
    vlm/audio:  {"embeddings"/"frames" (B,S,d) bf16, ["tokens"], "labels"}
    ssm/hybrid: {"tokens", "labels"}
M-RoPE positions for the vlm family ride in "positions" (3,B,S).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qtensor import QTensor, asarray
from repro.models import encdec, hybrid, ssm as ssm_lib, transformer
from repro.models.hints import hint_batch, hint_logits
from repro.models.layers import Params, norm, norm_init


def cast_for_compute(params: Any, cfg: ModelConfig) -> Any:
    """Cast >=2-D float params to compute dtype BEFORE the layer scan.

    Master params stay f32 for the optimizer; casting the *sharded* leaves
    up front means every FSDP all-gather inside the scan moves bf16, not
    f32 — half the ICI traffic (§Perf iteration 3). Gradients flow through
    the convert back to f32 masters. QTensor (int8) leaves pass through.
    """
    dt = jnp.dtype(cfg.compute_dtype)

    def conv(leaf):
        if isinstance(leaf, QTensor):
            return leaf
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and jnp.issubdtype(
            leaf.dtype, jnp.floating
        ):
            return leaf.astype(dt)
        return leaf

    with jax.named_scope("cast"):
        return jax.tree_util.tree_map(
            conv, params, is_leaf=lambda l: isinstance(l, QTensor)
        )
from repro.models.transformer import lm_loss


# ---------------------------------------------------------------------------
# pure-Mamba2 LM (homogeneous -> scan over stacked layers)
# ---------------------------------------------------------------------------


def mamba_lm_init(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 1)
    dt = jnp.dtype(cfg.param_dtype)
    stacked = jax.vmap(
        lambda k: {"ln": norm_init(cfg.d_model), "mamba": ssm_lib.mamba_init(k, cfg)}
    )(keys[: cfg.num_layers])
    return {
        "layers": stacked,
        "ln_f": norm_init(cfg.d_model),
        "embed": jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model), dt)
        * (1.0 / cfg.d_model**0.5),
    }


def mamba_lm_forward(params: Params, tokens: jax.Array, cfg: ModelConfig):
    dt = jnp.dtype(cfg.compute_dtype)
    x = asarray(params["embed"], dt)[tokens]

    def body(x, p):
        def fn(p, x):
            h, _ = ssm_lib.mamba_forward(p["mamba"], norm(x, p["ln"], cfg), cfg)
            return x + h

        step = jax.checkpoint(fn) if cfg.remat else fn
        return hint_batch(step(p, x)), None

    x, _ = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    x = norm(x, params["ln_f"], cfg)
    return hint_logits(x @ asarray(params["embed"], x.dtype).T)


def mamba_lm_init_caches(params, cfg: ModelConfig, batch: int, dtype,
                         paging=None):
    if paging is not None:
        from repro.serving import paged_cache as pc

        dims = ssm_lib.ssm_dims(cfg)
        s = cfg.ssm
        one = pc.empty_paged_ssm(batch, paging, dims["nheads"], s.head_dim,
                                 s.d_state, s.d_conv, dims["d_xbc"], dtype)
    else:
        one = ssm_lib.empty_ssm_cache(cfg, batch, dtype)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), one
    )


def mamba_lm_prefill(params: Params, tokens: jax.Array, caches,
                     lengths: jax.Array, cfg: ModelConfig):
    """One-shot batched prefill: full-sequence SSD per layer with dt
    zeroed past each lane's length (identity recurrence), returning
    layer-stacked {"ssd", "conv"} caches at exactly ``lengths`` tokens.

    Pooled state (paged serving) gathers each slot's state page into the
    dense per-slot view first and scatters the result back after — the
    recurrence itself is unchanged."""
    paged = isinstance(caches, dict) and "ssdp" in caches
    if paged:
        from repro.serving import paged_cache as pc

        caches, put_back = pc.ssm_gather(caches)
    dt = jnp.dtype(cfg.compute_dtype)
    x = asarray(params["embed"], dt)[tokens]

    def body(x, inp):
        p, cache = inp
        h, nc = ssm_lib.mamba_forward(
            p["mamba"], norm(x, p["ln"], cfg), cfg, h0=cache["ssd"],
            lengths=lengths,
        )
        return hint_batch(x + h), nc

    x, new_caches = jax.lax.scan(body, x, (params["layers"], caches),
                                 unroll=cfg.scan_unroll)
    if paged:
        new_caches = put_back(new_caches)
    x = norm(x, params["ln_f"], cfg)
    return hint_logits(x @ asarray(params["embed"], x.dtype).T), new_caches


def mamba_lm_decode(params: Params, token: jax.Array, caches, cfg: ModelConfig):
    paged = isinstance(caches, dict) and "ssdp" in caches
    if paged:
        from repro.serving import paged_cache as pc

        caches, put_back = pc.ssm_gather(caches)
    dt = jnp.dtype(cfg.compute_dtype)
    x = asarray(params["embed"], dt)[token]

    def body(x, inp):
        p, cache = inp
        h, nc = ssm_lib.mamba_step(p["mamba"], norm(x, p["ln"], cfg), cache, cfg)
        return hint_batch(x + h), nc

    x, new_caches = jax.lax.scan(
        body, x, (params["layers"], caches), unroll=cfg.scan_unroll
    )
    if paged:
        new_caches = put_back(new_caches)
    x = norm(x, params["ln_f"], cfg)
    return hint_logits(x @ asarray(params["embed"], x.dtype).T), new_caches


# ---------------------------------------------------------------------------
# unified bundle
# ---------------------------------------------------------------------------


def merge_caches_on_axis(axis: int) -> Callable[[Any, Any, jax.Array], Any]:
    """Per-sequence cache selector for continuous batching.

    Returns ``merge(old, new, active)`` where ``active`` is a (B,) bool
    mask over the cache's batch axis: active lanes take the freshly
    decoded cache, inactive lanes keep their previous state untouched.
    ``axis`` is where the batch dim lives in every cache leaf (1 for
    layer-stacked caches, 0 for per-layer cache lists).

    Paged cache nodes (page pools, no per-slot batch axis) merge per
    page via ``paged_cache.paged_merge`` — same invariant, pool layout.
    """

    def merge(old: Any, new: Any, active: jax.Array) -> Any:
        from repro.serving import paged_cache as pc

        def sel(o, n):
            if pc.is_paged(o):
                return pc.paged_merge(o, n, active)
            shape = [1] * o.ndim
            shape[axis] = active.shape[0]
            return jnp.where(active.reshape(shape), n, o)

        return jax.tree_util.tree_map(sel, old, new, is_leaf=pc.is_paged)

    return merge


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Params]
    forward: Callable[..., jax.Array]  # (params, batch) -> logits
    loss: Callable[..., jax.Array]  # (params, batch) -> scalar
    init_caches: Callable[..., Any]  # (params, batch_size, max_len, dtype)
    decode: Callable[..., tuple]  # (params, token, caches) -> (logits, caches)
    # (old_caches, new_caches, active (B,) bool) -> caches with inactive
    # sequences' state preserved — the serving engine's slot isolation.
    merge_caches: Callable[..., Any] = None
    # (params, tokens (B,S), caches, lengths (B,)) -> (logits, new_caches):
    # one-shot batched prefill — consume tokens[b, :lengths[b]] into slot
    # b's cache lanes in a single step (engine admission path).
    prefill: Callable[..., tuple] = None


def _tokens_or_embeddings(batch: dict) -> jax.Array:
    if "embeddings" in batch:
        return batch["embeddings"]
    if "frames" in batch:
        return batch["frames"]
    return batch["tokens"]


def build_model(cfg: ModelConfig) -> Model:
    cfg.validate()
    fam = cfg.family

    if fam in ("dense", "moe", "vlm"):
        def fwd(params, batch):
            logits, _ = transformer.forward(
                cast_for_compute(params, cfg), _tokens_or_embeddings(batch),
                batch.get("positions"), cfg,
            )
            return logits

        def loss(params, batch):
            logits, aux = transformer.forward(
                cast_for_compute(params, cfg), _tokens_or_embeddings(batch),
                batch.get("positions"), cfg,
            )
            return lm_loss(logits, batch["labels"], aux)

        wins = transformer.layer_windows(cfg)
        stacked = all(w == wins[0] for w in wins)
        return Model(
            cfg=cfg,
            init=lambda key: transformer.init_params(key, cfg),
            forward=fwd,
            loss=loss,
            init_caches=lambda params, b, L, dt=jnp.bfloat16, paging=None:
                transformer.init_decode_caches(params, cfg, b, L, dt,
                                               paging=paging),
            decode=lambda params, tok, caches: transformer.decode_step(
                cast_for_compute(params, cfg), tok, caches, cfg),
            merge_caches=merge_caches_on_axis(1 if stacked else 0),
            prefill=lambda params, toks, caches, lengths:
                transformer.prefill_step(
                    cast_for_compute(params, cfg), toks, caches, lengths,
                    cfg),
        )

    if fam == "audio" or cfg.is_encoder_decoder:
        def fwd(params, batch):
            return encdec.forward(cast_for_compute(params, cfg),
                                  batch["frames"], batch["tokens"], cfg)

        def loss(params, batch):
            logits = fwd(params, batch)
            return lm_loss(logits, batch["labels"])

        def init_caches(params, b, L, dt=jnp.bfloat16, enc_out=None,
                        paging=None):
            kv = encdec.init_decode_caches(params, cfg, b, L, dt,
                                           paging=paging)
            if enc_out is None:  # shape-only path for the dry-run
                enc_out = jnp.zeros((b, 1500, cfg.d_model), dt)
            cross = encdec.precompute_cross_kv(params, enc_out, cfg)
            return {"self": kv, "cross": cross}

        return Model(
            cfg=cfg,
            init=lambda key: encdec.init_params(key, cfg),
            forward=fwd,
            loss=loss,
            init_caches=init_caches,
            decode=lambda params, tok, caches: (
                lambda out: (out[0], {"self": out[1], "cross": caches["cross"]})
            )(encdec.decode_step(cast_for_compute(params, cfg), tok,
                                 caches["self"], caches["cross"], cfg)),
            merge_caches=merge_caches_on_axis(1),  # {self,cross}: (L,B,...)
            prefill=lambda params, toks, caches, lengths: (
                lambda out: (out[0], {"self": out[1],
                                      "cross": caches["cross"]})
            )(encdec.prefill_step(cast_for_compute(params, cfg), toks,
                                  caches["self"], caches["cross"], lengths,
                                  cfg)),
        )

    if fam == "hybrid":
        def loss(params, batch):
            logits, aux = hybrid.forward(
                cast_for_compute(params, cfg), batch["tokens"], None, cfg)
            return lm_loss(logits, batch["labels"], aux)

        return Model(
            cfg=cfg,
            init=lambda key: hybrid.init_params(key, cfg),
            forward=lambda params, batch: hybrid.forward(
                cast_for_compute(params, cfg), batch["tokens"], None,
                cfg)[0],
            loss=loss,
            init_caches=lambda params, b, L, dt=jnp.bfloat16, paging=None:
                hybrid.init_decode_caches(params, cfg, b, L, dt,
                                          paging=paging),
            decode=lambda params, tok, caches: hybrid.decode_step(
                cast_for_compute(params, cfg), tok, caches, cfg),
            merge_caches=merge_caches_on_axis(0),  # per-layer list: (B,...)
            prefill=lambda params, toks, caches, lengths:
                hybrid.prefill_step(cast_for_compute(params, cfg), toks,
                                    caches, lengths, cfg),
        )

    if fam == "ssm":
        def loss(params, batch):
            logits = mamba_lm_forward(
                cast_for_compute(params, cfg), batch["tokens"], cfg)
            return lm_loss(logits, batch["labels"])

        return Model(
            cfg=cfg,
            init=lambda key: mamba_lm_init(key, cfg),
            forward=lambda params, batch: mamba_lm_forward(
                cast_for_compute(params, cfg), batch["tokens"], cfg),
            loss=loss,
            init_caches=lambda params, b, L, dt=jnp.float32, paging=None:
                mamba_lm_init_caches(params, cfg, b, dt, paging=paging),
            decode=lambda params, tok, caches: mamba_lm_decode(
                cast_for_compute(params, cfg), tok, caches, cfg),
            merge_caches=merge_caches_on_axis(1),  # layer-stacked: (L,B,...)
            prefill=lambda params, toks, caches, lengths: mamba_lm_prefill(
                cast_for_compute(params, cfg), toks, caches, lengths, cfg),
        )

    raise ValueError(f"unknown family {fam!r}")


def param_count(params: Any) -> int:
    def leaf_size(a):
        return int(a.size) if hasattr(a, "size") else 0

    return sum(leaf_size(a) for a in jax.tree_util.tree_leaves(params))


def active_param_count(cfg: ModelConfig, total: int) -> int:
    """MoE-aware active parameter count (for MODEL_FLOPS = 6 N_active D)."""
    if cfg.moe is None:
        return total
    m = cfg.moe
    # expert params scale by top_k/num_experts; estimate expert fraction
    expert = 3 * cfg.d_model * m.d_ff * m.num_experts
    n_moe_layers = len(
        [i for i in range(cfg.num_layers)
         if i % m.layer_period == m.layer_offset]
    )
    expert_total = expert * n_moe_layers
    active_expert = expert_total * m.top_k / m.num_experts
    return int(total - expert_total + active_expert)
