"""Decoder-only LM: train forward, prefill, and KV-cache decode.

Covers the dense / vlm / moe families (qwen2*, qwen3, command-r, gemma3,
granite-moe). Layers are homogeneous, so parameters are *stacked* along
axis 0 and the layer loop is a ``jax.lax.scan`` (fast compiles at 80
layers, GSPMD-friendly: the per-layer all-gather of FSDP-sharded weights
happens inside the loop body). Gemma3's 5:1 local:global pattern rides the
same scan via a traced per-layer ``is_local`` flag.

Decode uses a python loop over layers when the arch mixes cache sizes
(sliding-window rings for local layers, full KV for global ones) and a
scanned stacked cache otherwise.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models.hints import hint_batch, hint_batch_seq, hint_logits
from repro.models.layers import (
    Params,
    attention,
    attention_decode,
    attn_init,
    dense_init,
    empty_kv_cache,
    lin,
    mlp,
    mlp_init,
    norm,
    norm_init,
    write_prefill_kv,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def layer_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    p: Params = {
        "ln1": norm_init(cfg.d_model),
        "attn": attn_init(ks[0], cfg),
        "ln2": norm_init(cfg.d_model),
    }
    if cfg.moe is not None:
        p["moe"] = moe_lib.moe_init(ks[1], cfg, cfg.moe)
    else:
        p["mlp"] = mlp_init(ks[1], cfg)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 2)
    stacked = jax.vmap(lambda k: layer_init(k, cfg))(keys[: cfg.num_layers])
    p: Params = {
        "layers": stacked,
        "ln_f": norm_init(cfg.d_model),
    }
    if (not cfg.input_is_embeddings) or cfg.tie_embeddings:
        p["embed"] = (
            jax.random.normal(
                keys[-2], (cfg.vocab_size, cfg.d_model), jnp.dtype(cfg.param_dtype)
            )
            * (1.0 / cfg.d_model**0.5)
        )
    if not cfg.tie_embeddings:
        p["head"] = dense_init(keys[-1], cfg.d_model, cfg.vocab_size,
                               jnp.dtype(cfg.param_dtype))
    return p


def layer_windows(cfg: ModelConfig) -> list[Optional[int]]:
    """Static per-layer sliding window (None = global attention)."""
    out: list[Optional[int]] = []
    for i in range(cfg.num_layers):
        if cfg.sliding_window is not None and cfg.global_period is not None:
            is_global = (i % cfg.global_period) == cfg.global_period - 1
            out.append(None if is_global else cfg.sliding_window)
        elif cfg.sliding_window is not None:
            out.append(cfg.sliding_window)
        else:
            out.append(None)
    return out


def is_local_flags(cfg: ModelConfig) -> jax.Array:
    return jnp.asarray([w is not None for w in layer_windows(cfg)])


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def embed_tokens(params: Params, tokens: jax.Array, cfg: ModelConfig):
    """tokens (B,S) int32 -> (B,S,d) activations, or pass embeddings through."""
    dt = jnp.dtype(cfg.compute_dtype)
    if tokens.dtype in (jnp.int32, jnp.int64):
        from repro.core.qtensor import asarray

        x = asarray(params["embed"], dt)[tokens]
    else:
        x = tokens.astype(dt)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model**0.5, dt)
    return x


def logits_from_hidden(params: Params, x: jax.Array, cfg: ModelConfig):
    x = norm(x, params["ln_f"], cfg)
    if cfg.tie_embeddings:
        from repro.core.qtensor import asarray

        return x @ asarray(params["embed"], x.dtype).T
    return lin(x, params["head"], site="head")


def _layer_body(p: Params, x, positions, is_local, *, cfg: ModelConfig,
                window: Optional[int]):
    """One pre-norm transformer layer. Returns (x, aux_loss)."""
    h = attention(
        p["attn"], norm(x, p["ln1"], cfg), positions, cfg,
        causal=True, window=window, use_window=is_local,
    )
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        h, aux = moe_lib.moe_ffn(p["moe"], norm(x, p["ln2"], cfg), cfg, cfg.moe)
    else:
        h = mlp(p["mlp"], norm(x, p["ln2"], cfg), cfg)
    return x + h, aux


def forward(
    params: Params,
    tokens: jax.Array,  # (B, S) int32 or (B, S, d) embeddings
    positions: Optional[jax.Array] = None,
    cfg: ModelConfig = None,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, moe_aux_loss)."""
    b = tokens.shape[0]
    s = tokens.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions, (3, b, s))
    hint = hint_batch_seq if cfg.seq_parallel else hint_batch
    x = hint(embed_tokens(params, tokens, cfg))

    window = cfg.sliding_window
    flags = is_local_flags(cfg)

    def body(carry, inp):
        x, aux = carry
        p, flag = inp
        fn = partial(_layer_body, cfg=cfg, window=window)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        x, a = fn(p, x, positions, flag)
        return (hint(x), aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (params["layers"], flags),
                               unroll=cfg.scan_unroll)
    logits = hint_logits(logits_from_hidden(params, x, cfg))
    return logits, aux / max(cfg.num_layers, 1)


# ---------------------------------------------------------------------------
# one-shot batched prefill (serving admission path)
# ---------------------------------------------------------------------------


def prefill_step(
    params: Params,
    tokens: jax.Array,  # (B, S) int32 or (B, S, d) embeddings; left-aligned
    caches: Any,
    lengths: jax.Array,  # (B,) int32 — valid prompt tokens per slot (0=skip)
    cfg: ModelConfig,
) -> tuple[jax.Array, Any]:
    """Consume whole prompts in ONE batched step, filling decode caches.

    Functionally equivalent to feeding each slot's tokens[b, :lengths[b]]
    through ``decode_step`` one position at a time, but executed as a
    single full-sequence forward: per-layer post-RoPE K/V are captured
    (unexpanded) and scattered into the per-slot cache lanes, masked by
    ``lengths`` — padded tail positions never touch the cache, and
    causality keeps them from influencing valid positions. Returns
    (logits (B, S, V), new_caches) with ``pos = lengths``.
    """
    b = tokens.shape[0]
    s = tokens.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(positions, (3, b, s))
    with jax.named_scope("embed"):
        x = hint_batch(embed_tokens(params, tokens, cfg))

    wins = layer_windows(cfg)
    flags = is_local_flags(cfg)
    homogeneous = all(w == wins[0] for w in wins)

    def one_layer(p, x, cache, flag, win):
        with jax.named_scope("attn"):
            h, (k, v) = attention(
                p["attn"], norm(x, p["ln1"], cfg), positions, cfg,
                causal=True, window=win, use_window=flag, return_kv=True,
            )
            x = x + h
            cache = write_prefill_kv(cache, k, v, lengths)
        with jax.named_scope("mlp"):
            if cfg.moe is not None:
                # per-token routing: identical capacity situation to
                # decode, so prefill never capacity-drops a token decode
                # would keep
                h, _ = moe_lib.moe_ffn_per_token(
                    p["moe"], norm(x, p["ln2"], cfg), cfg, cfg.moe)
            else:
                h = mlp(p["mlp"], norm(x, p["ln2"], cfg), cfg)
            x = x + h
        return x, cache

    if homogeneous:
        def body(x, inp):
            p, flag, cache = inp
            x, new_cache = one_layer(p, x, cache, flag, wins[0])
            return hint_batch(x), new_cache

        # the scan's own per-layer slicing of weights and caches
        with jax.named_scope("layers"):
            x, new_caches = jax.lax.scan(
                body, x, (params["layers"], flags, caches),
                unroll=cfg.scan_unroll,
            )
    else:
        new_caches = []
        for i, win in enumerate(wins):
            p = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x, nc = one_layer(p, x, caches[i], flags[i], win)
            new_caches.append(nc)
    with jax.named_scope("head"):
        logits = hint_logits(logits_from_hidden(params, x, cfg))
    return logits, new_caches


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_decode_caches(
    params: Params, cfg: ModelConfig, batch: int, max_len: int, dtype,
    paging=None,
) -> Any:
    """Stacked (homogeneous) or per-layer-list (mixed-window) caches.

    With ``paging`` (a ``serving.paged_cache.PagedSpec``) global layers
    get pool-backed paged KV; sliding-window layers keep dense rings —
    they are already O(window) per slot, so paging buys them nothing.
    """
    wins = layer_windows(cfg)

    def one(win):
        if paging is not None and win is None:
            from repro.serving import paged_cache as pc

            return pc.empty_paged_kv(batch, paging, cfg.num_kv_heads,
                                     cfg.resolved_head_dim, dtype)
        return empty_kv_cache(cfg, batch, max_len, win, dtype)

    if all(w == wins[0] for w in wins):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape),
            one(wins[0]),
        )
    return [one(w) for w in wins]


def decode_step(
    params: Params,
    token: jax.Array,  # (B, 1) int32 or (B, 1, d) embeddings
    caches: Any,
    cfg: ModelConfig,
) -> tuple[jax.Array, Any]:
    """One decode step; returns (logits (B,1,V), new_caches)."""
    with jax.named_scope("embed"):
        x = embed_tokens(params, token, cfg)
    wins = layer_windows(cfg)
    homogeneous = all(w == wins[0] for w in wins)

    def one_layer(p, x, cache, window):
        with jax.named_scope("attn"):
            h, new_cache = attention_decode(
                p["attn"], norm(x, p["ln1"], cfg), cache, cfg, window=window
            )
            x = x + h
        with jax.named_scope("mlp"):
            if cfg.moe is not None:
                h, _ = moe_lib.moe_ffn(
                    p["moe"], norm(x, p["ln2"], cfg), cfg, cfg.moe)
            else:
                h = mlp(p["mlp"], norm(x, p["ln2"], cfg), cfg)
            x = x + h
        return x, new_cache

    if homogeneous:
        def body(x, inp):
            p, cache = inp
            x, new_cache = one_layer(p, x, cache, wins[0])
            return hint_batch(x), new_cache

        # the scan's own per-layer slicing of weights and caches
        with jax.named_scope("layers"):
            x, new_caches = jax.lax.scan(body, x, (params["layers"], caches),
                                         unroll=cfg.scan_unroll)
    else:
        new_caches = []
        for i, w in enumerate(wins):
            p = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x, nc = one_layer(p, x, caches[i], w)
            new_caches.append(nc)
    with jax.named_scope("head"):
        logits = hint_logits(logits_from_hidden(params, x, cfg))
    return logits, new_caches


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def lm_loss(
    logits: jax.Array,  # (B, S, V)
    labels: jax.Array,  # (B, S) int32; -1 = ignore
    aux: jax.Array = 0.0,
    aux_weight: float = 0.01,
    z_weight: float = 1e-4,
) -> jax.Array:
    lg = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(
        lg, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    valid = (labels >= 0).astype(jnp.float32)
    nll = (lse - gold) * valid
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    z_loss = jnp.sum((lse**2) * valid) / denom
    return jnp.sum(nll) / denom + aux_weight * aux + z_weight * z_loss
