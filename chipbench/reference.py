"""Plain float32 reference of a dense Qwen-style decoder.

Follows the published architecture (Qwen2 / Qwen3 ``modeling_*.py``):
pre-norm RMSNorm blocks, grouped-query attention with rotary positions
(half-split ``rotate_half``), optional q/k/v biases (Qwen2) and per-head
q/k RMSNorm (Qwen3), a SiLU-gated MLP, and tied or untied logits. No
cache, no kernels, no batching across sequences: one causal forward over
each whole sequence, with every matmul at ``highest`` precision. It
imports nothing of the program under test and makes its weights from the
seed itself (``weights.py``).

``quant_bits`` gives the control: every projection (logits included)
computed from weights requantized to that many bits per output channel
and activations quantized per tensor, the precision step below the int8
the configurations state.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import Dims, dequant, global_weights, layer_key, \
    layer_weights, root_key


def _fake_quant(x: jax.Array, bits: int, axis=None) -> jax.Array:
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12)
    s = amax / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _matmul(x, w, quant_bits: Optional[int]):
    """x (..., in) @ w (in, out); the control quantizes both operands."""
    if quant_bits is not None:
        x = _fake_quant(x, quant_bits)
        w = _fake_quant(w, quant_bits, axis=0)
    return x @ w


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x (S, H, hd); HF rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv  # (S, hd/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(x, w, dims: Dims, quant_bits):
    """One decoder layer over one sequence x (S, d)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms_norm(x, w["input_layernorm"], dims.eps)
    proj = {n: dequant(w[n]) for n in dims.projections()}
    q = _matmul(h, proj["q_proj"], quant_bits)
    k = _matmul(h, proj["k_proj"], quant_bits)
    v = _matmul(h, proj["v_proj"], quant_bits)
    if dims.qkv_bias:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    q = q.reshape(s, dims.heads, dims.head_dim)
    k = k.reshape(s, dims.kv_heads, dims.head_dim)
    v = v.reshape(s, dims.kv_heads, dims.head_dim)
    if dims.qk_norm:
        q = _rms_norm(q, w["q_norm"], dims.eps)
        k = _rms_norm(k, w["k_norm"], dims.eps)
    q, k = _rope(q, pos, dims.rope_theta), _rope(k, pos, dims.rope_theta)
    rep = dims.heads // dims.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dims.head_dim)
    causal = pos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1)
    x = x + _matmul(o, proj["o_proj"], quant_bits)
    h = _rms_norm(x, w["post_attention_layernorm"], dims.eps)
    g = jax.nn.silu(_matmul(h, proj["gate_proj"], quant_bits))
    u = _matmul(h, proj["up_proj"], quant_bits)
    return x + _matmul(g * u, proj["down_proj"], quant_bits)


@functools.partial(jax.jit, static_argnames=("dims", "quant_bits"))
def _layer_all(xs, seed_key, dims: Dims, quant_bits):
    """One layer over every sequence (B, S, d), one sequence at a time."""
    w = layer_weights(seed_key, dims)
    return jax.lax.map(lambda x: _layer(x, w, dims, quant_bits), xs)


_global_weights = jax.jit(global_weights, static_argnums=(1,))


@jax.jit
def _embed(tokens, emb):
    return dequant(emb)[tokens]


@functools.partial(jax.jit, static_argnames=("dims", "quant_bits"))
def _gaps(h, tokens, norm, head, dims: Dims, quant_bits):
    """Per position: (reference best - reference logit of ``tokens``) in
    units of the reference logits' standard deviation, and the argmax."""
    logits = _matmul(_rms_norm(h, norm, dims.eps), head, quant_bits)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return (best - got) / jnp.std(logits, -1), jnp.argmax(logits, -1)


def _padded_length(n: int) -> int:
    """Few distinct lengths, so that few reference programs compile:
    powers of two up to 512, then multiples of 512."""
    s = 128
    while s < n:
        s = s * 2 if s < 512 else s + 512
    return s


def hidden_states(root: jax.Array, dims: Dims, seqs: list[np.ndarray],
                  rows: tuple[np.ndarray, np.ndarray],
                  quant_bits: Optional[int] = None) -> jax.Array:
    """Final hidden states at ``rows`` (sequence, position).

    Sequences of one padded length run together, right-padded (causal
    attention keeps the padding out of the real positions), layer by
    layer so that one layer's weights are live at a time."""
    seq_of, pos = np.asarray(rows[0]), np.asarray(rows[1])
    padded = np.asarray([_padded_length(len(t)) for t in seqs])
    emb = _global_weights(root, dims)["embed_tokens"]
    parts, order = [], []
    for s in sorted(set(padded.tolist())):
        members = np.flatnonzero(padded == s)
        toks = np.zeros((len(members), s), np.int32)
        for j, i in enumerate(members):
            toks[j, : len(seqs[i])] = seqs[i]
        with jax.default_matmul_precision("highest"):
            x = _embed(jnp.asarray(toks), emb)
            for i in range(dims.layers):
                x = _layer_all(x, layer_key(root, i), dims=dims,
                               quant_bits=quant_bits)
        pick = np.flatnonzero(padded[seq_of] == s)
        row = np.searchsorted(members, seq_of[pick])
        parts.append(x[jnp.asarray(row), jnp.asarray(pos[pick])])
        order.append(pick)
    back = np.argsort(np.concatenate(order))
    return jnp.concatenate(parts)[jnp.asarray(back)]


def logit_gaps(seed: int, dims: Dims, prompts: list[np.ndarray],
               outputs: list[list[int]], quant_bits: Optional[int] = None
               ) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """Reference gaps of every served token, and of the token the control
    ranks first.

    For each request the reference runs once over its prompt and its
    served tokens; the logits at position ``len(prompt) - 1 + t`` rank
    served token ``t``. Returns ``(gap_served, gap_control)``: the first
    is the reference's gap of each served token; the second, with
    ``quant_bits`` set, is the full-precision reference's gap of the
    token that the lower precision ranks first at each position (None
    otherwise).
    """
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outputs)]
    bi = np.asarray([b for b, o in enumerate(outputs) for _ in o])
    pi = np.asarray([len(prompts[b]) - 1 + t
                     for b, o in enumerate(outputs) for t in range(len(o))])
    served = np.asarray([t for o in outputs for t in o], np.int32)
    root = root_key(seed)
    g = _global_weights(root, dims)
    head = (dequant(g["embed_tokens"]).T if dims.tied
            else dequant(g["lm_head"]))
    chunk = 256
    pad = -len(served) % chunk

    def gaps_of(h, toks, bits):
        h = jnp.pad(h, ((0, pad), (0, 0)))
        toks = np.pad(toks, (0, pad))
        out_gap, out_arg = [], []
        with jax.default_matmul_precision("highest"):
            for c in range(0, len(toks), chunk):
                gp, am = _gaps(h[c:c + chunk], jnp.asarray(toks[c:c + chunk]),
                               g["norm"], head, dims=dims, quant_bits=bits)
                out_gap.append(np.asarray(gp))
                out_arg.append(np.asarray(am))
        n = len(served)
        return np.concatenate(out_gap)[:n], np.concatenate(out_arg)[:n]

    h = hidden_states(root, dims, seqs, (bi, pi))
    gap_served, _ = gaps_of(h, served, None)
    if quant_bits is None:
        return gap_served, None
    hq = hidden_states(root, dims, seqs, (bi, pi), quant_bits)
    _, picked = gaps_of(hq, served, quant_bits)
    gap_control, _ = gaps_of(h, picked.astype(np.int32), None)
    return gap_served, gap_control
