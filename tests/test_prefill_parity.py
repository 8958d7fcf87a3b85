"""One-shot batched prefill parity with the token-by-token oracle.

The serving engine's admission path consumes a whole cohort of prompts
in ONE jitted batched prefill step (``Model.prefill``). The contract,
per architecture family (attention KV, SSM state, hybrid interleave,
MoE routing, cross-attention): greedy decode after batched prefill
produces exactly the same tokens as after the legacy token-by-token
prefill, under mixed prompt lengths and slot reuse — and it does so in
one device step per admission cohort instead of one per prompt
position.

The family sweep pins f32 compute: the SSD prefill is the chunked dual
form while decode is the stepwise recurrence, so in bf16 their float
reassociation can flip a near-tie argmax on random smoke weights (the
same documented tolerance as the fwd-vs-decode consistency test). The
routing/caching semantics under test are dtype-independent; a bf16
greedy case is kept for the attention-KV family where the paths share
op-for-op numerics.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import Request, ServingEngine

KEY = jax.random.PRNGKey(0)

# one per family: dense GQA KV, mixed local/global window rings, pure
# SSM state, Mamba+attention hybrid with interleaved MoE, top-k-routed
# MoE transformer, encoder-decoder cross-KV
FAMILY_ARCHS = ["qwen2-1.5b", "gemma3-12b", "mamba2-2.7b",
                "jamba-v0.1-52b", "granite-moe-1b-a400m", "whisper-medium"]


def _requests(vocab, lens, max_new=4):
    rng = np.random.default_rng(7)
    return [
        Request(uid=i,
                prompt=rng.integers(1, vocab, size=int(n)).astype(np.int32),
                max_new_tokens=max_new)
        for i, n in enumerate(lens)
    ]


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_batched_prefill_matches_stepwise(arch):
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32",
                              param_dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    # ragged lengths (incl. a single-token prompt) across few slots so
    # admission cohorts mix lengths AND slots get reused mid-stream
    lens = [5, 3, 7, 1, 6]
    a = ServingEngine(model, params, num_slots=2, max_len=32,
                      prefill_mode="steps")
    b = ServingEngine(model, params, num_slots=2, max_len=32,
                      prefill_mode="batched")
    ra, rb = _requests(cfg.vocab_size, lens), _requests(cfg.vocab_size, lens)
    a.drain(ra)
    b.drain(rb)
    for qa, qb in zip(ra, rb):
        assert qa.output == qb.output, (
            f"{arch}: batched prefill diverged from token-by-token"
        )
    # admission latency: one batched step per cohort vs one per position
    assert b.stats["prefill_steps"] <= b.stats["cohorts"]
    assert a.stats["prefill_steps"] > a.stats["cohorts"]
    # identical decode work either way
    assert a.stats["decode_steps"] == b.stats["decode_steps"]


def test_batched_prefill_matches_stepwise_bf16_dense():
    """Attention-KV decode and prefill share op-for-op numerics, so the
    greedy-token contract holds at the production compute dtype too."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    lens = [5, 3, 7, 1, 6]
    a = ServingEngine(model, params, num_slots=2, max_len=32,
                      prefill_mode="steps")
    b = ServingEngine(model, params, num_slots=2, max_len=32,
                      prefill_mode="batched")
    ra, rb = _requests(cfg.vocab_size, lens), _requests(cfg.vocab_size, lens)
    a.drain(ra)
    b.drain(rb)
    assert [r.output for r in ra] == [r.output for r in rb]


def test_prefill_cache_state_matches_stepwise():
    """Beyond greedy tokens: the cache pytrees themselves line up."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    lens = [6, 4]
    a = ServingEngine(model, params, num_slots=2, max_len=16,
                      prefill_mode="steps")
    b = ServingEngine(model, params, num_slots=2, max_len=16,
                      prefill_mode="batched")
    for eng, reqs in ((a, _requests(cfg.vocab_size, lens)),
                      (b, _requests(cfg.vocab_size, lens))):
        for r in reqs:
            eng.submit(r)
        eng._admit()
        eng._maybe_prefill()  # prefill only — no decode yet
    for la, lb in zip(jax.tree_util.tree_leaves(a.caches),
                      jax.tree_util.tree_leaves(b.caches)):
        np.testing.assert_allclose(
            np.asarray(la, np.float32), np.asarray(lb, np.float32),
            rtol=0, atol=1e-4,
        )


def test_batched_prefill_respects_occupied_slots():
    """Admitting into slot 1 while slot 0 is mid-generation must not
    perturb slot 0's cache lanes or its sampled continuation."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    rng = np.random.default_rng(3)
    p0 = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)
    p1 = rng.integers(1, cfg.vocab_size, size=6).astype(np.int32)

    solo = ServingEngine(model, params, num_slots=2, max_len=32)
    r_solo = Request(uid=0, prompt=p0.copy(), max_new_tokens=6)
    solo.drain([r_solo])

    eng = ServingEngine(model, params, num_slots=2, max_len=32)
    r0 = Request(uid=0, prompt=p0.copy(), max_new_tokens=6)
    eng.submit(r0)
    eng.step()
    eng.step()  # slot 0 is two tokens into generation
    r1 = Request(uid=1, prompt=p1.copy(), max_new_tokens=3)
    eng.submit(r1)
    eng.drain([])
    assert r0.output == r_solo.output


def test_single_token_prompts_skip_prefill():
    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    eng = ServingEngine(model, params, num_slots=2, max_len=16)
    reqs = _requests(cfg.vocab_size, [1, 1])
    eng.drain(reqs)
    assert eng.stats["prefill_steps"] == 0
    assert all(len(r.output) == 4 for r in reqs)
