"""Paged / int8-quantized cache: parity, lifecycle, isolation, admission.

The serving contract under paging: moving KV/SSM state from monolithic
per-slot lanes into a page pool with per-slot page tables must be
invisible to decode semantics — greedy f32 tokens bit-identical to the
dense engine for every architecture family — while the allocator obeys
a strict lifecycle (reserve at admission, draw lazily, free on
completion, never run dry mid-decode). int8 KV pages trade a bounded
logits perturbation for a ~4x pool-footprint cut; SSM/conv state stays
float. Admission grows backpressure (queue until pages exist), bounded
head-of-line skip, and interleaved prefill — none of which may change
what tokens any single request produces.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import PageAllocator, Request, ServingEngine

KEY = jax.random.PRNGKey(0)

FAMILY_ARCHS = ["qwen2-1.5b", "gemma3-12b", "mamba2-2.7b",
                "jamba-v0.1-52b", "granite-moe-1b-a400m", "whisper-medium"]

_MODELS: dict = {}


def _family(arch):
    """Build-once cache: f32-pinned smoke model + params per family."""
    if arch not in _MODELS:
        cfg = get_config(arch, smoke=True)
        cfg = dataclasses.replace(cfg, compute_dtype="float32",
                                  param_dtype="float32")
        model = build_model(cfg)
        _MODELS[arch] = (cfg, model, model.init(KEY))
    return _MODELS[arch]


def _requests(vocab, lens, max_new=4, temperature=0.0, uid0=0):
    rng = np.random.default_rng(7)
    return [
        Request(uid=uid0 + i,
                prompt=rng.integers(1, vocab, size=int(n)).astype(np.int32),
                max_new_tokens=max_new, temperature=temperature)
        for i, n in enumerate(lens)
    ]


def _assert_no_leaks(eng):
    assert eng._alloc.in_use == 0, "pages leaked after drain"
    assert eng._alloc.pending_reserved == 0, "reservations leaked"
    assert sorted(eng._alloc._free) == list(range(eng.paging.num_pages))
    assert (eng._table == -1).all(), "host page table leaked entries"
    assert sorted(eng._free_sidx) == list(range(eng.num_slots))


# ---------------------------------------------------------------- allocator

def test_allocator_lifecycle():
    a = PageAllocator(4)
    assert a.free_pages == 4 and a.can_reserve(4) and not a.can_reserve(5)
    a.reserve(0, 2)
    # reserved-not-drawn pages are already committed
    assert a.free_pages == 4 and not a.can_reserve(3)
    p0, p1 = a.alloc(0), a.alloc(0)
    assert p0 != p1 and a.in_use == 2 and a.peak_in_use == 2
    with pytest.raises(RuntimeError):
        a.alloc(0)  # past the reservation
    a.reserve(1, 2)
    with pytest.raises(RuntimeError):
        a.reserve(2, 1)  # pool fully committed
    a.free_slot(0)
    assert a.in_use == 0 and a.can_reserve(2)
    a.free_slot(1)  # drops the undrawn reservation too
    assert a.can_reserve(4) and a.peak_in_use == 2


# ------------------------------------------------------------------- parity

@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_matches_dense_greedy(arch):
    """f32 paged greedy decode is bit-identical to the dense engine,
    under ragged lengths, slot reuse, and page recycling."""
    cfg, model, params = _family(arch)
    lens = [5, 3, 7, 1, 6]
    dense = ServingEngine(model, params, num_slots=2, max_len=32)
    paged = ServingEngine(model, params, num_slots=2, max_len=32,
                          page_size=8)
    rd = _requests(cfg.vocab_size, lens)
    rp = _requests(cfg.vocab_size, lens)
    dense.drain(rd)
    paged.drain(rp)
    for qd, qp in zip(rd, rp):
        assert qd.output == qp.output, (
            f"{arch}: paged cache diverged from dense lanes"
        )
    assert dense.stats["decode_steps"] == paged.stats["decode_steps"]
    _assert_no_leaks(paged)


def test_paged_matches_dense_stepwise_prefill():
    """The legacy token-by-token prefill oracle also holds on pages."""
    cfg, model, params = _family("qwen2-1.5b")
    lens = [6, 4, 3]
    a = ServingEngine(model, params, num_slots=2, max_len=32)
    b = ServingEngine(model, params, num_slots=2, max_len=32,
                      page_size=8, prefill_mode="steps")
    ra = _requests(cfg.vocab_size, lens)
    rb = _requests(cfg.vocab_size, lens)
    a.drain(ra)
    b.drain(rb)
    for qa, qb in zip(ra, rb):
        assert qa.output == qb.output
    _assert_no_leaks(b)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma3-12b", "mamba2-2.7b"])
def test_int8_pages_close_and_smaller(arch):
    """int8 KV pages: first-decode logits within tolerance of f32 pages
    (bit-exact for pure-SSM state, which is never quantized), and the
    cache footprint strictly shrinks where KV pools exist."""
    cfg, model, params = _family(arch)
    lens = [5, 3]
    engs, logits = [], []
    for dtype in ("float32", "int8"):
        eng = ServingEngine(model, params, num_slots=2, max_len=32,
                            page_size=8, cache_dtype=dtype)
        for r in _requests(cfg.vocab_size, lens):
            eng.submit(r)
        eng._admit()
        eng._maybe_prefill()  # prefill into the pools, no decode yet
        mask = np.array([True, True])
        lg, eng.caches = eng._step(
            eng.params, jnp.asarray(eng._next_token), eng.caches,
            jnp.asarray(mask),
        )
        engs.append(eng)
        logits.append(np.asarray(lg, np.float32))
    rel = np.linalg.norm(logits[1] - logits[0]) / max(
        np.linalg.norm(logits[0]), 1e-9)
    assert rel < 0.06, f"{arch}: int8 page dequant drifted {rel:.3f}"
    if arch == "mamba2-2.7b":
        assert rel == 0.0  # no KV pool to quantize
        assert engs[1].cache_nbytes() == engs[0].cache_nbytes()
    elif arch == "gemma3-12b":
        # mixed family: sliding-window layers keep dense f32 rings, so
        # only the global-attention pools shrink
        assert engs[1].cache_nbytes() < engs[0].cache_nbytes()
    else:
        assert engs[1].cache_nbytes() < 0.55 * engs[0].cache_nbytes(), (
            "int8 pages did not shrink the cache"
        )


def test_int8_requires_paging():
    _, model, params = _family("qwen2-1.5b")
    with pytest.raises(ValueError, match="page"):
        ServingEngine(model, params, num_slots=2, max_len=32,
                      cache_dtype="int8")


# ------------------------------------------------- lifecycle / backpressure

def test_page_exhaustion_backpressures():
    """A pool far smaller than slots x worst-case must still drain every
    request — admission simply waits for pages, it never crashes."""
    cfg, model, params = _family("qwen2-1.5b")
    eng = ServingEngine(model, params, num_slots=4, max_len=16,
                        page_size=8, num_pages=2)
    reqs = _requests(cfg.vocab_size, [4, 5, 3, 6, 4])
    eng.drain(reqs)
    assert all(len(r.output) == 4 for r in reqs)
    assert eng.stats["queue_wait_steps"] > 0, (
        "undersized pool produced no queueing — backpressure untested"
    )
    assert eng.stats["pages_peak"] <= 2
    _assert_no_leaks(eng)


def test_submit_rejects_impossible_request():
    cfg, model, params = _family("qwen2-1.5b")
    eng = ServingEngine(model, params, num_slots=2, max_len=32,
                        page_size=8, num_pages=2)
    req = _requests(cfg.vocab_size, [20], max_new=8)[0]  # needs 4 pages
    with pytest.raises(ValueError, match="pages"):
        eng.submit(req)


def test_hol_blocked_head_is_skipped():
    """A head-of-queue request waiting on pages must not starve a small
    request behind it (bounded skip-scan)."""
    cfg, model, params = _family("qwen2-1.5b")
    eng = ServingEngine(model, params, num_slots=2, max_len=32,
                        page_size=8, num_pages=5)
    big0 = _requests(cfg.vocab_size, [20], max_new=8, uid0=0)[0]  # 4 pages
    big1 = _requests(cfg.vocab_size, [20], max_new=8, uid0=1)[0]  # 4 pages
    small = _requests(cfg.vocab_size, [2], max_new=4, uid0=2)[0]  # 1 page
    eng.submit(big0)
    eng.step()  # big0 admitted: 4 of 5 pages committed
    eng.submit(big1)
    eng.submit(small)
    eng.step()  # big1 blocked (needs 4 > 1 free); small admits past it
    assert eng.stats["hol_skips"] >= 1
    assert any(r is small for r in eng.slots), (
        "small request should have been admitted past the blocked head"
    )
    eng.drain([])
    assert all(len(r.output) == r.max_new_tokens
               for r in (big0, big1, small))
    _assert_no_leaks(eng)


# ---------------------------------------------------------------- isolation

def test_paged_admission_respects_occupied_slots():
    """Admitting into slot 1 (prefill scatter + page claims) while slot 0
    is mid-generation must not perturb slot 0's pages or tokens."""
    cfg, model, params = _family("qwen2-1.5b")
    rng = np.random.default_rng(3)
    p0 = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)
    p1 = rng.integers(1, cfg.vocab_size, size=6).astype(np.int32)

    solo = ServingEngine(model, params, num_slots=2, max_len=32,
                         page_size=8)
    r_solo = Request(uid=0, prompt=p0.copy(), max_new_tokens=6)
    solo.drain([r_solo])

    eng = ServingEngine(model, params, num_slots=2, max_len=32, page_size=8)
    r0 = Request(uid=0, prompt=p0.copy(), max_new_tokens=6)
    eng.submit(r0)
    eng.step()
    eng.step()  # slot 0 is two tokens into generation
    r1 = Request(uid=1, prompt=p1.copy(), max_new_tokens=3)
    eng.submit(r1)
    eng.drain([])
    assert r0.output == r_solo.output
    _assert_no_leaks(eng)


def test_sampling_reproducible_under_batch_composition():
    """Sampled (temperature>0) output of a request depends only on
    (engine seed, request uid) — not on what else shares the batch."""
    cfg, model, params = _family("qwen2-1.5b")
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)

    def gen(extra_lens):
        eng = ServingEngine(model, params, num_slots=4, max_len=32,
                            page_size=8)
        tgt = Request(uid=42, prompt=prompt.copy(), max_new_tokens=6,
                      temperature=1.0)
        others = _requests(cfg.vocab_size, extra_lens, max_new=6,
                           temperature=0.7, uid0=100)
        eng.drain([tgt] + others)
        return tgt.output

    solo = gen([])
    crowded = gen([4, 6, 3])
    permuted = gen([6, 3])
    assert solo == crowded == permuted, (
        "sampling stream leaked across batch compositions"
    )


def test_interleaved_prefill_matches_immediate():
    """prefill_decode_ratio > 0 changes *when* prefills run, never what
    any request generates."""
    cfg, model, params = _family("qwen2-1.5b")
    lens = [5, 3, 7, 1, 6, 4]
    a = ServingEngine(model, params, num_slots=2, max_len=32, page_size=8)
    b = ServingEngine(model, params, num_slots=2, max_len=32, page_size=8,
                      prefill_decode_ratio=2)
    ra = _requests(cfg.vocab_size, lens, max_new=6)
    rb = _requests(cfg.vocab_size, lens, max_new=6)
    a.drain(ra)
    b.drain(rb)
    for qa, qb in zip(ra, rb):
        assert qa.output == qb.output
    _assert_no_leaks(b)


def test_single_token_prompts_paged():
    cfg, model, params = _family("qwen2-1.5b")
    eng = ServingEngine(model, params, num_slots=2, max_len=16, page_size=8)
    reqs = _requests(cfg.vocab_size, [1, 1])
    eng.drain(reqs)
    assert eng.stats["prefill_steps"] == 0
    assert all(len(r.output) == 4 for r in reqs)
    _assert_no_leaks(eng)


# ----------------------------------------------------------------- sharding

def test_cache_shardings_paged_serve_mode():
    """Pool leaves shard their page axis over "data" in serve mode;
    tables/indices/positions replicate everywhere."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import cache_shardings

    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    shapes = {
        "kp": jax.ShapeDtypeStruct((4, 32, 16, 2, 64), jnp.int8),
        "ks": jax.ShapeDtypeStruct((4, 32, 16, 2), jnp.float32),
        "table": jax.ShapeDtypeStruct((8, 4), jnp.int32),
        "pos": jax.ShapeDtypeStruct((8,), jnp.int32),
        "ssdp": jax.ShapeDtypeStruct((32, 32, 64, 16), jnp.float32),
        "convp": jax.ShapeDtypeStruct((32, 3, 128), jnp.float32),
        "sidx": jax.ShapeDtypeStruct((8,), jnp.int32),
    }
    s = cache_shardings(mesh, shapes, serve_mode=True)
    assert s["kp"].spec == P(None, "data", None, None, "model")
    assert s["ks"].spec == P(None, "data", None, None)
    assert s["ssdp"].spec == P("data", "model", None, None)
    assert s["convp"].spec == P("data", None, "model")
    for name in ("table", "pos", "sidx"):
        assert s[name].spec == P()
    # default (dry-run) mode keeps pools replicated over data
    d = cache_shardings(mesh, shapes)
    assert d["kp"].spec == P(None, None, None, None, "model")
    assert d["ssdp"].spec == P(None, "model", None, None)
