"""The qwen3 path of the benchmark's ``qwen3-32b-s16`` configuration, on the
CPU: per-head q/k RMSNorm, no q/k/v bias, an untied head, every projection
an integer ``pqs_dot`` under the cells' accumulation settings.

- Prefill and then decode through the int8 paged cache, served by
  ``ServingEngine``, agree with the plain float32 reference's full forward
  pass (``chipbench/reference.py``) at test widths, logit for logit.
- The head is an integer ``pqs_dot`` of the cell's policy, not a float
  matmul.
- One ``sorted_tiled_seq`` dot at the configuration's longest K, 25600
  (the down projection), equals the overflow library's oracle bit for bit
  with the 24-bit register saturating.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program, reference, weights
from chipbench.cell import HERE, _json
from chipbench.tests.tiny import SIZES as TEST_SIZES
from repro.core import dispatch, overflow

CONFIG = "qwen3-32b-s16"
# the configuration's layer equations at test widths (K of 64 and 160)
SIZES = TEST_SIZES[CONFIG]
# (prompt length, tokens decoded): one prompt shorter than a page, one
# over several pages, one of a single page
REQUESTS = ((37, 12), (5, 20), (90, 8))
SEEDS = (1, 2**33 + 7)
# Tolerances, in standard deviations of the reference's logits at each
# position. The served path quantizes every activation to int8 per tensor
# and keeps K and V as int8 pages; the reference keeps both in float32.
# Measured at these widths (seeds 1, 2, 2**33 + 7, both settings): the
# widest error 0.24-0.35, the mean 0.029-0.033. The reference computed at
# int4 (the precision step below the configuration's int8) reads 3.9-4.4
# and 0.53-0.57 against the float32 one, so each limit lies about three
# times above the served path and four to five times below the control.
MAX_ERR = 1.0
MEAN_ERR = 0.1


def _config() -> dict:
    cfg = copy.deepcopy(_json(HERE / "configs" / f"{CONFIG}.json"))
    cfg["config"].update(SIZES)
    cfg["reduced"] = sorted(set(cfg["reduced"]) | set(SIZES))
    return cfg


def _serve(accum: str, seed: int):
    """Serve REQUESTS to their budgets; return the prompts, the served
    tokens and the logits each token was sampled from, in request order."""
    cfg = _config()
    dims = weights.Dims.from_config(cfg["config"])
    model, params = program.build(cfg, seed)
    serving = dict(cfg["serving"], slots=4, max_len=128)
    engine = program.make_engine(model, params, serving,
                                 _json(HERE / "accum" / f"{accum}.json"))
    seen: dict[int, list] = {}
    sample = engine._sample

    def keep(logits, slot):
        seen.setdefault(engine.slots[slot].uid, []).append(
            np.array(logits[slot, -1]))
        return sample(logits, slot)
    engine._sample = keep
    rng = np.random.default_rng(seed % 2**32)
    reqs = [program.Request(uid=i, max_new_tokens=m,
                            prompt=rng.integers(0, dims.vocab, n,
                                                dtype=np.int32))
            for i, (n, m) in enumerate(REQUESTS)]
    engine.drain(reqs)
    assert all(r.done and len(r.output) == m
               for r, (_, m) in zip(reqs, REQUESTS))
    logits = np.stack([row for r in reqs for row in seen[r.uid]])
    return dims, [r.prompt for r in reqs], [list(r.output) for r in reqs], \
        logits


def _reference_logits(dims, seed, prompts, outputs, quant_bits=None):
    """The reference's logits at every position that ranked a served
    token: one full forward pass over each prompt and its served tokens."""
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outputs)]
    rows = (np.asarray([b for b, o in enumerate(outputs) for _ in o]),
            np.asarray([len(prompts[b]) - 1 + t
                        for b, o in enumerate(outputs)
                        for t in range(len(o))]))
    root = weights.root_key(seed)
    g = weights.global_weights(root, dims)
    h = reference.hidden_states(root, dims, seqs, rows, quant_bits)
    with jax.default_matmul_precision("highest"):
        h = reference._rms_norm(h, g["norm"], dims.eps)
        return np.asarray(reference._matmul(
            h, weights.dequant(g["lm_head"]), quant_bits))


def _errors(got, ref):
    e = np.abs(got - ref) / ref.std(-1, keepdims=True)
    return float(e.max()), float(e.mean())


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("accum", ["sorted24", "int32"])
def test_prefill_then_decode_matches_reference_logits(accum, seed):
    dims, prompts, outputs, got = _serve(accum, seed)
    assert not dims.tied and dims.qk_norm and not dims.qkv_bias
    ref = _reference_logits(dims, seed, prompts, outputs)
    widest, mean = _errors(got, ref)
    assert widest <= MAX_ERR and mean <= MEAN_ERR, (widest, mean)


def test_tolerances_refuse_the_precision_below_int8():
    """The reference at int4 against the float32 one fails both limits:
    the comparison is tight enough to see a lower precision."""
    seed = SEEDS[0]
    dims, prompts, outputs, _ = _serve("int32", seed)
    ref = _reference_logits(dims, seed, prompts, outputs)
    ctl = _reference_logits(dims, seed, prompts, outputs, quant_bits=4)
    widest, mean = _errors(ctl, ref)
    assert widest > MAX_ERR and mean > MEAN_ERR, (widest, mean)


def test_untied_head_is_an_integer_pqs_dot_of_the_cell_policy(monkeypatch):
    calls = []
    pqs_dot = dispatch.pqs_dot

    def spy(x, w, **kw):
        calls.append((w.shape, kw.get("policy"), kw.get("acc_bits")))
        return pqs_dot(x, w, **kw)
    monkeypatch.setattr(dispatch, "pqs_dot", spy)
    dims, *_ = _serve("sorted24", SEEDS[0])
    head = [c for c in calls if c[0] == (dims.vocab, dims.d)]
    assert head and all(c[1:] == ("sorted_tiled_seq", 24) for c in head)
    # and every projection of a layer: q, k, v, o, gate, up, down
    shapes = {c[0] for c in calls}
    hq, hkv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    assert {(hq, dims.d), (hkv, dims.d), (dims.d, hq), (dims.ff, dims.d),
            (dims.d, dims.ff)} <= shapes


def test_sorted_seq_dot_at_the_down_projection_k_matches_the_oracle():
    """K = 25600 (qwen3-32b's intermediate size), 24-bit register, 64-wide
    sort tiles, as ``sorted24``. Rows of x against rows of w:

    - random values: the running sum stays far inside 24 bits, so the
      register returns the exact sum;
    - all +127 against all +127: 16129 a product, 4.1e8 in all, so the
      register saturates at its top;
    - +127 for the first half of K, then -127, against all +127: the exact
      sum is 0, but every sort tile is of one sign, so the sum climbs past
      2**23 before it falls, and saturation leaves it at the bottom.
    """
    k, acc = 25600, _json(HERE / "accum" / "sorted24.json")
    rng = np.random.default_rng(25600)
    half = np.concatenate([np.full(k // 2, 127), np.full(k // 2, -127)])
    x = np.stack([rng.integers(-127, 128, k), np.full(k, 127), half])
    w = np.stack([rng.integers(-127, 128, k), np.full(k, 127),
                  rng.integers(-127, 128, k)])
    x, w = jnp.asarray(x, jnp.int8), jnp.asarray(w, jnp.int8)
    got = np.asarray(dispatch.pqs_dot(
        x, w, acc_bits=acc["acc_bits"], policy=acc["policy"],
        k_tile=acc["k_tile"], backend="jnp"))
    want = np.asarray(overflow.accumulate(
        overflow.partial_products(w, x), acc["acc_bits"], acc["policy"],
        acc["k_tile"], 1))
    np.testing.assert_array_equal(got, want)
    exact = np.asarray(x, np.int64) @ np.asarray(w, np.int64).T
    top, bottom = 2**23 - 1, -2**23
    assert got[0, 0] == exact[0, 0]
    assert got[1, 1] == top and exact[1, 1] > top
    assert got[2, 1] == bottom and exact[2, 1] == 0
