"""Every mix makes the same requests from the same seed, and every seed
the same amount of work."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import traffic
from chipbench.tests.tiny import CHAT, REASON, full_cell

WORKLOADS = [REASON, CHAT]


def _gen(workload, seed, seconds=30.0):
    cell = full_cell(workload)
    s = cell.config["serving"]
    return traffic.generate(cell.mix, cell.load, seed=seed, seconds=seconds,
                            slots=s["slots"], max_len=s["max_len"],
                            vocab=cell.config["config"]["vocab_size"])


def _key(reqs):
    return [(r.uid, r.prompt.tolist(), r.max_new, r.due) for r in reqs]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_same_seed_same_requests(workload):
    assert _key(_gen(workload, 2**33 + 1)) == _key(_gen(workload,
                                                         2**33 + 1))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_seeds_draw_only_the_tokens(workload):
    a, b = _gen(workload, 5), _gen(workload, 6)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    for f in (lambda r: len(r.prompt), lambda r: r.max_new,
              lambda r: r.due):
        assert list(map(f, a)) == list(map(f, b))


def test_arrival_gaps_are_exponential_quantiles_in_a_mixed_order():
    reqs = _gen(CHAT, 5, seconds=40.0)
    g = np.diff([r.due for r in reqs] + [40.0])
    np.testing.assert_allclose(
        sorted(g), traffic.gaps(len(reqs), 40.0, "poisson"), rtol=1e-9)
    lens = [len(r.prompt) for r in reqs]
    assert lens != sorted(lens)


def test_reason_mix_catches_each_slot_at_a_depth_across_its_budget():
    """Each slot holds one request caught at a depth spread evenly over
    its budget: contexts from hundreds of tokens to near max_len, built
    in one prefill cohort, with budgets that fill max_len."""
    reqs = _gen(REASON, 7)
    assert len(reqs) == 32
    depths = sorted(len(r.prompt) for r in reqs)
    assert depths[0] >= 128 and depths[-1] <= 1792
    assert depths == traffic.quantiles(32, full_cell(REASON).mix["context"])
    assert len(set(depths)) == 32
    assert all(len(r.prompt) + r.max_new == 2048 for r in reqs)
    assert min(r.max_new for r in reqs) >= 256
    # one cohort: its bucket is that of the longest context
    assert traffic.prefill_buckets(reqs)[-1] == 2048


def test_chat_mix_arrives_in_the_window_at_the_cell_rate():
    cell = full_cell(CHAT)
    reqs = _gen(CHAT, 9, seconds=40.0)
    assert len(reqs) == round(cell.load["rate_per_s"] * 40.0)
    due = sorted(r.due for r in reqs)
    assert due[0] == 0.0 and due[-1] < 40.0
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 32 and max(lens) <= 1024
    assert set(traffic.prefill_buckets(reqs)) <= {32, 64, 128, 256, 512,
                                                  1024}


def test_quantiles_follow_the_distribution():
    q = traffic.quantiles(1001, {"dist": "lognormal", "median": 512,
                                 "sigma": 0.8, "lo": 32, "hi": 1024})
    assert q[500] == 512 and q == sorted(q)
    u = traffic.quantiles(16, {"dist": "uniform", "lo": 9, "hi": 16})
    assert u == [v for v in range(9, 17) for _ in range(2)]
