"""The reduction from a recorded trace to the per-layer metrics.

The fixture is 70 ms of a traced ``qwen2-1.5b.reason.sorted24`` window on
one TPU v5e, around the boundary between two decode steps: the device's
operations (HLO instructions, the scanned layer's ``%while`` among them),
its program executions and the harness's host spans, with times rebased
to the window's start.
"""

from __future__ import annotations

import json
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import roofline
from chipbench import trace as T
from chipbench.cell import reader

FIXTURE = Path(__file__).parent / "fixtures" / "qwen2-reason-step-boundary.json"
PEAKS = roofline.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def tr():
    return T.Trace.from_json(json.loads(FIXTURE.read_text()))


def _view(tr):
    return types.SimpleNamespace(trace=tr, peaks=PEAKS)


def test_busy_is_the_union_of_operation_intervals(tr):
    # brute force: which of 1 µs slots does some operation cover
    slots = np.zeros(int(tr.window_s() * 1e6) + 1, bool)
    for a, b in zip(tr.ops.start, tr.ops.end):
        slots[int(round(a * 1e6)):int(round(b * 1e6))] = True
    assert T.busy_s(tr) == pytest.approx(slots.sum() * 1e-6, abs=2e-5)


def test_idle_gaps_fill_the_window_with_the_busy_time(tr):
    gaps = T.idle_gaps(tr, n=10_000)
    assert sum(g for _, g in gaps) == pytest.approx(
        tr.window_s() - T.busy_s(tr), abs=1e-9)
    name, longest = gaps[0]
    assert name == "step" and longest == pytest.approx(0.005332293,
                                                       rel=1e-6)


def test_idle_share_reader(tr):
    got = reader("metrics", "device.idle_share").read(_view(tr))
    assert got == pytest.approx(100 * (1 - T.busy_s(tr) / tr.window_s()))
    assert 0 < got < 20


def test_loops_are_not_leaves(tr):
    loops = [n for n in tr.ops.name if n.startswith("%while")]
    assert loops
    leaves = T.leaves(tr.ops)
    assert not any(n.startswith("%while") for n in leaves.name)
    assert len(leaves.name) == len(tr.ops.name) - len(loops)
    assert all("while" not in name for name, _ in T.top_ops(tr))


def test_call_shapes_from_the_hlo_name():
    name = ("%seq_policy_matmul.43 = s32[16,256]{1,0:T(8,128)S(1)} "
            "custom-call(s8[16,1536]{1,0:T(8,128)(4,1)S(1)} %fusion.137, "
            "s8[1536,256]{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_bitcast_fusion"
            ".18), custom_call_target=\"tpu_custom_call\"")
    assert roofline.call_from_hlo(name) == roofline.projection(16, 1536,
                                                               256)
    assert roofline.call_from_hlo("%fusion.1 = f32[8]{0} fusion(f32[8])") \
        is None


def test_kernel_roofline_reader(tr):
    want_best = want_spent = 0.0
    for name, a, b in zip(tr.ops.name, tr.ops.start, tr.ops.end):
        if name.startswith("%seq_policy_matmul"):
            m, n = map(int, name.split("s32[")[1].split("]")[0].split(","))
            k = int(name.split("custom-call(s8[")[1].split("]")[0]
                    .split(",")[1])
            nbytes = m * k + k * n + 4 * m * n
            want_best += max(2 * m * k * n / 393e12, nbytes / 819e9)
            want_spent += b - a
    got = reader("metrics", "pqs_dot_roofline").read(_view(tr))
    assert got == pytest.approx(100 * want_best / want_spent)
    assert 0.1 < got < 1.0  # the VPU sort, not HBM, sets the time


def test_step_readers(tr):
    steps = [b - a for n, a, b in zip(tr.modules.name, tr.modules.start,
                                      tr.modules.end)
             if n.startswith("jit_step(")]
    got = reader("metrics", "decode_step_ms").read(_view(tr))
    assert steps and got == pytest.approx(1e3 * np.mean(steps))
    assert reader("metrics", "prefill_step_ms").read(_view(tr)) is None


def test_fixture_round_trips(tr):
    again = T.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again.ops.name == tr.ops.name
    np.testing.assert_array_equal(again.ops.end, tr.ops.end)
