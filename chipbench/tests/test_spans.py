"""The program's spans and scopes, read beside the harness's own view.

The fixture is one decode step (950 ms) of a traced
``qwen2-1.5b.reason.sorted24`` window on one TPU v5e, read with
``spans.load`` from a run that wrote the compiled modules into its trace
(``ProfileOptions.enable_hlo_proto``): the device's operations with the
innermost scope of each, its program executions, the harness's and the
engine's host spans with their stats. Times are rebased to the step's
start, and every operation's name but the policy kernels' is cut to what
``trace.top_ops`` keeps of it.
"""

from __future__ import annotations

import json
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import roofline
from chipbench import spans as S
from chipbench import trace as T
from chipbench.cell import reader

FIXTURE = Path(__file__).parent / "fixtures" / "qwen2-reason-spans.json"
PEAKS = roofline.peaks("TPU v5 lite")
READERS = ("decode_step_ms", "pqs_dot_roofline", "device.idle_share")


@pytest.fixture(scope="module")
def sp():
    return S.Spans.from_json(json.loads(FIXTURE.read_text()))


def _harness_view(sp) -> T.Trace:
    """The same trace as ``trace.load`` keeps it: the harness's spans."""
    t = sp.trace
    host = t.host.named(lambda n: n in T.HOST_SPANS)
    return T.Trace(t.window, t.chips, t.ops, t.modules, host)


def test_existing_readers_read_the_same(sp):
    """The engine's spans change no reading of the harness's view but the
    names of the idle gaps."""
    t, h = sp.trace, _harness_view(sp)
    assert set(h.host.name) == {"window", "step"}
    for name in READERS:
        mod = reader("metrics", name)
        got = mod.read(types.SimpleNamespace(trace=t, peaks=PEAKS))
        assert got == mod.read(types.SimpleNamespace(trace=h, peaks=PEAKS))
        assert got is not None
    assert T.top_ops(t) == T.top_ops(h)
    gaps, old = T.idle_gaps(t), T.idle_gaps(h)
    assert [g for _, g in gaps] == [g for _, g in old]
    assert not any(n.startswith(S.ENGINE) for n, _ in old)
    assert all(n.startswith(S.ENGINE) for n, _ in gaps)


def test_one_decoding_step_and_its_phases(sp):
    (step,) = S.decoding_steps(sp)
    assert step["step"] == 7
    stats = dict(zip(sp.trace.host.name, sp.host_stats))
    assert stats["engine.step"] == {"step": 7, "rows": 32}
    phases = ["engine.admit", "engine.pages", "engine.dispatch",
              "engine.fetch", "engine.sample"]
    starts = [step[p][0] for p in phases]
    assert starts == sorted(starts)
    assert step["start"] <= starts[0] and step["engine.sample"][1] <= \
        step["end"]


def test_host_readers(sp):
    (step,) = S.decoding_steps(sp)
    fetch = step["engine.fetch"][1] - step["engine.fetch"][0]
    host = S.host_ms(sp)
    assert host == pytest.approx(1e3 * (step["end"] - step["start"] - fetch))
    assert S.sample_ms(sp) == pytest.approx(
        1e3 * (step["engine.sample"][1] - step["engine.sample"][0]))
    # the step waits on the device: nearly all of it is the fetch
    assert 0 < S.sample_ms(sp) < host < 0.02 * 1e3 * fetch


def test_scopes_cover_the_decode_step(sp):
    """Per execution of ``jit_step``, the scopes' leaf time adds up to the
    module's time, bar the gaps between operations inside it."""
    per = {s: S.scope_ms(sp, s) for s in S.SCOPES + (
        "pqs_dot.sorted_tiled_seq", "")}
    step_ms = reader("metrics", "decode_step_ms").read(
        types.SimpleNamespace(trace=sp.trace))
    assert sum(per.values()) == pytest.approx(step_ms, rel=1e-3)
    assert per["pqs_dot.sorted_tiled_seq"] > 0.9 * step_ms
    named = sum(v for s, v in per.items() if s not in ("layers", "cast", ""))
    assert named > 0.98 * step_ms
    assert per[""] < 1e-3 * step_ms
    for s in ("embed", "attn", "mlp", "head", "merge", "layers"):
        assert per[s] > 0, s


def test_scope_arithmetic_on_a_hand_made_trace():
    """Two executions of ``jit_step``: a loop that holds operations is no
    leaf; an operation under both ``attn`` and ``pqs_dot.*`` is kernel
    time; operations outside the module do not count."""
    ops = [  # (name, start, end, scope)
        ("%while.1 = ()", 0.5, 9.0, "layers"),
        ("%fusion.1 = s8[4]", 1.0, 2.0, "attn"),
        ("%seq_policy_matmul.2 = s32[4]", 2.0, 5.0,
         "pqs_dot.sorted_tiled_seq"),
        ("%fusion.3 = bf16[4]", 6.0, 7.0, "head"),
        ("%convert.4 = f32[4]", 11.0, 12.0, "attn"),
        ("%fusion.1 = s8[4]", 21.0, 23.0, "attn"),
    ]
    mods = [("jit_step(1)", 0.0, 10.0), ("jit_convert_element_type(2)",
                                        10.5, 12.5),
            ("jit_step(1)", 20.0, 30.0)]

    def ev(rows):
        return T.Events([r[0] for r in rows],
                        np.asarray([r[1] for r in rows]),
                        np.asarray([r[2] for r in rows]),
                        np.zeros(len(rows), int))
    host = ev([("window", 0.0, 30.0)])
    sp = S.Spans(T.Trace((0.0, 30.0), 1, ev(ops), ev(mods), host), [{}],
                 [o[3] for o in ops])
    assert S.scope_ms(sp, "attn") == pytest.approx(1e3 * (1.0 + 2.0) / 2)
    assert S.scope_ms(sp, "pqs_dot.sorted_tiled_seq") == pytest.approx(
        1e3 * 3.0 / 2)
    assert S.scope_ms(sp, "head") == pytest.approx(1e3 * 1.0 / 2)
    assert S.scope_ms(sp, "layers") == 0.0
    assert S.scope_ms(sp, "attn", module="jit_convert_element_type") == \
        pytest.approx(1e3 * 1.0)
    assert list(S.module_of(sp.trace)) == [0, 0, 0, 0, 1, 2]
    unscoped = S.Spans(sp.trace, sp.host_stats, [""] * len(ops))
    assert S.scope_ms(unscoped, "attn") is None  # a trace without modules


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/layers/while/body/closed_call/attn/pqs_dot.sorted_tiled_seq"
     "/jit(seq_policy_matmul)/pallas_call", "pqs_dot.sorted_tiled_seq"),
    ("jit(step)/layers/while/body/closed_call/attn/gather", "attn"),
    ("jit(step)/layers/while/body/squeeze", "layers"),
    ("jit(step)/merge/select_n", "merge"),
    ("jit(step)/cast/convert_element_type", "cast"),
    ("jit(step)/reduce_sum", ""),
    ("", ""),
])
def test_innermost_scope(op_name, scope):
    assert S.innermost_scope(op_name) == scope


def test_fixture_round_trips(sp):
    again = S.Spans.from_json(json.loads(json.dumps(sp.to_json())))
    assert again.op_scope == sp.op_scope
    assert again.host_stats == sp.host_stats
    np.testing.assert_array_equal(again.trace.ops.end, sp.trace.ops.end)
