"""The serving engine's spans on the profiler's clock.

``ServingEngine.step`` opens one ``engine.step`` span per step and, inside
it, one span per phase in the order the phases run (``engine.admit``,
``engine.prefill``, ``engine.pages``, ``engine.dispatch``,
``engine.fetch``, ``engine.sample``), each carrying the step index. A
tiny paged engine runs here on the CPU under ``jax.profiler``; the trace
is read back with the benchmark's loaders, as a traced chip run would be.
"""

from __future__ import annotations

import dataclasses
import gc

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from chipbench import spans as spans_lib
from chipbench import trace as trace_lib
from repro.configs import get_config
from repro.core.dispatch import IntegerLinConfig
from repro.core.qtensor import quantize_tree
from repro.models.model import build_model
from repro.serving import Request, ServingEngine

PHASES = ("engine.admit", "engine.prefill", "engine.pages",
          "engine.dispatch", "engine.fetch", "engine.sample")
WINDOW_STEPS = 4


def _requests(vocab: int, uids, lens, max_new: int) -> list[Request]:
    rng = np.random.default_rng(3)
    return [Request(uid=u, prompt=rng.integers(1, vocab, size=n).astype(
        np.int32), max_new_tokens=max_new) for u, n in zip(uids, lens)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two requests decode; two more arrive inside the traced window and
    are admitted at its first step."""
    # the profiler writes every executable the process still holds into
    # the trace: drop those of earlier tests in this worker (other
    # engines' ``jit_step`` among them) so that the trace's one
    # ``jit_step`` is this engine's
    jax.clear_caches()
    gc.collect()
    cfg = dataclasses.replace(get_config("qwen2-1.5b", smoke=True),
                              num_layers=1)
    model = build_model(cfg)
    # smoke widths: quantize every projection, so each runs through pqs_dot
    params = quantize_tree(model.init(jax.random.PRNGKey(0)), bits=8,
                           min_size=1 << 10, min_dim=16)
    engine = ServingEngine(
        model, params, num_slots=4, max_len=64, page_size=8,
        cache_dtype="int8",
        int_lin=IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24,
                                 k_tile=64))
    vocab = cfg.vocab_size
    for r in _requests(vocab, (0, 1), (5, 9), 24):
        engine.submit(r)
    engine.step()  # compiles the prefill and the decode step
    late = _requests(vocab, (7, 8), (5, 9), 24)
    engine.step()
    logdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with TraceAnnotation("window"):
        for r in late:
            engine.submit(r)
        for _ in range(WINDOW_STEPS):
            with TraceAnnotation("step"):
                engine.step()
    jax.profiler.stop_trace()
    path = trace_lib.find_xplane(logdir)
    with open(path, "rb") as f:
        raw = f.read()
    return spans_lib.load(path), trace_lib.load(path), raw


def _host(sp, name):
    h = sp.trace.host
    return [(a, b, st) for n, a, b, st in
            zip(h.name, h.start, h.end, sp.host_stats) if n == name]


def test_each_decoding_step_holds_its_phases_in_order(traced):
    sp = traced[0]
    steps = _host(sp, "engine.step")
    assert len(steps) == WINDOW_STEPS
    for i, (a, b, st) in enumerate(steps):
        assert st["rows"] == 4  # two decoding, two admitted at the first
        kids = sorted((x, y, n) for n in PHASES for x, y, s in _host(sp, n)
                      if s["step"] == st["step"])
        # only the first step has a cohort to prefill
        want = [n for n in PHASES if i == 0 or n != "engine.prefill"]
        assert [n for _, _, n in kids] == want
        for x, y, _ in kids:
            assert a <= x <= y <= b


def test_admit_carries_the_admitted_uids(traced):
    sp = traced[0]
    admits = [st for _, _, st in _host(sp, "engine.admit")]
    assert len(admits) == WINDOW_STEPS  # one per step, admitting or not
    assert admits[0]["uids"] == "7 8"
    assert all("uids" not in st for st in admits[1:])


def test_host_readers_read_the_engine_spans(traced):
    sp = traced[0]
    steps = spans_lib.decoding_steps(sp)
    assert len(steps) == WINDOW_STEPS
    host, sample = spans_lib.host_ms(sp), spans_lib.sample_ms(sp)
    assert host > 0 and sample > 0
    fetch = np.mean([s["engine.fetch"][1] - s["engine.fetch"][0]
                     for s in steps]) * 1e3
    step = np.mean([s["end"] - s["start"] for s in steps]) * 1e3
    assert host + fetch == pytest.approx(step)
    assert sample < host


def test_device_readers_need_a_tpu_plane(traced):
    sp = traced[0]
    assert sp.trace.chips == 1 and not len(sp.trace.ops.name)
    assert spans_lib.scope_ms(sp, "attn") is None
    assert spans_lib.scope_ms(sp, "head") is None


def test_the_harness_view_is_unchanged(traced):
    """The benchmark's own loader still sees only its spans; the spans
    loader sees those too, with the engine's beside them."""
    sp, t, _ = traced
    assert set(t.host.name) == {"window", "step"}
    harness = [i for i, n in enumerate(sp.trace.host.name)
               if not n.startswith("engine.")]
    assert [sp.trace.host.name[i] for i in harness] == t.host.name
    np.testing.assert_array_equal(sp.trace.host.start[harness], t.host.start)
    assert sp.trace.window == t.window


def test_the_trace_holds_the_step_scopes(traced):
    """The compiled step the profiler writes into the trace names its
    work by the model's scopes; a TPU's operations are read through it."""
    names = spans_lib.module_op_names(traced[2])
    (step,) = [v for k, v in names.items() if k.startswith("jit_step(")]
    scopes = {spans_lib.innermost_scope(on) for on in step.values()}
    assert {"embed", "attn", "mlp", "head", "merge", "layers",
            "pqs_dot.sorted_tiled_seq"} <= scopes
