"""A run's correctness check, driven end to end on the CPU at test size.

A sound run passes; the control (the reference at int4, the precision
step below the configurations' int8) reads past the cell's limit; and
each fault a serving cell can have, planted under the timed path, makes
``correct`` come out false.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program, run
from chipbench.tests.tiny import CHAT, REASON, tiny_cell

CELLS = [REASON, CHAT]
SEED = 2**33 + 17


def _run(workload, **kw):
    return run.run_cell(tiny_cell(workload), SEED, 1.5, False,
                        on_chip=False, t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_control_is_not(workload):
    out = _run(workload, control_bits=4)
    assert out["correct"], out["checks"]
    assert not out["control_correct"], out["control_checks"]


def _stale_state(engine):
    """The decode step returns its caches unchanged."""
    step = engine._step

    def stale(params, tok, caches, active):
        logits, _ = step(params, tok, caches, active)
        return logits, caches
    engine._step = stale


def _half_batch(engine):
    """Prefill leaves every other slot of the cohort out."""
    prefill = engine._prefill_step

    def half(params, toks, caches, lengths, active):
        return prefill(params, toks, caches, lengths.at[1::2].set(0),
                       active)
    engine._prefill_step = half


def _token_altered(engine):
    """Every fifth token the engine samples is replaced by its neighbour."""
    sample = engine._sample
    calls = [0]

    def altered(logits, slot):
        calls[0] += 1
        tok = sample(logits, slot)
        return (tok + 1) % logits.shape[-1] if calls[0] % 5 == 0 else tok
    engine._sample = altered


@pytest.mark.parametrize("fault", [_stale_state, _half_batch,
                                   _token_altered])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_under_the_timed_path_is_caught(workload, fault, monkeypatch):
    make = program.make_engine

    def broken(*a, **kw):
        engine = make(*a, **kw)
        fault(engine)
        if fault is not _token_altered:
            # the steps are jitted again when set-up switches the
            # accumulation setting: the fault goes into those too
            build = engine._build_step_fns

            def rebuild():
                build()
                fault(engine)
            engine._build_step_fns = rebuild
        return engine
    monkeypatch.setattr(program, "make_engine", broken)
    out = _run(workload)
    assert not out["correct"], out["checks"]


def test_faults_reach_the_window_of_a_cell_that_switches_in_set_up(
        monkeypatch):
    """A decode fault planted only once set-up has switched the
    accumulation setting, so only the window's steps carry it."""
    cell = tiny_cell(REASON)
    assert cell.setup_accum is not None
    set_accum = program.set_accum

    def switch_then_break(engine, accum):
        set_accum(engine, accum)
        _stale_state(engine)
    monkeypatch.setattr(program, "set_accum", switch_then_break)
    out = _run(REASON)
    assert not out["correct"], out["checks"]


def test_prefill_fault_changes_lengths():
    lengths = jnp.asarray(np.arange(1, 5, dtype=np.int32))
    assert lengths.at[1::2].set(0).tolist() == [1, 0, 3, 0]
