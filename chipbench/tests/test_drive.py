"""Which token gaps the window counts: ``Driver.itl_ms`` takes a gap only
where both of its tokens came in the window, so a closed loop's pause
between set-up and the window is never read as a decode step, and an
open loop's gaps are all still there.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench import drive, run
from chipbench.tests.tiny import CHAT, REASON, tiny_cell
from chipbench.traffic import Spec

SEED = 2**33 + 29
PAUSE_S = 1.0


def _pairs(driver, counted):
    """Gaps (ms) between consecutive tokens of a request, where
    ``counted(a, b)`` says which pairs of token times to take."""
    return [(b - a) * 1e3 for r in driver.records
            for a, b in zip(r.times, r.times[1:]) if counted(a, b)]


@dataclasses.dataclass
class _Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    output: list = dataclasses.field(default_factory=list)
    done: bool = False


class _Engine:
    """Each step emits one token for every request in a slot, after
    ``step_s`` of work."""

    def __init__(self, slots: int, step_s: float):
        self.queue, self.slots, self.step_s = [], [None] * slots, step_s

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        while self.queue and None in self.slots:
            self.slots[self.slots.index(None)] = self.queue.pop(0)
        time.sleep(self.step_s)
        for i, req in enumerate(self.slots):
            if req is not None:
                req.output.append(0)
                if len(req.output) == req.max_new_tokens:
                    req.done, self.slots[i] = True, None


def test_closed_loop_counts_no_gap_across_the_set_up_pause():
    rows = 4
    specs = [Spec(uid=i, prompt=np.zeros(8, np.int32), max_new=10**6)
             for i in range(rows)]
    driver = drive.Driver(_Engine(rows, 0.01), specs, "closed", _Request)
    driver.warm(vocab=16)
    time.sleep(0.3)
    driver.window(0.2)
    gaps = driver.itl_ms()
    # every row's first token of the window follows one made in set-up
    assert len(gaps) == rows * (len(driver.window_step_ms) - 1)
    assert max(gaps) < 300.0
    spanning = _pairs(driver, lambda a, b: a <= driver.t0 < b)
    assert len(spanning) == rows and min(spanning) >= 300.0


def _run_capturing_driver(workload, monkeypatch, pause_s=0.0):
    """One tiny run on the CPU; the driver it used, and its result."""
    drivers = []
    warm = drive.Driver.warm

    def warm_then_pause(self, *a, **kw):
        warm(self, *a, **kw)
        drivers.append(self)
        time.sleep(pause_s)
    monkeypatch.setattr(drive.Driver, "warm", warm_then_pause)
    out = run.run_cell(tiny_cell(workload), SEED, 1.5, False,
                       on_chip=False, t_start=time.perf_counter())
    (driver,) = drivers
    return driver, out


def test_tiny_reason_cell_leaves_out_the_pause_before_the_window(
        monkeypatch):
    driver, out = _run_capturing_driver(REASON, monkeypatch, PAUSE_S)
    gaps = driver.itl_ms()
    both = _pairs(driver, lambda a, b: (driver.in_window(a)
                                        and driver.in_window(b)))
    assert gaps == both and gaps
    assert max(gaps) < PAUSE_S * 1e3
    spanning = _pairs(driver, lambda a, b: a <= driver.t0 < b)
    assert spanning and min(spanning) >= PAUSE_S * 1e3
    r = out["run"]
    assert r["itl_gaps"] == len(gaps)
    assert r["step_ms_max"] >= r["step_ms_median"] > 0
    assert out["correct"], out["checks"]


def test_tiny_chat_cell_keeps_every_gap_of_the_window(monkeypatch):
    driver, out = _run_capturing_driver(CHAT, monkeypatch)
    later_in_window = _pairs(driver, lambda a, b: driver.in_window(b))
    assert later_in_window and driver.itl_ms() == later_in_window
    assert out["run"]["itl_gaps"] == len(later_in_window)


def test_no_gap_without_two_tokens_in_the_window():
    driver = drive.Driver(_Engine(1, 0.0), [], "closed", _Request)
    rec = drive.Record(Spec(0, np.zeros(1, np.int32), 3),
                       _Request(0, None, 3))
    rec.times = [1.0, 2.0, 3.0]
    driver.records.append(rec)
    driver.t0, driver.t_end = 1.5, 2.5
    assert driver.itl_ms() == []
    driver.t_end = 3.0
    assert driver.itl_ms() == [1000.0]
