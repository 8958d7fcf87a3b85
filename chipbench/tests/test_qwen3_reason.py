"""The ``qwen3-32b-s16.reason.sorted24`` cell's correctness check, driven end
to end on the CPU at test widths by ``test_faults.py``'s own cases: a sound
run passes, the int4 control reads past the limit, and each fault planted
under the timed path makes ``correct`` come out false."""

from __future__ import annotations

import pytest

from chipbench.tests import test_faults
from chipbench.tests.tiny import tiny_cell

CELL = "qwen3-32b-s16.reason.sorted24"


def test_cell_is_the_qwen3_path_under_sorted24():
    cell = tiny_cell(CELL)
    c = cell.config["config"]
    assert c["qk_norm"] and not c["attention_bias"]
    assert not c["tie_word_embeddings"]
    assert cell.accum["policy"] == "sorted_tiled_seq"
    assert cell.accum["acc_bits"] == 24
    assert cell.setup_accum == {"policy": "wide"}


def test_sound_run_is_correct_and_control_is_not():
    test_faults.test_sound_run_is_correct_and_control_is_not(CELL)


@pytest.mark.parametrize("fault", [test_faults._stale_state,
                                   test_faults._half_batch,
                                   test_faults._token_altered])
def test_fault_under_the_timed_path_is_caught(fault, monkeypatch):
    test_faults.test_fault_under_the_timed_path_is_caught(CELL, fault,
                                                          monkeypatch)
