"""Cells small enough for the CPU: the harness end to end, the program's
jnp oracle in place of the compiled kernels.

``reason`` is the benchmark's cell; ``chat`` is the open-loop mix on the
``qwen3-32b-s16`` configuration, which has its data files but no cell in
``BENCHMARK.json`` yet, so that the open-loop path stays tested."""

from __future__ import annotations

import copy

from chipbench.cell import HERE, Cell, _json, resolve

# qwen2-1.5b's and qwen3-32b's layer equations at test widths
SIZES = {
    "qwen2-1.5b": {"num_hidden_layers": 2, "hidden_size": 64,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 16, "intermediate_size": 128,
                   "vocab_size": 512},
    "qwen3-32b-s16": {"num_hidden_layers": 2, "hidden_size": 64,
                      "num_attention_heads": 4, "num_key_value_heads": 2,
                      "head_dim": 16, "intermediate_size": 160,
                      "vocab_size": 512},
}
REASON = "qwen2-1.5b.reason.sorted24"
CHAT = "chat"


def full_cell(name: str) -> Cell:
    """The cell as the chip runs it: the benchmark's, or the chat mix on
    qwen3-32b-s16 at the rate and limit its readings gave (§6 of PERF.md)."""
    if name != CHAT:
        return resolve(name)
    return Cell(
        "qwen3-32b-s16.chat.int32", 1,
        _json(HERE / "configs" / "qwen3-32b-s16.json"),
        _json(HERE / "mixes" / "chat.json"),
        {"rate_per_s": 0.28,
         "correct": {"widest_gap": 3.5, "tokens_checked": 128}},
        _json(HERE / "accum" / "int32.json"),
        ("setup_s", "tokens_per_s", "itl_p95_ms", "ttft_p95_ms"),
        ("device.idle_share", "step_mfu", "pqs_dot_roofline",
         "decode_step_ms", "prefill_step_ms", "queue_wait_ms"))


def tiny_cell(name: str, slots: int = 4, max_len: int = 256,
              rate: float = 4.0) -> Cell:
    cell = full_cell(name)
    config = copy.deepcopy(cell.config)
    sizes = SIZES["qwen3-32b-s16" if config["arch"] == "qwen3-32b"
                  else "qwen2-1.5b"]
    config["config"].update(sizes)
    config["reduced"] = sorted(set(config["reduced"]) | set(sizes))
    config["serving"].update(slots=slots, max_len=max_len)
    mix = copy.deepcopy(cell.mix)
    if mix["loop"] == "open":
        mix["prompt"].update(median=24, lo=4, hi=64)
        mix["output"].update(median=6, lo=2, hi=16)
    load = dict(cell.load)
    # limits of their own at test size: a test-size window serves fewer
    # tokens, and at these widths sound runs read widest gaps of 0.02-0.13
    # and the int4 control 2.1-4.4 (CPU readings, seeds 1-3 and 2**33+7)
    load["correct"] = {"widest_gap": 1.0, "tokens_checked": 16}
    if "rate_per_s" in load:
        load["rate_per_s"] = rate
    if mix["loop"] == "closed":
        mix["context"].update(lo=8, hi=max_len - 64)
    return Cell(cell.name, 1, config, mix, load, cell.accum,
                cell.end_to_end, cell.per_layer, cell.setup_accum)
