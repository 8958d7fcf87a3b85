"""Run one cell of BENCHMARK.json once, on the chips of this machine.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

Set-up makes the weights on the device from ``--seed``, builds the
serving engine with the cell's deployment and accumulation setting, and
runs every shape the window will use (served from the persistent
compilation cache after a checkout's first run). A closed loop's
contexts are built here, under the cell's ``setup_accum`` where it names
one, and the window decodes on from them under its own. The window then serves
the cell's traffic for ``--seconds``; ``--trace 1`` records it with the
profiler and reports the per-layer metrics instead of the end-to-end
ones. Once the window has closed, the program's state is freed and the
plain reference checks what was served. The last line of standard output
is one JSON object; the numbers compared, each beside its limit, are the
last lines of standard error and the result's last key.

With no TPU, fewer chips than the cell asks for, or a chip kind missing
from ``peaks.json``, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from chipbench.cell import Cell, reader, resolve  # noqa: E402

# after the window, how long the open loop may take to finish what it sent
DRAIN_CAP_S = 120.0
# requests of an open loop whose served tokens the reference checks
SAMPLE = 8


@dataclasses.dataclass
class RunView:
    """What a metric reader reads."""

    cell: Cell
    dims: object  # weights.Dims
    driver: object  # drive.Driver
    setup_s: float
    peak_bytes: Optional[int]
    trace: object  # trace.Trace or None
    peaks: Optional[dict]


class CompileCounter:
    """Counts JAX lowerings and backend compiles while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


def device_info(chips: int) -> dict:
    """The chips as JAX reports them; refuses anything but enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(chips: int) -> Optional[int]:
    import jax

    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    if not all(stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def sample(driver, seed: int) -> list:
    """The requests the reference checks. Closed loop: every client's
    request as served so far. Open loop: ``SAMPLE`` finished requests
    drawn from the seed, the one with the longest output among them."""
    if driver.loop == "closed":
        return [r for r in driver.records if r.req.output]
    done = [r for r in driver.records if r.req.done and r.req.output]
    if len(done) <= SAMPLE:
        return done
    longest = max(range(len(done)), key=lambda i: len(done[i].req.output))
    rest = [i for i in range(len(done)) if i != longest]
    pick = np.random.default_rng(seed % (1 << 64)).choice(
        rest, SAMPLE - 1, replace=False)
    return [done[longest]] + [done[i] for i in sorted(pick)]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             on_chip: bool = True, t_start: float = T_START,
             control_bits: Optional[int] = None) -> dict:
    """One run; ``control_bits`` also judges the control, by the same
    comparison and limits, on the same requests (``readings.py``; the
    benchmark's runs do not)."""
    import jax

    from chipbench import drive, program, reference, roofline, traffic
    from chipbench import trace as trace_lib
    from chipbench.weights import Dims

    info = device_info(cell.chips) if on_chip else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": 1}
    pk = roofline.peaks(info["kind"]) if on_chip else None
    program.enable_compile_cache()
    counter = CompileCounter()
    serving = cell.config["serving"]
    dims = Dims.from_config(cell.config["config"])

    model, params = program.build(cell.config, seed)
    engine = program.make_engine(model, params, serving,
                                 cell.setup_accum or cell.accum)
    specs = traffic.generate(
        cell.mix, cell.load, seed=seed, seconds=seconds,
        slots=serving["slots"], max_len=serving["max_len"], vocab=dims.vocab)
    driver = drive.Driver(engine, specs, cell.mix["loop"], program.Request)
    switch = (None if cell.setup_accum is None
              else lambda: program.set_accum(engine, cell.accum))
    driver.warm(dims.vocab, switch)
    jax.block_until_ready(engine.caches)
    # what set-up made stays: no collection walks it inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        # no Python call tracing: it would slow the host it is measuring
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(logdir, profiler_options=opts)
    counter.armed = True
    driver.window(seconds)
    counter.armed = False
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(cell.chips) if on_chip else None
    t_drain = time.perf_counter()
    driver.drain(DRAIN_CAP_S)
    drain_s = time.perf_counter() - t_drain

    # the program's state goes before the reference runs
    checked = sample(driver, seed)
    prompts = [r.spec.prompt for r in checked]
    outputs = [list(r.req.output) for r in checked]
    driver.engine = None
    del engine, params, model
    gc.collect()

    tr = None
    if trace:
        tr = trace_lib.load(trace_lib.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
    view = RunView(cell, dims, driver, setup_s, peak, tr, pk)
    kind = "metrics" if trace else "end_to_end"
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        mod = reader(kind, name)
        v = mod.read(view)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": mod.UNIT}

    limits = cell.load["correct"]
    n_tok = sum(len(o) for o in outputs)
    t_ref = time.perf_counter()
    widest = control = float("inf")
    if n_tok:
        gaps, gaps_c = reference.logit_gaps(seed, dims, prompts, outputs,
                                            control_bits)
        widest = float(np.max(gaps))
        if gaps_c is not None:
            control = float(np.max(gaps_c))
    reference_s = time.perf_counter() - t_ref

    def judged(gap: float) -> tuple[bool, dict]:
        return (gap <= limits["widest_gap"]
                and n_tok >= limits["tokens_checked"]), {
            "widest_gap": {"value": gap, "limit": limits["widest_gap"]},
            "tokens_checked": {"value": n_tok,
                               "min": limits["tokens_checked"]}}
    correct, checks = judged(widest)
    failed = len(driver.failed())

    device = dict(info)
    device["memory_peak_bytes"] = peak
    if tr is not None:
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = tr.window_s()
    out = {"correct": bool(correct), "attempted": len(driver.records),
           "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        out["breakdown"] = {"device_ops": trace_lib.top_ops(tr),
                            "idle_gaps": trace_lib.idle_gaps(tr)}
    lat = driver.lateness_ms()
    step_ms = driver.window_step_ms
    out["run"] = {"window_s": driver.window_s(),
                  "window_steps": len(step_ms),
                  "tokens_in_window": driver.tokens_in_window(),
                  # what set itl_p95_ms: in a closed loop every row
                  # advances together, so each gap spans one step
                  "itl_gaps": len(driver.itl_ms()),
                  "step_ms_max": max(step_ms) if step_ms else None,
                  "step_ms_median": (float(np.median(step_ms)) if step_ms
                                     else None),
                  "compiles_in_window": counter.count,
                  "queued_at_close": driver.queued_at_close,
                  "unfinished_at_close": driver.unfinished_at_close,
                  "ttft_ms_by_third": driver.ttft_ms_by_third(),
                  "generator_late_ms_max": max(lat) if lat else 0.0,
                  "drain_s": drain_s, "reference_s": reference_s}
    if control_bits is not None:
        # the control, judged by the same comparison and limits
        out["control_correct"], out["control_checks"] = judged(control)
    out["checks"] = checks
    return out


def report(out: dict) -> None:
    r = out["run"]
    print(f"[run] window {r['window_s']:.3f}s, {r['window_steps']} steps, "
          f"{r['tokens_in_window']} tokens, {r['itl_gaps']} token gaps; "
          f"step ms max {r['step_ms_max']!r}, median "
          f"{r['step_ms_median']!r}; compiles in window "
          f"{r['compiles_in_window']}; generator late by at most "
          f"{r['generator_late_ms_max']:.1f} ms", file=sys.stderr)
    for name, c in out["checks"].items():
        if "limit" in c:
            print(f"check {name} {c['value']!r} <= limit {c['limit']!r}",
                  file=sys.stderr)
        else:
            print(f"check {name} {c['value']!r} >= min {c['min']!r}",
                  file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell = resolve(args.workload)
    report(run_cell(cell, args.seed, args.seconds, bool(args.trace)))


if __name__ == "__main__":
    main()
