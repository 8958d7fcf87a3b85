"""Serving throughput under Poisson load: dense vs paged vs int8-paged.

Drives the continuous-batching engine with Poisson request arrivals and
reports, per cache mode:

  tokens_per_s     decoded tokens / wall time over the whole run
  p50_ms, p99_ms   end-to-end request latency (scheduled arrival ->
                   last token) percentiles
  step_ms          median jitted decode-step wall time
  cache_mb         cache footprint (pools + tables + state) — the
                   measured memory story: int8 pages vs f32 pages vs
                   dense f32 lanes
  queue_wait/pages engine admission + page-occupancy counters

Modes: ``f32_dense`` (monolithic per-slot lanes), ``f32_paged`` (page
pools, bit-identical decode), ``int8_paged`` (quantized KV pages). The
paged pool is deliberately undersized (num_pages < slots x pages/slot)
so admission backpressure and page recycling are on the measured path.

Also folds in the decode-step latency comparison that used to live in
``serving_latency.py`` (dynamic vs calibrated-static activation
quantization of the integer serving path) — one request-generation and
reporting path for all serving benches (``benchmarks.common``).

``check_against`` gates tokens_per_s against a committed baseline via
``run.py --check-serving-against`` (generous tolerance: CI guards
structural collapses, not jitter).

``--inject-failures`` (or the ``failures`` key of a full run) measures
the fault-tolerance overhead: the same Poisson workload is driven twice
through ``ServingFleet`` + ``ServeSupervisor`` — once failure-free, once
with two injected mid-decode crashes recovered from periodic snapshots —
and reports the per-recovery restore latency, the goodput ratio
(crash-run throughput / failure-free throughput), and whether the
recovered token streams stayed bit-identical. The committed baseline
gates goodput_ratio and tokens_match the same way it gates tokens_per_s.
"""

from __future__ import annotations

import json
import time

import jax
import numpy as np

from benchmarks.common import emit, gen_requests, poisson_arrivals
from repro.configs import get_config
from repro.core.dispatch import IntegerLinConfig
from repro.core.qtensor import quantize_tree
from repro.models.model import build_model
from repro.serving import ServingEngine

MODES = ("f32_dense", "f32_paged", "int8_paged")


def _make_engine(mode: str, model, params, *, num_slots, max_len, page_size,
                 num_pages):
    kw = {}
    if mode.endswith("paged"):
        kw.update(page_size=page_size, num_pages=num_pages)
    if mode.startswith("int8"):
        kw.update(cache_dtype="int8")
    return ServingEngine(model, params, num_slots=num_slots, max_len=max_len,
                         **kw)


def _warmup(eng, vocab: int, lens=(5, 9, 13)) -> None:
    """Compile the decode step and the prefill buckets the run will hit."""
    for j, n in enumerate(lens):
        reqs = gen_requests(vocab, 1, seed=10_000 + j, len_lo=n, len_hi=n,
                            max_new=2, uid_base=1_000_000 + j)
        eng.drain(reqs)


def _drive(eng, reqs, arrivals) -> dict:
    """Submit requests on their Poisson schedule; step until drained."""
    t0 = time.perf_counter()
    i = 0
    step_ms = []
    while True:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now:
            eng.submit(reqs[i])
            i += 1
        busy = any(s is not None for s in eng.slots) or eng.queue
        if not busy and i < len(reqs):
            time.sleep(max(float(arrivals[i]) - now, 0.0))
            continue
        t1 = time.perf_counter()
        n_active = eng.step()
        step_ms.append((time.perf_counter() - t1) * 1e3)
        if i >= len(reqs) and n_active == 0 and not eng.queue:
            break
    elapsed = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    # latency vs the *scheduled* arrival: queueing delay under load counts
    lat_ms = [
        (r.t_done - (t0 + float(arrivals[j]))) * 1e3
        for j, r in enumerate(reqs)
    ]
    return {
        "tokens_per_s": toks / max(elapsed, 1e-9),
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "step_ms": float(np.median(step_ms)),
        "queue_wait_steps": eng.stats["queue_wait_steps"],
        "hol_skips": eng.stats["hol_skips"],
        "pages_peak": eng.stats["pages_peak"],
    }


def run(arch: str = "qwen2-1.5b", quick: bool = False, seed: int = 0) -> dict:
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    n_requests = 8 if quick else 24
    max_new = 6 if quick else 12
    num_slots, max_len, page_size = 4, 64, 16
    # undersized pool: 3/4 of the dense worst case, so page recycling
    # and admission backpressure are part of what gets measured
    num_pages = 3 * num_slots * (max_len // page_size) // 4

    results: dict = {}
    rows = []
    for mode in MODES:
        eng = _make_engine(mode, model, params, num_slots=num_slots,
                           max_len=max_len, page_size=page_size,
                           num_pages=num_pages)
        _warmup(eng, cfg.vocab_size)
        reqs = gen_requests(cfg.vocab_size, n_requests, seed=seed,
                            len_lo=4, len_hi=12, max_new=max_new)
        # arrival rate ~ a few requests per measured decode-step time;
        # fast enough to keep slots contended, slow enough to spread out
        arrivals = poisson_arrivals(n_requests, rate_per_s=40.0, seed=seed)
        res = _drive(eng, reqs, arrivals)
        res["cache_mb"] = eng.cache_nbytes() / 1e6
        results[mode] = res
        rows.append({"mode": mode, **{k: round(v, 3) if isinstance(v, float)
                                      else v for k, v in res.items()}})

    emit("BENCH_serving", rows,
         ["mode", "tokens_per_s", "p50_ms", "p99_ms", "step_ms", "cache_mb",
          "queue_wait_steps", "hol_skips", "pages_peak"])
    shrink = results["f32_paged"]["cache_mb"] / max(
        results["int8_paged"]["cache_mb"], 1e-9)
    print(f"[serving_throughput] int8 pages shrink the cache "
          f"{shrink:.2f}x vs f32 pages "
          f"({results['int8_paged']['cache_mb']:.3f} MB vs "
          f"{results['f32_paged']['cache_mb']:.3f} MB; dense f32 "
          f"{results['f32_dense']['cache_mb']:.3f} MB)")
    results["int8_shrink"] = shrink

    results["failures"] = bench_failures(arch, quick=quick, seed=seed)

    if not quick:
        results["int_decode"] = bench_int_decode(arch)
    return results


def bench_failures(arch: str = "qwen2-1.5b", quick: bool = False,
                   seed: int = 0) -> dict:
    """Fault-tolerance overhead: injected crashes vs a failure-free run.

    Drives the same request set through ``ServingFleet`` twice — clean,
    then with two mid-decode crashes recovered from periodic in-memory
    snapshots — and reports per-recovery restore latency, the goodput
    ratio (crashed throughput over clean throughput: snapshotting +
    restore + replayed steps are the overhead), and whether every
    recovered token stream stayed bit-identical to the clean run.
    """
    from repro.runtime import FailureInjector, ServeSupervisor
    from repro.serving import ServingFleet

    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    n_requests = 6 if quick else 12
    max_new = 6 if quick else 10
    num_slots, max_len, page_size = 2, 64, 16
    num_pages = num_slots * (max_len // page_size)

    def drive(inject: bool) -> tuple[dict, dict, float]:
        eng = ServingEngine(model, params, num_slots=num_slots,
                            max_len=max_len, page_size=page_size,
                            num_pages=num_pages)
        _warmup(eng, cfg.vocab_size)
        if inject:
            # schedule relative to the post-warmup step counter
            s = eng._step_idx
            eng.failure_injector = FailureInjector({s + 4, s + 11})
        reqs = gen_requests(cfg.vocab_size, n_requests, seed=seed,
                            len_lo=4, len_hi=10, max_new=max_new)
        fleet = ServingFleet(snapshot_every=4 if inject else 0)
        fleet.add_engine("m", eng)
        for r in reqs:
            fleet.submit("m", r)
        sup = ServeSupervisor(fleet)
        t0 = time.perf_counter()
        sup.run()
        wall = time.perf_counter() - t0
        return {r.uid: list(r.output) for r in reqs}, fleet.stats, wall

    base_out, _, base_wall = drive(inject=False)
    fail_out, stats, fail_wall = drive(inject=True)

    toks = sum(len(o) for o in base_out.values())
    base_tps = toks / max(base_wall, 1e-9)
    fail_tps = sum(len(o) for o in fail_out.values()) / max(fail_wall, 1e-9)
    res = {
        "recoveries": stats["recoveries"],
        "snapshots": stats["snapshots"],
        "recovery_ms": stats["recovery_s"] / max(stats["recoveries"], 1)
        * 1e3,
        "clean_tokens_per_s": base_tps,
        "failed_tokens_per_s": fail_tps,
        "goodput_ratio": fail_tps / max(base_tps, 1e-9),
        "tokens_match": fail_out == base_out,
    }
    emit("BENCH_serving_failures",
         [{k: round(v, 3) if isinstance(v, float) else v
           for k, v in res.items()}],
         ["recoveries", "snapshots", "recovery_ms", "clean_tokens_per_s",
          "failed_tokens_per_s", "goodput_ratio", "tokens_match"])
    print(f"[serving_throughput/failures] {stats['recoveries']} recoveries "
          f"at {res['recovery_ms']:.1f} ms each; goodput ratio "
          f"{res['goodput_ratio']:.2f} "
          f"(bit-identical={res['tokens_match']})")
    return res


def bench_int_decode(arch: str = "qwen2-1.5b", steps: int = 20,
                     slots: int = 4) -> dict:
    """Decode latency: dynamic vs calibrated-static activation quant.

    The integer serving path quantizes activations before every
    ``pqs_dot``; dynamically that is a per-call absmax reduction, after
    calibrate→freeze the scale is a constant and the reduction leaves
    the step (paper §2.1: ranges collected offline). Times the jitted
    decode step in float / int-dynamic / int-calibrated modes.
    """
    import jax.numpy as jnp

    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_tree(params, bits=8, min_size=1 << 10, min_dim=16)
    il = IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24, k_tile=64)
    rng = np.random.default_rng(0)
    cal_batches = [
        {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)))}
        for _ in range(4)
    ]

    def time_decode(eng) -> float:
        reqs = gen_requests(cfg.vocab_size, slots, seed=0, len_lo=4,
                            len_hi=4, max_new=steps + 4)
        for r in reqs:
            eng.submit(r)
        eng.step()  # admit + prefill + first decode (compiles)
        eng.step()  # warm
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            eng.step()
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    results = {}
    eng = ServingEngine(model, qparams, num_slots=slots, max_len=64)
    results["float"] = time_decode(eng)

    eng = ServingEngine(model, qparams, num_slots=slots, max_len=64,
                        int_lin=il)
    results["int_dynamic"] = time_decode(eng)

    eng = ServingEngine(model, qparams, num_slots=slots, max_len=64,
                        int_lin=il)
    eng.calibrate(cal_batches)
    results["int_calibrated"] = time_decode(eng)

    speedup = results["int_dynamic"] / max(results["int_calibrated"], 1e-12)
    print(f"[serving_throughput/int] {arch} decode step ({slots} slots, "
          f"median of {steps}):")
    for k in ("float", "int_dynamic", "int_calibrated"):
        print(f"  {k:15s} {results[k] * 1e3:8.2f} ms/step")
    print(f"  calibrated static ranges: {speedup:.2f}x vs dynamic absmax")
    results["dyn_over_cal"] = speedup
    return results


def check_against(results: dict, baseline_path: str, tolerance: float):
    """Throughput regression guard vs a committed baseline.

    Returns [(mode, field, baseline, now), ...] for every mode whose
    tokens_per_s fell below baseline / tolerance (or disappeared).
    When both sides carry a ``failures`` entry it is gated too:
    goodput_ratio may not collapse below baseline / tolerance, and
    recovered token streams must stay bit-identical (tokens_match).
    Mode gating is skipped for failures-only runs (--inject-failures).
    """
    with open(baseline_path) as f:
        base = json.load(f)
    regs = []
    if any(m in results for m in MODES):
        for mode, b in base.items():
            if mode not in MODES:
                continue
            now = results.get(mode)
            if now is None:
                regs.append((mode, "tokens_per_s", b["tokens_per_s"], None))
                continue
            if now["tokens_per_s"] < b["tokens_per_s"] / tolerance:
                regs.append((mode, "tokens_per_s", b["tokens_per_s"],
                             now["tokens_per_s"]))
    bf, nf = base.get("failures"), results.get("failures")
    if bf is not None and nf is not None:
        if not nf.get("tokens_match", False):
            regs.append(("failures", "tokens_match", True,
                         nf.get("tokens_match")))
        if nf["goodput_ratio"] < bf["goodput_ratio"] / tolerance:
            regs.append(("failures", "goodput_ratio", bf["goodput_ratio"],
                         nf["goodput_ratio"]))
    return regs


if __name__ == "__main__":
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject-failures", action="store_true",
                    help="run only the failure-injection bench")
    ap.add_argument("--check-against", default=None,
                    help="baseline JSON; nonzero exit on regression")
    ap.add_argument("--tolerance", type=float, default=2.0)
    args = ap.parse_args()

    if args.inject_failures:
        res = {"failures": bench_failures(args.arch, quick=args.quick,
                                          seed=args.seed)}
    else:
        res = run(args.arch, quick=args.quick, seed=args.seed)
    if args.check_against:
        regs = check_against(res, args.check_against, args.tolerance)
        for mode, field, b, now in regs:
            print(f"[serving_throughput] REGRESSION {mode}.{field}: "
                  f"baseline {b} -> now {now}")
        if regs:
            sys.exit(1)
        print(f"[serving_throughput] baseline check OK "
              f"({args.check_against}, tolerance {args.tolerance}x)")
