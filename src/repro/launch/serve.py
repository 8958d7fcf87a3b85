"""Serving driver: batched prefill + continuous-batching decode.

CPU container: reduced configs, real token generation through the
ServingEngine. Production: the same ``serve_step`` is the object the
decode dry-run cells lower on the 256/512-chip meshes.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --requests 6 --max-new 16

Fault-tolerance drills run the same engine under the fleet supervisor:

  PYTHONPATH=src python -m repro.launch.serve --smoke --inject-fail 5,11 \
      --snapshot-every 3
  PYTHONPATH=src python -m repro.launch.serve --smoke --int-policy \
      sorted_tiled_seq --acc-bits 17 --census-threshold 0.01
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model, param_count
from repro.serving import CensusWatch, Request, ServingEngine, ServingFleet


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prefill-mode", default="batched",
                    choices=["batched", "steps"],
                    help="batched: one jitted prefill step per admission "
                         "cohort; steps: legacy token-by-token")
    ap.add_argument("--page-size", type=int, default=None,
                    help="enable the paged KV/SSM cache with this many "
                         "tokens per page (default: dense per-slot lanes)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV page-pool size (default: worst case, "
                         "slots x ceil(max_len / page_size))")
    ap.add_argument("--cache-dtype", default=None,
                    choices=["int8"],
                    help="int8: quantize KV pages (needs --page-size)")
    ap.add_argument("--prefill-decode-ratio", type=int, default=0,
                    help="interleave: decode steps between prefill "
                         "micro-steps (0 = prefill immediately on admit)")
    # fault-tolerance drills: fleet supervision, failures, degradation
    ap.add_argument("--fleet", action="store_true",
                    help="drive the engine through ServingFleet + "
                         "ServeSupervisor instead of engine.drain")
    ap.add_argument("--inject-fail", default=None, metavar="STEPS",
                    help="comma-separated engine steps to crash at "
                         "(implies --fleet; recovery from snapshots)")
    ap.add_argument("--snapshot-every", type=int, default=4,
                    help="fleet steps between serving-state snapshots")
    ap.add_argument("--snapshot-dir", default=None,
                    help="persist snapshots here via AsyncCheckpointer "
                         "(default: in-memory only)")
    ap.add_argument("--quota", type=int, default=None,
                    help="fleet admission quota (max in-flight requests)")
    ap.add_argument("--deadline", type=int, default=None,
                    help="per-request deadline in fleet steps; expired "
                         "requests are cancelled and retried with backoff")
    ap.add_argument("--int-policy", default=None,
                    choices=["wide", "clip", "wrap", "sorted",
                             "sorted_tiled", "sorted_tiled_seq"],
                    help="quantize weights and decode through integer "
                         "pqs_dot under this accumulator policy")
    ap.add_argument("--acc-bits", type=int, default=24,
                    help="accumulator width for --int-policy")
    ap.add_argument("--census-threshold", type=float, default=None,
                    help="enable census-triggered degradation at this "
                         "overflow rate (requires --int-policy)")
    ap.add_argument("--census-window", type=int, default=8,
                    help="decode steps per census window")
    ap.add_argument("--certify", action="store_true",
                    help="enforce the A2Q accumulator bound on the "
                         "quantized weights, certify every site "
                         "(core.certify), and serve certified sites "
                         "census-free (requires --int-policy)")
    ap.add_argument("--qat-steps", type=int, default=0,
                    help="accumulator-aware fine-tuning steps before "
                         "quantization (runtime.a2q_finetune; 0 = skip)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"[serve] arch={cfg.name} params={param_count(params):,} "
          f"slots={args.slots}")

    int_lin = None
    census_watch = None
    cert = None
    if args.int_policy:
        from repro.core import dispatch

        if args.qat_steps:
            from repro.runtime import QATConfig, a2q_finetune

            rng = np.random.default_rng(1)

            def next_batch(i: int) -> dict:
                tok = rng.integers(
                    0, cfg.vocab_size, size=(2, 16)
                ).astype(np.int32)
                return {"tokens": jnp.asarray(tok),
                        "labels": jnp.asarray(tok)}

            qcfg = QATConfig(acc_bits=args.acc_bits)
            params, history = a2q_finetune(
                model, params, next_batch, args.qat_steps, qcfg
            )
            print(f"[serve] qat: {args.qat_steps} steps, "
                  f"loss {history[0]['loss']:.4f} -> "
                  f"{history[-1]['loss']:.4f}, final census rates "
                  f"{ {k: round(v, 4) for k, v in history[-1]['census_rates'].items()} }")

        if args.certify:
            from repro.runtime import quantize_and_certify

            params, cert = quantize_and_certify(params, args.acc_bits)
            print("[serve] " + cert.summary().replace("\n", "\n[serve] "))
        else:
            from repro.core.qtensor import quantize_tree

            # smoke widths need low thresholds to count as matrices; at
            # full width they would take layer-stacked (L, out) biases
            # for matrices once L >= 16
            small = dict(min_size=1 << 10, min_dim=16) if args.smoke else {}
            params = quantize_tree(params, bits=8, **small)
        int_lin = dispatch.IntegerLinConfig(
            policy=args.int_policy, acc_bits=args.acc_bits,
            k_tile=64, certificate=cert,
        )
        if args.census_threshold is not None:
            census_watch = CensusWatch(
                threshold=args.census_threshold, window=args.census_window
            )
    elif args.census_threshold is not None:
        ap.error("--census-threshold requires --int-policy")
    elif args.certify or args.qat_steps:
        ap.error("--certify/--qat-steps require --int-policy")

    failure_injector = None
    if args.inject_fail:
        from repro.runtime import FailureInjector

        failure_injector = FailureInjector(
            {int(s) for s in args.inject_fail.split(",")}
        )
        args.fleet = True

    engine = ServingEngine(
        model, params, num_slots=args.slots, max_len=args.max_len,
        prefill_mode=args.prefill_mode,
        page_size=args.page_size, num_pages=args.num_pages,
        cache_dtype=args.cache_dtype or "float32",
        prefill_decode_ratio=args.prefill_decode_ratio,
        int_lin=int_lin, census_watch=census_watch,
        failure_injector=failure_injector,
    )
    if int_lin is not None:
        cal = {"tokens": jnp.asarray(
            (np.arange(32).reshape(2, 16) % cfg.vocab_size + 1) % cfg.vocab_size,
            jnp.int32,
        )}
        frozen = engine.calibrate([cal])
        print(f"[serve] integer decode: policy={args.int_policy} "
              f"acc_bits={args.acc_bits} calibrated {len(frozen)} sites"
              + (f", census threshold={args.census_threshold} "
                 f"window={args.census_window}" if census_watch else ""))
    if args.page_size:
        print(f"[serve] paged cache: page_size={args.page_size} "
              f"pages={engine.paging.num_pages} "
              f"dtype={args.cache_dtype or 'float32'} "
              f"footprint={engine.cache_nbytes() / 1e6:.3f} MB")
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            uid=i,
            prompt=rng.integers(
                0, cfg.vocab_size, size=rng.integers(4, 12)
            ).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=args.temperature,
        )
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    if args.fleet:
        from repro.runtime import ServeSupervisor

        fleet = ServingFleet(
            snapshot_dir=args.snapshot_dir,
            snapshot_every=args.snapshot_every,
            default_deadline=args.deadline,
        )
        fleet.add_engine("m", engine, quota=args.quota)
        for r in reqs:
            fleet.submit("m", r)
        ServeSupervisor(fleet).run()
        fleet.wait()
    else:
        engine.drain(reqs)
    dt = time.perf_counter() - t0
    total_new = sum(len(r.output) for r in reqs)
    for r in reqs:
        print(f"[serve] req {r.uid}: prompt {r.prompt.tolist()} -> "
              f"{r.output}")
    print(f"[serve] {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s, batched over {args.slots} slots)")
    st = engine.stats
    print(f"[serve] device steps: {st['prefill_steps']} prefill for "
          f"{st['cohorts']} admission cohorts ({args.prefill_mode}), "
          f"{st['decode_steps']} decode")
    if args.page_size:
        print(f"[serve] pages: peak {st['pages_peak']} in use, "
              f"queue_wait_steps={st['queue_wait_steps']}, "
              f"hol_skips={st['hol_skips']}")
    if args.fleet:
        fs = fleet.stats
        print(f"[serve] fleet: snapshots={fs['snapshots']} "
              f"recoveries={fs['recoveries']} "
              f"recovery_s={fs['recovery_s']:.3f} "
              f"deadline_cancels={fs['deadline_cancels']} "
              f"failed={fs['failed_requests']}")
        for ev in fleet.events:
            print(f"[serve] event: {ev}")
    if census_watch is not None:
        print(f"[serve] census: degrades={st['census_degrades']} "
              f"rates={ {k: round(v, 4) for k, v in engine.last_census_rates.items()} }")
        for ev in engine.events:
            print(f"[serve] event: {ev}")


if __name__ == "__main__":
    main()
