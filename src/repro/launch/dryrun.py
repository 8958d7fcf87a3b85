import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: a successful
``.lower().compile()`` on the 256-chip single-pod mesh and the 512-chip
2-pod mesh means every sharding constraint, collective, and memory
placement is accepted by the SPMD partitioner. Captures per cell:

  - memory_analysis()      : per-device bytes (argument/output/temp/peak)
  - cost_analysis()        : per-device HLO flops + bytes accessed (NB:
                             while bodies counted once — see probe below)
  - collective byte census : trip-count-weighted parse of the partitioned
                             HLO call graph (launch/hlo_census.py)
  - FLOP probe             : a second, UNROLLED + unchunked-attention
                             lowering on one device whose
                             lowered.cost_analysis() gives trip-exact
                             *global* HLO flops (no compile, no alloc)

Roofline terms (benchmarks/roofline.py) combine these per DESIGN.md §7.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi    # 2-pod pass
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Optional

import jax

from repro.configs import ARCH_IDS, SHAPES, cells_for, get_config
from repro.launch import sharding as shard_lib
from repro.launch.hlo_census import collective_census, loop_flop_multiplier
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    batch_specs,
    cache_specs,
    make_opt_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    params_specs,
    token_specs,
)
from repro.models.model import build_model


def _make_step(model, kind: str):
    if kind == "train":
        return make_train_step(model), (0, 1)
    if kind == "prefill":
        return make_prefill_step(model), ()
    return make_serve_step(model), (2,)


def _shardings_for(mesh, model, kind: str, shape, quantized: bool = False):
    """(in_shardings, out_shardings, arg_specs) for one cell's step.

    quantized=True lowers the step against PQS int8 QTensor weights
    (bits=8, 8:16 N:M) — the paper's storage format at production scale
    (§Perf iteration 6: decode weight-streaming).
    """
    p_specs = params_specs(model)
    if quantized:
        from repro.core.qtensor import quantize_tree

        p_specs = jax.eval_shape(
            lambda p: quantize_tree(p, bits=8, n_keep=8, m=16,
                                    min_size=1 << 16),
            p_specs,
        )
    moe_rep = bool(getattr(model.cfg, "moe_local_groups", False))
    serve_mode = quantized and kind == "decode"
    p_shard = shard_lib.params_shardings(mesh, p_specs,
                                         moe_replicate=moe_rep,
                                         serve_mode=serve_mode)
    if kind == "train":
        o_specs = make_opt_specs(model)
        o_shard = shard_lib.opt_shardings(mesh, o_specs)
        b_specs = batch_specs(model.cfg, shape)
        b_shard = shard_lib.batch_shardings(mesh, b_specs)
        ins = (p_shard, o_shard, b_shard)
        outs = (p_shard, o_shard, shard_lib.replicated(mesh))
        args = (p_specs, o_specs, b_specs)
    elif kind == "prefill":
        b_specs = batch_specs(model.cfg, shape)
        b_shard = shard_lib.batch_shardings(mesh, b_specs)
        logits_spec = jax.eval_shape(
            lambda p, b: model.forward(p, b), p_specs, b_specs
        )
        ins = (p_shard, b_shard)
        outs = shard_lib.logits_sharding(mesh, logits_spec.shape)
        args = (p_specs, b_specs)
    else:  # decode
        c_specs = cache_specs(model, shape)
        c_shard = shard_lib.cache_shardings(mesh, c_specs)
        t_specs = token_specs(model.cfg, shape)
        t_shard = shard_lib.batch_shardings(mesh, {"token": t_specs})["token"]
        logits_spec = jax.eval_shape(
            lambda p, t, c: model.decode(p, t, c)[0], p_specs, t_specs, c_specs
        )
        ins = (p_shard, t_shard, c_shard)
        outs = (shard_lib.logits_sharding(mesh, logits_spec.shape), c_shard)
        args = (p_specs, t_specs, c_specs)
    return ins, outs, args


def _cost_dict(cost) -> dict:
    """Normalize jax cost_analysis() output: some versions return a dict,
    others a per-program list of dicts (take the entry program's)."""
    if isinstance(cost, dict):
        return cost
    if isinstance(cost, (list, tuple)):
        return cost[0] if cost else {}
    return {}


def _memory_dict(mem) -> dict:
    """Per-device memory stats; older xla builds lack peak_memory_in_bytes,
    in which case arguments + outputs + temps is the standard upper bound."""
    arg = getattr(mem, "argument_size_in_bytes", None)
    out = getattr(mem, "output_size_in_bytes", None)
    tmp = getattr(mem, "temp_size_in_bytes", None)
    peak = getattr(mem, "peak_memory_in_bytes", None)
    if peak is None and None not in (arg, out, tmp):
        peak = arg + out + tmp
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "peak_bytes": peak,
    }


def probe_cost(arch: str, shape_name: str) -> dict[str, float]:
    """Trip-exact global HLO flops/bytes: unrolled scans, unchunked attention,
    single logical device, lower-only (never compiled, never allocated)."""
    cfg = get_config(arch)
    cfg = dataclasses.replace(
        cfg, scan_unroll=True, attn_chunk_threshold=1 << 30
    )
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    step, _ = _make_step(model, shape.kind)
    if shape.kind == "train":
        args = (params_specs(model), make_opt_specs(model),
                batch_specs(cfg, shape))
    elif shape.kind == "prefill":
        args = (params_specs(model), batch_specs(cfg, shape))
    else:
        args = (params_specs(model), token_specs(cfg, shape),
                cache_specs(model, shape))
    lowered = jax.jit(step).lower(*args)
    cost = _cost_dict(lowered.cost_analysis())
    return {
        "global_flops": float(cost.get("flops", 0.0)),
        "global_bytes_hlo": float(cost.get("bytes accessed", 0.0)),
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    verbose: bool = True,
    with_probe: bool = True,
    variant: Optional[str] = None,
) -> dict[str, Any]:
    cfg = get_config(arch)
    if variant and "sp" in variant:
        cfg = dataclasses.replace(cfg, seq_parallel=True)
    if variant and "moe" in variant:
        cfg = dataclasses.replace(cfg, moe_local_groups=True)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shape.kind

    step, donate = _make_step(model, kind)
    quantized = bool(variant and "q8" in variant)
    ins, outs, args = _shardings_for(mesh, model, kind, shape,
                                     quantized=quantized)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            step, in_shardings=ins, out_shardings=outs, donate_argnums=donate
        )
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled.cost_analysis())
    census = collective_census(compiled.as_text())
    ndev = int(mesh.devices.size)

    result = {
        "arch": arch,
        "shape": shape_name,
        "variant": variant,
        "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "num_devices": ndev,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": _memory_dict(mem),
        "cost": {
            "flops_per_device_hlo": cost.get("flops"),
            "bytes_per_device_hlo": cost.get("bytes accessed"),
        },
        "collectives": census,
    }
    if with_probe:
        t0 = time.time()
        result["probe"] = probe_cost(arch, shape_name)
        result["probe"]["probe_s"] = round(time.time() - t0, 1)
        r = loop_flop_multiplier(
            result["probe"]["global_flops"],
            cost.get("flops") or 0.0,
            ndev,
        )
        result["loop_multiplier"] = r
        result["derived"] = {
            "flops_per_device": result["probe"]["global_flops"] / ndev,
            "bytes_per_device": (cost.get("bytes accessed") or 0.0) * r,
        }
    if verbose:
        d = result.get("derived", {})
        print(
            f"[dryrun] {arch:22s} {shape_name:12s} {result['mesh']:8s} OK "
            f"lower {t_lower:5.1f}s compile {t_compile:6.1f}s  "
            f"flops/dev {d.get('flops_per_device', 0):.3e}  "
            f"bytes/dev {d.get('bytes_per_device', 0):.3e}  "
            f"coll/dev {census['total_bytes_per_device']:.3e}B  "
            f"peak {result['memory']['peak_bytes'] or 0:.2e}B"
        , flush=True)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results, failures = [], []
    for arch in archs:
        shapes = [args.shape] if args.shape else cells_for(arch)
        for shape_name in shapes:
            if shape_name not in cells_for(arch):
                print(f"[dryrun] skip {arch} x {shape_name} (see DESIGN.md)")
                continue
            for mp in meshes:
                try:
                    results.append(
                        run_cell(arch, shape_name, mp,
                                 with_probe=not args.no_probe)
                    )
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape_name, mp, repr(e)))
                    print(f"[dryrun] {arch} {shape_name} multi_pod={mp} "
                          f"FAILED: {e}", flush=True)

    out = args.out or os.path.join(
        os.path.dirname(__file__), "..", "..", "..",
        "benchmarks", "results", f"dryrun_{args.mesh}.json",
    )
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"results": results, "failures": failures}, f, indent=1)
    print(f"[dryrun] wrote {len(results)} cells, {len(failures)} failures -> {out}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
