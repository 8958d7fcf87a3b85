#!/usr/bin/env python3
"""Smoke test of the integer serving path on a TPU.

  python chip_smoke.py             # one chip: kernel parity, then serving
  python chip_smoke.py --chips 4   # four chips: the meshed paths only

One chip. Kernel phase: every main-path Pallas kernel runs compiled at
qwen2-1.5b projection widths and must be bit-identical to the jnp oracle
of ``pqs_dot``. Serve phase: qwen2-1.5b at its published widths (random
weights from ``--seed``, int8 weights, int8 paged KV) serves requests
through ``ServingEngine`` with the platform's default backend, under
``wide`` and then ``sorted_tiled_seq``; every request must finish.

Four chips: a ``ServingEngine`` on the host serving mesh must emit exactly
the tokens of the same engine on one device, and ``pqs_dot`` K-sharded
over a 4-way mesh axis must equal the single-device ``k_shards=4``
hierarchy.

With no TPU it exits non-zero before any phase. Any failure ends the run
with a non-zero exit. The last line printed is one JSON object naming the
device; times printed on earlier lines are informational.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
M_DECODE = 8  # rows of a decode-step projection: one per serving slot
# (K, N) of qwen2-1.5b's widest projections: gate/up, then down
WIDTHS = ((1536, 8960), (8960, 1536))
SLOTS, MAX_LEN, PAGE = 8, 512, 16
ACC_BITS, K_TILE = 24, 64  # the serving defaults of launch/serve.py
NEW_TOKENS = 16


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def require_compiled(fn, *args) -> None:
    """The lowered program holds a Mosaic kernel: the Pallas path ran
    compiled, not in interpret mode and not through the jnp oracle."""
    import jax

    check("tpu_custom_call" in jax.jit(fn).lower(*args).as_text(),
          f"{getattr(fn, '__name__', fn)}: no compiled Pallas kernel")


def _int8(key, shape):
    import jax
    import jax.numpy as jnp

    return jax.random.randint(key, shape, -127, 128, jnp.int32).astype(
        jnp.int8)


def phase_kernels(seed: int, widths=WIDTHS, m: int = M_DECODE) -> None:
    """Each main-path kernel: compiled Pallas == jnp oracle, bit for bit."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.dispatch import pqs_dot
    from repro.core.pruning import nm_compress_jax, nm_prune_mask
    from repro.kernels import ops

    def dense(policy, **kw):
        return lambda b: functools.partial(
            pqs_dot, policy=policy, backend=b, **kw)

    def nm24(policy, **kw):
        def make(b):
            def f(x, v, i):
                return pqs_dot(x, (v, i), storage="nm", m_group=4,
                               policy=policy, backend=b, **kw)
            return f
        return make

    kernels = [
        (f"dense:{p}", dense(p, acc_bits=16, k_tile=K_TILE), False)
        for p in ("wide", "clip", "wrap", "sorted_tiled_seq")
    ] + [
        (f"nm2:4:{p}", nm24(p, acc_bits=16, k_tile=K_TILE), True)
        for p in ("wide", "sorted_tiled_seq")
    ] + [
        ("certified", dense("sorted_tiled_seq", acc_bits=ACC_BITS,
                            k_tile=K_TILE, certified=True), False),
    ]
    key = jax.random.PRNGKey(seed)
    operands = []
    for k, n in widths:
        key, kx, kw = jax.random.split(key, 3)
        x, w = _int8(kx, (m, k)), _int8(kw, (n, k))
        w24 = w * nm_prune_mask(w, 2, 4).astype(w.dtype)
        operands.append((k, n, x, w, nm_compress_jax(w24, 2, 4)))
    for name, make, sparse in kernels:
        pallas, oracle = jax.jit(make("pallas")), jax.jit(make("jnp"))
        times = []
        for k, n, x, w, (v, i) in operands:
            args = (x, v, i) if sparse else (x, w)
            require_compiled(pallas, *args)
            got = jax.block_until_ready(pallas(*args))
            check(np.array_equal(np.asarray(got), np.asarray(oracle(*args))),
                  f"{name} K={k} N={n}: pallas != jnp oracle")
            t0 = time.perf_counter()
            jax.block_until_ready(pallas(*args))
            times.append(f"K={k} N={n} {(time.perf_counter() - t0) * 1e6:.0f}us")
        print(f"[kernel] {name:24s} bit-identical  ({', '.join(times)})",
              flush=True)
    # the plain int8 matmul kernel has no pqs_dot policy: XLA's int32 dot
    for k, n, x, w, _ in operands:
        qm = jax.jit(lambda a, b: ops.quant_matmul(a, b.T))
        require_compiled(qm, x, w)
        ref = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        check(np.array_equal(np.asarray(qm(x, w)), np.asarray(ref)),
              f"quant_matmul K={k} N={n}: pallas != XLA int32 dot")
    print("[kernel] quant_matmul             bit-identical", flush=True)


def build_quantized(cfg, seed: int):
    """Random float32 weights from ``seed``, quantized to int8 QTensors."""
    import jax

    from repro.core.qtensor import quantize_tree
    from repro.models.model import build_model, param_count

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    n_params = param_count(params)
    qparams = quantize_tree(params, bits=8)
    del params
    jax.block_until_ready(qparams)
    print(f"[serve] {cfg.name}: {n_params:,} params, int8 weights in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return model, qparams


def make_requests(cfg, seed: int):
    """One request per slot, 16-64-token prompts from ``seed``."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [
        Request(uid=i, max_new_tokens=NEW_TOKENS,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(16, 65))
                                    ).astype(np.int32))
        for i in range(SLOTS)
    ]


def make_engine(model, params, policy: str, mesh=None):
    """Paged int8-KV engine whose integer projections take the platform's
    default ``pqs_dot`` backend (no backend pin)."""
    from repro.core.dispatch import IntegerLinConfig
    from repro.serving import ServingEngine

    return ServingEngine(
        model, params, num_slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
        cache_dtype="int8", mesh=mesh,
        int_lin=IntegerLinConfig(policy=policy, acc_bits=ACC_BITS,
                                 k_tile=K_TILE),
    )


def calibrate(engine, cfg, seed: int) -> None:
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + 1)
    toks = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    engine.calibrate([{"tokens": jnp.asarray(toks)}])


def serve(engine, reqs, label: str) -> list[list[int]]:
    """Drain ``reqs``; every request must finish with its full budget."""
    import jax.numpy as jnp

    tok = jnp.zeros((engine.num_slots, 1), jnp.int32)
    active = jnp.ones((engine.num_slots,), bool)
    require_compiled(engine._step, engine.params, tok, engine.caches, active)
    t0 = time.perf_counter()
    engine.drain(reqs)
    dt = time.perf_counter() - t0
    check(all(r.done and len(r.output) == r.max_new_tokens for r in reqs),
          f"{label}: a request did not finish with its token budget")
    st = engine.stats
    print(f"[serve] {label}: {len(reqs)} requests, "
          f"{sum(len(r.output) for r in reqs)} tokens in {dt:.1f}s "
          f"(compiles included), {st['prefill_steps']} prefill + "
          f"{st['decode_steps']} decode steps, peak pages "
          f"{st['pages_peak']}", flush=True)
    return [list(r.output) for r in reqs]


def phase_serve(cfg, seed: int) -> None:
    from repro.core import dispatch

    check(dispatch.default_backend() == "pallas",
          f"default pqs_dot backend is {dispatch.default_backend()!r}")
    model, params = build_quantized(cfg, seed)
    outs = {}
    for policy in ("wide", "sorted_tiled_seq"):
        engine = make_engine(model, params, policy)
        if policy == "wide":
            calibrate(engine, cfg, seed)
            params = engine.params  # act ranges frozen into the QTensors
        outs[policy] = serve(engine, make_requests(cfg, seed), policy)
        del engine
    a = np.asarray(outs["wide"])
    b = np.asarray(outs["sorted_tiled_seq"])
    print(f"[serve] greedy agreement wide vs sorted_tiled_seq: "
          f"{int((a == b).sum())}/{a.size} tokens", flush=True)


def phase_mesh(cfg, seed: int, k_widths=WIDTHS[1:]) -> None:
    """Meshed engine and K-sharded pqs_dot == their one-device results."""
    import jax

    from repro.core.dispatch import pqs_dot
    from repro.launch.mesh import make_host_serve_mesh

    mesh = make_host_serve_mesh()
    check(len({d.id for d in mesh.devices.flat}) == mesh.size == 4,
          f"serving mesh {dict(mesh.shape)} is not 4 distinct devices")
    model, params = build_quantized(cfg, seed)
    one = make_engine(model, params, "sorted_tiled_seq")
    calibrate(one, cfg, seed)
    params = one.params
    ref = serve(one, make_requests(cfg, seed), "one device")
    del one
    meshed = make_engine(model, params, "sorted_tiled_seq", mesh=mesh)
    got = serve(meshed, make_requests(cfg, seed),
                f"mesh {dict(mesh.shape)}")
    check(got == ref, "meshed engine tokens != one-device engine tokens")
    print("[mesh] engine tokens bit-identical to one device", flush=True)

    kmesh = make_host_serve_mesh(model_parallel=4)  # K on a 4-way axis
    key = jax.random.PRNGKey(seed)
    for k, n in k_widths:
        key, kx, kw = jax.random.split(key, 3)
        x, w = _int8(kx, (M_DECODE, k)), _int8(kw, (n, k))
        for policy in ("wide", "clip", "sorted_tiled_seq"):
            opts = dict(policy=policy, acc_bits=16, k_tile=K_TILE)
            want = pqs_dot(x, w, k_shards=4, **opts)
            out = pqs_dot(x, w, mesh=kmesh, k_axis="model", n_axis="data",
                          m_axes=(), **opts)
            check(len(out.sharding.device_set) == 4,
                  f"K-sharded {policy} ran on {out.sharding.device_set}")
            check(np.array_equal(np.asarray(out), np.asarray(want)),
                  f"K-sharded {policy} K={k} N={n}: mesh != k_shards=4")
            print(f"[mesh] pqs_dot k_axis 4-way {policy:16s} K={k} N={n} "
                  "bit-identical to k_shards=4", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the meshed paths, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {dev.platform!r}")
    check(len(jax.devices()) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(jax.devices())}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    before = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"[setup] {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache} ({before} entries)",
          flush=True)
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(cfg, args.seed))]
    else:
        phases = [("kernels", lambda: phase_kernels(args.seed)),
                  ("serve", lambda: phase_serve(cfg, args.seed))]
    for name, run in phases:
        tp = time.perf_counter()
        run()
        print(f"[phase] {name} {time.perf_counter() - tp:.1f}s (compiles "
              "included)", flush=True)
    after = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"[done] {time.perf_counter() - t0:.1f}s wall; compile cache "
          f"{before} -> {after} entries", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
