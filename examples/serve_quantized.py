"""End-to-end driver (the paper's kind = inference): serve a small LM with
batched requests under PQS int8 + N:M quantized weights.

Pipeline:
  1. build + briefly train a reduced qwen2-family LM on the synthetic
     token stream (so the weights are not random noise),
  2. P->Q: N:M-prune + quantize every large matrix to a QTensor
     (int8 values + per-channel scales) — the PQS storage format,
  3. serve a batch of requests through the continuous-batching engine in
     both fp32 and PQS form; compare outputs and report the bandwidth win,
  4. calibrate->freeze->serve: run the TRUE integer decode path
     (pqs_dot under an accumulation policy) with activation ranges
     frozen from a calibration pass — the paper's S2.1 static setup,
  5. run the overflow census on the LM head matmul to show the
     accumulator story end-to-end on a *model*, not a toy.

  PYTHONPATH=src python examples/serve_quantized.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.overflow import matmul_census
from repro.core.qtensor import QTensor, quantize_tree
from repro.core.quant import activation_qparams, quantize
from repro.data import TokenStream
from repro.models.model import build_model, param_count
from repro.optim import adamw
from repro.serving import Request, ServingEngine

cfg = get_config("qwen2-1.5b", smoke=True)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
print(f"[1] model {cfg.name}: {param_count(params):,} params")

# --- brief training so serving ops see trained statistics -------------------
opt = adamw(lr=1e-3)
opt_state = opt.init(params)
data = TokenStream(vocab_size=cfg.vocab_size, seq_len=64, batch_size=8)


@jax.jit
def step(params, opt_state, batch):
    loss, g = jax.value_and_grad(model.loss)(params, batch)
    params, opt_state = opt.update(g, opt_state, params)
    return params, opt_state, loss


t0 = time.time()
for i in range(60):
    batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
    params, opt_state, loss = step(params, opt_state, batch)
print(f"[2] trained 60 steps in {time.time()-t0:.1f}s, "
      f"final loss {float(loss):.3f}")

# --- PQS quantization ---------------------------------------------------
# int8-only (lossless-ish) for the serving comparison, and int8 + 8:16 N:M
# for the compression numbers. One-shot 50% pruning of a briefly-trained
# model without the P->Q fine-tuning phase is intentionally aggressive —
# launch/train.py runs the full schedule when accuracy matters.
qparams = quantize_tree(params, bits=8, min_size=1 << 12, min_dim=16)
qparams_nm = quantize_tree(params, bits=8, n_keep=8, m=16,
                           min_size=1 << 12, min_dim=16)
n_q = sum(isinstance(x, QTensor)
          for x in jax.tree_util.tree_leaves(
              qparams, is_leaf=lambda l: isinstance(l, QTensor)))
fp_bytes = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(params))
q_bytes = sum(
    (a.size if a.dtype == jnp.int8 else a.size * a.dtype.itemsize)
    for a in jax.tree_util.tree_leaves(qparams_nm))
print(f"[3] PQS-quantized {n_q} matrices; "
      f"param bytes {fp_bytes:,} -> {q_bytes:,} "
      f"({fp_bytes/q_bytes:.1f}x smaller before N:M packing; 8:16 zeros "
      f"compress a further 2x via kernels/nm_spmm)")

# --- serve the same requests through both ------------------------------------
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
           for _ in range(6)]


def serve(p):
    eng = ServingEngine(model, p, num_slots=3, max_len=64)
    reqs = [Request(uid=i, prompt=pr, max_new_tokens=12)
            for i, pr in enumerate(prompts)]
    t0 = time.time()
    eng.drain(reqs)
    return reqs, time.time() - t0


fp_reqs, fp_t = serve(params)
q_reqs, q_t = serve(qparams)
qnm_reqs, _ = serve(qparams_nm)


def agreement(a_reqs, b_reqs):
    return 100 * np.mean([
        np.mean(np.asarray(a.output) == np.asarray(b.output))
        for a, b in zip(a_reqs, b_reqs)
    ])


print(f"[4] served {len(prompts)} requests: fp32 {fp_t:.1f}s, "
      f"PQS-int8 {q_t:.1f}s; greedy agreement int8 "
      f"{agreement(fp_reqs, q_reqs):.1f}%, int8+8:16-one-shot "
      f"{agreement(fp_reqs, qnm_reqs):.1f}% (no P->Q fine-tune)")
print(f"    sample fp32: {fp_reqs[0].output}")
print(f"    sample pqs : {q_reqs[0].output}")

# --- calibrate -> freeze -> serve (true integer decode) ----------------------
from repro.core.dispatch import IntegerLinConfig  # noqa: E402

int_eng = ServingEngine(
    model, qparams, num_slots=3, max_len=64,
    int_lin=IntegerLinConfig(policy="sorted_tiled_seq", acc_bits=24,
                             k_tile=64),
)
frozen = int_eng.calibrate(
    [{k: jnp.asarray(v) for k, v in data.next_batch().items()}
     for _ in range(4)]
)
int_reqs = [Request(uid=i, prompt=pr, max_new_tokens=12)
            for i, pr in enumerate(prompts)]
int_eng.drain(int_reqs)
print(f"[4b] integer decode (sorted_tiled_seq @ 24b, calibrated static "
      f"ranges over {len(frozen)} sites): greedy agreement vs fp32 "
      f"{agreement(fp_reqs, int_reqs):.1f}%; "
      f"{int_eng.stats['prefill_steps']} batched prefill steps for "
      f"{int_eng.stats['cohorts']} admission cohorts")

# --- accumulator census on the real LM head ----------------------------------
head = qparams_nm["embed"]  # tied head, QTensor (V, d) -> dot length d
x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
x_qp = activation_qparams(jnp.min(x), jnp.max(x), 8)
xq = quantize(x, x_qp)
for bits in (14, 16, 18):
    c = matmul_census(head.values.astype(jnp.int32), xq, acc_bits=bits)
    print(f"[5] LM-head dots @ {bits}b: {int(c.n_persistent)} persistent, "
          f"{int(c.n_transient)} transient of {int(c.n_dots)} "
          f"(sorted accumulation removes the transient share)")
