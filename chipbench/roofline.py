"""Operations and bytes of the work, from shapes alone, and the chip's peaks.

Counted as the algorithm needs them: a projection call of an int8
activation block (M, K) with an int8 weight (K, N) does 2*M*K*N integer
operations and reads M*K + K*N bytes and writes 4*M*N (int32 out). A
model step's operations count every projection and the logits (2 per
weight per token) plus attention's two contractions against the context
each token sees (2 * 2 * context * heads * head_dim per layer).
"""

from __future__ import annotations

import json
import re
from pathlib import Path

from chipbench.weights import Dims

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def projection(m: int, k: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one int8 projection call."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * m * n)


def least_time(ops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """Seconds the chip needs at best, and which bound sets it."""
    t_ops = ops / pk["int8_ops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def weights_per_token(dims: Dims, logits: bool) -> int:
    """Projection weights one token multiplies through (and the logits'
    when it is ranked)."""
    per_layer = sum(i * o for i, o in dims.projections().values())
    return dims.layers * per_layer + (dims.d * dims.vocab if logits else 0)


def token_ops(dims: Dims, context: int, logits: bool) -> float:
    """Operations one token needs, seeing ``context`` positions."""
    attn = 4.0 * context * dims.heads * dims.head_dim * dims.layers
    return 2.0 * weights_per_token(dims, logits) + attn


def prefill_ops(dims: Dims, length: int) -> float:
    """A prompt of ``length`` tokens consumed into the cache (causal: token
    t sees t + 1 positions; no logits)."""
    seen = length * (length + 1) / 2
    return (2.0 * weights_per_token(dims, False) * length
            + 4.0 * seen * dims.heads * dims.head_dim * dims.layers)


_ARRAY = re.compile(r"\b(s8|u8|s32|u32|f32|bf16|s16)\[([\d,]*)\]")
_BYTES = {"s8": 1, "u8": 1, "s16": 2, "bf16": 2, "s32": 4, "u32": 4,
          "f32": 4}


def call_from_hlo(instruction: str):
    """(operations, bytes) of one kernel call, from its HLO instruction
    as the trace names it: ``%k = s32[M,N]{..} custom-call(s8[M,K]{..}
    %x, <weight operands>), ...``. Operations are 2*M*K*N; bytes are every
    operand read and the result written, as launched. None when the
    instruction has no such shapes."""
    lhs, _, rhs = instruction.partition(" = ")
    call = rhs.split("custom-call(", 1)
    if len(call) != 2:
        return None
    out = _ARRAY.findall(call[0])
    args = _ARRAY.findall(call[1].split("), ", 1)[0])
    if len(out) != 1 or not args:
        return None

    def dims(a):
        return [int(v) for v in a[1].split(",") if v]

    def size(a):
        n = 1
        for v in dims(a):
            n *= v
        return n * _BYTES[a[0]]
    (m, n), (m2, k) = dims(out[0]), dims(args[0])[:2]
    if m != m2:
        return None
    return 2.0 * m * k * n, float(size(out[0]) + sum(map(size, args)))
