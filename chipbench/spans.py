"""The program's own spans and named scopes, read from a profiler trace.

``trace.load`` keeps what the harness annotates (``window``, ``step``,
``submit``, ``sleep``) and names each device operation by its HLO
instruction. ``load`` here reads the same ``.xplane.pb`` into the same
``Trace`` and adds what the program writes into it:

- the host spans of ``ServingEngine.step`` (every name that starts with
  ``engine.``), each with its stats: ``step`` on all of them, ``rows`` on
  ``engine.step`` and ``engine.prefill``, ``uids`` on an ``engine.admit``
  that admitted requests;
- for each device operation, the innermost named scope of the code that
  traced it (``SCOPES``, or ``pqs_dot.<policy>`` for a policy kernel).
  A TPU's operation events carry no op_name: the scope is looked up, by
  module and instruction name, in the compiled modules the profiler
  writes when ``ProfileOptions.enable_hlo_proto`` is on (the
  ``/host:metadata`` plane, which ``ProfileData`` does not expose, so it
  is decoded here from the protobuf's wire format). Without them every
  scope reads "".

On that view ``trace.idle_gaps`` names each gap by the engine's phase,
and the readers below split a decode step's host time by phase and its
device time by scope.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

from chipbench import trace as trace_lib

ENGINE = "engine."
# the named scopes of the model's jitted steps (models/transformer.py,
# models/model.py, serving/engine.py); core/dispatch.pqs_dot adds
# "pqs_dot.<policy>" around each policy kernel
SCOPES = ("embed", "attn", "mlp", "head", "merge", "layers", "cast")
DECODE_MODULE = "jit_step"
_HLO_OP = re.compile(r"^%?([\w.\-]+) = ")


@dataclasses.dataclass
class Spans:
    """A ``trace.Trace`` whose host events include the engine's spans,
    with the stats of each host event and the scope of each operation."""

    trace: trace_lib.Trace
    host_stats: list  # dict per host event
    op_scope: list  # str per device operation ("" where none)

    def to_json(self) -> dict:
        d = self.trace.to_json()
        d["host_stats"] = self.host_stats
        d["op_scope"] = self.op_scope
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Spans":
        return cls(trace_lib.Trace.from_json(d), list(d["host_stats"]),
                   list(d["op_scope"]))


def innermost_scope(op_name: str) -> str:
    """The innermost scope of ``SCOPES`` or ``pqs_dot.*`` on an op_name
    such as ``jit(step)/layers/while/body/closed_call/attn/pqs_dot.
    sorted_tiled_seq/jit(seq_policy_matmul)/pallas_call``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES or part.startswith("pqs_dot."):
            return part
    return ""


def hlo_op_names(hlo_text: str) -> dict:
    """``{instruction: op_name}`` of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line.strip().removeprefix("ROOT "))
        if m:
            on = re.search(r'op_name="([^"]*)"', line)
            if on:
                out[m.group(1)] = on.group(1)
    return out


# -- the compiled modules in the .xplane.pb ----------------------------------
# field numbers: tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto,
# xla/xla_data.proto (OpMetadata)
_XSPACE_PLANES, _XPLANE_NAME, _XPLANE_EVENT_METADATA = 1, 2, 4
_XPLANE_STAT_METADATA, _MAP_VALUE, _XSTATMETA_ID, _XSTATMETA_NAME = 5, 2, 1, 2
_XMETA_NAME, _XMETA_STATS, _XSTAT_METADATA_ID, _XSTAT_BYTES = 2, 5, 1, 6
_HLO_MODULE, _MODULE_COMPUTATIONS, _COMPUTATION_INSTRUCTIONS = 1, 3, 2
_INSTR_NAME, _INSTR_METADATA, _METADATA_OP_NAME = 1, 7, 2
_METADATA_PLANE, _HLO_STAT = "/host:metadata", "Hlo Proto"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``: an
    int for a varint, a (start, end) slice for a length-delimited field,
    None for a fixed-width one."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            yield key >> 3, None
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _sub(buf: bytes, span, field: int) -> list:
    return [v for f, v in _fields(buf, *span) if f == field]


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode()


def module_op_names(xplane: bytes) -> dict:
    """``{module: {instruction: op_name}}`` of every compiled module the
    profiler wrote into the trace; the module is named as on the trace's
    XLA Modules line (``jit_step(<fingerprint>)``)."""
    out: dict = {}
    for plane in _sub(xplane, (0, len(xplane)), _XSPACE_PLANES):
        names = _sub(xplane, plane, _XPLANE_NAME)
        if not names or _text(xplane, names[0]) != _METADATA_PLANE:
            continue
        stat_ids = set()
        for entry in _sub(xplane, plane, _XPLANE_STAT_METADATA):
            for meta in _sub(xplane, entry, _MAP_VALUE):
                fs = dict(_fields(xplane, *meta))
                if (_XSTATMETA_NAME in fs
                        and _text(xplane, fs[_XSTATMETA_NAME]) == _HLO_STAT):
                    stat_ids.add(fs[_XSTATMETA_ID])
        for entry in _sub(xplane, plane, _XPLANE_EVENT_METADATA):
            for meta in _sub(xplane, entry, _MAP_VALUE):
                name = _text(xplane, _sub(xplane, meta, _XMETA_NAME)[0])
                for stat in _sub(xplane, meta, _XMETA_STATS):
                    fs = dict(_fields(xplane, *stat))
                    if fs.get(_XSTAT_METADATA_ID) in stat_ids:
                        out[name] = _hlo_op_names(xplane, fs[_XSTAT_BYTES])
    return out


def _hlo_op_names(buf: bytes, hlo_proto) -> dict:
    out = {}
    for module in _sub(buf, hlo_proto, _HLO_MODULE):
        for comp in _sub(buf, module, _MODULE_COMPUTATIONS):
            for instr in _sub(buf, comp, _COMPUTATION_INSTRUCTIONS):
                name = meta = None
                for f, v in _fields(buf, *instr):
                    if f == _INSTR_NAME:
                        name = _text(buf, v)
                    elif f == _INSTR_METADATA:
                        meta = v
                if name and meta:
                    for on in _sub(buf, meta, _METADATA_OP_NAME):
                        out[name] = _text(buf, on)
    return out


# -- loading -----------------------------------------------------------------


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def load(path: str) -> Spans:
    """Read one ``.xplane.pb``: the harness's ``Trace`` with the engine's
    spans added to its host events and each operation's scope, clipped to
    the ``window`` span."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    new = lambda: {"name": [], "start": [], "end": [], "device": []}  # noqa
    ops, modules, host = new(), new(), new()
    host_stats = []
    chips = set()

    for plane in pd.planes:
        m = trace_lib.DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            chips.add(chip)
            for line in plane.lines:
                if line.name == trace_lib.OPS_LINE:
                    trace_lib._collect(line, chip, ops)
                elif line.name == trace_lib.MODULES_LINE:
                    trace_lib._collect(line, chip, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                keep = {e.name for e in line.events
                        if e.name in trace_lib.HOST_SPANS
                        or e.name.startswith(ENGINE)}
                trace_lib._collect(line, 0, host, keep)
                host_stats += [_stats(e) for e in line.events
                               if e.name in keep]
    if "window" not in host["name"]:
        raise ValueError("the trace holds no 'window' span")
    w = host["name"].index("window")
    t = trace_lib.Trace((host["start"][w], host["end"][w]),
                        max(len(chips), 1), trace_lib._events(ops),
                        trace_lib._events(modules), trace_lib._events(host))
    return clip(Spans(t, host_stats, op_scopes(t, module_op_names(raw))))


def module_of(t: trace_lib.Trace) -> np.ndarray:
    """For each operation of ``t``, the index of the module execution it
    started in (on its chip), or -1."""
    out = np.full(len(t.ops.name), -1)
    for d in set(t.modules.device.tolist()):
        mods = np.flatnonzero(t.modules.device == d)
        mods = mods[np.argsort(t.modules.start[mods])]
        here = np.flatnonzero(t.ops.device == d)
        k = np.searchsorted(t.modules.start[mods], t.ops.start[here],
                            side="right") - 1
        inside = (k >= 0) & (t.ops.start[here]
                             < t.modules.end[mods[np.maximum(k, 0)]])
        out[here[inside]] = mods[k[inside]]
    return out


def op_scopes(t: trace_lib.Trace, names: dict) -> list:
    """The innermost scope of each operation of ``t``, from the op_names
    of the module it ran in (``module_op_names``)."""
    out = []
    for name, k in zip(t.ops.name, module_of(t)):
        m = _HLO_OP.match(name)
        op_name = ""
        if k >= 0 and m:
            op_name = names.get(t.modules.name[k], {}).get(m.group(1), "")
        out.append(innermost_scope(op_name))
    return out


def clip(sp: Spans) -> Spans:
    """``trace.clip``, keeping each event's stats and scope beside it."""
    lo, hi = sp.trace.window
    t = sp.trace

    def keep(e):
        return (e.end > lo) & (e.start < hi)
    kh, ko = keep(t.host), keep(t.ops)
    return Spans(trace_lib.clip(t),
                 [s for s, k in zip(sp.host_stats, kh) if k],
                 [s for s, k in zip(sp.op_scope, ko) if k])


# -- readers -----------------------------------------------------------------


def decoding_steps(sp: Spans) -> list:
    """Each ``engine.step`` of the window that decoded (it holds an
    ``engine.dispatch``): ``{"step", "start", "end", <phase>: (start,
    end), ...}`` in order."""
    h = sp.trace.host
    steps: dict = {}
    for name, a, b, st in zip(h.name, h.start, h.end, sp.host_stats):
        if name.startswith(ENGINE) and "step" in st:
            s = steps.setdefault(st["step"], {"step": st["step"]})
            if name == "engine.step":
                s["start"], s["end"] = float(a), float(b)
            else:
                s[name] = (float(a), float(b))
    return [s for _, s in sorted(steps.items())
            if "start" in s and "engine.dispatch" in s]


def host_ms(sp: Spans) -> Optional[float]:
    """Mean host time of a decoding step, in ms: the ``engine.step`` span
    less its ``engine.fetch``, the wait for the device and the copy of
    the logits."""
    steps = decoding_steps(sp)
    if not steps:
        return None
    return 1e3 * float(np.mean([
        (s["end"] - s["start"]) - (s["engine.fetch"][1] - s["engine.fetch"][0])
        for s in steps]))


def sample_ms(sp: Spans) -> Optional[float]:
    """Mean ``engine.sample`` span of a decoding step, in ms."""
    steps = decoding_steps(sp)
    if not steps:
        return None
    return 1e3 * float(np.mean([s["engine.sample"][1] - s["engine.sample"][0]
                                for s in steps]))


def scope_ms(sp: Spans, scope: str,
             module: str = DECODE_MODULE) -> Optional[float]:
    """Device time per execution of ``module`` of the leaf operations run
    in it whose innermost scope is ``scope``, in ms. An operation under
    both ``attn`` and ``pqs_dot.*`` is kernel time, not ``attn``'s. None
    without an execution of the module, or where no operation has a scope
    (a trace without the compiled modules, or of a program without
    scopes)."""
    t = sp.trace
    runs = [n.split("(")[0] == module for n in t.modules.name]
    if not any(runs) or not any(sp.op_scope):
        return None
    idx = np.arange(len(t.ops.name))
    leaf = trace_lib.leaves(dataclasses.replace(t.ops, name=list(idx))).name
    k = module_of(t)
    total = sum(float(t.ops.end[i] - t.ops.start[i]) for i in leaf
                if sp.op_scope[i] == scope and k[i] >= 0 and runs[k[i]])
    return 1e3 * total / sum(runs)
