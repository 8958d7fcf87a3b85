"""From a profiler trace to the events the per-layer readers take.

A traced run records the window with ``jax.profiler``; ``load`` reads the
``.xplane.pb`` it wrote and keeps, clipped to the harness's ``window``
span: the device's operations (the ``XLA Ops`` line of each TPU plane),
its program executions (``XLA Modules``), and the host spans the harness
wrote (``TraceAnnotation``) on the Python thread. All three share the
trace's clock. ``Trace.to_json`` / ``from_json`` keep a small recorded
trace as a test fixture.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Optional

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("window", "step", "submit", "sleep")


@dataclasses.dataclass
class Events:
    """Events of one kind: names (a device operation's name is its HLO
    instruction, shapes included), start and end in seconds, chip."""

    name: list
    start: np.ndarray
    end: np.ndarray
    device: np.ndarray  # chip index (0 for host spans)

    def select(self, keep: np.ndarray) -> "Events":
        return Events([n for n, k in zip(self.name, keep) if k],
                      self.start[keep], self.end[keep], self.device[keep])

    def named(self, pred) -> "Events":
        return self.select(np.asarray([bool(pred(n)) for n in self.name],
                                      bool))

    def dur(self) -> np.ndarray:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]
    chips: int
    ops: Events
    modules: Events
    host: Events

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    # -- fixture form -------------------------------------------------------

    def to_json(self) -> dict:
        def ev(e: Events):
            return {"name": e.name, "start": e.start.tolist(),
                    "end": e.end.tolist(), "device": e.device.tolist()}
        return {"window": list(self.window), "chips": self.chips,
                "ops": ev(self.ops), "modules": ev(self.modules),
                "host": ev(self.host)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def ev(x):
            return Events(list(x["name"]), np.asarray(x["start"], float),
                          np.asarray(x["end"], float),
                          np.asarray(x["device"], int))
        return cls(tuple(d["window"]), d["chips"], ev(d["ops"]),
                   ev(d["modules"]), ev(d["host"]))


def _collect(line, device: int, into: dict, keep=None) -> None:
    for e in line.events:
        if keep is None or e.name in keep:
            into["name"].append(e.name)
            into["start"].append(e.start_ns * 1e-9)
            into["end"].append((e.start_ns + e.duration_ns) * 1e-9)
            into["device"].append(device)


def _events(d: dict) -> Events:
    return Events(d["name"], np.asarray(d["start"], float),
                  np.asarray(d["end"], float), np.asarray(d["device"], int))


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``; clip everything to the ``window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    new = lambda: {"name": [], "start": [], "end": [], "device": []}  # noqa
    ops, modules, host = new(), new(), new()
    chips = set()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chips.add(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    _collect(line, int(m.group(1)), ops)
                elif line.name == MODULES_LINE:
                    _collect(line, int(m.group(1)), modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                _collect(line, 0, host, HOST_SPANS)
    host_ev = _events(host)
    win = host_ev.named(lambda n: n == "window")
    if not len(win.name):
        raise ValueError("the trace holds no 'window' span")
    window = (float(win.start[0]), float(win.end[0]))
    return clip(Trace(window, max(len(chips), 1), _events(ops),
                      _events(modules), host_ev))


def clip(t: Trace) -> Trace:
    lo, hi = t.window

    def c(e: Events) -> Events:
        keep = (e.end > lo) & (e.start < hi)
        e = e.select(keep)
        return Events(e.name, np.maximum(e.start, lo), np.minimum(e.end, hi),
                      e.device)
    return Trace(t.window, t.chips, c(t.ops), c(t.modules), c(t.host))


def union_s(start: np.ndarray, end: np.ndarray) -> float:
    """Length of the union of intervals."""
    if not len(start):
        return 0.0
    order = np.argsort(start)
    s, e = start[order], end[order]
    total, cur_s, cur_e = 0.0, s[0], e[0]
    for a, b in zip(s[1:], e[1:]):
        if a > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = a, b
        else:
            cur_e = max(cur_e, b)
    return float(total + cur_e - cur_s)


def busy_s(t: Trace) -> float:
    """Seconds in which some operation ran on a chip, averaged over chips."""
    per = [union_s(t.ops.start[t.ops.device == d], t.ops.end[t.ops.device == d])
           for d in sorted(set(t.ops.device.tolist()))]
    return float(sum(per) / t.chips) if per else 0.0


def leaves(e: Events) -> Events:
    """The operations that hold no other: a loop (``%while``) is an
    operation of its own on the trace, spanning the operations of its
    body."""
    keep = np.ones(len(e.name), bool)
    for d in set(e.device.tolist()):
        idx = np.flatnonzero(e.device == d)
        stack: list[int] = []
        for j in idx[np.lexsort((-e.end[idx], e.start[idx]))]:
            while stack and e.start[j] >= e.end[stack[-1]]:
                stack.pop()
            if stack and e.end[j] > e.start[j]:
                keep[stack[-1]] = False
            stack.append(j)
    return e.select(keep)


def top_ops(t: Trace, n: int = 10) -> list:
    """[name, seconds] of the device operations (leaves) that took most
    time, each name cut to the HLO instruction's left-hand side."""
    tot: dict[str, float] = {}
    ops = leaves(t.ops)
    for name, d in zip(ops.name, ops.dur()):
        lhs, eq, rhs = name.partition(" = ")
        short = lhs + eq + rhs.split("{")[0] if eq else name
        tot[short] = tot.get(short, 0.0) + float(d)
    return sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]


def idle_gaps(t: Trace, n: int = 10, device: int = 0) -> list:
    """[host span, seconds] of the longest gaps with no device operation,
    each named by the innermost harness span that covers its middle."""
    sel = t.ops.device == device
    s, e = t.ops.start[sel], t.ops.end[sel]
    order = np.argsort(s)
    s, e = s[order], e[order]
    edges, cur = [], t.window[0]
    for a, b in zip(s, e):
        if a > cur:
            edges.append((cur, a))
        cur = max(cur, b)
    if t.window[1] > cur:
        edges.append((cur, t.window[1]))
    edges.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in edges[:n]:
        out.append([host_span_at(t, (a + b) / 2), float(b - a)])
    return out


def host_span_at(t: Trace, when: float) -> str:
    best: Optional[tuple[float, str]] = None
    for name, a, b in zip(t.host.name, t.host.start, t.host.end):
        if a <= when <= b and (best is None or b - a < best[0]):
            best = (b - a, name)
    return best[1] if best else "none"
