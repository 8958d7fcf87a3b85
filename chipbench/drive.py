"""Drives the serving engine through one run: set-up, window, drain.

Times are the host's clock at ``ServingEngine.step()`` boundaries; a
step returns only after its logits reached the host, so a token's time
is when the client could have it. An open loop times each request from
when it was due, not from when the driver got round to submitting it.
Each call into the engine, and the driver's sleep, is wrapped in a
``TraceAnnotation`` so that a traced run can say what the host was doing
while the device sat idle.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from chipbench.traffic import Spec, prefill_buckets


@dataclasses.dataclass
class Record:
    spec: Spec
    req: object  # the engine's Request
    due: float = 0.0  # host clock
    submitted: float = 0.0
    admitted: Optional[float] = None
    times: list = dataclasses.field(default_factory=list)  # per token


class Driver:
    def __init__(self, engine, specs: list[Spec], loop: str, make_request):
        self.engine = engine
        self.specs = specs
        self.loop = loop
        self.make_request = make_request
        self.records: list[Record] = []
        self.step_ms: list[float] = []  # host time of each step() call
        self.t0 = self.t_end = 0.0

    # -- engine calls -------------------------------------------------------

    def _submit(self, spec: Spec, due: float) -> Record:
        req = self.make_request(uid=spec.uid, prompt=spec.prompt,
                                max_new_tokens=spec.max_new)
        rec = Record(spec, req, due=due)
        with TraceAnnotation("submit"):
            self.engine.submit(req)
        rec.submitted = time.perf_counter()
        self.records.append(rec)
        return rec

    def _step(self, live: list[Record]) -> None:
        queued = {id(r) for r in self.engine.queue}
        t = time.perf_counter()
        with TraceAnnotation("step"):
            self.engine.step()
        now = time.perf_counter()
        self.step_ms.append((now - t) * 1e3)
        still = {id(r) for r in self.engine.queue}
        for rec in live:
            if rec.admitted is None and id(rec.req) in queued - still:
                rec.admitted = t
            new = len(rec.req.output) - len(rec.times)
            rec.times.extend([now] * new)

    def _busy(self) -> bool:
        return bool(self.engine.queue) or any(
            s is not None for s in self.engine.slots)

    # -- phases -------------------------------------------------------------

    def warm(self, vocab: int, switch=None) -> None:
        """Compile and run every shape the window will use, once.

        Closed loop: the clients' requests are submitted here; their one
        prefill cohort builds every context, and a first decode step
        runs, which is the set-up the traffic itself needs. ``switch``,
        where given, then changes the engine's accumulation setting, and
        one more decode step runs under the new one. Open loop: one
        short request per prefill bucket that the mix reaches, each run
        to completion."""
        if self.loop == "closed":
            for spec in self.specs:
                rec = self._submit(spec, time.perf_counter())
                rec.admitted = rec.submitted
            self._step(self.records)
            if switch is not None:
                switch()
                self._step(self.records)
            return
        if switch is not None:
            raise ValueError("an open loop prefills in its window: it "
                             "cannot build its contexts under another "
                             "accumulation setting")
        for j, b in enumerate(prefill_buckets(self.specs)):
            prompt = np.arange(b + 1, dtype=np.int32) % vocab
            req = self.make_request(uid=(1 << 40) + j, prompt=prompt,
                                    max_new_tokens=2)
            self.engine.drain([req])
            if not req.done:
                raise RuntimeError(f"warm-up request of bucket {b} did not "
                                   "finish")

    def window(self, seconds: float) -> None:
        """Serve for ``seconds``, closing at the first step boundary after.

        Open loop: requests are submitted as they fall due, between steps;
        when the engine has nothing to do the driver sleeps to the next
        arrival."""
        live = list(self.records)
        steps_before = len(self.step_ms)
        self.t0 = time.perf_counter()
        end = self.t0 + seconds
        pending = sorted(self.specs, key=lambda s: s.due) \
            if self.loop == "open" else []
        i = 0
        with TraceAnnotation("window"):
            while True:
                now = time.perf_counter()
                if now >= end:
                    break
                while i < len(pending) and self.t0 + pending[i].due <= now:
                    live.append(self._submit(pending[i],
                                             self.t0 + pending[i].due))
                    i += 1
                if self._busy():
                    self._step(live)
                    live = [r for r in live if not r.req.done]
                elif i < len(pending):
                    with TraceAnnotation("sleep"):
                        time.sleep(max(0.0, min(
                            self.t0 + pending[i].due, end) - now))
                else:
                    with TraceAnnotation("sleep"):
                        time.sleep(max(0.0, end - now))
        self.t_end = time.perf_counter()
        self.window_step_ms = self.step_ms[steps_before:]
        self.queued_at_close = len(self.engine.queue)
        self.unfinished_at_close = sum(not r.req.done for r in self.records)

    def drain(self, cap_s: float) -> None:
        """After the window: no new arrivals; step until every request sent
        has finished, for at most ``cap_s`` seconds (open loop only: a
        closed loop's budgets run past the window by design)."""
        if self.loop != "open":
            return
        stop = time.perf_counter() + cap_s
        live = [r for r in self.records if not r.req.done]
        while live and time.perf_counter() < stop:
            self._step(live)
            live = [r for r in live if not r.req.done]

    # -- what the window shows ----------------------------------------------

    def in_window(self, t: float) -> bool:
        return self.t0 < t <= self.t_end

    def window_s(self) -> float:
        return self.t_end - self.t0

    def tokens_in_window(self) -> int:
        return sum(self.in_window(t) for r in self.records for t in r.times)

    def ttft_ms(self) -> list[float]:
        """Due to first token, for every request sent in the window."""
        return [(r.times[0] - r.due) * 1e3 for r in self.records
                if r.times and r.due >= self.t0]

    def itl_ms(self) -> list[float]:
        """Gaps between consecutive tokens of a request, both emitted in
        the window.

        A closed loop's last set-up token comes before the window opens,
        so the gap from it to the first token of the window spans the end
        of set-up, not a decode step: it is left out. An open loop sends
        every request inside the window, so it loses none of its gaps."""
        return [(b - a) * 1e3 for r in self.records
                for a, b in zip(r.times, r.times[1:])
                if self.in_window(a) and self.in_window(b)]

    def queue_wait_ms(self) -> list[float]:
        return [(r.admitted - r.due) * 1e3 for r in self.records
                if r.admitted is not None and r.due >= self.t0
                and r.admitted <= self.t_end]

    def ttft_ms_by_third(self) -> list[float]:
        """Median time to first token of the requests due in each third of
        the window: a backlog that grows through the window shows as a
        rising sequence."""
        out = []
        for i in range(3):
            lo = self.t0 + i * self.window_s() / 3
            hi = lo + self.window_s() / 3
            t = [(r.times[0] - r.due) * 1e3 for r in self.records
                 if r.times and lo <= r.due < hi]
            out.append(float(np.median(t)) if t else None)
        return out

    def lateness_ms(self) -> list[float]:
        """How late the driver submitted each request of the window."""
        return [(r.submitted - r.due) * 1e3 for r in self.records
                if r.due >= self.t0]

    def failed(self) -> list[Record]:
        """Open loop: sent and not finished by the end of the drain."""
        if self.loop != "open":
            return []
        return [r for r in self.records if not r.req.done]
