"""Batched serving engine: slot-based continuous batching over decode steps.

The engine owns a batch of ``num_slots`` sequence slots backed by one
batched KV/SSM cache pytree (batch = slot axis). Requests are admitted
into free slots, prefilled, then advanced together by a single jitted
decode step per token — the slot axis stays fully batched no matter how
requests arrive/finish (continuous batching). Finished slots are freed and
refilled from the queue.

Cache layouts:
  dense (default)           one (B, max_len, ...) lane per slot
  paged (``page_size=``)    KV/SSM state in shared page pools with
                            per-slot page tables (serving/paged_cache.py):
                            pages allocate lazily as sequences grow, free
                            on completion, and admission applies
                            *backpressure* (request waits in queue) when
                            the pool cannot cover a request's worst case —
                            never a mid-decode allocation failure, because
                            admission reserves the worst-case page count
                            up front. ``cache_dtype="int8"`` (paged only)
                            stores KV pages as int8 with per-position,
                            per-kv-head scales; SSM/conv state stays float.

Prefill is ONE jitted batched step per admission cohort
(``Model.prefill``): every admitted slot's whole prompt (minus the
held-back final token) is consumed in a single full-sequence pass that
scatters per-layer K/V (or runs the length-masked SSD recurrence) into
the slot cache lanes — across all architecture families (attention KV,
SSM state, hybrid, cross-attn). Prompt lengths are padded to power-of-
two buckets so recompiles stay bounded. ``prefill_mode="steps"`` keeps
the legacy token-by-token path (the parity oracle in tests).

Admission interleaving: by default (``prefill_decode_ratio=0``) admitted
requests prefill immediately, as before. With ratio N > 0, admitted
slots wait in a pending list and one batched prefill micro-step runs per
N decode steps, so a long prompt arriving mid-stream does not stall
every in-flight decode. ``_admit`` also skip-scans the queue (bounded by
``admit_lookahead``) past requests too long for the *remaining* page
budget, so one long request cannot head-of-line-block shorter ones;
skips and queue wait are counted in ``stats``.

Slot isolation: every jitted step takes an ``active`` (B,) mask and
merges caches through ``model.merge_caches``, so inactive slots' cache
lanes — and, on the paged path, the pool pages their tables own — are
bit-identical before and after the step. Decode results therefore do not
depend on which other requests happen to share the batch — greedy decode
of a prompt is reproducible under any slot occupancy.

Sampling: greedy or temperature; the temperature path draws from a
per-request generator seeded by ``(engine seed, request uid)``, so a
request's sampled continuation is reproducible regardless of batch
composition or admission order.

Long-K layers can opt into hierarchical K-sharded accumulation:
``int_lin=IntegerLinConfig(k_shards=S, k_shard_min_k=...)`` routes every
QTensor projection whose contraction dim reaches the threshold through
the per-shard-partials + tree-combine ``pqs_dot`` path (shorter
projections keep the bit-identical full-K path); with a serving mesh,
``k_axis=`` names the mesh axis the K shards live on — pair it with
``launch.sharding.params_shardings(..., k_axis=, k_shard_min_k=)`` so
the weight shards are already resident where the dot needs them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import pickle
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import dispatch
from repro.models.model import Model
from repro.serving import paged_cache

logger = logging.getLogger("repro.serving")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    # filled by the engine
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    failed: bool = False  # fleet gave up (deadline retries exhausted)
    t_submit: float = 0.0  # wall clock at submit()
    t_done: float = 0.0  # wall clock when the request finished


@dataclasses.dataclass(frozen=True)
class CensusWatch:
    """Census-triggered graceful degradation knobs.

    Every ``window`` decode steps the engine reads the per-site overflow
    census rates accumulated since the last check. A site whose
    event/dot ratio exceeds ``threshold`` (with at least ``min_dots``
    dots observed — tiny windows don't trigger) is hot-swapped:
    ``mode="wide"`` flips that site's policy to the overflow-free wide
    accumulator, ``mode="widen"`` raises its ``acc_bits`` to
    ``widen_to``. Either way the rest of the model keeps its narrow
    policies, a structured event is appended to ``engine.events``, and
    ``stats["census_degrades"]`` counts.

    By default degradation is monotone — a site never narrows back
    within an engine's lifetime (re-calibration is the undo, not a rate
    dip). ``undegrade_after=N`` makes it reversible: a degraded site
    whose census stays clean (rate <= threshold over >= min_dots dots)
    for N *consecutive* windows drops its overrides and returns to the
    engine-wide narrow config — logged as a ``census_undegrade`` event,
    counted in ``stats["census_undegrades"]``, and, like the overrides
    themselves, surviving snapshot/restore (a snapshot taken after the
    removal never resurrects the override). A dirty window resets the
    streak; windows with fewer than ``min_dots`` observed dots neither
    advance nor reset it.
    """

    threshold: float = 0.01
    window: int = 8
    mode: str = "wide"  # "wide" (policy swap) | "widen" (acc_bits raise)
    widen_to: int = 30
    min_dots: int = 1
    undegrade_after: Optional[int] = None  # N clean windows to re-narrow


class ServingEngine:
    def __init__(
        self,
        model: Model,
        params: Any,
        num_slots: int = 8,
        max_len: int = 512,
        cache_dtype=jnp.float32,
        seed: int = 0,
        int_lin: Optional["dispatch.IntegerLinConfig"] = None,
        mesh=None,
        prefill_mode: str = "batched",
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        prefill_decode_ratio: int = 0,
        admit_lookahead: int = 8,
        failure_injector: Optional[Any] = None,
        census_watch: Optional[CensusWatch] = None,
    ):
        if prefill_mode not in ("batched", "steps"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if census_watch is not None and int_lin is None:
            raise ValueError(
                "census_watch monitors integer projections — it needs "
                "int_lin= (float engines have no overflow census)"
            )
        if int_lin is not None:
            # K-sharded integer projections need a coherent (k_shards,
            # k_axis, mesh) triple before any step traces — fail at
            # construction, not on the first decode
            if int_lin.k_axis is not None:
                if mesh is None:
                    raise ValueError(
                        f"int_lin.k_axis={int_lin.k_axis!r} needs a "
                        "serving mesh (ServingEngine(..., mesh=...))"
                    )
                if int_lin.k_axis not in mesh.axis_names:
                    raise ValueError(
                        f"int_lin.k_axis={int_lin.k_axis!r} is not an "
                        f"axis of the serving mesh {mesh.axis_names}"
                    )
            elif int_lin.k_shards is not None and mesh is not None:
                raise ValueError(
                    "int_lin.k_shards on a meshed engine needs "
                    "int_lin.k_axis= naming the mesh axis the K shards "
                    "live on"
                )
            if int_lin.certificate is not None:
                # a certificate only proves accumulator safety for the
                # exact integer weights it hashed — refuse to serve a
                # census-free path for anything else
                # (core.certify.CertificateError on mismatch)
                int_lin.certificate.verify(params)
        if mesh is not None and int_lin is not None:
            # distribute the integer projections over the serving mesh
            int_lin = dataclasses.replace(int_lin, mesh=mesh)
        if mesh is not None:
            # every member holds the whole tree (the projections'
            # shard_map slices it per device); params committed to one
            # device would otherwise be refused by the meshed step
            params = jax.device_put(
                params, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            )
        quantized = (
            cache_dtype == "int8"
            if isinstance(cache_dtype, str)
            else jnp.dtype(cache_dtype) == jnp.int8
        )
        if quantized:
            if page_size is None:
                raise ValueError(
                    'cache_dtype="int8" quantizes KV *pages* — it '
                    "requires the paged cache (page_size=...)"
                )
            # non-KV float leaves (SSM state, conv rings, window rings)
            # stay f32 — only the KV page pools store int8
            cache_dtype = jnp.float32
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.int_lin = int_lin
        self.mesh = mesh
        self.prefill_mode = prefill_mode
        self.page_size = page_size
        self.prefill_decode_ratio = prefill_decode_ratio
        self.admit_lookahead = admit_lookahead
        self._seed = seed
        if page_size is not None:
            pages_per_slot = -(-max_len // page_size)
            if num_pages is None:
                num_pages = num_slots * pages_per_slot
            self.paging = paged_cache.PagedSpec(
                page_size=page_size,
                num_pages=num_pages,
                pages_per_slot=pages_per_slot,
                num_state_pages=num_slots,
                quantized=quantized,
            )
            self.caches = model.init_caches(
                params, num_slots, max_len, cache_dtype, paging=self.paging
            )
            self._alloc = paged_cache.PageAllocator(num_pages)
            self._table = np.full((num_slots, pages_per_slot), -1, np.int32)
            self._sidx = np.full((num_slots,), -1, np.int32)
            self._free_sidx = list(range(num_slots - 1, -1, -1))
        else:
            self.paging = None
            self.caches = model.init_caches(
                params, num_slots, max_len, cache_dtype
            )
        self.slots: list[Optional[Request]] = [None] * num_slots
        self.queue: list[Request] = []
        # admitted but not yet prefilled (interleaved admission)
        self._pending: list[tuple[int, Request]] = []
        self._ready = np.zeros(num_slots, bool)  # prefilled, decoding
        self._pos = np.zeros(num_slots, np.int64)  # tokens written so far
        self._next_token = np.zeros((num_slots, 1), np.int32)
        self._budget = np.zeros(num_slots, np.int64)
        self._since_prefill = 0
        self._step_idx = 0
        # fault tolerance: every live request is registered by uid so a
        # snapshot restore can rebind engine state to the caller's
        # Request objects; done uids never get resurrected
        self.failure_injector = failure_injector
        self._requests: dict[int, Request] = {}
        self._done_uids: set[int] = set()
        self._submit_seq = 0
        self.events: list[dict] = []  # structured log (census degrades, ...)
        # census-triggered degradation: one monitor for the engine's
        # lifetime (jitted traces bind it permanently), drained per window
        self.census_watch = census_watch
        self._census = (
            dispatch.CensusMonitor() if census_watch is not None else None
        )
        self._census_steps = 0
        self._degraded: set[str] = set()
        # consecutive clean windows per degraded site (un-degrade path)
        self._clean_windows: dict[str, int] = {}
        self.last_census_rates: dict[str, float] = {}
        # device-step accounting: admission latency is prefill_steps per
        # cohort (1 on the batched path, max prompt length - 1 on the
        # token-by-token path); queue_wait_steps sums engine steps each
        # request spent queued before admission, hol_skips counts
        # requests skip-scanned past for page-budget backpressure
        self.stats = {
            "prefill_steps": 0,
            "decode_steps": 0,
            "cohorts": 0,
            "hol_skips": 0,
            "queue_wait_steps": 0,
            "pages_in_use": 0,
            "pages_peak": 0,
            "census_degrades": 0,
            "census_undegrades": 0,
        }

        self._build_step_fns()

    def _build_step_fns(self) -> None:
        """(Re)build and re-jit the decode/prefill/reset step functions.

        jax.jit caches by function object, so anything the closures bake
        in at trace time — the ``int_lin`` config (census degradation
        hot-swaps it), the mesh (elastic remesh replaces it) — requires
        fresh function objects to force a retrace. Called from __init__
        and again after every hot-swap/remesh.
        """
        model = self.model

        def _int_ctx():
            # trace-time context: QTensor projections lower to true
            # integer dot products through pqs_dot under this policy
            # (sharded over the mesh when one is configured); the census
            # monitor context makes every site report overflow counts
            stack = contextlib.ExitStack()
            if self.int_lin is not None:
                stack.enter_context(dispatch.integer_lin(self.int_lin))
            if self._census is not None:
                stack.enter_context(dispatch.census_monitor(self._census))
            return stack

        def step(params, tok, caches, active):
            with _int_ctx():
                logits, new_caches = model.decode(params, tok, caches)
            with jax.named_scope("merge"):
                return logits, model.merge_caches(caches, new_caches, active)

        def prefill_step(params, toks, caches, lengths, active):
            with _int_ctx():
                _, new_caches = model.prefill(params, toks, caches, lengths)
            # match cache leaf dtypes (e.g. f32 conv rings fed bf16
            # activations) so merged caches keep the decode signature
            with jax.named_scope("merge"):
                new_caches = jax.tree_util.tree_map(
                    lambda o, n: n.astype(o.dtype), caches, new_caches
                )
                return model.merge_caches(caches, new_caches, active)

        self._step = jax.jit(step)
        self._prefill_step = jax.jit(prefill_step)
        self._reset = jax.jit(
            lambda caches, mask: model.merge_caches(
                caches,
                jax.tree_util.tree_map(jnp.zeros_like, caches),
                mask,
            )
        )

    # -- calibration ---------------------------------------------------------

    def calibrate(
        self,
        batches: list[Any],
        act_bits: int = 8,
        symmetric: bool = True,
        decay: float = 0.9,
    ) -> dict:
        """Calibrate→freeze static activation ranges for integer decode.

        Runs the model forward over ``batches`` (training-style batch
        dicts) with the activation-range observer active, freezes the
        bias-corrected per-site bounds into static QParams, and attaches
        them to this engine's QTensor params (``QTensor.act_qparams``).
        Subsequent decode steps quantize activations with the frozen
        scales — no per-call absmax reduction (the jitted steps retrace
        automatically because the param pytree structure changed).
        Returns the frozen site → QParams dict.
        """
        from repro.core.quant import ActCalibrator
        from repro.core.qtensor import attach_act_qparams

        cal = ActCalibrator(decay=decay)
        with dispatch.calibration(cal):
            # jit keeps the pass fast; the range observations ride
            # jax.debug.callback, which fires at runtime under jit/scan.
            # The lambda (not the bound method) matters: bound methods of
            # a shared model compare equal across engines, so a second
            # engine's jit would hit the first's trace cache and leave
            # its observation callbacks bound to the first (dead) store
            fwd = jax.jit(lambda p, b: self.model.forward(p, b))
            for batch in batches:
                jax.block_until_ready(fwd(self.params, batch))
        # the observer ran inside host callbacks, which JAX runs on its
        # CPU device: take the frozen scales as host values, or they would
        # pin every later step to the CPU
        frozen = jax.tree_util.tree_map(
            np.asarray, cal.freeze(bits=act_bits, symmetric=symmetric))
        self.params = attach_act_qparams(self.params, frozen)
        return frozen

    # -- request lifecycle ---------------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        """Worst-case pages for a request: its prompt (minus the held-
        back final token) plus every token its budget may decode."""
        tokens = max(len(req.prompt) + req.max_new_tokens - 1, 1)
        return -(-tokens // self.page_size)

    def submit(self, req: Request) -> None:
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_len:
            # past max_len the per-slot write index leaves the cache and
            # scatters are silently dropped — refuse loudly instead
            raise ValueError(
                f"request {req.uid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) = {total} exceeds "
                f"max_len={self.max_len}"
            )
        if self.paging is not None:
            need = self._pages_needed(req)
            if need > self.paging.num_pages:
                # could never be admitted — backpressure would deadlock
                raise ValueError(
                    f"request {req.uid}: needs {need} pages, pool has "
                    f"{self.paging.num_pages} (page_size={self.page_size})"
                )
        req.t_submit = time.perf_counter()
        req._submit_step = self._step_idx
        # per-request sampling stream: reproducible under any batch
        # composition / admission order
        req._rng = np.random.default_rng((self._seed, req.uid))
        # registry + submission order: a snapshot restore rebinds slots
        # to these objects and re-queues post-snapshot submissions in
        # their original order
        req._submit_seq = self._submit_seq
        self._submit_seq += 1
        self._requests[req.uid] = req
        self._done_uids.discard(req.uid)
        self.queue.append(req)

    def _admit(self) -> list[tuple[int, Request]]:
        """Claim free slots from the queue; reserve + allocate pages.

        Paged backpressure: a request only leaves the queue once its
        worst-case page count is reservable, so the lazy per-step
        ``alloc`` calls during decode can never fail. A blocked request
        does not block shorter ones behind it — the scan skips past it
        (up to ``admit_lookahead`` skips) and counts ``hol_skips``.
        """
        free = [i for i in range(self.num_slots) if self.slots[i] is None]
        admitted: list[tuple[int, Request]] = []
        qi = 0
        skipped = 0
        while free and qi < len(self.queue):
            req = self.queue[qi]
            if self.paging is not None:
                need = self._pages_needed(req)
                if not self._alloc.can_reserve(need):
                    self.stats["hol_skips"] += 1
                    skipped += 1
                    if skipped >= self.admit_lookahead:
                        break
                    qi += 1
                    continue
            slot = free.pop(0)
            self.queue.pop(qi)
            if self.paging is not None:
                self._alloc.reserve(slot, need)
                # prompt pages up front (prefill scatters the whole
                # prompt at once); decode pages allocate lazily
                n_prefill = max(len(req.prompt) - 1, 0)
                for j in range(-(-n_prefill // self.page_size)):
                    self._table[slot, j] = self._alloc.alloc(slot)
                self._sidx[slot] = self._free_sidx.pop()
            self.slots[slot] = req
            self._ready[slot] = False
            self._pos[slot] = 0
            self.stats["queue_wait_steps"] += self._step_idx - getattr(
                req, "_submit_step", self._step_idx
            )
            admitted.append((slot, req))
        if not admitted:
            return admitted
        # clear stale cache lanes (KV pages, SSM state, positions) of
        # the re-used slots; on the paged path the new page tables go
        # live first so the reset zeroes the freshly claimed pages
        mask = np.zeros(self.num_slots, bool)
        for slot, _ in admitted:
            mask[slot] = True
        if self.paging is not None:
            self.caches = paged_cache.set_tables(
                self.caches, self._table, self._sidx
            )
        self.caches = self._reset(self.caches, jnp.asarray(mask))
        self._pending.extend(admitted)
        return admitted

    def _maybe_prefill(self) -> None:
        """Prefill the pending cohort, subject to the interleave budget.

        ``prefill_decode_ratio=0`` (default): immediately. Ratio N > 0:
        only after N decode steps since the last prefill — unless
        nothing is mid-decode, in which case waiting helps no one.
        """
        if not self._pending:
            return
        have_ready = any(
            self.slots[i] is not None and self._ready[i]
            for i in range(self.num_slots)
        )
        if have_ready and self._since_prefill < self.prefill_decode_ratio:
            return
        cohort, self._pending = self._pending, []
        self._prefill(cohort)
        self._since_prefill = 0
        for slot, req in cohort:
            self._pos[slot] = len(req.prompt) - 1
            self._ready[slot] = True

    def _prefill(self, admitted: list[tuple[int, Request]]) -> None:
        """Consume the admitted prompts into their slots' cache lanes.

        The final prompt token is always held back — it is fed by the
        first decode step, which produces the first sampled token.
        """
        self.stats["cohorts"] += 1
        with TraceAnnotation("engine.prefill", step=self._step_idx,
                             rows=len(admitted)):
            if self.prefill_mode == "batched":
                self._prefill_batched(admitted)
            else:
                self._prefill_steps(admitted)
        for slot, req in admitted:
            self._next_token[slot, 0] = int(req.prompt[-1])
            self._budget[slot] = req.max_new_tokens

    def _prefill_batched(self, admitted: list[tuple[int, Request]]) -> None:
        """ONE jitted batched prefill step for the whole admission cohort.

        Prompts are left-aligned into a (num_slots, S) buffer with
        per-slot lengths; S is padded to a power-of-two bucket so the
        number of distinct compiled shapes stays logarithmic in max_len.
        Non-admitted slots carry length 0 and are additionally masked
        out of the cache merge, so mid-generation lanes are untouched.
        """
        longest = max(len(req.prompt) for _, req in admitted) - 1
        if longest <= 0:
            return  # single-token prompts: nothing to prefill
        s = 1 << (longest - 1).bit_length()  # pow2 bucket >= longest
        toks = np.zeros((self.num_slots, s), np.int32)
        lengths = np.zeros(self.num_slots, np.int32)
        active = np.zeros(self.num_slots, bool)
        for slot, req in admitted:
            n = len(req.prompt) - 1
            toks[slot, :n] = req.prompt[:-1]
            lengths[slot] = n
            active[slot] = True
        self.caches = self._prefill_step(
            self.params, jnp.asarray(toks), self.caches,
            jnp.asarray(lengths), jnp.asarray(active),
        )
        self.stats["prefill_steps"] += 1

    def _prefill_steps(self, admitted: list[tuple[int, Request]]) -> None:
        """Legacy path: prompts through the decode step token-by-token.

        At step t every admitted slot with a t-th prompt token is
        active; all other slots (both mid-generation and idle) are
        masked out, so their caches do not advance. Kept as the parity
        oracle for the batched path (tests/test_prefill_parity.py and
        the paged suite).
        """
        longest = max(len(req.prompt) for _, req in admitted)
        for t in range(longest - 1):
            active = np.zeros(self.num_slots, bool)
            tok = self._next_token.copy()
            for slot, req in admitted:
                if t < len(req.prompt) - 1:
                    active[slot] = True
                    tok[slot, 0] = int(req.prompt[t])
            if active.any():
                _, self.caches = self._step(
                    self.params, jnp.asarray(tok), self.caches,
                    jnp.asarray(active),
                )
                self.stats["prefill_steps"] += 1

    # -- decode loop ----------------------------------------------------------

    def _ensure_decode_pages(self, active: list[int]) -> None:
        """Lazily claim the page each active slot's next write lands in.

        Guaranteed to succeed: admission reserved the worst case. Only
        pushes the table to the device when something actually changed.
        """
        dirty = False
        for slot in active:
            lp = int(self._pos[slot]) // self.page_size
            if self._table[slot, lp] < 0:
                self._table[slot, lp] = self._alloc.alloc(slot)
                dirty = True
        if dirty:
            self.caches = paged_cache.set_tables(
                self.caches, self._table, self._sidx
            )

    def _free_slot(self, slot: int) -> None:
        self.slots[slot] = None
        self._ready[slot] = False
        if self.paging is not None:
            self._alloc.free_slot(slot)
            self._table[slot, :] = -1
            self._free_sidx.append(int(self._sidx[slot]))
            self._sidx[slot] = -1
            # the stale device-side table row is harmless (the slot is
            # inactive, so merges revert anything it could touch); the
            # next admission's set_tables overwrites it

    def _sample(self, logits: np.ndarray, slot: int) -> int:
        req = self.slots[slot]
        row = logits[slot, -1]
        if req.temperature <= 0:
            return int(row.argmax())
        z = row / req.temperature
        z = z - z.max()
        p = np.exp(z) / np.exp(z).sum()
        rng = getattr(req, "_rng", None)
        if rng is None:  # request bypassed submit(); still per-request
            rng = req._rng = np.random.default_rng((self._seed, req.uid))
        return int(rng.choice(len(p), p=p))

    def step(self) -> int:
        """One batched decode step (plus admission/prefill bookkeeping).

        Returns the number of slots that decoded plus the number of
        admitted-but-pending prefills — 0 means the engine is idle.
        """
        self._step_idx += 1
        idx = self._step_idx
        # one span per phase of the step, never per slot: the step index
        # rides as a stat, so spans of one step can be matched up
        with TraceAnnotation("engine.step", step=idx) as span:
            if self.failure_injector is not None:
                self.failure_injector.maybe_fail(idx)
            with TraceAnnotation("engine.admit", step=idx) as admit:
                admitted = self._admit()
                if admitted:
                    admit.set_metadata(
                        uids=" ".join(str(r.uid) for _, r in admitted))
            self._maybe_prefill()
            active = [
                i for i, r in enumerate(self.slots)
                if r is not None and self._ready[i]
            ]
            span.set_metadata(rows=len(active))
            if not active:
                return len(self._pending)
            if self.paging is not None:
                with TraceAnnotation("engine.pages", step=idx):
                    self._ensure_decode_pages(active)
            with TraceAnnotation("engine.dispatch", step=idx):
                mask = np.zeros(self.num_slots, bool)
                mask[active] = True
                logits, self.caches = self._step(
                    self.params, jnp.asarray(self._next_token), self.caches,
                    jnp.asarray(mask),
                )
            self.stats["decode_steps"] += 1
            self._since_prefill += 1
            with TraceAnnotation("engine.fetch", step=idx):
                logits = np.asarray(logits.astype(jnp.float32))
            with TraceAnnotation("engine.sample", step=idx):
                self._sample_all(logits, active)
            if self.paging is not None:
                self.stats["pages_in_use"] = self._alloc.in_use
                self.stats["pages_peak"] = self._alloc.peak_in_use
            if self.census_watch is not None:
                self._check_census()
            return len(active) + len(self._pending)

    def _sample_all(self, logits: np.ndarray, active: list[int]) -> None:
        """Sample each active slot's next token; retire finished requests."""
        for slot in active:
            req = self.slots[slot]
            nxt = self._sample(logits, slot)
            req.output.append(nxt)
            self._next_token[slot, 0] = nxt
            self._pos[slot] += 1
            self._budget[slot] -= 1
            if self._budget[slot] <= 0 or (
                req.eos_id is not None and nxt == req.eos_id
            ):
                req.done = True
                req.t_done = time.perf_counter()
                self._done_uids.add(req.uid)
                self._free_slot(slot)

    def drain(self, requests: list[Request], max_steps: int = 100_000) -> None:
        for r in requests:
            self.submit(r)
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break

    def cache_nbytes(self) -> int:
        """Current cache footprint in bytes (pools + tables + state)."""
        return paged_cache.cache_nbytes(self.caches)

    # -- census-triggered graceful degradation --------------------------------

    def _check_census(self) -> None:
        """Window check: hot-swap any site saturating its accumulator.

        Drains the per-site overflow census every ``window`` decode
        steps. A site over threshold degrades exactly once: its policy
        flips to ``wide`` (or its ``acc_bits`` widens), the step
        functions re-jit against the new config, and a structured event
        is logged. Degraded-to-wide sites keep reporting dots with zero
        events, so the next window observably reads rate 0.0 — and,
        when ``undegrade_after`` is set, those clean windows accumulate
        toward the reverse transition: after N consecutive clean
        windows the site's overrides are dropped (``census_undegrade``
        event) and it re-narrows to the engine-wide config, back under
        full watch (it can re-degrade if the workload is still hot).

        Certified sites (``int_lin.certificate``) never appear here at
        all: `dispatch.qtensor_dot` dispatches them census-free, so the
        monitor has nothing to drain for them and the watch can never
        degrade a provably-safe site — that is the certified fast path's
        contract, enforced by construction rather than by filtering.
        """
        self._census_steps += 1
        if self._census_steps < self.census_watch.window:
            return
        self._census_steps = 0
        totals = self._census.drain()
        self.last_census_rates = {
            s: (e / d if d else 0.0) for s, (d, e) in totals.items()
        }
        changed = False
        # reverse transition first: a site whose census stayed clean for
        # N consecutive windows drops its overrides and re-narrows
        after = self.census_watch.undegrade_after
        if after is not None:
            for site in sorted(self._degraded):
                dots, events = totals.get(site, (0, 0))
                if dots < self.census_watch.min_dots:
                    continue  # no evidence either way: freeze the streak
                rate = events / dots
                if rate > self.census_watch.threshold:
                    self._clean_windows[site] = 0
                    continue
                streak = self._clean_windows.get(site, 0) + 1
                self._clean_windows[site] = streak
                if streak < after:
                    continue
                self.int_lin = self.int_lin.without_site(site)
                self._degraded.discard(site)
                self._clean_windows.pop(site, None)
                self.stats["census_undegrades"] += 1
                changed = True
                event = {
                    "event": "census_undegrade",
                    "site": site,
                    "clean_windows": streak,
                    "rate": rate,
                    "dots": dots,
                    "step": self._step_idx,
                }
                self.events.append(event)
                logger.info(
                    "census_undegrade site=%s after %d clean windows "
                    "(rate=%.4f over %d dots) at step %d",
                    site, streak, rate, dots, self._step_idx,
                )
        for site, (dots, events) in sorted(totals.items()):
            if dots < self.census_watch.min_dots or site in self._degraded:
                continue
            rate = events / dots
            if rate <= self.census_watch.threshold:
                continue
            if self.census_watch.mode == "widen":
                self.int_lin = self.int_lin.with_site_acc_bits(
                    site, self.census_watch.widen_to
                )
                action = {"acc_bits": self.census_watch.widen_to}
            else:
                self.int_lin = self.int_lin.with_site_policy(site, "wide")
                action = {"policy": "wide"}
            self._degraded.add(site)
            self.stats["census_degrades"] += 1
            changed = True
            event = {
                "event": "census_degrade",
                "site": site,
                "rate": rate,
                "dots": dots,
                "overflows": events,
                "step": self._step_idx,
                **action,
            }
            self.events.append(event)
            logger.warning(
                "census_degrade site=%s rate=%.4f (%d/%d dots) -> %s "
                "at step %d",
                site, rate, events, dots, action, self._step_idx,
            )
        if changed:
            self._build_step_fns()

    # -- fault tolerance: cancel / snapshot / restore / remesh ----------------

    def cancel(self, uid: int) -> bool:
        """Remove a live request wherever it is (queue, pending, slot).

        Frees the slot/pages and unregisters the uid, so a later
        snapshot restore will not resurrect it — the fleet's deadline
        path re-queues the prompt itself. Returns False for unknown or
        already-finished uids.
        """
        for qi, req in enumerate(self.queue):
            if req.uid == uid:
                self.queue.pop(qi)
                self._requests.pop(uid, None)
                return True
        for pi, (slot, req) in enumerate(self._pending):
            if req.uid == uid:
                self._pending.pop(pi)
                self._free_slot(slot)
                self._requests.pop(uid, None)
                return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.uid == uid:
                self._free_slot(slot)
                self._requests.pop(uid, None)
                return True
        return False

    def snapshot(self) -> dict:
        """Serving-state snapshot: everything a mid-decode resume needs.

        Two leaves, sized for ``checkpoint.save_checkpoint``:
          "caches"  the cache pytree on host (page pools + tables +
                    positions + scales)
          "meta"    a pickled uint8 blob: per-slot request bindings
                    (uid, emitted output, sampling RNG state), queue and
                    pending order, decode cursors (pos/budget/
                    next_token/ready), page-allocator state, stats,
                    census-degradation overrides.
        Restoring on a fresh or crashed engine resumes decode such that
        in-flight requests continue bit-identically to a failure-free
        run (same caches, same next token, same RNG stream position).
        """

        def req_state(req: Request) -> dict:
            return {
                "uid": req.uid,
                "output": list(req.output),
                "rng": req._rng.bit_generator.state
                if getattr(req, "_rng", None) is not None
                else None,
            }

        meta: dict[str, Any] = {
            "step_idx": self._step_idx,
            "submit_seq": self._submit_seq,
            "slots": [
                None if r is None else req_state(r) for r in self.slots
            ],
            "queue": [req_state(r) for r in self.queue],
            "pending": [(slot, r.uid) for slot, r in self._pending],
            "ready": self._ready.copy(),
            "pos": self._pos.copy(),
            "next_token": self._next_token.copy(),
            "budget": self._budget.copy(),
            "since_prefill": self._since_prefill,
            "stats": dict(self.stats),
            "done_uids": set(self._done_uids),
            "degraded": set(self._degraded),
            "clean_windows": dict(self._clean_windows),
            "site_policies": self.int_lin.site_policies
            if self.int_lin is not None
            else (),
            "site_acc_bits": self.int_lin.site_acc_bits
            if self.int_lin is not None
            else (),
        }
        if self.paging is not None:
            meta["paging"] = {
                "table": self._table.copy(),
                "sidx": self._sidx.copy(),
                "free_sidx": list(self._free_sidx),
                "alloc_free": list(self._alloc._free),
                "alloc_owned": {
                    k: list(v) for k, v in self._alloc._owned.items()
                },
                "alloc_pending": dict(self._alloc._pending),
                "alloc_peak": self._alloc.peak_in_use,
            }
        return {
            "caches": paged_cache.snapshot(self.caches),
            "meta": np.frombuffer(pickle.dumps(meta), np.uint8),
        }

    def restore(self, snap: dict) -> None:
        """Resume from a ``snapshot()`` after a crash (or on a twin engine).

        Request objects are rebound from the live registry by uid:
        covered in-flight requests get their emitted output truncated to
        the snapshot point and their RNG stream rewound, so replayed
        decode re-emits the identical continuation — no duplicate and no
        lost tokens. Requests that finished since the snapshot stay
        finished (their slots are freed; delivered output is never
        regenerated). Requests submitted after the snapshot restart from
        their prompt, re-queued in original submission order.
        """
        meta = pickle.loads(np.asarray(snap["meta"]).tobytes())
        self.caches = paged_cache.restore(self.caches, snap["caches"])
        self._step_idx = int(meta["step_idx"])
        self._submit_seq = max(self._submit_seq, int(meta["submit_seq"]))
        self._ready = np.asarray(meta["ready"]).copy()
        self._pos = np.asarray(meta["pos"]).copy()
        self._next_token = np.asarray(meta["next_token"]).copy()
        self._budget = np.asarray(meta["budget"]).copy()
        self._since_prefill = int(meta["since_prefill"])
        self.stats = dict(meta["stats"])
        self._done_uids |= set(meta["done_uids"])
        if self.paging is not None:
            pg = meta["paging"]
            self._table = np.asarray(pg["table"]).copy()
            self._sidx = np.asarray(pg["sidx"]).copy()
            self._free_sidx = list(pg["free_sidx"])
            alloc = paged_cache.PageAllocator(self.paging.num_pages)
            alloc._free = list(pg["alloc_free"])
            alloc._owned = {k: list(v) for k, v in pg["alloc_owned"].items()}
            alloc._pending = dict(pg["alloc_pending"])
            alloc.peak_in_use = int(pg["alloc_peak"])
            self._alloc = alloc
            self.caches = paged_cache.set_tables(
                self.caches, self._table, self._sidx
            )

        def rebind(st: Optional[dict]) -> Optional[Request]:
            if st is None:
                return None
            req = self._requests.get(st["uid"])
            if req is None or req.done:
                # finished (and delivered) since the snapshot, or
                # cancelled by the fleet — never resurrect
                return None
            req.output[:] = st["output"]
            req.done = False
            if st["rng"] is not None:
                req._rng = np.random.default_rng((self._seed, req.uid))
                req._rng.bit_generator.state = st["rng"]
            return req

        covered: set[int] = set()
        self.slots = [rebind(st) for st in meta["slots"]]
        for slot, req in enumerate(self.slots):
            if req is None:
                if meta["slots"][slot] is not None:
                    # occupied at snapshot, finished since: release the
                    # restored pages/state index for this slot
                    self.slots[slot] = object.__new__(Request)  # placeholder
                    self.slots[slot].uid = meta["slots"][slot]["uid"]
                    self._free_slot(slot)
                self.slots[slot] = None
                self._ready[slot] = False
            else:
                covered.add(req.uid)
        self.queue = []
        for st in meta["queue"]:
            req = rebind(st)
            if req is not None:
                self.queue.append(req)
                covered.add(req.uid)
        self._pending = []
        for slot, uid in meta["pending"]:
            req = self.slots[slot]
            if req is not None and req.uid == uid:
                self._pending.append((slot, req))
        # post-snapshot submissions (and anything else live but not in
        # the snapshot): restart from the prompt, original order
        missing = sorted(
            (
                r
                for uid, r in self._requests.items()
                if uid not in covered and uid not in self._done_uids
                and not r.done
            ),
            key=lambda r: getattr(r, "_submit_seq", 0),
        )
        for req in missing:
            req.output.clear()
            req._rng = np.random.default_rng((self._seed, req.uid))
            self.queue.append(req)
        # census degradation state: adopt the snapshot's overrides on
        # top of any the engine already applied (union — recovery never
        # narrows a site the snapshot or the engine holds degraded; a
        # site un-degraded *before* the snapshot appears in neither, so
        # its removal survives the restore)
        if self.int_lin is not None:
            cfg = self.int_lin
            for site, pol in meta["site_policies"]:
                if cfg.policy_for(site) != pol:
                    cfg = cfg.with_site_policy(site, pol)
            for site, bits in meta["site_acc_bits"]:
                if cfg.acc_bits_for(site) < bits:
                    cfg = cfg.with_site_acc_bits(site, bits)
            if cfg is not self.int_lin:
                self.int_lin = cfg
                self._build_step_fns()
            self._degraded |= set(meta["degraded"])
            # clean-window streaks resume from the snapshot, pruned to
            # sites still degraded after the union
            cw = dict(meta.get("clean_windows", ()))
            cw.update(self._clean_windows)
            self._clean_windows = {
                s: n for s, n in cw.items() if s in self._degraded
            }
        self._census_steps = 0
        if self._census is not None:
            self._census.drain()

    def remesh(self, new_mesh) -> None:
        """Re-place the engine on a different mesh (elastic shrink/grow).

        Params and caches round-trip through host (surviving devices
        hold complete copies under the serving placement) and the step
        functions re-jit against the new mesh so the sharded integer
        projections re-partition. In-flight decode state (positions,
        tables, RNG streams) is untouched — decode resumes bit-identically
        because ``pqs_dot`` is bit-exact at any mesh shape.
        """
        self.mesh = new_mesh
        if self.int_lin is not None:
            self.int_lin = dataclasses.replace(self.int_lin, mesh=new_mesh)

        def rehost(a):
            if isinstance(a, jax.Array):
                return jnp.asarray(np.asarray(a))
            return a

        self.params = jax.tree_util.tree_map(rehost, self.params)
        self.caches = jax.tree_util.tree_map(rehost, self.caches)
        self._build_step_fns()
