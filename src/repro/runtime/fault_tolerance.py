"""Fault tolerance runtime: checkpoint/restart, stragglers, elastic re-mesh.

Production framing (DESIGN.md §6), CPU-simulatable components:

- ``TrainSupervisor`` — drives a train step under failure: on an injected
  or real exception it restores the latest checkpoint and resumes, with
  bounded restarts. Data-iterator state is checkpointed too, so restart
  replays no batch twice.
- ``StragglerMonitor`` — per-step deadline from a rolling p50×k rule; on a
  real fleet the signal piggybacks on the existing all-reduce (no extra
  collectives): each host contributes its last step time into a tiny
  padded lane of the gradient buffer; slow hosts are flagged for preemptive
  re-scheduling. Here the aggregation is simulated over reported times.
- ``elastic_remesh`` — rebuild a smaller/larger mesh after losing or
  gaining hosts and re-shard a checkpointed state onto it. The batch axis
  shrinks; training resumes at the same step with the same params (tested
  at toy scale on CPU devices).
- ``ServeSupervisor`` — the serving analogue: drives a ``ServingFleet``
  step loop, restoring crashed engines from their latest serving-state
  snapshot (optionally remeshing onto survivors first via the
  ``on_failure`` hook) with the same bounded-restart budget.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint


def default_retryable() -> tuple[type[BaseException], ...]:
    """Exception types a supervisor treats as recoverable node failures.

    Device loss / runtime aborts surface from jax as
    ``jax.errors.JaxRuntimeError``. It subclasses RuntimeError today, so
    the plain default already covers it, but the subclassing is not
    contractual — list it explicitly so the default survives a jax that
    moves it off RuntimeError.
    """
    from jax.errors import JaxRuntimeError

    return tuple(dict.fromkeys((RuntimeError, JaxRuntimeError)))


class FailureInjector:
    """Deterministic failure schedule for tests: fail at given steps."""

    def __init__(self, fail_at: set[int]):
        self.fail_at = set(fail_at)
        self.failures = 0

    def maybe_fail(self, step: int) -> None:
        if step in self.fail_at:
            self.fail_at.discard(step)
            self.failures += 1
            raise RuntimeError(f"injected node failure at step {step}")


@dataclasses.dataclass
class StragglerReport:
    step: int
    times: dict[int, float]  # host -> seconds
    stragglers: list[int]
    deadline: float


class StragglerMonitor:
    """Rolling-median deadline straggler detection.

    A host is a straggler when its step time exceeds ``k`` x the rolling
    median of the fleet. Mitigation hooks: the supervisor can drop the
    host from the mesh (elastic_remesh) or re-dispatch its shard.
    """

    def __init__(self, k: float = 2.0, window: int = 32):
        self.k = k
        self.history: deque[float] = deque(maxlen=window)

    def observe(self, step: int, host_times: dict[int, float]) -> StragglerReport:
        med = float(np.median(list(host_times.values())))
        self.history.append(med)
        deadline = self.k * float(np.median(self.history))
        stragglers = [h for h, t in host_times.items() if t > deadline]
        return StragglerReport(step, host_times, stragglers, deadline)


def elastic_remesh(
    state: Any,
    make_mesh: Callable[[int], jax.sharding.Mesh],
    new_num_devices: int,
    sharding_rule: Callable[[jax.sharding.Mesh], Any],
) -> tuple[Any, jax.sharding.Mesh]:
    """Re-shard ``state`` onto a mesh over ``new_num_devices``.

    ``sharding_rule(mesh)`` returns a pytree of NamedShardings matching
    ``state`` (same rule used at startup, evaluated on the new mesh) —
    shrink/grow happens purely through the mesh shape.
    """
    from repro.launch.sharding import place_tree

    mesh = make_mesh(new_num_devices)
    return place_tree(state, sharding_rule(mesh)), mesh


class TrainSupervisor:
    """Checkpoint/restart training driver with bounded restarts.

    step_fn(state, batch) -> (state, metrics); state is any pytree.
    data_state/data_restore checkpoint the input pipeline position.
    """

    def __init__(
        self,
        ckpt_dir: str,
        step_fn: Callable,
        ckpt_every: int = 50,
        max_restarts: int = 5,
        failure_injector: Optional[FailureInjector] = None,
        retryable: Optional[tuple[type[BaseException], ...]] = None,
        reset_after: int = 0,
    ):
        self.ckpt_dir = ckpt_dir
        self.step_fn = step_fn
        self.ckpt_every = ckpt_every
        self.max_restarts = max_restarts
        self.injector = failure_injector
        self.retryable = retryable if retryable is not None else default_retryable()
        # after this many consecutive clean steps the restart budget
        # refills — long runs aren't killed by unrelated sporadic faults
        self.reset_after = reset_after
        self.restarts = 0

    def run(
        self,
        state: Any,
        next_batch: Callable[[], Any],
        num_steps: int,
        data: Any = None,  # object with .state()/.restore() (TokenStream)
        start_step: int = 0,
    ) -> tuple[Any, int]:
        step = start_step
        # entry-state snapshot: the restart-from-scratch path must rewind
        # to *this* state and data position, not whatever the failed step
        # left behind (host copies — state may alias donated buffers)
        init_state = jax.tree_util.tree_map(np.asarray, state)
        init_data = dict(data.state()) if data is not None else None
        clean_steps = 0
        # resume if a checkpoint exists
        if latest_step(self.ckpt_dir) is not None:
            payload, ck_step = restore_checkpoint(
                self.ckpt_dir, self._payload(state, data)
            )
            state = payload["state"]
            if data is not None:
                data.restore(
                    {"step": int(payload["data_step"]), "seed": 0, "host_id": 0}
                )
            step = ck_step

        while step < num_steps:
            try:
                if self.injector is not None:
                    self.injector.maybe_fail(step)
                batch = next_batch()
                state, _metrics = self.step_fn(state, batch)
                step += 1
                clean_steps += 1
                if self.reset_after and clean_steps >= self.reset_after:
                    self.restarts = 0
                if step % self.ckpt_every == 0 or step == num_steps:
                    save_checkpoint(
                        self.ckpt_dir, step, self._payload(state, data)
                    )
            except self.retryable:
                self.restarts += 1
                clean_steps = 0
                if self.restarts > self.max_restarts:
                    raise
                ck = latest_step(self.ckpt_dir)
                if ck is None:
                    # restart from scratch: rewind to the entry snapshot,
                    # not the mid-failure state/data position
                    step = start_step
                    state = init_state
                    if data is not None:
                        data.restore(dict(init_data))
                    continue
                payload, step = restore_checkpoint(
                    self.ckpt_dir, self._payload(state, data)
                )
                state = payload["state"]
                if data is not None:
                    data.restore(
                        {"step": int(payload["data_step"]), "seed": 0, "host_id": 0}
                    )
        return state, step

    @staticmethod
    def _payload(state: Any, data: Any) -> dict:
        return {
            "state": state,
            "data_step": np.asarray(data.step if data is not None else 0),
        }


class ServeSupervisor:
    """Supervised serving loop: step the fleet, recover on failure.

    The serving analogue of ``TrainSupervisor``: each ``step()`` drives
    one ``ServingFleet.step()`` under the retryable-exception umbrella.
    On a retryable failure the supervisor asks the fleet to restore the
    crashed engine from its latest snapshot (``fleet.recover``) and
    retries the step; non-retryable exceptions and exhausted budgets
    propagate. ``on_failure(fleet, error)`` runs before recovery — the
    hook point for elastic remesh onto surviving devices
    (``fleet.remesh_engine``) when the failure was a mesh-member loss.
    """

    def __init__(
        self,
        fleet: Any,
        max_restarts: int = 5,
        retryable: Optional[tuple[type[BaseException], ...]] = None,
        reset_after: int = 0,
        on_failure: Optional[Callable[[Any, BaseException], None]] = None,
    ):
        self.fleet = fleet
        self.max_restarts = max_restarts
        self.retryable = retryable if retryable is not None else default_retryable()
        self.reset_after = reset_after
        self.on_failure = on_failure
        self.restarts = 0
        self.recoveries: list[dict] = []
        self._clean_steps = 0

    def step(self) -> int:
        """One protected fleet step. Returns the fleet's pending count."""
        while True:
            try:
                n = self.fleet.step()
            except self.retryable as e:
                self.restarts += 1
                self._clean_steps = 0
                if self.restarts > self.max_restarts:
                    raise
                if self.on_failure is not None:
                    self.on_failure(self.fleet, e)
                self.recoveries.append(self.fleet.recover(e))
                continue
            self._clean_steps += 1
            if self.reset_after and self._clean_steps >= self.reset_after:
                self.restarts = 0
            return n

    def run(self, max_steps: int = 100_000) -> None:
        """Step until the fleet drains (no active, queued, or backlogged work)."""
        for _ in range(max_steps):
            if self.step() == 0:
                return
        raise RuntimeError(f"fleet failed to drain within {max_steps} steps")
