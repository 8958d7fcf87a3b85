"""Fault-tolerance runtime: supervisors, stragglers, remesh, async ckpt.

Single-device-safe throughout; the elastic-remesh resume test needs >= 4
devices and self-skips otherwise (scripts/ci.sh's ``fault`` stage runs
this file under REPRO_FORCE_MULTIDEVICE=8, where it is live).
"""

import os

# same opt-in idiom as test_sharded_dispatch.py: only effective before
# the first jax backend init, never leaks into the single-device suite
if os.environ.get("REPRO_FORCE_MULTIDEVICE") and (
    "--xla_force_host_platform_device_count"
    not in os.environ.get("XLA_FLAGS", "")
):
    _v = os.environ["REPRO_FORCE_MULTIDEVICE"]
    _n = int(_v) if _v.isdigit() and int(_v) > 1 else 8
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", "")
    )

import tempfile  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.checkpoint import (  # noqa: E402
    AsyncCheckpointer,
    load_checkpoint,
    save_checkpoint,
    unflatten_like,
)
from repro.data import TokenStream  # noqa: E402
from repro.runtime import (  # noqa: E402
    FailureInjector,
    StragglerMonitor,
    TrainSupervisor,
    default_retryable,
    elastic_remesh,
)


# --- retryable-exception policy ---------------------------------------------


def test_default_retryable_covers_device_loss():
    types = default_retryable()
    assert RuntimeError in types
    # device loss surfaces as jax's JaxRuntimeError — must be listed
    # explicitly, not assumed to stay a RuntimeError subclass forever
    from jax.errors import JaxRuntimeError

    assert JaxRuntimeError in types


def test_supervisor_retryable_is_configurable():
    class Flaky(Exception):
        pass

    calls = {"n": 0}

    def step_fn(state, batch):
        calls["n"] += 1
        if calls["n"] == 2:
            raise Flaky("transient")
        return {"x": state["x"] + batch}, {}

    # not in the retryable set -> propagates immediately
    with tempfile.TemporaryDirectory() as d:
        sup = TrainSupervisor(d, step_fn, ckpt_every=2)
        with pytest.raises(Flaky):
            sup.run({"x": jnp.asarray(0.0)}, lambda: jnp.asarray(1.0), 6)

    # listed -> recovered like any node failure
    calls["n"] = 0
    with tempfile.TemporaryDirectory() as d:
        sup = TrainSupervisor(
            d, step_fn, ckpt_every=2, retryable=(RuntimeError, Flaky)
        )
        state, step = sup.run(
            {"x": jnp.asarray(0.0)}, lambda: jnp.asarray(1.0), 6
        )
        assert step == 6 and float(state["x"]) == 6.0 and sup.restarts == 1


def test_supervisor_restart_budget_resets_after_clean_steps():
    def step_fn(state, batch):
        return {"x": state["x"] + batch}, {}

    # three sporadic failures, each separated by >= 3 clean steps: a
    # max_restarts=1 budget only survives if it refills between them
    def run(reset_after):
        with tempfile.TemporaryDirectory() as d:
            inj = FailureInjector({2, 7, 12})
            sup = TrainSupervisor(
                d, step_fn, ckpt_every=1, failure_injector=inj,
                max_restarts=1, reset_after=3,
            ) if reset_after else TrainSupervisor(
                d, step_fn, ckpt_every=1, failure_injector=inj,
                max_restarts=1,
            )
            return sup.run(
                {"x": jnp.asarray(0.0)}, lambda: jnp.asarray(1.0), 16
            )

    state, step = run(reset_after=True)
    assert step == 16 and float(state["x"]) == 16.0
    with pytest.raises(RuntimeError):
        run(reset_after=False)


# --- resume semantics --------------------------------------------------------


def _consume_stream(num_steps, fail_at, ckpt_every, max_restarts=3):
    """Drive a supervisor over a TokenStream, recording every batch the
    step function actually *applied* to the state. The state accumulates
    a checksum, so replayed-but-discarded work cannot hide."""
    data = TokenStream(vocab_size=50, seq_len=4, batch_size=2, seed=7)
    applied = []

    def step_fn(state, batch):
        tok = int(batch["tokens"][0, 0])
        applied.append(tok)
        return {"sum": state["sum"] + jnp.asarray(float(tok))}, {}

    with tempfile.TemporaryDirectory() as d:
        sup = TrainSupervisor(
            d, step_fn, ckpt_every=ckpt_every, max_restarts=max_restarts,
            failure_injector=FailureInjector(fail_at),
        )
        state, step = sup.run(
            {"sum": jnp.asarray(0.0)}, data.next_batch, num_steps, data=data
        )
    return float(state["sum"]), step, applied


def test_supervisor_resume_replays_no_batch_twice():
    clean_sum, _, clean_applied = _consume_stream(10, set(), ckpt_every=2)
    # unique batches in the clean run (sanity on the fixture itself)
    assert len(clean_applied) == 10

    faulty_sum, step, _ = _consume_stream(10, {3, 7}, ckpt_every=2)
    # every batch contributes exactly once to the final state: failures
    # rewind both the params AND the data stream to the checkpoint
    assert step == 10
    assert faulty_sum == clean_sum


def test_supervisor_scratch_restart_rewinds_state_and_data():
    # no checkpoint exists when the failure hits (ckpt_every huge):
    # restart-from-scratch must rewind to the ENTRY state and data
    # position, not keep the mid-failure state or a advanced stream
    clean_sum, _, _ = _consume_stream(6, set(), ckpt_every=100)
    faulty_sum, step, _ = _consume_stream(6, {3}, ckpt_every=100)
    assert step == 6
    assert faulty_sum == clean_sum


# --- straggler monitor -------------------------------------------------------


def test_straggler_deadline_tracks_rolling_median():
    mon = StragglerMonitor(k=2.0, window=4)
    for step in range(6):
        rep = mon.observe(step, {0: 0.10, 1: 0.10, 2: 0.10})
    assert rep.deadline == pytest.approx(0.20)
    assert rep.stragglers == []
    # a slow host is flagged against the fleet's deadline...
    rep = mon.observe(6, {0: 0.10, 1: 0.25, 2: 0.10})
    assert rep.stragglers == [1]
    # ...and a fleet-wide slowdown raises the deadline instead of
    # flagging everyone: after the window fills with slow steps the
    # same times stop being straggler-worthy
    for step in range(7, 12):
        rep = mon.observe(step, {0: 0.30, 1: 0.31, 2: 0.29})
    assert rep.deadline == pytest.approx(0.60)
    assert rep.stragglers == []


# --- async checkpointer error surfacing -------------------------------------


def test_async_checkpointer_surfaces_write_error_on_next_wait():
    with tempfile.TemporaryDirectory() as d:
        # point the checkpointer at a path occupied by a FILE: the
        # background mkdir/rename fails, and the failure must surface on
        # the next wait() instead of vanishing with the thread
        blocked = os.path.join(d, "ckpts")
        with open(blocked, "w") as f:
            f.write("not a directory")
        ck = AsyncCheckpointer(blocked)
        ck.save(1, {"x": np.ones(3)})
        with pytest.raises(OSError):
            ck.wait()
        # the error is consumed — the checkpointer is reusable after
        os.unlink(blocked)
        ck.save(2, {"x": np.ones(3)})
        ck.wait()
        flat, step = load_checkpoint(blocked)
        assert step == 2 and flat["['x']"].shape == (3,)


def test_load_checkpoint_target_free_roundtrip():
    tree = {
        "a": np.arange(6, dtype=np.int8).reshape(2, 3),
        "blob": np.frombuffer(b"variable-length", np.uint8),
    }
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 3, tree)
        flat, step = load_checkpoint(d)
        assert step == 3
        # unflatten_like rebuilds the structure even when the template's
        # leaf SHAPES differ (the variable-length-blob use case)
        template = {"a": np.zeros((2, 3), np.int8), "blob": np.zeros(0, np.uint8)}
        out = unflatten_like(template, flat)
        np.testing.assert_array_equal(out["a"], tree["a"])
        assert out["blob"].tobytes() == b"variable-length"
        with pytest.raises(KeyError):
            unflatten_like({"missing": np.zeros(1)}, flat)


# --- elastic remesh: reshard and RESUME -------------------------------------


@pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >= 4 devices (fault CI stage)"
)
def test_elastic_remesh_reshard_and_resume():
    """Lose half the fleet mid-run; training resumes on the survivors
    with bit-identical math (the step is a pure elementwise update)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def make_mesh(n):
        return jax.sharding.Mesh(np.asarray(jax.devices()[:n]), ("data",))

    def rule(mesh):
        return {
            "w": NamedSharding(mesh, P("data")),
            "step": NamedSharding(mesh, P()),
        }

    @jax.jit
    def train_step(state):
        return {
            "w": state["w"] * 1.5 + 1.0,
            "step": state["step"] + 1,
        }

    def run(n_devices, switch_at=None, switch_to=None):
        state = {
            "w": jnp.arange(8, dtype=jnp.float32),
            "step": jnp.asarray(0),
        }
        state = jax.device_put(state, rule(make_mesh(n_devices)))
        for i in range(6):
            if switch_at is not None and i == switch_at:
                state, _mesh = elastic_remesh(
                    state, make_mesh, switch_to, rule
                )
            state = train_step(state)
        return np.asarray(state["w"]), int(state["step"])

    w_ref, s_ref = run(4)
    w_el, s_el = run(4, switch_at=3, switch_to=2)
    assert s_ref == s_el == 6
    np.testing.assert_array_equal(w_ref, w_el)
