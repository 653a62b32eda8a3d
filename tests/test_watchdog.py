"""Training watchdog + durable rollback (ISSUE 3): NaN/Inf and loss-spike
detection, skip-then-rollback recovery through the elastic coordinator,
corrupt-checkpoint fallback, and the /metrics counter export — all on the
virtual 8-device CPU mesh (conftest.py)."""
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.elastic import (
    ElasticCoordinator,
    EventLog,
    FaultPlan,
    NumericBlowup,
    RecoveryFailed,
    TrainingWatchdog,
    WatchdogPolicy,
)


# -- helpers (the test_elastic.py fixtures) ------------------------------
def make_config(devices=4, batch=12, budget=4):
    cfg = ff.FFConfig()
    cfg.batch_size = batch
    cfg.search_budget = budget
    cfg.measure_op_costs = False
    cfg.device_ids = list(range(devices))
    return cfg


def builder(cfg):
    m = ff.FFModel(cfg)
    t = m.create_tensor([cfg.batch_size, 32])
    t = m.dense(t, 64, ff.ActiMode.AC_MODE_RELU)
    t = m.dense(t, 10)
    t = m.softmax(t)
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.05),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return m


def make_data(batch, n_batches=4, din=32, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(batch * n_batches, din).astype(np.float32)
    w = rng.randn(din, 10).astype(np.float32)
    y = np.argmax(x @ w, axis=1).reshape(-1, 1).astype(np.int32)
    return x, y


# -- policy + verdict state machine --------------------------------------
def test_policy_validation():
    with pytest.raises(ValueError):
        WatchdogPolicy(spike_factor=1.0)
    with pytest.raises(ValueError):
        WatchdogPolicy(max_consecutive_bad=0)


def test_nonfinite_skips_then_rollback_then_reset():
    events = EventLog()
    wd = TrainingWatchdog(WatchdogPolicy(max_consecutive_bad=3,
                                         warmup_steps=0), events=events)
    assert wd.check(0, 1.0) == "ok"
    assert wd.check(1, float("nan")) == "skip"
    assert wd.check(2, float("inf")) == "skip"
    assert wd.check(3, float("nan")) == "rollback"
    # the consecutive counter resets after a rollback verdict...
    assert wd.check(4, float("nan")) == "skip"
    # ...and after any good step
    assert wd.check(5, 1.0) == "ok"
    assert wd.consecutive_bad == 0
    assert len(events.events("watchdog.bad_step")) == 4
    assert len(events.events("watchdog.skip")) == 3
    # a ROLLBACK verdict alone records nothing — the event belongs to the
    # site that actually restores a checkpoint (coordinator._rollback)
    assert events.events("watchdog.rollback") == []
    wd.note_rollback(2)
    assert [e.step for e in events.events("watchdog.rollback")] == [2]


def test_spike_detection_arms_after_warmup():
    wd = TrainingWatchdog(WatchdogPolicy(spike_factor=5.0, warmup_steps=3,
                                         ema_alpha=0.5))
    # wild warmup losses are tolerated (a fresh model's first steps)
    assert wd.check(0, 40.0) == "ok"
    assert wd.check(1, 2.0) == "ok"
    assert wd.check(2, 2.0) == "ok"
    assert wd.check(3, 2.0) == "ok"
    # post-warmup: a finite 100x spike is a bad step; the EMA baseline is
    # NOT polluted by it, so the next normal loss is fine again
    assert wd.check(4, 200.0) == "skip"
    assert wd.check(5, 2.0) == "ok"


def test_guard_raises_numeric_blowup():
    wd = TrainingWatchdog(WatchdogPolicy(max_consecutive_bad=1,
                                         warmup_steps=0))
    wd.guard(0, 1.0)  # fine
    with pytest.raises(NumericBlowup, match="step 3"):
        wd.guard(3, float("nan"))


# -- FFModel.fit hook (no rollback available -> typed abort) -------------
def test_model_fit_watchdog_aborts_on_nan():
    model = builder(make_config(devices=1, batch=8))
    x = np.full((32, 32), np.inf, dtype=np.float32)  # guaranteed blow-up
    y = np.zeros((32, 1), np.int32)
    wd = TrainingWatchdog(WatchdogPolicy(max_consecutive_bad=2,
                                         warmup_steps=0))
    with pytest.raises(NumericBlowup, match="consecutive bad steps"):
        model.fit(x, y, epochs=3, watchdog=wd)
    assert len(wd.events.events("watchdog.bad_step")) == 2


# -- coordinator: skip -> rollback -> replay -----------------------------
def test_coordinator_nan_steps_skip_rollback_resume(tmp_path):
    """Four consecutive blown-up steps against the default policy (3
    consecutive bad = rollback): two skips, a rollback to the step-2
    checkpoint, a clean replay, one more skip, then healthy training."""
    events = EventLog()
    plan = FaultPlan()
    for s in range(3, 7):
        plan.add_nan_step(s)
    x, y = make_data(batch=12)
    coord = ElasticCoordinator(
        builder, make_config(), fault_plan=plan, events=events,
        checkpoint_dir=str(tmp_path), checkpoint_every=2)
    assert coord.detector.rng is not None  # seeded retry jitter threaded
    history = coord.fit(x, y, steps=10)

    assert len(events.events("watchdog.rollback")) == 1
    assert len(events.events("watchdog.skip")) == 3
    assert len(events.events("fault.nan_step")) == 4
    # rollback restored the step-2 checkpoint (newest before the bad run)
    restores = events.events("recovery.restore")
    assert len(restores) == 1 and restores[0].step == 2
    # steps 3 and 4 were skipped pre-rollback but REPLAYED clean after it
    # (their faults were spent); step 6's fault hits the replay as a
    # post-rollback skip, so it alone never commits
    assert [h["step"] for h in history] == [0, 1, 2, 3, 4, 5, 7, 8, 9]
    losses = {h["step"]: h["loss"] for h in history}
    assert all(np.isfinite(list(losses.values())))
    # the rollback + replay put training back on the trajectory it would
    # have had with no fault: every step committed up to the replayed
    # window has the uninjected run's loss (step 6 never commits, so the
    # two runs part ways after step 5)
    clean = ElasticCoordinator(
        builder, make_config(), events=EventLog(),
        checkpoint_dir=str(tmp_path / "clean"), checkpoint_every=2)
    clean_losses = {h["step"]: h["loss"] for h in clean.fit(x, y, steps=6)}
    for step in range(6):
        assert losses[step] == pytest.approx(clean_losses[step], rel=1e-5)


def test_coordinator_corrupt_checkpoint_falls_back(tmp_path):
    """Torn newest checkpoint + chip loss in the same dispatch: the
    recovery restore must fall back to the previous verified checkpoint
    instead of crashing on the corrupt one."""
    events = EventLog()
    plan = (FaultPlan()
            .add_corrupt_checkpoint(4)
            .add_chip_loss(4, chips=[3]))
    x, y = make_data(batch=12)
    coord = ElasticCoordinator(
        builder, make_config(devices=4, batch=12), fault_plan=plan,
        events=events, checkpoint_dir=str(tmp_path), checkpoint_every=2,
        # the scenario exists to prove the DISK path's verified fallback;
        # with live resharding on, a clean live tree would sidestep the
        # torn checkpoint entirely
        live_resharding=False)
    history = coord.fit(x, y, steps=8)

    assert len(events.events("recovery.done")) == 1
    assert coord.device_ids == [0, 1, 2]
    assert len(events.events("fault.corrupt_checkpoint")) == 1
    assert len(events.events("checkpoint.corrupt")) == 1
    # the step-4 file was torn, so restore fell back to step 2
    fallbacks = events.events("checkpoint.fallback")
    assert len(fallbacks) == 1 and fallbacks[0].step == 2
    restores = events.events("recovery.restore")
    assert restores and restores[0].step == 2
    assert [h["step"] for h in history] == list(range(8))


def test_rollback_budget_exhausts_on_deterministic_blowup(tmp_path):
    """A blow-up that recurs after every restore (faults re-arm via times)
    cannot be healed by replaying — the rollback budget must end it with a
    typed error instead of looping forever."""
    events = EventLog()
    plan = FaultPlan().add_nan_step(1, times=50)
    x, y = make_data(batch=8)
    wd = TrainingWatchdog(WatchdogPolicy(max_consecutive_bad=1,
                                         warmup_steps=0), events=events)
    coord = ElasticCoordinator(
        builder, make_config(devices=2, batch=8), fault_plan=plan,
        events=events, checkpoint_dir=str(tmp_path), watchdog=wd,
        max_rollbacks=2)
    with pytest.raises(RecoveryFailed, match="rollback budget"):
        coord.fit(x, y, steps=5)
    # only PERFORMED rollbacks are recorded; the third attempt hits the
    # budget and raises before restoring anything
    assert len(events.events("watchdog.rollback")) == 2


# -- /metrics export ------------------------------------------------------
def test_watchdog_and_checkpoint_counters_on_metrics():
    from flexflow_tpu.serving.server import InferenceServer

    # force the process-wide counters nonzero
    wd = TrainingWatchdog(WatchdogPolicy(max_consecutive_bad=2,
                                         warmup_steps=0))
    wd.check(0, float("nan"))
    srv = InferenceServer()
    text = srv.prometheus_text()
    assert "ff_watchdog_bad_steps_total" in text
    assert "ff_watchdog_skips_total" in text
    # any earlier durable save/restore in this test process shows up too
    stats = srv.stats()
    assert stats["_watchdog"]["bad_steps"] >= 1
