"""Elastic runtime: fault injection, failure detection, retry, and the
kill-a-chip -> re-search -> restore -> resume recovery path, all on the
virtual 8-device CPU mesh (conftest.py)."""
import json

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.elastic import (
    ElasticCoordinator,
    EventLog,
    FaultPlan,
    RetriesExhausted,
    RetryPolicy,
    TopologyLoss,
    TransientFault,
    call_with_retry,
    classify_error,
    ring_topology_spec,
    shrink_topology_spec,
)


# -- helpers -------------------------------------------------------------
def make_config(devices=4, batch=12, budget=4):
    cfg = ff.FFConfig()
    cfg.batch_size = batch
    cfg.search_budget = budget  # > 0: recovery re-runs the Unity search
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
    w = rng.randn(din, 10).astype(np.float32)  # learnable labels
    y = np.argmax(x @ w, axis=1).reshape(-1, 1).astype(np.int32)
    return x, y


# -- retry policy --------------------------------------------------------
def test_retry_policy_backoff_bounded():
    p = RetryPolicy(max_retries=10, base_delay_s=0.1, backoff=2.0,
                    max_delay_s=0.5)
    delays = [p.delay_s(k) for k in range(8)]
    assert delays[0] == pytest.approx(0.1)
    assert delays[1] == pytest.approx(0.2)
    assert all(d <= 0.5 for d in delays)  # capped
    assert delays[-1] == pytest.approx(0.5)


def test_retry_jitter_seeded_deterministic():
    """With a seeded random.Random the jitter — and so a drill's whole
    retry timeline — replays exactly; the global-random fallback stays for
    callers that don't care."""
    import random

    p = RetryPolicy(max_retries=5, base_delay_s=0.1, jitter_frac=0.5,
                    max_delay_s=10.0)
    d1 = [p.delay_s(k, random.Random(7)) for k in range(5)]
    d2 = [p.delay_s(k, random.Random(7)) for k in range(5)]
    assert d1 == d2
    # jitter lands inside [base, base * (1 + jitter_frac)]
    for k, d in enumerate(d1):
        base = min(0.1 * 2.0 ** k, 10.0)
        assert base <= d <= base * 1.5


def test_call_with_retry_threads_rng_into_delays():
    import random

    events = EventLog()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise TransientFault("x")
        return 1

    policy = RetryPolicy(max_retries=5, base_delay_s=0.125, jitter_frac=1.0)
    call_with_retry(flaky, policy, events=events, step=1,
                    sleep=lambda s: None, rng=random.Random(3))
    got = [e.details["delay_s"] for e in events.events("retry")]
    replay = random.Random(3)
    assert got == [policy.delay_s(k, replay) for k in range(3)]


def test_call_with_retry_transient_then_success():
    events = EventLog()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("flaky")
        return "done"

    out = call_with_retry(flaky, RetryPolicy(max_retries=3,
                                             base_delay_s=0.0),
                          events=events, step=7, sleep=lambda s: None)
    assert out == "done"
    assert calls["n"] == 3
    retries = events.events("retry")
    assert len(retries) == 2
    assert all(e.step == 7 for e in retries)


def test_call_with_retry_exhaustion_and_topology():
    def always_transient():
        raise TransientFault("never heals")

    with pytest.raises(RetriesExhausted) as ei:
        call_with_retry(always_transient,
                        RetryPolicy(max_retries=2, base_delay_s=0.0),
                        sleep=lambda s: None)
    assert isinstance(ei.value.__cause__, TransientFault)

    calls = {"n": 0}

    def topo():
        calls["n"] += 1
        raise TopologyLoss([3])

    # topology loss must escalate on the FIRST occurrence, never retry
    with pytest.raises(TopologyLoss):
        call_with_retry(topo, RetryPolicy(max_retries=5, base_delay_s=0.0),
                        sleep=lambda s: None)
    assert calls["n"] == 1


def test_classify_error_patterns():
    assert classify_error(TransientFault("x")) == "transient"
    assert classify_error(TopologyLoss([0])) == "topology"
    assert classify_error(RuntimeError("DEADLINE_EXCEEDED: rpc")) \
        == "transient"
    assert classify_error(RuntimeError("DATA_LOSS: chip went away")) \
        == "topology"
    assert classify_error(RuntimeError("slice has been preempted")) \
        == "topology"
    assert classify_error(ValueError("plain bug")) == "unknown"


# -- fault plan ----------------------------------------------------------
def test_fault_plan_times_and_spending():
    plan = FaultPlan().add_transient(at_step=3, times=2)
    assert plan.take(2) == []
    assert len(plan.take(3)) == 1  # first firing
    assert len(plan.take(3)) == 1  # the retry's re-dispatch
    assert plan.take(3) == []      # spent
    assert plan.pending() == []


def test_same_step_faults_fire_one_at_a_time():
    """A raising fault must not consume later same-step faults: the
    transient fires first, and the chip loss survives for the retry's
    re-dispatch instead of being silently spent."""
    plan = (FaultPlan()
            .add_transient(at_step=5)
            .add_chip_loss(at_step=5, chips=[3]))
    first = plan.take(5)
    assert len(first) == 1 and first[0].kind == "transient"
    assert [f.kind for f in plan.pending()] == ["chip_loss"]
    second = plan.take(5)
    assert len(second) == 1 and second[0].kind == "chip_loss"
    assert plan.take(5) == []


def test_slow_link_stall_flagged_by_ewma():
    from flexflow_tpu.elastic import FailureDetector
    from flexflow_tpu.elastic.faults import FaultInjector

    t = {"now": 0.0}
    events = EventLog()
    plan = FaultPlan().add_slow_link(at_step=5, stall_s=1.0)
    inj = FaultInjector(plan, events=events,
                        sleep=lambda s: t.__setitem__("now", t["now"] + s))
    det = FailureDetector(events=events, injector=inj, warmup_steps=0,
                          clock=lambda: t["now"])

    def thunk():
        t["now"] += 0.01  # steady-state dispatch time
        return 0

    for step in range(8):
        det.current_step = step
        det.dispatch(thunk)
    slow = events.events("detect.slow_step")
    assert len(slow) == 1 and slow[0].step == 5
    assert len(events.events("fault.slow_link")) == 1


def test_fault_plan_rejects_bad_faults():
    with pytest.raises(ValueError):
        FaultPlan().add_chip_loss(at_step=1, chips=[])
    from flexflow_tpu.elastic import Fault

    with pytest.raises(ValueError):
        Fault("meteor", at_step=0)


# -- topology shrink -----------------------------------------------------
def test_shrink_topology_spec_renumbers():
    spec = ring_topology_spec(8)
    out = shrink_topology_spec(spec, [6, 7])
    assert out["num_chips"] == 6
    chips = {i for link in out["links"] for i in link[:2]}
    assert chips <= set(range(6))  # densely renumbered
    # the ring lost the 5-6, 6-7, 7-0 arcs: 5 surviving links
    assert len(out["links"]) == 5

    # losing both neighbors of a chip can empty the link list — the
    # machine model falls back to its default ring (the from_json fix)
    tiny = shrink_topology_spec(ring_topology_spec(3), [1, 2])
    assert tiny == {"num_chips": 1, "links": []}
    from flexflow_tpu.search.machine_model import NetworkedMachineModel

    m = NetworkedMachineModel.from_json(tiny)
    assert m.num_chips == 1 and m.link_gbps == 45.0


# -- integration: retry in place ----------------------------------------
def test_retry_on_transient_resumes_in_place():
    events = EventLog()
    plan = FaultPlan().add_transient(at_step=1, times=2)
    x, y = make_data(batch=12)
    coord = ElasticCoordinator(
        builder, make_config(), fault_plan=plan, events=events,
        retry_policy=RetryPolicy(max_retries=3, base_delay_s=0.0),
        checkpoint_every=100)
    history = coord.fit(x, y, steps=3)
    assert [h["step"] for h in history] == [0, 1, 2]
    assert len(events.events("fault.transient")) == 2
    assert len(events.events("retry")) == 2
    assert events.events("recovery.start") == []  # no re-plan needed


def test_retries_exhausted_escalates():
    plan = FaultPlan().add_transient(at_step=1, times=10)
    x, y = make_data(batch=8)
    coord = ElasticCoordinator(
        builder, make_config(devices=1, batch=8), fault_plan=plan,
        retry_policy=RetryPolicy(max_retries=2, base_delay_s=0.0))
    with pytest.raises(RetriesExhausted):
        coord.fit(x, y, steps=3)


# -- integration: chip loss -> re-search -> restore -> resume ------------
def test_kill_chip_research_restore_resume(tmp_path):
    events = EventLog()
    plan = FaultPlan.kill_chips(at_step=3, chips=[3])
    x, y = make_data(batch=12)
    coord = ElasticCoordinator(
        builder, make_config(devices=4, batch=12), fault_plan=plan,
        events=events, checkpoint_dir=str(tmp_path), checkpoint_every=2,
        # this test pins the DISK restore path (checkpoint + replay);
        # the zero-disk live path has its own tests in test_resharding.py
        live_resharding=False)
    history = coord.fit(x, y, steps=8)

    # recovered exactly once onto the 3 survivors
    assert len(events.events("recovery.done")) == 1
    assert coord.device_ids == [0, 1, 2]
    assert coord.model.config.total_devices == 3
    if coord.model.mesh is not None:
        assert coord.model.mesh.devices.size == 3
    # the re-plan ran the strategy selection for the shrunken machine
    search_evs = events.events("recovery.search")
    assert search_evs and search_evs[0].details["n_devices"] == 3
    # restore came from the step-2 checkpoint (latest before the fault)
    restore_evs = events.events("recovery.restore")
    assert restore_evs and restore_evs[0].step == 2

    # every step committed exactly once, in order
    assert [h["step"] for h in history] == list(range(8))
    # loss keeps decreasing from the checkpoint through the recovery:
    # batches cycle with period 4, so compare like against like
    for phase in range(4):
        losses = [h["loss"] for h in history if h["step"] % 4 == phase]
        assert losses[-1] < losses[0], (phase, losses)


def test_recover_to_single_survivor(tmp_path):
    """2 -> 1 devices: the rebuilt model is mesh-less, and params must be
    committed to the SURVIVOR, not jax.devices()[0] (the lost chip)."""
    import jax

    events = EventLog()
    plan = FaultPlan.kill_chips(at_step=2, chips=[0])
    x, y = make_data(batch=8)
    coord = ElasticCoordinator(
        builder, make_config(devices=2, batch=8), fault_plan=plan,
        events=events, checkpoint_dir=str(tmp_path), checkpoint_every=2)
    history = coord.fit(x, y, steps=4)
    assert coord.device_ids == [1]
    assert coord.model.mesh is None
    survivor = jax.devices()[1]
    for entry in coord.model.params.values():
        for arr in entry.values():
            assert survivor in arr.devices(), arr.devices()
    # the whole restored training state follows, not just params
    for leaf in jax.tree.leaves(coord.model.opt_state):
        assert survivor in leaf.devices(), leaf.devices()
    assert [h["step"] for h in history] == [0, 1, 2, 3]
    assert len(events.events("recovery.done")) == 1


def test_unidentified_topology_loss_fails_fast(tmp_path):
    """Real topology-classified errors carry no chip ids; the coordinator
    must fail with a clear message instead of 'recovering' onto the same
    device set (which would re-hit the dead chip until the budget runs
    out)."""
    from flexflow_tpu.elastic import RecoveryFailed

    x, y = make_data(batch=8)
    coord = ElasticCoordinator(
        builder, make_config(devices=2, batch=8),
        checkpoint_dir=str(tmp_path))
    coord._save(0)
    with pytest.raises(RecoveryFailed, match="did not identify"):
        coord._recover(TopologyLoss([]))


# -- event log -----------------------------------------------------------
def test_event_log_roundtrip_and_counts():
    log = EventLog()
    log.record("fault.chip_loss", step=5, chips=[6, 7])
    log.record("recovery.done", step=4, n_devices=6)
    log.record("recovery.done", step=9, n_devices=4)
    assert log.counts() == {"fault.chip_loss": 1, "recovery.done": 2}
    clone = EventLog.from_json(log.to_json())
    assert [e.to_dict() for e in clone.events()] \
        == [e.to_dict() for e in log.events()]
    text = log.prometheus_text()
    assert 'ff_elastic_events_total{kind="recovery.done"} 2' in text
    assert "recovery.done=2" in log.summary()


def test_event_log_on_serving_metrics_endpoint():
    from flexflow_tpu.serving.server import InferenceServer

    log = EventLog()
    log.record("fault.transient", step=1)
    srv = InferenceServer()
    srv.attach_elastic_events(log)
    text = srv.prometheus_text()
    assert 'ff_elastic_events_total{kind="fault.transient"} 1' in text
    assert srv.stats()["_elastic"] == {"fault.transient": 1}


def test_print_event_log(capsys):
    from flexflow_tpu.runtime.profiling import print_event_log

    log = EventLog()
    print_event_log(log)
    assert "no events" in capsys.readouterr().out
    log.record("retry", step=2, attempt=1)
    print_event_log(log)
    out = capsys.readouterr().out
    assert "retry" in out and "attempt=1" in out
