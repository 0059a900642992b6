"""Async direction service: fault injection, elastic resizes, and the
bit-replayability contract -- plus regression pins for the fleet-path
bugfix sweep (pipeline shutdown, replay-log conflicts, straggler-policy
validation, stranded-device warning)."""

import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.replay_log import ReplayLog, replay_into
from repro.configs import get_config
from repro.core.engine import MezoConfig, STALE_SGD, SGD
from repro.data.pipeline import DataPipeline
from repro.runtime.elastic import mesh_shape_for
from repro.runtime.fleet import (FaultSpec, FleetCoordinator, FleetSim,
                                 WorkerSpec, get_grade, lease_latency_s)
from repro.runtime.stragglers import StragglerPolicy

CFG = get_config("gemma-2b").reduced()
MZ = MezoConfig(lr=1e-3, n_directions=2, staleness_decay=0.95)


def _max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(
            jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32)))),
        a, b)))


# ---------------------------------------------------------------------------
# the acceptance scenario: everything at once


def test_faulty_elastic_run_replays_bit_exact(tmp_path):
    """Stragglers + duplicate deliveries + one mid-run join + one leave:
    the staleness-bearing log alone reconstructs live params at atol=0."""
    log = str(tmp_path / "fleet.jsonl")
    workers = [
        WorkerSpec("flagship", FaultSpec(jitter=0.2, duplicate_every=2)),
        WorkerSpec("flagship", FaultSpec(jitter=0.2)),
        WorkerSpec("flagship", FaultSpec(jitter=0.2)),
        WorkerSpec("flagship", FaultSpec(latency_scale=5.0)),  # straggler
    ]
    sim = FleetSim(CFG, workers, total_steps=20, mezo_cfg=MZ, batch=2,
                   seq=16, seed=0, log_path=log,
                   step_events=[(5, "join", WorkerSpec("flagship")),
                                (10, "leave", 2)])
    rep = sim.run()

    assert rep.applied == 20
    assert rep.resizes == 2                      # one join, one leave
    assert rep.dropped > 0                       # duplicates discarded
    assert max(rep.staleness) > 0                # genuinely async
    assert sorted(r["step"] for r in rep.records) == list(range(20))
    assert [r["step"] for r in rep.records] != list(range(20)), \
        "applies should arrive out of step order under async delivery"

    # crash recovery: theta_0 + the log is the whole checkpoint
    recs = ReplayLog.read(log)
    p0 = sim.model.init(jax.random.PRNGKey(0))
    replayed, last = replay_into(p0, recs, MZ)
    assert _max_diff(replayed, rep.params) == 0.0
    assert last == rep.records[-1]["step"]


def test_worker_death_mid_lease_reissues(tmp_path):
    """A worker that dies holding a lease never stalls the run: its step
    is re-issued and every update still lands, bit-replayable."""
    log = str(tmp_path / "death.jsonl")
    grade = get_grade("flagship")
    base = lease_latency_s(CFG, grade, 2 * 16, MZ.n_directions)
    workers = [WorkerSpec("flagship", FaultSpec(jitter=0.1)),
               # dies mid-flight of an early lease, result discarded
               WorkerSpec("flagship", FaultSpec(die_at=base * 1.5))]
    sim = FleetSim(CFG, workers, total_steps=8, mezo_cfg=MZ, batch=2,
                   seq=16, seed=1, log_path=log)
    rep = sim.run()
    assert rep.applied == 8
    assert sorted(r["step"] for r in rep.records) == list(range(8))
    p0 = sim.model.init(jax.random.PRNGKey(1))
    replayed, _ = replay_into(p0, ReplayLog.read(log), MZ)
    assert _max_diff(replayed, rep.params) == 0.0


def test_late_and_duplicate_deliveries_dropped_not_logged(tmp_path):
    """First delivery wins; late re-issue results and transport
    duplicates are counted but never reach the log (no divergent-retry
    warning on read)."""
    log = str(tmp_path / "dup.jsonl")
    workers = [WorkerSpec("flagship", FaultSpec(duplicate_every=1)),
               WorkerSpec("flagship", FaultSpec(jitter=0.1)),
               WorkerSpec("flagship", FaultSpec(latency_scale=8.0))]
    sim = FleetSim(CFG, workers, total_steps=10, mezo_cfg=MZ, batch=2,
                   seq=16, seed=2, log_path=log)
    rep = sim.run()
    assert rep.applied == 10
    assert rep.dropped > 0
    with warnings.catch_warnings():
        warnings.simplefilter("error")           # any warning fails
        recs = ReplayLog.read(log)
    assert len(recs) == 10                       # one record per step


def test_join_and_leave_resize_policy_and_params():
    coord_cfg = dict(total_steps=4, n_workers=2, seed=0)
    sim_params = {"w": jnp.ones((4, 4), jnp.float32)}
    c = FleetCoordinator(sim_params, MZ, **coord_cfg)
    c._observe(0, 1.0)
    assert c.policy.total == 2
    wid = c.worker_join(now=0.0)
    assert wid == 2 and c.policy.total == 3
    # newcomer's EMA seeded with the fleet median, not zero
    assert c.policy.ema_latencies[-1] > 0
    c.worker_leave(0, now=0.0)
    assert c.policy.total == 2
    assert c.resizes == 2
    with pytest.raises(ValueError, match="not in the roster"):
        c.worker_leave(99, now=0.0)


def test_leave_orphans_inflight_leases_for_reissue():
    params = {"w": jnp.ones((4,), jnp.float32)}
    c = FleetCoordinator(params, MZ, total_steps=3, n_workers=2, seed=0)
    lease = c.next_lease(worker=1, now=0.0)
    assert lease.step == 0
    c.worker_leave(1, now=0.0)
    release = c.next_lease(worker=0, now=0.0)
    assert release.step == 0                    # orphaned step re-issued
    assert c.reissued == 1


def test_stale_sgd_staleness_zero_matches_sgd_bit_exact():
    params = {"w": jnp.linspace(-1, 1, 32, dtype=jnp.float32)}
    gs = np.array([0.3, -0.7], np.float32)
    a, _ = SGD.update_fn(params, {}, np.uint32(7), gs, None, MZ)
    b, _ = STALE_SGD.update_fn(params, {}, np.uint32(7), gs, None, MZ)
    c, _ = STALE_SGD.update_fn(params, {}, np.uint32(7), gs, None, MZ,
                               staleness=0)
    assert _max_diff(a, b) == 0.0
    assert _max_diff(a, c) == 0.0
    d, _ = STALE_SGD.update_fn(params, {}, np.uint32(7), gs, None, MZ,
                               staleness=3)
    assert _max_diff(a, d) > 0.0                # decay actually applied


def test_coordinator_validates_config():
    params = {"w": jnp.ones((2,), jnp.float32)}
    with pytest.raises(ValueError, match="total_steps"):
        FleetCoordinator(params, MZ, total_steps=0, n_workers=1)
    with pytest.raises(ValueError, match="staleness_decay"):
        FleetCoordinator(params,
                         MezoConfig(staleness_decay=0.0),
                         total_steps=1, n_workers=1)
    with pytest.raises(ValueError, match="unknown direction estimator"):
        FleetSim(CFG, [WorkerSpec()], total_steps=1, estimator="sequential")
    with pytest.raises(ValueError, match="unknown device grade"):
        get_grade("abacus")
    with pytest.raises(ValueError, match="never fire"):
        FleetSim(CFG, [WorkerSpec()], total_steps=2,
                 step_events=[(2, "join", WorkerSpec())]).run()


def test_lease_latency_orders_device_grades():
    fast = lease_latency_s(CFG, get_grade("flagship"), 64, 2)
    slow = lease_latency_s(CFG, get_grade("budget"), 64, 2)
    assert 0 < fast < slow
    assert lease_latency_s(CFG, get_grade("flagship"), 64, 4) > fast


# ---------------------------------------------------------------------------
# regression pins: the bugfix sweep


def test_pipeline_close_joins_worker_with_full_queue():
    """Shutdown deadlock pin: close() while the worker is blocked on a
    full queue must join the thread promptly, not hang forever."""
    def endless():
        while True:
            yield {"x": np.zeros(4)}

    pipe = DataPipeline(endless(), prefetch=1)
    next(pipe)                         # worker now refilling a full queue
    t0 = time.monotonic()
    pipe.close()
    assert time.monotonic() - t0 < 5.0
    assert not pipe._thread.is_alive()


def test_pipeline_next_after_close_raises_not_hangs():
    pipe = DataPipeline(iter([{"x": np.zeros(2)}]), prefetch=1)
    pipe.close()
    with pytest.raises(RuntimeError, match="closed"):
        next(pipe)


def test_pipeline_next_after_worker_error_raises_not_hangs():
    def boom():
        raise ValueError("source died")
        yield  # pragma: no cover

    pipe = DataPipeline(boom())
    with pytest.raises(ValueError):
        next(pipe)
    # the queue is empty and the worker is gone: a second next() must
    # fail fast instead of blocking on q.get() forever
    with pytest.raises(RuntimeError, match="worker raised ValueError"):
        next(pipe)


def test_pipeline_exhaustion_keeps_raising_stopiteration():
    pipe = DataPipeline(iter([{"x": np.zeros(2)}]))
    assert len(list(pipe)) == 1
    with pytest.raises(StopIteration):          # iterator protocol holds
        next(pipe)


def test_replay_log_conflicting_duplicate_warns(tmp_path):
    path = str(tmp_path / "log.jsonl")
    log = ReplayLog(path)
    log.append(0, 7, [0.1], lr=1e-3, eps=1e-3)
    log.append(1, 8, [0.2], lr=1e-3, eps=1e-3)
    log.append(1, 8, [0.2], lr=1e-3, eps=1e-3)   # benign retry
    log.append(0, 9, [0.5], lr=1e-3, eps=1e-3)   # divergent retry!
    log.close()
    with pytest.warns(RuntimeWarning, match="conflicting duplicate"):
        recs = ReplayLog.read(path)
    assert [r["step"] for r in recs] == [0, 1]
    assert recs[0]["seed"] == 7                  # first-applied wins

    benign = str(tmp_path / "benign.jsonl")
    log = ReplayLog(benign)
    log.append(0, 7, [0.1], lr=1e-3, eps=1e-3)
    log.append(0, 7, [0.1], lr=1e-3, eps=1e-3)
    log.close()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert len(ReplayLog.read(benign)) == 1  # silent dedup


def test_straggler_observe_shape_error_names_expectation():
    pol = StragglerPolicy(n_directions=4, redundancy=2)
    with pytest.raises(ValueError, match=r"\(6,\)"):
        pol.observe([1.0, 2.0])


def test_straggler_deadline_inf_until_seen_then_median_scaled():
    pol = StragglerPolicy(n_directions=4, deadline_factor=3.0)
    assert pol.deadline() == float("inf")
    pol.observe([1.0, 1.0, 2.0, 4.0])
    assert pol.deadline() == pytest.approx(3.0 * 1.5)
    # copy-trick: feeding an entry's own EMA back leaves it unchanged
    vec = pol.ema_latencies
    vec[0] = 10.0
    pol.observe(vec)
    np.testing.assert_allclose(pol.ema_latencies[1:], [1.0, 2.0, 4.0])


def test_mesh_shape_for_warns_on_stranded_devices():
    with pytest.warns(RuntimeWarning, match="stranding 8 of 24"):
        shape = mesh_shape_for(24, model_parallel=4, data_parallel=4)
    assert shape == (1, 4, 4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert mesh_shape_for(32, 4, 4) == (2, 4, 4)   # exact fit: silent
