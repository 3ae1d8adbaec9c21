"""Tests for repro.chaos: trace record/replay determinism plus every fault
class's dedicated recovery path across the training and serving layers.

Recovery-path coverage map (one test per taxonomy entry):

* ``host_crash``       -> test_serve_chaos_trace_replay_is_identical /
                          test_train_escalating_backoff_on_repeated_step
* ``slowdown``         -> test_serve_slowdown_stalls_then_resumes_bit_identical
                          / test_train_slowdown_and_capacity_loss
* ``capacity_loss``    -> test_serve_capacity_loss_sheds_hopeless_only
* ``ckpt_corrupt``     -> test_restore_falls_back_to_previous_checkpoint /
                          test_train_ckpt_corrupt_falls_back
* ``snapshot_corrupt`` -> test_serve_snapshot_corrupt_falls_back_to_reprefill
                          / test_serve_snapshot_corrupt_survives_delta_snapshots
* ``nan_poison``       -> test_train_nan_poison_guard_skips_batch
* ``net_partition``    -> test_train_net_partition_parks_single_actor
                          (quorum/minority split: tests/test_crosspod.py)
* ``disk_full``        -> test_store_enospc_prunes_oldest_and_retries /
                          test_train_disk_full_prunes_and_survives
"""
import collections
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chaos import (CAPACITY_LOSS, CKPT_CORRUPT, DISK_FULL, HOST_CRASH,
                         NAN_POISON, NET_PARTITION, SERVE_KINDS, SLOWDOWN,
                         SNAPSHOT_CORRUPT, ChaosEngine, FaultEvent,
                         FaultTrace, corrupt_checkpoint_shard, sample_trace)
from repro.configs import get_config
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.distributed.steps import make_train_step
from repro.ft import (CheckpointStore, DynamicInterval, FaultInjector,
                      TrainingCoordinator)
from repro.models import lm
from repro.optim import adamw_init
from repro.obs import FlightRecorder, Tracer
from repro.serve import (AdmissionQueue, EngineConfig, Request, ServeEngine,
                         WorkItem, WorkerPool, prompt_bucket, uniform_policy)
from repro.serve import snapshot


# ------------------------------------------------------------- fixtures ----

@pytest.fixture(scope="module")
def serve_setup():
    cfg = get_config("olmo-1b", tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def train_setup():
    cfg = get_config("olmo-1b", tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, q_chunk=16, xent_chunk=16))
    data_cfg = DataConfig(global_batch=4, seq_len=32)
    return cfg, params, opt, step, data_cfg


def _req(rid, plen, newt, *, arrival=0, deadline=None, vocab=256, seed=0):
    rng = np.random.default_rng(seed * 7919 + rid)
    return Request(rid=rid,
                   prompt=rng.integers(1, vocab, plen,
                                       dtype=np.int64).astype(np.int32),
                   max_new_tokens=newt, arrival=arrival, deadline=deadline)


def _copy(params):
    """A copy of the weights for one engine: an engine takes the weights
    it is given and releases those it casts, and the fixture builds
    several."""
    return jax.tree.map(jnp.copy, params)


def _engine(cfg, params, reqs, *, workers=2, slots=2, chaos=None,
            policy=None, snapshot_lambda=4, max_steps=2_000, tracer=None,
            before_run=None):
    cache_len = max(prompt_bucket(r.prompt_len) + r.max_new_tokens
                    for r in reqs)
    pool = WorkerPool(workers, slots, mtbf_steps=0.0, mttr_steps=6, seed=0)
    engine = ServeEngine(
        cfg, EngineConfig(cache_len=cache_len, q_chunk=32,
                          snapshot_lambda=snapshot_lambda),
        pool=pool, policy=policy or uniform_policy(1), params=_copy(params),
        chaos=chaos, tracer=tracer)
    if before_run is not None:
        before_run(engine)
    for r in reqs:
        engine.submit(r)
    engine.run(max_steps=max_steps)
    return engine


def _coordinator(train_setup, tmp_path, *, chaos=None, injector=None,
                 lam=2.0, name="ckpt"):
    cfg, params, opt, step, data_cfg = train_setup
    return TrainingCoordinator(
        train_step=step, params=params, opt_state=opt,
        pipeline=SyntheticTokenPipeline(data_cfg, cfg),
        store=CheckpointStore(str(tmp_path / name)),
        interval=DynamicInterval(gamma_s=1.0, lam_min=lam, lam_max=lam),
        injector=injector, chaos=chaos)


# ---------------------------------------------------- traces and replay ----

def test_sample_trace_deterministic_and_roundtrips(tmp_path):
    a = sample_trace("unstable", horizon=300, n_targets=4, seed=11)
    b = sample_trace("unstable", horizon=300, n_targets=4, seed=11)
    assert a.to_json() == b.to_json() and len(a) > 0
    assert sample_trace("unstable", horizon=300, n_targets=4,
                        seed=12).to_json() != a.to_json()
    path = str(tmp_path / "trace.json")
    a.save(path)
    assert FaultTrace.load(path).to_json() == a.to_json()
    only = sample_trace("unstable", horizon=300, seed=11,
                        kinds=(HOST_CRASH,))
    assert only.kinds() == {HOST_CRASH}


def test_trace_load_rejects_unknown_version(tmp_path):
    trace = FaultTrace(events=[FaultEvent(step=1, kind=HOST_CRASH)])
    path = str(tmp_path / "trace.json")
    trace.save(path)
    import json
    with open(path) as f:
        d = json.load(f)
    d["version"] = 99
    with open(path, "w") as f:
        json.dump(d, f)
    with pytest.raises(ValueError, match="'version'"):
        FaultTrace.load(path)


def test_trace_rejects_unknown_fault_kind():
    trace = FaultTrace(events=[FaultEvent(step=1, kind=HOST_CRASH)])
    d = trace.to_json()
    d["events"][0]["kind"] = "gamma_ray"
    with pytest.raises(ValueError, match="gamma_ray"):
        FaultTrace.from_json(d)


def test_chaos_engine_fires_each_event_exactly_once():
    trace = FaultTrace(events=[
        FaultEvent(step=3, kind=HOST_CRASH, targets=(0,), duration=2),
        FaultEvent(step=3, kind=SLOWDOWN, targets=(1,), duration=4),
        FaultEvent(step=7, kind=NAN_POISON)])
    eng = ChaosEngine(trace)
    assert eng.pending() == 3
    assert len(eng.events_at(3)) == 2
    assert eng.events_at(3) == []          # never re-fires
    assert [e.kind for e in eng.events_at(7)] == [NAN_POISON]
    assert eng.pending() == 0
    assert eng.applied_by_kind == collections.Counter(
        {HOST_CRASH: 1, SLOWDOWN: 1, NAN_POISON: 1})


# ------------------------------------------- fault injector (multiset) ----

def test_fault_injector_multiset_defer_not_absorbed():
    inj = FaultInjector(mtbf_steps=10.0, seed=0, horizon_steps=0)
    inj.fail_steps = {5, 8}               # legacy set assignment still works
    assert 5 in inj.fail_steps and inj.fails_at(8)
    inj.defer(5, 8)                       # lands on an occupied step
    assert 5 not in inj.fail_steps
    assert inj.fail_steps[8] == 2         # stacked, not absorbed
    assert inj.consume(8) and inj.consume(8)
    assert not inj.consume(8)
    inj.fail_steps = collections.Counter({3: 2})   # mapping form
    assert inj.consume(3) and inj.consume(3) and not inj.consume(3)


# ---------------------------------------------------- checkpoint store ----

def test_restore_falls_back_to_previous_checkpoint(tmp_path):
    """Flipped bytes in a committed shard: restore must land on the previous
    verified checkpoint with the bad shard quarantined (reason logged)."""
    store = CheckpointStore(str(tmp_path), n_hosts=2)
    for s in (1, 2, 3):
        store.save(s, {"w": np.arange(1000.0) * s, "b": np.ones(600) * s},
                   extra={"next_index": s})
    assert corrupt_checkpoint_shard(store, seed=0) is not None
    like = {"w": np.zeros(1000), "b": np.zeros(600)}
    tree, step, extra = store.restore(like)
    assert step == 2 and extra["next_index"] == 2
    np.testing.assert_array_equal(tree["w"], np.arange(1000.0) * 2)
    assert store.last_restore_fallbacks == 1
    assert store.quarantined and \
        "checksum" in store.quarantined[0]["reason"]
    assert os.path.exists(os.path.join(str(tmp_path), "quarantine",
                                       "LOG.jsonl"))
    # the failed index is retired: the next restore goes straight to step 2
    assert store.committed_steps() == [1, 2]


def test_restore_raises_clear_error_when_all_corrupt(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save(1, {"w": np.arange(64.0)})
    store.save(2, {"w": np.arange(64.0) + 9})
    for root, _, files in os.walk(tmp_path):
        for f in files:
            if f.endswith(".npy"):
                p = os.path.join(root, f)
                np.save(p, np.load(p) + 1.0)
    with pytest.raises(IOError, match="checksum"):
        store.restore({"w": np.zeros(64)})
    assert len(store.quarantined) == 2


class _Boom:
    def __array__(self, *a, **k):
        raise RuntimeError("boom: disk full")


def test_async_save_errors_surface_from_wait(tmp_path):
    """An exception inside the async _write thread must re-raise from
    wait(), never silently leave a stale pointer."""
    store = CheckpointStore(str(tmp_path))
    store.save(1, {"x": np.ones(8)})
    store.save(2, {"x": _Boom()}, sync=False)
    with pytest.raises(RuntimeError, match="disk full"):
        store.wait()
    assert store.latest_step() == 1       # failed save committed nothing
    store.save(3, {"x": np.ones(8)}, sync=False)   # store remains usable
    store.wait()
    assert store.latest_step() == 3


def test_store_enospc_prunes_oldest_and_retries(tmp_path):
    """A mid-save ENOSPC must free space by pruning the *oldest* committed
    checkpoint and retry — the committed index stays consistent throughout
    and the new save lands."""
    store = CheckpointStore(str(tmp_path))
    for s in (1, 2, 3):
        store.save(s, {"w": np.arange(256.0) * s}, extra={"next_index": s})
    store.inject_disk_full()
    store.save(4, {"w": np.arange(256.0) * 4}, extra={"next_index": 4},
               sync=False)
    store.wait()
    assert store.enospc_retries == 1
    assert store.pruned_for_space == [1]      # oldest went first
    assert store.committed_steps() == [2, 3, 4]
    assert store.verify_committed() == []     # every index entry verifies
    tree, step, extra = store.restore({"w": np.zeros(256)})
    assert step == 4 and extra["next_index"] == 4
    np.testing.assert_array_equal(tree["w"], np.arange(256.0) * 4)


def test_store_enospc_with_nothing_to_prune_raises(tmp_path):
    """With no older committed checkpoint to free, the ENOSPC surfaces —
    and commits nothing (no torn index entry)."""
    store = CheckpointStore(str(tmp_path))
    store.inject_disk_full()
    with pytest.raises(OSError):
        store.save(1, {"w": np.ones(64)})
    assert store.committed_steps() == []
    assert store.verify_committed() == []


# ----------------------------------------------------- training chaos ----

def test_train_nan_poison_guard_skips_batch(tmp_path, train_setup):
    trace = FaultTrace(events=[FaultEvent(step=2, kind=NAN_POISON)])
    coord = _coordinator(train_setup, tmp_path, chaos=ChaosEngine(trace))
    rep = coord.run(6)
    assert rep.steps_completed == 6
    assert rep.nan_rollbacks == 1 and rep.skipped_batches == 1
    assert all(np.isfinite(rep.losses))
    assert coord._nan_skip                # poisoned batch stays quarantined


def test_train_ckpt_corrupt_falls_back(tmp_path, train_setup):
    """ckpt_corrupt + same-step crash: the restore must skip the corrupted
    newest checkpoint and recover from its predecessor."""
    trace = FaultTrace(events=[
        FaultEvent(step=4, kind=CKPT_CORRUPT, seed=7),
        FaultEvent(step=4, kind=HOST_CRASH, duration=2)])
    coord = _coordinator(train_setup, tmp_path, chaos=ChaosEngine(trace))
    rep = coord.run(8)
    assert rep.steps_completed == 8
    assert rep.ckpt_corruptions == 1
    assert rep.ckpt_fallbacks >= 1 and rep.restores >= 1
    assert coord.store.quarantined


def test_train_escalating_backoff_on_repeated_step(tmp_path, train_setup):
    """Three faults stacked on one step: repair wait doubles per repeat and
    a pre-retry checkpoint bounds the replay."""
    inj = FaultInjector(mtbf_steps=10.0, mttr_steps=4.0, seed=0,
                        horizon_steps=0)
    inj.fail_steps = collections.Counter({3: 3})
    coord = _coordinator(train_setup, tmp_path, injector=inj)
    rep = coord.run(6)
    assert rep.steps_completed == 6
    assert rep.failures == 3 and rep.restores == 3
    # streaks 1..3 at mttr=4: extra wait (2-1)*4 + (4-1)*4 = 16 steps
    assert rep.backoff_steps == pytest.approx(16.0)
    assert 3 in coord._ckpt_before        # pre-retry sync barrier installed


def test_train_slowdown_and_capacity_loss(tmp_path, train_setup):
    trace = FaultTrace(events=[
        FaultEvent(step=1, kind=SLOWDOWN, duration=5),
        FaultEvent(step=3, kind=CAPACITY_LOSS, targets=(0,), duration=4)])
    coord = _coordinator(train_setup, tmp_path, chaos=ChaosEngine(trace))
    rep = coord.run(6)
    assert rep.steps_completed == 6
    assert rep.slowdowns == 1
    assert rep.failures == 1 and rep.restores == 1   # capacity loss = outage


def test_train_disk_full_prunes_and_survives(tmp_path, train_setup):
    """disk_full + same-step crash: the forced checkpoint hits ENOSPC,
    prunes-and-retries, and the restore immediately *reads* the rewritten
    index — which must audit clean."""
    trace = FaultTrace(events=[
        FaultEvent(step=3, kind=DISK_FULL),
        FaultEvent(step=3, kind=HOST_CRASH, duration=2)])
    coord = _coordinator(train_setup, tmp_path, chaos=ChaosEngine(trace))
    rep = coord.run(8)
    assert rep.steps_completed == 8
    assert rep.disk_full_events == 1
    assert rep.enospc_retries >= 1            # the save pruned and retried
    assert rep.index_violations == 0          # committed index never torn
    assert rep.restores >= 1                  # crash read the pruned index
    assert all(np.isfinite(rep.losses))


def test_train_net_partition_parks_single_actor(tmp_path, train_setup):
    """On the single-actor coordinator a partition is the degenerate one-pod
    cluster: no quorum anywhere, so the whole cluster parks for the window —
    virtual time is lost, state and data order are not."""
    trace = FaultTrace(events=[FaultEvent(step=2, kind=NET_PARTITION,
                                          targets=(0,), duration=4)])
    coord = _coordinator(train_setup, tmp_path, chaos=ChaosEngine(trace))
    rep = coord.run(6)
    clean = _coordinator(train_setup, tmp_path, name="clean")
    ref = clean.run(6)
    assert rep.steps_completed == 6
    assert rep.partitions == 1 and rep.parked_steps == pytest.approx(4.0)
    assert rep.failures == 0 and rep.restores == 0   # no state lost
    np.testing.assert_array_equal(rep.losses, ref.losses)


# ------------------------------------------------------ serving chaos ----

def test_serve_slowdown_stalls_then_resumes_bit_identical(serve_setup):
    """A straggler worker stalls its slots without losing state: the run
    takes longer but the delivered tokens are exactly the clean run's."""
    cfg, params = serve_setup
    reqs = [_req(i, 8 + 2 * i, 10, vocab=cfg.vocab_size, seed=3)
            for i in range(2)]
    clean = _engine(cfg, params, reqs, workers=1, slots=2)
    trace = FaultTrace(events=[
        FaultEvent(step=4, kind=SLOWDOWN, targets=(0,), duration=6)])
    slow = _engine(cfg, params, reqs, workers=1, slots=2,
                   chaos=ChaosEngine(trace))
    assert slow.metrics.slowdown_events == 1
    assert slow.step_no > clean.step_no   # the stall cost real steps
    assert len(slow.completed) == len(reqs)
    for rid in clean.completed:
        assert clean.completed[rid] == slow.completed[rid], rid
    assert slow.metrics.failures == 0     # no state was lost


def test_serve_capacity_loss_sheds_hopeless_only(serve_setup):
    """Deadline-aware degraded mode: queued hedges collapse and provably
    hopeless requests are shed — but nothing past its first token."""
    cfg, params = serve_setup
    reqs = [_req(0, 8, 8, vocab=cfg.vocab_size, seed=1),
            _req(1, 8, 8, deadline=3, vocab=cfg.vocab_size, seed=1),
            _req(2, 8, 8, deadline=200, vocab=cfg.vocab_size, seed=1)]
    trace = FaultTrace(events=[
        FaultEvent(step=2, kind=CAPACITY_LOSS, targets=(1,), duration=30)])
    engine = _engine(cfg, params, reqs, workers=2, slots=1,
                     policy=uniform_policy(2), chaos=ChaosEngine(trace))
    m = engine.metrics
    assert m.capacity_events == 1
    # rid 1 can never finish by step 3 (needs >= 6 steps): shed, not run
    assert 1 in engine.shed and 1 not in engine.completed
    assert m.shed == 1 and m.records[1].shed_step is not None
    assert m.hedge_drops >= 1             # queued copies collapsed to one
    assert 0 in engine.completed and 2 in engine.completed
    assert m.past_first_token_drops == 0  # the tripwire


def test_serve_snapshot_corrupt_falls_back_to_reprefill(serve_setup):
    """A corrupted decode snapshot must fail its checksum at resume time and
    the request re-prefills from scratch — same final tokens, never garbage
    state."""
    cfg, params = serve_setup
    reqs = [_req(0, 10, 12, vocab=cfg.vocab_size, seed=5)]
    clean = _engine(cfg, params, reqs, workers=1, slots=1,
                    snapshot_lambda=3)
    trace = FaultTrace(events=[
        FaultEvent(step=6, kind=SNAPSHOT_CORRUPT, seed=123),
        FaultEvent(step=6, kind=HOST_CRASH, targets=(0,), duration=2)])
    faulty = _engine(cfg, params, reqs, workers=1, slots=1,
                     snapshot_lambda=3, chaos=ChaosEngine(trace))
    m = faulty.metrics
    assert m.snapshots_corrupted == 1
    assert m.snapshot_restore_failures == 1   # checksum caught it
    assert m.restores == 0                    # corrupt snapshot never used
    assert m.resubmissions == 1
    assert faulty.completed[0] == clean.completed[0]


def test_serve_snapshot_corrupt_survives_delta_snapshots(serve_setup,
                                                         monkeypatch):
    """A flip in a stored chunk is never laundered: a later delta snapshot
    of the same lineage shares the flipped chunk but takes its checksum
    from the bytes as they came off the device, so it fails its verify at
    the resume and the request re-prefills to the clean tokens."""
    monkeypatch.setattr(snapshot, "CHUNK_ROWS", 8)
    cfg, params = serve_setup
    # prompt 16 (pos 16 after prefill), 24 new: cache_len 40, 8-row chunks;
    # at a cadence of 8 the snapshots fall at pos 24 (end of step 7: three
    # sealed chunks and nothing else, so any flip lands in one) and pos 32
    # (end of step 15: one more chunk, a delta)
    reqs = [_req(0, 16, 24, vocab=cfg.vocab_size, seed=5)]
    clean = _engine(cfg, params, reqs, workers=1, slots=1,
                    snapshot_lambda=8)
    trace = FaultTrace(events=[
        FaultEvent(step=10, kind=SNAPSHOT_CORRUPT, seed=123),
        FaultEvent(step=18, kind=HOST_CRASH, targets=(0,), duration=2)])
    rec = FlightRecorder(1 << 12)
    faulty = _engine(cfg, params, reqs, workers=1, slots=1,
                     snapshot_lambda=8, chaos=ChaosEngine(trace),
                     tracer=Tracer(rec))
    taken = [(e["attrs"]["step"], e["attrs"]["pos"]) for e in rec.snapshot()
             if e["name"] == "serve.snapshot"]
    assert taken[:2] == [(7, 24), (15, 32)]
    m = faulty.metrics
    assert m.snapshots_corrupted == 1
    assert m.snapshot_deltas >= 1
    assert m.snapshot_restore_failures == 1   # the delta carried the flip
    assert m.restores == 0
    assert m.resubmissions == 1
    assert faulty.completed[0] == clean.completed[0]


def test_serve_restore_ignores_finite_rows_above_pos(serve_setup,
                                                     monkeypatch):
    """A restore writes rows [0, pos) only (its last chunk zero-padded);
    the target slot's rows above hold whatever it held.  Filled with finite
    garbage first, they change no token: decode attention gives every row
    above the written position exactly zero weight."""
    monkeypatch.setattr(snapshot, "CHUNK_ROWS", 8)
    cfg, params = serve_setup
    reqs = [_req(i, 8 + 3 * i, 16, vocab=cfg.vocab_size, seed=3)
            for i in range(4)]
    clean = _engine(cfg, params, reqs, snapshot_lambda=3)
    trace = FaultTrace(events=[
        FaultEvent(step=9, kind=HOST_CRASH, targets=(0,), duration=2)])
    rng = np.random.default_rng(0)
    poisoned = []

    def poison_restores(engine):
        restore = engine._restore

        def garbage_then_restore(sid, snap):
            row = jax.device_get(engine._get(engine.cache, sid))
            junk = jax.tree.map(
                lambda l: (rng.normal(size=l.shape) * 4).astype(l.dtype), row)
            engine.cache = engine._set(engine.cache, sid, junk)
            poisoned.append((sid, snap.pos))
            restore(sid, snap)
        engine._restore = garbage_then_restore

    faulty = _engine(cfg, params, reqs, snapshot_lambda=3,
                     chaos=ChaosEngine(trace), before_run=poison_restores)
    assert faulty.metrics.restores >= 1 and poisoned
    # rows past the last restored chunk kept the garbage
    assert any(-(-pos // 8) * 8 < faulty.ecfg.cache_len
               for _, pos in poisoned)
    for rid in clean.completed:
        assert clean.completed[rid] == faulty.completed[rid], rid


def test_serve_chaos_trace_replay_is_identical(serve_setup):
    """Two runs over one recorded trace (host crashes included) produce the
    same tokens and the same counters — the record/replay guarantee."""
    cfg, params = serve_setup
    reqs = [_req(i, 6 + 3 * i, 12, vocab=cfg.vocab_size, seed=9)
            for i in range(3)]
    trace = sample_trace("unstable", horizon=80, n_targets=2, seed=5,
                         kinds=SERVE_KINDS)
    assert trace.kinds() & {HOST_CRASH}
    runs = [_engine(cfg, params, reqs, chaos=ChaosEngine(trace))
            for _ in range(2)]
    a, b = (r.metrics.summary(r.step_no) for r in runs)
    assert a == b
    assert runs[0].completed == runs[1].completed
    assert runs[0].metrics.past_first_token_drops == 0


def test_queue_depth_bound_rejects_with_retry_after():
    q = AdmissionQueue(max_depth=2, drain_rate=2.0)
    assert q.admit([WorkItem(_req(0, 4, 8))]) is None
    assert q.admit([WorkItem(_req(1, 4, 8))]) is None
    hint = q.admit([WorkItem(_req(2, 4, 8))])
    # excess of 1 item ahead of the bound: 8 tokens at 2 tok/step -> 4 steps
    assert hint == 4
    assert len(q) == 2                        # the rejected item never queued
    # resubmissions carry work already paid for: they bypass the bound
    assert q.admit([WorkItem(_req(3, 4, 8), is_resubmission=True)]) is None
    assert len(q) == 3


def test_serve_bounded_admission_under_capacity_loss(serve_setup):
    """Queue-length-priced admission: once the backlog crosses the bound,
    fresh arrivals are rejected with a retry_after hint instead of growing
    the queue without limit — and the admitted work still completes through
    a capacity-loss window."""
    cfg, params = serve_setup
    reqs = [_req(i, 8, 8, vocab=cfg.vocab_size, seed=2) for i in range(8)]
    cache_len = max(prompt_bucket(r.prompt_len) + r.max_new_tokens
                    for r in reqs)
    pool = WorkerPool(2, 1, mtbf_steps=0.0, mttr_steps=6, seed=0)
    trace = FaultTrace(events=[FaultEvent(step=2, kind=CAPACITY_LOSS,
                                          targets=(1,), duration=30)])
    engine = ServeEngine(
        cfg, EngineConfig(cache_len=cache_len, q_chunk=32,
                          snapshot_lambda=4, max_queue_depth=4),
        pool=pool, policy=uniform_policy(2), params=_copy(params),
        chaos=ChaosEngine(trace))
    admitted = []
    for r in reqs:
        if engine.submit(r):
            admitted.append(r.rid)
        # all-or-nothing admits of rep=2 keep depth <= bound - 1 + rep
        assert len(engine.queue) <= 4 + 1
    assert admitted == [0, 1]                 # depth 4 reached after two
    m = engine.metrics
    assert m.rejected_on_arrival == 6
    assert set(engine.rejected) == {2, 3, 4, 5, 6, 7}
    assert all(hint >= 1 for hint in engine.rejected.values())
    assert m.records[2].rejected_step == 0 and m.records[2].retry_after >= 1
    assert set(engine.requests) == {0, 1}     # rejected rids never tracked
    engine.run(max_steps=2_000)
    s = m.summary(engine.step_no)
    assert m.capacity_events == 1
    assert set(engine.completed) == {0, 1}    # admitted work survives chaos
    assert s["rejected_on_arrival"] == 6.0
    assert m.past_first_token_drops == 0


def test_queue_drop_hedges_spares_resubmissions():
    q = AdmissionQueue()
    r0, r1 = _req(0, 4, 4), _req(1, 4, 4)
    q.submit(WorkItem(r0, copy_id=0))
    q.submit(WorkItem(r0, copy_id=1))
    q.submit(WorkItem(r1, copy_id=0))
    q.submit(WorkItem(r1, copy_id=0, is_resubmission=True))  # jumps head
    # r0's second copy and r1's plain copy (hedging the resubmission) go;
    # the resubmission itself and one copy per request survive
    assert q.drop_hedges() == 2
    kept = [(it.req.rid, it.is_resubmission) for it in q.items()]
    assert kept == [(1, True), (0, False)]
