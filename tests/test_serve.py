"""Tests for repro.serve: queue, snapshots, CRCH routing, and the engine's
failure-determinism guarantee."""
import collections
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.obs import FlightRecorder, Tracer
from repro.serve import (AdmissionQueue, EngineConfig, Request, ServeEngine,
                         ServeMetrics, WorkItem, WorkerPool, crch_policy,
                         engine_supported, greedy_reference, prompt_bucket,
                         reference_logits, request_class, request_features,
                         uniform_policy)
from repro.serve import snapshot
from repro.serve.snapshot import (SlotLayout, cache_batch_axes, slot_get,
                                  slot_set)


def _req(rid, plen, newt, *, arrival=0, deadline=None, vocab=256, seed=0,
         cfg=None):
    rng = np.random.default_rng(seed * 7919 + rid)
    frames = embeds = None
    if cfg is not None:
        vocab = cfg.vocab_size
        if cfg.is_encdec:
            frames = rng.normal(size=(cfg.n_frames, cfg.d_model)) \
                        .astype(np.float32)
        if cfg.n_image_tokens:
            embeds = rng.normal(size=(cfg.n_image_tokens, cfg.d_model)) \
                        .astype(np.float32)
    return Request(rid=rid,
                   prompt=rng.integers(1, vocab, plen,
                                       dtype=np.int64).astype(np.int32),
                   max_new_tokens=newt, arrival=arrival, deadline=deadline,
                   frames=frames, image_embeds=embeds)


# ---------------------------------------------------------------- queue ----

def test_prompt_bucket_next_pow2():
    assert prompt_bucket(1) == 8
    assert prompt_bucket(8) == 8
    assert prompt_bucket(9) == 16
    assert prompt_bucket(33) == 64


def test_request_class_buckets():
    c = request_class(_req(0, 13, 20))
    assert (c.prompt_bucket, c.new_bucket) == (16, 32)


def test_request_features_shape_and_slack():
    reqs = [_req(0, 8, 8, deadline=100), _req(1, 16, 32)]
    feats = request_features(reqs)
    assert feats.shape == (2, 10)
    assert feats[0, 4] == 100 - 16          # deadline slack
    assert np.isfinite(feats).all()         # no deadline -> capped, not inf


def test_admission_queue_resubmission_jumps_head_and_cancel():
    q = AdmissionQueue()
    q.submit(WorkItem(_req(0, 8, 8)))
    q.submit(WorkItem(_req(1, 8, 8)))
    q.submit(WorkItem(_req(2, 8, 8), is_resubmission=True))
    assert q.pop().req.rid == 2
    assert q.cancel(1) == 1
    assert q.pending_rids() == {0}
    # pop with a predicate skips inadmissible items without dropping them
    assert q.pop(lambda it: it.req.rid == 99) is None
    assert len(q) == 1


# ------------------------------------------------------------- replicas ----

def test_crch_policy_hedges_failure_prone_class_more():
    """The long-decode outlier class must get a strictly larger hedging
    budget than the dominant short class (and than no-replication)."""
    reqs = ([_req(i, 8, 8, seed=1) for i in range(24)] +
            [_req(100 + i, 30, 64, seed=1) for i in range(4)])
    pol = crch_policy(reqs, max_rep=3)
    short_rep = pol.rep_for(reqs[0])
    long_rep = pol.rep_for(reqs[-1])
    assert short_rep == 1
    assert long_rep > short_rep
    assert long_rep > uniform_policy(1).rep_for(reqs[-1])
    assert long_rep <= 3


def test_worker_pool_failure_and_repair():
    pool = WorkerPool(2, 2, mtbf_steps=0.0, mttr_steps=5, seed=0)
    assert pool.worker_of(3) == 1
    assert list(pool.slots_of(0)) == [0, 1]
    pool.force_failure(10, wid=0)
    assert pool.step_failures(10) == [0]
    assert not pool.is_up(0, 12)
    assert pool.is_up(0, 15)
    assert pool.is_up(1, 12)


# -------------------------------------------------------------- snapshot ----

@pytest.mark.parametrize("arch", ["olmo-1b", "rwkv6-3b", "recurrentgemma-2b",
                                  "whisper-small"])
def test_slot_get_set_roundtrip(arch):
    """Row extraction/insertion must be exact for every cache pytree shape:
    dense KV, RWKV recurrent state, RG-LRU hybrid, enc-dec cross-KV."""
    cfg = get_config(arch, tiny=True)
    cache = lm.init_cache(cfg, 3, 16)
    axes = cache_batch_axes(cfg, 16)
    marked = jax.tree.map(lambda l: l + 1.0, cache)
    row = slot_get(marked, axes, 1)
    out = slot_set(cache, axes, 1, row)
    for leaf, a, want in zip(jax.tree.leaves(out), jax.tree.leaves(axes),
                             jax.tree.leaves(marked)):
        got = np.moveaxis(np.asarray(leaf), a, 0)
        ref = np.moveaxis(np.asarray(want), a, 0)
        np.testing.assert_array_equal(got[1], ref[1])   # written row
        assert (got[0] == 0).all() and (got[2] == 0).all()  # untouched


# the snapshot tests' families: dense KV, RWKV state, RG-LRU hybrid, enc-dec,
# MLA's latent cache beside a leading dense layer's
SNAPSHOT_ARCHS = ("olmo-1b", "rwkv6-3b", "recurrentgemma-2b", "whisper-small",
                  "deepseek-v2-lite")


def _leaf_names(cfg, cache_len):
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 2, cache_len))
    return [jax.tree_util.keystr(p, simple=True, separator="/")
            for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]]


@pytest.mark.parametrize("arch,cache_len,append_only", [
    ("olmo-1b", 48, {"k", "v"}),
    ("rwkv6-3b", 48, set()),
    # the local-attention ring stops growing at the window (16 tiny)...
    ("recurrentgemma-2b", 48, set()),
    # ...below it, it is indexed by position and append-only
    ("recurrentgemma-2b", 12, {"k", "v"}),
    ("whisper-small", 48, {"k", "v"}),
    ("deepseek-v2-lite", 48, {"c_kv", "k_pe", "dense_c_kv", "dense_k_pe"}),
])
def test_snapshot_leaf_classification(arch, cache_len, append_only):
    """Leaves that grow with the cache length are append-only along that
    axis and move in chunks; every other leaf is copied whole."""
    cfg = get_config(arch, tiny=True)
    lay = SlotLayout(cfg, cache_len)
    names = _leaf_names(cfg, cache_len)
    assert {names[i] for i, _, _ in lay.rows_leaves} == append_only
    assert {names[i] for i, _ in lay.state_leaves} == \
        set(names) - append_only
    for i, b, s in lay.rows_leaves:
        assert (b, s) == (1, 2)   # (layers, batch, seq, kv heads, head dim)
    assert lay.chunk == (min(cache_len, snapshot.CHUNK_ROWS)
                         if append_only else 0)


# --------------------------------------------------------------- metrics ----

def test_metrics_wastage_accounting():
    m = ServeMetrics()
    r = _req(0, 10, 10, deadline=50)
    m.register(r)
    m.prefill_tokens += 16
    m.decode_tokens += 10
    m.snapshot_overhead_tokens += 2.0
    m.complete(0, 30)
    s = m.summary(100)
    assert s["completed"] == 1
    assert s["in_deadline"] == 1
    assert s["usage_tokens"] == 28
    assert s["wasted_tokens"] == 28 - 20
    assert s["p50_latency"] == 30


# ---------------------------------------------------------------- engine ----

@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("olmo-1b", tiny=True)
    ok, why = engine_supported(cfg)
    assert ok, why
    params = lm.init_params(jax.random.key(0), cfg)
    return cfg, params


def _cache_len_for(cfg, reqs):
    offset = cfg.n_image_tokens or 0
    cache_len = max(offset + prompt_bucket(r.prompt_len) + r.max_new_tokens
                    for r in reqs)
    if cfg.rglru and cfg.window:
        cache_len = max(cache_len, cfg.window)
    return cache_len


def _copy(params):
    """A copy of the weights for one engine: an engine takes the weights
    it is given and releases those it casts, and the module's fixture
    builds several."""
    return jax.tree.map(jnp.copy, params)


def _engine(cfg, params, reqs, *, fail=None, snapshot_lambda=4,
            policy=None, retain_completed=4096, tracer=None):
    cache_len = _cache_len_for(cfg, reqs)
    pool = WorkerPool(2, 2, mtbf_steps=0.0, mttr_steps=6, seed=0)
    if fail is not None:
        pool.force_failure(fail[0], wid=fail[1])
    return ServeEngine(
        cfg, EngineConfig(cache_len=cache_len, q_chunk=32,
                          snapshot_lambda=snapshot_lambda,
                          retain_completed=retain_completed),
        pool=pool, policy=policy or uniform_policy(1), params=_copy(params),
        tracer=tracer)


def _run_engine(cfg, params, reqs, **kw):
    engine = _engine(cfg, params, reqs, **kw)
    for r in reqs:
        engine.submit(r)
    engine.run(max_steps=2_000)
    return engine


def _resume_matches_failure_free(cfg, params, reqs, **kw):
    """Run ``reqs`` failure-free and with worker 0 failing at step 9: every
    request completes and delivers the failure-free tokens exactly
    (Algorithm 3's correctness bar).  Returns the faulty engine."""
    clean = _run_engine(cfg, params, reqs, **kw)
    faulty = _run_engine(cfg, params, reqs, fail=(9, 0), **kw)
    assert len(clean.completed) == len(reqs)
    assert len(faulty.completed) == len(reqs)
    assert faulty.metrics.failures >= 1
    assert faulty.metrics.resubmissions >= 1
    for rid in clean.completed:
        assert clean.completed[rid] == faulty.completed[rid], rid
    return faulty


def test_engine_failure_resume_matches_failure_free(tiny_setup):
    """Mid-decode worker failure + snapshot resume must reproduce the
    failure-free greedy tokens exactly."""
    cfg, params = tiny_setup
    _resume_matches_failure_free(cfg, params, [
        _req(i, 8 + 3 * i, 16, vocab=cfg.vocab_size, seed=3)
        for i in range(4)])


def test_engine_replicated_requests_survive_single_worker_loss(tiny_setup):
    """With a replica on each worker, killing one worker must not trigger a
    resubmission — the surviving copy delivers."""
    cfg, params = tiny_setup
    reqs = [_req(0, 12, 16, vocab=cfg.vocab_size, seed=5)]
    engine = _run_engine(cfg, params, reqs, fail=(6, 0),
                         policy=uniform_policy(2))
    assert engine.completed and engine.metrics.failures >= 1
    assert engine.metrics.resubmissions == 0


def test_engine_rejects_oversized_request(tiny_setup):
    cfg, params = tiny_setup
    engine_req = _req(0, 8, 8, vocab=cfg.vocab_size)
    cache_len = 16
    pool = WorkerPool(1, 2, mtbf_steps=0.0, seed=0)
    engine = ServeEngine(cfg, EngineConfig(cache_len=cache_len, q_chunk=32),
                         pool=pool, policy=uniform_policy(1),
                         params=_copy(params))
    engine.submit(engine_req)
    with pytest.raises(ValueError):
        engine.submit(_req(1, 20, 16, vocab=cfg.vocab_size))


def test_engine_supports_all_families():
    """The family gate is gone: the continuous engine drives every arch."""
    for arch in ("olmo-1b", "rwkv6-3b", "recurrentgemma-2b",
                 "whisper-small", "llava-next-mistral-7b"):
        ok, why = engine_supported(get_config(arch, tiny=True))
        assert ok, f"{arch}: {why}"


def test_engine_idle_slot_cache_row_untouched(tiny_setup):
    """A freed slot's cache row must stay bit-identical while other slots
    keep decoding — stale last_token/pos must be masked out of the batched
    cache write (regression: recurrent state accumulates corruption)."""
    cfg, params = tiny_setup
    reqs = [_req(0, 8, 3, vocab=cfg.vocab_size, seed=9),
            _req(1, 8, 24, vocab=cfg.vocab_size, seed=9)]
    cache_len = _cache_len_for(cfg, reqs)
    pool = WorkerPool(2, 2, mtbf_steps=0.0, seed=0)
    engine = ServeEngine(cfg, EngineConfig(cache_len=cache_len, q_chunk=32),
                         pool=pool, policy=uniform_policy(1),
                         params=_copy(params))
    for r in reqs:
        engine.submit(r)
    while 0 not in engine.completed:
        engine.step()
    freed = [s.sid for s in engine.slots if not s.busy]
    assert freed and any(s.busy for s in engine.slots)
    before = {sid: jax.device_get(engine._get(engine.cache, sid))
              for sid in freed}
    for _ in range(6):
        engine.step()
    for sid in freed:
        after = jax.device_get(engine._get(engine.cache, sid))
        for a, b in zip(jax.tree.leaves(before[sid]),
                        jax.tree.leaves(after)):
            np.testing.assert_array_equal(a, b)


def test_worker_pool_mid_mttr_failure_deferred_not_lost():
    """A sampled failure landing while the worker is already down must not
    be silently absorbed: it strikes again at repair completion."""
    pool = WorkerPool(1, 1, mtbf_steps=1e9, mttr_steps=10, seed=0)
    inj = pool.injectors[0]
    inj.fail_steps = {5, 8}
    assert pool.step_failures(5) == [0]
    assert not pool.is_up(0, 8)
    assert pool.step_failures(8) == []      # mid-MTTR: deferred, not dropped
    assert 8 not in inj.fail_steps
    assert 15 in inj.fail_steps             # rescheduled to repair step
    assert pool.step_failures(15) == [0]    # strikes again once repaired


def test_engine_state_bounded_over_many_requests(tiny_setup):
    """A long-running service must not grow host state without bound:
    completed/request/snapshot entries are evicted FIFO beyond
    ``retain_completed`` and ``active`` never retains empty sets."""
    cfg, params = tiny_setup
    n = 1_000
    reqs = [_req(i, 6, 2, vocab=cfg.vocab_size, seed=11) for i in range(n)]
    engine = _run_engine(cfg, params, reqs, retain_completed=64)
    assert engine.metrics.summary(engine.step_no)["completed"] == n
    assert len(engine.completed) <= 64
    assert len(engine.requests) <= 64
    assert len(engine._completed_order) <= 64
    assert engine.active == {}
    assert len(engine.store) == 0
    # the newest requests are the retained ones
    assert max(engine.completed) == n - 1


ALL_ARCHS = ("rwkv6-3b", "recurrentgemma-2b", "whisper-small",
             "llava-next-mistral-7b", "deepseek-v2-lite")


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_engine_token_parity_with_static_reference(arch):
    """Continuous batching must be output-transparent for every family:
    engine tokens == batch=1 exact-length static greedy tokens."""
    cfg = get_config(arch, tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    reqs = [_req(i, 5 + 2 * i, 8, seed=13, cfg=cfg) for i in range(4)]
    engine = _run_engine(cfg, params, reqs)
    assert len(engine.completed) == len(reqs)
    ref = greedy_reference(params, cfg, reqs, _cache_len_for(cfg, reqs),
                           q_chunk=32)
    for r in reqs:
        assert engine.output(r.rid) == ref[r.rid], r.rid


def test_reference_logits_fed_greedy_tokens_reproduce_them():
    """Fed the greedy tokens, the static path's logits rows are the ones
    greedy decoding chose from: one row per token, argmax == that token."""
    cfg = get_config("olmo-1b", tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    reqs = [_req(i, 5 + 2 * i, 8, seed=13, cfg=cfg) for i in range(3)]
    cache_len = _cache_len_for(cfg, reqs)
    greedy = greedy_reference(params, cfg, reqs, cache_len, q_chunk=32)
    rows = reference_logits(params, cfg, reqs, cache_len, greedy, q_chunk=32)
    for r in reqs:
        assert rows[r.rid].shape == (r.max_new_tokens, cfg.vocab_size)
        assert rows[r.rid].argmax(-1).tolist() == greedy[r.rid], r.rid


def test_engine_rwkv_failure_resume_matches_failure_free():
    """Recurrent-state snapshot restore must reproduce the failure-free
    greedy tokens exactly (the state is NOT reconstructible from the KV
    overwrite argument — the snapshot itself must be exact)."""
    cfg = get_config("rwkv6-3b", tiny=True)
    params = lm.init_params(jax.random.key(1), cfg)
    _resume_matches_failure_free(cfg, params, [
        _req(i, 7 + 3 * i, 16, seed=17, cfg=cfg) for i in range(4)])


@pytest.mark.parametrize("arch", SNAPSHOT_ARCHS)
def test_delta_snapshot_resume_matches_failure_free(arch, monkeypatch):
    """Worker failures with a short snapshot cadence and 8-row chunks: the
    requests resume from snapshots that extended their lineage, and deliver
    the failure-free tokens exactly."""
    monkeypatch.setattr(snapshot, "CHUNK_ROWS", 8)
    cfg = get_config(arch, tiny=True)
    params = lm.init_params(jax.random.key(2), cfg)
    faulty = _resume_matches_failure_free(cfg, params, [
        _req(i, 8 + 3 * i, 16, seed=19, cfg=cfg) for i in range(4)],
        snapshot_lambda=2)
    m = faulty.metrics
    assert m.restores >= 1 and m.snapshot_restore_failures == 0
    if faulty.layout.rows_leaves:
        assert m.snapshot_deltas >= 1


def test_delta_snapshot_resume_on_seq_sharded_cache():
    """On four devices the KV cache is split along its sequence axis, and a
    chunk (6 rows, a shard holding 12) is read and written by the shard
    that holds it: resuming from delta snapshots still delivers the
    failure-free tokens.  In a child process of its own, since the device
    count is fixed when JAX starts."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.serve import (EngineConfig, Request, ServeEngine,
                                 WorkerPool, snapshot, uniform_policy)
        snapshot.CHUNK_ROWS = 8
        cfg = get_config("olmo-1b", tiny=True)
        params = lm.init_params(jax.random.key(2), cfg)
        rng = np.random.default_rng(19)
        reqs = [Request(rid=i, prompt=rng.integers(
                    1, cfg.vocab_size, 8 + 3 * i).astype(np.int32),
                    max_new_tokens=16, arrival=0, deadline=None)
                for i in range(4)]

        def run(fail):
            pool = WorkerPool(2, 2, mtbf_steps=0.0, mttr_steps=6, seed=0)
            if fail:
                pool.force_failure(9, wid=0)
            with use_rules(make_mesh(4)):
                e = ServeEngine(cfg, EngineConfig(
                    cache_len=48, q_chunk=32, snapshot_lambda=2),
                    pool=pool, policy=uniform_policy(1),
                    params=jax.tree.map(jnp.copy, params))
                for r in reqs:
                    e.submit(r)
                e.run(max_steps=2000)
            return e

        clean, faulty = run(False), run(True)
        m = faulty.metrics
        assert faulty.cache["k"].sharding.spec[2] == "model"
        assert faulty.layout.chunk == 6
        assert m.restores >= 1 and m.snapshot_deltas >= 1
        assert m.snapshot_restore_failures == 0
        assert len(faulty.completed) == len(reqs)
        assert clean.completed == faulty.completed

        # every chunk of one slot reads as the cache holds it, and a write
        # lands in that slot's chunk alone
        C, (i, b, s) = faulty.layout.chunk, faulty.layout.rows_leaves[0]
        assert (b, s) == (1, 2)
        before = np.array(jax.tree.leaves(faulty.cache)[i])
        cache = faulty.cache
        for start in range(0, 48, C):
            got = faulty._read_rows(cache, np.int32(3), np.int32(start))[0]
            want = np.moveaxis(before[:, 3, start:start + C], 1, 0)
            assert (np.asarray(got) == want).all(), start
            new = rng.normal(size=want.shape).astype(want.dtype)
            cache = faulty._write_rows(
                cache, np.int32(2), np.int32(start),
                [new] + [np.zeros_like(r) for r in jax.device_get(
                    faulty._read_rows(cache, np.int32(2),
                                      np.int32(start)))[1:]])
            after = jax.device_get(jax.tree.leaves(cache)[i])
            assert (np.moveaxis(after[:, 2, start:start + C], 1, 0)
                    == new).all(), start
            before[:, 2, start:start + C] = after[:, 2, start:start + C]
            assert (after == before).all(), start
        print("ok")
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code],
                         cwd=Path(__file__).resolve().parents[1], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("ok")


@pytest.mark.parametrize("arch", SNAPSHOT_ARCHS)
def test_snapshot_copies_rows_written_since_last(arch, monkeypatch):
    """A lineage's first snapshot copies the chunks holding rows [0, pos);
    each later one only those from its first unsealed chunk (at most 2 at
    a cadence of no more than a chunk), plus the whole-copied leaves every
    time.  ``snapshot_deltas`` counts the later ones."""
    monkeypatch.setattr(snapshot, "CHUNK_ROWS", 8)
    cfg = get_config(arch, tiny=True)
    params = lm.init_params(jax.random.key(2), cfg)
    reqs = [_req(0, 11, 32, seed=23, cfg=cfg)]   # cache_len 48
    e = _engine(cfg, params, reqs, snapshot_lambda=5)
    lay = e.layout
    i32 = jax.ShapeDtypeStruct((), np.int32)
    shapes = (jax.eval_shape(lay.slot_read_rows, e.cache, i32, i32)
              + jax.eval_shape(lay.slot_read_state, e.cache, i32))
    chunk_b = sum(x.size * x.dtype.itemsize for x in shapes[:len(
        lay.rows_leaves)])
    state_b = sum(x.size * x.dtype.itemsize for x in shapes[len(
        lay.rows_leaves):])
    e.submit(reqs[0])
    copied = []   # (pos, bytes) per snapshot
    while e.pending():
        n, b = e.metrics.snapshots, e.metrics.snapshot_bytes
        e.step()
        if e.metrics.snapshots > n:
            slot = next(s for s in e.slots if s.rid == 0)
            copied.append((slot.pos, e.metrics.snapshot_bytes - b))
    assert len(copied) >= 4
    C = lay.chunk
    prev = 0
    for k, (pos, nbytes) in enumerate(copied):
        chunks = -(-pos // C) - prev // C if C else 0
        assert nbytes == chunks * chunk_b + state_b, (k, pos)
        if k and C:
            assert 1 <= chunks <= 2
        prev = pos
    deltas = len(copied) - 1 if C and copied[0][0] >= C else 0
    assert e.metrics.snapshot_deltas == deltas
    assert e.metrics.registry.value(
        "serve_events_total", kind="snapshot_delta") == deltas


# -------------------------------------------------------------- tracing ----

class TickClock:
    """Strictly increasing fake clock: every reading is one tick later."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


# each engine span and the span it must open inside
SPAN_PARENT = {
    "serve.tick.faults": "serve.tick",
    "serve.tick.shed": "serve.tick",
    "serve.tick.admit": "serve.tick",
    "serve.decode": "serve.tick",
    "serve.tick.snapshots": "serve.tick",
    "serve.prefill": "serve.tick.admit",
    "serve.restore": "serve.tick.admit",
    "serve.restore.verify": "serve.restore",
    "serve.restore.write": "serve.restore",
    "serve.decode.wait": "serve.decode",
    "serve.prefill.wait": "serve.prefill",
    "serve.snapshot.take": "serve.tick.snapshots",
    "serve.snapshot.copy": "serve.snapshot.take",
    "serve.snapshot.digest": "serve.snapshot.take",
}


def _traced_failure_run(tiny_setup, **kw):
    cfg, params = tiny_setup
    reqs = [_req(i, 8 + 3 * i, 16, vocab=cfg.vocab_size, seed=3)
            for i in range(4)]
    rec = FlightRecorder(1 << 16)
    tracer = Tracer(rec, clock=TickClock(), **kw)
    engine = _run_engine(cfg, params, reqs, fail=(9, 0), tracer=tracer)
    return engine, reqs, rec.snapshot()


def test_engine_tick_spans_nest_under_their_parents(tiny_setup):
    engine, reqs, events = _traced_failure_run(tiny_setup)
    spans = {e["span_id"]: e for e in events if e["type"] == "span"}
    names = {e["name"] for e in spans.values()}
    # the run exercises every phase: failures, restores and snapshots
    assert set(SPAN_PARENT) | {"serve.tick"} <= names
    ticks = [e for e in spans.values() if e["name"] == "serve.tick"]
    assert len(ticks) == engine.step_no
    assert all(e["parent_id"] is None for e in ticks)
    for e in spans.values():
        parent = spans[e["parent_id"]] if e["parent_id"] else None
        want = SPAN_PARENT.get(e["name"])
        if want is None:
            continue
        assert parent is not None and parent["name"] == want, e
        assert parent["t0"] < e["t0"] <= e["t1"] < parent["t1"], e
    # serve.start: one per copy that takes a slot, one first per request
    starts = [e["attrs"] for e in events if e["name"] == "serve.start"]
    assert all(a["waited_s"] >= 0 for a in starts)
    firsts = collections.Counter(a["rid"] for a in starts if a["first"])
    assert firsts == collections.Counter(r.rid for r in reqs)
    resumed = [a for a in starts if a["resumed"]]
    assert resumed and not any(a["first"] for a in resumed)
    assert len(resumed) == engine.metrics.restores
    # snapshot bytes: at this cache length one chunk spans the whole
    # sequence, so each snapshot copies one slot row
    assert engine.layout.chunk == engine.ecfg.cache_len
    row = jax.device_get(engine._get(engine.cache, 0))
    per = sum(leaf.nbytes for leaf in jax.tree.leaves(row))
    assert engine.metrics.snapshot_bytes == per * engine.metrics.snapshots
    assert engine.metrics.registry.value(
        "serve_bytes_total", kind="snapshot") == engine.metrics.snapshot_bytes


class _Probe:
    """A device result whose host conversion reads the tracer's clock."""

    def __init__(self, value, clock, reads):
        self.value, self.clock, self.reads = value, clock, reads

    def __getitem__(self, i):
        return _Probe(self.value[i], self.clock, self.reads)

    def __array__(self, dtype=None, copy=None):
        self.reads.append(self.clock())
        return np.asarray(self.value, dtype=dtype)


def test_wait_spans_hold_the_host_conversion(tiny_setup):
    cfg, params = tiny_setup
    reqs = [_req(i, 8 + 3 * i, 6, vocab=cfg.vocab_size, seed=3)
            for i in range(3)]
    rec = FlightRecorder(1 << 16)
    clock = TickClock()
    engine = _engine(cfg, params, reqs, tracer=Tracer(rec, clock=clock))
    reads = {"serve.decode.wait": [], "serve.prefill.wait": []}
    serve0, prefill0 = engine._serve, engine._prefill

    def serve(*args):
        nxt, logits, cache = serve0(*args)
        return _Probe(nxt, clock, reads["serve.decode.wait"]), logits, cache

    def prefill(seq):
        fn = prefill0(seq)

        def run(*args):
            logits, row1 = fn(*args)
            return _Probe(logits, clock, reads["serve.prefill.wait"]), row1
        return run

    engine._serve, engine._prefill = serve, prefill
    for r in reqs:
        engine.submit(r)
    engine.run(max_steps=200)
    assert len(engine.completed) == len(reqs)
    spans = [e for e in rec.snapshot() if e["type"] == "span"]
    by_id = {e["span_id"]: e for e in spans}
    for name, times in reads.items():
        waits = [e for e in spans if e["name"] == name]
        assert times and len(times) == len(waits), name
        for t, w in zip(sorted(times), sorted(waits, key=lambda e: e["t0"])):
            assert w["t0"] < t < w["t1"], name
            # the decode and prefill spans close after their host sync
            assert by_id[w["parent_id"]]["t1"] > t


def test_slot_programs_have_stable_names(tiny_setup):
    cfg, params = tiny_setup
    reqs = [_req(0, 8, 4, vocab=cfg.vocab_size)]
    e = _engine(cfg, params, reqs)
    row = slot_get(e.cache, e.axes, 0)
    row1 = jax.tree.map(lambda l, a: jax.numpy.expand_dims(l, a), row,
                        e.axes)
    for fn, args, name in ((e._get, (e.cache, 0), "slot_read"),
                           (e._set, (e.cache, 0, row), "slot_write"),
                           (e._insert, (e.cache, 0, row1), "cache_insert")):
        assert f"module @jit_{name} " in fn.lower(*args).as_text(), name
    # the snapshot's chunk programs, compiled when the engine is built
    for fn, name in ((e._read_rows, "slot_read_rows"),
                     (e._write_rows, "slot_write_rows")):
        assert f"HloModule jit_{name}," in fn.as_text(), name


def test_annotate_sink_leaves_tokens_and_counters_identical(tiny_setup):
    plain, _, _ = _traced_failure_run(tiny_setup)
    annotated, _, events = _traced_failure_run(
        tiny_setup, annotate=jax.profiler.TraceAnnotation)
    assert events and plain.completed == annotated.completed
    assert plain.step_no == annotated.step_no
    assert (plain.metrics.registry.to_json()
            == annotated.metrics.registry.to_json())
    assert plain.metrics.snapshots > 0 and plain.metrics.restores > 0
