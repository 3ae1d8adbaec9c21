"""Slot-based continuous-batching decode engine with fault tolerance.

The serving counterpart of the CheckpointHEFT runtime (paper Algorithm 3):

* a fixed pool of decode *slots* (n_workers x slots_per_worker) advances one
  token per engine step via a single jit'd ``make_serve_step`` call with a
  per-slot position vector — new requests prefill into freed slots while
  live requests keep decoding (no static-batch barrier);
* each admitted request runs ``repCount`` copies on distinct workers
  (:class:`~repro.serve.replicas.ReplicaPolicy`, Algorithm 1); the first
  copy to emit its full budget wins, siblings are cancelled (their tokens
  are the paper's late-replica wastage);
* a worker failure kills all its slots (Algorithm 3 Case 1); only when the
  *last* copy of a request dies is it resubmitted (steps 14-15/25-26) —
  resuming from its latest decode snapshot when one exists (steps 22-23),
  else re-prefilling from scratch (steps 16-21);
* snapshots are taken every ``lambda`` generated tokens per slot, with
  ``lambda`` re-derived online by :class:`repro.ft.interval.DynamicInterval`
  from observed failures (Lemma 3.1).  Each copies off the device, and
  hashes, only the append-only cache rows the slot wrote since its previous
  snapshot, plus any state copied whole (:mod:`repro.serve.snapshot`).

Supported model families: **all of them**.  Dense / MoE causal-KV
architectures prefill into right-padded buckets (causality + the
overwrite-before-admit cache argument make padding safe).  Recurrent-state
(RWKV) and rolling-window hybrid (RG-LRU) caches are *not* padding-safe —
pad positions would advance the recurrent state — so those families prefill
per request at the exact prompt length instead of a bucket.  Encoder-decoder
and multimodal requests carry their side inputs (encoder frames, image
embeds) on the :class:`~repro.serve.queue.Request`; the derived per-slot
state (cross-attention K/V, image-token KV rows) lands inside the slot's
cache row, so freed-slot reuse and snapshot/restore carry it automatically.
Idle slots are masked out of the batched cache write every tick (stale
``last_token``/``pos`` must never rewrite a freed row), and completed
request state is evicted FIFO beyond ``retain_completed`` so a long-running
service holds bounded host memory.

Chaos hardening (``repro.chaos`` serving-side recovery paths): a
:class:`~repro.chaos.ChaosEngine` passed as ``chaos=`` injects the wider
fault taxonomy each tick — ``host_crash`` / ``capacity_loss`` take workers
down (the latter for its own MTTR window), ``slowdown`` stalls a worker's
slots without losing state (they are masked out of the batched decode until
the straggler recovers, then resume bit-identically), and
``snapshot_corrupt`` flips bytes in a stored decode snapshot.  Recovery:
snapshots are checksum-verified before a resume — a corrupt one is
quarantined and the request re-prefills from scratch; under capacity loss
the admission queue runs **deadline-aware load shedding** (degraded-mode
serving): queued hedge copies collapse to one, and a queued request that
provably cannot meet its deadline even if admitted this very tick is shed,
lowest request class (priority, then slack) first.  A request with a live
copy past its first token is *never* shed — the ``past_first_token_drops``
metric is the tripwire proving it.

Tracing: every tick is a ``serve.tick`` span whose children cover its
phases — ``serve.tick.faults`` (chaos and worker failures),
``serve.tick.shed``, ``serve.tick.admit`` (holding ``serve.prefill`` and
``serve.restore``), ``serve.decode`` and ``serve.tick.snapshots`` (one
``serve.snapshot.take`` per snapshot: ``.copy`` off the device, then
``.digest``).  A span that waits for the device closes only after the host
holds the result, inside a ``*.wait`` child (``serve.decode.wait``,
``serve.prefill.wait``), so host work and device waits can be told apart.
For an MoE model the ``serve.decode`` span also carries the tick's expert
counts (``moe_experts_active``, ``moe_tokens``; see ``ServeMetrics``),
fetched in the same transfer as the tick's tokens.
Building the engine casts its weights inside a ``serve.weights`` span.
With a tracer whose spans are also profiler annotations the phases line up
with the device's operations.  ``serve.start`` reports the queue wait of
each copy that takes a slot.
"""
from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.chaos import faults
from repro.distributed import params as pshard
from repro.distributed.sharding import current_mesh
from repro.distributed.steps import make_prefill_step, make_serve_step
from repro.ft.interval import DynamicInterval
from repro.models import lm
from repro.models.config import ModelConfig
from repro.obs.trace import NULL_TRACER

from .metrics import ServeMetrics
from .queue import AdmissionQueue, Request, WorkItem, prompt_bucket
from .replicas import ReplicaPolicy, WorkerPool, uniform_policy
from .snapshot import (DecodeSnapshot, Lineage, SlotLayout, SnapshotStore,
                       slot_get, slot_set)

__all__ = ["EngineConfig", "ServeEngine", "engine_supported",
           "prefill_inputs", "prefill_len", "take_serving_weights"]


def engine_supported(cfg: ModelConfig) -> tuple[bool, str]:
    """Whether the continuous-batching engine can drive ``cfg``.

    Every assigned family is supported: recurrent state (RWKV) and
    rolling-window hybrids (RG-LRU) via exact-length per-request prefill,
    encoder-decoder and multimodal via per-request side inputs whose derived
    state lives in the slot's cache row.  Kept as a predicate so a future
    family can still be gated with a reason string.
    """
    if cfg.rwkv and cfg.d_model % 64 != 0:
        return False, "rwkv d_model must be a multiple of the 64 head size"
    return True, ""


def prefill_len(cfg: ModelConfig, prompt_len: int) -> int:
    """Prefill length of a prompt: its power-of-two bucket, or the exact
    length for the recurrent families — recurrent state treats every
    position as a state update, so pad positions are not maskable after the
    fact."""
    return prompt_len if (cfg.rwkv or cfg.rglru) else prompt_bucket(prompt_len)


def prefill_inputs(cfg: ModelConfig, req: Request, seq: int) -> dict:
    """Batch=1 prefill inputs: the prompt right-padded to ``seq`` plus the
    request's side inputs (encoder frames, image embeds)."""
    padded = np.zeros((1, seq), np.int32)
    padded[0, :req.prompt_len] = np.asarray(req.prompt, np.int32)
    batch = {"tokens": jnp.asarray(padded)}
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(np.asarray(req.frames, np.float32))[None]
    if cfg.n_image_tokens:
        batch["image_embeds"] = jnp.asarray(
            np.asarray(req.image_embeds, np.float32))[None]
    return batch


# one elementwise program a leaf shape: the copy keeps the leaf's sharding
_cast = jax.jit(lambda x, dtype: x.astype(dtype), static_argnums=1)


def take_serving_weights(params, cfg: ModelConfig):
    """``lm.serving_params`` of ``params``, cast leaf by leaf on each
    leaf's own devices and sharding.  Each given leaf that is cast is
    released once its copy exists, so the peak is the given tree plus one
    leaf; the leaves left as they are are the given ones."""
    want = jax.eval_shape(functools.partial(lm.serving_params, cfg=cfg),
                          params)

    def take(leaf, to):
        if leaf.dtype == to.dtype:
            return leaf
        out = _cast(leaf, to.dtype).block_until_ready()
        leaf.delete()
        return out

    return jax.tree.map(take, params, want)


def init_slot_cache(cfg: ModelConfig, slots: int, cache_len: int):
    """Zero decode cache for ``slots`` slots.  Inside a ``use_rules(mesh)``
    scope it takes the mesh layout of ``params.cache_specs``: slots on
    ``data``, the KV sequence on ``model`` (the ``kv_seq`` rule)."""
    cache = lm.init_cache(cfg, slots, cache_len)
    mesh = current_mesh()
    if mesh is None:
        return cache
    specs = pshard.cache_specs(cache, cfg, mesh)
    return jax.device_put(cache, jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs))


@dataclasses.dataclass
class EngineConfig:
    cache_len: int = 128
    q_chunk: int = 64
    snapshots_enabled: bool = True
    snapshot_lambda: float | None = None   # None -> DynamicInterval (Lemma 3.1)
    snapshot_gamma: float = 1.0            # per-snapshot cost, token-steps
    prior_mtbf_steps: float = 200.0
    lam_min: float = 2.0
    lam_max: float = 256.0
    # completed requests retained for ``output()`` before FIFO eviction of
    # their request / completed / snapshot entries (bounds engine host state
    # for a long-running service)
    retain_completed: int = 4096
    # degraded mode: deadline-aware admission-queue load shedding under
    # capacity loss (hedge copies collapse first, then provably-late
    # requests are shed lowest-class-first)
    shed_enabled: bool = True
    # queue-length-priced admission: fresh arrivals are rejected with a
    # retry_after hint once queue depth crosses this bound, so the queue
    # stays bounded under sustained capacity loss (None = unbounded)
    max_queue_depth: int | None = None
    # keep every logits row the engine computes in ``logit_log`` as
    # (rid, token index, row) — for checks against a reference; each decode
    # tick then copies the whole (slots, V) logits to the host
    record_logits: bool = False


@dataclasses.dataclass
class _Slot:
    sid: int
    busy: bool = False
    rid: int = -1
    copy_id: int = 0
    pos: int = 0                 # absolute position of the next decode write
    last_token: int = 0
    max_new: int = 0
    since_snapshot: int = 0
    req: Request | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)


class ServeEngine:
    """The continuous-batching engine over ``pool``'s slots.

    The engine takes the weights it is given: at build it casts each leaf
    the model reads only in the compute dtype (``lm.serving_params``) and
    releases the given leaf, so a caller that needs the float32 weights
    afterwards makes them again or passes a copy.  ``self.params`` is the
    tree every program of the engine is called with."""

    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig | None = None, *,
                 pool: WorkerPool, policy: ReplicaPolicy | None = None,
                 params=None, metrics: ServeMetrics | None = None,
                 chaos=None, seed: int = 0, tracer=None):
        ok, why = engine_supported(cfg)
        if not ok:
            raise ValueError(f"{cfg.name}: {why}")
        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        if cfg.rglru and cfg.window and self.ecfg.cache_len < cfg.window:
            raise ValueError(
                f"{cfg.name}: cache_len {self.ecfg.cache_len} < local-"
                f"attention window {cfg.window}; the rolling KV ring and the "
                f"decode slot index (pos % window) would disagree")
        if cfg.is_encdec and self.ecfg.cache_len > cfg.max_decode_len:
            raise ValueError(
                f"{cfg.name}: cache_len {self.ecfg.cache_len} exceeds the "
                f"learned decoder position table ({cfg.max_decode_len})")
        self.pool = pool
        self.chaos = chaos   # repro.chaos.ChaosEngine | None
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.shed: set[int] = set()   # rids dropped in degraded mode
        self.policy = policy or uniform_policy(1)
        self.metrics = metrics or ServeMetrics()
        # before the cache: the peak is the given weights plus one leaf
        self.params = self._take_weights(
            params if params is not None
            else lm.init_params(jax.random.key(seed), cfg))
        self.queue = AdmissionQueue(max_depth=self.ecfg.max_queue_depth,
                                    drain_rate=max(pool.n_slots, 1))
        self.rejected: dict[int, int] = {}   # rid -> retry_after hint
        self.store = SnapshotStore()
        self.slots = [_Slot(sid) for sid in range(pool.n_slots)]
        self.active: dict[int, set[int]] = {}      # rid -> live slot ids
        self.completed: dict[int, list[int]] = {}  # rid -> delivered tokens
        self.requests: dict[int, Request] = {}
        self._started: set[int] = set()   # rids a copy of which took a slot
        self._completed_order: collections.deque[int] = collections.deque()
        self.step_no = 0
        self.interval = DynamicInterval(
            gamma_s=self.ecfg.snapshot_gamma, lam_min=self.ecfg.lam_min,
            lam_max=self.ecfg.lam_max,
            prior_mtbf_s=self.ecfg.prior_mtbf_steps)

        cache_len = self.ecfg.cache_len
        self.cache = init_slot_cache(cfg, pool.n_slots, cache_len)
        self.layout = SlotLayout(
            cfg, cache_len, jax.tree.map(lambda l: l.sharding, self.cache))
        self.axes = self.layout.batch_axes
        self._serve = jax.jit(make_serve_step(cfg, cache_axes=self.axes),
                              donate_argnums=(1,))
        axes = self.axes

        # named, so each compiles to its own module (jit_slot_read, ...)
        # and a profile tells the three apart
        def slot_read(cache, sid):
            return slot_get(cache, axes, sid)

        def slot_write(cache, sid, row):
            return slot_set(cache, axes, sid, row)

        def cache_insert(cache, sid, row1):
            row = jax.tree.map(lambda l, a: jnp.squeeze(l, a), row1, axes)
            return slot_set(cache, axes, sid, row)

        self._get = jax.jit(slot_read)
        self._set = jax.jit(slot_write, donate_argnums=(0,))
        self._insert = jax.jit(cache_insert, donate_argnums=(0,))
        self._compile_snapshot_programs()
        self._lineage = [Lineage(self.layout.chunk) for _ in self.slots]
        self._prefill_fns: dict[int, callable] = {}
        self.logit_log: list[tuple[int, int, np.ndarray]] = []

    def _take_weights(self, params):
        """``take_serving_weights`` inside a ``serve.weights`` span; sets
        ``serve_weight_bytes{dtype=...}``."""
        with self.tracer.span("serve.weights"):
            params = take_serving_weights(params, self.cfg)
            nbytes = collections.Counter()
            for leaf in jax.tree.leaves(params):
                nbytes[leaf.dtype.name] += leaf.nbytes
            gauge = self.metrics.registry.gauge(
                "serve_weight_bytes", "bytes of the serving weights, by "
                "dtype", ("dtype",))
            for name, n in nbytes.items():
                gauge.set(n, dtype=name)
        return params

    def _compile_snapshot_programs(self) -> None:
        """Compile the snapshot's chunk and state programs now, against the
        cache's own shapes and shardings: one shape each serves every
        position, so taking or restoring a snapshot never compiles."""
        lay = self.layout
        cache = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=l.sharding), self.cache)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)

        def compile_(fn, *args, donate=()):
            return jax.jit(fn, donate_argnums=donate).lower(*args).compile()

        def host(shapes):
            return [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in shapes]

        self._read_rows = self._write_rows = None
        self._read_state = self._write_state = None
        if lay.rows_leaves:
            rows = host(jax.eval_shape(lay.slot_read_rows, cache, i32, i32))
            self._read_rows = compile_(lay.slot_read_rows, cache, i32, i32)
            self._write_rows = compile_(lay.slot_write_rows, cache, i32, i32,
                                        rows, donate=(0,))
        if lay.state_leaves:
            state = host(jax.eval_shape(lay.slot_read_state, cache, i32))
            self._read_state = compile_(lay.slot_read_state, cache, i32)
            self._write_state = compile_(lay.slot_write_state, cache, i32,
                                         state, donate=(0,))

    # -- submission ----------------------------------------------------------
    def submit(self, req: Request) -> int:
        """Enqueue a request; returns its replication count (0 = rejected on
        arrival by the queue-depth bound, with the retry-after hint recorded
        in ``self.rejected[rid]`` and the ``rejected_on_arrival`` metric)."""
        bucket = prompt_bucket(req.prompt_len)
        offset = self.cfg.n_image_tokens or 0
        if offset + bucket + req.max_new_tokens > self.ecfg.cache_len:
            raise ValueError(
                f"request {req.rid}: image tokens {offset} + bucket {bucket} "
                f"+ max_new {req.max_new_tokens} exceeds cache_len "
                f"{self.ecfg.cache_len}")
        if self.cfg.is_encdec and req.frames is None:
            raise ValueError(
                f"request {req.rid}: {self.cfg.name} needs per-request "
                f"encoder frames")
        if offset and req.image_embeds is None:
            raise ValueError(
                f"request {req.rid}: {self.cfg.name} needs per-request "
                f"image embeds")
        self.metrics.register(req)
        rep = self.policy.rep_for(req)
        now = self.tracer.clock()
        retry_after = self.queue.admit(
            [WorkItem(req, copy_id=k, enqueued_at=now) for k in range(rep)])
        if retry_after is not None:
            self.rejected[req.rid] = retry_after
            self.metrics.mark_rejected(req.rid, self.step_no, retry_after)
            self.tracer.event("serve.reject", rid=req.rid,
                              retry_after=retry_after)
            return 0
        self.requests[req.rid] = req
        self.tracer.event("serve.admit", rid=req.rid, rep=rep)
        return rep

    # -- chaos injection (repro.chaos taxonomy) ------------------------------
    def _apply_chaos(self, t: int) -> None:
        for ev in self.chaos.events_at(t):
            if ev.kind == faults.HOST_CRASH:
                for wid in (ev.targets or (0,)):
                    self.pool.force_failure(t, wid % self.pool.n_workers)
            elif ev.kind == faults.CAPACITY_LOSS:
                wids = sorted({w % self.pool.n_workers
                               for w in (ev.targets or (0,))})
                self.pool.force_outage(t, wids, ev.duration)
                self.metrics.capacity_events += 1
            elif ev.kind == faults.SLOWDOWN:
                for wid in (ev.targets or (0,)):
                    self.pool.slow(wid % self.pool.n_workers,
                                   t + ev.duration)
                self.metrics.slowdown_events += 1
            elif ev.kind == faults.SNAPSHOT_CORRUPT:
                self.metrics.snapshots_corrupted += \
                    self.store.corrupt(ev.seed)
            # ckpt_corrupt / nan_poison are training-side faults: no-op here

    # -- failures (Algorithm 3 Case 1) ---------------------------------------
    def _on_worker_failures(self, t: int) -> None:
        for wid in self.pool.step_failures(t):
            self.metrics.failures += 1
            self.tracer.event("serve.worker_failure", worker=wid, step=t)
            self.interval.record_failure(float(t))
            self.interval.record_repair(float(self.pool.mttr_steps))
            for sid in self.pool.slots_of(wid):
                slot = self.slots[sid]
                if slot.busy:
                    self._kill_copy(slot, resubmit_if_last=True)

    def _release(self, slot: _Slot) -> None:
        """Free a slot and scrub its decode registers: a freed slot's stale
        ``rid``/``pos``/``last_token`` must never reach the serve step (its
        cache row is additionally masked out of the batched write)."""
        slot.busy = False
        slot.rid = -1
        slot.copy_id = 0
        slot.pos = 0
        slot.last_token = 0
        slot.max_new = 0
        slot.since_snapshot = 0
        slot.req = None
        slot.tokens = []
        self._lineage[slot.sid] = Lineage(self.layout.chunk)

    def _kill_copy(self, slot: _Slot, *, resubmit_if_last: bool) -> None:
        rid = slot.rid
        had_tokens = bool(slot.tokens)
        live = self.active.get(rid, set())
        live.discard(slot.sid)
        if not live:
            self.active.pop(rid, None)   # prune: empty sets must not linger
        self._release(slot)
        if rid in self.shed:
            # tripwire: shedding must never have dropped a request that was
            # already past its first token (the guard in _shed forbids it)
            if had_tokens:
                self.metrics.past_first_token_drops += 1
            return
        if not resubmit_if_last or rid in self.completed:
            return
        # resubmit only when every copy has failed AND none is still queued
        if not live and rid not in self.queue.pending_rids():
            snap = (self.store.get(rid)
                    if self.ecfg.snapshots_enabled else None)
            self.queue.submit(WorkItem(self.requests[rid], copy_id=0,
                                       snapshot=snap, is_resubmission=True,
                                       enqueued_at=self.tracer.clock()))
            self.metrics.resubmissions += 1
            self.tracer.recovery("host_crash", rid=rid,
                                 from_snapshot=snap is not None)

    # -- degraded mode: deadline-aware load shedding -------------------------
    def _min_finish_step(self, item: WorkItem, t: int) -> int:
        """Earliest step this item could complete if admitted at ``t``.

        A fresh prefill emits its first token at the admit tick AND the slot
        joins the same tick's batched decode (two tokens by end of step
        ``t``); a snapshot resume re-enters with ``e`` tokens banked and
        decodes at ``t``.  The bound must never overshoot — shedding a
        request that could still have met its deadline is forbidden."""
        emitted = len(item.snapshot.tokens) if item.snapshot is not None else 0
        need = item.req.max_new_tokens
        if emitted >= need:
            return t
        return t + need - max(emitted, 1) - 1

    @staticmethod
    def _shed_rank(req: Request):
        """Shedding order: lowest request class first — priority ascending,
        then tightest deadline slack (the least likely to finish)."""
        slack = (req.deadline - req.arrival - req.total_work
                 if req.deadline is not None else float("inf"))
        return (req.priority, slack)

    def _shed(self, t: int) -> None:
        if not self.ecfg.shed_enabled or not len(self.queue):
            return
        # capacity loss -> stop paying for hedges: collapse queued copies
        up_slots = sum(self.pool.slots_per_worker
                       for w in range(self.pool.n_workers)
                       if self.pool.is_up(w, t))
        busy = sum(s.busy for s in self.slots)
        if (up_slots < self.pool.n_slots
                and len(self.queue) > max(up_slots - busy, 0)):
            self.metrics.hedge_drops += self.queue.drop_hedges()
        # shed requests that provably cannot meet their deadline even if
        # admitted this very tick, lowest request class first
        doomed: dict[int, Request] = {}
        for item in self.queue.items():
            dl = item.req.deadline
            if dl is None or self._min_finish_step(item, t) <= dl:
                continue
            doomed.setdefault(item.req.rid, item.req)
        for rid, req in sorted(doomed.items(),
                               key=lambda kv: self._shed_rank(kv[1])):
            if self.active.get(rid):
                # never shed a request with a live copy — once past its
                # first token it either completes or is resubmitted
                continue
            self.queue.cancel(rid)
            self.shed.add(rid)
            self.metrics.mark_shed(rid, t)
            self.tracer.recovery("capacity_loss", rid=rid, action="shed",
                                 step=t)

    # -- admission into freed slots ------------------------------------------
    def _admit(self, t: int) -> None:
        for slot in self.slots:
            wid = self.pool.worker_of(slot.sid)
            if (slot.busy or not self.pool.is_up(wid, t)
                    or self.pool.is_slow(wid, t)):
                continue

            def admissible(item: WorkItem, _wid=wid) -> bool:
                rid = item.req.rid
                if (rid in self.completed or rid in self.shed
                        or item.req.arrival > t):
                    return False
                others = self.active.get(rid, set())
                return all(self.pool.worker_of(s) != _wid for s in others)

            item = self.queue.pop(admissible)
            if item is not None:
                self._start(slot, item, t)

    def _prefill(self, seq: int):
        """Jitted prefill keyed by prompt length.  Dense/MoE/enc-dec/VLM key
        on the power-of-two bucket; the recurrent families key on the exact
        prompt length (one compile per distinct length — the price of
        padding-unsafe state)."""
        fn = self._prefill_fns.get(seq)
        if fn is None:
            fn = jax.jit(make_prefill_step(
                self.cfg, self.ecfg.cache_len,
                q_chunk=min(self.ecfg.q_chunk, seq), with_last_idx=True))
            self._prefill_fns[seq] = fn
        return fn

    def _start(self, slot: _Slot, item: WorkItem, t: int) -> None:
        req = item.req
        tr = self.tracer
        slot.busy = True
        slot.rid = req.rid
        slot.copy_id = item.copy_id
        slot.max_new = req.max_new_tokens
        slot.req = req
        slot.since_snapshot = 0
        self.active.setdefault(req.rid, set()).add(slot.sid)
        first = req.rid not in self._started
        self._started.add(req.rid)
        snap: DecodeSnapshot | None = item.snapshot
        tr.event("serve.start", rid=req.rid, copy_id=item.copy_id,
                 resumed=snap is not None, first=first,
                 waited_s=tr.clock() - item.enqueued_at)
        if snap is not None:
            with tr.span("serve.restore", rid=req.rid, step=t):
                with tr.span("serve.restore.verify"):
                    intact = self.store.verify(snap)
                if intact:
                    with tr.span("serve.restore.write"):
                        self._restore(slot.sid, snap)
            if not intact:
                # checksum mismatch: quarantine the snapshot and fall back to
                # a full re-prefill — never resume from garbage decode state
                self.metrics.snapshot_restore_failures += 1
                self.store.drop(snap.rid)
                tr.recovery("snapshot_corrupt", rid=req.rid,
                            action="reprefill")
                snap = None
        if snap is not None:
            slot.pos = snap.pos
            slot.tokens = list(snap.tokens)
            slot.last_token = snap.last_token
            self.metrics.restores += 1
            tr.event("serve.resume", rid=req.rid, pos=snap.pos,
                     banked=len(snap.tokens))
        else:
            p = req.prompt_len
            offset = self.cfg.n_image_tokens or 0
            seq = prefill_len(self.cfg, p)
            with tr.span("serve.prefill", rid=req.rid, seq=seq, step=t):
                logits, row1 = self._prefill(seq)(
                    self.params, prefill_inputs(self.cfg, req, seq),
                    jnp.asarray([offset + p - 1], jnp.int32))
                self.cache = self._insert(self.cache, slot.sid, row1)
                with tr.span("serve.prefill.wait"):
                    row = np.asarray(logits[0])
            tok = int(np.argmax(row))
            if self.ecfg.record_logits:
                self.logit_log.append((req.rid, 0, row))
            slot.pos = offset + p
            slot.tokens = [tok]
            slot.last_token = tok
            self.metrics.prefill_tokens += seq + offset
        if len(slot.tokens) >= slot.max_new:
            self._finish(slot, t)

    def _restore(self, sid: int, snap: DecodeSnapshot) -> None:
        """Write a verified snapshot into slot ``sid``: rows ``[0, pos)``
        chunk by chunk (the last chunk's rows at and above ``pos`` zero;
        rows past it keep the slot's earlier finite values, which decode
        attention never weights), the other leaves whole.  The slot's
        lineage continues from the snapshot's sealed chunks."""
        lay = self.layout
        at = np.int32(sid)
        for k, rows in enumerate(snap.chunks):
            if rows and len(rows[0]) < lay.chunk:   # the partial last chunk
                rows = [np.concatenate([r, np.zeros(
                    (lay.chunk - len(r), *r.shape[1:]), r.dtype)])
                        for r in rows]
            self.cache = self._write_rows(self.cache, at,
                                          np.int32(k * lay.chunk), rows)
        if lay.state_leaves:
            self.cache = self._write_state(self.cache, at, snap.state)
        self._lineage[sid] = Lineage.resume(lay.chunk, snap)

    # -- one batched decode step ---------------------------------------------
    def _decode(self, t: int) -> None:
        # straggler slots stall: masked out of the batched write, no token
        # progress, state intact — they resume bit-identically on recovery
        stalled = {s.sid for s in self.slots if s.busy and
                   self.pool.is_slow(self.pool.worker_of(s.sid), t)}
        busy = [s for s in self.slots
                if s.busy and s.sid not in stalled]
        if not busy:
            return
        tr = self.tracer
        with tr.span("serve.decode", step=t, live=len(busy),
                     stalled=len(stalled)) as span:
            toks = np.zeros((len(self.slots), 1), np.int32)
            poss = np.zeros((len(self.slots),), np.int32)
            live = np.zeros((len(self.slots),), bool)
            for s in self.slots:
                toks[s.sid, 0] = s.last_token
                poss[s.sid] = s.pos
                live[s.sid] = s.busy and s.sid not in stalled
            nxt, logits, self.cache = self._serve(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(poss), jnp.asarray(live))
            # an MoE step carries its expert counts beside the logits
            logits, moe = logits if self.cfg.is_moe else (logits, None)
            with tr.span("serve.decode.wait"):
                if moe is None:
                    nxt = np.asarray(nxt)
                else:     # one transfer for the tokens and the counts
                    nxt, moe = jax.device_get((nxt, moe))
                rows = (np.asarray(logits) if self.ecfg.record_logits
                        else None)
            if moe is not None:
                self.metrics.moe_experts_active += int(moe[0])
                self.metrics.moe_tokens += int(moe[1])
                span.set(moe_experts_active=int(moe[0]),
                         moe_tokens=int(moe[1]))
            for s in busy:
                tok = int(nxt[s.sid, 0])
                if rows is not None:
                    self.logit_log.append((s.rid, len(s.tokens),
                                           rows[s.sid]))
                s.tokens.append(tok)
                s.last_token = tok
                s.pos += 1
                s.since_snapshot += 1
                self.metrics.decode_tokens += 1
            for s in busy:
                if s.busy and len(s.tokens) >= s.max_new:
                    self._finish(s, t)

    def _finish(self, slot: _Slot, t: int) -> None:
        rid = slot.rid
        self.completed[rid] = list(slot.tokens[:slot.max_new])
        self.metrics.complete(rid, t)
        self.tracer.event("serve.finish", rid=rid, step=t,
                          tokens=slot.max_new)
        self.queue.cancel(rid)
        self.store.drop(rid)
        for sid in sorted(self.active.get(rid, set())):
            # late replicas: their tokens become wastage
            self._release(self.slots[sid])
        self.active.pop(rid, None)
        self._completed_order.append(rid)
        while len(self._completed_order) > self.ecfg.retain_completed:
            old = self._completed_order.popleft()
            self.completed.pop(old, None)
            self.requests.pop(old, None)
            self._started.discard(old)
            self.store.drop(old)

    # -- snapshot cadence (Lemma 3.1 online) ---------------------------------
    def _snapshot_every(self) -> int:
        if self.ecfg.snapshot_lambda is not None:
            return max(1, int(round(self.ecfg.snapshot_lambda)))
        return max(1, int(round(self.interval.current_lambda())))

    def _take_snapshots(self, t: int) -> None:
        if not self.ecfg.snapshots_enabled:
            return
        cadence = self._snapshot_every()
        tr = self.tracer
        lay = self.layout
        for s in self.slots:
            if not (s.busy and s.since_snapshot >= cadence):
                continue
            lin = self._lineage[s.sid]
            delta = bool(lin.chunks)
            with tr.span("serve.snapshot.take", rid=s.rid, step=t):
                with tr.span("serve.snapshot.copy"):
                    # only the chunks written since the lineage's last
                    # sealed one: all dispatched, then one copy to the host
                    sid = np.int32(s.sid)
                    rows = [self._read_rows(self.cache, sid,
                                            np.int32(k * lay.chunk))
                            for k in lin.pending(s.pos)]
                    state = (self._read_state(self.cache, sid)
                             if lay.state_leaves else [])
                    rows, state = jax.device_get((rows, state))
                with tr.span("serve.snapshot.digest"):
                    snap = lin.extend(rows, state, rid=s.rid, pos=s.pos,
                                      tokens=s.tokens,
                                      last_token=s.last_token, step=t)
                    self.store.save(snap)
                self.metrics.snapshots += 1
                self.metrics.snapshot_deltas += delta
                self.metrics.snapshot_bytes += sum(
                    leaf.nbytes for arrays in (*rows, state)
                    for leaf in arrays)
                self.metrics.snapshot_overhead_tokens += \
                    self.ecfg.snapshot_gamma
                tr.event("serve.snapshot", rid=s.rid, pos=s.pos, step=t)
                s.since_snapshot = 0

    # -- main loop -----------------------------------------------------------
    def step(self) -> None:
        t = self.step_no
        tr = self.tracer
        with tr.span("serve.tick", step=t,
                     live=sum(s.busy for s in self.slots),
                     queued=len(self.queue)):
            with tr.span("serve.tick.faults"):
                if self.chaos is not None:
                    self._apply_chaos(t)
                self._on_worker_failures(t)
            with tr.span("serve.tick.shed"):
                self._shed(t)
            with tr.span("serve.tick.admit"):
                self._admit(t)
            self._decode(t)
            with tr.span("serve.tick.snapshots"):
                self._take_snapshots(t)
        self.step_no = t + 1

    def pending(self) -> bool:
        return bool(self.queue) or any(s.busy for s in self.slots)

    def run(self, max_steps: int = 10_000) -> ServeMetrics:
        while self.pending() and self.step_no < max_steps:
            self.step()
        return self.metrics

    def output(self, rid: int) -> list[int] | None:
        return self.completed.get(rid)
