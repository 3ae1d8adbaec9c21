"""repro.serve — fault-tolerant continuous-batching inference service.

This package carries the paper's offline CRCH machinery (replication
heuristics + synchronized checkpointing, ``repro.core``) into an *online*
serving runtime layered on the jax model stack.  An inference request plays
the role of a DAG task; a decode slot plays the role of a VM; a generated
token plays the role of an execution second.

Architecture / paper mapping
----------------------------

``queue.py`` — admission queue
    Requests carry prompts, decode budgets, deadlines, and priorities, and
    are bucketed into (prompt-length, new-token) *request classes*.  The
    10-dimensional request feature embedding mirrors the task features of
    paper Section 3.1 (work sizes, priority, slack, criticality proxies).

``replicas.py`` — Algorithm 1 online
    ``crch_policy`` applies the exact unsupervised pipeline of Algorithm 1
    to request features instead of DAG tasks: ``request_features`` ->
    ``fit_pca`` (coverage-of-variance stop, steps 2-10) ->
    ``triplet_agglomerate`` (Eq. 5/6 merges, steps 11-16) ->
    ``replication_counts`` (size-ranked rep counts, steps 17-19), reduced to
    a per-class hedged-resubmission budget.  The largest cluster (common
    short requests) runs a single copy; outlier clusters (long-decode,
    failure-exposed requests) are hedged with replicas on distinct workers.
    ``uniform_policy`` provides the Replicate-All and no-replication
    baselines of the paper's comparison.  ``WorkerPool`` models the
    accelerator replicas with Weibull-MTBF / log-normal-MTTR failures
    (Section 4.1) via ``repro.ft.coordinator.FaultInjector``.

``snapshot.py`` — Eq. 10 online
    Synchronized decode-state checkpoints: every ``lambda`` generated
    tokens, one slot's KV-cache row + position + emitted tokens is copied to
    host memory at cost ``gamma``.  Cache-layout agnostic via batch-axis
    probing, so the same code handles dense, RWKV and hybrid cache pytrees.

``engine.py`` — Algorithm 3 online
    The slot-based continuous-batching engine.  Freed slots prefill new
    requests (bucket-padded, per-row ``last_idx`` logits) while live slots
    keep decoding through one jit'd ``make_serve_step`` with a per-slot
    position vector.  Worker failures kill their slots (Case 1); a request
    is resubmitted only when its last copy dies (steps 14-15/25-26),
    resuming from its latest snapshot when one exists (steps 22-23) instead
    of re-prefilling (steps 16-21).  The snapshot cadence is re-derived
    online from observed failures by ``repro.ft.interval.DynamicInterval``
    (Lemma 3.1).  Every model family runs through the engine: recurrent
    (RWKV) and rolling-window hybrid (RG-LRU) caches prefill per request at
    the exact prompt length (their state is not padding-safe), and enc-dec /
    multimodal requests carry per-request side inputs whose derived state
    lives in the slot's cache row.

``reference.py`` — parity oracle
    Batch=1 exact-length static greedy decoding through the same model
    code; token-for-token agreement with the engine certifies that slot
    reuse, padding, masking and snapshot restore are output-transparent.

``metrics.py`` — Section 4.2 online
    Usage (tokens processed across all copies incl. checkpoint overhead),
    wastage (usage minus one clean copy per delivered response, Fig. 8/9),
    goodput (in-deadline completions per 1k steps) and p50/p99 latency.

``benchmarks/serve_slo.py`` reports the no-replication vs. Replicate-All
vs. CRCH comparison under the stable/normal/unstable failure environments —
the serving analogue of the paper's Figs. 8-12 wastage-vs-completion
trade-off.
"""
from .engine import (EngineConfig, ServeEngine, engine_supported,
                     prefill_inputs, prefill_len)
from .metrics import ServeMetrics, format_table
from .queue import (AdmissionQueue, Request, RequestClass, WorkItem,
                    prompt_bucket, request_class, request_features)
from .reference import greedy_reference, reference_logits
from .replicas import (SERVE_ENVIRONMENTS, ReplicaPolicy, WorkerPool,
                       crch_policy, uniform_policy)
from .snapshot import DecodeSnapshot, SnapshotStore

__all__ = [
    "AdmissionQueue",
    "DecodeSnapshot",
    "EngineConfig",
    "Request",
    "RequestClass",
    "ReplicaPolicy",
    "SERVE_ENVIRONMENTS",
    "ServeEngine",
    "ServeMetrics",
    "SnapshotStore",
    "WorkItem",
    "WorkerPool",
    "crch_policy",
    "engine_supported",
    "format_table",
    "greedy_reference",
    "prefill_inputs",
    "prefill_len",
    "prompt_bucket",
    "reference_logits",
    "request_class",
    "request_features",
    "uniform_policy",
]
