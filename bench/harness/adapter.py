"""The one file that touches the program under test.

Everything the harness needs of ``repro`` goes through here, so a rename in
the program is mended in one place: the served path as the launcher
builds it (a ``(1, chips)`` mesh under ``use_rules``, weights placed by
the program's parameter shardings, ``ServeEngine`` with a ``WorkerPool``
and a ``crch_policy`` fitted on the run's own requests), the programs it
jits, its counters (``ServeMetrics`` and every series of its registry)
and its spans and events (a ``repro.obs`` tracer whose records the
harness reads each tick), and the slot state from which the client's
view of each request is taken.

The weights are the benchmark's, made by the configuration's reference
``init`` from the seed's key in one jitted call straight into the
program's layout, so that the reference can make the same weights again without
taking anything from the program.
"""
from __future__ import annotations

import dataclasses
import sys

from .spec import ROOT

sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.distributed import params as pshard  # noqa: E402
from repro.distributed.sharding import use_rules  # noqa: E402
from repro.launch.mesh import enable_compile_cache, make_mesh  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.obs.trace import Tracer  # noqa: E402
from repro.serve import (EngineConfig, Request, ServeEngine,  # noqa: E402
                         ServeMetrics, WorkerPool, crch_policy,
                         prompt_bucket, uniform_policy)
from repro.serve.engine import prefill_inputs  # noqa: E402

# programs whose device time the per-layer readers look up in the trace
DECODE_PROGRAM = "serve_step_masked"
PREFILL_PROGRAM = "prefill_last_idx_step"


def compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or the fixed ``<checkout>/.jax_cache``); every program is cached."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


class _Records:
    """Recorder for the program's tracer: keeps this tick's records."""

    def __init__(self):
        self.records: list[dict] = []

    def record(self, rec: dict) -> None:
        self.records.append(rec)

    def on_fault(self, kind, step=None) -> None:
        pass

    def on_recovery(self, kind) -> None:
        pass


@dataclasses.dataclass(frozen=True)
class SlotView:
    rid: int
    copy_id: int
    tokens: int       # tokens this copy holds
    pos: int          # position of its next decode write


def _request(spec, arrival: int) -> Request:
    return Request(rid=spec.rid, prompt=spec.prompt,
                   max_new_tokens=spec.max_new, arrival=arrival,
                   deadline=None)


def registry_series(registry) -> dict[str, float]:
    """Every series of a ``repro.obs`` metrics registry under one stable
    name: ``family{label=value,...}`` with the labels in the family's
    order (``serve_bytes_total{kind=snapshot}``), or ``family`` where it
    has no labels.  A histogram gives ``family_count{...}`` and
    ``family_sum{...}``.  A series appears once the program first moves
    it; a reader takes a missing one as 0."""
    out = {}
    for family, inst in registry.to_json().items():
        for key, value in inst["series"].items():
            labels = "{" + key.replace("|", ",") + "}" if key else ""
            if isinstance(value, dict):
                out[f"{family}_count{labels}"] = float(value["count"])
                out[f"{family}_sum{labels}"] = float(value["sum"])
            else:
                out[family + labels] = float(value)
    return out


class System:
    """The served path for one configuration, built from the seed."""

    def __init__(self, model: dict, deployment: dict, mix: dict, *,
                 init_fn, key, chips: int, requests):
        self.cfg = ModelConfig(**model)
        self.mesh = make_mesh(chips)
        self._rules = use_rules(self.mesh)
        self._rules.__enter__()
        shapes = jax.eval_shape(init_fn, key)
        psh = pshard.param_shardings(shapes, self.mesh, zero1=True)
        # the key is an argument, so one compiled program serves every seed
        self.params = jax.jit(init_fn, out_shardings=psh)(key)
        sample = [_request(r, 0) for r in requests]
        policy = (crch_policy(sample) if mix["policy"] == "crch"
                  else uniform_policy(1))
        env = mix["env"] if mix["env"] != "none" else None
        # the failures' phase is the mix's, the same for every run's seed
        pool = WorkerPool(deployment["workers"],
                          deployment["slots_per_worker"], environment=env,
                          seed=mix["schedule_seed"])
        self._rec = _Records()
        self.engine = ServeEngine(
            self.cfg, EngineConfig(cache_len=deployment["cache_len"],
                                   q_chunk=64),
            pool=pool, policy=policy, params=self.params,
            metrics=ServeMetrics(), tracer=Tracer(self._rec))
        self.policy_name = policy.name

    # -- set-up ---------------------------------------------------------
    def warm(self, prompt_lens) -> None:
        """Compile every program the window can run, with the arguments'
        own types: each prefill bucket the prompts fall in and its logits
        row, the cache insert, the masked decode step, and a snapshot's
        slot read and restore.  Slot 0's cache row is overwritten, which
        every admission does again; no counter moves."""
        e = self.engine
        n = len(e.slots)
        # twice: the second pass sees the cache as each program returns it
        # (its layout after a jitted call may differ from the first one's)
        for _ in range(2):
            for seq in sorted({prompt_bucket(p) for p in prompt_lens}):
                req = Request(rid=-1, prompt=np.ones(seq, np.int32),
                              max_new_tokens=1)
                logits, row1 = e._prefill(seq)(
                    e.params, prefill_inputs(self.cfg, req, seq),
                    jnp.asarray([seq - 1], jnp.int32))
                e.cache = e._insert(e.cache, 0, row1)
                np.asarray(logits[0])
            nxt, _, e.cache = e._serve(
                e.params, e.cache, jnp.asarray(np.zeros((n, 1), np.int32)),
                jnp.asarray(np.zeros((n,), np.int32)),
                jnp.asarray(np.zeros((n,), bool)))
            np.asarray(nxt)
            row = jax.device_get(e._get(e.cache, 0))
            e.cache = e._set(e.cache, 0, jax.tree.map(jnp.asarray, row))
        jax.block_until_ready(e.cache)

    # -- the window -----------------------------------------------------
    def submit(self, spec) -> int:
        return self.engine.submit(_request(spec, self.engine.step_no))

    def step(self) -> None:
        self.engine.step()

    def pending(self) -> bool:
        return self.engine.pending()

    def slots(self) -> dict[int, SlotView]:
        return {s.sid: SlotView(s.rid, s.copy_id, len(s.tokens), s.pos)
                for s in self.engine.slots if s.busy}

    def completed_len(self, rid: int) -> int | None:
        out = self.engine.completed.get(rid)
        return None if out is None else len(out)

    def take_records(self) -> list[dict]:
        """The program's span and event records since the last call."""
        out, self._rec.records = self._rec.records, []
        return out

    def counters(self) -> dict[str, float]:
        """The ten counters the harness has always read, and every series
        of the engine's metrics registry (``registry_series``), so that a
        reader finds a counter the program adds without a change here."""
        m = self.engine.metrics
        return {"prefill_tokens": float(m.prefill_tokens),
                "decode_tokens": float(m.decode_tokens),
                "usage_tokens": float(m.usage_tokens),
                "useful_tokens": float(m.useful_tokens),
                "failures": float(m.failures),
                "resubmissions": float(m.resubmissions),
                "restores": float(m.restores),
                "snapshots": float(m.snapshots),
                "shed": float(m.shed),
                "rejected": float(m.rejected_on_arrival),
                **registry_series(m.registry)}

    def outputs(self) -> dict[int, list[int]]:
        return {rid: list(t) for rid, t in self.engine.completed.items()}

    def free(self) -> None:
        """Drop every device buffer of the program (weights, cache) so the
        reference runs on a chip that holds nothing of it."""
        e = self.engine
        e.cache = None
        e.params = None
        self.params = None
        self.engine = None
        self._rules.__exit__(None, None, None)
