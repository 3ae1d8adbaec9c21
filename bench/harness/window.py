"""The open-loop client and the measured window.

Each request has a due time.  Every pass of the loop submits each request
whose due time has passed, steps the engine once, then reads the clock and
the engine's state.  Every tick ends in a host sync inside the engine, so
the clock after ``step()`` is the time the tick's tokens exist on the
host.  When nothing is queued or running, the loop sleeps until the next
due time instead of stepping an idle engine (engine steps are the failure
model's clock).

The client sees a request's progress as the most tokens any of its
copies holds, or that its completed output holds.  The count never goes
back: a replica race or a snapshot restore that holds fewer tokens than
the client has seen shows nothing new until it passes them, so no token
is delivered twice and the stall shows as one long gap.
"""
from __future__ import annotations

import dataclasses
import time

import jax


@dataclasses.dataclass
class ClientView:
    due: float                    # absolute clock
    seen: int = 0
    first: float | None = None
    last: float | None = None
    done: bool = False
    gaps: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    decoded: list         # positions attended by each slot decoded
    prefills: list        # (prompt_len, padded length) of each prefill
    snapshots: int
    restores: int


@dataclasses.dataclass
class WindowResult:
    t0: float
    t1: float
    clients: dict          # rid -> ClientView, every request due in it
    ticks: list
    delivered: int
    lateness: list         # submit time - due time, seconds
    counters_mid: dict     # program counters halfway through
    counters_end: dict
    restored: set          # rids resumed from a decode snapshot
    replicated: set        # rids admitted with more than one copy

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def backlog(self, t: float) -> int:
        """Requests due by ``t`` and not finished by ``t``."""
        return sum(1 for c in self.clients.values()
                   if c.due <= t and not (c.done and c.last <= t))


def _annotate(name):
    return jax.profiler.TraceAnnotation(name)


def run(system, specs, *, seconds: float, hook=None,
        clock=time.perf_counter, sleep=time.sleep) -> WindowResult:
    """Serve ``specs`` (in due order) open-loop for ``seconds``.

    ``hook(now, ticks)`` is called at each tick boundary (the traced run
    starts and stops the profiler there)."""
    by_rid = {s.rid: s for s in specs}
    clients: dict[int, ClientView] = {}
    ticks: list[Tick] = []
    lateness: list[float] = []
    delivered = 0
    t0 = clock()
    end = t0 + seconds
    mid = t0 + seconds / 2
    counters_mid = None
    restored: set[int] = set()
    replicated: set[int] = set()
    i = 0
    prev = system.slots()
    while True:
        now = clock()
        if hook is not None:
            hook(now, ticks)
        if counters_mid is None and now >= mid:
            counters_mid = system.counters()
        if now >= end:
            break
        with _annotate("bench.submit"):
            while i < len(specs) and t0 + specs[i].due_s <= now:
                s = specs[i]
                system.submit(s)
                clients[s.rid] = ClientView(due=t0 + s.due_s)
                lateness.append(now - (t0 + s.due_s))
                i += 1
        if not system.pending():
            wake = t0 + specs[i].due_s if i < len(specs) else end
            with _annotate("bench.wait"):
                sleep(max(0.0, min(wake, end) - clock()))
            continue
        ts = clock()
        with _annotate("bench.step"):
            system.step()
        with _annotate("bench.observe"):
            te = clock()
            slots = system.slots()
            recs = system.take_records()
            finished = {r["attrs"]["rid"] for r in recs
                        if r["name"] == "serve.finish"}
            progress: dict[int, int] = {}
            for v in slots.values():
                progress[v.rid] = max(progress.get(v.rid, 0), v.tokens)
            for rid in finished:
                progress[rid] = system.completed_len(rid)
            for rid, n in progress.items():
                c = clients[rid]
                if n > c.seen:
                    if c.first is None:
                        c.first = te
                    else:
                        c.gaps.append(te - c.last)
                    c.gaps.extend([0.0] * (n - c.seen - 1))
                    delivered += n - c.seen
                    c.seen, c.last = n, te
                c.done = c.done or rid in finished
            decoded = [v.pos for v in slots.values()]
            decoded += [v.pos + 1 for sid, v in prev.items()
                        if sid not in slots and v.rid in finished]
            prefills = [(by_rid[r["attrs"]["rid"]].prompt.shape[0],
                         r["attrs"]["seq"]) for r in recs
                        if r["name"] == "serve.prefill"]
            ticks.append(Tick(
                ts, te, decoded, prefills,
                snapshots=sum(r["name"] == "serve.snapshot" for r in recs),
                restores=sum(r["name"] == "serve.resume" for r in recs)))
            restored |= {r["attrs"]["rid"] for r in recs
                         if r["name"] == "serve.resume"}
            replicated |= {r["attrs"]["rid"] for r in recs
                           if r["name"] == "serve.admit"
                           and r["attrs"]["rep"] > 1}
            prev = slots
    t1 = clock()
    for c in clients.values():
        if c.seen and not c.done:
            c.gaps.append(t1 - c.last)   # an open stall still counts
    counters_end = system.counters()
    return WindowResult(
        t0=t0, t1=t1, clients=clients, ticks=ticks, delivered=delivered,
        lateness=lateness, counters_mid=counters_mid or counters_end,
        counters_end=counters_end, restored=restored,
        replicated=replicated)
