"""The client's view and the window arithmetic, with a scripted engine
and a fake clock: time to first token from the due time, gaps between
the tokens a client sees, tokens counted once across copies and
restores."""
import numpy as np
import pytest

from harness import window
from harness.adapter import SlotView
from harness.traffic import RequestSpec


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Scripted:
    """Plays a list of per-tick states: {sid: SlotView} and finished rids
    with their output lengths.  Each step takes ``tick`` seconds."""

    def __init__(self, clock, script, tick=1.0):
        self.clock, self.script, self.tick = clock, list(script), tick
        self.state, self.done, self.recs, self.submitted = {}, {}, [], []

    def submit(self, spec):
        self.submitted.append(spec.rid)
        self.recs.append({"name": "serve.admit",
                          "attrs": {"rid": spec.rid, "rep": 2}})

    def pending(self):
        return bool(self.script)

    def step(self):
        self.clock.t += self.tick
        self.state, fin = self.script.pop(0)
        for rid, n in fin.items():
            self.done[rid] = n
            self.recs.append({"name": "serve.finish", "attrs": {"rid": rid}})

    def slots(self):
        return dict(self.state)

    def completed_len(self, rid):
        return self.done[rid]

    def take_records(self):
        out, self.recs = self.recs, []
        return out

    def counters(self):
        return {"n": len(self.done)}


def _spec(rid, due):
    return RequestSpec(rid=rid, due_s=due, prompt=np.ones(4, np.int32),
                       max_new=4)


def test_client_view_counts_each_token_once():
    clock = Clock()
    v = lambda rid, copy, n: SlotView(rid, copy, n, 10 + n)  # noqa: E731
    script = [
        ({0: v(0, 0, 2), 1: v(0, 1, 2)}, {}),       # two copies, 2 tokens
        ({0: v(0, 0, 3)}, {}),                      # copy 1 lost
        ({2: v(0, 0, 2)}, {}),                      # restore holds fewer
        ({2: v(0, 0, 4)}, {}),
        ({}, {0: 5}),                               # finished with 5
    ]
    sysm = Scripted(clock, script)
    res = window.run(sysm, [_spec(0, 0.0)], seconds=10.0, clock=clock,
                     sleep=clock.sleep)
    c = res.clients[0]
    assert res.delivered == 5 and c.seen == 5 and c.done
    assert c.first - c.due == pytest.approx(1.0)
    # tokens 1-2 in tick 1 (gap 0), token 3 in tick 2, none in tick 3,
    # token 4 in tick 4 (a 2 s stall), token 5 in tick 5
    assert c.gaps == pytest.approx([0.0, 1.0, 2.0, 1.0])
    assert res.replicated == {0}
    assert res.seconds == pytest.approx(10.0)


def test_waiting_requests_and_open_stalls_count():
    clock = Clock()
    script = [({0: SlotView(0, 0, 1, 5)}, {}), ({}, {})]
    sysm = Scripted(clock, script, tick=2.0)
    specs = [_spec(0, 0.0), _spec(1, 3.0)]
    res = window.run(sysm, specs, seconds=8.0, clock=clock,
                     sleep=clock.sleep)
    # request 1 is due at 3 s and never gets a slot; request 0 stalls
    # after its first token until the window closes
    assert sorted(res.clients) == [0, 1]
    assert res.clients[1].first is None
    assert res.clients[0].gaps == pytest.approx([res.t1 - (res.t0 + 2.0)])
    assert res.lateness[1] >= 0.0


def test_backlog_counts_requests_due_and_not_finished():
    clock = Clock()
    v = lambda rid, n: SlotView(rid, 0, n, 10 + n)  # noqa: E731
    script = [({0: v(0, 1)}, {}), ({1: v(1, 1)}, {0: 3}), ({1: v(1, 2)}, {}),
              ({}, {1: 3})]
    sysm = Scripted(clock, script)
    specs = [_spec(0, 0.0), _spec(1, 0.5), _spec(2, 2.5)]
    res = window.run(sysm, specs, seconds=6.0, clock=clock,
                     sleep=clock.sleep)
    t0 = res.t0
    # request 0 finishes in the tick ending at 2 s, request 1 at 4 s;
    # request 2 is due at 2.5 s and never served
    assert [res.backlog(t0 + t) for t in (0.0, 1.0, 2.0, 3.0, 4.0, 6.0)] \
        == [1, 2, 1, 2, 1, 1]
