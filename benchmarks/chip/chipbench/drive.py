"""Warm-up and the measured window: the client side of the server.

The window drives ``ExplanationServer.submit`` and ``.poll`` from one
thread, as the plan says.  Open loop: every request is due at its planned
time whatever the server does.  Closed loop: each client sends its next
request when its last one completed.  A request's latency runs from the
time it was due, so a stall is charged to every request behind it; how
late the client itself submitted is recorded apart.  Requests due after
the window's close are not sent; those sent are waited for, up to
``drain_s`` past the close, and a request that never completes has failed.
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from chipbench import traffic

PREDICT, EXPLAIN = "predict", "explain"
#: how long the client naps when the batcher holds requests, s
_NAP_S = 1e-4
#: longest nap while waiting for the next due request, s
_MAX_NAP_S = 1e-3


class SetupError(Exception):
    pass


@dataclass
class Rec:
    """One request as the client saw it."""
    uid: str
    kind: str
    session: int
    method: Optional[str]
    topk: Optional[int]
    due: float
    late_s: float = 0.0          # submit time - due time
    submit_s: float = 0.0        # time spent inside submit()
    latency_s: Optional[float] = None
    ok: bool = False
    error: str = ""
    resp: Any = None

    @property
    def done_t(self) -> float:
        return self.due + (self.latency_s or 0.0)


def _request(uid, kind, x, kind_of, due=None):
    from repro.serve import Request
    method, topk = kind_of if kind == EXPLAIN else ("saliency", None)
    return Request(uid=uid, kind=kind, x=x, method=method, topk=topk,
                   arrive_t=due)


def warm_up(server, mix: dict, plan: traffic.Plan, warm_payloads) -> int:
    """Run every shape the mix launches, twice.  ``warm_payloads(plan,
    seats)`` (the model kind's) gives, per payload shape the plan sends, as
    many payloads of it as the server has seats.  A launch stacks its live
    requests of one shape and pads them to a power of two, so for each
    shape every live count up to the seats is sent once; each padded size
    is then sent for every (method, panel) bucket of the explains: cache
    hits after the predicts, or cold.  Returns the number of requests
    served; raises SetupError on any failed response."""
    fill = server.batcher.fill_target
    pow2 = traffic.pad_sizes(fill)
    bucket_list = traffic.buckets(mix)
    served = 0

    def wave(reqs):
        nonlocal served
        for r in reqs:
            server.submit(r)
        out = server.drain()
        bad = [r for r in out if not r.ok]
        if bad or len(out) != len(reqs):
            raise SetupError(f"warm-up wave of {len(reqs)} got {len(out)} "
                             f"responses, {len(bad)} failed: "
                             f"{bad[0].error if bad else ''}")
        served += len(out)

    for g, group in enumerate(warm_payloads(plan, fill)):
        for rnd, n in itertools.product(range(2), range(1, fill + 1)):
            xs = group[:n]
            kinds = bucket_list if n in pow2 else bucket_list[:1]
            if plan.predict_first:
                uids = [f"w{g}.{rnd}.{n}.{j}" for j in range(n)]
                wave([_request(u, PREDICT, x, None)
                      for u, x in zip(uids, xs)])
                if n in pow2:
                    for b in kinds:
                        wave([_request(u, EXPLAIN, x, b)
                              for u, x in zip(uids, xs)])
            else:
                for b in kinds:
                    wave([_request(f"w{g}.{rnd}.{n}.{b}.{j}", EXPLAIN, x, b)
                          for j, x in enumerate(xs)])
    return served


class Window:
    """Drive ``plan`` through ``server`` for ``seconds``; see module doc."""

    def __init__(self, server, plan: traffic.Plan, seconds: float, *,
                 clock=time.monotonic, drain_s: float = 60.0,
                 annotate: bool = False):
        self.server = server
        # with the profiler on: name what the client thread does (submit,
        # poll the server, wait for the next arrival) on the trace's clock
        if annotate:
            import jax
            self._span = jax.profiler.TraceAnnotation
        else:
            self._span = lambda name: contextlib.nullcontext()
        self.plan = plan
        self.seconds = seconds
        self.clock = clock
        self.drain_s = drain_s
        self.recs: Dict[Tuple[str, str], Rec] = {}
        self._heap: List[Tuple[float, int, str, int]] = []
        self._seq = itertools.count()
        self._outstanding = 0
        self._next_session = 0
        self.t0 = self.end = 0.0
        self.closed_t = 0.0          # when the last request completed
        self.longest_poll_s = 0.0    # the longest server.poll() call

    def _push(self, due: float, kind: str, session: int) -> None:
        heapq.heappush(self._heap, (due, next(self._seq), kind, session))

    def _uid(self, kind: str, session: int) -> str:
        return f"{'s' if self.plan.predict_first else 'c'}{session}"

    def _new_session(self, due: float) -> None:
        s = self._next_session
        self._next_session += 1
        self._push(due, PREDICT if self.plan.predict_first else EXPLAIN, s)

    def _submit(self, due: float, kind: str, session: int) -> None:
        plan = self.plan
        kind_of = plan.kind(session)
        uid = self._uid(kind, session)
        rec = Rec(uid=uid, kind=kind, session=session,
                  method=kind_of[0] if kind == EXPLAIN else None,
                  topk=kind_of[1] if kind == EXPLAIN else None, due=due)
        self.recs[(uid, kind)] = rec
        req = _request(uid, kind, plan.payload(session), kind_of, due)
        t = self.clock()
        rec.late_s = t - due
        try:
            self.server.submit(req)
        except Exception as e:                      # noqa: BLE001
            rec.submit_s = self.clock() - t
            rec.error = f"{type(e).__name__}: {e}"
            rec.latency_s = self.clock() - due
            self._after(rec)
            return
        rec.submit_s = self.clock() - t
        self._outstanding += 1

    def _complete(self, resp) -> None:
        rec = self.recs.get((resp.uid, resp.kind))
        if rec is None or rec.latency_s is not None:
            return
        self._outstanding -= 1
        rec.latency_s = resp.latency_s
        rec.ok = resp.ok
        rec.error = "" if resp.ok else f"{resp.error_type}: {resp.error}"
        rec.resp = resp
        self._after(rec)

    def _after(self, rec: Rec) -> None:
        """What the rec's client does next."""
        if rec.kind == PREDICT and rec.ok:
            self._push(rec.done_t + self.plan.think_s(rec.session), EXPLAIN,
                       rec.session)
        elif self.plan.loop == "closed" and (rec.kind == EXPLAIN
                                             or not rec.ok):
            if rec.done_t < self.end:
                self._new_session(rec.done_t)

    def run(self) -> "Window":
        clock, server = self.clock, self.server
        self.t0 = clock()
        self.end = self.t0 + self.seconds
        if self.plan.loop == "open":
            for _ in range(len(self.plan.arrivals)):
                s = self._next_session
                self._next_session += 1
                self._push(self.t0 + float(self.plan.arrivals[s]),
                           PREDICT if self.plan.predict_first else EXPLAIN, s)
        else:
            for _ in range(self.plan.clients):
                self._new_session(self.t0)
        give_up = self.end + self.drain_s
        heap = self._heap
        span = self._span
        while True:
            now = clock()
            while heap and heap[0][0] <= now:
                due, _, kind, session = heapq.heappop(heap)
                if due < self.end:
                    with span("client.submit"):
                        self._submit(due, kind, session)
            t_poll = clock()
            with span("server.poll"):
                done = server.poll()
            now = clock()
            self.longest_poll_s = max(self.longest_poll_s, now - t_poll)
            for resp in done:
                self._complete(resp)
            if now >= self.end:
                pending_due = heap and heap[0][0] < self.end
                if not self._outstanding and not pending_due:
                    break
                if now >= give_up:
                    break
            if server.batcher.pending():
                with span("client.wait_for_batch"):
                    time.sleep(_NAP_S)
            else:
                nxt = heap[0][0] if heap else self.end
                wait = min(nxt - clock(), _MAX_NAP_S)
                if wait > 0:
                    with span("client.wait_for_arrival"):
                        time.sleep(wait)
        self.closed_t = clock()
        for rec in self.recs.values():
            if rec.latency_s is None:             # never completed: failed
                rec.latency_s = self.closed_t - rec.due
                rec.error = rec.error or "no response before the drain ended"
        return self

    # -- readings ---------------------------------------------------------------

    def records(self, kind: Optional[str] = None) -> List[Rec]:
        return [r for r in self.recs.values() if kind in (None, r.kind)]

    def miss_latency_s(self) -> float:
        """What a failed request counts as: longer than any completed one."""
        return max([self.closed_t - self.t0]
                   + [r.latency_s for r in self.recs.values() if r.ok])
