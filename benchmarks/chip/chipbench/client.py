"""The client side of a run: it drives the engine's public calls (``submit``,
``step``) and stamps every token with the host clock as the engine's
``on_token`` callback delivers it.

Times are host ``perf_counter`` seconds. ``engine.step`` ends in the host
sync that materialises its tokens, so a stamp is the moment a client could
have seen the token.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, Optional

import numpy as np

# how long after the window closes the client still waits for a due
# request's first token before counting it as failed
DRAIN_S = 60.0


@dataclasses.dataclass
class Rec:
    spec: object                    # traffic.Spec
    due: float                      # scheduled submit time (host clock)
    submitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    finished: Optional[float] = None
    status: Optional[str] = None
    tokens: list = dataclasses.field(default_factory=list)
    stamps: list = dataclasses.field(default_factory=list)  # (t, n tokens)


class Client:
    """Submits requests to an engine and records what comes back."""

    def __init__(self, engine, Request, span: Callable):
        self.engine = engine
        self.Request = Request
        self.span = span            # span(name) -> context manager
        self.recs: dict[int, Rec] = {}
        self.outstanding = 0
        engine.set_stream_callbacks(on_token=self._on_token,
                                    on_result=self._on_result)

    def _on_token(self, rid, tokens, tick):
        now = time.perf_counter()
        r = self.recs[rid]
        if r.first is None:
            r.first = now
        r.last = now
        r.tokens.extend(int(t) for t in tokens)
        r.stamps.append((now, len(tokens)))

    def _on_result(self, result):
        r = self.recs[result.rid]
        r.finished = time.perf_counter()
        r.status = result.status
        self.outstanding -= 1

    def submit(self, spec, rid: int, due: float) -> None:
        rec = Rec(spec=spec, due=due)
        self.recs[rid] = rec
        req = self.Request(rid=rid, prompt=spec.prompt.tolist(),
                           max_new_tokens=spec.max_new_tokens,
                           arrival=self.engine.clock)
        with self.span("bench.submit"):
            self.engine.submit(req)
        rec.submitted = time.perf_counter()
        self.outstanding += 1

    def step(self) -> None:
        with self.span("bench.engine_step"):
            self.engine.step()

    def prefilled_tokens(self) -> dict:
        """{rid: prompt positions prefilled so far}. The engine keeps a
        request's prefill progress only while it is in flight; a request
        whose first token came has prefilled its whole prompt."""
        inflight = {fl.req.rid: fl.prefilled
                    for fl in getattr(self.engine, "_inflight", {}).values()}
        return {rid: (len(r.spec.prompt) if r.first is not None
                      else inflight.get(rid, 0))
                for rid, r in self.recs.items()}

    def delivered(self) -> dict:
        """{rid: tokens delivered so far}."""
        return {rid: len(r.tokens) for rid, r in self.recs.items()}


def run_closed(client: Client, stream: Iterator, num_slots: int,
               backlog: int, seconds: float, on_open: Callable,
               on_tick: Callable, finished: int = 0) -> tuple:
    """Keep ``backlog`` x ``num_slots`` requests of ``stream`` queued behind
    full slots; the window opens once every slot has been admitted. After
    the window the engine serves on, sending nothing new, until
    ``finished`` requests have finished (``DRAIN_S`` at most), so that the
    check has answers to compare. Returns the window's (open, close) host
    times."""
    def top_up():
        while client.engine.scheduler.pending() < backlog * num_slots:
            spec = next(stream)
            client.submit(spec, spec.rid, time.perf_counter())

    top_up()
    while client.outstanding - client.engine.scheduler.pending() < num_slots:
        client.step()
        top_up()
    t_open = time.perf_counter()
    on_open(t_open)
    t_end = t_open + seconds
    now = t_open
    while now < t_end:
        client.step()
        top_up()
        now = time.perf_counter()
        on_tick(now)
    t_close = now
    while (sum(r.status == "ok" for r in client.recs.values()) < finished
           and client.outstanding and now < t_close + DRAIN_S):
        client.step()
        now = time.perf_counter()
    return t_open, t_close


def run_open(client: Client, specs: list, seconds: float, on_open: Callable,
             on_tick: Callable) -> tuple:
    """Submit each request when it is due, whether or not earlier ones have
    finished; after the window, serve on until every request sent has its
    first token (``DRAIN_S`` at most). Returns the window's (open, close)
    host times."""
    t_open = time.perf_counter()
    on_open(t_open)
    t_close = t_open + seconds
    due = [t_open + s.at for s in specs]
    i, n = 0, len(specs)
    while True:
        now = time.perf_counter()
        while i < n and due[i] <= now:
            client.submit(specs[i], i, due[i])
            i += 1
        if client.outstanding:
            client.step()
        elif i < n:
            with client.span("bench.wait_arrival"):
                time.sleep(max(0.0, due[i] - time.perf_counter()))
        now = time.perf_counter()
        on_tick(now)
        if i == n and now >= t_close:
            if all(r.first is not None for r in client.recs.values()):
                break
            if now > t_close + DRAIN_S or not client.outstanding:
                break
    return t_open, t_close


def tpot_s(rec: Rec, close: float) -> Optional[float]:
    """A request's time per output token over the tokens it got by
    ``close``: (last - first) / (tokens - 1); None below two tokens."""
    n, last = 0, None
    for t, k in rec.stamps:
        if t <= close:
            n, last = n + k, t
    if n < 2:
        return None
    return (last - rec.first) / (n - 1)


def lateness_p95_ms(client: Client) -> float:
    late = [r.submitted - r.due for r in client.recs.values()
            if r.submitted is not None]
    return float(np.percentile(late, 95) * 1e3) if late else 0.0
