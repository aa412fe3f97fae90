"""The end-to-end arithmetic: a tail over all requests and a rate over the
whole window, each shown to move when a stall is put into the window."""
import dataclasses

import numpy as np
import pytest

from chipbench import layout
from chipbench.client import Rec
from chipbench.context import percentile


@dataclasses.dataclass
class _Spec:
    prompt: np.ndarray
    max_new_tokens: int


class _Ctx:
    def __init__(self, recs, window):
        self.records = recs
        self.window = window
        self.cell = type("C", (), {"pinned": {}})()
        self.notes = []

    def note(self, line):
        self.notes.append(line)


def _reader(name):
    return layout._reader(layout.BENCH_DIR, name)


def _traffic(stall_at=None, stall=0.0, n=200, gap=0.05, tokens=20,
             tpot=0.01):
    """n requests due every ``gap`` s, each answered after 0.1 s with tokens
    ``tpot`` apart; a stall of ``stall`` s at ``stall_at`` delays every
    later token."""
    recs = {}
    for i in range(n):
        due = i * gap
        times = due + 0.1 + tpot * np.arange(tokens)
        if stall_at is not None:
            times = np.where(times >= stall_at, times + stall, times)
        r = Rec(spec=_Spec(np.zeros(8, np.int32), tokens), due=due,
                submitted=due, first=times[0], last=times[-1],
                finished=times[-1], status="ok",
                tokens=[0] * tokens, stamps=[(t, 1) for t in times])
        recs[i] = r
    return recs


def test_percentile_is_nearest_rank_over_all():
    v = list(range(1, 101))
    assert percentile(v, 95) == 95 and percentile(v, 50) == 50
    assert percentile([1.0, float("inf")], 95) == float("inf")


def test_ttft_tail_counts_every_request_and_moves_with_a_stall():
    read = _reader("ttft_p95_ms")
    base = read(_Ctx(_traffic(), (0.0, 10.0)))
    assert base == pytest.approx(100.0)
    # a 1 s stall delays the first token of the ~20 requests due in it:
    # 10% of all requests, so the 95th percentile sees it
    stalled = read(_Ctx(_traffic(stall_at=4.0, stall=1.0), (0.0, 10.0)))
    assert stalled > base + 500.0
    # a request that never got its first token counts as a miss
    recs = _traffic()
    for r in list(recs.values())[:20]:
        r.first = None
    assert read(_Ctx(recs, (0.0, 10.0))) > 60_000.0


def test_tpot_tail_moves_with_a_stall():
    read = _reader("tpot_p95_ms")
    # 2 s requests: ~40 in flight when a 1 s stall comes, 20% of all
    base = read(_Ctx(_traffic(tokens=200), (0.0, 20.0)))
    assert base == pytest.approx(10.0)
    stalled = read(_Ctx(_traffic(stall_at=4.0, stall=1.0, tokens=200),
                        (0.0, 20.0)))
    assert stalled > 13.0


def test_tpot_counts_unfinished_requests_up_to_the_close():
    read = _reader("tpot_p95_ms")
    # a window that closes at 6 s: the 98 requests due before 4.9 s have
    # two tokens or more by then (the stall holds back the rest), and
    # every one of them is unfinished
    ctx = _Ctx(_traffic(stall_at=4.0, stall=1.0, tokens=200), (0.0, 6.0))
    stalled = read(ctx)
    assert "98 requests" in ctx.notes[0]
    assert stalled > 13.0
    # tokens after the close do not count: a stall after it changes nothing
    late = read(_Ctx(_traffic(stall_at=6.5, stall=1.0, tokens=200),
                     (0.0, 6.0)))
    assert late == pytest.approx(10.0)


def test_token_rate_is_over_the_whole_window_and_falls_with_a_stall():
    read = _reader("decode_tok_s")
    base = read(_Ctx(_traffic(), (0.0, 5.0)))
    stalled = read(_Ctx(_traffic(stall_at=2.0, stall=1.0), (0.0, 5.0)))
    assert stalled < 0.85 * base
    # tokens after the window's close are not counted
    assert read(_Ctx(_traffic(), (0.0, 2.5))) < base
