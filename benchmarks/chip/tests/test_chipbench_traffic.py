"""The seeded traffic generator: same seed, same schedule; every seed the
same sizes and arrivals in the same order; the stated rate, burstiness and
clips over a long draw."""
import itertools
import json

import numpy as np

from chipbench import layout, traffic

MIXES = layout.BENCH_DIR / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _key(specs):
    return [(s.rid, s.prompt.tolist(), s.max_new_tokens, s.at)
            for s in specs]


def _closed(seed, n):
    return list(itertools.islice(
        traffic.closed_loop(_mix("decode-heavy"), seed, 151936), n))


def test_same_seed_same_schedule():
    chat = _mix("chat")
    a = traffic.open_loop(chat, 20.0, 10.0, 2 ** 31 + 5, 151936)
    b = traffic.open_loop(chat, 20.0, 10.0, 2 ** 31 + 5, 151936)
    assert _key(a) == _key(b)
    c = traffic.open_loop(chat, 20.0, 10.0, 2 ** 31 + 6, 151936)
    assert _key(a) != _key(c)
    assert _key(_closed(7, 600)) == _key(_closed(7, 600))


def test_the_seed_draws_only_token_ids():
    chat = _mix("chat")
    runs = [traffic.open_loop(chat, 30.0, 20.0, seed, 1000)
            for seed in (1, 2 ** 33 + 1)]
    runs += [_closed(seed, 600) for seed in (1, 2 ** 33 + 1)]
    for a, b in (runs[:2], runs[2:]):
        assert ([(len(s.prompt), s.max_new_tokens, s.at) for s in a]
                == [(len(s.prompt), s.max_new_tokens, s.at) for s in b])
        assert any(not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, b))


def test_open_loop_rate_burstiness_and_clips():
    chat = _mix("chat")
    rate, seconds = 40.0, 300.0
    specs = traffic.open_loop(chat, rate, seconds, 11, 151936)
    at = np.array([s.at for s in specs])
    assert len(specs) == rate * seconds
    assert at[0] == 0.0 and np.all(np.diff(at) >= 0) and at[-1] < seconds
    g = np.diff(at)
    assert abs(g.std() / g.mean() - chat["arrivals"]["cv"]) < 0.2
    for key, get in (("prompt", lambda s: len(s.prompt)),
                     ("output", lambda s: s.max_new_tokens)):
        v = np.array([get(s) for s in specs])
        d = chat[key]
        assert v.min() >= d["min"] and v.max() <= d["max"]
        assert abs(np.median(v) - d["median"]) <= 0.02 * d["median"]
    assert all(0 <= s.prompt.min() and s.prompt.max() < 151936
               for s in specs)


def test_closed_loop_clips_and_every_stretch_holds_the_spread():
    mix = _mix("decode-heavy")
    specs = _closed(3, 1024)
    assert [s.rid for s in specs] == list(range(1024))
    assert all(s.at == 0.0 for s in specs)
    for key, get in (("prompt", lambda s: len(s.prompt)),
                     ("output", lambda s: s.max_new_tokens)):
        v = np.array([get(s) for s in specs])
        d = mix[key]
        assert v.min() >= d["min"] and v.max() <= d["max"]
        assert abs(np.median(v) - d["median"]) <= 0.02 * d["median"]
        # the first 64 requests, and any later 64, offer about the same
        # work as the whole stream
        for lo in (0, 64, 500):
            part = v[lo:lo + 64].mean()
            assert abs(part - v.mean()) <= 0.1 * v.mean()
    p = np.array([len(s.prompt) for s in specs], float)
    o = np.array([s.max_new_tokens for s in specs], float)
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.1
