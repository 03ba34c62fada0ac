"""The generators repeat for a seed, keep to their limits, and the client
times from the due time."""

import time

import numpy as np

from benchmark import cells
from benchmark.traffic import client as _client
from benchmark.traffic import generate


def _chat():
    return cells.load_cell("opt67b-serve-chat-r80")["mix"]


def _window(schedule, mix, seconds):
    lead = mix["lead_in_s"]
    return [r for r in schedule if lead <= r["due_s"] < lead + seconds]


def _sizes(requests):
    return [(len(r["prompt"]), r["max_new"]) for r in requests]


def test_schedule_repeats_and_keeps_to_limits():
    mix = _chat()
    a = generate.request_schedule(mix, 2 ** 31 + 12345, 45.0, 50272)
    b = generate.request_schedule(mix, 2 ** 31 + 12345, 45.0, 50272)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])
    for r in a:
        assert 16 <= len(r["prompt"]) <= 1536 and 16 <= r["max_new"] <= 512
        assert len(r["prompt"]) + r["max_new"] <= 2048
        assert r["prompt"].min() >= 1 and r["prompt"].max() <= 50272
    due = [r["due_s"] for r in a]
    assert due == sorted(due) and 0 <= due[0]
    assert due[-1] < mix["lead_in_s"] + 45.0
    assert len(_window(a, mix, 45.0)) == round(mix["rate"] * 45.0)


def test_every_seed_replays_the_one_trace():
    """A fixed trace, replayed: the window holds every request of the
    trace once, whatever the seed; two seeds see one cyclic sequence from
    another phase; the lead-in replays the trace's end."""
    def cyclic(x, y):
        return any(x == y[k:] + y[:k] for k in range(len(x)))
    mix = _chat()
    a = generate.request_schedule(mix, 1, 45.0, 50272)
    b = generate.request_schedule(mix, 2, 45.0, 50272)
    wa, wb = _window(a, mix, 45.0), _window(b, mix, 45.0)
    assert _sizes(wa) != _sizes(wb) and cyclic(_sizes(wa), _sizes(wb))
    lead = [r for r in a if r["due_s"] < mix["lead_in_s"]]
    assert lead and _sizes(lead) == _sizes(wa)[-len(lead):]
    # the prompts' tokens are the seed's
    first = _sizes(wa)[0]
    twin = next(r for r in wb if (len(r["prompt"]), r["max_new"]) == first)
    assert not np.array_equal(wa[0]["prompt"], twin["prompt"])


def test_the_trace_does_not_depend_on_the_window():
    """``--seconds`` says how long the trace is replayed, not what it is:
    a shorter window sees the start of what a longer one sees, and two
    windows end to end see the trace twice."""
    mix = _chat()
    assert mix["period_s"] == 45.0
    full = generate.request_schedule(mix, 7, 45.0, 50272)
    short = generate.request_schedule(mix, 7, 10.0, 50272)
    twice = generate.request_schedule(mix, 7, 90.0, 50272)
    assert [(r["due_s"], r["max_new"]) for r in short] == \
        [(r["due_s"], r["max_new"]) for r in full][:len(short)]
    lead = mix["lead_in_s"]
    first = [r for r in twice if lead <= r["due_s"] < lead + 45.0]
    second = [r for r in twice if lead + 45.0 <= r["due_s"]]
    assert _sizes(first) == _sizes(second) == _sizes(_window(full, mix, 45.0))


def test_a_lower_rate_is_a_prefix_of_the_trace():
    """The sweep changes only ``rate``: the slower trace's requests are
    the first of the faster one's."""
    mix = _chat()
    slow = generate.request_schedule(dict(mix, rate=1.0), 3, 45.0, 50272)
    fast = generate.request_schedule(dict(mix, rate=2.0), 3, 45.0, 50272)
    ws, wf = _window(slow, mix, 45.0), _window(fast, mix, 45.0)
    assert len(ws) == 45 and len(wf) == 90
    assert set(_sizes(ws)) <= set(_sizes(wf))


def test_token_sequences_follow_one_rule():
    mix = {"seq_len": 64}
    t = generate.token_sequences(mix, 997, seed=3, n_sequences=5)
    assert t.shape == (5, 65) and t.min() >= 1 and t.max() <= 997
    assert np.array_equal(t, generate.token_sequences(mix, 997, 3, 5))
    # the next token is one function of the last, across sequences
    nxt = {}
    for row in t:
        for a, b in zip(row[:-1], row[1:]):
            assert nxt.setdefault(int(a), int(b)) == int(b)


class _StallingEngine:
    """Serves a request in 5 ms, except that the second ``submit`` blocks
    the caller for 0.3 s (a stalled front door)."""

    def __init__(self):
        self.calls = 0

    def submit(self, prompt, max_new):
        self.calls += 1
        if self.calls == 2:
            time.sleep(0.3)
        return _Stream(max_new)


class _Stream:
    def __init__(self, n):
        self.n, self.outcome = n, None

    def __iter__(self):
        for i in range(self.n):
            time.sleep(0.005)
            yield i + 1
        self.outcome = "completed"


def test_client_times_from_due_time():
    """Requests due every 50 ms; the second one's submit stalls 300 ms.
    The requests behind it are handed over late, and because latency is
    counted from the due time the stall shows in *their* first tokens:
    a client that re-anchored its schedule, or timed from the actual
    submit, would report 5 ms for all of them."""
    schedule = [{"due_s": 0.05 * i, "prompt": np.ones(4, np.int32),
                 "max_new": 3} for i in range(8)]
    eng = _StallingEngine()
    cl = _client.OpenLoopClient(eng.submit, schedule)
    t0 = cl.start()
    assert cl.drain(t0 + 5.0) == 0
    cl.close()
    recs = cl.records
    assert all(r.outcome == "completed" and len(r.tokens) == 3
               for r in recs)
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in recs]
    late = [(r.submitted - r.due) * 1e3 for r in recs]
    assert ttft[0] < 50 and late[0] < 5
    # request 2 (due 100 ms) could not be offered before 50 + 300 ms
    assert late[2] > 200 and ttft[2] > 200
    # the backlog is worked off: nothing is forgiven, then it recovers
    assert late[3] > 150 and late[7] < 50
    s = _client.summarize(recs, t0, t0 + 1.0, deadline_ms=1000.0)
    assert s["due_in_window"] == 8 and s["failed_of_due"] == 0
    assert s["gen_late_max_ms"] > 200
    assert s["ttft_p90_ms"] > 150


def test_failed_request_counts_as_the_deadline():
    r = _client.RequestRecord(0, due=10.0, prompt_len=4, max_new=2)
    r.outcome, r.submitted = "shed", 10.0
    ok = _client.RequestRecord(1, due=10.1, prompt_len=4, max_new=2)
    ok.outcome, ok.submitted = "completed", 10.1
    ok.stamps, ok.tokens, ok.finished = [10.2, 10.25], [1, 2], 10.25
    s = _client.summarize([r, ok], 10.0, 11.0, deadline_ms=30000.0)
    assert s["ttft_ms"] == [30000.0, 100.00000000000142] or \
        s["ttft_ms"][0] == 30000.0
    assert s["failed_of_due"] == 1 and s["tokens_in_window"] == 2
    assert s["ttft_p90_ms"] == 30000.0


def test_quantile_is_nearest_rank():
    v = list(range(1, 101))
    assert _client.quantile(v, 0.9) == 90 and _client.quantile(v, 0.95) == 95
    assert _client.quantile([5.0], 0.9) == 5.0
    assert _client.quantile([], 0.9) is None
