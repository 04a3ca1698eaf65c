"""The traffic: every seed does the same work (one schedule of independent
draws; the seed draws the ids), a shorter window is the start of a longer
one, and each kind's parameters do what its file says."""
import numpy as np
import pytest

from benchmark import manifest as mf, trafficgen


def _mix(name):
    t = trafficgen.load(name)
    return t, mf.traffic_kind(t)


def test_documents_come_in_one_order_for_every_seed():
    t, kind = _mix("packed-4k")
    a = [len(d) for d in kind.documents(t, 1, 1000)]
    b = [len(d) for d in kind.documents(t, 2**31 + 5, 1000)]
    # the same lengths in the same order (the packer makes the same rows),
    # other ids
    assert a == b and min(a) >= 16 and max(a) <= 4096
    assert abs(np.median(a) - 600) < 40
    assert not np.array_equal(kind.documents(t, 1, 1000)[0][:8],
                              kind.documents(t, 2, 1000)[0][:8])
    # independent draws, not a dealt set of quantiles: lengths repeat
    assert len(set(a)) < len(a)


def test_open_loop_is_one_schedule_and_a_short_window_starts_a_long_one():
    t, kind = _mix("chat-steady")
    r1 = kind.generate(t, 1, 1000, 30.0)
    r2 = kind.generate(t, 2**31 + 7, 1000, 30.0)
    plan = lambda rs: [(len(r["prompt"]), r["max_new_tokens"], r["due"])
                       for r in rs]
    assert plan(r1) == plan(r2) and r1[0]["prompt"] != r2[0]["prompt"]
    assert plan(kind.generate(t, 1, 1000, 51.0))[:len(r1)] == plan(r1)
    due = [r["due"] for r in r1]
    assert due == sorted(due) and min(due) == -t["ramp_s"]
    assert all(r["in_window"] == (r["due"] >= 0.0) for r in r1)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 2048 for r in r1)


def test_open_loop_gaps_are_exponential_and_lengths_as_stated():
    t, kind = _mix("chat-steady")
    t = dict(t, running_since_s=t["ramp_s"])        # nothing aged
    rs = [r for r in kind.generate(t, 1, 1000, 4000.0) if r["in_window"]]
    gaps = np.diff([r["due"] for r in rs])
    rate = t["rate_per_s"]
    assert abs(len(rs) / 4000.0 / rate - 1) < 0.05
    # exponential: the standard deviation equals the mean, and requests
    # bunch (a de-bunched stream has far fewer short gaps)
    assert abs(gaps.std() * rate - 1) < 0.1
    assert abs(np.mean(gaps < 0.1 / rate) - (1 - np.exp(-0.1))) < 0.02
    # the published means the mix was set from (its file's `source`)
    assert abs(np.mean([len(r["prompt"]) for r in rs]) - 161) < 12
    assert abs(np.mean([r["max_new_tokens"] for r in rs]) - 338) < 20


def test_a_server_that_has_been_running_holds_aged_requests():
    t, kind = _mix("chat-steady")
    rs = kind.generate(t, 1, 1000, 10.0)
    aged = [r for r in rs if r["due"] == -t["ramp_s"]]
    # Little's law, roughly: rate x a request's life (output x token_s)
    expect = t["rate_per_s"] * 338 * t["token_s"]
    assert 0.5 * expect < len(aged) < 1.6 * expect
    # what an aged request had produced sits in its prompt
    fresh = [r for r in rs if r["due"] > -t["ramp_s"]]
    assert (np.mean([len(r["prompt"]) for r in aged])
            > np.mean([len(r["prompt"]) for r in fresh]) + 50)
    assert all(r["max_new_tokens"] >= 1 for r in rs)


def test_bursts_multiply_the_rate_inside_them():
    t, kind = _mix("chat-steady")
    t = dict(t, burst={"every_s": 5.0, "for_s": 1.0, "times": 4.0})
    due = np.array([r["due"] for r in kind.generate(t, 1, 1000, 2000.0)
                    if r["in_window"]])
    # 1 s at 4 x rate and 4 s at the rate: half of the arrivals in bursts
    assert abs(np.mean(np.mod(due, 5.0) < 1.0) - 0.5) < 0.04
    assert abs(len(due) / 2000.0 / (t["rate_per_s"] * 8 / 5) - 1) < 0.06


def test_closed_loop_is_one_schedule_with_staggered_first_requests():
    t, kind = _mix("rollout-saturated")
    q1, q2 = kind.generate(t, 3, 1000), kind.generate(t, 4, 1000)
    assert len(q1) == 64 and all(len(q) == t["rounds"] for q in q1)
    shape = lambda qs: [[(len(r["prompt"]), r["max_new_tokens"]) for r in q]
                        for q in qs]
    assert shape(q1) == shape(q2) and q1[0][0]["prompt"] != q2[0][0]["prompt"]
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 2048
               for q in q1 for r in q)
    # first requests start part-way through: less is left of them
    assert (np.mean([q[0]["max_new_tokens"] for q in q1])
            < 0.75 * np.mean([q[1]["max_new_tokens"] for q in q1]))


@pytest.mark.parametrize("spec,lo,hi", [
    ({"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
      "max": 10**6}, 95, 105),
    ({"dist": "table", "quantiles": [[0, 10], [0.5, 20], [1, 40]],
      "min": 1, "max": 10**6}, 19, 21)])
def test_length_distributions(spec, lo, hi):
    x = trafficgen.lengths(spec, 20000, np.random.default_rng(0))
    assert lo <= np.median(x) <= hi
    with pytest.raises(ValueError):
        trafficgen.lengths(dict(spec, dist="other"), 1,
                           np.random.default_rng(0))
