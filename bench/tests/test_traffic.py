"""Seeded traffic: the same seed gives the same requests, every seed the
same sizes, and every length stays inside its clip."""

import os

import pytest

import closed
import lengths
import poisson
import run

MIXES = ["chat", "agent"]


def _mix(name):
    return run.load_json(os.path.join(run.BENCH, "traffic", name + ".json"))


def _gen(mix):
    return poisson if mix["arrival"] == "poisson" else closed


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = _gen(mix).schedule(mix, 1000, 2**31 + 17, 51)
    b = _gen(mix).schedule(mix, 1000, 2**31 + 17, 51)
    assert a == b
    c = _gen(mix).schedule(mix, 1000, 5, 51)
    assert run.jobs_of(a) != run.jobs_of(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    mix = _mix(name)
    sizes = lambda s: sorted((len(j["prompt"]), j["max_tokens"], j["greedy"])
                             for j in run.jobs_of(_gen(mix).schedule(
                                 mix, 1000, s, 51)))
    a, b = sizes(3), sizes(4)
    assert sorted(x[0] for x in a) == sorted(x[0] for x in b)
    assert sorted(x[1] for x in a) == sorted(x[1] for x in b)
    assert sum(x[2] for x in a) == sum(x[2] for x in b)


def test_closed_loop_seeds_run_the_same_scripts():
    mix = _mix("agent")
    script = lambda jobs: tuple((len(j["prompt"]), j["max_tokens"],
                                 j["greedy"]) for j in jobs)
    runs = [closed.schedule(mix, 1000, s, 51)["clients"]
            for s in (3, 2**31 + 17)]
    assert [script(c) for c in runs[0]] != [script(c) for c in runs[1]]
    assert sorted(map(script, runs[0])) == sorted(map(script, runs[1]))
    assert sorted(map(script, runs[0])) == sorted(
        tuple(s) for s in closed.scripts(mix))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips(name):
    mix = _mix(name)
    jobs = run.jobs_of(_gen(mix).schedule(mix, 1000, 9, 51))
    assert jobs
    for j in jobs:
        assert mix["prompt"]["min"] <= len(j["prompt"]) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= j["max_tokens"] <= mix["output"]["max"]
        assert all(0 <= t < 1000 for t in j["prompt"])
        assert len(j["prompt"]) + j["max_tokens"] <= lengths.max_seq_len(mix)


def test_open_loop_arrivals_fill_each_phase():
    mix = _mix("chat")
    sched = poisson.schedule(mix, 1000, 1, 51)
    warm = [j["due"] for j in sched["jobs"] if j["phase"] == "warmup"]
    win = [j["due"] for j in sched["jobs"] if j["phase"] == "window"]
    w = mix["warmup_s"]
    assert all(0 < t <= w + 1e-9 for t in warm)
    assert all(w < t <= w + 51 + 1e-9 for t in win)
    assert len(win) == int(mix["rate_per_s"] * 51)
    assert sorted(win) == sorted(set(win))


def test_lognormal_quantiles_center_on_the_median():
    spec = {"median": 100, "sigma": 0.5, "min": 1, "max": 10**6}
    xs = sorted(lengths.lognormal_sizes(101, spec))
    assert xs[50] == 100
    gaps = lengths.exponential_gaps(40, 2.0)
    assert abs(sum(gaps) - 20.0) < 1e-9
