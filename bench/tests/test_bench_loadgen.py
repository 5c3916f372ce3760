"""The seeded request schedules: the same seed gives the same schedule,
every seed the same set of sizes, lengths inside their clips, and the run
reports how late the generator was."""

import collections
import json

import numpy as np
import pytest

from bench import loadgen
from tinycells import ROOT

MIXES = ["iot-open-loop", "code-closed-loop"]


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    t = _mix(name)
    a = loadgen.schedule(t, 2**31 + 99, 30, 1000)
    b = loadgen.schedule(t, 2**31 + 99, 30, 1000)
    assert [(r.due, r.n_out, r.prompt.tolist()) for r in a] == \
           [(r.due, r.n_out, r.prompt.tolist()) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_in_another_order(name):
    t = _mix(name)
    a = loadgen.schedule(t, 1, 30, 1000)
    b = loadgen.schedule(t, 2, 30, 1000)
    count = lambda rs, f: collections.Counter(f(r) for r in rs)
    assert count(a, lambda r: len(r.prompt)) == count(b, lambda r: len(r.prompt))
    assert count(a, lambda r: r.n_out) == count(b, lambda r: r.n_out)
    assert [r.n_out for r in a] != [r.n_out for r in b]
    if t["arrivals"]["process"] == "poisson":
        assert a[-1].due == pytest.approx(b[-1].due)
        assert [r.due for r in a] != [r.due for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clips(name):
    t = _mix(name)
    reqs = loadgen.schedule(t, 7, 30, 1000)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.n_out for r in reqs])
    assert p.min() >= t["prompt_len"]["min"] and p.max() <= t["prompt_len"]["max"]
    assert o.min() >= t["output_len"]["min"] and o.max() <= t["output_len"]["max"]
    assert abs(np.median(p) - t["prompt_len"]["median"]) <= 1
    assert abs(np.median(o) - t["output_len"]["median"]) <= 1
    assert max(r.prompt.max() for r in reqs) < 1000


def test_open_loop_rate_and_closed_loop_clients():
    t = _mix("iot-open-loop")
    reqs = loadgen.schedule(t, 3, 30, 1000)
    rate = t["arrivals"]["rate_per_s"]
    in_window = sum(r.due < 30 for r in reqs)
    assert in_window == pytest.approx(rate * 30, rel=0.1)
    c = _mix("code-closed-loop")
    reqs = loadgen.schedule(c, 3, 30, 1000)
    arr = c["arrivals"]
    firsts = [r for r in reqs if r.due is not None]
    assert len(firsts) == arr["clients"]
    assert max(r.due for r in firsts) < arr["ramp_s"]
    assert len(reqs) == arr["clients"] * arr["requests_per_client"]


def test_lateness_counts_window_submissions():
    reqs = [loadgen.Req(rid=i, prompt=np.zeros(1, np.int32), n_out=1) for i in range(3)]
    for r, (due, sub) in zip(reqs, [(1.0, 1.5), (2.0, 2.25), (9.0, 9.0)]):
        r.due_at, r.submitted = due, sub
    np.testing.assert_allclose(loadgen.lateness(reqs, 0.0, 5.0), [0.5, 0.25])

