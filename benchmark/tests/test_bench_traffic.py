"""The traffic generator: the same requests for the same seed, others
for another, and the same work whatever the seed."""

import json
import os

import pytest

from benchmark import traffic_gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mixes():
    out = []
    for root in (BENCH, os.path.join(BENCH, "tests", "rehearsal")):
        for name in sorted(os.listdir(os.path.join(root, "traffic"))):
            with open(os.path.join(root, "traffic", name)) as f:
                doc = json.load(f)
            if "arrival" in doc:
                out.append(pytest.param(doc, id=name))
    return out


@pytest.mark.parametrize("mix", mixes())
def test_same_seed_same_requests_other_seed_same_work(mix):
    n = 3 * mix["cycle"]
    a = traffic_gen.build_requests(mix, 1000, 2**31 + 5, n)
    b = traffic_gen.build_requests(mix, 1000, 2**31 + 5, n)
    c = traffic_gen.build_requests(mix, 1000, 7, n)
    assert a == b
    assert a != c
    shape = lambda reqs: sorted((len(r["token_ids"]), r["max_new_tokens"]) for r in reqs)
    assert shape(a) == shape(c) == sorted(3 * traffic_gen.cycle_shapes(mix))
    # every whole cycle holds the same shapes, in another order
    k = mix["cycle"]
    assert shape(a[:k]) == shape(a[k:2 * k])
    assert [len(r["token_ids"]) for r in a[:k]] != [len(r["token_ids"]) for r in c[:k]]
    assert traffic_gen.clients(mix) == mix["arrival"]["clients"]


@pytest.mark.parametrize("mix", mixes())
def test_lengths_keep_their_limits(mix):
    for p, o in traffic_gen.cycle_shapes(mix):
        assert mix["prompt_tokens"]["min"] <= p <= mix["prompt_tokens"]["max"]
        assert p % mix["prompt_tokens"]["multiple"] == 0
        assert mix["output_tokens"]["min"] <= o <= mix["output_tokens"]["max"]


def test_an_unknown_arrival_kind_is_refused():
    with pytest.raises(ValueError):
        traffic_gen.clients({"arrival": {"kind": "poisson", "rate_per_s": 6.0}})
