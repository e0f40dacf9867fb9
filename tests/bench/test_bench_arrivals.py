"""The stratified traffic generator: the same work for every seed."""
import collections

import numpy as np

from bench import arrivals
from bench.spec import load_traffic

MIX = {**load_traffic("chat-sysprompt"), "rate_per_s": 3.0}


def _shape(reqs):
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_new_tokens for r in reqs),
            sorted(r.adapter_id for r in reqs),
            sorted(np.round(np.diff([0.0] + [r.due_s for r in reqs]), 9)))


def test_every_seed_gets_the_same_multiset_in_another_order():
    a = arrivals.serve_mix(MIX, 8, 131072, 5, 30.0)
    b = arrivals.serve_mix(MIX, 8, 131072, 2 ** 40 + 3, 30.0)
    assert _shape(a) == _shape(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_every_stratum_gets_the_same_work_for_every_seed():
    """Each consecutive block of arrivals holds the same multiset for every
    seed, so each stretch of the window brings the same work."""
    k = MIX["strata"]
    a = arrivals.serve_mix(MIX, 8, 131072, 11, 30.0)
    b = arrivals.serve_mix(MIX, 8, 131072, 2 ** 33 + 1, 30.0)
    sizes = np.bincount(arrivals.strata(len(a), k), minlength=k)
    assert sizes.max() - sizes.min() <= 1
    edges = np.cumsum(sizes)[:-1]
    for x, y in zip(np.split(np.array(a, object), edges),
                    np.split(np.array(b, object), edges)):
        assert _shape(list(x))[:3] == _shape(list(y))[:3]
        assert abs(x[-1].due_s - y[-1].due_s) < 1e-9
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_strata_share_out_small_and_large_values():
    s = arrivals.strata(16, 4)
    assert list(s) == [0, 1, 2, 3, 3, 2, 1, 0] * 2
    v = np.arange(16)
    assert len({int(v[s == j].sum()) for j in range(4)}) == 1


def test_same_seed_same_requests():
    a = arrivals.serve_mix(MIX, 8, 1000, 9, 10.0)
    b = arrivals.serve_mix(MIX, 8, 1000, 9, 10.0)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               for x, y in zip(a, b))


def test_all_requests_fall_due_inside_the_window():
    reqs = arrivals.serve_mix(MIX, 8, 1000, 1, 30.0)
    assert len(reqs) == 90
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0


def test_lengths_follow_the_mix():
    reqs = arrivals.serve_mix(MIX, 8, 1000, 1, 100.0)
    users = np.array([len(r.prompt) - 512 for r in reqs])
    outs = np.array([r.max_new_tokens for r in reqs])
    assert users.min() >= 16 and users.max() <= 1024
    assert outs.min() >= 16 and outs.max() <= 384
    assert abs(np.median(users) - 192) <= 2
    assert abs(np.median(outs) - 96) <= 2


def test_system_prompts_are_shared_within_an_adapter():
    reqs = arrivals.serve_mix(MIX, 8, 1000, 4, 30.0)
    heads = collections.defaultdict(set)
    for r in reqs:
        heads[r.adapter_id].add(r.prompt[:512].tobytes())
    assert all(len(h) <= 2 for h in heads.values())
    assert max(len(h) for h in heads.values()) == 2


def test_zipf_counts():
    c = arrivals.zipf_counts(100, 8, 1.0)
    assert c.sum() == 100 and list(c) == sorted(c, reverse=True)
    assert c[0] == 37                   # 100 / H_8 = 36.8
