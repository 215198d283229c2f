import collections

import numpy as np
import pytest

import traffic


@pytest.mark.parametrize("mix_name", ["decode", "prefill"])
def test_window_stream_deterministic_per_seed(mix_name):
    mix = traffic.load_mix(mix_name)
    a = traffic.window_requests(mix, 8192, 2**33 + 5)
    b = traffic.window_requests(mix, 8192, 2**33 + 5)
    assert [(r.rid, r.master, r.gen_len, r.t_arrive, r.slack) for r in a] == \
        [(r.rid, r.master, r.gen_len, r.t_arrive, r.slack) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.window_requests(mix, 8192, 2**33 + 6)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix_name", ["decode", "prefill"])
def test_every_seed_offers_the_same_work(mix_name):
    """Stratified sizes: per master, the multiset of (prompt, output)
    sizes and of inter-arrival gaps is the same for every seed."""
    mix = traffic.load_mix(mix_name)

    def sizes(seed):
        reqs = traffic.window_requests(mix, 4096, seed)
        per = collections.defaultdict(list)
        times = collections.defaultdict(list)
        for r in reqs:
            per[r.master].append((len(r.prompt), r.gen_len))
            times[r.master].append(r.t_arrive)
        gaps = {m: np.sort(np.diff(t)) for m, t in times.items()}
        return {m: sorted(v) for m, v in per.items()}, gaps
    s1, g1 = sizes(1)
    s2, g2 = sizes(987654321012)
    assert s1 == s2
    assert all(np.allclose(g1[m], g2[m]) for m in g1)


@pytest.mark.parametrize("mix_name", ["decode", "prefill"])
def test_stream_shape(mix_name):
    mix = traffic.load_mix(mix_name)
    reqs = traffic.window_requests(mix, 4096, 3)
    M, S = mix["masters"], mix["slots_per_master"]
    assert len(reqs) == M * (mix["stream_requests_per_master"] + mix["burst_per_master"])
    assert [r.rid for r in reqs] == list(range(len(reqs)))
    assert all(a.t_arrive <= b.t_arrive for a, b in zip(reqs, reqs[1:]))
    # the burst fills every slot at t=0
    assert sum(1 for r in reqs if r.t_arrive == 0.0) == M * S
    assert {len(r.prompt) for r in reqs} == set(mix["prompt_buckets"])
    out = mix["output"]
    assert all(out["min"] <= r.gen_len <= out["max"] for r in reqs)
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 4096 for r in reqs[:50])
    assert max(len(r.prompt) + r.gen_len for r in reqs) + 8 <= traffic.max_len(mix)


def _batch_sizes(outs):
    """Batch sizes one master walks through: the first request runs alone,
    the rest join at step 2, a request leaves after its last token."""
    left = [outs[0] - 1] + list(outs[1:])
    sizes = [1]
    while any(left):
        active = [i for i, v in enumerate(left) if v]
        sizes.append(len(active))
        for i in active:
            left[i] -= 1
    return sizes


@pytest.mark.parametrize("mix_name", ["decode", "prefill"])
def test_probe_walks_every_batch_size(mix_name):
    mix = traffic.load_mix(mix_name)
    S = mix["slots_per_master"]
    probe = traffic.probe_requests(mix, 4096, 11)
    assert len(probe) == mix["masters"] * len(mix["probe"]["output_lens"])
    for m in range(mix["masters"]):
        mine = [r for r in probe if r.master == m]
        assert {len(r.prompt) for r in mine} == set(mix["prompt_buckets"])
        assert set(_batch_sizes([r.gen_len for r in mine])) == set(range(1, S + 1))
