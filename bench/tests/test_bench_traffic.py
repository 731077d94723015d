"""The traffic mixes: deterministic per seed, the same work for every seed,
and the parameters each mix states."""
import collections

import pytest

from bench import loadgen

SEEDS = (0, 2 ** 31 + 11)


def arrivals(m: dict, seed: int, n_closed: int = 640) -> list:
    """An open mix's arrivals over 30 s, or the first ``n_closed``
    requests of a closed loop's tenants."""
    got = loadgen.generate(m, seed, 30.0)
    if isinstance(got, list):
        return got
    return [got.next(t["name"]) for t in m["tenants"]
            for _ in range(n_closed)]


@pytest.mark.parametrize("mix", ["squeeze", "saturate"])
def test_same_seed_same_arrivals(mix):
    m = loadgen.load_mix(mix)
    assert arrivals(m, 7) == arrivals(m, 7)
    assert arrivals(m, 7) != arrivals(m, 8)


@pytest.mark.parametrize("mix", ["squeeze", "saturate"])
def test_every_seed_gets_the_same_work(mix):
    m = loadgen.load_mix(mix)
    works = []
    for s in SEEDS:
        arr = arrivals(m, s)
        assert all(0.0 <= a.t < 30.0 for a in arr)
        assert [a.t for a in arr] == sorted(a.t for a in arr)
        works.append(sorted(collections.Counter(
            (a.tenant, a.function, a.prompt, a.decode) for a in arr).items()))
    assert works[0] == works[1]


def test_squeeze_parameters():
    m = loadgen.load_mix("squeeze")
    assert [t["name"] for t in m["tenants"]] == ["A", "B"]
    assert {t["replica"] for t in m["tenants"]} == {"A", "B"}
    assert (m["budget_partitions"], m["pool_partitions"]) == (32, 8)
    assert m["keep_alive_s"] == 4.0
    assert m["arrivals"] == {"kind": "alternating_bursts", "period_s": 10.0,
                             "burst_x": 4.0}
    arr = loadgen.generate(m, 3, 30.0)
    per = collections.Counter((a.tenant, int(a.t // 5)) for a in arr)
    base = m["rate_per_s"] / 5.0             # one tenant at 4x, one at 1x
    for k in range(6):
        hot, cold = ("A", "B") if k % 2 == 0 else ("B", "A")
        assert per[(hot, k)] == round(5 * 4 * base)
        assert per[(cold, k)] == round(5 * base)
    assert abs(len(arr) / 30.0 - m["rate_per_s"]) < 0.05 * m["rate_per_s"]


@pytest.mark.parametrize("mix,n_fn", [("squeeze", 8), ("saturate", 16)])
def test_function_grid(mix, n_fn):
    """Prompt and decode lengths come from the stated grids with the
    weights 0.4/0.3/0.2/0.1 (largest remainder over the functions)."""
    m = loadgen.load_mix(mix)
    for t in m["tenants"]:
        fns = t["functions"]
        assert len(fns) == n_fn
        for key, grid in (("prompt", (256, 512, 1024, 1536)),
                          ("decode", (32, 64, 128, 256))):
            got = collections.Counter(f[key] for f in fns)
            assert set(got) <= set(grid)
            for v, w in zip(grid, (0.4, 0.3, 0.2, 0.1)):
                assert abs(got[v] - w * n_fn) < 1.0
    assert m["zipf_s"] == 1.0


def test_zipf_picks_follow_rank():
    counts = loadgen.zipf_counts(1000, 8, 1.0)
    assert sum(counts) == 1000
    assert counts == sorted(counts, reverse=True)
    assert counts[0] == round(1000 / sum(1 / k for k in range(1, 9)))


def test_saturate_is_steady():
    """Closed-loop clients outnumber the arena's 64 rows, and every block
    of requests holds the same Zipf allocation over the functions."""
    m = loadgen.load_mix("saturate")
    assert m["pool_partitions"] == 0 and m["budget_partitions"] == 64
    assert m["arrivals"]["kind"] == "closed"
    loop = loadgen.generate(m, 1, 30.0)
    assert loop.clients == {"S": 96} and loop.clients["S"] > 64
    block = m["arrivals"]["block"]
    want = loadgen.zipf_counts(block, 16, 1.0)
    names = [f["name"] for f in m["tenants"][0]["functions"]]
    for _ in range(3):
        got = collections.Counter(loop.next("S").function
                                  for _ in range(block))
        assert [got[n] for n in names] == want
