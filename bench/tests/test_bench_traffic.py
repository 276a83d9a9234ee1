"""The traffic generator: every seed serves the same lengths in another
order, within the mix's limits, deterministically."""

import numpy as np
import pytest

from bench.harness.catalog import Catalog
from bench.harness.clients import bucket_of
from bench.harness.traffic import Requests, quantile, strata_lengths


@pytest.mark.parametrize("mix", ["chat", "preempt", "fleet-migrate"])
def test_blocks_hold_the_same_lengths_for_every_seed(mix):
    m = Catalog().traffic(mix)
    n = m["strata"]
    a, b = Requests(m, 1, 50304), Requests(m, 2 ** 33 + 7, 50304)
    for block in range(3):
        la = sorted(a.lengths(block * n + k) for k in range(n))
        lb = sorted(b.lengths(block * n + k) for k in range(n))
        assert la == lb == sorted(strata_lengths(m))
    # ... in another order
    assert [a.lengths(k) for k in range(n)] != [b.lengths(k)
                                                  for k in range(n)]


def test_lengths_within_limits_and_heavy_tailed():
    m = Catalog().traffic("chat")
    pairs = strata_lengths(m)
    p = [x for x, _ in pairs]
    o = [y for _, y in pairs]
    assert min(p) >= 32 and max(p) <= 512
    assert min(o) >= 16 and max(o) <= 64
    assert np.mean(p) > np.median(p)          # right tail
    assert np.mean(p) > 3 * np.mean(o)        # prompts longer than answers
    assert quantile(m["prompt_len"], 0.5) == 192


def test_requests_are_deterministic():
    m = Catalog().traffic("chat")
    r1, r2 = Requests(m, 99, 1000), Requests(m, 99, 1000)
    for i in (0, 5, 40):
        p1, n1 = r1.request(i)
        p2, n2 = r2.request(i)
        assert n1 == n2 and np.array_equal(p1, p2)
        assert p1.dtype == np.int32 and p1.max() < 1000
    assert not np.array_equal(r1.request(0)[0][:16],
                              Requests(m, 100, 1000).request(0)[0][:16])


def test_warmup_fills_each_bucket():
    m = Catalog().traffic("chat")
    w = Requests(m, 3, 1000).warmup((128, 512))
    assert [len(p) for p, _ in w] == [128, 512]
    assert [bucket_of(len(p), (128, 512)) for p, _ in w] == [128, 512]
    assert bucket_of(129, (128, 512)) == 512
    assert bucket_of(600, (128, 512)) == 512


def test_client_count():
    m = Catalog().traffic("fleet-migrate")
    assert Requests(m, 0, 10).clients(8) == 2 * 8 * 3
    assert Requests(Catalog().traffic("chat"), 0, 10).clients(8) == 16
