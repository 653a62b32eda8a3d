"""The generators: the file fixes the work, the seed only orders it."""
from collections import Counter

import numpy as np
import pytest

from benchmark import loadgen, traffic
from benchmark.tests.tiny import EXAMPLE


def _load(name):
    return traffic.load_traffic(
        name, EXAMPLE if name.startswith("example") else traffic.HERE)


@pytest.mark.parametrize("name", ["decode_sat", "example_open_chat"])
def test_two_seeds_same_multiset_other_order_and_ids(name):
    spec = _load(name)
    n = 2 * len(traffic.length_pairs(spec))
    a = traffic.make_requests(spec, 1, 30522, n, 0)
    b = traffic.make_requests(spec, 2**31 + 5, 30522, n, 0)
    lens = lambda rs: Counter((len(r.prompt), r.max_new_tokens) for r in rs)
    assert lens(a) == lens(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    for r in a:
        assert len(r.prompt) + r.max_new_tokens <= spec["max_total"]
    # the same seed gives the same inputs
    c = traffic.make_requests(spec, 1, 30522, n, 0)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_arrival_gaps_same_multiset_mean_is_the_rate():
    spec = _load("example_open_chat")
    n = 3 * int(spec["gaps"]["quantiles"])
    gaps = lambda rs: np.round(np.diff([0.0] + [r.due_s for r in rs]), 9)
    a = gaps(traffic.make_requests(spec, 3, 30522, n, 0))
    b = gaps(traffic.make_requests(spec, 4, 30522, n, 0))
    assert Counter(a.tolist()) == Counter(b.tolist())
    assert a.tolist() != b.tolist()
    assert np.mean(a) == pytest.approx(1.0 / spec["rate_per_s"], rel=1e-9)


def test_first_wave_starts_mid_life():
    spec = traffic.load_traffic("decode_sat")
    rs = traffic.make_requests(spec, 7, 30522, 128, first_wave=48)
    cut = [r for r in rs[:48] if r.max_new_tokens < r.full_output]
    assert len(cut) >= 40                      # a uniform fraction of itself
    assert all(1 <= r.max_new_tokens <= r.full_output for r in rs[:48])
    assert all(r.max_new_tokens == r.full_output for r in rs[48:])


def test_train_rows_all_differ_and_seeded():
    spec = traffic.load_traffic("train_packed_512")
    x, y = traffic.make_train_rows(spec, 2**31 + 1, 30522, 64, 512, 2)
    assert x.shape == (64, 512) and y.shape == (64, 512, 1)
    assert len({row.tobytes() for row in x}) == 64
    x2, y2 = traffic.make_train_rows(spec, 2**31 + 1, 30522, 64, 512, 2)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    # the file fixes how many tokens of a row carry each class, the seed
    # only places them
    _, y3 = traffic.make_train_rows(spec, 7, 30522, 64, 512, 2)
    want = round(spec["label_shares"][1] * 512)
    assert (y.sum(axis=(1, 2)) == want).all()
    assert (y3.sum(axis=(1, 2)) == want).all()
    assert not np.array_equal(y, y3) and not np.array_equal(y[0], y[1])


def test_open_loop_due_clock_and_lateness():
    now = [100.0]
    sent = []

    def sleep(dt):
        now[0] += dt

    def send(i):
        sent.append((i, now[0]))
        if i == 1:
            now[0] += 0.5          # a slow submit makes the next one late
        return i

    gen = loadgen.OpenLoop([0.0, 0.1, 0.2, 1.0], send, clock=lambda: now[0],
                           sleep=sleep)
    gen.t_start = 100.0
    gen._run()                      # the thread's body, on the fake clock
    assert [i for i, _ in sent] == [0, 1, 2, 3]
    assert gen.due_at(3) == pytest.approx(101.0)
    late = gen.late_s
    assert late[0] == pytest.approx(0.0, abs=1e-9)
    assert late[2] == pytest.approx(0.4, abs=0.06)   # due 100.2, sent ~100.6
    assert late[3] == pytest.approx(0.0, abs=0.06)   # back on schedule
