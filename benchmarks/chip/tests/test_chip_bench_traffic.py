"""The general traffic generator with a fake system (no JAX): closed and
open loops, size specs, and the window's record."""
import time

import numpy as np
import pytest

from chipbench_tiny import spec  # noqa: F401  (puts the benchmark on the path)

from chipbench import traffic


class FakeSystem:
    """Serves each call in ``delay`` seconds; a unit per request."""

    def __init__(self, delay):
        self.delay, self.calls = delay, []

    def request(self, sizes):
        return sizes

    def call(self, requests):
        self.calls.append(len(requests))
        time.sleep(self.delay)
        return sum(r["n"] for r in requests)


def test_sizes_and_draws():
    mix = {"request": {"n": 3, "p": {"uniform": [2, 4]},
                       "q": {"choice": [8, 1, 8]}}}
    assert traffic.sizes(mix) == {"n": [3], "p": [2, 3, 4], "q": [1, 8]}
    a = traffic.Schedule(mix, np.random.default_rng(1))
    b = traffic.Schedule(mix, np.random.default_rng(1))
    draws = [a.next_sizes() for _ in range(50)]
    assert draws == [b.next_sizes() for _ in range(50)]
    assert {d["p"] for d in draws} == {2, 3, 4}
    with pytest.raises(ValueError):
        traffic.draw({"zipf": 2}, np.random.default_rng(0))


def test_closed_loop_batches_clients():
    mix = {"loop": "closed", "clients": 3, "batch": 2, "request": {"n": 1}}
    sysm = FakeSystem(0.01)
    rec = traffic.run_window(sysm, mix, traffic.Schedule(
        mix, np.random.default_rng(0)), 0.2)
    # 3 clients and 2 per call: a call always finds 2 waiting
    assert set(sysm.calls) == {2} and rec.calls == len(sysm.calls)
    assert rec.attempted == rec.units == 2 * rec.calls
    assert len(rec.latencies_s) == rec.attempted and rec.failed == 0
    # a request that waited a call out takes about two calls
    assert max(rec.latencies_s) >= 0.02
    assert rec.elapsed_s >= 0.2


def test_open_loop_follows_the_rate():
    mix = {"loop": "open", "rate_per_s": 200.0, "batch": 4,
           "request": {"n": 1}}
    sysm = FakeSystem(0.0)
    rec = traffic.run_window(sysm, mix, traffic.Schedule(
        mix, np.random.default_rng(0)), 0.5)
    assert 50 < rec.attempted < 200      # about 100 arrive in 0.5 s
    assert rec.late_s < 0.05


def test_a_failing_call_fails_its_requests():
    class Broken(FakeSystem):
        def call(self, requests):
            raise RuntimeError("device lost")

    mix = {"loop": "closed", "clients": 2, "batch": 2, "request": {"n": 1}}
    rec = traffic.run_window(Broken(0.0), mix, traffic.Schedule(
        mix, np.random.default_rng(0)), 0.2)
    assert rec.attempted == rec.failed == 2 and rec.units == 0
