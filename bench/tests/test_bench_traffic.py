"""The traffic generators: fixed work per seed, latency from the due time,
lateness recorded, against a fake gateway that answers slowly."""
import asyncio
import time

import numpy as np
import pytest

from bench.catalog import Catalog
from bench.harness import Load
from bench.traffic import onoff, open_loop
from bench.traffic.open_loop import size_quantiles

POISSON = {"kind": "poisson", "rate_rps": 200.0,
           "rows": {"dist": "log_uniform", "lo": 1, "hi": 256}}


class FakeRows:
    def __init__(self, stall_at=None, stall_s=0.0):
        self.stall_at, self.stall_s = stall_at, stall_s

    def take(self, stream, k, n):
        if k == self.stall_at:
            time.sleep(self.stall_s)  # a sender that stalls once
        return np.full((n, 2), k, np.float32)


class FakeGateway:
    """Answers after ``delay_s``; counts how many requests are in flight."""

    def __init__(self, delay_s=0.004):
        self.delay_s, self.in_flight, self.most = delay_s, 0, 0

    async def submit(self, model, X):
        self.in_flight += 1
        self.most = max(self.most, self.in_flight)
        await asyncio.sleep(self.delay_s)
        self.in_flight -= 1
        return X[:, :1].astype(np.uint32), X[:, 0].astype(np.int32)


def drive(kind, mix, seconds, seed, gateway=None, rows=None):
    gateway = gateway or FakeGateway()
    load = Load(gateway, rows or FakeRows())
    gen = Catalog().generator(kind)
    return asyncio.run(gen.drive(load, mix, seconds, seed, 3)), gateway


def poisson_schedule(seed, seconds=2.0, mix=POISSON):
    rate = mix["rate_rps"]
    return open_loop.schedule(mix, seconds, seed, lambda t: rate * t,
                              lambda a: a / rate)


def test_schedule_is_fixed_work_in_seeded_order():
    off_a, n_a = poisson_schedule(2**31 + 99)
    off_b, n_b = poisson_schedule(2**31 + 99)
    off_c, n_c = poisson_schedule(5)
    assert np.array_equal(off_a, off_b) and np.array_equal(n_a, n_b)
    assert len(n_a) == len(n_c) == 400
    assert sorted(n_a) == sorted(n_c) and not np.array_equal(n_a, n_c)
    assert off_a[0] == 0.0 and (np.diff(off_a) > 0).all() and off_a[-1] < 2.0
    assert n_a.min() >= 1 and n_a.max() <= 256
    # log-uniform over 1..256: mean 255/ln(257), about 46
    assert 40 < n_a.mean() < 50


def test_size_distributions():
    assert set(size_quantiles({"dist": "fixed", "lo": 256}, 7)) == {256}
    u = size_quantiles({"dist": "uniform", "lo": 1, "hi": 8}, 800)
    assert sorted(set(u)) == list(range(1, 9)) and (np.bincount(u)[1:] == 100).all()
    with pytest.raises(ValueError):
        size_quantiles({"dist": "zipf"}, 3)


def test_latency_from_due_and_lateness_recorded():
    mix = dict(POISSON, rate_rps=100.0)
    window, _ = drive("poisson", mix, 0.5, 1, rows=FakeRows(stall_at=10, stall_s=0.1))
    recs = window.records
    assert len(recs) == 50 and all(r.ok for r in recs)
    assert all(r.t_done >= r.t_sent >= r.t_due - 1e-3 for r in recs)
    # the stall delays the stalled request's successors: they are sent late,
    # and their latency, counted from the due time, holds the delay
    late = [r for r in recs if r.late_s > 0.03]
    assert late and all(r.k >= 10 for r in late)
    assert all(r.latency_s >= r.late_s for r in recs)
    assert min(r.latency_s for r in late) > 0.03
    # every request's rows are its own, whatever the timing
    assert all(int(r.answer[1][0]) == r.k for r in recs)


def test_onoff_bursts_keep_the_mean():
    mix = {"rate_rps": 100.0, "period_s": 1.0, "on_share": 0.2,
           "on_factor": 2.5, "off_factor": 0.625,
           "rows": {"dist": "uniform", "lo": 1, "hi": 8}}
    cumulative, inverse = onoff.profile(mix, 10.0)
    assert cumulative(10.0) == pytest.approx(1000.0)
    off, _ = open_loop.schedule(mix, 10.0, 3, cumulative, inverse)
    phase = off % 1.0
    on = (phase < 0.2).sum()
    # 0.2 s at 250/s against 0.8 s at 62.5/s: half the arrivals each
    assert len(off) == 1000 and 450 < on < 550


def test_closed_loop_holds_its_clients():
    mix = {"kind": "closed", "clients": 3, "rows": {"dist": "fixed", "lo": 4}}
    window, gateway = drive("closed", mix, 0.3, 7, gateway=FakeGateway(0.01))
    recs = window.records
    assert gateway.most == 3
    assert sorted(r.k for r in recs) == list(range(len(recs)))
    assert 60 < len(recs) < 100 and all(r.rows == 4 and r.ok for r in recs)
    assert all(r.t_due >= window.t0 - 1e-3 for r in recs)
    assert max(r.t_due for r in recs) < window.t0 + 0.3


@pytest.mark.parametrize("raised, refused", [("admission", True), ("other", False)])
def test_send_records_refusals_and_failures(raised, refused):
    """A refused request is marked refused; any other failure is kept as an
    error, which the check counts as unanswered.  Neither is answered."""
    from repro.serve.queue import AdmissionError

    class Failing:
        async def submit(self, model, X):
            raise AdmissionError("full") if raised == "admission" else RuntimeError("lost")

    rec = open_loop.Record(k=0, rows=2, t_due=time.perf_counter())
    asyncio.run(Load(Failing(), FakeRows()).send(rec, np.zeros((2, 2))))
    assert not rec.ok and rec.refused is refused
    assert (rec.error is None) is refused
    assert rec.t_done >= rec.t_sent >= rec.t_due


@pytest.mark.parametrize("lost, want", [(0, 25.0), (1, 30.0), (4, None)])
def test_p50_counts_a_lost_request_as_later_than_any(lost, want):
    """p50_ms is the median over all requests: one refused or failed sits
    above every answered one, and with half lost there is no median."""
    from types import SimpleNamespace

    recs = []
    for k, ms in enumerate([10.0, 20.0, 30.0, 40.0]):
        recs.append(open_loop.Record(k=k, rows=1, t_due=0.0, t_done=ms / 1e3,
                                     answer=(np.zeros((1, 1)), np.zeros(1))))
    recs += [open_loop.Record(k=9, rows=1, t_due=0.0, refused=True)] * lost
    window = open_loop.Window(t0=0.0, seconds=1.0, records=recs)
    ctx = SimpleNamespace(window=window,
                          answered=[r for r in recs if r.ok])
    got = Catalog().reader("p50_ms").read(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
