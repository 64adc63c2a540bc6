"""Open-loop arithmetic: the schedule, latency from the due time, the
drain at the window's end, and the tail rule of the percentiles."""
import numpy as np
import pytest

from bench.stats import percentile_ms
from bench.traffic import offline
from bench.traffic import open as open_loop


class FakeClock:
    """A clock that only moves when the server works or the loop sleeps."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def _no_span(name):
    import contextlib
    return contextlib.nullcontext()


def _server(clock, service_s, seen):
    def serve(Q):
        seen.append(Q.shape)
        clock.t += service_s
        return np.arange(len(Q)), np.full(len(Q), 1.0)
    return serve


def test_arrivals_same_gaps_every_seed():
    a = open_loop.arrivals(500, 20.0, np.random.default_rng(1))
    b = open_loop.arrivals(500, 20.0, np.random.default_rng(2))
    assert not np.allclose(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)))
    assert a[-1] == pytest.approx(b[-1])
    assert a[-1] == pytest.approx(500 / 20.0, rel=0.02)
    assert np.all(np.diff(a) > 0)


def test_latency_counts_from_due_time_and_drains():
    clock = FakeClock()
    seen = []
    trf = {"batch": 4, "rate_qps": 40.0}
    Q = np.zeros((80, 8), np.float32)
    # 0.25 s per batch of 4 is 16 q/s against 40 offered: a queue builds
    s = open_loop.drive(_server(clock, 0.25, seen), Q, trf, 2.0,
                        np.random.default_rng(0), _no_span,
                        clock=clock, sleep=clock.sleep)
    due = open_loop.arrivals(80, 40.0, np.random.default_rng(0))
    assert len(s.latency_s) == 80                # every due request served
    assert all(shape == (4, 8) for shape in seen)  # padded to the batch
    assert s.elapsed_s == pytest.approx(clock.t)
    assert s.elapsed_s > 2.0                     # the drain ran past it
    done = s.latency_s + due
    assert np.all(s.latency_s >= 0.25 - 1e-9)    # at least one service
    assert np.all(np.diff(done) >= -1e-12)       # served in order
    assert s.latency_s[-1] > s.latency_s[0]      # the backlog grew


def test_idle_server_waits_for_arrivals():
    clock = FakeClock()
    trf = {"batch": 16, "rate_qps": 10.0}
    Q = np.zeros((20, 4), np.float32)
    s = open_loop.drive(_server(clock, 0.001, []), Q, trf, 2.0,
                        np.random.default_rng(3), _no_span,
                        clock=clock, sleep=clock.sleep)
    # a fast server answers each request alone, one service after due
    np.testing.assert_allclose(s.latency_s, 0.001, atol=1e-9)
    assert s.n_batches == 20


def test_offline_counts_the_batch_in_flight():
    clock = FakeClock()
    Q = np.zeros((64, 4), np.float32)
    s = offline.drive(_server(clock, 0.3, []), Q, {"batch": 16}, 1.0,
                      np.random.default_rng(0), _no_span,
                      clock=clock, sleep=clock.sleep)
    assert s.n_batches == 4                  # 0, 0.3, 0.6, 0.9 < 1.0
    assert s.elapsed_s == pytest.approx(1.2)
    assert len(s.nn) == 64 and len(s.queries) == 64


@pytest.mark.parametrize("n,pct,ok", [(199, 95, False), (200, 95, True),
                                      (19, 50, False), (20, 50, True),
                                      (0, 50, False)])
def test_tail_needs_ten_samples_beyond(n, pct, ok):
    x = np.linspace(0.001, 1.0, n)
    v = percentile_ms(x, pct)
    assert (v is not None) == ok
    if ok:
        assert v == pytest.approx(1e3 * np.percentile(x, pct))
