"""FIFO finish times of the serving fluid queue.

A request finishes at the first instant the served work covers its own;
where the queue empties, that instant lies inside the interval and not
at the next arrival.
"""
import types

import numpy as np
import pytest

from repro.serving import fluid_queue

CAP = 1.0           # work served a second at full duty


def _cost(work: float):
    """A request of ``work`` with no serialized-decode floor."""
    return types.SimpleNamespace(
        request_flops=work, prefill_flops=0.0, decode_flops_per_token=0.0,
        request=types.SimpleNamespace(output_tokens=1))


def _latency(arrivals, work, throttle=1.0, dt=1.0):
    q = fluid_queue(np.asarray(arrivals), _cost(work), CAP,
                    np.broadcast_to(throttle, (len(arrivals),)), dt, 32)
    return q.latency_s


def test_a_lone_request_finishes_when_its_work_is_done():
    # arrives mid-interval 0 (t = 0.5), work 2.5 s; the next arrival is at
    # interval 8, so the queue is empty from t = 2.5 until then
    lat = _latency([1, 0, 0, 0, 0, 0, 0, 0, 1, 0, 0, 0], 2.5)
    assert lat == pytest.approx([2.0, 2.0], rel=1e-12)


def test_requests_drained_in_one_interval_finish_inside_it():
    lat = _latency([2, 0, 0], 1.75, dt=4.0)
    # arrivals at 1 and 3; finishes at 1.75 and 3.5
    assert lat == pytest.approx([0.75, 0.5], rel=1e-12)


def test_a_throttled_interval_stretches_the_finish():
    lat = _latency([1, 0, 0, 0, 0, 0], 2.5, throttle=[1.0, 0.5, 1.0, 1.0,
                                                     1.0, 1.0])
    # 1 + 0.5 of work by t = 2, the last 1.0 by t = 3
    assert lat == pytest.approx([2.5], rel=1e-12)


def test_a_backlog_at_the_horizon_drains_at_the_last_rate():
    lat = _latency([3, 0], 1.0, throttle=[1.0, 0.5])
    # arrivals at 1/6, 1/2, 5/6; 1.5 of work served by t = 2, the rest
    # at 0.5 a second: finishes at 1, 2 + 0.5 / 0.5, 2 + 1.5 / 0.5
    assert lat == pytest.approx([1 - 1 / 6, 3 - 0.5, 5 - 5 / 6], rel=1e-12)
