"""Latency is timed from when a request was DUE. A fake server whose steps
stall shows what the program's own ``ttft_ms`` (timed from ``submit()``)
leaves out, and that the generator's lateness is reported."""

import importlib.util
import os
import time

import numpy as np
import pytest

from benchmark.drivers import open_loop
from benchmark.loadgen import TrafficRequest
from benchmark.serving import Served

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")


def reader(kind, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeServer:
    """Each step takes ``step_s`` and gives every running request a token."""

    def __init__(self, session, step_s):
        self.session, self.step_s, self.queue = session, step_s, []

    def has_work(self):
        return bool(self.queue)

    def queued_count(self):
        return 0


class FakeSession:
    def __init__(self, step_s):
        self.server = FakeServer(self, step_s)
        self.records, self.live, self.rows_log = [], {}, None

    def submit(self, rec):
        rec.submitted = time.perf_counter()
        self.records.append(rec)
        self.server.queue.append(rec)

    def step(self):
        time.sleep(self.server.step_s)
        now = time.perf_counter()
        for rec in list(self.server.queue):
            rec.stamps.append(now)
            if len(rec.stamps) == rec.req.max_new_tokens:
                rec.finished = now
                rec.output = np.concatenate([rec.req.prompt, np.zeros(rec.req.max_new_tokens, np.int32)])
                self.server.queue.remove(rec)

    def drain(self, limit_s):
        while self.server.has_work():
            self.step()

    def counters(self):
        return {}


def test_a_request_that_fell_due_during_a_stalled_step_is_timed_from_due():
    # steps of 50 ms; A is due at once, B 20 ms later, while A's first step runs
    session = FakeSession(step_s=0.05)
    prompt = np.arange(4, dtype=np.int32)
    trace = [TrafficRequest(0, prompt, 2, due_s=0.0, measured=False), TrafficRequest(1, prompt, 2, due_s=0.02)]
    win = open_loop.drive(session, trace, seconds=0.03, mix={"drain_seconds": 5.0})
    a, b = session.records
    assert a.ok() and b.ok()
    late_b = (b.submitted - b.due) * 1e3
    assert 25.0 <= late_b <= 45.0  # sent when A's step returned, ~30 ms after it was due
    ttft_from_due = (b.stamps[0] - b.due) * 1e3
    ttft_from_submit = (b.stamps[0] - b.submitted) * 1e3
    assert ttft_from_due == pytest.approx(ttft_from_submit + late_b, abs=1e-6)
    assert 75.0 <= ttft_from_due <= 100.0 and 50.0 <= ttft_from_submit <= 65.0
    # the readers: A only filled the batch, B is the measured one
    assert win["requests"] == [b]
    window = {"requests": win["requests"], "t0": win["t0"], "window_s": win["window_s"]}
    assert reader("end_to_end", "ttft_mean_ms").value(window, {}) == pytest.approx(ttft_from_due)
    assert reader("end_to_end", "itl_p50_ms").value(window, {}) == pytest.approx((b.stamps[1] - b.stamps[0]) * 1e3)
    assert reader("layer_metrics", "loadgen_late_p90_ms").value(None, {"late_ms": [late_b]}, {}) == pytest.approx(late_b)


def test_a_failed_request_counts_as_infinite_latency():
    p = np.arange(3, dtype=np.int32)
    good = Served(req=TrafficRequest(0, p, 2), due=0.0, stamps=[0.1, 0.2], output=np.concatenate([p, [7, 8]]).astype(np.int32))
    short = Served(req=TrafficRequest(1, p, 2), due=0.0, stamps=[0.1], output=np.concatenate([p, [7]]).astype(np.int32))
    never = Served(req=TrafficRequest(2, p, 2), due=0.0)
    assert good.ok() and not short.ok() and not never.ok()
    ttft = reader("end_to_end", "ttft_mean_ms")
    assert ttft.samples({"requests": [good, short, never]}) == [pytest.approx(100.0), float("inf"), float("inf")]
    assert ttft.value({"requests": [good, never]}, {}) == float("inf")
    assert reader("end_to_end", "itl_p50_ms").samples({"requests": [good, never]}) == [pytest.approx(100.0), float("inf")]


def test_tokens_per_second_counts_stamps_inside_the_window_only():
    p = np.arange(3, dtype=np.int32)
    r = Served(req=TrafficRequest(0, p, 4), due=10.0, stamps=[10.5, 11.5, 12.5, 13.5])
    window = {"requests": [r], "t0": 10.0, "window_s": 2.0}
    assert reader("end_to_end", "serve_tokens_per_s").value(window, {}) == pytest.approx(1.0)  # 10.5 and 11.5


def test_in_flight_stream_is_consistent_or_not():
    p = np.arange(3, dtype=np.int32)
    r = Served(req=TrafficRequest(0, p, 4), due=0.0, stamps=[0.1, 0.2], partial=np.concatenate([p, [5, 6]]).astype(np.int32))
    assert not r.ok() and r.ok_so_far()
    r.stamps.append(0.3)  # a stamp without a token
    assert not r.ok_so_far()


def test_ttft_is_the_mean_and_the_gap_the_median_over_the_measured_requests():
    p = np.arange(3, dtype=np.int32)
    out = np.concatenate([p, [7, 8, 9]]).astype(np.int32)
    quick = Served(req=TrafficRequest(0, p, 3), due=0.0, stamps=[0.1, 0.15, 0.2], output=out)
    slow = Served(req=TrafficRequest(1, p, 3), due=1.0, stamps=[1.4, 1.45, 1.6], output=out)
    window = {"requests": [quick, slow]}
    assert reader("end_to_end", "ttft_mean_ms").value(window, {}) == pytest.approx(250.0)  # (100 + 400) / 2
    assert reader("end_to_end", "itl_p50_ms").value(window, {}) == pytest.approx(50.0)  # gaps 50, 50, 50, 150
    assert reader("end_to_end", "ttft_mean_ms").value({"requests": []}, {}) is None


@pytest.mark.parametrize("stall_s", [0.0, 0.7])
def test_training_rate_is_the_median_over_groups_of_steps_so_one_stall_does_not_move_it(stall_s):
    # 481 steps of 0.1 s and 1,000 tokens each seen complete; one of them is held up by the machine
    gaps = np.full(480, 0.1)
    gaps[200] += stall_s
    stamps = list(np.concatenate([[5.0], 5.0 + np.cumsum(gaps)]))
    train = reader("end_to_end", "train_tokens_per_s_per_chip")
    rates = train.group_rates(stamps, 1000.0)
    assert len(rates) == train.GROUPS and sum(1 for r in rates if r < 9999.0) == (1 if stall_s else 0)
    window = {"tokens": 483_000, "steps": 483, "window_s": 48.3 + stall_s, "step_stamps": stamps}
    assert train.value(window, {"chips": 2}) == pytest.approx(5000.0)
    # every step slower moves it in full
    slower = {**window, "step_stamps": list(5.0 + 1.01 * (np.asarray(stamps) - 5.0))}
    assert train.value(slower, {"chips": 2}) == pytest.approx(5000.0 / 1.01)
    # too few steps for a group: the window's total over its length
    assert train.value({**window, "step_stamps": stamps[:1]}, {"chips": 2}) == pytest.approx(483_000 / window["window_s"] / 2)
