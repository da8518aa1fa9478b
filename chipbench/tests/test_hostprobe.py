"""The stall probe: a step of half a second or more is kept with what the
host was doing in it; a wait for an arrival is not a stall. The pin: the
loop's thread alone on one core, every other thread off it, and all as
they were after the release."""

import gc
import os
import threading
import time

import pytest

from chipbench import hostprobe


def _window(monkeypatch, stall_s=0.05):
    monkeypatch.setattr(hostprobe, "STALL_S", stall_s)
    probe = hostprobe.HostProbe()
    t0 = time.perf_counter()
    probe.open(t0)
    return probe, t0


def _step(probe, t0, index, work, record=None):
    a = time.perf_counter() - t0
    work()
    b = time.perf_counter() - t0
    probe.step(index, a, b, record or {})
    return {"t0": a, "t1": b}


def test_a_blocked_step_reads_as_wall_time_without_cpu(monkeypatch):
    probe, t0 = _window(monkeypatch)
    steps = [_step(probe, t0, 0, lambda: None),
             _step(probe, t0, 1, lambda: time.sleep(0.12),
                   {"phase_wall_s": {"tick_wait": 0.12, "emit": 0.0},
                    "live_slots": 7, "queue_depth": 2})]
    probe.close()
    found = probe.summary(steps)
    assert found["long_steps"] == 1 and found["long_steps_s"] >= 0.12
    (stall,) = found["stalls"]
    assert stall["step"] == 1 and stall["step_s"] >= 0.12
    assert stall["phases"] == {"tick_wait": 0.12}
    assert stall["thread_cpu_s"] < 0.05      # asleep, not computing
    assert stall["live"] == 7 and stall["queue"] == 2


def test_a_computing_step_reads_as_cpu(monkeypatch):
    probe, t0 = _window(monkeypatch)

    def spin():
        end = time.perf_counter() + 0.12
        while time.perf_counter() < end:
            pass

    steps = [_step(probe, t0, 0, spin)]
    probe.close()
    (stall,) = probe.summary(steps)["stalls"]
    assert stall["thread_cpu_s"] > 0.08


def test_a_gap_between_steps_is_kept_and_an_idle_wait_is_not(monkeypatch):
    probe, t0 = _window(monkeypatch)
    _step(probe, t0, 0, lambda: None)
    time.sleep(0.08)                       # the loop itself stalled
    _step(probe, t0, 1, lambda: None)
    probe.idle()                           # nothing to do: waits
    time.sleep(0.08)
    _step(probe, t0, 2, lambda: None)
    probe.close()
    stalls = probe.stalls
    assert [s["step"] for s in stalls] == [1]
    assert stalls[0]["gap_before_s"] >= 0.08 and stalls[0]["step_s"] < 0.05


def test_collections_inside_the_window_are_kept(monkeypatch):
    probe, t0 = _window(monkeypatch)
    before = len(gc.callbacks)
    gc.collect(0)
    steps = [_step(probe, t0, 0, lambda: gc.collect(2))]
    probe.close()
    assert len(gc.callbacks) == before - 1
    found = probe.summary(steps)
    assert found["gc"]["0"]["count"] >= 1 and found["gc"]["2"]["count"] == 1
    assert all(0 <= t and s >= 0 for t, _, s in probe.collections)
    gc.collect()                           # after close: not kept
    assert found["gc"] == probe.summary(steps)["gc"]


@pytest.mark.skipif(not hasattr(os, "sched_setaffinity")
                    or len(os.sched_getaffinity(0)) < 3,
                    reason="needs three cores and sched_setaffinity")
def test_pin_holds_one_core_and_gives_every_thread_its_cores_back():
    allowed = os.sched_getaffinity(0)
    stop, seen = threading.Event(), {}
    other = threading.Thread(target=stop.wait)
    other.start()
    pin = hostprobe.Pin()
    try:
        cpu = pin.hold()
        assert cpu == max(allowed) and os.sched_getaffinity(0) == {cpu}
        assert cpu not in os.sched_getaffinity(other.native_id)

        def child():   # started by the pinned thread: inherits its core
            seen["born"] = os.sched_getaffinity(0)
            pin.free_this_thread()
            seen["freed"] = os.sched_getaffinity(0)

        t = threading.Thread(target=child)
        t.start()
        t.join()
        assert seen["born"] == {cpu} and cpu not in seen["freed"]
    finally:
        pin.release()
        stop.set()
        other.join()
    assert os.sched_getaffinity(0) == allowed
    pin.release()                          # a second release does nothing


def test_python_speed_is_a_positive_time():
    assert 0.0 < hostprobe.python_speed_ms(repeats=2) < 1e3
