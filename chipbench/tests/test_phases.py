"""The readers of the program's phase counters on made-up snapshots (the
arithmetic; ``None`` on a missing key; ``None`` on a zero count), and
``tools/phase_gaps.py`` on made-up events where the answer is known by
construction."""

import copy

import pytest

from chipbench import run
from chipbench.tools import phase_gaps

OPEN = {"engine_steps": 10, "step_wall_s": 3.0, "decode_ticks": 10,
        "queue_pops": 3, "queue_wait_s": 0.5,
        "admissions": 2, "admit_wall_s": 0.6,
        "phase_wall_s": {"reap": 0.01, "admit": 0.6,
                         "first_token_wait": 0.5, "append_blocks": 0.01,
                         "tick_dispatch": 0.1, "tick_wait": 2.2,
                         "emit": 0.05}}
CLOSE = {"engine_steps": 30, "step_wall_s": 9.0, "decode_ticks": 26,
         "queue_pops": 8, "queue_wait_s": 0.52,
         "admissions": 6, "admit_wall_s": 1.8,
         "phase_wall_s": {"reap": 0.03, "admit": 1.8,
                          "first_token_wait": 1.5, "append_blocks": 0.03,
                          "tick_dispatch": 0.26, "tick_wait": 6.6,
                          "emit": 0.15}}
# name -> (the value OPEN and CLOSE give, the key it cannot do without,
# the count that must not stand still)
EXPECTED = {
    # One of the five popped was cancelled before its slot: a pop and
    # no admission.
    "sched_queue_wait_mean_ms": (1e3 * 0.02 / 5, "queue_wait_s",
                                 "queue_pops"),
    "admit_wall_ms_per_request": (1e3 * 1.2 / 4, "admit_wall_s",
                                  "admissions"),
    "admit_stall_share_pct": (100 * 1.2 / 6.0, "phase_wall_s",
                              "step_wall_s"),
    "step_host_self_ms": (1e3 * (6.0 - 4.4 - 1.0) / 20, "phase_wall_s",
                          "engine_steps"),
    "tick_dispatch_ms_per_tick": (1e3 * 0.16 / 16, "phase_wall_s",
                                  "decode_ticks"),
    "tick_wait_ms_per_tick": (1e3 * 4.4 / 16, "decode_ticks",
                              "decode_ticks"),
}


def _obs(open_=OPEN, close=CLOSE):
    return {"facts": {"counters_open": copy.deepcopy(open_),
                      "counters_close": copy.deepcopy(close)}}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_arithmetic_and_nothing_to_read(name):
    read = run.metric_reader(name)
    value, key, count = EXPECTED[name]
    assert read(_obs()) == pytest.approx(value)
    # An older program: the snapshot has no such key, on either side.
    for side in ("counters_open", "counters_close"):
        obs = _obs()
        del obs["facts"][side][key]
        assert read(obs) is None
    assert read({"facts": {}}) is None
    # Nothing counted between the open and the close.
    obs = _obs()
    obs["facts"]["counters_close"][count] = OPEN[count]
    assert read(obs) is None


def test_a_phase_the_program_does_not_have_reads_as_nothing():
    obs = _obs()
    for side in obs["facts"].values():
        del side["phase_wall_s"]["tick_wait"]
    assert run.metric_reader("tick_wait_ms_per_tick")(obs) is None
    assert run.metric_reader("step_host_self_ms")(obs) is None
    assert run.metric_reader("admit_stall_share_pct")(obs) is not None


def _events():
    """Two harness steps of 1 s. Step 1: ``admit`` [0.1, 0.5] holding one
    ``admit_request`` [0.15, 0.45] with a runtime span beneath it, then
    ``tick_wait`` [0.6, 0.9]. The device is busy [0.2, 0.4] (a chunk
    program), [0.62, 0.9] and [1.1, 1.9]: idle gaps [0, 0.2] (midpoint
    0.1: ``admit``), [0.4, 0.62] (midpoint 0.51: the step itself),
    [0.9, 1.1] (midpoint 1.0: outside any engine step) and [1.9, 2.0]
    (inside the second step)."""
    host = [["chipbench.step", 0.0, 1.0, "python"],
            ["chipbench.step", 1.0, 1.0, "python"],
            ["pddl.serve.step", 0.05, 0.9, "python"],
            ["pddl.serve.admit", 0.1, 0.4, "python"],
            ["pddl.serve.admit_request", 0.15, 0.3, "python"],
            ["PjitFunction(_chunk_paged)", 0.16, 0.02, "python"],
            ["pddl.serve.tick_wait", 0.6, 0.3, "python"],
            ["pddl.serve.step", 1.05, 0.94, "python"],
            ["chipbench.step", 2.0, 1.0, "python"]]
    ops = [["fusion.1", 0.2, 0.2, "bf16[8]", "fusion", ""],
           ["fusion.2", 0.62, 0.28, "bf16[8]", "fusion", ""],
           ["fusion.3", 1.1, 0.8, "bf16[8]", "fusion", ""],
           ["fusion.4", 2.5, 0.1, "bf16[8]", "fusion", ""]]
    modules = [["jit__chunk_paged(1)", 0.2, 0.2],
               ["jit__tick_paged(2)", 0.62, 0.28]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host}


def test_phase_gaps_puts_each_gap_down_to_the_engine_phase():
    got = phase_gaps.analyse(_events(), steps=2)
    assert got["window_s"] == pytest.approx(2.0)
    assert got["busy_s"] == pytest.approx(0.2 + 0.28 + 0.8)
    assert got["idle_pct"] == pytest.approx(100 * 0.72 / 2.0)
    ph = got["phases"]
    assert ph["admit"]["idle_s"] == pytest.approx(0.2)
    assert ph["step"]["idle_s"] == pytest.approx(0.22 + 0.1)
    assert ph[phase_gaps.OUTSIDE]["idle_s"] == pytest.approx(0.2)
    assert ph["tick_wait"]["idle_s"] == 0
    assert sum(r["idle_s"] for r in ph.values()) == pytest.approx(
        got["idle_s"])
    assert "PjitFunction(_chunk_paged)" not in ph  # the runtime's span
    assert (ph["step"]["entries"], ph["admit"]["entries"]) == (2, 1)
    assert ph["admit"]["wall_s"] == pytest.approx(0.4)
    assert ph["admit"]["self_s"] == pytest.approx(0.1)
    assert ph["admit_request"]["busy_s"] == pytest.approx(0.2)
    assert ph["tick_wait"]["busy_s"] == pytest.approx(0.28)
    assert sum(r["self_s"] for r in ph.values()) == pytest.approx(2.0)
    (req,) = got["requests"]
    assert req["wall_s"] == pytest.approx(0.3)
    assert (req["chunks"], req["chunk_device_s"]) == (1, pytest.approx(0.2))
    assert "`admit_request`" in phase_gaps.table(got)


def test_phase_gaps_on_a_program_without_spans():
    events = _events()
    events["host"] = [h for h in events["host"]
                      if not h[0].startswith("pddl.serve.")]
    got = phase_gaps.analyse(events, steps=2)
    assert set(got["phases"]) == {phase_gaps.OUTSIDE}
    assert got["phases"][phase_gaps.OUTSIDE]["idle_s"] == pytest.approx(
        got["idle_s"])
    assert got["requests"] == []
    assert phase_gaps.table(got)
