"""``append_blocks_ms_per_step`` on made-up snapshots: the arithmetic,
and nothing to read (``None``, never 0) from a program without the
counter or a window without a step."""

import copy

import pytest

from chipbench import run

OPEN = {"engine_steps": 10, "phase_wall_s": {"append_blocks": 0.01,
                                             "tick_wait": 2.2}}
CLOSE = {"engine_steps": 30, "phase_wall_s": {"append_blocks": 0.13,
                                              "tick_wait": 6.6}}


def _obs():
    return {"facts": {"counters_open": copy.deepcopy(OPEN),
                      "counters_close": copy.deepcopy(CLOSE)}}


def test_reads_the_phase_wall_over_the_steps():
    read = run.metric_reader("append_blocks_ms_per_step")
    assert read(_obs()) == pytest.approx(1e3 * 0.12 / 20)


@pytest.mark.parametrize("side", ["counters_open", "counters_close"])
@pytest.mark.parametrize("lacks", ["phase_wall_s", "append_blocks",
                                   "engine_steps"])
def test_an_older_program_reads_as_nothing(side, lacks):
    read = run.metric_reader("append_blocks_ms_per_step")
    obs = _obs()
    if lacks == "append_blocks":
        del obs["facts"][side]["phase_wall_s"][lacks]
    else:
        del obs["facts"][side][lacks]
    assert read(obs) is None


def test_no_facts_and_no_steps_read_as_nothing():
    read = run.metric_reader("append_blocks_ms_per_step")
    assert read({}) is None and read({"facts": {}}) is None
    obs = _obs()
    obs["facts"]["counters_close"]["engine_steps"] = OPEN["engine_steps"]
    assert read(obs) is None


def test_its_cells_report_what_it_moves():
    bench = run.load_benchmark()
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "append_blocks_ms_per_step")
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    assert entry["workloads"] == ["gpt2l_chat_saturated",
                                  "glm47f_agent_saturated"]
    assert set(entry["workloads"]) <= set(moved["workloads"])
