"""The reduction from a trace to the device numbers, checked on a small
recorded trace of a real traced run (``recorded/gpt2l_saturated_steps.json.gz``:
a few engine steps of ``gpt2l_chat_saturated`` on one TPU v5e) against
the same quantities computed here another way, and on made-up events
where the answer is known by construction."""

import gzip
import json
import os

import numpy as np
import pytest

from chipbench import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "recorded", "gpt2l_saturated_steps.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def _window(events):
    spans = [h for h in events["host"] if h[0] == tr.STEP_SPAN]
    return min(h[1] for h in spans), max(h[1] + h[2] for h in spans)


def test_idle_share_matches_a_rasterised_timeline(recorded):
    t0, t1 = _window(recorded)
    got = tr.reduce(recorded, t0, t1)
    # Another way: mark every microsecond in which some op runs.
    n = int(np.ceil((t1 - t0) * 1e6))
    busy = np.zeros(n + 1, np.int32)
    for name, start, dur, *_ in recorded["devices"][0]["ops"]:
        a = int(np.clip(np.floor((start - t0) * 1e6), 0, n))
        b = int(np.clip(np.ceil((start + dur - t0) * 1e6), 0, n))
        busy[a] += 1
        busy[b] -= 1
    covered = np.count_nonzero(np.cumsum(busy)[:n] > 0) / n
    assert got["chips"] == 1
    assert abs(got["busy_s"] / got["window_s"] - covered) < 0.01
    assert 0.0 < got["idle_pct"] < 100.0
    assert got["busy_s"] > 0


def test_kernel_and_program_times(recorded):
    t0, t1 = _window(recorded)
    got = tr.reduce(recorded, t0, t1)
    ops = [o for o in recorded["devices"][0]["ops"]
           if o[1] + o[2] > t0 and o[1] < t1]
    kernel = [o for o in ops
              if o[5] == "tpu_custom_call" and "_decode_step" in o[0]]
    assert kernel, "the recorded steps hold a tick: the kernel must be there"
    assert got["kernel_calls"] == len(kernel)
    assert got["kernel_s"] == pytest.approx(sum(o[2] for o in kernel))
    # 36 layers: the kernel runs once per layer per tick
    ticks = got["programs"]["jit__tick_paged"]["count"]
    assert len(kernel) == 36 * ticks
    assert got["kernel_s"] < got["programs"]["jit__tick_paged"]["seconds"]
    # self times add up to the busy time (nothing counted twice)
    assert sum(got["ops"].values()) == pytest.approx(got["busy_s"], rel=0.02)
    # every harness step found, each with device time inside it
    steps = [h for h in recorded["host"] if h[0] == tr.STEP_SPAN]
    assert len(got["steps"]) == len(steps)
    assert all(0 < s["busy_s"] <= s["t1"] - s["t0"] for s in got["steps"])
    assert got["collective_s"] == 0.0  # one chip: no collective


def _op(name, start, dur, opcode="fusion", target=""):
    return [name, start, dur, "f32[8]", opcode, target]


def test_known_answers_on_made_up_events():
    events = {"devices": [{"name": "/device:TPU:0", "modules": [
        ["jit_train_step(1)", 0.0, 1.0]], "ops": [
        _op("while.1", 0.0, 0.4, "while"),          # a container ...
        _op("fusion.1", 0.0, 0.1), _op("fusion.2", 0.1, 0.3),  # ... of two
        _op("all-reduce.1", 0.5, 0.2, "all-reduce"),
        _op("fusion.3", 0.6, 0.2),                  # hides half of it
        _op("attn._decode_step.4", 0.9, 0.05, "custom-call",
            "tpu_custom_call"),
    ]}], "host": [[tr.STEP_SPAN, 0.0, 1.0, "python3"],
                  ["DevicePut", 0.41, 0.08, "python3"],
                  ["PjitFunction(step)", 0.8, 0.1, "python3"]]}
    got = tr.reduce(events, 0.0, 1.0)
    assert got["busy_s"] == pytest.approx(0.4 + 0.3 + 0.05)
    assert got["idle_pct"] == pytest.approx(25.0)
    assert got["ops"]["while_f32_8"] == pytest.approx(0.0)  # self time
    assert got["ops"]["fusion_f32_8"] == pytest.approx(0.6)
    assert got["collective_s"] == pytest.approx(0.2)
    assert got["collective_exposed_s"] == pytest.approx(0.1)
    assert got["kernel_s"] == pytest.approx(0.05)
    assert got["kernel_calls"] == 1
    gaps = dict(got["idle_gaps"])
    assert gaps["DevicePut"] == pytest.approx(0.1)       # 0.4 .. 0.5
    assert gaps["PjitFunction_step_"] == pytest.approx(0.1)  # 0.8 .. 0.9
    assert got["steps"][0]["busy_s"] == pytest.approx(0.75)


def test_a_trace_with_no_device_op_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"devices": [{"name": "/device:TPU:0", "ops": [],
                                "modules": []}], "host": []})


def test_parse_op_reads_the_instruction():
    text = ('%attn._decode_step.36 = bf16[48,20,1,64]{3,2,1,0:T(2,128)(2,1)'
            'S(1)} custom-call(s32[48,64]{1,0} %copy-done.151), '
            'custom_call_target="tpu_custom_call"')
    assert tr.parse_op(text) == ("attn._decode_step.36", "bf16[48,20,1,64]",
                                 "custom-call", "tpu_custom_call")
    text = ("%copy.291 = bf16[3456,20,16,64]{3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[3456,20,16,64]{0,3,2,1:T(8,128)(2,1)} %cache.1)")
    assert tr.parse_op(text)[:3] == ("copy.291", "bf16[3456,20,16,64]",
                                     "copy")
    assert tr.op_label(["copy.291", 0, 0, "bf16[3456,20,16,64]", "copy",
                        ""]) == "copy_bf16_3456_20_16_64"
