"""What decides ``correct``, held to its own rules at a size a CPU test can
hold. Each case drives ``run.run_cell`` — the harness itself, with only
its look for a chip skipped — and sees ``correct`` come out false: for
the control put in the program's place (the reference one precision down,
and the program's own int8 path switched on), and for the timed path
broken underneath (``chipbench/tools/faults.py``).

The chip's own readings, at the cell's size, are in PERF.md; the limits
here are this size's (stated below), not the chip's.
"""

import pytest

from chipbench import rehearse, run
from chipbench.tools import faults

# Readings at this size on the CPU (4 x 256, vocab 8192; seeds 1-4 and
# 2**31+5): greedy_gap_mean program 2.5e-5 to 7.1e-5, the fp8 control
# 1.9e-3 to 3.1e-3; greedy_gap_max program 0.0031-0.0062, fp8 0.046-0.084;
# nucleus_excess_max program -0.014 to -0.002, the filter dropped 0.094.
SMALL = {"gap_mean_limit": 4e-4, "gap_max_limit": 0.02,
         "nucleus_excess_limit": 0.03}


def small(cfg, spec):
    rehearse.shrink(cfg, spec)
    cfg.update(n_layer=4, n_embd=256, n_head=4, vocab_size=8192)
    cfg["check"] = dict(cfg["check"], requests=6, sampled_requests=6,
                        max_rows=64, **SMALL)


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_control_in_the_programs_place_comes_out_not_correct(seed):
    out = run.run_cell("gpt2l_chat_saturated", seed, 3.0, False,
                       allow_cpu=True, control="fp8", overrides=small)
    assert out["correct"], out["checks"]
    control = out["control"]["fp8"]
    assert control["correct"] is False, control["checks"]
    for name in ("greedy_gap_mean", "greedy_gap_max"):
        assert control["checks"][name]["value"] \
            >= 3 * out["checks"][name]["value"]


def broken(fault, workload, seed):
    undo = faults.plant(fault)
    try:
        return run.run_cell(workload, seed, 3.0, False, allow_cpu=True,
                            overrides=small)
    finally:
        undo()


def test_altered_token_comes_out_not_correct():
    out = broken("altered_token", "gpt2l_chat_saturated", 3)
    assert not out["correct"]
    assert out["checks"]["greedy_gap_max"]["value"] > SMALL["gap_max_limit"]
    assert out["checks"]["greedy_gap_mean"]["value"] \
        > SMALL["gap_mean_limit"]


def test_dropped_nucleus_filter_comes_out_not_correct():
    out = broken("no_top_p", "gpt2l_chat_saturated", 5)
    assert not out["correct"]
    assert out["checks"]["nucleus_excess_max"]["value"] \
        > SMALL["nucleus_excess_limit"]
    # the greedy rows are untouched by this fault: another number's catch
    assert out["checks"]["greedy_gap_max"]["value"] \
        <= SMALL["gap_max_limit"]


def test_unfinished_requests_come_out_not_correct():
    out = broken("short_answers", "gpt2l_chat_steady", 4)
    assert not out["correct"]
    assert out["failed"] == out["attempted"] > 0


def test_the_programs_int8_path_runs_through_the_harness():
    """The program's own weight-only int8 path, switched on by the tools'
    ``program_path``: it serves, and reads a wider mean gap than bf16 does
    on the same seed (at this size the two are too close for a limit
    between them; the chip's readings at the cell's size are PERF.md's)."""
    plain = run.run_cell("gpt2l_chat_saturated", 2, 3.0, False,
                         allow_cpu=True, overrides=small)
    int8 = run.run_cell("gpt2l_chat_saturated", 2, 3.0, False,
                        allow_cpu=True, overrides=small,
                        program_path="int8")
    assert int8["failed"] == 0 and int8["attempted"] > 0
    assert int8["checks"]["greedy_gap_mean"]["value"] \
        > plain["checks"]["greedy_gap_mean"]["value"]
