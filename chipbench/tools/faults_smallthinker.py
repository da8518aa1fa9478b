"""The SmallThinker cell's timed path broken underneath, one fault at a
time (the GPT-2 cells' faults are ``tools/faults.py``, which this file
leaves as it is): what ``chipbench/tests/test_smallthinker.py`` plants to
see ``correct`` come out false.

    undo = plant("no_window"); ...; undo()
"""

from __future__ import annotations

from chipbench.tools import faults


def _no_window():
    """Every layer attends to its whole history: the window layers' mask
    (and their block skip) dropped in the program."""
    import pddl_tpu.models.llama as llama_mod

    real = llama_mod.Llama.layer_window
    llama_mod.Llama.layer_window = lambda self, i: None
    return lambda: setattr(llama_mod.Llama, "layer_window", real)


def _top5():
    """The sixth expert dropped: the serving expert path is handed the
    gates of the five likeliest alone (the last choice's gate zeroed)."""
    import pddl_tpu.ops.moe as moe_mod

    real = moe_mod.grouped_expert_ffn

    def five(x, expert_index, gates, *args, **kw):
        return real(x, expert_index, gates.at[:, -1].set(0.0), *args, **kw)

    moe_mod.grouped_expert_ffn = five
    return lambda: setattr(moe_mod, "grouped_expert_ffn", real)


FAULTS = {"no_window": _no_window, "top5": _top5,
          "short_answers": faults.FAULTS["short_answers"]}


def plant(name: str):
    """As ``tools/faults.plant``: the traced programs are dropped on the
    way in and out."""
    import jax

    jax.clear_caches()
    undo = FAULTS[name]()

    def take_out():
        undo()
        jax.clear_caches()

    return take_out
