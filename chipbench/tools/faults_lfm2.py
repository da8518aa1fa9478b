"""The LFM2-24B-A2B cell's timed path broken underneath, one fault at a
time (``tools/faults.py``'s stay as they are and are offered here too):
what ``chipbench/tests/test_lfm2.py`` plants to see ``correct`` come out
false, and what a run on the chip plants at the cell's own size:

    python3 chipbench/tools/faults_lfm2.py --fault state_not_carried \\
        --workload lfm2_extract_saturated --seed 7 --seconds 50

    undo = plant("taps_reversed"); ...; undo()

The convolution's state (``llama.ShortConv``): ``state_not_carried`` —
every chunk starts from zeros, as if it were a prompt's first;
``state_from_padded_tail`` — a chunk leaves the state of its last two ROWS,
padding or not; ``previous_stream_kept`` — a prompt's first chunk starts
from what the slot's last stream left. Its mathematics: ``b_c_exchanged``,
``taps_reversed``. Attention: ``qk_norm_left_out``. The router:
``selection_bias_dropped``, ``bias_in_the_gates``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tools import faults  # noqa: E402


def _swap(owner, name, broken):
    real = owner.__dict__[name]
    setattr(owner, name, broken)
    return lambda: setattr(owner, name, real)


def _state_not_carried():
    import jax.numpy as jnp
    from pddl_tpu.models.llama import ShortConv

    real = ShortConv._history
    return _swap(ShortConv, "_history", staticmethod(
        lambda held, position: real(held, position) if position.ndim
        else jnp.zeros_like(held)))


def _state_from_padded_tail():
    from pddl_tpu.models.llama import ShortConv

    return _swap(ShortConv, "_state_after",
                 lambda self, zp, valid: zp[:, zp.shape[1] - (
                     self.kernel_size - 1):])


def _previous_stream_kept():
    from pddl_tpu.models.llama import ShortConv

    return _swap(ShortConv, "_history",
                 staticmethod(lambda held, position: held))


def _b_c_exchanged():
    import jax.numpy as jnp
    from pddl_tpu.models.llama import ShortConv

    def exchanged(projected):
        b, c, x = jnp.split(projected, 3, axis=-1)
        return c, b, x

    return _swap(ShortConv, "_split_in", staticmethod(exchanged))


def _taps_reversed():
    from flax import linen as nn
    from pddl_tpu.models.llama import ShortConv

    def param(self, name, *args, **kw):
        value = nn.Module.param(self, name, *args, **kw)
        return value[::-1] if name == "taps" else value

    ShortConv.param = param
    return lambda: delattr(ShortConv, "param")


def _lfm2_option(key, value):
    """One of the constructor's published options changed under every
    ``LFM2_24B_A2B`` built while the fault is in."""
    def plant():
        from pddl_tpu.models import llama

        real = llama._LFM2[key]
        llama._LFM2[key] = value
        return lambda: llama._LFM2.__setitem__(key, real)
    return plant


def _bias_in_the_gates():
    """The selection bias leaks into the gates: they are taken from the
    biased scores the experts were chosen by."""
    import pddl_tpu.ops.moe as moe_mod

    serve = moe_mod.SwitchFFN._serve
    return _swap(
        moe_mod.SwitchFFN, "_serve",
        lambda self, x, probs, select, *w: serve(self, x, select, select, *w))


FAULTS = dict(
    faults.FAULTS, state_not_carried=_state_not_carried,
    state_from_padded_tail=_state_from_padded_tail,
    previous_stream_kept=_previous_stream_kept,
    b_c_exchanged=_b_c_exchanged, taps_reversed=_taps_reversed,
    qk_norm_left_out=_lfm2_option("qk_norm", False),
    selection_bias_dropped=_lfm2_option("moe_select_bias", False),
    bias_in_the_gates=_bias_in_the_gates)


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


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--fault", required=True, choices=sorted(FAULTS))
    p.add_argument("--workload", default="lfm2_extract_saturated")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=50.0)
    args = p.parse_args(argv)
    from chipbench import run

    plant(args.fault)
    out = run.run_cell(args.workload, args.seed, args.seconds, False)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "fault": args.fault, "correct": out["correct"],
                      "attempted": out["attempted"],
                      "failed": out["failed"], "checks": out["checks"],
                      "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
