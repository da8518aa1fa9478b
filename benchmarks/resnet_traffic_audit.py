"""ResNet-50 train-step HBM-traffic audit: is ~330 MB/image real?

Round-3 answer to "audit the 330 MB/image op-by-op" — two parts:

1. **Empirical boundedness probe** (`--probe`): times three program
   variants on the real chip (bf16 fwd+bwd+adam, bf16 fwd-only, f32
   fwd+bwd+adam) against two predictors — XLA cost-analysis bytes at the
   819 GB/s HBM spec vs model FLOPs at peak. Measured (v5e, B256 I224):

       variant        measured   bytes-predicted   flops-predicted
       bf16 full      109.2 ms       103.4 ms          29.2 ms
       bf16 fwd-only   30.2 ms        25.5 ms           9.9 ms
       f32  full      187.8 ms       169.6 ms     29.8-120 ms

   Wall-clock tracks the BYTES model within 5-16% across all three
   programs (and not FLOPs, off by 1.6-3.7x) — the step is genuinely
   HBM-bandwidth-bound and the cost model's byte count is predictive of
   the hardware, validating bench.py's fixed 328.7 MB/image roofline
   denominator (bench.py:86-95).

2. **Instruction-level attribution** (`--attribute`): parses the
   optimized HLO and sums operand/result bytes per top-level
   instruction, grouped by op kind and by model layer. This accounts
   for ~80 MB/image; the remaining ~250 of the cost model's 330 lives
   INSIDE convolution/fusion internals — overlapping-window re-reads
   and multi-pass tile accesses that the instruction-boundary view
   cannot see but (per the probe) the hardware really pays.
   Instruction-level traffic concentrates in the high-resolution early
   stages (stage1 blocks ~8/4.8/4.8 MB/img, stem ~3.3) and the maxpool
   fwd/bwd pair (reduce_window + select-and-scatter + pad, ~9.6).

Conclusion recorded in docs/ARCHITECTURE.md §7c: at ~95% of the HBM
roofline with XLA already fusing BN/ReLU/residual chains into the convs,
the remaining byte levers (activation dtype below bf16, different
normalization, resolution/architecture changes) all change the trained
model — exactly the boundary bench.py:54-59 asserts. The audit turns
that assertion into a measured result.

    PYTHONPATH=. python benchmarks/resnet_traffic_audit.py --probe
    PYTHONPATH=. python benchmarks/resnet_traffic_audit.py --attribute
"""

from __future__ import annotations

import argparse
import collections
import re
import time

import jax
import jax.numpy as jnp
import optax

from pddl_tpu.models.resnet import ResNet50
from pddl_tpu.train.state import TrainState

B, I = 256, 224
HBM = 819e9
BF16_PEAK = 197e12


def _setup(dtype):
    model = ResNet50(num_classes=1000, dtype=dtype, stem="space_to_depth")
    images = jnp.zeros((B, I, I, 3), jnp.float32)
    labels = jnp.zeros((B,), jnp.int32)
    tx = optax.adam(1e-3)

    def init(rng):
        v = model.init(rng, images[:1], train=False)
        return TrainState(step=jnp.zeros((), jnp.int32), params=v["params"],
                          batch_stats=v.get("batch_stats", {}),
                          opt_state=tx.init(v["params"]))

    state = jax.jit(init)(jax.random.key(0))
    return model, state, images, labels, tx


def _step_fn(model, tx, fwd_only=False):
    def step(state, images, labels):
        def loss_of(params):
            (logits, upd) = model.apply(
                {"params": params, "batch_stats": state.batch_stats},
                images, train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, upd

        if fwd_only:
            loss, _ = loss_of(state.params)
            return state, loss
        (loss, upd), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state.params)
        return state.apply_gradients(tx, grads, upd.get("batch_stats")), loss

    return step


def probe() -> None:
    for name, dtype, fwd_only, iters in (
        ("bf16 fwd+bwd+adam", jnp.bfloat16, False, 30),
        ("bf16 fwd only", jnp.bfloat16, True, 30),
        ("f32 fwd+bwd+adam", jnp.float32, False, 10),
    ):
        model, state, images, labels, tx = _setup(dtype)
        j = jax.jit(_step_fn(model, tx, fwd_only), donate_argnums=(0,))
        compiled = j.lower(state, images, labels).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # per-program list on some versions
            ca = ca[0]
        by, fl = ca.get("bytes accessed", 0.0), ca.get("flops", 0.0)
        state, loss = j(state, images, labels)
        float(loss)  # fetching the value waits for the device
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = j(state, images, labels)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        print(f"{name:18s} {dt*1e3:7.1f} ms | bytes {by/1e9:6.1f} GB -> "
              f"{by/HBM*1e3:6.1f} ms at HBM spec | flops {fl/1e12:5.2f} TF "
              f"-> {fl/BF16_PEAK*1e3:5.1f} ms at bf16 peak")


_DT = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
       "pred": 1, "s64": 8, "u64": 8, "s16": 2, "u16": 2}
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_INST = re.compile(r"^\s*(?:ROOT )?%([\w\.\-]+) = (\([^)]*\)|\S+) ([\w\-]+)\(")
# Data movement pairs / structural ops: counting them would double-count
# the producer+consumer bytes already attributed to the compute ops.
_SKIP = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "copy-start", "copy-done", "slice-start", "slice-done",
         "async-start", "async-done", "async-update"}


def _nbytes(shape: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(shape):
        if dt not in _DT:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DT[dt]
    return total


def attribute() -> None:
    model, state, images, labels, tx = _setup(jnp.bfloat16)
    compiled = jax.jit(_step_fn(model, tx), donate_argnums=(0,)).lower(
        state, images, labels).compile()
    lines = compiled.as_text().split("\n")
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))

    defs, rows = {}, []
    for ln in lines[start + 1:]:
        mm = _INST.match(ln)
        if not mm:
            continue
        name, shape, kind = mm.groups()
        defs[name] = _nbytes(shape)
        rows.append((name, defs[name], kind, ln))

    by_kind = collections.Counter()
    by_layer = collections.Counter()
    for name, obytes, kind, ln in rows:
        if kind in _SKIP:
            continue
        args = re.search(r" [\w\-]+\(([^)]*)\)", ln)
        rbytes = sum(defs.get(a, 0)
                     for a in re.findall(r"%([\w\.\-]+)", args.group(1))) \
            if args else 0
        t = obytes + rbytes
        meta = re.search(r'op_name="([^"]+)"', ln)
        if meta:
            opn = re.sub(r"jit\(\w+\)/", "", meta.group(1))
            seg = opn.split("/")
            by_kind[f"{kind}:{seg[-1][:30]}"] += t
            by_layer[next((s for s in seg
                           if re.match(r"stage\d|stem|head", s)),
                          "other")] += t
        else:
            by_kind[kind] += t
            by_layer["other"] += t

    total = sum(by_kind.values())
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    ca_total = ca.get("bytes accessed", 0.0)
    print(f"instruction-level traffic: {total/1e9:.1f} GB "
          f"({total/B/1e6:.1f} MB/img); cost-model total "
          f"{ca_total/1e9:.1f} GB ({ca_total/B/1e6:.1f} MB/img) — the "
          "difference lives inside conv/fusion internals (window "
          "re-reads), which the boundedness probe shows are real")
    print("-- by op kind:")
    for label, b in by_kind.most_common(12):
        print(f"{b/1e9:7.2f} GB {b/B/1e6:6.1f} MB/img  {label}")
    print("-- by layer group:")
    for lay, b in by_layer.most_common(12):
        print(f"{b/1e9:7.2f} GB {b/B/1e6:6.1f} MB/img  {lay}")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--probe", action="store_true")
    p.add_argument("--attribute", action="store_true")
    a = p.parse_args()
    if not (a.probe or a.attribute):
        a.probe = a.attribute = True
    if a.probe:
        probe()
    if a.attribute:
        attribute()
