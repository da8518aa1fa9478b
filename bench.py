"""Headline benchmark: ResNet-50 training-step throughput (images/sec/chip).

The reference publishes no numbers (BASELINE.md); the driver-set north star
is >=70% of the MLPerf-reference ResNet-50 throughput per chip
(`BASELINE.json`). This bench measures the full jitted training step —
forward + backward + Adam update, bfloat16 compute, batch-norm in training
mode — on one chip with a device-resident batch, which is the per-chip
number the data-parallel strategies multiply out (gradient all-reduce is
the only addition at scale and rides ICI).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Runs on a TPU only: any other platform is refused (ResNet-50 at B256 on
XLA:CPU measures nothing a user pays for), and so is a TPU whose
``device_kind`` is not in the bandwidth table below, unless
PDDL_BENCH_HBM_GBPS names its bandwidth explicitly.

Env overrides: PDDL_BENCH_BATCH (default 256), PDDL_BENCH_STEPS (default
60), PDDL_BENCH_IMAGE (default 224), PDDL_BENCH_STEM ("space_to_depth"
default / "keras" for the import-parity-shaped stem), PDDL_BENCH_HBM_GBPS
(chip HBM bandwidth, for a device kind the table does not know).

Baseline derivation (the ``vs_baseline`` denominator): the reference
publishes nothing ("published": {} in BASELINE.json), so the target is
derived from physics, not assumed: ResNet-50 training at these shapes is
HBM-bandwidth-bound (measured: MXU ~26%, >90% of spec bandwidth), so the
per-chip reference throughput is the memory roofline

    roofline img/s = HBM_bytes_per_sec / REFERENCE_bytes_per_image,

with the bandwidth from the published chip spec by device kind (Google
Cloud TPU docs; v5e = 819 GB/s HBM2) and bytes-per-image a FIXED recorded
constant of the reference formulation (328.7 MB at image 224, from XLA
cost analysis of the keras-stem step on v5e; area-scaled for other image
sizes) — deliberately NOT re-derived from the live step, so a change that
regresses bytes moved shows up in vs_baseline instead of re-rating its
own target; the live cost analysis is printed alongside for comparison.
vs_baseline = achieved / (0.7 * roofline), 0.7 per the BASELINE.json
north star ("≥70% of reference images/sec/chip").

The default stem is ``space_to_depth`` (measured neutral against the
keras stem); the ``stem`` used is emitted with the result.

Tuning history (measured on one v5e chip, batch 256, in rounds 1-5 on an
earlier stack; the driver's records of those runs are no longer kept and
nothing here has been re-measured since): rematerialization
variants (full-block and save-convs-only nn.remat) both LOSE (~2330 ->
~1920/~2020 img/s) — XLA's schedule already trades FLOPs for bytes better
than manual checkpointing here; batches 224/288/384/512 are all worse
than 256. The space-to-depth stem (models/resnet.py, MLPerf-style:
block-2 space-to-depth + 4x4/s1 conv, mathematically identical to the
padded 7x7/s2 stem) is the default bench variant; measured, it is
throughput-NEUTRAL here (2350 vs 2346 img/s, keras stem) because the
stem is noise against the step's ~330 MB/image total traffic — the
measurement that shows why "3000 img/s" is not reachable for this
formulation on this chip: the physical ceiling is the roofline above
(~2480 img/s at 819 GB/s), and the bench already runs at ~96% of it
(2380-2392 img/s at the 60-step window). Past that ceiling the lever is
not scheduling but changing the formulation's bytes (e.g. smaller
images, different normalization), which would change the trained model.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import optax

# Published per-chip HBM bandwidth by device kind (Google Cloud TPU
# system-architecture docs), matched against jax's device_kind string.
HBM_BYTES_PER_SEC = {
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,   # v5e: 16 GB HBM2 @ 819 GB/s
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,       # v5p
    "TPU v6 lite": 1640e9,  # v6e / Trillium
    "TPU v6e": 1640e9,
}

# The REFERENCE formulation's traffic: bytes-per-image of the compiled
# keras-stem step at image 224, batch 256, recorded from XLA cost
# analysis on v5e (84.1 GB/step = 328.7 MB/image). This is a FIXED
# constant on purpose: deriving the denominator from the live step's own
# cost analysis would make vs_baseline self-referential (a change that
# doubles bytes moved would halve throughput AND halve the roofline,
# hiding the regression). The live cost analysis is still printed for
# comparison. For non-224 images the constant scales by area (conv
# activation traffic is proportional to pixel count to first order).
REFERENCE_BYTES_PER_IMAGE_224 = 328.7e6
# BASELINE.json north star: ">=70% of reference images/sec/chip".
TARGET_FRACTION = 0.7


def _live_bytes_per_image(compiled, batch: int) -> float | None:
    """Bytes the compiled step actually moves per image (diagnostics)."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        total = float(cost["bytes accessed"])
        return total / batch if total > 0 else None
    except Exception:
        return None


def hbm_bytes_per_sec(device) -> float:
    """The device's HBM bandwidth, or exit: the bench runs on a TPU whose
    bandwidth is on record (or stated through PDDL_BENCH_HBM_GBPS)."""
    if device.platform != "tpu":
        sys.exit(f"bench: refusing to run on platform {device.platform!r} "
                 f"({device.device_kind}): this benchmark measures a TPU")
    override = float(os.environ.get("PDDL_BENCH_HBM_GBPS", "0")) * 1e9
    if override:
        return override
    if device.device_kind not in HBM_BYTES_PER_SEC:
        sys.exit(f"bench: unknown device_kind {device.device_kind!r}: no "
                 f"HBM bandwidth on record, so no roofline — set "
                 f"PDDL_BENCH_HBM_GBPS to state it explicitly")
    return HBM_BYTES_PER_SEC[device.device_kind]


def main() -> None:
    batch = int(os.environ.get("PDDL_BENCH_BATCH", "256"))
    steps = int(os.environ.get("PDDL_BENCH_STEPS", "60"))
    image = int(os.environ.get("PDDL_BENCH_IMAGE", "224"))
    stem = os.environ.get("PDDL_BENCH_STEM", "space_to_depth")

    from pddl_tpu.models.resnet import ResNet50
    from pddl_tpu.train.state import TrainState
    from pddl_tpu.utils.compile_cache import enable_persistent_compile_cache

    device = jax.devices()[0]
    hbm = hbm_bytes_per_sec(device)
    enable_persistent_compile_cache()
    print(f"bench: device={device} ({device.device_kind}), batch={batch}, "
          f"image={image}, steps={steps}, stem={stem}", file=sys.stderr)

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, stem=stem)
    tx = optax.adam(1e-3)
    rng = jax.random.key(0)

    images = jax.device_put(
        jax.random.normal(rng, (batch, image, image, 3), jnp.float32), device
    )
    labels = jax.device_put(
        jax.random.randint(jax.random.key(1), (batch,), 0, 1000), device
    )

    def init(rng):
        variables = model.init(rng, images[:1], train=False)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=variables["params"],
            batch_stats=variables.get("batch_stats", {}),
            opt_state=tx.init(variables["params"]),
        )

    t0 = time.perf_counter()
    state = jax.jit(init)(rng)
    jax.block_until_ready(state)
    print(f"bench: init {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    def train_step(state, images, labels):
        def loss_of(params):
            logits, updates = model.apply(
                {"params": params, "batch_stats": state.batch_stats},
                images, train=True, mutable=["batch_stats"],
            )
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
            return loss, updates["batch_stats"]

        (loss, batch_stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
            state.params
        )
        new_state = state.apply_gradients(tx, grads, batch_stats)
        return new_state, loss

    step = jax.jit(train_step, donate_argnums=(0,))
    t0 = time.perf_counter()
    # Explicit AOT lower+compile: the same executable is then CALLED
    # directly (calling the jit wrapper would compile a second time).
    step = step.lower(state, images, labels).compile()
    print(f"bench: compile {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    ref_bpi = REFERENCE_BYTES_PER_IMAGE_224 * (image / 224) ** 2
    roofline = hbm / ref_bpi
    live_bpi = _live_bytes_per_image(step, batch)
    live_note = (f"live {live_bpi / 1e6:.1f} MB/image (cost analysis)"
                 if live_bpi else "cost analysis unavailable")
    print(f"bench: reference {ref_bpi / 1e6:.1f} MB/image -> roofline "
          f"{roofline:.0f} img/s at {hbm / 1e9:.0f} GB/s; {live_note}",
          file=sys.stderr)

    t0 = time.perf_counter()
    state, loss = step(state, images, labels)
    float(loss)  # fetching the value waits for the device
    print(f"bench: first step {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    for _ in range(3):  # warmup
        state, loss = step(state, images, labels)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step(state, images, labels)
    loss = float(loss)
    dt = time.perf_counter() - t0

    images_per_sec = batch * steps / dt
    print(f"bench: {dt:.3f}s for {steps} steps, loss={loss:.3f}", file=sys.stderr)
    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            images_per_sec / (TARGET_FRACTION * roofline), 4),
        "stem": stem,
    }))


if __name__ == "__main__":
    main()
