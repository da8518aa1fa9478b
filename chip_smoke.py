"""The quickest proof that pddl_tpu still starts on the chip.

    python chip_smoke.py                # one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips   # the data-parallel path, four chips

One process, jax imported once, no child process. Without the option it
drives the system's two entry points once each at the full width of
models the repo ships, on one chip:

1. *train* — the CLI's own path (``pddl_tpu.run``: argv -> config ->
   ``run_experiment`` -> ``Trainer.fit``): ResNet-50, 224 px, batch 32,
   bf16, synthetic data, a few steps of one epoch.
2. *serve* — ``GPT_Small`` (vocab 50257, context 1024, bf16, weights from
   a fixed seed) through ``ServeEngine``: warmup, a wave of greedy
   requests of mixed prompt length, every stream checked against the
   model's own conditional and against one-shot ``generate()``; the
   tick must contain the Mosaic paged-decode kernel.
3. *kernels* — flash forward + fused backward and the paged decode
   kernel against their jnp oracles at GPT-small (12x64) and Llama-small
   (12/4x64) head shapes, compiled, not interpreted.

With ``--four-chips`` it runs only the path that exists across chips and
what that is compared with: ResNet-50 SGD steps under ``MirroredStrategy``
and ``ParameterServerStrategy`` on a four-chip mesh against the same seed
and batches on one device, and where parameters and batch really live.

Any phase that raises ends the run non-zero: nothing is caught and
downgraded to a warning. The script refuses any platform but a TPU and
has no option to run elsewhere; ``tests/test_chip_smoke.py`` rehearses the
phase functions on the CPU at tiny sizes, with the steering in the test.
Earlier stdout lines are one JSON object per phase (wall time, compile
count and seconds, persistent-cache hits, losses, gaps); the LAST line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing else.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# GPT-small and Llama-small attention geometry: (query heads, kv heads,
# head dim). The 64-wide minor dim is what the chip's compiler once
# refused in the paged kernel.
HEAD_SHAPES = ((12, 12, 64), (12, 4, 64))

# The paged decode kernel as the benchmark's cells call it: (slots, query
# heads, kv heads, head dim, context, window, shallowest and deepest
# row). `gpt2l_chat_*`: 48 rows of 20 x 64 at mixed depths over the
# 1,024-token context (block 16: a 64-wide table); `st21b_longdoc_steady`:
# 8 rows of 28 q / 4 kv heads of 128 at 1k-12k of a 16,384-token context
# (a 1,024-wide table), on a NoPE-global layer and on a window layer.
CELL_SHAPES = (
    (48, 20, 20, 64, 1024, None, 0, 1023),
    (8, 28, 4, 128, 16384, None, 1024, 12288),
    (8, 28, 4, 128, 16384, 4096, 1024, 12288),
)

# Greedy-consistency bound for untrained bf16 GPT-small — the one
# tests_tpu/ uses. Its logits are bf16 (spacing 2^-7 relative, ~0.016
# near the top logits of ~2.4) and two compiled programs for the same
# math differ by one or two of those: measured on a v5e, 0.021 for the
# paged engine and 0.010 for generate() against the teacher-forced
# forward, with 4 of 8 streams bit-equal. A WRONG token lands at a
# typical logit, ~2.4 under the max (std 0.55 over a 50257-way vocab),
# and only a handful of tokens are within 0.1 of it.
GREEDY_GAP_TOL = 0.1


# ------------------------------------------------------------- bookkeeping
class CompileMeter:
    """What jax compiled while this process ran, from jax's own
    monitoring events: backend compiles (count, seconds — a persistent-
    cache retrieval counts as a fast one) and persistent-cache hits."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.totals = {"compiles": 0, "compile_s": 0.0,
                       "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == self._COMPILE:
            self.totals["compiles"] += 1
            self.totals["compile_s"] += seconds

    def _event(self, event, **_):
        if event == self._HIT:
            self.totals["cache_hits"] += 1
        elif event == self._MISS:
            self.totals["cache_misses"] += 1

    def run(self, name: str, phase) -> None:
        """Run one phase and print its line: wall time, what compiled
        during it, and the facts the phase returned."""
        before = dict(self.totals)
        t0 = time.perf_counter()
        facts = phase()
        line = {"phase": name,
                "wall_s": round(time.perf_counter() - t0, 2)}
        for key, now in self.totals.items():
            line[key] = round(now - before[key], 2)
        line.update(facts)
        print(json.dumps(line), flush=True)


def require_tpu(count: int) -> list:
    """The ``count`` TPU devices this run needs, or exit non-zero. There
    is no fallback: fewer chips, or another platform, is a failure."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform "
                 f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found "
                 f"{len(devices)}")
    return devices[:count]


def memory_report(devices) -> dict:
    """Real HBM numbers from the backend, per device; raises where the
    backend reports none (``utils/profiling.device_memory_stats`` turns
    that into -1 for logs — a smoke must not)."""
    out = {}
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats \
                or "peak_bytes_in_use" not in stats:
            raise RuntimeError(
                f"{d} reports no bytes_limit/peak_bytes_in_use: {stats!r}")
        out[str(d)] = {"bytes_limit": int(stats["bytes_limit"]),
                       "peak_bytes_in_use": int(stats["peak_bytes_in_use"])}
    return {"memory": out}


# ------------------------------------------------------------------ train
def train_phase(steps: int = 4,
                cli: tuple = ("--model", "resnet50", "--image-size", "224",
                              "--batch", "32")) -> dict:
    """``python -m pddl_tpu --preset single --synthetic ...`` in-process:
    the CLI's argv -> config -> ``run_experiment`` path, not a hand-written
    step. bf16 compute is the preset's default."""
    from pddl_tpu import run

    cfg = run.config_from_argv([
        "--preset", "single", "--synthetic", *cli, "--epochs", "1",
        "--steps-per-epoch", str(steps), "--verbose", "0"])
    history = run.run_experiment(cfg)
    trainer = history.trainer
    losses = history.history["loss"] + history.history["val_loss"]
    if not losses or not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss in history: {history.history}")
    step = int(trainer.state.step)
    if step != steps:
        raise AssertionError(f"asked {steps} steps, counter says {step}")
    counts = trainer.compile_counts()
    if counts != {"train_step": 1, "eval_step": 1}:
        raise AssertionError(f"one executable per program, got {counts}")
    return {"model": cfg.model, "image_size": cfg.image_size,
            "batch": cfg.per_replica_batch, "dtype": cfg.compute_dtype,
            "steps": step, "loss": history.history["loss"][-1],
            "val_loss": history.history["val_loss"][-1],
            "compile_counts": counts}


# ------------------------------------------------------------------ serve
def _serve_wave(engine, model, variables, prompts, new_tokens: int,
                width: int, gap_tol: float) -> dict:
    """Warm ``engine``, serve ``prompts`` greedily to completion, and hold
    every stream to the checks the module docstring lists. Sequences are
    right-padded to ``width`` for the teacher-forced check, so its forward
    compiles once (causal: the pad changes nothing before it)."""
    from pddl_tpu.models.gpt import generate, greedy_gap
    from pddl_tpu.serve import FinishReason

    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    handles = [engine.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    engine.run()
    run_s = time.perf_counter() - t0
    for h in handles:
        if h.finish_reason != FinishReason.LENGTH \
                or len(h.tokens) != new_tokens:
            raise AssertionError(f"stream did not run to length: {h!r} "
                                 f"finished {h.finish_reason}")
    counts = engine.compile_counts()
    if set(counts.values()) != {1}:
        raise AssertionError(f"recompile after warmup: {counts}")

    def gap(prompt, continuation):
        seq = np.zeros((1, width), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + new_tokens] = continuation
        return float(greedy_gap(model, variables, seq,
                                len(prompt))[0, :new_tokens].max())

    worst_stream = worst_oneshot = 0.0
    exact = 0
    for prompt, h in zip(prompts, handles):
        oneshot = np.asarray(generate(
            model, variables, jnp.asarray(prompt, jnp.int32)[None],
            new_tokens))[0, len(prompt):]
        exact += int(np.array_equal(oneshot, h.tokens))
        worst_stream = max(worst_stream, gap(prompt, h.tokens))
        worst_oneshot = max(worst_oneshot, gap(prompt, oneshot))
    # Both are greedy decodes of one model iff each token of each is an
    # argmax-or-tie of the model's own conditional; bit-equality between
    # two compiled programs is not the bar on untrained bf16 weights.
    if max(worst_stream, worst_oneshot) >= gap_tol:
        raise AssertionError(
            f"not a greedy decode: gap engine {worst_stream:.4f} / "
            f"generate() {worst_oneshot:.4f} >= {gap_tol}")
    return {"requests": len(prompts), "new_tokens": new_tokens,
            "prompt_lens": [len(p) for p in prompts],
            "warmup_s": round(warm_s, 2), "run_s": round(run_s, 2),
            "streams_equal_generate": exact,
            "gap_engine": round(worst_stream, 4),
            "gap_generate": round(worst_oneshot, 4),
            "compile_counts": counts}


def serve_phase(model=None,
                prompt_lens=(32, 64, 128, 256, 512, 200, 64, 256),
                new_tokens: int = 32, gap_tol: float = GREEDY_GAP_TOL,
                seed: int = 0) -> dict:
    from pddl_tpu.models.gpt import GPT_Small
    from pddl_tpu.serve import ServeEngine

    if model is None:
        model = GPT_Small(vocab_size=50257, max_len=1024,
                          dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    variables = {"params": jax.jit(
        lambda r: model.init(r, jnp.ones((1, 8), jnp.int32),
                             train=False)["params"])(jax.random.key(seed))}
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model.vocab_size, size=n).astype(np.int32)
               for n in prompt_lens]

    engine = ServeEngine(model, variables)
    # The platform decides, not an option: on a TPU the tick must carry
    # the Mosaic kernel (an interpreted or jnp tick cannot pass); off it
    # (the CPU rehearsal) the jnp oracle serves and no kernel can be there.
    has_kernel = "tpu_custom_call" in engine.tick_lowering().as_text()
    if has_kernel != (jax.devices()[0].platform == "tpu"):
        raise AssertionError(
            f"paged tick on {jax.devices()[0].platform}: Mosaic kernel "
            f"present={has_kernel}")
    width = max(prompt_lens) + new_tokens
    return {"paged": _serve_wave(engine, model, variables, prompts,
                                 new_tokens, width, gap_tol),
            "tick_has_mosaic_kernel": has_kernel}


# ---------------------------------------------------------------- kernels
def _max_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def kernels_phase(head_shapes=HEAD_SHAPES, seq: int = 1024,
                  block_sizes=(8, 16), context: int = 1024,
                  cell_shapes=CELL_SHAPES) -> dict:
    """Flash fwd + fused bwd and the paged decode kernel vs their jnp
    oracles, the paged kernel also at the benchmark cells' own shapes
    (``cell_shapes``, block 16). Compiled (``interpret=False``) on a
    TPU; interpreted only where there is no TPU to compile for."""
    from pddl_tpu.ops.attention import (
        attention_reference,
        flash_attention,
        paged_decode_attention,
        paged_decode_attention_kernel,
    )

    interpret = jax.devices()[0].platform != "tpu"
    worst = {"flash_fwd": 0.0, "flash_bwd": 0.0, "paged": 0.0}

    def paged_err(q1, pool, slots, t, index, window=None, seed=0):
        """Kernel against the jnp path over a scattered table; entries
        past a row's depth are scratch, as the engine leaves them."""
        bs = pool.shape[2]
        table = np.random.RandomState(seed).permutation(
            np.arange(1, slots * t + 1)).reshape(slots, t).astype(np.int32)
        table[np.arange(t) > np.asarray(index)[:, None] // bs] = 0
        got = jax.jit(lambda *a: paged_decode_attention_kernel(
            *a, window=window, interpret=interpret))(q1, pool, table, index)
        want = jax.jit(lambda *a: paged_decode_attention(
            *a, window=window, kernel=False))(q1, pool, table, index)
        return _max_err(got, want)

    for h, hkv, d in head_shapes:
        ks = jax.random.split(jax.random.key(h * hkv), 5)
        q = jax.random.normal(ks[0], (2, h, seq, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (2, hkv, seq, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (2, hkv, seq, d), jnp.bfloat16)
        cot = jax.random.normal(ks[3], q.shape, jnp.float32)

        def grads_and_output(attend):
            def loss(q, k, v):
                o = attend(q, k, v, causal=True)
                return jnp.sum(o.astype(jnp.float32) * cot), o

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2),
                                    has_aux=True))(q, k, v)

        g_flash, o_flash = grads_and_output(functools.partial(
            flash_attention, interpret=interpret))
        g_ref, o_ref = grads_and_output(attention_reference)
        worst["flash_fwd"] = max(worst["flash_fwd"], _max_err(o_flash, o_ref))
        for a, b in zip(g_flash, g_ref):
            worst["flash_bwd"] = max(worst["flash_bwd"], _max_err(a, b))

        for bs in block_sizes:
            slots, t = 8, context // bs
            n = slots * t + 1  # block 0 is the scratch sink
            pool = jax.random.normal(ks[4], (n, hkv, bs, 2 * d), jnp.bfloat16)
            # Depths from empty to the last position, unaligned included.
            index = jnp.asarray(np.linspace(0, context - 1, slots), jnp.int32)
            q1 = q[:1, :, :slots].transpose(2, 1, 0, 3)  # [slots, h, 1, d]
            worst["paged"] = max(worst["paged"], paged_err(
                q1, pool, slots, t, index, seed=bs))
    for slots, h, hkv, d, ctx, window, lo, hi in cell_shapes:
        bs, t = 16, ctx // 16
        ks = jax.random.split(jax.random.key(slots * h), 2)
        q1 = jax.random.normal(ks[0], (slots, h, 1, d), jnp.bfloat16)
        pool = jax.random.normal(ks[1], (slots * t + 1, hkv, bs, 2 * d),
                                 jnp.bfloat16)
        index = jnp.asarray(np.random.RandomState(slots).permutation(
            np.linspace(lo, hi, slots)), jnp.int32)
        worst["paged"] = max(worst["paged"], paged_err(
            q1, pool, slots, t, index, window=window, seed=h))
    # bf16 in and out, f32 accumulation inside: the bounds
    # tests_tpu/test_on_chip_numerics.py holds the same kernels to.
    bounds = {"flash_fwd": 2e-2, "flash_bwd": 5e-2, "paged": 2e-2}
    for name, err in worst.items():
        if not err <= bounds[name]:
            raise AssertionError(f"{name}: max abs error {err} > "
                                 f"{bounds[name]} vs the jnp oracle")
    return {"interpret": interpret, "head_shapes": list(head_shapes),
            "cell_shapes": [list(c) for c in cell_shapes],
            "max_abs_err": {k: round(v, 5) for k, v in worst.items()}}


# ------------------------------------------------------------ across chips
def _resnet50():
    from pddl_tpu.models.resnet import ResNet50

    return ResNet50(num_classes=1000, dtype=jnp.float32)


def _placement(trainer, strategy, batch, n: int, platform: str) -> dict:
    """Where the mesh, the parameters and a batch really are: ``n``
    distinct devices of ``platform``, nothing silently on device 0."""
    mesh_devices = list(strategy.mesh.devices.flat)
    if len(set(mesh_devices)) != n \
            or {d.platform for d in mesh_devices} != {platform}:
        raise AssertionError(f"mesh is not {n} distinct {platform} "
                             f"devices: {mesh_devices}")
    sharded_bytes = total_bytes = 0
    for leaf in jax.tree.leaves(trainer.state.params):
        if {s.device for s in leaf.addressable_shards} != set(mesh_devices):
            raise AssertionError(f"a parameter lives on "
                                 f"{leaf.sharding.device_set}, not the mesh")
        total_bytes += leaf.nbytes
        if not leaf.is_fully_replicated:
            sharded_bytes += leaf.nbytes
    for leaf in jax.tree.leaves(strategy.distribute_batch(batch)):
        shards = leaf.addressable_shards
        if {s.device for s in shards} != set(mesh_devices) \
                or {s.data.shape[0] for s in shards} != {leaf.shape[0] // n}:
            raise AssertionError(
                f"batch not split {n} ways: "
                f"{[(s.device, s.data.shape) for s in shards]}")
    return {"param_bytes": total_bytes, "param_bytes_sharded": sharded_bytes}


def data_parallel_phase(devices=None, model_fn=_resnet50,
                        image_size: int = 224, global_batch: int = 128,
                        num_classes: int = 1000, steps: int = 3,
                        min_shard_bytes: int = 256 << 10) -> dict:
    """SGD steps under ``MirroredStrategy`` and ``ParameterServerStrategy``
    over four devices vs the same seed and batch on one, on loss, global
    gradient norm and post-step parameters — ``__graft_entry__``'s
    equivalence check, at ResNet-50 scale on real chips. ``devices=None``
    is the real thing: each strategy picks its own devices, as users'
    do. SGD, not Adam: Adam's update cancels a constant gradient factor,
    so a mis-averaged gradient would be invisible under it.

    float32 at ``highest`` matmul precision, not the trainer's bf16: the
    question is whether four chips compute what one does, and bf16
    blurs the answer. Sharded and unsharded sums differ in their last
    f32 bit; wherever a value is then rounded to bf16 (every activation;
    at default precision every conv input too) some of those bits flip
    a whole bf16 step. The bounds (5e-4 on loss, 5% on gradient norm,
    0.02 on parameters) have to let a gross fault (a gradient summed
    where it should be averaged: 4x) and a subtle one (one BatchNorm on
    per-shard statistics: ~1e-3 on loss) both stand out."""
    from __graft_entry__ import _assert_matches_oracle
    from pddl_tpu.core.mesh import MeshConfig, build_mesh
    from pddl_tpu.data.synthetic import SyntheticImageClassification
    from pddl_tpu.parallel.mirrored import MirroredStrategy
    from pddl_tpu.parallel.ps import ParameterServerStrategy
    from pddl_tpu.parallel.single import SingleDeviceStrategy
    from pddl_tpu.train.loop import Trainer

    n = 4
    mirrored = MirroredStrategy(devices=devices)
    ps = ParameterServerStrategy(min_shard_bytes=min_shard_bytes)
    if devices is not None:
        ps._mesh = build_mesh(MeshConfig(data=n), devices=list(devices))
    first = (devices or jax.local_devices())[0]

    def data():
        return SyntheticImageClassification(
            batch_size=global_batch, image_size=image_size,
            num_classes=num_classes, seed=0)

    def run(strategy):
        """(trainer, step-1 observables) after ``steps`` SGD steps. Only
        the FIRST step is held to the oracle: loss, gradient norm and
        one-step parameters are well-conditioned, while later steps
        amplify last-bit differences chaotically (an untrained ResNet-50
        at this learning rate, float32 on CPU: step-1 losses agree to
        3e-6, step-2 losses differ by 0.03). The rest prove the sharded
        program keeps stepping: counter, finite loss, one executable."""
        trainer = Trainer(model_fn(), optimizer="sgd", learning_rate=0.005,
                          strategy=strategy, seed=0, log_grad_norm=True)
        stream = data()
        first = trainer.fit(stream, epochs=1, steps_per_epoch=1, verbose=0)
        observed = (float(first.history["loss"][-1]),
                    float(first.history["grad_norm"][-1]),
                    jax.device_get(trainer.state.params))
        rest = trainer.fit(stream, epochs=1, steps_per_epoch=steps - 1,
                           verbose=0)
        if int(trainer.state.step) != steps \
                or not np.isfinite(rest.history["loss"][-1]) \
                or trainer.compile_counts() != {"train_step": 1}:
            raise AssertionError(
                f"{strategy}: step {trainer.state.step}, loss "
                f"{rest.history['loss']}, {trainer.compile_counts()}")
        return trainer, observed

    batch = next(iter(data()))
    with jax.default_matmul_precision("highest"):
        _, oracle = run(SingleDeviceStrategy(device=first))
        runs = {"mirrored": (mirrored, *run(mirrored)),
                "ps": (ps, *run(ps))}
    facts = {"global_batch": global_batch, "image_size": image_size,
             "steps": steps, "loss_one_device": oracle[0]}
    for name, (strategy, trainer, observed) in runs.items():
        _assert_matches_oracle(f"{name}_x{n}", observed, oracle)
        placed = _placement(trainer, strategy, batch, n, first.platform)
        if (placed["param_bytes_sharded"] > 0) != (name == "ps"):
            raise AssertionError(f"{name}: {placed} — mirrored state is "
                                 f"replicated, ps state is sharded")
        facts[name] = {"loss": observed[0], "grad_norm": observed[1],
                       **placed}
    return facts


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run ONLY the data-parallel path on a four-chip mesh and "
             "its one-device oracle")
    args = parser.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)

    from pddl_tpu.utils.compile_cache import enable_persistent_compile_cache

    meter = CompileMeter()
    print(json.dumps({"compile_cache_dir": enable_persistent_compile_cache(),
                      "jax": jax.__version__,
                      "devices": [str(d) for d in jax.devices()]}),
          flush=True)
    if args.four_chips:
        meter.run("data_parallel_x4", data_parallel_phase)
    else:
        meter.run("train", train_phase)
        meter.run("serve", serve_phase)
        meter.run("kernels", kernels_phase)
    print(json.dumps({"total": meter.totals, **memory_report(devices)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
