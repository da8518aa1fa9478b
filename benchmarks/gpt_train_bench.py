"""End-to-end GPT training throughput on one chip (tokens/sec, MFU).

The harness behind the architecture doc's long-context numbers
(v5e, GPT-2-small shape, B8 S2048 bf16 flash + fused-CE head; round 4
with the fused single-sweep attention backward: ~101k tokens/s, 50.6%
6ND MFU against the 197 TFLOP/s bf16 peak — chip-state variance of a
few percent per run is normal; decomposition of the remainder:
docs/ARCHITECTURE.md §7b, artifacts/gpt_bench/r04_b8_s2048.json).

Long context on ONE chip (``--remat dots``, round 4): S=8192 at ~48k
tokens/s, S=16384 at ~30k tokens/s (B1) — where the
materialized-scores attention could not even hold a single layer's S²
matrix (``r04_b1_s8192.json``, ``r04_b1_s16384.json``).

``--family llama`` benches the modern-decoder family at the same shape
(RoPE/SwiGLU/RMSNorm, GQA ``--kv-heads``, llama-tokenizer 32000 vocab):
125M params at B8 S2048 bf16 train at ~112.7k tokens/s/chip with the
GQA-native kernels — 145.4 vs GPT's 161.8 ms/step, pinned as
``artifacts/gpt_bench/r04_llama_b8_s2048.json`` vs ``r04_b8_s2048.json``.

    PYTHONPATH=. python benchmarks/gpt_train_bench.py [--seq 2048 --batch 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import optax

from pddl_tpu.models.gpt import GPT, fused_lm_loss
from pddl_tpu.train.state import TrainState

V5E_BF16_PEAK_FLOPS = 197e12
V5E_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")


def _write_record(path: str, record: dict) -> None:
    """The one artifact-writing convention (both legs use it)."""
    if not path:
        return
    out_dir = os.path.dirname(path)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def _checkpoint_overhead_leg(args, state, jstep, tokens, targets) -> None:
    """Paired leg: the same N-step loop with verified step-granular
    checkpointing on vs off (`utils/bench_artifact.py` discipline:
    >=3 repeats, median + spread, provenance). The checkpointed leg
    pays what `CheckpointEveryN` pays in training: a host fetch of the
    state for per-leaf checksums at each save, the (async) Orbax write
    overlapping subsequent steps, and one wait at the end — against
    compute that keeps running between saves. Writes ONE JSON record
    (the `--out` artifact: `artifacts/gpt_bench/r10_train_faults.json`).
    """
    import shutil
    import tempfile

    from pddl_tpu.ckpt.checkpoint import Checkpointer
    from pddl_tpu.utils.bench_artifact import provenance, timed_stats

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="pddl_ckpt_bench_")
    holder = {"state": state}

    def run_clean():
        for _ in range(args.steps):
            holder["state"], loss = jstep(holder["state"], tokens, targets)
        return loss

    saves_per_repeat = args.steps // args.ckpt_every
    # ONE manager across repeats, warmed with a throwaway save: the
    # first Orbax save pays directory/manager setup that a long-running
    # training job amortizes to nothing — timing it would charge the
    # steady-state cadence for a one-time cost.
    ckpt = Checkpointer(ckpt_dir, max_to_keep=2, async_save=True)
    ckpt.save(holder["state"], force=True, checksum=True)
    ckpt.wait()

    def run_ckpt():
        for i in range(args.steps):
            holder["state"], loss = jstep(holder["state"], tokens,
                                          targets)
            if (i + 1) % args.ckpt_every == 0:
                ckpt.save(holder["state"], force=True, checksum=True)
        ckpt.wait()
        return loss

    sync = lambda loss: float(loss)  # noqa: E731 - scalar fetch = sync
    clean = timed_stats(run_clean, sync, n_repeats=args.repeats)
    ckpt_on = timed_stats(run_ckpt, sync, n_repeats=args.repeats)
    ckpt.close()
    if not args.ckpt_dir:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    B, S = args.batch, args.seq
    toks_clean = B * S * args.steps / clean["median_s"]
    toks_ckpt = B * S * args.steps / ckpt_on["median_s"]
    ratio = toks_ckpt / toks_clean
    n_params = sum(x.size for x in jax.tree.leaves(holder["state"].params))
    per_save_ms = ((ckpt_on["median_s"] - clean["median_s"])
                   / max(saves_per_repeat, 1) * 1e3)
    print(f"checkpoint-overhead ({n_params / 1e6:.0f}M params, "
          f"every {args.ckpt_every} of {args.steps} steps, "
          f"{args.repeats} repeats):", file=sys.stderr)
    print(f"  off: {toks_clean:,.0f} tok/s  on: {toks_ckpt:,.0f} tok/s "
          f"-> {ratio:.3f}x retained "
          f"(~{per_save_ms:.1f} ms amortized per verified save)",
          file=sys.stderr)
    record = {
        "metric": "train_checkpoint_throughput_retained",
        "value": round(ratio, 4),
        "unit": "ratio (checkpoint-every-N on / off, tokens/sec)",
        "clean_tokens_per_sec": round(toks_clean, 1),
        "checkpointed_tokens_per_sec": round(toks_ckpt, 1),
        "amortized_ms_per_save": round(per_save_ms, 2),
        "clean": clean,
        "checkpointed": ckpt_on,
        "config": {"family": args.family, "batch": B, "seq": S,
                   "depth": args.depth, "width": args.width,
                   "heads": args.heads, "vocab": args.vocab,
                   "params_m": round(n_params / 1e6, 1),
                   "attention": args.attention,
                   "steps": args.steps, "ckpt_every": args.ckpt_every,
                   "saves_per_repeat": saves_per_repeat,
                   "checksums": True, "async_save": True},
        "device": jax.devices()[0].device_kind,
        "provenance": provenance(args.repeats),
    }
    print(json.dumps(record))
    _write_record(args.out, record)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--family", default="gpt", choices=["gpt", "llama"],
                   help="gpt: learned-pos/GELU/LayerNorm GPT-2 shape; "
                        "llama: RoPE/SwiGLU/RMSNorm with GQA "
                        "(--kv-heads), llama-tokenizer vocab default")
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--depth", type=int, default=12)
    p.add_argument("--width", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--kv-heads", type=int, default=4,
                   help="llama family only: grouped-query KV heads")
    p.add_argument("--intermediate", type=int, default=None,
                   help="llama family only: SwiGLU hidden dim "
                        "(default: the ~8E/3 convention)")
    p.add_argument("--vocab", type=int, default=None,
                   help="default: 50257 (gpt) / 32000 (llama)")
    p.add_argument("--experts", type=int, default=0,
                   help="llama family only: >0 routes every block's MLP "
                        "over this many SwiGLU experts (Mixtral-style)")
    p.add_argument("--moe-top-k", type=int, default=2,
                   help="experts per token (with --experts)")
    p.add_argument("--moe-capacity", type=float, default=2.0,
                   help="train capacity factor (with --experts)")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--remat", default="none",
                   choices=["none", "dots", "full"],
                   help="activation checkpointing (long sequences: dots)")
    p.add_argument("--param-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="parameter storage dtype; bfloat16 halves "
                        "weight+optimizer HBM (how the 1B shape fits "
                        "one chip)")
    p.add_argument("--param-update", default="plain",
                   choices=["plain", "stochastic_round", "f32_master"],
                   help="bf16-storage update rule "
                        "(train/mixed_precision.py); the 1B headline "
                        "uses stochastic_round — same memory as plain, "
                        "f32-equivalent convergence (docs/CONVERGENCE.md)")
    p.add_argument("--chunk-size", type=int, default=None,
                   help="fused-CE vocab chunk (memory valve)")
    p.add_argument("--fused-ce", type=int, default=1,
                   help="1 (default): fused head+CE via fused_lm_loss; "
                        "0: materialized logits + sparse CE")
    p.add_argument("--attention", default="flash",
                   choices=["flash", "reference"],
                   help="training attention path (reference lets the "
                        "bench run on hosts whose jax lacks the Mosaic "
                        "kernel prerequisites, e.g. CPU CI)")
    p.add_argument("--checkpoint-overhead", action="store_true",
                   help="paired leg: the SAME step loop with verified "
                        "step-granular checkpointing (Checkpointer.save "
                        "with per-leaf checksums, CheckpointEveryN "
                        "cadence) on vs off, >=3 timed repeats each — "
                        "the cost of the crash-resilience layer "
                        "(docs/OPERATIONS.md 'Failure modes & recovery "
                        "(training)')")
    p.add_argument("--ckpt-every", type=int, default=5,
                   help="save cadence in steps for --checkpoint-overhead")
    p.add_argument("--repeats", type=int, default=5,
                   help="timed repeats per leg for --checkpoint-overhead")
    p.add_argument("--ckpt-dir", default="",
                   help="checkpoint directory for --checkpoint-overhead "
                        "(default: a temp dir)")
    p.add_argument("--out", default="",
                   help="also write the JSON record to this path")
    args = p.parse_args()

    if args.experts and args.family != "llama":
        # GPT's MoE knob exists but takes the module defaults (no
        # capacity/eval controls); benching it here would emit an
        # MoE-labeled record for a config the flags don't describe.
        p.error("--experts requires --family llama")
    kind = jax.devices()[0].device_kind
    if not args.checkpoint_overhead and kind not in V5E_DEVICE_KINDS:
        # The MFU below divides by the v5e peak: on any other device it
        # would be a wrong number under a device metric's name. (The
        # checkpoint-overhead leg reports a ratio of two host timings
        # and no MFU, so it runs anywhere.)
        sys.exit(f"gpt_train_bench: device_kind {kind!r} is not a v5e; "
                 f"the MFU denominator is the v5e bf16 peak")
    if args.vocab is None:
        args.vocab = 50257 if args.family == "gpt" else 32000
    param_dtype = jnp.bfloat16 if args.param_dtype == "bfloat16" \
        else jnp.float32
    if args.family == "gpt":
        model = GPT(vocab_size=args.vocab, max_len=args.seq,
                    embed_dim=args.width, depth=args.depth,
                    num_heads=args.heads, attention=args.attention,
                    remat=args.remat, dtype=jnp.bfloat16,
                    param_dtype=param_dtype)
    else:
        from pddl_tpu.models.llama import Llama

        model = Llama(vocab_size=args.vocab, max_len=args.seq,
                      embed_dim=args.width, depth=args.depth,
                      num_heads=args.heads, num_kv_heads=args.kv_heads,
                      intermediate_dim=args.intermediate,
                      attention=args.attention, remat=args.remat,
                      moe_experts=args.experts, moe_top_k=args.moe_top_k,
                      moe_capacity_factor=args.moe_capacity,
                      dtype=jnp.bfloat16, param_dtype=param_dtype)
    B, S = args.batch, args.seq
    tokens = jax.random.randint(jax.random.key(0), (B, S), 0, args.vocab)
    targets = jax.random.randint(jax.random.key(1), (B, S), 0, args.vocab)
    tx = optax.adamw(1e-4)
    if args.param_update != "plain":
        from pddl_tpu.train.mixed_precision import wrap_param_update

        tx = wrap_param_update(tx, args.param_update)

    def init(rng):
        params = model.init(rng, tokens[:1], train=False)["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          batch_stats={}, opt_state=tx.init(params))

    state = jax.jit(init)(jax.random.key(0))

    def step(state, tokens, targets):
        def loss_of(params):
            if args.fused_ce:
                # Fused head + CE (models/gpt.py fused_lm_loss): only
                # logsumexp rows cross the fwd/bwd boundary — head+CE
                # measured 33.7 vs 39.7 ms standalone, ~4.7 ms/step
                # end-to-end (the one-chunk default trades a transient
                # f32 logits chunk for speed; chunk_size < vocab is the
                # memory valve).
                return fused_lm_loss(model, {"params": params}, tokens,
                                     targets, train=True,
                                     chunk_size=args.chunk_size)
            logits = model.apply({"params": params}, tokens, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, targets).mean()

        loss, grads = jax.value_and_grad(loss_of)(state.params)
        return state.apply_gradients(tx, grads), loss

    jstep = jax.jit(step, donate_argnums=(0,))
    state, loss = jstep(state, tokens, targets)
    float(loss)  # fetching the value waits for the device
    if args.checkpoint_overhead:
        _checkpoint_overhead_leg(args, state, jstep, tokens, targets)
        return
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, loss = jstep(state, tokens, targets)
    float(loss)
    dt = (time.perf_counter() - t0) / args.steps

    toks = B * S / dt
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    # MoE: 6ND must count ACTIVE params per token — each token runs
    # top_k of the n experts, so expert weights contribute top_k/n of
    # their size (router + dense weights count fully). For dense models
    # n_active == n_params.
    expert_params = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            state.params)[0]
        if "moe" in jax.tree_util.keystr(path)
        and "router" not in jax.tree_util.keystr(path))
    n_active = n_params - expert_params
    if args.experts:
        n_active += expert_params * args.moe_top_k // args.experts
    mfu = 6 * n_active * toks / V5E_BF16_PEAK_FLOPS
    # Human-readable lines on stderr, ONE JSON line on stdout (the
    # bench.py contract: callers may json.loads captured stdout).
    print(f"{n_params / 1e6:.0f}M params, B{B} S{S} bf16 "
          f"{args.remat} remat, fused_ce={bool(args.fused_ce)}:",
          file=sys.stderr)
    print(f"  {dt * 1e3:.1f} ms/step = {toks:,.0f} tokens/sec/chip",
          file=sys.stderr)
    print(f"  ~{mfu * 100:.0f}% MFU (6ND / {V5E_BF16_PEAK_FLOPS / 1e12:.0f}"
          " TFLOP/s v5e bf16 peak)", file=sys.stderr)
    gb = n_params / 1e9
    rounded = max(1, round(gb))
    # Integer tag only when honest (within 15%); 600M is "0.6b", not "1b".
    size_tag = ("small" if n_params < 5e8
                else f"{rounded}b" if abs(gb - rounded) / rounded <= 0.15
                else f"{gb:.1f}b")
    family_tag = (f"{args.family}_moe{args.experts}top{args.moe_top_k}"
                  if args.experts else args.family)
    record = {
        "metric": f"{family_tag}_{size_tag}_train_tokens_per_sec_per_chip",
        "value": round(toks, 1),
        "unit": "tokens/sec/chip",
        "mfu_6nd": round(mfu, 4),
        "ms_per_step": round(dt * 1e3, 2),
        "config": {"family": args.family, "batch": B, "seq": S,
                   "depth": args.depth,
                   "width": args.width, "heads": args.heads,
                   "vocab": args.vocab, "params_m": round(n_params / 1e6, 1),
                   "remat": args.remat, "fused_ce": bool(args.fused_ce),
                   "attention": "flash", "dtype": "bfloat16",
                   "param_dtype": args.param_dtype,
                   "param_update": args.param_update,
                   "chunk_size": args.chunk_size if args.fused_ce else None,
                   "steps": args.steps},
        "device": jax.devices()[0].device_kind,
    }
    if args.experts:
        record["config"]["experts"] = args.experts
        record["config"]["moe_top_k"] = args.moe_top_k
        record["config"]["moe_capacity_factor"] = args.moe_capacity
        record["config"]["params_active_m"] = round(n_active / 1e6, 1)
    if args.family == "llama":
        record["config"]["kv_heads"] = args.kv_heads
        # Record the RESOLVED SwiGLU width (the model's ~8E/3 convention
        # when the flag is unset) so the artifact is self-describing.
        record["config"]["intermediate"] = (
            args.intermediate
            if args.intermediate is not None
            else -(-(8 * args.width // 3) // 128) * 128)
    print(json.dumps(record))
    _write_record(args.out, record)


if __name__ == "__main__":
    main()
