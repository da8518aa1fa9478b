"""Speculative decoding throughput on one chip (single-stream serving).

ARCHITECTURE.md §7e attributed single-stream decode to a fixed per-tick
serial-latency cost (~0.29 ms on v5e, round 5, an earlier stack) and named
multi-token decoding as the remaining lever. This bench measures that
lever end to end: :func:`pddl_tpu.models.speculative.generate_speculative`
(prompt-lookup drafting, exact greedy output) against plain
:func:`~pddl_tpu.models.gpt.generate` on the SAME trained model and
prompts.

Honesty requirements baked in:

- The model is TRAINED (briefly, on the byte-level CPython corpus the
  convergence tracks use) — acceptance rate on random weights is
  meaningless because drafts are verified against the model's own argmax.
- Both the favorable case (real Python source prompts — repetitive, the
  draft's home turf) and the adversarial case (uniform-random token
  prompts, where lookup never helps and every tick still pays a
  draft_len+1-wide verify) are reported. The worst case bounds the
  regression a serving stack could ever see from leaving speculation on.
- Outputs are asserted EQUAL to plain greedy before any timing counts.

    PYTHONPATH=. python benchmarks/specdecode_bench.py \
        [--train-steps 600] [--out artifacts/gpt_bench/r05_specdecode.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from pddl_tpu.models.gpt import generate
from pddl_tpu.models.llama import Llama_Small
from pddl_tpu.models.speculative import generate_speculative
from pddl_tpu.utils.bench_artifact import provenance, timed_stats


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _train_on_pycorpus(model, steps: int, seq_len: int, batch: int,
                       work_dir: str, param_update: str = "plain"):
    """Brief byte-level LM training; returns (params, val_tokens)."""
    from examples.real_data_convergence import (_build_atomically,
                                                build_python_corpus)
    from pddl_tpu.data.text import load_token_corpus
    from pddl_tpu.parallel.single import SingleDeviceStrategy
    from pddl_tpu.train.loop import Trainer

    data_dir = os.path.join(work_dir, "pycorpus")
    _build_atomically(data_dir, build_python_corpus)
    train_ds, val_ds = load_token_corpus(
        data_dir, seq_len=seq_len, train_batch_size=batch,
        val_batch_size=batch)
    tr = Trainer(model, optimizer="adamw", learning_rate=3e-4,
                 strategy=SingleDeviceStrategy(), seed=0,
                 param_update=param_update,
                 input_key="tokens", target_key="targets")
    t0 = time.time()
    hist = tr.fit(train_ds, epochs=1, steps_per_epoch=steps, verbose=0)
    _log(f"trained {steps} steps in {time.time() - t0:.0f}s, "
         f"final loss {hist.history['loss'][-1]:.3f}")
    # Keep params ON DEVICE: host arrays would be copied to the device
    # on every timed call and measure the copy, not the chip.
    params = tr.state.params
    val_tokens = val_ds._tokens  # flat byte-token array (held-out split)
    return params, val_tokens, float(hist.history["loss"][-1])


def _bench_pair(model, variables, prompt, new_tokens: int,
                draft_len: int, ngram: int, temperature: float = 0.0,
                top_k=None, n_repeats: int = 3):
    """(plain tok/s, spec tok/s, stats, spreads) on one prompt batch —
    timing is median-over-``n_repeats`` with spread recorded
    (`pddl_tpu/utils/bench_artifact.py` discipline).

    Greedy: asserts speculative output == greedy output before timing.
    Sampling (temperature > 0): outputs are draws, not unique strings —
    the check becomes the SUPPORT invariant instead: every emitted
    token must have nonzero probability under the model's own
    recomputed FILTERED conditional. The filter must be sharp for the
    check to discriminate anything (with temperature alone the whole
    vocab is in support and the assertion is vacuous), which is why the
    sampled bench runs with ``top_k`` on — generation and verification
    share the same filter, so a token outside the recomputed top-k set
    is a real exactness violation."""
    import jax

    sample_kw = ({} if temperature <= 0
                 else {"temperature": temperature, "top_k": top_k,
                       "rng": jax.random.key(0)})
    out, stats = generate_speculative(
        model, variables, prompt, new_tokens, draft_len=draft_len,
        ngram=ngram, return_stats=True, **sample_kw)
    if temperature <= 0:
        ref = generate(model, variables, prompt, max_new_tokens=new_tokens)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    else:
        from pddl_tpu.models.gpt import filtered_logits

        logits = model.apply(variables, out[:, :-1], train=False)
        flog = filtered_logits(logits, temperature=temperature, top_k=top_k)
        sel = np.take_along_axis(
            np.asarray(flog), np.asarray(out)[:, 1:, None], axis=-1)[..., 0]
        p = prompt.shape[1]
        assert np.all(np.isfinite(sel[:, p - 1:])), "token outside support"

    b = prompt.shape[0]
    sync = lambda x: int((x[0] if isinstance(x, tuple) else x)[0, -1])
    s_plain = timed_stats(
        lambda: generate(model, variables, prompt, max_new_tokens=new_tokens,
                         **sample_kw),
        sync, n_repeats=n_repeats)
    s_spec = timed_stats(
        lambda: generate_speculative(model, variables, prompt, new_tokens,
                                     draft_len=draft_len, ngram=ngram,
                                     **sample_kw),
        sync, n_repeats=n_repeats)
    spreads = {"plain": s_plain["spread_pct"], "spec": s_spec["spread_pct"]}
    return (b * new_tokens / s_plain["median_s"],
            b * new_tokens / s_spec["median_s"], stats, spreads)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--train-steps", type=int, default=600)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--train-batch", type=int, default=16)
    p.add_argument("--prompt-len", type=int, default=256)
    p.add_argument("--new-tokens", type=int, default=256)
    p.add_argument("--draft-len", type=int, default=7)
    p.add_argument("--ngram", type=int, default=3)
    p.add_argument("--int8", action="store_true",
                   help="also evaluate weight-only int8 serving: "
                        "val-loss delta of the quantized model on "
                        "held-out text, and int8 x speculative "
                        "throughput (exactness asserted against the "
                        "quantized model's own greedy decode)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="> 0: measure SAMPLED speculation (rejection "
                        "verifier; acceptance is probabilistic, so the "
                        "speedup is the honest serving number for "
                        "temperature sampling, lower than greedy's)")
    p.add_argument("--top-k", type=int, default=8,
                   help="sampled mode only: top-k filter applied to BOTH "
                        "generation and the support-invariant "
                        "verification pass. Must be sharp (small) for "
                        "the invariant to be discriminative — with "
                        "temperature alone every token is in support "
                        "and the check is vacuous. 0 disables (and "
                        "downgrades the exactness claim accordingly)")
    p.add_argument("--batches", default="1",
                   help="comma-joined batch sizes, e.g. 1,4,8. B>1 "
                        "quantifies the min-over-batch acceptance cost "
                        "(the KV caches share one scalar index, so each "
                        "tick emits the batch's WORST row's acceptance "
                        "— see speculative.py; the suite pins the "
                        "behavior in tests/test_speculative.py)")
    p.add_argument("--repeats", type=int, default=3,
                   help="timed repetitions per series (>= 3; median is "
                        "the headline, spread the drift detector)")
    p.add_argument("--family", default="llama_small",
                   choices=("llama_small", "llama_1b"),
                   help="llama_1b: the 1B-on-one-chip serving story -- "
                        "trained with the safe bf16 recipe (stochastic "
                        "rounding), where int8 x speculation matters "
                        "most (the 1B is weight-read-bound)")
    p.add_argument("--work-dir", default="/tmp/pddl_specdecode")
    p.add_argument("--out", default="")
    args = p.parse_args()

    # Serving configuration: bf16 storage + compute, same as decode_bench.
    if args.family == "llama_1b":
        from pddl_tpu.models.llama import Llama_1B

        model = Llama_1B(vocab_size=256, max_len=1024,
                         dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        model_desc = "llama_1b (16x2048, GQA 32/8, vocab 256)"
        # bf16 params on one chip -> the measured-safe update rule
        # (docs/CONVERGENCE.md): stochastic rounding, bf16 moments.
        param_update = "stochastic_round"
        args.train_batch = min(args.train_batch, 8)
    else:
        model = Llama_Small(vocab_size=256, max_len=1024,
                            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        model_desc = "llama_small (12x768, GQA 12/4, vocab 256)"
        param_update = "plain"
    params, val_tokens, final_loss = _train_on_pycorpus(
        model, args.train_steps, args.seq_len, args.train_batch,
        args.work_dir, param_update)
    variables = {"params": params}

    # Real-text prompts: held-out Python source windows at spread-out
    # offsets (B>1 rows are DISTINCT windows — realistic mixed traffic,
    # each row drafting off its own self-similarity). Random prompts:
    # uniform bytes — the lookup's adversarial case.
    batches = [int(b) for b in args.batches.split(",")]

    def text_prompt(b):
        starts = [len(val_tokens) // 3 + i * (args.prompt_len + 37)
                  for i in range(b)]
        return jnp.stack([jnp.asarray(
            val_tokens[s:s + args.prompt_len], jnp.int32)
            for s in starts])

    def rand_prompt(b):
        return jax.random.randint(
            jax.random.key(7), (b, args.prompt_len), 0, 256,
            dtype=jnp.int32)

    record = {
        "metric": "speculative_decode_new_tokens_per_sec",
        "unit": "tokens/sec/chip",
        "config": {
            "model": model_desc,
            "param_update": param_update,
            "trained_steps": args.train_steps,
            "final_train_loss_nats": round(final_loss, 4),
            "prompt_len": args.prompt_len, "new_tokens": args.new_tokens,
            "draft_len": args.draft_len, "ngram": args.ngram,
            "dtype": "bfloat16", "batch": 1,
            "temperature": args.temperature,
            "top_k": (args.top_k or None) if args.temperature > 0 else None,
            "exactness": (
                "speculative output asserted equal to greedy generate() "
                "before every timed series" if args.temperature <= 0 else
                f"sampling mode: support invariant asserted against the "
                f"model's recomputed top_k={args.top_k} filtered "
                "conditional (generation and verification share the "
                "sharp filter, so an out-of-support token is a real "
                "violation)" if args.top_k else
                "sampling mode: support check run WITHOUT a sharp "
                "filter (top_k=0) — vacuous at these settings, speed "
                "numbers only"),
        },
        "provenance": provenance(args.repeats),
        "results": {},
        "device": jax.devices()[0].device_kind,
    }
    record["config"]["batches"] = batches
    for b in batches:
        for kind, prompt in (("pycorpus", text_prompt(b)),
                             ("random", rand_prompt(b))):
            plain, spec, stats, spreads = _bench_pair(
                model, variables, prompt, args.new_tokens,
                args.draft_len, args.ngram, args.temperature,
                top_k=(args.top_k or None) if args.temperature > 0
                else None, n_repeats=args.repeats)
            # B1 keeps the legacy key names so artifact consumers (and
            # round-over-round diffs) stay comparable.
            suffix = f"b{b}" if b > 1 else "b1"
            key = (f"{kind}_speedup" if b == 1
                   else f"{kind}_speedup_{suffix}")
            record["results"][f"{kind}_plain_{suffix}"] = round(plain, 1)
            record["results"][f"{kind}_speculative_{suffix}"] = round(
                spec, 1)
            record["results"][key] = round(spec / plain, 3)
            record["results"][f"{kind}_tokens_per_tick"
                              + ("" if b == 1 else f"_{suffix}")] = round(
                stats["tokens_per_tick"], 3)
            record["results"][f"{kind}_{suffix}_spread_pct"] = round(
                max(spreads.values()), 2)
            _log(f"{kind} B{b}: plain {plain:,.0f} tok/s, speculative "
                 f"{spec:,.0f} tok/s ({spec / plain:.2f}x, "
                 f"{stats['tokens_per_tick']:.2f} tokens/tick, spread "
                 f"{max(spreads.values()):.1f}%)")

    if args.int8:
        from pddl_tpu.ops.quant import (dequantize, quantize_int8,
                                        quantized_bytes)

        qparams = quantize_int8(params)

        # Quality: mean CE (nats/byte) over held-out windows, quantized
        # weights vs the bf16 originals — the number a serving owner
        # trades against the bytes.
        n_eval, ebatch = 16, 8
        win = args.seq_len + 1
        starts = np.linspace(0, len(val_tokens) - win, n_eval * ebatch,
                             dtype=np.int64)
        chunks = np.stack([np.asarray(val_tokens[s:s + win])
                           for s in starts]).astype(np.int32)

        @jax.jit
        def ce(p, tokens, targets):
            logits = model.apply({"params": p}, tokens, train=False)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(
                logp, targets[..., None], axis=-1))

        def eval_loss(p):
            losses = [float(ce(p, jnp.asarray(c[:, :-1]),
                               jnp.asarray(c[:, 1:])))
                      for c in np.split(chunks, n_eval)]
            return sum(losses) / len(losses)

        loss_bf16 = eval_loss(params)
        loss_int8 = eval_loss(dequantize(qparams))
        stored = quantized_bytes(qparams)
        dense = quantized_bytes(params)

        # Throughput: int8 x speculative, exact vs the QUANTIZED model's
        # own greedy decode (int8 changes the weights, so the oracle is
        # int8 plain generate, not the bf16 series above).
        qvars = {"params": qparams}
        prompt8 = text_prompt(1)
        ref8 = generate(model, qvars, prompt8,
                        max_new_tokens=args.new_tokens,
                        param_transform=dequantize)
        out8, stats8 = generate_speculative(
            model, qvars, prompt8, args.new_tokens,
            draft_len=args.draft_len, ngram=args.ngram,
            return_stats=True, param_transform=dequantize)
        np.testing.assert_array_equal(np.asarray(out8), np.asarray(ref8))
        sync = lambda x: int((x[0] if isinstance(x, tuple) else x)[0, -1])
        t_plain8 = timed_stats(
            lambda: generate(model, qvars, prompt8,
                             max_new_tokens=args.new_tokens,
                             param_transform=dequantize), sync,
            n_repeats=args.repeats)["median_s"]
        t_spec8 = timed_stats(
            lambda: generate_speculative(
                model, qvars, prompt8, args.new_tokens,
                draft_len=args.draft_len, ngram=args.ngram,
                param_transform=dequantize), sync,
            n_repeats=args.repeats)["median_s"]
        record["results"]["int8_val_loss_nats"] = round(loss_int8, 5)
        record["results"]["bf16_val_loss_nats"] = round(loss_bf16, 5)
        record["results"]["int8_val_loss_delta_pct"] = round(
            100.0 * (loss_int8 - loss_bf16) / loss_bf16, 3)
        record["results"]["int8_stored_mb"] = round(stored["bytes"] / 2**20, 1)
        record["results"]["bf16_stored_mb"] = round(dense["bytes"] / 2**20, 1)
        record["results"]["int8_pycorpus_plain_b1"] = round(
            args.new_tokens / t_plain8, 1)
        record["results"]["int8_pycorpus_speculative_b1"] = round(
            args.new_tokens / t_spec8, 1)
        record["results"]["int8_pycorpus_tokens_per_tick"] = round(
            stats8["tokens_per_tick"], 3)
        _log(f"int8: val loss {loss_int8:.5f} vs bf16 {loss_bf16:.5f} "
             f"({record['results']['int8_val_loss_delta_pct']:+.2f}%), "
             f"{stored['bytes'] / 2**20:.0f} MB vs "
             f"{dense['bytes'] / 2**20:.0f} MB; plain "
             f"{args.new_tokens / t_plain8:,.0f} tok/s, speculative "
             f"{args.new_tokens / t_spec8:,.0f} tok/s")

    line = json.dumps(record)
    print(line)
    if args.out:
        out_dir = os.path.dirname(args.out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
