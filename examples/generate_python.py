"""Train a byte-level GPT on real Python source, then sample from it.

End-to-end demonstration of the LM stack on a REAL trained model (the
unit tests exercise ``generate()`` on tiny random models): build the
CPython-stdlib corpus (same recipe as ``real_data_convergence.py``),
train GPT-Small for a few thousand steps on the chip, then generate
continuations of Python-looking prompts with the KV-cache sampler
(temperature + nucleus). Samples are written next to the convergence
artifacts so the repo carries evidence the trained model writes
plausible Python.

Run on the TPU chip::

    python examples/generate_python.py

Smoke mode (``PDDL_EXAMPLE_SMOKE=1``, used by tests/test_examples.py):
tiny model, a handful of steps, samples land in the work dir.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from examples.real_data_convergence import (  # noqa: E402
    ARTIFACTS,
    _build_atomically,
    build_python_corpus,
)

SMOKE = bool(os.environ.get("PDDL_EXAMPLE_SMOKE"))

# Equal byte lengths on purpose: one BATCHED generate() call compiles the
# prefill + the on-device decode scan exactly once (per-call closures
# re-jit, so four separate calls would compile four times).
PROMPTS = (
    b"def get_",
    b"class My",
    b"import o",
    b"    for ",
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--work-dir", default="/tmp/pddl_tpu_real_data")
    p.add_argument("--steps", type=int, default=30 if SMOKE else 3000)
    p.add_argument("--max-new", type=int, default=16 if SMOKE else 256)
    p.add_argument("--speculative", action="store_true",
                   help="sample via speculative (prompt-lookup) "
                        "decoding -- same distribution, fewer ticks")
    p.add_argument("--out", default=None,
                   help="samples file (default: committed artifacts dir; "
                        "the work dir in smoke mode)")
    args = p.parse_args(argv)
    if args.out is None:
        args.out = os.path.join(
            args.work_dir if SMOKE else ARTIFACTS, "pycorpus_samples.txt")

    # The decode-scan program takes a while to compile; persist it so
    # reruns start at once.
    from pddl_tpu.utils.compile_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pddl_tpu.data.text import load_token_corpus
    from pddl_tpu.models.gpt import GPT, generate
    from pddl_tpu.train.loop import Trainer

    data_dir = os.path.join(args.work_dir, "pycorpus")
    _build_atomically(data_dir, build_python_corpus)

    seq_len = 64 if SMOKE else 256
    batch = 8 if SMOKE else 32
    train_ds, _ = load_token_corpus(
        data_dir, seq_len=seq_len, train_batch_size=batch,
        val_batch_size=batch, seed=0)

    model = GPT(
        vocab_size=256, max_len=max(seq_len, 512 if not SMOKE else seq_len),
        embed_dim=32 if SMOKE else 768, depth=2 if SMOKE else 12,
        num_heads=2 if SMOKE else 12,
        attention="reference" if SMOKE else "flash",
        dtype=jnp.bfloat16 if not SMOKE else jnp.float32,
    )
    trainer = Trainer(
        model, optimizer="adamw", learning_rate=3e-4,
        input_key="tokens", target_key="targets",
        lr_schedule="cosine",
        lr_schedule_options={"decay_steps": args.steps, "warmup_steps":
                             max(2, args.steps // 30)},
        metrics=["accuracy", "perplexity"],
    )
    t0 = time.time()
    epochs = max(1, args.steps // 300)
    spe = args.steps // epochs
    hist = trainer.fit(train_ds, epochs=epochs, steps_per_epoch=spe,
                       verbose=0)
    print(f"trained {epochs * spe} steps in {time.time() - t0:.0f}s, "
          f"final loss {hist.history['loss'][-1]:.3f} nats/byte",
          file=sys.stderr)

    variables = {"params": trainer.state.params}
    prompts = jnp.asarray(np.stack([
        np.frombuffer(p, np.uint8).astype(np.int32) for p in PROMPTS
    ]))
    t0 = time.time()
    if args.speculative:
        from pddl_tpu.models.speculative import generate_speculative

        out, stats = generate_speculative(
            model, variables, prompts, args.max_new,
            temperature=0.8, top_p=0.95, rng=jax.random.key(0),
            return_stats=True)
        print(f"speculative sampling: {stats['tokens_per_tick']:.2f} "
              f"tokens/tick over {stats['ticks']} ticks", file=sys.stderr)
    else:
        out = generate(model, variables, prompts, args.max_new,
                       temperature=0.8, top_p=0.95, rng=jax.random.key(0))
    out = np.asarray(out)
    gen_s = time.time() - t0
    n_tok = len(PROMPTS) * args.max_new
    print(f"generated {n_tok} tokens in {gen_s:.1f}s "
          f"(incl. compile; one dispatch for the whole decode)",
          file=sys.stderr)

    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(f"# GPT samples after {epochs * spe} steps on the CPython "
                f"corpus (temperature 0.8, top-p 0.95, seed 0; "
                f"{gen_s:.1f}s for {n_tok} tokens incl. compile)\n")
        for row in out:
            text = bytes(row.astype(np.uint8)).decode(
                "utf-8", errors="replace")
            f.write("\n" + "-" * 60 + "\n" + text + "\n")
            print("-" * 60 + "\n" + text, file=sys.stderr)
    print(f"samples -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
