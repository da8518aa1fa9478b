"""Chunked large-vocab CE vs the materialized-logits loss (real chip).

The harness behind the numbers in ``ops/large_vocab.py`` /
``docs/ARCHITECTURE.md`` — measures loss+grad wall-clock and XLA's peak
temp allocation for both paths on a GPT-2-small-shape model.

    PYTHONPATH=. python benchmarks/large_vocab_bench.py [--chunk 4096]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import optax

from pddl_tpu.models.gpt import GPT
from pddl_tpu.ops.large_vocab import chunked_cross_entropy


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--vocab", type=int, default=50257)
    p.add_argument("--chunk", type=int, default=4096)
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args()

    model = GPT(vocab_size=args.vocab, max_len=args.seq, embed_dim=768,
                depth=12, num_heads=12, attention="flash",
                dtype=jnp.bfloat16)
    B, S = args.batch, args.seq
    tokens = jax.random.randint(jax.random.key(0), (B, S), 0, args.vocab)
    targets = jax.random.randint(jax.random.key(1), (B, S), 0, args.vocab)
    params = jax.jit(
        lambda r: model.init(r, tokens[:1], train=False)["params"]
    )(jax.random.key(0))

    def loss_logits(params):
        logits = model.apply({"params": params}, tokens, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    def loss_chunked(params):
        _, state = model.apply(
            {"params": params}, tokens, train=True,
            capture_intermediates=lambda m, _: m.name == "ln_final",
        )
        feats = jax.tree.leaves(
            state["intermediates"]["ln_final"]["__call__"])[0]
        head = params["lm_head"]
        return chunked_cross_entropy(feats, head["kernel"], targets,
                                     head["bias"], chunk_size=args.chunk)

    for name, fn in (("logits ", loss_logits), ("chunked", loss_chunked)):
        g = jax.jit(jax.value_and_grad(fn))
        mem = g.lower(params).compile().memory_analysis()
        loss, _ = g(params)
        float(loss)  # fetching the value waits for the device
        t0 = time.perf_counter()
        for _ in range(args.steps):
            loss, grads = g(params)
        float(loss)
        dt = (time.perf_counter() - t0) / args.steps
        print(f"{name}: loss {float(loss):.3f}  {dt * 1e3:7.1f} ms/step  "
              f"peak temp alloc {mem.temp_size_in_bytes / 1e9:.2f} GB")


if __name__ == "__main__":
    main()
