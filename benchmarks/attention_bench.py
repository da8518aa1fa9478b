"""Flash-attention kernel sweep vs the fused-XLA reference (real chip).

This is the harness behind the tuned ``block_q=512, block_k=1024``
defaults in ``pddl_tpu/ops/attention.py``. Timing uses a scalar fetch as
the sync point: dispatch is asynchronous, and a loop timed without
waiting for a result measures the dispatch rate.

    python benchmarks/attention_bench.py [--seqs 2048,4096,8192]

Representative v5e numbers (B4 H16 D64 bf16, causal, forward):

    S=2048  fl128x128 17.5  fl512x512 13.8  fl512x1024 10.3   ref 15.0
    S=4096  fl128x128 39.2  fl512x512 16.8  fl512x1024 10.6   ref 28.6
    S=8192  fl128x128 125.1 fl512x512 33.9  fl512x1024 25.3   ref OOM

(ms/call; at S=8192 the reference's O(S²) scores exceed HBM.)
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from pddl_tpu.ops.attention import attention_reference, flash_attention

BLOCKS = ((128, 128), (256, 512), (512, 512), (512, 1024), (1024, 1024),
          (256, 1024), (1024, 512))


def bench(make_fn, *arrs, iters: int = 10) -> float:
    f = jax.jit(make_fn)
    float(f(*arrs))  # compile + genuine sync (scalar fetch)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*arrs)
    float(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", default="2048,4096,8192")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--backward", action="store_true",
                   help="time fwd+bwd instead of forward only")
    args = p.parse_args()

    B, H, D = args.batch, args.heads, args.head_dim
    for S in (int(s) for s in args.seqs.split(",")):
        q, k, v = (
            jax.random.normal(jax.random.key(i), (B, H, S, D), jnp.bfloat16)
            for i in range(3)
        )
        row = [f"S={S}"]

        def run(attn, **kw):
            if args.backward:
                return bench(lambda a, b, c: jax.grad(
                    lambda aa: attn(aa, b, c, causal=True, **kw)
                    .astype(jnp.float32).sum()
                )(a).astype(jnp.float32).sum(), q, k, v)
            return bench(lambda a, b, c: attn(a, b, c, causal=True, **kw)
                         .astype(jnp.float32).sum(), q, k, v)

        for bq, bk in BLOCKS:
            try:
                row.append(f"fl{bq}x{bk} {run(flash_attention, block_q=bq, block_k=bk):6.1f}")
            except Exception:
                row.append(f"fl{bq}x{bk}    ERR")
        try:
            row.append(f"ref {run(attention_reference):6.1f}")
        except Exception:
            row.append("ref OOM/ERR")
        print("  ".join(row), flush=True)


if __name__ == "__main__":
    main()
