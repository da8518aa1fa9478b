"""Autoregressive decode throughput on one chip (generation serving path).

Measures :func:`pddl_tpu.models.gpt.generate` — batched prefill + the
ENTIRE decode as one on-device ``lax.scan`` dispatch (sampling included)
— for the GPT and Llama families at small-model shapes. With the scan
on the device the number measures the model: a host-side token loop
would add one dispatch per token.

Reports new-tokens/sec (prompt excluded) for greedy decoding, single
stream (B1) and batched (B8). Representative v5e numbers are pinned in
``artifacts/gpt_bench/r03_decode.json``.

    PYTHONPATH=. python benchmarks/decode_bench.py [--out out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

from pddl_tpu.models.gpt import GPT_Small, generate
from pddl_tpu.models.llama import Llama_1B, Llama_Small
from pddl_tpu.utils.bench_artifact import provenance, timed_stats


# Peak HBM bandwidth per chip, GB/s — the denominator of the decode
# roofline (single-stream decode is weight+KV-read bound).
HBM_GBPS = {"TPU v5 lite": 819.0, "TPU v5e": 819.0}


def _roofline_tokens_per_sec(model, variables, prompt_len: int,
                             new_tokens: int) -> float | None:
    """Weight+KV bandwidth roofline for single-stream greedy decode.

    Every decoded token must read all MATMUL parameters once plus the
    live KV prefix (k and v, kv-head granularity, storage dtype) in each
    layer; the prefix is averaged over the decode. Input-embedding (and
    position) tables are excluded from the per-token weight read — decode
    GATHERS one row per token, it does not stream the table — with the
    gathered rows added back. Anything above the returned rate would
    exceed the chip's HBM bandwidth.
    """
    bw = HBM_GBPS.get(jax.devices()[0].device_kind)
    if bw is None:
        return None
    params = dict(variables["params"])
    gathered_rows = 0
    for name in ("token_embed", "embed", "pos_embed"):  # gather, not stream
        node = params.pop(name, None)
        if node is not None:
            leaves = jax.tree.leaves(node)
            gathered_rows += sum(  # one row per decoded token
                leaf.shape[-1] * leaf.dtype.itemsize for leaf in leaves)
    param_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(params))
    hkv = getattr(model, "num_kv_heads", None) or model.num_heads
    head_dim = model.embed_dim // model.num_heads
    avg_prefix = prompt_len + new_tokens / 2
    itemsize = jnp.dtype(model.dtype).itemsize
    kv_bytes = 2 * model.depth * hkv * head_dim * itemsize * avg_prefix
    return bw * 1e9 / (param_bytes + gathered_rows + kv_bytes)


def _bench_generate(model, variables, batch: int, prompt_len: int,
                    new_tokens: int, n_repeats: int = 3,
                    param_transform=None):
    """(median tokens/s, spread_pct) over ``n_repeats`` timed runs —
    the artifact-discipline shape (median headline + drift-detecting
    spread; `pddl_tpu/utils/bench_artifact.py`)."""
    prompt = jax.random.randint(jax.random.key(0), (batch, prompt_len),
                                0, model.vocab_size)
    kw = dict(max_new_tokens=new_tokens, param_transform=param_transform)
    out = generate(model, variables, prompt, **kw)
    int(out[0, -1])  # fetching the value waits for the device
    stats = timed_stats(
        lambda: generate(model, variables, prompt, **kw),
        lambda o: int(o[0, -1]), n_repeats=n_repeats)
    return batch * new_tokens / stats["median_s"], stats["spread_pct"]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--new-tokens", type=int, default=256)
    p.add_argument("--models", default="",
                   help="comma-joined subset of gpt_small,llama_small,"
                        "llama_1b (default: the two smalls)")
    p.add_argument("--int8", action="store_true",
                   help="also measure weight-only int8 storage "
                        "(ops/quant.py) — halves the B1 weight-read "
                        "floor IF XLA streams the int8 (the comparison "
                        "against the int8 roofline is the check)")
    p.add_argument("--repeats", type=int, default=3,
                   help="timed repetitions per series (>= 3; median is "
                        "the headline, spread the drift detector)")
    p.add_argument("--out", default="")
    args = p.parse_args()

    # param_dtype=bf16: the serving configuration — decode is weight-
    # bandwidth-bound, so f32 storage would halve throughput for nothing.
    all_models = {
        "gpt_small": lambda: GPT_Small(vocab_size=50257, max_len=1024,
                                       dtype=jnp.bfloat16,
                                       param_dtype=jnp.bfloat16),
        "llama_small": lambda: Llama_Small(vocab_size=32000, max_len=1024,
                                           dtype=jnp.bfloat16,
                                           param_dtype=jnp.bfloat16),
        # The 1B-on-one-chip headline's serving twin (2.2 GB of bf16
        # weights: B1 decode is purely weight-read-bound, the int8 case
        # that matters most).
        "llama_1b": lambda: Llama_1B(vocab_size=128256, max_len=1024,
                                     dtype=jnp.bfloat16,
                                     param_dtype=jnp.bfloat16),
    }
    names = args.models.split(",") if args.models else [
        "gpt_small", "llama_small"]
    unknown = set(names) - set(all_models)
    if unknown:
        raise SystemExit(f"unknown --models {sorted(unknown)}; "
                         f"choose from {sorted(all_models)}")
    models = {n: all_models[n]() for n in names}
    record = {
        "metric": "greedy_decode_new_tokens_per_sec",
        "unit": "tokens/sec/chip",
        "config": {"prompt_len": args.prompt_len,
                   "new_tokens": args.new_tokens, "dtype": "bfloat16"},
        "provenance": provenance(args.repeats),
        "results": {},
        "device": jax.devices()[0].device_kind,
    }
    for name, model in models.items():
        variables = jax.jit(model.init)(
            jax.random.key(0),
            jnp.zeros((1, args.prompt_len), jnp.int32), train=False)
        variables = {"params": variables["params"]}
        roof = _roofline_tokens_per_sec(model, variables,
                                        args.prompt_len, args.new_tokens)
        for batch in (1, 8):
            tps, spread = _bench_generate(model, variables, batch,
                                          args.prompt_len,
                                          args.new_tokens,
                                          n_repeats=args.repeats)
            record["results"][f"{name}_b{batch}"] = round(tps, 1)
            record["results"][f"{name}_b{batch}_spread_pct"] = round(
                spread, 2)
            if batch == 1 and roof is not None:
                record["results"][f"{name}_roofline_b1"] = round(roof, 1)
                record["results"][f"{name}_roofline_ratio_b1"] = round(
                    tps / roof, 3)
            print(f"{name} B{batch}: {tps:,.0f} new tokens/s "
                  f"(spread {spread:.1f}%)"
                  + (f" ({tps / roof:.0%} of {roof:,.0f} roofline)"
                     if batch == 1 and roof else ""),
                  file=sys.stderr, flush=True)
        if args.int8:
            from pddl_tpu.ops.quant import dequantize, quantize_int8

            qvars = {"params": quantize_int8(variables["params"])}
            # Same roofline formula over the STORED (int8) bytes: the
            # q-leaf dicts flatten to int8 + scale + dtype-carrier
            # leaves, so the weight-read numerator is what HBM actually
            # holds.
            roof8 = _roofline_tokens_per_sec(model, qvars,
                                             args.prompt_len,
                                             args.new_tokens)
            for batch in (1, 8):
                tps8, spread8 = _bench_generate(model, qvars, batch,
                                                args.prompt_len,
                                                args.new_tokens,
                                                n_repeats=args.repeats,
                                                param_transform=dequantize)
                record["results"][f"{name}_int8_b{batch}"] = round(tps8, 1)
                record["results"][f"{name}_int8_b{batch}_spread_pct"] = (
                    round(spread8, 2))
                if batch == 1 and roof8 is not None:
                    record["results"][f"{name}_int8_roofline_b1"] = round(
                        roof8, 1)
                    record["results"][f"{name}_int8_roofline_ratio_b1"] = (
                        round(tps8 / roof8, 3))
                print(f"{name} int8 B{batch}: {tps8:,.0f} new tokens/s "
                      f"(spread {spread8:.1f}%)"
                      + (f" ({tps8 / roof8:.0%} of {roof8:,.0f} int8 "
                         "roofline)" if batch == 1 and roof8 else ""),
                      file=sys.stderr, flush=True)

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
