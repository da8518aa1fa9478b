"""Decode-tick attribution: where every millisecond of serving lives.

Round 4 reported single-stream greedy decode at 26-32% of the
weight-read roofline (``artifacts/gpt_bench/r04_decode.json``) without
locating the other ~70%. Two findings from building this attribution:

1. **The r04 ratio conflated transport with chip time.** r04 divided
   tokens by the WHOLE ``generate()`` wall clock — prefill dispatch,
   host round trips, scalar fetch — not the decode scan. Measured
   program-level (prefill program timed separately and subtracted), the
   on-chip decode tick is several times faster than the r04 numbers
   implied.

2. **In-situ differences, not synthetic kernels.** A first attempt
   timed hand-built "matmul-only"/"attention-only" scan programs; their
   parts summed to MORE than the whole (a scalar-carry chain serializes
   what the real program overlaps). This harness instead times REAL
   decode programs that differ by exactly one component — the method
   that settled the training-step attribution (docs/ARCHITECTURE.md
   §7b) — so every line is a fusion-faithful marginal cost:

   - ``full``       — the real greedy decode scan (sampling included);
   - ``no_sample``  — same scan, next token replaced by a constant
                      (drops argmax + the sampled-token data path);
   - ``no_head``    — + ``features_only=True`` (drops final norm +
                      LM-head matmul);
   - ``no_attn``    — + ``decode_attention`` stubbed to identity (drops
                      the cache READ sweep; cache writes remain).

   marginal costs: sampling = full−no_sample, head = no_sample−no_head,
   attention read = no_head−no_attn, everything-else = no_attn (block
   matmuls, RoPE/norm vector work, cache writes, scan machinery).

Programs are jitted directly from ``_decode_fns``-style closures (the
``_decode_programs`` LRU is bypassed: the attention stub monkeypatches a
module global, which the cache key cannot see).

    PYTHONPATH=. python benchmarks/decode_attribution.py \
        [--out artifacts/gpt_bench/r05_decode_attrib.json]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from pddl_tpu.models.gpt import GPT_Small, _decode_cache_shapes
from pddl_tpu.models.llama import Llama_Small

PROMPT = 64
NEW = 256
HBM_GBPS = 819.0


def _fresh_cache(dec, batch):
    return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                        _decode_cache_shapes(dec, batch))


def _programs(dec, *, sample: bool, head: bool):
    """(prefill, decode_scan) jitted fresh — no LRU, no donation."""

    def step_fn(params, cache, tok, features_only=False):
        out, mutated = dec.apply(
            {"params": params, "cache": cache}, tok,
            train=False, mutable=["cache"], features_only=features_only,
        )
        return mutated["cache"], out[:, -1]

    def prefill(params, cache, prompt):
        return step_fn(params, cache, prompt)

    def decode_all(params, cache, logits):
        def body(carry, _):
            cache, prev = carry
            if sample:
                tok = jnp.argmax(prev, axis=-1).astype(jnp.int32)[:, None]
            else:
                # constant next token: same shapes, no sampling data path
                tok = jnp.full((prev.shape[0], 1), 1, jnp.int32)
            cache, out = step_fn(params, cache, tok,
                                 features_only=not head)
            return (cache, out if sample else prev), out[:, :1]

        (_, _), outs = jax.lax.scan(body, (cache, logits), None, length=NEW)
        return outs

    return jax.jit(prefill), jax.jit(decode_all)


def _scalar_sync(out):
    """Wait for the device: fetch a scalar reduced from the output."""
    leaf = jax.tree.leaves(out)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def _time(fn, *args, iters=5):
    _scalar_sync(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        _scalar_sync(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _decode_ms_per_tick(dec, params, batch, *, sample, head):
    prefill, decode_all = _programs(dec, sample=sample, head=head)
    prompt = jax.random.randint(jax.random.key(0), (batch, PROMPT), 0, 1000)
    cache, logits = prefill(params, _fresh_cache(dec, batch), prompt)
    t = _time(decode_all, params, cache,
              logits if logits.ndim == 2 else logits[..., 0])
    return t / NEW * 1e3


class _AttnStub:
    """Context manager replacing decode_attention with an identity in the
    model modules (they import it by name at module load)."""

    def __enter__(self):
        import pddl_tpu.models.llama as ml
        import pddl_tpu.models.vit as mv

        self._saved = [(ml, ml.decode_attention), (mv, mv.decode_attention)]

        def stub(q, k_cache, v_cache, index, **kw):
            if kw.get("return_lse"):
                return q, jnp.zeros(q.shape[:-1], jnp.float32)
            return q

        for mod, _ in self._saved:
            mod.decode_attention = stub
        return self

    def __exit__(self, *exc):
        for mod, fn in self._saved:
            mod.decode_attention = fn
        return False


def _weight_bytes(params, *, head_keys=("lm_head",)):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    head = body = 0
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if "embed" in name.lower():
            continue  # gathered, not streamed
        if leaf.ndim < 2:
            continue
        b = leaf.size * leaf.dtype.itemsize
        if any(k in name for k in head_keys):
            head += b
        else:
            body += b
    return body, head


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="")
    args = p.parse_args()

    models = {
        "gpt_small": GPT_Small(vocab_size=50257, max_len=1024,
                               dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16),
        "llama_small": Llama_Small(vocab_size=32000, max_len=1024,
                                   dtype=jnp.bfloat16,
                                   param_dtype=jnp.bfloat16),
    }
    record = {
        "metric": "decode_tick_attribution_ms",
        "method": "in-situ marginal costs: real decode-scan programs "
                  "differing by one component; prefill timed separately "
                  "and excluded",
        "config": {"prompt_len": PROMPT, "new_tokens": NEW,
                   "dtype": "bfloat16"},
        "device": jax.devices()[0].device_kind,
        "results": {},
    }
    for name, model in models.items():
        dec = model.clone(decode=True)
        variables = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, PROMPT), jnp.int32),
            train=False)
        params = variables["params"]
        body_b, head_b = _weight_bytes(params)
        hkv = getattr(model, "num_kv_heads", None) or model.num_heads
        d = model.embed_dim // model.num_heads
        kv_avg = 2 * model.depth * hkv * d * 2 * (PROMPT + NEW / 2)
        for batch in (1, 8):
            full = _decode_ms_per_tick(dec, params, batch,
                                       sample=True, head=True)
            nosample = _decode_ms_per_tick(dec, params, batch,
                                           sample=False, head=True)
            nohead = _decode_ms_per_tick(dec, params, batch,
                                         sample=False, head=False)
            with _AttnStub():
                noattn = _decode_ms_per_tick(dec, params, batch,
                                             sample=False, head=False)
            roof = (body_b + head_b + batch * kv_avg) / (HBM_GBPS * 1e9) * 1e3
            res = {
                "full_ms": round(full, 4),
                "sampling_ms": round(full - nosample, 4),
                "head_ms": round(nosample - nohead, 4),
                "attention_read_ms": round(nohead - noattn, 4),
                "rest_ms": round(noattn, 4),
                "weight_read_roofline_ms": round(roof, 4),
                "head_read_roofline_ms": round(
                    head_b / (HBM_GBPS * 1e9) * 1e3, 4),
                "body_read_roofline_ms": round(
                    body_b / (HBM_GBPS * 1e9) * 1e3, 4),
                "kv_read_roofline_ms": round(
                    batch * kv_avg / (HBM_GBPS * 1e9) * 1e3, 4),
                "full_vs_roofline": round(full / roof, 3),
                "tokens_per_sec_decode_only": round(batch / full * 1e3, 1),
            }
            record["results"][f"{name}_b{batch}"] = res
            print(name, f"b{batch}", json.dumps(res), flush=True)
    js = json.dumps(record)
    print(js)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js + "\n")


if __name__ == "__main__":
    sys.exit(main() or 0)
