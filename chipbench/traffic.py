"""The one general traffic generator. A traffic mix is a JSON file of
parameters under ``chipbench/traffic/``; this module turns it, a seed, a
window length and the model's limits into a list of requests. A later PR
adds a mix by adding a data file only.

Every seed offers THE SAME WORK: the multiset of (prompt length, output
length) pairs and the multiset of inter-arrival gaps are quantiles of the
file's distributions, and their order comes from the file's
``order_seed``; both are fixed by the file and the window length alone.
The run's seed decides the token ids and which requests are greedy.
(Drawing lengths, instants or their order per seed made one seed's window
heavier than another's, which no bound on a tail can carry.)

Schedule arithmetic follows ``benchmarks/serve_bench.py``'s
``_poisson_load`` / ``_trace_schedule`` (exponential gaps, due instants as
their running sum); that original is listed in PERF.md for a later PR to
delete.

File keys:
  kind           "open_loop" (requests fall due at instants) or "backlog"
                 (a standing queue: the harness keeps ``queue_target``
                 requests waiting, never over the engine's limit). Any
                 other kind names a generator module of its own,
                 ``chipbench/generators/<kind>.py`` with the same
                 ``generate(spec, seed, seconds, vocab_size, max_prompt,
                 max_total)``: a later benchmark PR brings sessions or
                 bursts as such a file, with the cell that uses it.
  rate_per_s     open loop: the offered rate, fixed (found by a sweep once).
  ramp_live      open loop: requests already in flight when the window
                 opens (the harness admits them in set-up, see ``ramp``).
  prompt_len /   {"median", "sigma", "min", "max"}: a log-normal clipped
  output_len     to [min, max], taken at stratified quantiles.
  multiset_size  backlog: how many pairs the cycle holds.
  sampling_mix   [{"share", "temperature", "top_p"}...]; shares are dealt
                 exactly (not drawn), so every seed has the same count of
                 greedy requests.
  strata         how many strata one "round" of the order holds.
  order_seed     the order of lengths and of gaps comes from THIS number,
                 not from the run's seed, so every run offers the same
                 schedule and the seed decides token ids, weights and
                 which requests are greedy only. For an open loop near
                 the knee, who arrives beside whom decides the tails
                 (PERF.md, PR 27: order alone moved ``ttft_p90_ms`` by
                 9-21 %). A second schedule is a second file.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import statistics
from typing import List, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class PlannedRequest:
    index: int
    due_s: float                 # open loop: offset into the window
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int
    temperature: float
    top_p: Optional[float]

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0


def load_traffic(name: str) -> dict:
    path = os.path.join(_HERE, "traffic", name + ".json")
    with open(path) as f:
        spec = json.load(f)
    if "kind" not in spec:
        raise ValueError(f"{path}: no kind")
    return spec


def lognormal_quantiles(spec: dict, n: int) -> List[int]:
    """``n`` stratified quantiles ((i + 0.5) / n) of a log-normal with the
    given median and sigma, clipped to [min, max]."""
    nd = statistics.NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(math.exp(mu + spec["sigma"] * z)))
        out.append(max(int(spec["min"]), min(int(spec["max"]), v)))
    return out


def gap_quantiles(n: int, total_s: float) -> np.ndarray:
    """``n`` stratified quantiles of the exponential gap distribution,
    rescaled to sum to ``total_s`` — a Poisson process conditioned on its
    count, with the luck of the draw taken out."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps * (total_s / gaps.sum())


def stratified_order(keys, strata: int, rng: np.random.RandomState):
    """A seeded order of ``range(len(keys))`` in which every run of
    ``strata`` consecutive items holds one item of each stratum of ``keys``
    (strata by rank), so that no stretch of the window is all heavy or all
    light. Within a stratum and within a round the order is random."""
    n = len(keys)
    ranked = np.argsort(np.asarray(keys), kind="stable")
    strata = max(1, min(strata, n))
    buckets = [list(rng.permutation(b))
               for b in np.array_split(ranked, strata)]
    order = []
    while any(buckets):
        round_ = [b.pop() for b in buckets if b]
        order.extend(int(i) for i in rng.permutation(round_))
    return order


def _pairs(spec: dict, n: int):
    """The fixed multiset: prompt quantile i paired with output quantile
    (i * stride) mod n, stride coprime to n, so the two lengths are
    uncorrelated without drawing anything."""
    prompts = lognormal_quantiles(spec["prompt_len"], n)
    outputs = lognormal_quantiles(spec["output_len"], n)
    stride = next(s for s in range(max(2, int(n * 0.382)), 2 * n + 2)
                  if math.gcd(s, n) == 1)
    return [(prompts[i], outputs[(i * stride) % n]) for i in range(n)]


def _deal_sampling(mix: list, n: int, rng: np.random.RandomState):
    """Exact shares: floor(share * n) of each, the remainder to the first
    entries; order shuffled by the seed."""
    counts = [int(math.floor(m["share"] * n)) for m in mix]
    for i in range(n - sum(counts)):
        counts[i % len(counts)] += 1
    kinds = [k for k, c in enumerate(counts) for _ in range(c)]
    return [mix[k] for k in rng.permutation(kinds)]


# A standing backlog's plan holds as many cycles as a system draining
# this many requests a second would get through in the window (a repeated
# prompt would hit the prefix cache, so the plan must not run out).
BACKLOG_PLAN_RATE_PER_S = 8.0


def generate(spec: dict, seed: int, seconds: float, vocab_size: int,
             max_prompt: int, max_total: int) -> List[PlannedRequest]:
    """The requests of one run. ``seed`` may be any whole number up to a
    little over 2**31."""
    kind = spec["kind"]
    if kind not in ("open_loop", "backlog"):
        module = importlib.import_module("chipbench.generators." + kind)
        return module.generate(spec, seed, seconds, vocab_size, max_prompt,
                               max_total)
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    order_rng = np.random.RandomState(int(spec["order_seed"]))
    if kind == "open_loop":
        n = max(1, int(round(spec["rate_per_s"] * seconds)))
        return _one_pass(spec, rng, order_rng, n, seconds, vocab_size,
                         max_prompt, max_total, 0)
    # A standing backlog: the multiset over and over, each cycle in a
    # fresh order with fresh token ids.
    n = int(spec["multiset_size"])
    out = []
    for _ in range(max(2, math.ceil(seconds * BACKLOG_PLAN_RATE_PER_S / n))):
        out.extend(_one_pass(spec, rng, order_rng, n, seconds, vocab_size,
                             max_prompt, max_total, len(out)))
    return out


def ramp(spec: dict, seed: int, vocab_size: int, max_prompt: int,
         max_total: int) -> List[PlannedRequest]:
    """Open loop: the ``ramp_live`` requests already in flight when the
    window opens, so that it opens on a busy engine and not on an empty
    one. Drawn from the same multiset in the file's order; request k has
    (k + 0.5) / ramp_live of its answer still to come (a stream in
    flight is caught at a uniform point of its life). They are admitted
    in set-up, are not among the requests the window's tails are over,
    and must still run to their length."""
    live = int(spec.get("ramp_live", 0))
    if live < 1:
        return []
    rng = np.random.RandomState((int(seed) + 0x5EED) % (2 ** 32))
    order_rng = np.random.RandomState(int(spec["order_seed"]) + 1)
    out = _one_pass(dict(spec, kind="backlog"), rng, order_rng, live, 0.0,
                    vocab_size, max_prompt, max_total, 0)
    for k, r in enumerate(out):
        r.index = -1 - k
        r.max_new_tokens = max(2, int(round(
            r.max_new_tokens * (k + 0.5) / live)))
    return out


def _one_pass(spec, rng, order_rng, n, seconds, vocab_size, max_prompt,
              max_total, first_index) -> List[PlannedRequest]:
    strata = int(spec.get("strata", 8))
    pairs = _pairs(spec, n)
    order = stratified_order([p + 4 * o for p, o in pairs], strata,
                             order_rng)
    if spec["kind"] == "open_loop":
        gaps = gap_quantiles(n, seconds)
        gaps = gaps[stratified_order(gaps, strata, order_rng)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        due = np.zeros(n)
    sampling = _deal_sampling(spec["sampling_mix"], n, rng)
    out = []
    for k, i in enumerate(order):
        plen, olen = pairs[i]
        plen = min(plen, max_prompt)
        olen = min(olen, max_total - plen)
        s = sampling[k]
        out.append(PlannedRequest(
            index=first_index + k, due_s=float(due[k]),
            prompt=rng.randint(0, vocab_size, size=plen).astype(np.int32),
            max_new_tokens=int(olen),
            temperature=float(s.get("temperature", 0.0)),
            top_p=s.get("top_p")))
    return out
