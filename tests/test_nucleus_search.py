"""The sampler's cuts found by a threshold search, held to the sort rule
they replaced (PR 39).

``batched_filtered_logits`` finds the nucleus cut, and the top-k
threshold when some row asks for it, by searching the row's order keys
with compare-and-sum passes: no sort over ``[rows, V]``. The parent's
filter — one stable sort carrying the ids, the CDF summed in sorted
order — is kept here, and only here, as the oracle; the kept sets must
be equal row for row, and so the draws.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import batched_filtered_logits, sample_logits_batched


def _parent_filtered_logits(logits, *, temperature, top_k, top_p):
    """PR 38's ``batched_filtered_logits``: the sort rule."""
    logits = logits.astype(jnp.float32)
    b, v = logits.shape
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    kk = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    pp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))[:, None]
    warped = logits / jnp.where(t > 0, t, 1.0)[:, None]
    ids = jax.lax.broadcasted_iota(jnp.int32, warped.shape, 1)
    neg, sorted_ids = jax.lax.sort((-warped, ids), dimension=1,
                                   is_stable=True, num_keys=1)
    sorted_l = -neg
    kth = jnp.take_along_axis(sorted_l, (jnp.clip(kk, 1, v) - 1)[:, None],
                              axis=-1)
    no_topk = kk[:, None] <= 0
    warped = jnp.where(no_topk | (warped >= kth), warped, -jnp.inf)
    sorted_l = jnp.where(no_topk | (sorted_l >= kth), sorted_l, -jnp.inf)
    cdf = jnp.cumsum(jax.nn.softmax(sorted_l, axis=-1), axis=-1)
    last = jnp.sum(cdf[:, :-1] < pp, axis=-1, keepdims=True, dtype=jnp.int32)
    v_last = jnp.take_along_axis(sorted_l, last, axis=-1)
    id_last = jnp.take_along_axis(sorted_ids, last, axis=-1)
    keep = ((warped > v_last) | ((warped == v_last) & (ids <= id_last))
            | (pp >= 1.0))
    return jnp.where(keep, warped, -jnp.inf)


def _away_from_the_steps(x, t, k, p):
    """``p`` with each row's ``top_p`` moved to the middle of the step of
    its float64 CDF (sort order, after top-k) where it lies within 2e-6
    of an edge of the step: there the two float32 sums may round to either
    side, which tests rounding and not the rule. Also returns, per row,
    how many entries tie at the cut."""
    p, ties = np.array(p, np.float32), []
    for i in range(x.shape[0]):
        w = x[i] / np.float32(t[i] if t[i] > 0 else 1.0)
        order = np.argsort(-w, kind="stable")
        ws = w[order].astype(np.float64)
        if k[i] > 0:
            ws = np.where(ws >= ws[min(k[i], w.size) - 1], ws, -np.inf)
        if p[i] >= 1.0 or not np.isfinite(ws[0]):
            ties.append(0)
            continue
        e = np.exp(ws - ws[0])
        cdf = np.cumsum(e / e.sum())
        j = int(np.searchsorted(cdf, p[i]))       # first cdf >= p
        lo, hi = (cdf[j - 1] if j else 0.0), cdf[min(j, cdf.size - 1)]
        # (No mass lies before the first entry: nothing rounds there.)
        near = hi - p[i] < 2e-6 or (j and p[i] - lo < 2e-6)
        if near and hi - lo > 8e-6:
            p[i] = (lo + hi) / 2
        ties.append(int(np.sum(w == ws[min(j, cdf.size - 1)])))
    return p, ties


def _case(name):
    """``(logits [B, V], temperature, top_k, top_p, least ties at a
    nucleus row's cut)``: seeded batches; the engine's sentinels (0 = no
    top-k, 2.0 = no nucleus)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    bf16 = lambda a: np.array(jnp.asarray(a, jnp.bfloat16)
                              .astype(jnp.float32))
    if name == "bf16_hundreds_tied_at_the_cut":
        # Near-flat logits as random weights give, away from 0: bf16
        # rounds them to steps of 1/64, hundreds of tokens a step.
        return (bf16(rng.normal(size=(4, 50257)) * 0.25 + 3.0),
                [0.7, 1.0, 0.7, 1.3],
                [0, 0, 0, 0], [0.9, 0.5, 0.99, 0.9], 100)
    if name == "neg_inf_rows_and_a_whole_row_tied":
        x = bf16(rng.normal(size=(5, 4096)) * 2.0)
        x[0, rng.random(4096) < 0.8] = -np.inf     # a grammar mask
        x[1, 7:] = -np.inf
        x[2] = 1.25                                 # the whole row tied
        x[3, ::2] = -np.inf
        return x, [0.7, 1.0, 0.7, 1.0, 0.9], [0] * 5, [0.9, 0.5, 0.9, 0.99,
                                                         0.9], 0
    if name == "top_p_1e-6_0p5_0p9_and_at_least_1":
        return (bf16(rng.normal(size=(5, 1000)) * 2.0), [0.7] * 5, [0] * 5,
                [1e-6, 0.5, 0.9, 1.0, 2.0], 0)
    if name == "v_not_a_multiple_of_128":
        return (rng.normal(size=(3, 50257)).astype(np.float32) * 3.0,
                [0.7, 1.0, 0.0], [0, 0, 0], [0.9, 0.95, 2.0], 0)
    if name == "glm_vocabulary_at_4_rows":
        return (bf16(rng.normal(size=(4, 154880)) * 2.0), [0.7, 0.0, 0.7, 1.0],
                [0, 0, 0, 0], [0.9, 2.0, 0.9, 0.5], 1)
    if name == "top_k_on_some_rows_only":
        # Integer logits: the k-th value is shared by several tokens.
        x = np.round(rng.normal(size=(6, 1001)) * 2.0).astype(np.float32)
        return (x, [0.7, 1.0, 0.7, 1.2, 0.0, 1.0], [0, 40, 0, 300, 0, 2000],
                [0.9, 0.9, 2.0, 0.5, 2.0, 0.9], 0)
    if name == "top_k_alone":
        return (bf16(rng.normal(size=(3, 4096)) * 2.0), [0.7, 1.0, 1.0],
                [1, 5, 4096], [2.0, 2.0, 2.0], 0)
    raise KeyError(name)


@pytest.mark.parametrize("case", [
    "bf16_hundreds_tied_at_the_cut", "neg_inf_rows_and_a_whole_row_tied",
    "top_p_1e-6_0p5_0p9_and_at_least_1", "v_not_a_multiple_of_128",
    "glm_vocabulary_at_4_rows", "top_k_on_some_rows_only", "top_k_alone"])
def test_search_keeps_the_sort_rules_set(case):
    """The searched filter against the parent's sorted one: the same kept
    set in every row (and the same kept values, so the same draw), with
    ties at the cut, grammar masks, a whole row tied, the extremes of
    top_p, a vocabulary off the 128 lanes and glm-4.7-flash's, and top-k
    on none, some and all rows (the device-side ``lax.cond`` both ways)."""
    x, t, k, p, ties_wanted = _case(case)
    p, ties = _away_from_the_steps(x, t, k, p)
    assert max(ties) >= ties_wanted, ties
    args = dict(temperature=jnp.asarray(t, jnp.float32),
                top_k=jnp.asarray(k, jnp.int32), top_p=jnp.asarray(p))
    got = np.asarray(jax.jit(batched_filtered_logits)(jnp.asarray(x), **args))
    want = np.asarray(jax.jit(_parent_filtered_logits)(jnp.asarray(x),
                                                       **args))
    for i in range(x.shape[0]):
        np.testing.assert_array_equal(
            got[i], want[i], err_msg=f"{case} row {i} (t={t[i]}, k={k[i]}, "
            f"p={p[i]}): kept {np.isfinite(got[i]).sum()} against "
            f"{np.isfinite(want[i]).sum()}")
    key = jax.random.key(39)
    drawn = sample_logits_batched(key, jnp.asarray(x), **args)
    sampled = jax.random.categorical(key, jnp.asarray(want), axis=-1)
    np.testing.assert_array_equal(
        np.asarray(drawn),
        np.where(np.asarray(t) > 0, np.asarray(sampled), np.argmax(x, -1)))
