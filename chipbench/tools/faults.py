"""The timed path broken underneath, one fault at a time: what the tests
under ``chipbench/tests`` and ``tools/limits.py`` plant to see ``correct``
come out false. Each fault is one a serving cell can have — a token or an
answer altered where it is produced.

    undo = plant("no_top_p"); ...; undo()
"""

from __future__ import annotations


def _altered_token():
    """Every greedy row of the engine's batched sampler returns the token
    after the best one."""
    import jax.numpy as jnp

    import pddl_tpu.serve.engine as engine_mod

    real = engine_mod.sample_logits_batched

    def altered(rng, logits, *, temperature, top_k, top_p):
        tok = real(rng, logits, temperature=temperature, top_k=top_k,
                   top_p=top_p)
        return jnp.where(jnp.asarray(temperature) <= 0,
                         (tok + 1) % logits.shape[-1], tok)

    engine_mod.sample_logits_batched = altered
    return lambda: setattr(engine_mod, "sample_logits_batched", real)


def _no_top_p():
    """The nucleus filter dropped: every sampled row draws from the whole
    vocabulary at its temperature."""
    import jax.numpy as jnp

    import pddl_tpu.models.gpt as gpt_mod

    real = gpt_mod.batched_filtered_logits

    def unfiltered(logits, *, temperature, top_k, top_p):
        return real(logits, temperature=temperature, top_k=top_k,
                    top_p=jnp.ones_like(jnp.asarray(top_p, jnp.float32)))

    gpt_mod.batched_filtered_logits = unfiltered
    return lambda: setattr(gpt_mod, "batched_filtered_logits", real)


def _short_answers():
    """Every stream of the window stops one token early, so none runs to
    its length."""
    import pddl_tpu.serve.engine as engine_mod

    real = engine_mod.ServeEngine.submit

    def short(self, prompt, max_new_tokens, **kw):
        cut = max_new_tokens - 1 if max_new_tokens > 3 else max_new_tokens
        return real(self, prompt, cut, **kw)

    engine_mod.ServeEngine.submit = short
    return lambda: setattr(engine_mod.ServeEngine, "submit", real)


FAULTS = {"altered_token": _altered_token, "no_top_p": _no_top_p,
          "short_answers": _short_answers}


def plant(name: str):
    """Plant the fault; returns the function that takes it out again.
    The process's traced programs are dropped on the way in and out, so
    that an engine built next traces the path as it now is."""
    import jax

    jax.clear_caches()
    undo = FAULTS[name]()

    def take_out():
        undo()
        jax.clear_caches()

    return take_out
