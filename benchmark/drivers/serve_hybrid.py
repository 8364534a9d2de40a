"""Driver ``serve_hybrid``: ``serve_decoder``'s binding and traffic for a
decoder whose published multipliers would blind ``serve_decoder``'s
comparison with the plain reference.

Everything that plays and measures is shared: ``serve_decoder.model_kw``
(the configuration's ``serve.model_kind`` and ``serve.model_kw``, ``"$key"``
standing for a published key), ``serve_lm.deployed`` / ``play`` / ``record``,
``warm_prompts``, the server's counters and profiler.  What differs is the
comparison that decides ``correct``, for this reason: under muP multipliers
seeded random weights leave the logits nearly flat (``lm_head_multiplier``
1/128: their spread over the vocabulary is under 0.01) and the branches of a
block unequal (the feed-forward adds about 1% of the residual), so an
absolute limit on a log-probability would pass a wrong or an 8-bit
feed-forward.  Hence:

(a) the cached path (prefill, then greedy tokens through the cache and the
    recurrent state) is held to the reference's one full forward in units of
    the reference logits' own spread at that position: the error of each
    chosen token's log-probability, and how far below the reference's best
    logit the chosen token's lies, each divided by the standard deviation of
    that row of reference logits (``logprob_err_sigmas``,
    ``argmax_margin_sigmas``);
(b) the program's plain forward over prompt + answer is compared with the
    reference branch by branch: what the mixer, the attention and the
    feed-forward each add to the residual stream, AFTER the branch's own
    multiplier (a relative error has no scale, and a multiplier left out
    then shows), the largest relative error over the layers
    (``branch_rel_err``).

Both twice a run, where the traffic file has a ``reference.long``.  A
program that cannot build the model (the parent of the PR that brought the
configuration) raises in the replica's constructor and the run ends non-zero
within seconds.
"""
from __future__ import annotations

import contextlib

from benchmark import common
from benchmark.drivers import serve_decoder, serve_lm
from benchmark.drivers.serve_lm import BenchLLMServer, warm_prompts

BRANCHES = ("mixer", "attn", "ffn")


def within(check: dict, limits: dict) -> bool:
    """Every token answered, both scale-free errors of the cached path and
    every branch's relative error inside the comparison's limits."""
    return (check["tokens"] == limits["new_tokens"]
            and check["logprob_err_sigmas"] <= limits["logprob_sigmas_max"]
            and check["argmax_margin_sigmas"] <= limits["logprob_sigmas_max"]
            and all(check["branch_rel_err"][b]
                    <= limits["branch_rel_err_max"] for b in BRANCHES))


def program_branches(model, params, ids):
    """What each branch of each layer adds to the residual stream in the
    program's own plain forward: {branch: [layers, B, S, d]}."""
    import jax
    import jax.numpy as jnp

    last = jnp.full((ids.shape[0],), ids.shape[1] - 1, jnp.int32)
    _, sown = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, logits_at=last, mutable=["branches"]))(params, ids)
    return {b: jnp.stack([
        sown["branches"][f"layer_{i}"][b + "_out"][0]
        for i in range(model.config.num_layers)]) for b in BRANCHES}


def compare(ref, config, model, params, prompt, got, have=None) -> dict:
    """One greedy answer (``got``: the engine's tokens and the
    log-probability it gave each) and the program's plain forward against
    the reference's one full forward over prompt + answer on ``params``:
    (a) and (b) of the module's docstring.  ``have``: the program's
    branches where the caller took them earlier (the precision probe, on
    weights it no longer holds)."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray([list(prompt) + got["tokens"]], jnp.int32)
    logits, want = ref.forward_with_branches(params, ids, config,
                                             first_row=len(prompt) - 1)
    logits = logits[0, :-1]
    sigma = jnp.std(logits, axis=-1)
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    if have is None:
        have = program_branches(model, params, ids)
    f32 = jnp.float32

    def rel(a, b):  # the largest over the layers
        return float(jnp.max(
            jnp.linalg.norm((a.astype(f32) - b).reshape(a.shape[0], -1),
                            axis=-1)
            / jnp.linalg.norm(b.reshape(b.shape[0], -1), axis=-1)))

    return {"tokens": len(got["tokens"]),
            "logprob_err_sigmas": float(jnp.max(jnp.abs(
                ref_lp - jnp.asarray(got["logprobs"])) / sigma)),
            "argmax_margin_sigmas": float(jnp.max(margin / sigma)),
            "logit_sigma": float(jnp.mean(sigma)),
            "branch_rel_err": {b: rel(have[b], want[b]) for b in BRANCHES},
            "branch_share_of_residual": branch_sizes(want, ids, params,
                                                     config)}


def branch_sizes(want, ids, params, config) -> dict:
    """How large each branch's addition is beside the residual stream it
    is added to (the embedding times its multiplier, in the first layer):
    the norms' ratio there.  A record, no limit: it says how unequal seeded
    weights leave the branches."""
    import jax.numpy as jnp

    x = params["embed"]["embedding"][ids].astype(jnp.float32) \
        * float(config["embedding_multiplier"])
    return {b: float(jnp.linalg.norm(want[b][0]) / jnp.linalg.norm(x))
            for b in BRANCHES}


class BenchHybridServer(BenchLLMServer):
    def warm(self, prompts, new_tokens):
        """``BenchLLMServer.warm`` after the programs it will reach have
        been compiled side by side.  Left to the warm-up's requests, the
        decode program and eight prefill buckets of six unrolled layers
        compile one after the other, ~30 s each: a cold set-up took 452 s
        (my chip run, PR 38).  A program compiled here is the one the
        engine's own call then finds (the jitted function's cache, or the
        persistent one); one that fails to compile here is left to that
        call, which raises what there is to raise."""
        import numpy as np
        from concurrent.futures import ThreadPoolExecutor

        eng = self.engine
        todo = [(eng._decode.__wrapped__, (
            eng._table, eng._lengths, eng._last_tok, eng._active,
            eng._temps, eng._top_ps, eng._seeds, eng._prev_tok, eng._fresh,
            eng._state))]
        for bucket in sorted({eng._bucket_for(len(p)) for p in prompts}):
            eng._prefill_fn(bucket)  # makes the jitted function
            todo.append((eng._prefills[("full", bucket)], (
                eng._table[0], np.zeros((bucket,), np.int32), np.int32(1),
                np.float32(0), np.float32(1), np.int32(0), np.int32(0),
                eng._state)))

        def compile_one(job):
            fn, rest = job
            fn.lower(eng._params, eng._k_pages, eng._v_pages,
                     *rest).compile()

        with ThreadPoolExecutor(len(todo)) as pool:
            for done in [pool.submit(compile_one, job) for job in todo]:
                try:
                    done.result()
                except Exception as e:  # noqa: BLE001 - the engine's call
                    print(f"[bench] compiling ahead failed: {e!r}",
                          flush=True)
        return super().warm(prompts, new_tokens)

    def reference_check(self, config_name, config, prompt, new_tokens):
        """Prefill and the cached decode of one greedy request, and the
        program's plain forward, against the reference (``compare``)."""
        eng = self.engine
        got = eng.rollout(eng.submit(prompt, new_tokens), timeout=600.0)
        return compare(common.load_module("reference", config_name), config,
                       eng._model, eng._params, prompt, got)


@contextlib.contextmanager
def session(cell, config, traffic, seed, allow_cpu=False):
    """``serve_decoder.session`` with this driver's server and limits."""
    s = config["serve"]
    with serve_lm.deployed(BenchHybridServer,
                           (s["model_kind"], serve_decoder.model_kw(config)),
                           config, seed, allow_cpu) as (handle, call):
        vocab = config["vocab_size"]
        call("warm", warm_prompts(traffic, vocab), 2)
        refs = serve_decoder.comparisons(traffic["reference"])
        found = [call("reference_check", cell["config"], config,
                      serve_decoder.reference_prompt(r["prompt_tokens"],
                                                     seed, vocab),
                      r["new_tokens"]) for r in refs]
        first = dict(found[0])
        if len(found) > 1:
            first["long"] = {**refs[1], **found[1]}
        sound = all(within(c, r) for c, r in zip(found, refs))

        def window(traffic, seconds, trace):
            played = serve_lm.play(handle, call, traffic, seed, vocab,
                                   seconds, trace,
                                   engine_keys=("state_pool_bytes",))
            return serve_lm.record(played, call("facts"), first, refs[0],
                                   sound)

        yield window


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    with session(cell, config, traffic, seed, allow_cpu) as window:
        return window(traffic, seconds, trace)
