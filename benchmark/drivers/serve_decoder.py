"""Driver ``serve_decoder``: ``serve_lm``'s open-loop chat traffic against one
``LLMServer`` replica, for a configuration that says itself which model of
the program it is.

``serve_lm.run`` binds ``"gpt2"`` and reads GPT-2's key names.  This one
binds ``config["serve"]["model_kind"]`` with ``config["serve"]["model_kw"]``,
in which a value ``"$key"`` stands for the configuration's published
``key``: the program's arguments are built from the published numbers, and a
rehearsal that shrinks a published number shrinks the model.  Everything else
is ``serve_lm``'s: ``deployed``, ``play`` (the client processes, the window
and the arithmetic on the stamps) and ``record``, its ``warm_prompts``, its
server's counters and profiler.

What differs besides the binding: the comparison with the plain reference
runs the reference as it is (it jits layer by layer, so that it fits beside
a 7 GB model and its page pool; one ``jax.jit`` around the whole of it would
hold every layer in float32 at once) and, where the reference says which
experts it chose, reports ``router_agreement``; it is made a second time
where the traffic file has a ``reference.long`` (a short prompt reaches only
the smallest prefill program, a few pages of context and, in a routed model,
only the few-rows form of the expert FFN; the long one has a limit of its
own); and the record carries the engine's ``moe_*`` shares.  A
program that cannot build the model (the parent of the PR that brought a
configuration) raises in the replica's constructor; ``serve.run`` hands that
error on and the run ends non-zero within seconds.
"""
from __future__ import annotations

import contextlib

from benchmark import common
from benchmark.drivers import serve_lm
from benchmark.drivers.serve_lm import BenchLLMServer, warm_prompts


def model_kw(config: dict) -> dict:
    """``serve.model_kw`` with every ``"$key"`` replaced by the published
    value of ``key``."""
    return {k: config[v[1:]] if isinstance(v, str) and v.startswith("$")
            else v for k, v in config["serve"]["model_kw"].items()}


def comparisons(reference: dict) -> list:
    """The traffic file's ``reference`` as the comparisons to make, each
    with ``prompt_tokens``, ``new_tokens``, ``logprob_tolerance`` and, for
    a routed model, ``router_agreement_min``: the block itself, then its
    ``long`` where it has one."""
    return [reference] + ([reference["long"]] if "long" in reference else [])


def reference_prompt(n: int, seed: int, vocab: int) -> list:
    return [(3 * j + seed) % vocab for j in range(n)]


def within(check: dict, limits: dict) -> bool:
    """Every token answered, both the largest log-probability error and
    the largest argmax margin inside the comparison's limit, and, where the
    comparison sets a floor for it, the experts chosen as the reference
    chose them in at least that share of (layer, token) pairs."""
    return (check["tokens"] == limits["new_tokens"]
            and check["logprob_max_err"] <= limits["logprob_tolerance"]
            and check["argmax_margin_max"] <= limits["logprob_tolerance"]
            and check.get("router_agreement", 1.0)
            >= limits.get("router_agreement_min", 0.0))


def _ids(prompt, got):
    import jax.numpy as jnp

    return jnp.asarray([list(prompt) + got["tokens"]], jnp.int32)


def compare(ref, config, params, prompt, got, program_experts=None) -> dict:
    """One greedy answer (``got``: the engine's tokens and the
    log-probability it gave each) against the reference's one full forward
    over prompt + answer on ``params``: the error of each chosen token's
    log-probability, how far below the reference's best logit the chosen
    token's lies (0 unless rounding flipped an argmax), and, for a routed
    model, the share of (layer, token) pairs in which the program (its
    ``program_experts``, [layers, 1, tokens, k]) and the reference chose
    the same set of experts."""
    import jax
    import jax.numpy as jnp

    ids = _ids(prompt, got)
    routed = program_experts is not None
    out = (ref.forward_with_experts if routed else ref.forward)(
        params, ids, config)
    logits = (out[0] if routed else out)[0, len(prompt) - 1:-1]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    check = {"tokens": len(got["tokens"]),
             "logprob_max_err": float(jnp.max(jnp.abs(
                 ref_lp - jnp.asarray(got["logprobs"])))),
             "argmax_margin_max": float(jnp.max(margin))}
    if routed:
        check["router_agreement"] = ref.router_agreement(
            program_experts, out[1])
    return check


def program_experts(model, params, prompt, got):
    """The experts the program's own forward chooses on prompt + answer
    ([layers, 1, tokens, k]), or None for a model that routes nothing: what
    its expert layers sow into the ``moe`` collection."""
    import jax
    import jax.numpy as jnp

    if not getattr(model.config, "num_experts", 0):
        return None
    _, sown = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, mutable=["moe"]))(params, _ids(prompt, got))
    return jnp.stack([
        sown["moe"][f"layer_{i}"]["moe"]["expert_idx"][0]
        for i in range(model.config.num_layers)])


class BenchDecoderServer(BenchLLMServer):
    def reference_check(self, config_name, config, prompt, new_tokens):
        """Prefill and the cached decode of one greedy request against the
        reference's full forward (``compare``)."""
        eng = self.engine
        got = eng.rollout(eng.submit(prompt, new_tokens), timeout=600.0)
        return compare(common.load_module("reference", config_name), config,
                       eng._params, prompt, got,
                       program_experts(eng._model, eng._params, prompt, got))


@contextlib.contextmanager
def session(cell, config, traffic, seed, allow_cpu=False):
    """``serve_lm.session`` for this driver's binding and comparisons."""
    s = config["serve"]
    with serve_lm.deployed(BenchDecoderServer,
                           (s["model_kind"], model_kw(config)), config, seed,
                           allow_cpu) as (handle, call):
        vocab = config["vocab_size"]
        call("warm", warm_prompts(traffic, vocab), 2)
        refs = comparisons(traffic["reference"])
        found = [call("reference_check", cell["config"], config,
                      reference_prompt(r["prompt_tokens"], seed, vocab),
                      r["new_tokens"]) for r in refs]
        first = dict(found[0])
        if len(found) > 1:
            first["long"] = {**refs[1], **found[1]}
        sound = all(within(c, r) for c, r in zip(found, refs))

        def window(traffic, seconds, trace):
            played = serve_lm.play(
                handle, call, traffic, seed, vocab, seconds, trace,
                engine_keys=("moe_experts_hit_share", "moe_max_expert_share"))
            return serve_lm.record(played, call("facts"), first, refs[0],
                                   sound)

        yield window


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    with session(cell, config, traffic, seed, allow_cpu) as window:
        return window(traffic, seconds, trace)
