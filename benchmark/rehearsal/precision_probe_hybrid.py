#!/usr/bin/env python3
"""Does a ``serve_hybrid`` cell's ``correct`` notice a lower precision?
``precision_probe_decoder.py``'s control for ``serve_hybrid.compare``: the
driver's own comparisons with the plain reference (the cached path in units
of the reference logits' spread, the plain forward branch by branch), in the
process that holds the chip, on the program as it is, on the program with
its weights rounded to 8 bits (``precision_probe_decoder.round_to_8_bits``)
and, with ``--bf16-state``, on the program holding its recurrent state in
bfloat16 between decode steps (rounded after every step on the host's
side: what a bfloat16 state array would keep), seed by seed, once for every
comparison the traffic file asks for.  The reference keeps the true weights
each time.  Each limit is set from what this prints: over the first line's
readings on every seed, under the second's.

A model that fills the chip cannot be held twice, so the weights are rounded
in place, the rounded program answers (its plain forward too), and the true
weights are then made again from the seed for the reference.  The engine
here has two slots: the pools are small, the programs are the cell's.

    python3 benchmark/rehearsal/precision_probe_hybrid.py [--tiny] \
        [--seeds 3000000011 2500000001] [--bf16-state] [cell]
"""
import argparse
import contextlib
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def answer_with_bf16_state(eng, prompt, new_tokens):
    """One greedy request, the loop stepped by hand, the recurrent state
    rounded to bfloat16 after the prefill and after every decode step."""
    import jax
    import jax.numpy as jnp

    def rounded(state):
        return [{k: (v.astype(jnp.bfloat16).astype(v.dtype)
                     if k == "ssm" else v) for k, v in layer.items()}
                for layer in state]

    rid = eng.submit(prompt, new_tokens)
    for _ in range(4 * new_tokens + 8):
        if eng._requests[rid].done.is_set():
            break
        eng._iteration(None)
        eng._state = jax.block_until_ready(rounded(eng._state))
    return eng.rollout(rid, timeout=60.0)


def probe(cell, config, traffic, seed, bf16_state=False):
    """{"as_it_is": [one check per comparison], "8bit": [...],
    "bf16_state": [...]}."""
    from benchmark import common
    from benchmark.drivers import serve_decoder, serve_hybrid
    from benchmark.rehearsal.precision_probe_decoder import round_to_8_bits
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    s = config["serve"]
    ref = common.load_module("reference", cell["config"])
    vocab = config["vocab_size"]
    refs = serve_decoder.comparisons(traffic["reference"])
    prompts = [serve_decoder.reference_prompt(r["prompt_tokens"], seed, vocab)
               for r in refs]

    def build():
        return build_model(s["model_kind"], serve_decoder.model_kw(config),
                           common.jax_seed(seed))

    @contextlib.contextmanager
    def engine(params, start=True):
        eng = LLMEngine(model, params, max_slots=2,
                        page_size=s["page_size"], max_ctx=s["max_ctx"],
                        chunk_tokens=1, start=start)
        try:
            yield eng
        finally:
            eng.close()
            eng._params = None  # a closed engine may outlive its name

    def answers(eng, params):
        """Per comparison: the engine's greedy answer and the branches of
        the program's own plain forward over prompt + answer."""
        import jax.numpy as jnp

        out = []
        for r, prompt in zip(refs, prompts):
            got = eng.rollout(eng.submit(prompt, r["new_tokens"]),
                              timeout=900.0)
            ids = jnp.asarray([list(prompt) + got["tokens"]], jnp.int32)
            out.append((got, serve_hybrid.program_branches(
                model, params, ids)))
        return out

    model, params = build()
    found = {}
    with engine(params) as eng:
        found["as_it_is"] = answers(eng, params)
    if bf16_state:
        with engine(params, start=False) as eng:
            found["bf16_state"] = [
                (answer_with_bf16_state(eng, prompt, r["new_tokens"]), have)
                for r, prompt, (_, have) in zip(refs, prompts,
                                                found["as_it_is"])]
    params = round_to_8_bits(params)
    with engine(params) as eng:
        found["8bit"] = answers(eng, params)
    eng = params = None
    gc.collect()  # the rounded weights go before the true ones come back
    _, params = build()  # the true weights again, for the reference

    return {how: [{"prompt_tokens": r["prompt_tokens"],
                   "within": serve_hybrid.within(check, r), **check}
                  for r, prompt, (got, have) in zip(refs, prompts, answered)
                  for check in [serve_hybrid.compare(
                      ref, config, model, params, prompt, got, have)]]
            for how, answered in found.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", nargs="?", default="falconh1_serve_steady")
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[3000000011, 2500000001])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--bf16-state", action="store_true")
    args = ap.parse_args()
    from benchmark import run
    from benchmark.rehearsal import rehearse
    from ray_tpu._private.jax_env import ensure_compile_cache

    ensure_compile_cache()
    overrides = rehearse.tiny_overrides(args.cell) if args.tiny else None
    _, cell, config, traffic = run.load_cell(args.cell, overrides)
    for seed in args.seeds:
        print("PROBE " + json.dumps({
            "cell": args.cell, "seed": seed,
            **probe(cell, config, traffic, seed, args.bf16_state)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
