#!/usr/bin/env python3
"""Does a ``serve_decoder`` cell's ``correct`` notice a lower precision?  The
driver's own comparison with the plain reference (``serve_decoder.compare``:
prefill, then greedy tokens through the cache, against one full forward),
in the process that holds the chip, on the program as it is and on the
program with its weights rounded to 8 bits (4 exponent and 3 mantissa bits,
scaled per tensor to that format's range), seed by seed, once for every
comparison the traffic file asks for (``serve_decoder.comparisons``).  The
reference keeps the true weights each time.  Each comparison's
``logprob_tolerance`` is set from what this prints: over the first line's
errors on every seed, under the second's.

A model that fills the chip cannot be held twice, so the weights are rounded
in place (donated, leaf by leaf), the rounded program answers, and the true
weights are then made again from the seed for the reference.  The engine
here has two slots: the pool is small, the programs are the cell's.

    python3 benchmark/rehearsal/precision_probe_decoder.py [--tiny] \
        [--seeds 3000000011 2500000001] [cell]
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def round_to_8_bits(params):
    """Every leaf rounded to 8 bits (a sign, 4 exponent bits, 3 mantissa
    bits, scaled per tensor so that its largest value is that format's
    largest finite one, 240), in its own memory.  ``reduce_precision`` and
    not a convert to float8 and back: inside one ``jit`` the compiler
    removes such a pair as excess precision, and the "rounded" weights come
    out unchanged."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        v = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(v)) / 240.0 + 1e-30
        return (jax.lax.reduce_precision(v / scale, 4, 3)
                * scale).astype(x.dtype)

    donated = jax.jit(leaf, donate_argnums=0)
    return jax.tree.map(donated, params)


def probe(cell, config, traffic, seed):
    """{"as_it_is": [one check per comparison], "8bit": [...]}."""
    from benchmark import common
    from benchmark.drivers import serve_decoder
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

    def answers(params):
        """Per comparison: the engine's greedy answer and the experts the
        program's own forward chose on prompt + answer."""
        out = []
        for r, prompt in zip(refs, prompts):
            got = eng.rollout(eng.submit(prompt, r["new_tokens"]),
                              timeout=900.0)
            out.append((got, serve_decoder.program_experts(
                model, params, prompt, got)))
        return out

    model, params = build()
    eng = LLMEngine(model, params, max_slots=2, page_size=s["page_size"],
                    max_ctx=s["max_ctx"], chunk_tokens=1)
    try:
        sound = answers(params)
        eng._params = params = round_to_8_bits(params)
        rounded = answers(params)
        eng._params = params = None
        _, params = build()  # the true weights again, for the reference
        return {how: [{"prompt_tokens": r["prompt_tokens"],
                       **serve_decoder.compare(ref, config, params, prompt,
                                               got, chose)}
                      for r, prompt, (got, chose)
                      in zip(refs, prompts, answered)]
                for how, answered in (("as_it_is", sound),
                                      ("8bit", rounded))}
    finally:
        eng.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", nargs="?", default="olmoe_serve_steady2")
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[3000000011, 2500000001])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    from benchmark import common
    from benchmark.rehearsal import rehearse
    from ray_tpu._private.jax_env import ensure_compile_cache

    ensure_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == args.cell)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = common.load_traffic(cell["traffic"])
    if args.tiny:
        common.merge({"config": config, "traffic": traffic},
                     rehearse.tiny_overrides(args.cell))
    for seed in args.seeds:
        print("PROBE " + json.dumps({
            "cell": args.cell, "seed": seed,
            **probe(cell, config, traffic, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
