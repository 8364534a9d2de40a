#!/usr/bin/env python3
"""Does a ``serve_eva`` cell's ``correct`` notice a lower precision, and the
two faults that only a cache of EVA layers can have?
``precision_probe_latent_moe.py``'s control for ``serve_eva.compare``: the
driver's own comparisons with the plain reference (the cached path's
log-probabilities, the parts), in the process that holds the chip, on the
program as it is; with ``--faults`` on the program with a fault planted,
(``last_window_missing``: a decode step reads the summaries of all closed
windows but the last, the off-by-one of R_i; ``mean_pooling``: phi and mu
zeroed, so that a chunk's summary is its mean and not the learned pooling;
the long comparison has to come out not ``within`` under each); and on the
program with its weights rounded to 8 bits
(``precision_probe_decoder.round_to_8_bits``), seed by seed, once for every
comparison the traffic file asks for.  The reference keeps the true weights
each time.  Each limit is set from what this prints: over the first line's
readings on every seed, under the others'.

The weights are rounded in place, the rounded program answers (its plain
forward too), and the true weights are then made again from the seed for the
reference.  The engine here has two slots: the pool is small, the programs
are the cell's.

    python3 benchmark/rehearsal/precision_probe_eva.py [--tiny] [--faults] \
        [--seeds 3000000011 2500000001] [cell]
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def last_window_missing(model):
    """``model`` with a cache map whose decode steps read the summaries of
    every closed window but the last."""
    import jax.numpy as jnp

    from ray_tpu.ops.eva import EvaCacheMap

    class OffByOne(EvaCacheMap):
        def summary_rows(self, n):
            return (self.window // self.chunk) * jnp.maximum(
                n // self.window - 1, 0)

    class Planted(type(model)):
        def cache_map(self, page_size, max_ctx):
            c = self.config
            return OffByOne(c.window_size, c.chunk_size, page_size, max_ctx)

    return Planted(model.config)


def mean_pooling(params):
    """``params`` with every layer's ``phi`` and ``mu`` zeroed (the other
    leaves are the same arrays): a chunk's softmax is then flat."""
    import jax.numpy as jnp

    return {name: ({**layer, "attn": {**layer["attn"], **{
        vec: jnp.zeros_like(layer["attn"][vec]) for vec in ("phi", "mu")}}}
        if name.startswith("layer_") else layer)
        for name, layer in params.items()}


def probe(cell, config, traffic, seed, faults=False):
    """{"as_it_is": [one check per comparison], "8bit": [...]} and, with
    ``faults``, "last_window_missing" and "mean_pooling"."""
    from benchmark import common
    from benchmark.drivers import serve_decoder
    from benchmark.drivers import serve_eva as driver
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

    def answers(model, params):
        """Per comparison: the engine's greedy answer and what the
        program's own plain forward over the same rows added (on the
        host)."""
        out = []
        eng = LLMEngine(model, params, max_slots=2, page_size=s["page_size"],
                        max_ctx=s["max_ctx"], chunk_tokens=1)
        try:
            for r, prompt in zip(refs, prompts):
                got = eng.rollout(eng.submit(prompt, r["new_tokens"]),
                                  timeout=1500.0)
                out.append((got, driver.program_parts(
                    model, params, driver.fed_rows(prompt, got))))
        finally:
            eng.close()
            eng._params = None  # a closed engine may outlive its name
        return out

    model, params = build()
    found = {"as_it_is": answers(model, params)}
    if faults:
        found["last_window_missing"] = answers(last_window_missing(model),
                                               params)
        found["mean_pooling"] = answers(model, mean_pooling(params))
    params = round_to_8_bits(params)
    found["8bit"] = answers(model, params)
    params = None
    gc.collect()  # the rounded weights go before the true ones come back
    _, params = build()  # the true weights again, for the reference

    return {how: [{"prompt_tokens": r["prompt_tokens"],
                   "within": driver.within(check, r), **check}
                  for r, prompt, (got, have) in zip(refs, prompts, answered)
                  for check in [driver.compare(ref, config, model, params,
                                               prompt, got, have)]]
            for how, answered in found.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", nargs="?", default="evabyte_serve_bytedoc")
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[3000000011, 2500000001])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--faults", action="store_true")
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
            **probe(cell, config, traffic, seed, args.faults)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
