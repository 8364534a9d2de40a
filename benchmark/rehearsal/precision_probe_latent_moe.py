#!/usr/bin/env python3
"""Does a ``serve_latent_moe`` cell's ``correct`` notice a lower precision,
and a rope that is not YaRN's?
``precision_probe_sparse_moe.py``'s control for ``serve_latent_moe.compare``:
the driver's own comparisons with the plain reference (the cached path's
log-probabilities given its choices of experts, the choices' slack and
overlap, the short comparison's parts), in the process that holds the chip,
on the program as it is, on the program with a fault planted that only this
family can have (``planted``: the true weights under a PLAIN rope, no blend
of frequencies and no ``mscale^2`` on the softmax scale; the comparison over
12,000 rows, whose positions lie past the original 4,096, has to come out
not ``within``), and on the program with its weights rounded to 8 bits
(``precision_probe_decoder.round_to_8_bits``), seed by seed, once for every
comparison the traffic file asks for.  The reference keeps the true weights
and the published rope each time.  Each limit is set from what this prints:
over the first line's readings on every seed, under the last's.

A model that fills the chip cannot be held twice, so the weights are rounded
in place, the rounded program answers (its plain forward too), and the true
weights are then made again from the seed for the reference.  The engine
here has two slots: the pool is small, the programs are the cell's.

    python3 benchmark/rehearsal/precision_probe_latent_moe.py [--tiny] \
        [--seeds 3000000011 2500000001] [cell]
"""
import argparse
import dataclasses
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def planted(model):
    """``model`` under a plain rope: the published frequencies unscaled, the
    softmax scale without ``mscale^2``."""
    return type(model)(dataclasses.replace(model.config, rope_scaling=None))


def probe(cell, config, traffic, seed):
    """{"as_it_is": [one check per comparison], "plain_rope": [...],
    "8bit": [...]}."""
    import jax

    from benchmark import common
    from benchmark.drivers import serve_decoder
    from benchmark.drivers import serve_latent_moe as driver
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
        """Per comparison: the engine's greedy answer with its rows' chosen
        experts and, where the comparison names parts, what the program's
        own plain forward over the same rows added and chose (on the
        host)."""
        out = []
        eng = LLMEngine(model, params, max_slots=2, page_size=s["page_size"],
                        max_ctx=s["max_ctx"], chunk_tokens=1,
                        record_experts=True)
        try:
            for r, prompt in zip(refs, prompts):
                got = eng.rollout(eng.submit(prompt, r["new_tokens"],
                                             record_experts=True),
                                  timeout=1500.0)
                have = None
                if "branch_rel_err_max" in r:
                    have = jax.device_get(driver.program_parts(
                        model, params, driver.fed_rows(prompt, got)))
                out.append((got, have))
        finally:
            eng.close()
            eng._params = None  # a closed engine may outlive its name
        return out

    model, params = build()
    found = {"as_it_is": answers(model, params),
             "plain_rope": answers(planted(model), params)}
    params = round_to_8_bits(params)
    found["8bit"] = answers(model, params)
    params = None
    gc.collect()  # the rounded weights go before the true ones come back
    _, params = build()  # the true weights again, for the reference

    return {how: [{"prompt_tokens": r["prompt_tokens"],
                   "within": driver.within(check, r), **check}
                  for r, prompt, (got, have) in zip(refs, prompts, answered)
                  for check in [driver.compare(
                      ref, config, model, params, prompt, got,
                      "branch_rel_err_max" in r, have)]]
            for how, answered in found.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", nargs="?", default="sarvam105b_serve_docreason")
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[3000000011, 2500000001])
    ap.add_argument("--tiny", action="store_true")
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
            **probe(cell, config, traffic, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
