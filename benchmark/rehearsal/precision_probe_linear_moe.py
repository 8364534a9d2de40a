#!/usr/bin/env python3
"""Does a ``serve_linear_moe`` cell's ``correct`` notice a lower precision,
and a fault of the cached path?  ``precision_probe_hybrid_moe.py``'s control
for ``serve_linear_moe.compare``: the driver's own comparisons with the
plain reference (the cached path's log-probabilities given its choices, the
plain forward part by part given its own, the choices' slack and overlap),
in the process that holds the chip, on the program as it is and on the
program with its weights rounded to 8 bits
(``precision_probe_decoder.round_to_8_bits``), seed by seed, once for every
comparison the traffic file asks for.  The reference keeps the true weights
each time.  Each limit is set from what this prints: over the first line's
readings on every seed, under the second's.  The first comparison's line
also carries ``spread``: the quantiles of the seeded decays and the experts'
load (``serve_linear_moe.spread``).

``--faults``: the program as it is but for one fault put into the cached
path (``precision_probe_hybrid_moe.Faulty``'s two that know no state's
name: the bucket's padding advances the state; one page's latent rows
written where another's belong), to see that the cached path's limit is one
such a fault does not pass.

A model that fills the chip cannot be held twice, so the weights are rounded
in place, the rounded program answers (its plain forward too), and the true
weights are then made again from the seed for the reference.  The engine
here has two slots: the pools are small, the programs are the cell's.

``--by-layer``: instead, the plain forward's parts layer by layer over the
longest comparison's prompt, as it is and with float32 activations at the
highest matmul precision (``DEPTH`` lines).  The second must read every
part of every layer within 1e-3 of the reference: what is left in the first
is then bfloat16's rounding carried through the depth and no fault of a
form (PR 47 found one so: the chunked delta rule's triangular inverse read
8% in float32 at the sixth layer and 1e-4 at the first).

    python3 benchmark/rehearsal/precision_probe_linear_moe.py [--tiny] \
        [--faults | --by-layer] [--seeds 3000000011 2500000001] [cell]
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

FAULTS = ("padding_advances", "page_misread")


def probe(cell, config, traffic, seed, faults=False):
    """{"as_it_is": [one check per comparison], "8bit": [...], and with
    ``faults`` one list a fault of ``FAULTS``}."""
    from benchmark import common
    from benchmark.drivers import serve_decoder
    from benchmark.drivers import serve_linear_moe as driver
    from benchmark.rehearsal.precision_probe_decoder import round_to_8_bits
    from benchmark.rehearsal.precision_probe_hybrid_moe import Faulty
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
    def engine(params, fault=None):
        served = model if fault is None else Faulty(model, fault,
                                                    s["page_size"])
        eng = LLMEngine(served, params, max_slots=2,
                        page_size=s["page_size"], max_ctx=s["max_ctx"],
                        chunk_tokens=1, record_experts=True)
        try:
            yield eng
        finally:
            eng.close()
            eng._params = None  # a closed engine may outlive its name

    def answers(params, fault=None, spread=False):
        """Per comparison: the engine's greedy answer with its rows'
        chosen experts, the parts and choices of the program's own plain
        forward over the same rows and, asked for, its ``spread``."""
        out = []
        with engine(params, fault) as eng:
            for r, prompt in zip(refs, prompts):
                got = eng.rollout(eng.submit(prompt, r["new_tokens"],
                                             record_experts=True),
                                  timeout=900.0)
                ids = driver.fed_rows(prompt, got)
                sown = driver.program_forward(model, params, ids)
                out.append((got, driver.program_parts(model, params, ids,
                                                      sown),
                            driver.spread(model, params, sown)
                            if spread else None))
        return out

    model, params = build()
    found = {"as_it_is": answers(params, spread=True)}
    for fault in FAULTS if faults else ():
        found[fault] = answers(params, fault)
    params = round_to_8_bits(params)
    found["8bit"] = answers(params)
    params = None
    gc.collect()  # the rounded weights go before the true ones come back
    _, params = build()  # the true weights again, for the reference

    return {how: [{"prompt_tokens": r["prompt_tokens"],
                   "within": driver.within(check, r), **check,
                   **({"spread": spread} if spread else {})}
                  for r, prompt, (got, have, spread)
                  in zip(refs, prompts, answered)
                  for check in [driver.compare(
                      ref, config, model, params, prompt, got, have)]]
            for how, answered in found.items()}


def by_layer(cell, config, traffic, seed) -> dict:
    """{"as_it_is" | "float32_highest": {part: [a relative error a layer
    that has the part]}} of the program's plain forward over the longest
    comparison's prompt, the reference given the program's choices."""
    import jax
    import jax.numpy as jnp

    from benchmark import common
    from benchmark.drivers import serve_decoder
    from benchmark.drivers import serve_linear_moe as driver
    from ray_tpu.serve.llm_engine import build_model

    ref = common.load_module("reference", cell["config"])
    rows = serve_decoder.comparisons(traffic["reference"])[-1]["prompt_tokens"]
    ids = jnp.asarray([serve_decoder.reference_prompt(
        rows, seed, config["vocab_size"])], jnp.int32)

    def errors(**over):
        kw = {**serve_decoder.model_kw(config), **over}
        model, params = build_model(config["serve"]["model_kind"], kw,
                                    common.jax_seed(seed))
        parts, plain = driver.program_parts(model, params, ids)
        _, want, _, _ = ref.forward_with_parts(
            params, ids, config, first_row=rows - 1, given=plain)
        return {p: [round(float(
            jnp.linalg.norm(have.astype(jnp.float32) - to)
            / jnp.linalg.norm(to)), 5) for have, to in zip(parts[p], want[p])]
            for p in driver.PARTS}

    found = {"as_it_is": errors()}
    with jax.default_matmul_precision("highest"):
        found["float32_highest"] = errors(dtype="float32")
    return found


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", nargs="?", default="ling3f_serve_reason")
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[3000000011, 2500000001])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--by-layer", action="store_true")
    args = ap.parse_args()
    from benchmark import run
    from benchmark.rehearsal import rehearse
    from ray_tpu._private.jax_env import ensure_compile_cache

    ensure_compile_cache()
    overrides = rehearse.tiny_overrides(args.cell) if args.tiny else None
    _, cell, config, traffic = run.load_cell(args.cell, overrides)
    for seed in args.seeds if args.by_layer else ():
        print("DEPTH " + json.dumps({
            "cell": args.cell, "seed": seed,
            **by_layer(cell, config, traffic, seed)}), flush=True)
    for seed in () if args.by_layer else args.seeds:
        print("PROBE " + json.dumps({
            "cell": args.cell, "seed": seed,
            **probe(cell, config, traffic, seed, args.faults)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
