#!/usr/bin/env python3
"""Does a ``serve_sparse_moe`` cell's ``correct`` notice a lower precision,
and a decode step that selects the wrong rows?
``precision_probe_linear_moe.py``'s control for ``serve_sparse_moe.compare``:
the driver's own comparisons with the plain reference (the cached path's
log-probabilities given its choices of experts and of rows, the selection's
agreement and its scores' error, the choices' slack and overlap, the short
comparison's parts), in the process that holds the chip, on the program as
it is, on the program with a fault planted in its decode form's selection
(``planted``: true weights, the prefill as it is; the comparison over a
context longer than ``index_topk`` has to come out not ``within``, by
``decode_selection_agreement``), and on the program with its weights rounded
to 8 bits (``precision_probe_decoder.round_to_8_bits``), seed by seed, once
for every comparison the traffic file asks for.  The reference keeps the true
weights each time.  Each limit is set from what this prints: over the first
line's readings on every seed, under the last's.  Here too, as a record, the
reference left to its own selection and choices (``own_choice_logprob_err``:
a third forward that a run's set-up does not pay for).

A model that fills the chip cannot be held twice, so the weights are rounded
in place, the rounded program answers (its plain forward too), and the true
weights are then made again from the seed for the reference.  The engine
here has two slots: the pools are small, the programs are the cell's.

    python3 benchmark/rehearsal/precision_probe_sparse_moe.py [--tiny] \
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


def planted(model):
    """``model`` with its decode form selecting the wrong rows: the index
    queries zeroed, so that every cached row and the token's own score 0
    and the ties go to the OLDEST ``index_topk`` rows."""
    import jax.numpy as jnp

    from ray_tpu.ops.dsa import sparse_paged_attention

    def oldest_rows(q, k_new, v_new, *, index, **kw):
        q_idx, w_idx, k_idx = index
        return sparse_paged_attention(
            q, k_new, v_new, index=(jnp.zeros_like(q_idx), w_idx, k_idx),
            **kw)

    class Planted(type(model)):
        sparse_paged_attend = staticmethod(oldest_rows)

    return Planted(model.config)


def probe(cell, config, traffic, seed):
    """{"as_it_is": [one check per comparison], "wrong_decode_selection":
    [...], "8bit": [...]}."""
    import jax

    from benchmark import common
    from benchmark.drivers import serve_decoder
    from benchmark.drivers import serve_sparse_moe as driver
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
        """Per comparison: the engine's greedy answer with its rows'
        chosen experts and its decode steps' selections, and what the
        program's own plain forward over the same rows selected and chose
        (on the host)."""
        out = []
        eng = LLMEngine(model, params, max_slots=2, page_size=s["page_size"],
                        max_ctx=s["max_ctx"], chunk_tokens=1,
                        record_experts=True)
        try:
            for r, prompt in zip(refs, prompts):
                got = eng.rollout(eng.submit(prompt, r["new_tokens"],
                                             record_experts=True),
                                  timeout=1500.0)
                ids = driver.fed_rows(prompt, got)
                out.append((got, driver.program_choices(
                    model, jax.device_get(driver.program_forward(
                        model, params, ids, "branch_rel_err_max" in r)))))
        finally:
            eng.close()
            eng._params = None  # a closed engine may outlive its name
        return out

    model, params = build()
    found = {"as_it_is": answers(model, params),
             "wrong_decode_selection": answers(planted(model), params)}
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
                      "branch_rel_err_max" in r, have, own=True)]]
            for how, answered in found.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", nargs="?", default="glm5_serve_longdoc")
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
