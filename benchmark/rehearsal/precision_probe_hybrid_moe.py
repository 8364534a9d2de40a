#!/usr/bin/env python3
"""Does a ``serve_hybrid_moe`` cell's ``correct`` notice a lower precision,
and a fault of the cached path?  ``precision_probe_hybrid.py``'s control
for ``serve_hybrid_moe.compare``: the driver's own comparisons with the
plain reference (the cached path's log-probabilities given its choices,
the plain forward part by part given its own, the choices' slack and
overlap), in the process that holds the chip, on the program as it is and
on the program with its weights rounded to 8 bits
(``precision_probe_decoder.round_to_8_bits``), seed by seed, once for every
comparison the traffic file asks for.  The reference keeps the true weights
each time.  Each limit is set from what this prints: over the first line's
readings on every seed, under the second's.

``--faults``: the program as it is but for one fault put into the cached
path (``Faulty``: the engine's prefill leaves a slot's state with another
sequence's on top, lets the bucket's padding advance it, or writes one
page's rows where another's belong), to see that the cached path's limit
is one such a fault does not pass.

A model that fills the chip cannot be held twice, so the weights are rounded
in place, the rounded program answers (its plain forward too), and the true
weights are then made again from the seed for the reference.  The engine
here has two slots: the pools are small, the programs are the cell's.

    python3 benchmark/rehearsal/precision_probe_hybrid_moe.py [--tiny] \
        [--faults] [--seeds 3000000011 2500000001] [cell]
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


FAULTS = ("stale_state", "padding_advances", "page_misread")


class Faulty:
    """The model, with one fault in what its prefill hands the engine (the
    plain forward, which takes no cache, is the sound one):

    - ``stale_state``: the state a prompt leaves behind carries another
      sequence's on top, as a slot not reset on admission would (here its
      own, the heads rolled by one: the right size, the wrong content);
    - ``padding_advances``: the bucket's padding past the prompt advances
      the state and the convolution's rows;
    - ``page_misread``: the second page's place gets the first page's K/V
      rows (a page table off by one for that page).  No layer embeds a
      position, so pages in another *order* change nothing: the fault is
      16 rows lost and 16 counted twice.
    """

    def __init__(self, model, fault: str, page_size: int):
        self._model, self._fault, self._page = model, fault, page_size

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, variables, ids, positions=None, kv_caches=None, **kw):
        import jax.numpy as jnp

        prefill = kv_caches is not None and "state" not in kw
        if prefill and self._fault == "padding_advances":
            kw["lengths"] = jnp.full_like(kw["lengths"], ids.shape[1])
        out = self._model.apply(variables, ids, positions, kv_caches, **kw)
        if not prefill or self._fault == "padding_advances":
            return out
        (logits, new_kvs, left), *sown = out if kw.get("mutable") else (out,)
        if self._fault == "stale_state":
            left = [{**one, "ssm": one["ssm"] + jnp.roll(one["ssm"], 1, 1)}
                    for one in left]
        else:
            ps = self._page
            new_kvs = [tuple(rows.at[:, ps:2 * ps].set(rows[:, :ps])
                             for rows in kv) for kv in new_kvs]
        return ((logits, new_kvs, left), *sown) if sown else (
            logits, new_kvs, left)


def probe(cell, config, traffic, seed, faults=False):
    """{"as_it_is": [one check per comparison], "8bit": [...], and with
    ``faults`` one list a fault of ``FAULTS``}."""
    from benchmark import common
    from benchmark.drivers import serve_decoder, serve_hybrid_moe
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

    def answers(params, fault=None):
        """Per comparison: the engine's greedy answer with its rows'
        chosen experts, and the parts and choices of the program's own
        plain forward over the same rows."""
        out = []
        with engine(params, fault) as eng:
            for r, prompt in zip(refs, prompts):
                got = eng.rollout(eng.submit(prompt, r["new_tokens"],
                                             record_experts=True),
                                  timeout=900.0)
                out.append((got, serve_hybrid_moe.program_parts(
                    model, params,
                    serve_hybrid_moe.fed_rows(prompt, got))))
        return out

    model, params = build()
    found = {"as_it_is": answers(params)}
    for fault in FAULTS if faults else ():
        found[fault] = answers(params, fault)
    params = round_to_8_bits(params)
    found["8bit"] = answers(params)
    params = None
    gc.collect()  # the rounded weights go before the true ones come back
    _, params = build()  # the true weights again, for the reference

    return {how: [{"prompt_tokens": r["prompt_tokens"],
                   "within": serve_hybrid_moe.within(check, r), **check}
                  for r, prompt, (got, have) in zip(refs, prompts, answered)
                  for check in [serve_hybrid_moe.compare(
                      ref, config, model, params, prompt, got, have)]]
            for how, answered in found.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", nargs="?", default="nemotron3s_serve_steady")
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
            **probe(cell, config, traffic, seed, args.faults)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
