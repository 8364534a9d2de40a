"""Driver ``serve_eva``: ``serve_decoder``'s binding and traffic for a decoder
whose every layer is EVA attention (an exact window of rows beside one pooled
row for every chunk before it) and whose cache's rows are therefore not its
tokens (``ray_tpu/models/eva_decoder.py``).

Shared: ``serve_lm``'s ``deployed``, ``play`` and ``record``;
``serve_decoder``'s binding (``model_kw``), ``comparisons`` and
``reference_prompt``; ``serve_hybrid``'s server, which compiles the decode
program and the prefill buckets side by side; ``serve_hybrid_moe``'s
``StallWatch`` and ``fed_rows``.  None of the serve drivers plays this family
as it is: every one of them that names a layer's parts names another
family's (a mixer, routed experts, a selection), and ``serve_decoder``
compares no part at all.

What is this driver's own: the comparison that decides ``correct``, made for
every comparison the traffic file asks for (its ``reference``, then its
``reference.long``):
(a) the cached path (``logprob_max_err``, ``argmax_margin_max``): one greedy
    answer through the prefill (which hands the cache the open window's rows
    and every whole chunk's summary, and nothing else), the ring, the
    summary pages and the decode steps' composed read (the programs the
    window then times), against the next-byte head of the reference's one
    full forward over prompt + answer.  In the long comparison the answer
    crosses a window's end: its steps close a chunk, then the window, and
    from there read one summary that a decode step pooled and a ring that is
    being overwritten from its first column;
(b) the parts (``branch_rel_err``): the program's plain forward over the same
    rows, what the attention and the feed-forward each add to the residual
    stream, layer by layer, against the reference's, over the context's
    last ``PART_ROWS`` rows: the largest relative error over the layers.
    The reference hands its parts over a layer at a time, so a context of
    6,000 rows fits beside the engine.

A program that cannot build the model (the parent of the PR that brought the
configuration) raises in the replica's constructor and the run ends non-zero
within seconds.
"""
from __future__ import annotations

import contextlib
import json

from benchmark import common
from benchmark.drivers import serve_decoder, serve_hybrid, serve_lm
from benchmark.drivers.serve_hybrid_moe import StallWatch, fed_rows
from benchmark.drivers.serve_lm import warm_prompts

PARTS = ("attn", "mlp")
# The parts are compared over a context's last rows: the open window and
# eight rows more.  A short context has fewer and is compared whole; a long
# one's last window is the one that sees the earlier windows' summaries, and
# all 16 parts of 6,000 rows would stand 0.8 GB high on the device beside an
# engine that fills it (PERF.md section 4, PR 61).
PART_ROWS = 2048 + 8
ENGINE_KEYS = ("kv_bytes_per_token", "kv_pages_per_slot", "kv_rows_per_slot",
               "kv_positions_per_slot", "cache_ctx_tokens", "cache_rows_read",
               "cache_rows_share", "cache_chunks_closed",
               "cache_windows_closed")


def within(check: dict, limits: dict) -> bool:
    """Every token answered; the cached path's two errors and every part's
    relative error inside the comparison's limits."""
    return (check["tokens"] == limits["new_tokens"]
            and check["logprob_max_err"] <= limits["logprob_tolerance"]
            and check["argmax_margin_max"] <= limits["logprob_tolerance"]
            and all(check["branch_rel_err"][p] <= most for p, most in
                    limits["branch_rel_err_max"].items()))


def program_parts(model, params, ids):
    """The program's own plain forward over ``ids``, no cache: {part:
    [layers of [last PART_ROWS rows, d]]}, what each part adds to the
    residual stream (a batch of one), on the host."""
    import jax

    def tail_parts(p, i):
        _, sown = model.apply({"params": p}, i, mutable=["branches"])
        layers = [sown["branches"][f"layer_{n}"]
                  for n in range(model.config.num_hidden_layers)]
        return {"attn": [x["attn_out"][0][0, -PART_ROWS:] for x in layers],
                "mlp": [x["mlp_out"][0][0, -PART_ROWS:] for x in layers]}

    return jax.device_get(jax.jit(tail_parts)(params, ids))


def compare(ref, config, model, params, prompt, got, have=None) -> dict:
    """(a) and (b) of the module's docstring.  ``got``: the engine's
    rollout.  ``have``: ``program_parts`` over ``fed_rows`` where the caller
    took them earlier (the precision probe, on weights it no longer
    holds)."""
    import jax
    import jax.numpy as jnp

    ids = fed_rows(prompt, got)
    f32 = jnp.float32
    mine = have or program_parts(model, params, ids)
    worst, share = dict.fromkeys(PARTS, 0.0), {}
    embedded = float(jnp.linalg.norm(
        params["embed"]["embedding"][ids].astype(f32)))

    def each(i, added):
        for name, want in added.items():
            size = float(jnp.linalg.norm(want))
            want = want[0, -PART_ROWS:]
            worst[name] = max(worst[name], float(jnp.linalg.norm(
                jnp.asarray(mine[name][i]).astype(f32) - want)) / float(
                    jnp.linalg.norm(want)))
            share.setdefault(name, size / embedded)

    logits, _ = ref.forward_with_parts(params, ids, config,
                                       first_row=len(prompt) - 1, each=each)
    logits = logits[0, :, 0]  # head 0: the next byte's
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    return {"tokens": len(got["tokens"]),
            "logprob_max_err": float(jnp.max(jnp.abs(
                ref_lp - jnp.asarray(got["logprobs"])))),
            "argmax_margin_max": float(jnp.max(margin)),
            "logit_sigma": float(jnp.mean(jnp.std(logits, axis=-1))),
            "branch_rel_err": worst, "rows": int(ids.shape[1]),
            # a record, no limit: each part's first addition beside the
            # embedding it is added to
            "branch_share_of_residual": share}


class BenchEvaServer(serve_hybrid.BenchHybridServer):
    def watch(self, on: bool):
        """Starts a ``StallWatch`` on the engine, or stops it and returns
        its report."""
        if on:
            self._watch = StallWatch(self.engine)
            return self._watch.start()
        return self._watch.report()

    def reference_check(self, config_name, config, prompt, new_tokens):
        eng = self.engine
        got = eng.rollout(eng.submit(prompt, new_tokens), timeout=900.0)
        check = compare(common.load_module("reference", config_name), config,
                        eng._model, eng._params, prompt, got)
        print("[bench] compared:", json.dumps(check), flush=True)
        return check


@contextlib.contextmanager
def session(cell, config, traffic, seed, allow_cpu=False):
    """``serve_hybrid_moe.session`` with this driver's server, comparison
    and engine keys."""
    s = config["serve"]
    with serve_lm.deployed(BenchEvaServer,
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
            before = call("facts")["memory_stats"]
            here = StallWatch()
            here.start()
            call("watch", True)
            played = serve_lm.play(handle, call, traffic, seed, vocab,
                                   seconds, trace, engine_keys=ENGINE_KEYS)
            there, start = call("watch", False), played["window_start"]
            played["counters"]["memory_before_window"] = {
                k: before.get(k) for k in (
                    "bytes_in_use", "peak_bytes_in_use",
                    "peak_bytes_reserved")}
            played["counters"]["stalls"] = {
                name: [[round(at - start, 2), round(took, 2)]
                       for at, took in found]
                for name, found in (("loop", there["still"]),
                                    ("replica_late", there["late"]),
                                    ("driver_late", here.report()["late"]))}
            return serve_lm.record(played, call("facts"), first, refs[0],
                                   sound)

        yield window


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    with session(cell, config, traffic, seed, allow_cpu) as window:
        return window(traffic, seconds, trace)
