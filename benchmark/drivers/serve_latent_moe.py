"""Driver ``serve_latent_moe``: ``serve_hybrid_moe``'s binding, traffic, play
and record for a decoder whose every layer is latent attention over the
WHOLE cache under a YaRN-scaled rope, whose cache is ONE latent row a token,
whose first layer is dense and whose other layers hold a share of a sigmoid
router's SwiGLU experts (``ray_tpu/models/latent_moe.py``).

Shared with ``serve_hybrid_moe``: its server (the programs compiled side by
side, ``record_experts`` on, the ``StallWatch``), ``fed_rows``, and the
shape of the comparison that decides ``correct``, for the reasons that
module gives: one greedy answer through prefill, the one pool of latent rows
and the held experts (the programs the window then times) against the
reference's full forward given the same share of the experts AND THE
PROGRAM'S OWN CHOICES among them.  None of the five other serve drivers
plays this family as it is: ``serve_linear_moe`` names Ling's parts (its
KDA layers' gates among them) and ``serve_sparse_moe`` compares a
selection of rows, which this family has not.

What is this driver's own: the parts it names.
(a) the cached path's two errors (``logprob_max_err``,
    ``argmax_margin_max``): the engine's log-probability of each token it
    chose against the reference's, given the cached path's expert choices
    (its rollout's ``experts``), and those choices' ``choice_slack`` and
    ``choice_overlap`` as ``serve_hybrid_moe``.  In the long comparison this
    is the 16,384 bucket (by blocks of heads, its padding writing no row and
    choosing no expert) and decode steps of the latent kernel over 12,000
    cached rows at positions past YaRN's original 4,096: a rope that is not
    YaRN's, or a softmax scale without ``mscale^2``, is another model there;
(b) where the comparison names parts (``branch_rel_err_max``: the short
    one; all layers' parts of a 12,000-row context, both sides', are more
    than fits beside an engine that holds 13.8 GB): the program's plain
    forward over the same rows part by part, what the attention, the dense
    layer, the routed experts and the shared expert each add to the
    residual stream, the largest relative error over the layers that have
    that part (``branch_rel_err``), given the plain forward's own choices,
    whose slack and overlap count too.

Twice a run: the traffic file's ``reference`` and its ``reference.long``.
A program that cannot build the model (the parent of the PR that brought
the configuration) raises in the replica's constructor and the run ends
non-zero within seconds.
"""
from __future__ import annotations

import contextlib
import json

from benchmark import common
from benchmark.drivers import serve_decoder, serve_hybrid_moe, serve_lm
from benchmark.drivers.serve_hybrid_moe import StallWatch, fed_rows
from benchmark.drivers.serve_lm import warm_prompts

PARTS = ("attn", "dense", "routed", "shared")
ENGINE_KEYS = serve_hybrid_moe.ENGINE_KEYS + ("kv_bytes_per_token",)


def within(check: dict, limits: dict) -> bool:
    """Every token answered; the cached path's two errors, the choices'
    slack and overlap and, where the comparison names parts, every part's
    relative error inside the comparison's limits."""
    return (check["tokens"] == limits["new_tokens"]
            and check["logprob_max_err"] <= limits["logprob_tolerance"]
            and check["argmax_margin_max"] <= limits["logprob_tolerance"]
            and check["choice_slack"] <= limits["choice_slack_max"]
            and check["choice_overlap"] >= limits["choice_overlap_min"]
            and all(check["branch_rel_err"][p] <= most for p, most in
                    limits.get("branch_rel_err_max", {}).items()))


def program_parts(model, params, ids):
    """The program's own plain forward over ``ids``, no cache: ({part:
    [layers that have that part] of [B, S, d]}: what each part adds to the
    residual stream, the chosen experts [expert layers, B, S, k])."""
    import jax
    import jax.numpy as jnp

    last = jnp.full((ids.shape[0],), ids.shape[1] - 1, jnp.int32)
    _, sown = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, logits_at=last,
        mutable=["branches", "moe"]))(params, ids)
    parts, chosen = {name: [] for name in PARTS}, []
    for i in range(model.config.num_hidden_layers):
        layer = sown["branches"][f"layer_{i}"]
        parts["attn"].append(layer["attn_out"][0])
        if "dense_out" in layer:
            parts["dense"].append(layer["dense_out"][0])
        else:
            parts["routed"].append(layer["moe"]["routed_out"][0])
            parts["shared"].append(layer["moe"]["shared_out"][0])
            chosen.append(sown["moe"][f"layer_{i}"]["moe"]["expert_idx"][0])
    return parts, jnp.stack(chosen)


def compare(ref, config, model, params, prompt, got, parts: bool,
            have=None) -> dict:
    """(a) and, where ``parts``, (b) of the module's docstring.  ``got``:
    the engine's rollout with ``experts``.  ``have``: ``program_parts``
    over ``fed_rows`` where the caller took them earlier (the precision
    probe, on weights it no longer holds)."""
    import jax
    import jax.numpy as jnp

    ids = fed_rows(prompt, got)
    rows = ids.shape[1]
    f32 = jnp.float32
    # (a): [rows, expert layers, k] as the engine gives them -> [expert
    # layers, 1, rows, k]
    cached = jnp.moveaxis(jnp.asarray(got["experts"]), 0, 1)[:, None]
    logits, _, own, slack = ref.forward_with_parts(
        params, ids, config, first_row=len(prompt) - 1, given=cached,
        each=ref.NOTHING)
    overlap = ref.choice_overlap(cached, own)
    logits = logits[0]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    check = {"tokens": len(got["tokens"]),
             "logprob_max_err": float(jnp.max(jnp.abs(
                 ref_lp - jnp.asarray(got["logprobs"])))),
             "argmax_margin_max": float(jnp.max(margin)),
             "logit_sigma": float(jnp.mean(jnp.std(logits, axis=-1))),
             "choice_slack": float(slack), "choice_overlap": overlap,
             "rows": rows}
    if not parts:
        return check
    # (b): a layer's parts against the program's as the reference makes
    # them, given the plain forward's own choices; the largest over the
    # layers that have the part
    mine, plain = have or program_parts(model, params, ids)
    plain = jnp.asarray(plain)
    embedded = float(jnp.linalg.norm(
        params["embed"]["embedding"][ids].astype(f32)))
    seen = {p: 0 for p in PARTS}
    worst, share = dict.fromkeys(PARTS, 0.0), {}

    def each(_, added):
        for name, want in added.items():
            got_part = jnp.asarray(mine[name][seen[name]]).astype(f32)
            seen[name] += 1
            size = float(jnp.linalg.norm(want))
            worst[name] = max(worst[name], float(
                jnp.linalg.norm(got_part - want)) / size)
            share.setdefault(name, size / embedded)

    _, _, own, plain_slack = ref.forward_with_parts(
        params, ids, config, first_row=rows - 1, given=plain, each=each)
    check.update(
        choice_slack=max(check["choice_slack"], float(plain_slack)),
        choice_overlap=min(overlap, ref.choice_overlap(plain, own)),
        branch_rel_err=worst,
        # records, no limit: the share of (layer, row) pairs in which the
        # two paths of the program chose the same experts, and each part's
        # first addition beside the embedding
        paths_choose_alike=float(jnp.mean(jnp.all(
            jnp.sort(cached, -1) == jnp.sort(plain, -1), axis=-1))),
        branch_share_of_residual=share)
    return check


class BenchLatentMoEServer(serve_hybrid_moe.BenchHybridMoEServer):
    def reference_check(self, config_name, config, prompt, new_tokens,
                        parts):
        eng = self.engine
        got = eng.rollout(eng.submit(prompt, new_tokens,
                                     record_experts=True), timeout=900.0)
        check = compare(common.load_module("reference", config_name), config,
                        eng._model, eng._params, prompt, got, parts)
        print("[bench] compared:", json.dumps(check), flush=True)
        return check


@contextlib.contextmanager
def session(cell, config, traffic, seed, allow_cpu=False):
    """``serve_hybrid_moe.session`` with this driver's server, parts and
    engine keys."""
    s = config["serve"]
    with serve_lm.deployed(BenchLatentMoEServer,
                           (s["model_kind"], serve_decoder.model_kw(config)),
                           config, seed, allow_cpu) as (handle, call):
        vocab = config["vocab_size"]
        call("warm", warm_prompts(traffic, vocab), 2)
        refs = serve_decoder.comparisons(traffic["reference"])
        found = [call("reference_check", cell["config"], config,
                      serve_decoder.reference_prompt(r["prompt_tokens"],
                                                     seed, vocab),
                      r["new_tokens"], "branch_rel_err_max" in r)
                 for r in refs]
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
