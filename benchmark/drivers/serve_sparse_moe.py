"""Driver ``serve_sparse_moe``: ``serve_hybrid_moe``'s binding, traffic, play
and record for a decoder whose every layer is latent attention under a
learned selection of the rows it attends to (an indexer's top-k: DeepSeek
sparse attention), whose first layer is dense and whose other layers hold a
share of a sigmoid router's SwiGLU experts (``ray_tpu/models/glm_dsa.py``).

Shared with ``serve_hybrid_moe``: its server (the programs compiled side by
side, ``record_experts`` on, the ``StallWatch``), ``fed_rows``, and the
shape of the comparison that decides ``correct``: one greedy answer through
prefill, the page pool of latent rows and index keys and the held experts
(the programs the window then times), and the program's plain forward over
the same rows, against the reference's full forward given the same share of
the experts AND THE PROGRAM'S OWN CHOICES: of experts, for
``reference/nemotron3_super_120b.py``'s reason, and of rows.  A row ranked
2,048th and one ranked 2,049th by the indexer lie a rounding apart, and
bfloat16 index keys decide some such pairs the other way than a float32
reference; each swaps one of 2,048 rows of a softmax.  So the selection is
compared with the reference's own as a selection (``selection_agreement``),
the scores behind it as scores (``index_score_err``), and the logits GIVEN
the program's selection.

What is this driver's own: the parts it names.
(a) the cached path's two errors (``logprob_max_err``,
    ``argmax_margin_max``): the engine's log-probability of each token it
    chose against the reference's, given the cached path's expert choices
    (its rollout's ``experts``) and its selections: on the rows its decode
    steps fed, the positions those steps selected (its rollout's
    ``selected``: the ``dsa_index`` kernel, the sort and the gather by
    row), on the prompt's rows those the program's plain forward selected
    over the same context (the prefill form);
    and ``decode_selection_agreement``: the share of the reference's own
    ``S_t``, on the rows the decode steps fed and given the cached path's
    choices of experts, that those steps selected too, the least over the
    layers, held to ``selection_agreement_min`` (1.0 where the context is
    no longer than ``index_topk``: every step must have selected every
    cached row and its own);
(b) the selection, of the program's plain forward against the reference
    GIVEN THAT FORWARD'S OWN choices of experts (the two paths of the
    program decide some near-ties among experts differently, and a row
    whose experts differ has another index key in every later layer):
    ``selection_agreement``, the share of the reference's own
    ``S_t`` that the program selected too, over the rows from
    ``index_topk`` on, the least over the layers (1.0 where no row
    selects: a context of ``index_topk`` rows or fewer, where the
    comparison says that the sparse path equals plain MLA), and
    ``index_score_err``, the largest error of the program's index scores of
    the context's last rows in units of the reference's scores' own spread
    over those rows;
(c) the experts: ``choice_slack`` and ``choice_overlap`` as
    ``serve_hybrid_moe``, both paths';
(d) the plain forward part by part (``branch_rel_err``, each against its
    own ``branch_rel_err_max``), given the plain forward's own choices, in
    the comparison whose limits name the parts: the short one (all layers'
    parts of a 9,000-row context, both sides', are more than fits beside
    the engine).
Beside them as records, no limit: how the seeded
indexer spreads a selection (``spread``: the share of a row's selected rows
among the newest ``index_topk``, and the pages they touch: random weights
select without locality, the worst case for a gather); and, where the
caller asks (``own``: the precision probe, not a run's set-up, which pays
for what decides ``correct`` and nothing else), the reference's logits left
to its OWN selection and choices (``own_choice_logprob_err``).

Twice a run: the traffic file's ``reference`` and its ``reference.long``.
A program that cannot build the model (the parent of the PR that brought
the configuration) raises in the replica's constructor and the run ends
non-zero within seconds.
"""
from __future__ import annotations

import contextlib
import json

import numpy as np

from benchmark import common
from benchmark.drivers import serve_decoder, serve_hybrid_moe, serve_lm
from benchmark.drivers.serve_hybrid_moe import StallWatch, fed_rows
from benchmark.drivers.serve_lm import warm_prompts

PARTS = ("attn", "dense", "routed", "shared")
ENGINE_KEYS = serve_hybrid_moe.ENGINE_KEYS + (
    "kv_bytes_per_token", "dsa_rows_scored", "dsa_rows_read",
    "dsa_selected_share")


def within(check: dict, limits: dict) -> bool:
    """Every token answered; the cached path's two errors, the selection's
    agreement (the plain forward's and the decode steps') and its scores'
    error, the choices' slack and overlap and,
    where the comparison names parts, every part's relative error inside
    the comparison's limits."""
    return (check["tokens"] == limits["new_tokens"]
            and check["logprob_max_err"] <= limits["logprob_tolerance"]
            and check["argmax_margin_max"] <= limits["logprob_tolerance"]
            and check["selection_agreement"]
            >= limits["selection_agreement_min"]
            and check["decode_selection_agreement"]
            >= limits["selection_agreement_min"]
            and check["index_score_err"] <= limits["index_score_err_max"]
            and check["choice_slack"] <= limits["choice_slack_max"]
            and check["choice_overlap"] >= limits["choice_overlap_min"]
            and all(check["branch_rel_err"][p] <= most for p, most in
                    limits.get("branch_rel_err_max", {}).items()))


def program_forward(model, params, ids, parts: bool):
    """What the program's own plain forward over ``ids`` (no cache) sows:
    ``dsa`` (a context longer than ``index_topk``: each layer's selection
    and its last rows' index scores), ``moe`` and, where ``parts``,
    ``branches``."""
    import jax
    import jax.numpy as jnp

    last = jnp.full((ids.shape[0],), ids.shape[1] - 1, jnp.int32)
    sown = ["dsa", "moe"] + (["branches"] if parts else [])
    return jax.jit(lambda p, i: model.apply(
        {"params": p}, i, logits_at=last, mutable=sown))(params, ids)[1]


def program_choices(model, sown):
    """From ``program_forward``: (the selection a layer, [B, S, S] bool or
    None where no row selects; the last rows' index scores a layer, or
    None; the chosen experts [expert layers, B, S, k]; {part: [layers that
    have it, B, S, d]} or None), on the host where ``sown`` lies there."""
    selected, scores, chosen = [], [], []
    parts = {name: [] for name in PARTS} if "branches" in sown else None
    for i in range(model.config.num_hidden_layers):
        name = f"layer_{i}"
        dsa = sown.get("dsa", {}).get(name, {}).get("attn", {})
        selected.append(dsa["selection"][0] if dsa else None)
        scores.append(dsa["last_scores"][0] if dsa else None)
        if "moe" in sown["moe"].get(name, {}):
            chosen.append(sown["moe"][name]["moe"]["expert_idx"][0])
        if parts is not None:
            layer = sown["branches"][name]
            parts["attn"].append(layer["attn_out"][0])
            if "dense_out" in layer:
                parts["dense"].append(layer["dense_out"][0])
            else:
                parts["routed"].append(layer["moe"]["routed_out"][0])
                parts["shared"].append(layer["moe"]["shared_out"][0])
    return selected, scores, np.stack([np.asarray(c) for c in chosen]), parts


def spread(selected, topk: int, page: int) -> dict:
    """A record, no limit, of how the seeded indexer spreads its
    selection, over the context's last row of every selecting layer: the
    share of the selected rows that lie among the newest ``topk`` (1.0: a
    sliding window), and the pages of ``page`` rows they touch of those the
    context fills (1.0: every page is read for its few rows)."""
    recent, pages = [], []
    for mask in selected:
        if mask is None:
            continue
        row = np.asarray(mask)[0, -1]
        t = row.shape[0] - 1
        recent.append(float(row[max(t - topk + 1, 0):].sum() / row.sum()))
        pages.append(float(len(set(np.flatnonzero(row) // page))
                           / (t // page + 1)))
    if not recent:
        return {}
    return {"selected_among_newest_share": float(np.mean(recent)),
            "pages_touched_share": float(np.mean(pages))}


def with_decode_rows(selected, taken, first: int) -> list:
    """``selected`` (a layer: [1, S, S] bool, or None where no row selects)
    with row ``first + j`` of every selecting layer replaced by the
    positions decode step ``j`` selected (``taken`` [steps, layers, k],
    -1: none)."""
    out = []
    for i, mask in enumerate(selected):
        if mask is not None:
            mask = np.array(mask)
            for j, step in enumerate(taken):
                mask[0, first + j] = False
                mask[0, first + j, step[i][step[i] >= 0]] = True
        out.append(mask)
    return out


def decode_selection_agreement(scores, taken, topk: int) -> float:
    """scores [layers, 1, steps, S]: a reference's own index scores of the
    rows the decode steps fed (``-inf``: no candidate); ``taken`` [steps,
    layers, k]: the positions those steps selected.  The share of the
    reference's ``S_t`` (its ``topk`` best, ties to the lower row) that the
    step selected too, over the steps, the least over the layers; a step
    that selected more rows than ``S_t`` holds is held to its own count."""
    worst = 1.0
    for i, layer in enumerate(np.asarray(scores)):
        same = total = 0
        for j, step in enumerate(taken):
            row = layer[0, j]
            able = np.flatnonzero(np.isfinite(row))
            best = able[np.argsort(-row[able], kind="stable")][:topk]
            mine = step[i][step[i] >= 0]
            same += len(np.intersect1d(mine, best))
            total += max(len(best), len(mine))
        worst = min(worst, same / total if total else 1.0)
    return worst


def compare(ref, config, model, params, prompt, got, parts: bool,
            have=None, own: bool = False) -> dict:
    """(a) to (d) of the module's docstring.  ``got``: the engine's rollout
    with ``experts`` and ``selected``.  ``parts``: whether the comparison
    names parts (d).  ``have``: ``program_choices`` over ``fed_rows`` where
    the caller took them earlier (the precision probe, on weights it no
    longer holds).  ``own``: also the record ``own_choice_logprob_err``, a
    third forward of the reference."""
    import jax
    import jax.numpy as jnp

    ids = fed_rows(prompt, got)
    rows = ids.shape[1]
    f32 = jnp.float32
    if have is None:
        have = program_choices(model, jax.device_get(
            program_forward(model, params, ids, parts)))
    selected, scores, plain, mine = have
    plain = jnp.asarray(plain)
    kept = next((s.shape[1] for s in scores if s is not None), 1)
    # (a): [rows, expert layers, k] as the engine gives them -> [expert
    # layers, 1, rows, k]
    cached = jnp.moveaxis(jnp.asarray(got["experts"]), 0, 1)[:, None]
    taken = np.asarray(got["selected"])  # [decode steps, layers, k]
    logits, _, theirs, slack, chose = ref.forward_with_parts(
        params, ids, config, first_row=len(prompt) - 1, given=cached,
        selected=with_decode_rows(selected, taken, len(prompt)),
        each=lambda i, added: None, rows_kept=max(len(taken), 1))
    overlap = ref.choice_overlap(cached, theirs)
    decode_agreement = decode_selection_agreement(
        chose["scores"], taken, int(config["index_topk"])) \
        if len(taken) else 1.0
    logits = logits[0]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    # (b) and (d): the reference on the plain forward's own residual
    # stream, that is given ITS choices of experts (the two paths decide
    # some near-ties differently, and a row whose experts differ has
    # another index key in every later layer): a layer's parts against the
    # program's as the reference makes them, its own selection against the
    # program's, its index scores against the program's
    embedded = float(jnp.linalg.norm(
        params["embed"]["embedding"][ids].astype(f32)))
    seen = {p: 0 for p in PARTS}
    worst, share = dict.fromkeys(PARTS, 0.0), {}

    def each(_, added):
        for name, want in added.items() if parts else ():
            got_part = jnp.asarray(mine[name][seen[name]]).astype(f32)
            seen[name] += 1
            size = float(jnp.linalg.norm(want))
            worst[name] = max(worst[name], float(
                jnp.linalg.norm(got_part - want)) / size)
            share.setdefault(name, size / embedded)

    _, _, theirs, plain_slack, chose = ref.forward_with_parts(
        params, ids, config, first_row=rows - 1, given=plain,
        selected=selected, each=each, rows_kept=kept)
    binding = [i for i, s in enumerate(selected) if s is not None]
    agreement = min((chose["agreement"][i] for i in binding), default=1.0)
    score_err = 0.0
    for i in binding:
        want = np.asarray(chose["scores"][i][0])
        finite = np.isfinite(want)
        mine_i = np.asarray(scores[i][0], np.float32)
        score_err = max(score_err, float(
            np.abs(mine_i[finite] - want[finite]).max()
            / want[finite].std()))
    check = {"tokens": len(got["tokens"]),
             "logprob_max_err": float(jnp.max(jnp.abs(
                 ref_lp - jnp.asarray(got["logprobs"])))),
             "argmax_margin_max": float(jnp.max(margin)),
             "logit_sigma": float(jnp.mean(jnp.std(logits, axis=-1))),
             "selection_agreement": float(agreement),
             "decode_selection_agreement": float(decode_agreement),
             "decode_steps": len(taken),
             "index_score_err": score_err,
             "choice_slack": max(float(slack), float(plain_slack)),
             "choice_overlap": min(overlap,
                                   ref.choice_overlap(plain, theirs)),
             "rows": rows, "selecting_layers": len(binding),
             # records, no limit
             "paths_choose_alike": float(jnp.mean(jnp.all(
                 jnp.sort(cached, -1) == jnp.sort(plain, -1), axis=-1))),
             "spread": spread(selected, int(config["index_topk"]),
                              int(config["serve"]["page_size"]))}
    if parts:
        check.update(branch_rel_err=worst, branch_share_of_residual=share)
    if own:  # the reference left to its own selection and its own choices
        own_lp = jnp.take_along_axis(jax.nn.log_softmax(ref.forward(
            params, ids, config, first_row=len(prompt) - 1)[0], -1), chosen,
            -1)[:, 0]
        check["own_choice_logprob_err"] = float(jnp.max(jnp.abs(
            own_lp - jnp.asarray(got["logprobs"]))))
    return check


class BenchSparseMoEServer(serve_hybrid_moe.BenchHybridMoEServer):
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
    with serve_lm.deployed(BenchSparseMoEServer,
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
