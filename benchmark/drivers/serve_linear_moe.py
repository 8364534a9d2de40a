"""Driver ``serve_linear_moe``: ``serve_hybrid_moe``'s binding, traffic, play
and record for a decoder whose mixers are gated delta-rule layers (KDA) with
one latent-attention layer (MLA) a group, whose first layer is dense and
whose other layers hold a share of group-routed SwiGLU experts
(``ray_tpu/models/ling_linear.py``).

Shared with ``serve_hybrid_moe``: its server (the programs compiled side by
side, ``record_experts`` on, the ``StallWatch``), ``fed_rows``, and the
shape of the comparison that decides ``correct``, for the reasons that
module gives: one greedy answer through prefill, the page pool of latent
rows, the recurrent state and the held experts (the programs the window
then times), and the program's plain forward over the same rows, against
the reference's full forward given the same share of the experts AND THE
PROGRAM'S OWN CHOICES among them.  With 8 of 512 inside 4 of 8 groups and
seeded weights the near-ties are of two kinds, the eighth and ninth score
inside the kept groups and the fourth and fifth group, and bfloat16
activations decide some of either kind the other way than a float32
reference; ``reference/ling3_flash_vl.py::route`` gives the slack of both.

What is this driver's own: the parts it names.  (a) the cached path's two
errors (``logprob_max_err``, ``argmax_margin_max``); (b) the plain forward
part by part, what the KDA mixers, the MLA mixer, the dense layer, the
routed experts and the shared expert each add to the residual stream, the
largest relative error over the layers that have that part
(``branch_rel_err``, each against its own ``branch_rel_err_max``); (c) the
choices, both paths' (``choice_slack``, ``choice_overlap``).  Twice a run:
the traffic file's ``reference`` and its ``reference.long``.

A program that cannot build the model (the parent of the PR that brought
the configuration) raises in the replica's constructor and the run ends
non-zero within seconds.
"""
from __future__ import annotations

import contextlib

from benchmark import common
from benchmark.drivers import serve_decoder, serve_hybrid_moe, serve_lm
from benchmark.drivers.serve_hybrid_moe import StallWatch, fed_rows
from benchmark.drivers.serve_lm import warm_prompts

PARTS = ("kda", "mla", "dense", "routed", "shared")
ENGINE_KEYS = serve_hybrid_moe.ENGINE_KEYS + ("kv_bytes_per_token",
                                              "state_slots_moved")


def within(check: dict, limits: dict) -> bool:
    """Every token answered; the cached path's two errors, every part's
    relative error, the choices' slack and their overlap inside the
    comparison's limits."""
    return (check["tokens"] == limits["new_tokens"]
            and check["logprob_max_err"] <= limits["logprob_tolerance"]
            and check["argmax_margin_max"] <= limits["logprob_tolerance"]
            and all(check["branch_rel_err"][p]
                    <= limits["branch_rel_err_max"][p] for p in PARTS)
            and check["choice_slack"] <= limits["choice_slack_max"]
            and check["choice_overlap"] >= limits["choice_overlap_min"])


def program_forward(model, params, ids):
    """What the program's own plain forward over ``ids`` (no cache) sows:
    ``branches``, ``moe`` and, under ``intermediates``, the KDA layers'
    gate projections (``spread`` reads them)."""
    import jax
    import jax.numpy as jnp

    last = jnp.full((ids.shape[0],), ids.shape[1] - 1, jnp.int32)
    return jax.jit(lambda p, i: model.apply(
        {"params": p}, i, logits_at=last,
        mutable=["branches", "moe", "intermediates"],
        capture_intermediates=lambda mdl, _: mdl.name in (
            "f_proj", "b_proj")))(params, ids)[1]


def program_parts(model, params, ids, sown=None):
    """({part: [layers that have that part, B, S, d]}: what each part adds
    to the residual stream in the program's plain forward, the chosen
    experts [expert layers, B, S, k]); on the host where ``sown`` lies
    there (``jax.device_get``)."""
    import jax.numpy as jnp
    import numpy as np

    sown = sown or program_forward(model, params, ids)
    parts = {name: [] for name in PARTS}
    chosen = []
    for i in range(model.config.num_hidden_layers):
        layer = sown["branches"][f"layer_{i}"]
        for name in ("kda", "mla", "dense"):
            if name + "_out" in layer:
                parts[name].append(layer[name + "_out"][0])
        if "moe" in layer:
            parts["routed"].append(layer["moe"]["routed_out"][0])
            parts["shared"].append(layer["moe"]["shared_out"][0])
            chosen.append(sown["moe"][f"layer_{i}"]["moe"]["expert_idx"][0])
    stack = np.stack if isinstance(chosen[0], np.ndarray) else jnp.stack
    return ({k: stack(v) for k, v in parts.items()}, stack(chosen))


def compare(ref, config, model, params, prompt, got, have=None) -> dict:
    """(a), (b) and (c) of the module's docstring
    (``serve_hybrid_moe.compare`` with this family's parts).  ``got``: the
    engine's rollout with ``experts``.  ``have``: the plain forward's parts
    and choices over ``fed_rows`` where the caller took them earlier.  The
    reference hands over its parts layer by layer and keeps none: at 2,000
    rows all layers' parts of both sides, held at once beside the engine,
    were 2 GB and set the run's ``memory_peak_bytes`` (PERF.md, PR 47)."""
    import jax
    import jax.numpy as jnp

    ids = fed_rows(prompt, got)
    rows = ids.shape[1]
    f32 = jnp.float32
    # (a): [rows, expert layers, k] as the engine gives them -> [expert
    # layers, 1, rows, k]
    cached = jnp.moveaxis(jnp.asarray(got["experts"]), 0, 1)[:, None]
    logits, _, own, cached_slack = ref.forward_with_parts(
        params, ids, config, first_row=len(prompt) - 1, given=cached,
        each=lambda i, added: None)
    cached_overlap = ref.choice_overlap(cached, own)
    logits = logits[0]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    # (b): a layer's parts against the program's as the reference makes
    # them; the largest over the layers that have the part
    parts, plain = have or program_parts(model, params, ids)
    plain = jnp.asarray(plain)
    embedded = float(jnp.linalg.norm(
        params["embed"]["embedding"][ids].astype(f32)))
    seen = {p: 0 for p in PARTS}
    worst, share = dict.fromkeys(PARTS, 0.0), {}

    def each(_, added):
        for name, want in added.items():
            mine = jnp.asarray(parts[name][seen[name]]).astype(f32)
            seen[name] += 1
            size = float(jnp.linalg.norm(want))
            worst[name] = max(worst[name],
                              float(jnp.linalg.norm(mine - want)) / size)
            share.setdefault(name, size / embedded)

    _, _, own, plain_slack = ref.forward_with_parts(
        params, ids, config, first_row=rows - 1, given=plain, each=each)
    return {"tokens": len(got["tokens"]),
            "logprob_max_err": float(jnp.max(jnp.abs(
                ref_lp - jnp.asarray(got["logprobs"])))),
            "argmax_margin_max": float(jnp.max(margin)),
            "logit_sigma": float(jnp.mean(jnp.std(logits, axis=-1))),
            "branch_rel_err": worst,
            "choice_slack": max(cached_slack, plain_slack),
            "choice_overlap": min(cached_overlap,
                                  ref.choice_overlap(plain, own)),
            # records, no limit: the share of (layer, row) pairs in which
            # the two paths of the program chose the same experts, and each
            # part's first addition beside the embedding
            "paths_choose_alike": float(jnp.mean(jnp.all(
                jnp.sort(cached, -1) == jnp.sort(plain, -1), axis=-1))),
            "branch_share_of_residual": share}


def spread(model, params, sown) -> dict:
    """A record, no limit, of what the seeded gates and routers did in the
    plain forward that ``sown`` comes from (``program_forward``): the
    quantiles of the KDA layers' per-channel decay ``exp(g)`` and of
    ``beta`` (no state frozen, none wiped), and how the choices load all
    the router's experts (no router collapsed: the busiest expert's share
    of the choices, the share of experts never chosen, and the share of
    choices that land on the held ones)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.kda import kda_gate

    c = model.config
    alphas, betas = [], []
    for name, layer in sown["intermediates"].items():
        kda, p = layer["kda"], params[name]["kda"]
        f = kda["f_proj"]["__call__"][0].astype(jnp.float32)
        g = kda_gate(f.reshape(f.shape[:2] + (c.num_attention_heads, -1)),
                     p["A_log"], p["dt_bias"], c.kda_lower_bound)
        alphas.append(np.asarray(jnp.exp(g)).ravel())
        betas.append(np.asarray(jax.nn.sigmoid(
            kda["b_proj"]["__call__"][0].astype(jnp.float32))).ravel())
    chosen = np.concatenate([np.asarray(v["moe"]["expert_idx"][0]).ravel()
                             for v in sown["moe"].values()])
    load = np.bincount(chosen, minlength=c.num_experts)
    held = load[c.expert_offset:c.expert_offset + c.experts_held]
    q = (0.01, 0.1, 0.5, 0.9, 0.99)
    alpha, beta = np.concatenate(alphas), np.concatenate(betas)
    quantiles = lambda a: dict(zip(  # noqa: E731
        map(str, q), np.quantile(a, q).round(4).tolist()))
    return {"alpha_quantiles": quantiles(alpha),
            "alpha_below_0.5_share": float(np.mean(alpha < 0.5)),
            "alpha_above_0.999_share": float(np.mean(alpha > 0.999)),
            "beta_quantiles": quantiles(beta),
            "busiest_expert_share": float(load.max() / load.sum()),
            "even_share": 1.0 / c.num_experts,
            "experts_never_chosen_share": float(np.mean(load == 0)),
            "held_choice_share": float(held.sum() / load.sum())}


class BenchLinearMoEServer(serve_hybrid_moe.BenchHybridMoEServer):
    def reference_check(self, config_name, config, prompt, new_tokens):
        import jax

        eng = self.engine
        got = eng.rollout(eng.submit(prompt, new_tokens,
                                     record_experts=True), timeout=600.0)
        ids = fed_rows(prompt, got)
        # to the host at once: the device keeps the engine's arrays alone
        sown = jax.device_get(program_forward(eng._model, eng._params, ids))
        found = compare(
            common.load_module("reference", config_name), config, eng._model,
            eng._params, prompt, got,
            have=program_parts(eng._model, eng._params, ids, sown))
        found["spread"] = spread(eng._model, eng._params, sown)
        return found


@contextlib.contextmanager
def session(cell, config, traffic, seed, allow_cpu=False):
    """``serve_hybrid_moe.session`` with this driver's server, parts and
    engine keys."""
    s = config["serve"]
    with serve_lm.deployed(BenchLinearMoEServer,
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
            # the device's two books as set-up left them: where the run's
            # end reads no more than this, set-up (the comparison) and not
            # the window set ``memory_peak_bytes``
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
