"""Driver ``serve_hybrid_moe``: ``serve_decoder``'s binding and traffic for a
decoder whose layers differ in kind (a state-space mixer, an attention or a
latent expert layer, by a pattern) and which holds a share of each layer's
experts.

Everything that plays and measures is shared: ``serve_decoder.model_kw``
(the configuration's ``serve.model_kind`` and ``serve.model_kw``, ``"$key"``
standing for a key of the file), ``serve_lm.deployed`` / ``play`` /
``record``, ``warm_prompts``, ``serve_hybrid.BenchHybridServer``'s warm-up
(the decode program and the prefill buckets compiled side by side).  What
differs is the comparison that decides ``correct``.  One greedy answer
through prefill, the page pool, the recurrent state and the held experts
(the programs the window then times), and the program's plain forward over
the same rows, against the reference's full forward given the same share
of the experts AND THE PROGRAM'S OWN CHOICES among them.

Why the choices are given.  With 22 of 512 and seeded weights the 22nd and
23rd of a token's scores lie closer than bfloat16 activations move them:
a program in bfloat16 decides some near-ties the other way than a float32
reference, each at a choice's whole weight (a fifth of the ~5.5 that land
here), and every later layer then routes on another residual.  Left to its
own choices the reference differs from a sound program by 10-50% part by
part, and weights rounded to 8 bits hide in that (PERF.md, PR 43).  So the
engine is built with ``record_experts`` and hands out, with the answer,
what each row's routers chose in the programs that computed it; the plain
forward sows the same.  The reference uses them in place of its own
(``forward_with_parts(given=)``), weighed by its own scores, and is then
what the program should have computed *having chosen so*.  That the
choices were ones to make is held apart, in (c).

(a) the cached path, given the cached path's choices: the error of each
    chosen token's log-probability and how far below the reference's best
    logit the chosen token's lies (``logprob_max_err``,
    ``argmax_margin_max``; no multiplier flattens this family's logits,
    so the limit is absolute, as ``serve_decoder``'s);
(b) the plain forward, given the plain forward's choices, part by part:
    what the mixers, the attention, the routed experts (after ``W_up`` and
    the scaling) and the shared expert each add to the residual stream,
    the largest relative error over the layers of that kind
    (``branch_rel_err``), each part against a limit of its own
    (``branch_rel_err_max``: {part: limit});
(c) the choices themselves, both paths': ``choice_slack``, how far the
    lowest-scored of a token's given choices lies, in the reference's
    score + bias on the residual the given choices led to, below the last
    place of the reference's own 22 (0 where they are the same 22; a
    near-tie decided the other way is a few thousandths; an expert that
    should not have been chosen is far more), against
    ``choice_slack_max``; and ``choice_overlap``, the mean share of a
    token's choices that are among the reference's own 22, the lower of
    the two paths', against ``choice_overlap_min``.

Twice a run, where the traffic file has a ``reference.long``.

**What stalled a run that enters its window behind.**  At 0.8 of the knee
a stall of two seconds shortly before the window moves ~2,000 tokens into
it (+4% of ``serve_tokens_per_s``), and nothing in the window says what
stalled: two of this cell's first twelve runs read so (PERF.md section 7,
PR 43).  So a ``StallWatch`` runs through pre-roll and window in the
replica and in the driver's process, and the record's ``counters.stalls``
names the longest stretches in which the engine's step count stood still
though slots were decoding (``loop``), and in which the watch itself woke
late in either process, held back with the whole interpreter or the whole
machine (``replica_late``, ``driver_late``): seconds from the window's
start (negative: the pre-roll) and seconds long.  A record, read by no
metric.

A program that cannot build the model (the parent of the PR that brought
the configuration) raises in the replica's constructor and the run ends
non-zero within seconds.
"""
from __future__ import annotations

import contextlib
import threading
import time

from benchmark import common
from benchmark.drivers import serve_decoder, serve_hybrid, serve_lm
from benchmark.drivers.serve_lm import warm_prompts

PARTS = ("mixer", "attn", "routed", "shared")
ENGINE_KEYS = ("state_pool_bytes", "moe_experts_held",
               "moe_experts_hit_share", "moe_experts_streamed_share",
               "moe_local_choice_share", "moe_max_expert_share")


class StallWatch(threading.Thread):
    """Wakes every ``TICK`` seconds and keeps the ``KEEP`` longest of (a)
    the times it woke over ``LATE`` seconds late, which is how long its
    process (the interpreter's lock) or the machine held it back, and (b),
    given an engine, the stretches of over ``STILL`` seconds in which the
    engine's step count stood still though slots were decoding (an
    admission's prefill holds the loop 0.03-0.3 s; a step takes 0.01).
    Each (at, length) on the clock of ``time.time()``."""
    TICK, LATE, STILL, KEEP = 0.02, 0.1, 0.4, 5

    def __init__(self, engine=None):
        super().__init__(daemon=True, name="stall-watch")
        self._engine, self._done = engine, threading.Event()
        self.late, self.still = [], []

    def run(self):
        eng, steps = self._engine, None
        last = moved = time.time()
        while not self._done.wait(self.TICK):
            now = time.time()
            if now - last - self.TICK > self.LATE:
                self.late.append((last, now - last - self.TICK))
            if eng is not None:
                n = eng._stats["steps"]
                if n != steps or not eng._active.any():
                    if now - moved > self.STILL:
                        self.still.append((moved, now - moved))
                    steps, moved = n, now
            last = now

    def report(self) -> dict:
        """Stops the watch: {"late": [...], "still": [...]}, the longest
        first."""
        self._done.set()
        self.join()
        longest = lambda found: sorted(  # noqa: E731
            found, key=lambda one: -one[1])[:self.KEEP]
        return {"late": longest(self.late), "still": longest(self.still)}


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


def program_parts(model, params, ids):
    """The program's own plain forward over ``ids``, no cache: ({part:
    [layers of that kind, B, S, d]}: what each part adds to the residual
    stream, the chosen experts [E layers, B, S, k])."""
    import jax
    import jax.numpy as jnp

    last = jnp.full((ids.shape[0],), ids.shape[1] - 1, jnp.int32)
    _, sown = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, logits_at=last,
        mutable=["branches", "moe"]))(params, ids)
    parts = {name: [] for name in PARTS}
    chosen = []
    for i, kind in enumerate(model.config.hybrid_override_pattern):
        layer = sown["branches"][f"layer_{i}"]
        if kind == "M":
            parts["mixer"].append(layer["mixer_out"][0])
        elif kind == "*":
            parts["attn"].append(layer["attn_out"][0])
        else:
            parts["routed"].append(layer["moe"]["routed_out"][0])
            parts["shared"].append(layer["moe"]["shared_out"][0])
            chosen.append(sown["moe"][f"layer_{i}"]["moe"]["expert_idx"][0])
    return ({k: jnp.stack(v) for k, v in parts.items()}, jnp.stack(chosen))


def fed_rows(prompt, got):
    """[1, rows] int32: the rows the programs were fed for this answer,
    the prompt's and every answered token's but the last."""
    import jax.numpy as jnp

    return jnp.asarray([list(prompt) + got["tokens"][:-1]], jnp.int32)


def compare(ref, config, model, params, prompt, got, have=None) -> dict:
    """(a), (b) and (c) of the module's docstring.  ``got``: the engine's
    rollout with ``experts``.  ``have``: the plain forward's parts and
    choices over ``fed_rows`` where the caller took them earlier (the
    precision probe, on weights it no longer holds)."""
    import jax
    import jax.numpy as jnp

    ids = fed_rows(prompt, got)
    rows = ids.shape[1]
    # (a): [rows, E layers, k] as the engine gives them -> [E layers, 1,
    # rows, k]
    cached = jnp.moveaxis(jnp.asarray(got["experts"]), 0, 1)[:, None]
    logits, _, own, cached_slack = ref.forward_with_parts(
        params, ids, config, first_row=len(prompt) - 1, given=cached)
    cached_overlap = ref.choice_overlap(cached, own)
    logits = logits[0]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    # (b)
    parts, plain = have or program_parts(model, params, ids)
    _, want, own, plain_slack = ref.forward_with_parts(
        params, ids, config, first_row=rows - 1, given=plain)
    f32 = jnp.float32

    def rel(a, b):  # the largest over the layers of that kind
        return float(jnp.max(
            jnp.linalg.norm((a.astype(f32) - b).reshape(a.shape[0], -1),
                            axis=-1)
            / jnp.linalg.norm(b.reshape(b.shape[0], -1), axis=-1)))

    x = params["embed"]["embedding"][ids].astype(f32)
    return {"tokens": len(got["tokens"]),
            "logprob_max_err": float(jnp.max(jnp.abs(
                ref_lp - jnp.asarray(got["logprobs"])))),
            "argmax_margin_max": float(jnp.max(margin)),
            "logit_sigma": float(jnp.mean(jnp.std(logits, axis=-1))),
            "branch_rel_err": {p: rel(parts[p], want[p]) for p in PARTS},
            "choice_slack": max(cached_slack, plain_slack),
            "choice_overlap": min(cached_overlap,
                                  ref.choice_overlap(plain, own)),
            # records, no limit: the share of (layer, row) pairs in which
            # the two paths of the program chose the same 22, and each
            # part's first addition beside the embedding it is added to
            "paths_choose_alike": float(jnp.mean(jnp.all(
                jnp.sort(cached, -1) == jnp.sort(plain, -1), axis=-1))),
            "branch_share_of_residual": {
                p: float(jnp.linalg.norm(want[p][0]) / jnp.linalg.norm(x))
                for p in PARTS}}


class BenchHybridMoEServer(serve_hybrid.BenchHybridServer):
    def __init__(self, *args, **kw):
        # the decode and prefill programs, the ones the window times, also
        # return their rows' chosen experts; only the comparison's one
        # request asks for them
        super().__init__(*args, record_experts=True, **kw)

    def watch(self, on: bool):
        """Starts a ``StallWatch`` on the engine, or stops it and returns
        its report."""
        if on:
            self._watch = StallWatch(self.engine)
            return self._watch.start()
        return self._watch.report()

    def reference_check(self, config_name, config, prompt, new_tokens):
        eng = self.engine
        got = eng.rollout(eng.submit(prompt, new_tokens,
                                     record_experts=True), timeout=600.0)
        return compare(common.load_module("reference", config_name), config,
                       eng._model, eng._params, prompt, got)


@contextlib.contextmanager
def session(cell, config, traffic, seed, allow_cpu=False):
    """``serve_decoder.session`` with this driver's server and limits."""
    s = config["serve"]
    with serve_lm.deployed(BenchHybridMoEServer,
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
            here = StallWatch()
            here.start()
            call("watch", True)
            played = serve_lm.play(handle, call, traffic, seed, vocab,
                                   seconds, trace, engine_keys=ENGINE_KEYS)
            there, start = call("watch", False), played["window_start"]
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
