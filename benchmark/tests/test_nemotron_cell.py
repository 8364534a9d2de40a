"""What PR 43 added to the benchmark, checked by hand-counted numbers: the
configuration file against the catalog's values, ``costs_hybrid_moe``
against the issue's sums (an expert 11.01 MB, 7.1 GB of weights a decode
step at 93 held experts hit a layer, 10.5 GB held), the six new readers on
made-up records (among them records whose share would pass 100% if unhit
experts, free slots or absent choices were counted), the driver's limits
(an altered answer turns ``correct`` false), the pinned realisation of the
cell's traffic, and the rehearsal of the cell at toy sizes.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import hashlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (common, costs_hybrid_moe as costs,  # noqa: E402
                       loadgen, program_spans)
from benchmark.drivers import serve_decoder, serve_hybrid_moe  # noqa: E402

CFG = common.load_json("configs", "nemotron3_super_120b.json")
CTX = {"config": CFG, "peak": {"hbm_bytes_per_s": 819e9,
                               "bf16_flops": 197e12}}
CELL = "nemotron3s_serve_steady"
TRAFFIC = "nemotron3s_chat_steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16: every width
PUBLISHED = {
    "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
    "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "moe_latent_size": 1024,
    "moe_intermediate_size": 2688, "intermediate_size": 2688,
    "moe_shared_expert_intermediate_size": 5376, "n_shared_experts": 1,
    "num_experts_per_tok": 22, "routed_scaling_factor": 5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "layer_norm_epsilon": 1e-05, "mlp_hidden_act": "relu2",
    "max_position_embeddings": 262144, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 1, "model_type": "nemotron_h"}


# ---- the configuration file ------------------------------------------------
def test_every_published_width_is_as_published():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert CFG["reduced_from"]["num_hidden_layers"] == 88
    assert CFG["reduced_from"]["n_routed_experts"] == 512
    assert CFG["reduced_from"]["vocab_size"] == 131072
    # the cut: a whole period, the router's width kept, the floors held
    pattern = CFG["hybrid_override_pattern"]
    assert pattern == CFG["reduced_from"]["hybrid_override_pattern"][:11]
    assert len(pattern) == CFG["num_hidden_layers"] == 11
    assert costs.layers(CFG) == {"M": 5, "E": 5, "*": 1}
    assert CFG["router_experts"] == 512 and CFG["n_routed_experts"] == 128
    assert CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CFG["reduced_from"]["vocab_size"]
    assert "4 chips share each layer" in CFG["deployment"]
    assert "77 layers" in CFG["deployment"]
    for key in ("no_position_embedding", "mtp_head",
                "e_score_correction_bias", "recurrent_state_dtype"):
        assert key in CFG["assumed"], key


def test_the_assumed_bias_is_the_one_the_program_draws():
    """The file says how wide ``e_score_correction_bias`` is drawn; the
    width sets the routing's skew, so the text is held to the initialiser."""
    import re

    import jax

    from ray_tpu.models import nemotron_h

    lo, hi = map(float, re.search(
        r"uniform in \[(-?[\d.]+), (-?[\d.]+)\]",
        CFG["assumed"]["e_score_correction_bias"]).groups())
    assert -lo == hi == 0.02
    drawn = nemotron_h._score_bias_init(jax.random.PRNGKey(7), (4096,))
    assert lo <= float(drawn.min()) < 0.95 * lo
    assert 0.95 * hi < float(drawn.max()) <= hi
    assert "_score_bias_init" in CFG["assumed"]["e_score_correction_bias"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_the_catalog_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CFG["source"])
    for key, value in row["config"].items():
        if key in CFG["reduced"] or key == "hybrid_override_pattern":
            continue
        assert CFG[key] == value, key
    assert row["config"]["hybrid_override_pattern"] \
        == CFG["reduced_from"]["hybrid_override_pattern"]


def test_model_kw_is_built_from_the_file_s_keys():
    kw = serve_decoder.model_kw(CFG)
    assert kw["n_routed_experts"] == 512 and kw["experts_held"] == 128
    assert kw["expert_offset"] == 0 and kw["vocab_size"] == 32768
    assert kw["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert kw["moe_latent_size"] == 1024 and kw["tiny"] is False
    from ray_tpu.models import NemotronHConfig

    kw.pop("tiny")
    c = NemotronHConfig(**kw)
    assert (c.num_layers, c.experts_held, c.num_experts) == (11, 128, 512)
    assert c.mixer.in_proj_dim == 18560 and c.mixer.conv_dim == 10240


# ---- the cost functions, by hand --------------------------------------------
def test_an_expert_and_a_layer_by_hand():
    assert costs.expert_params(CFG) == 2 * 1024 * 2688 == 5_505_024
    assert costs.expert_params(CFG) * 2 == 11_010_048       # 11.01 MB
    per = costs.layer_params(CFG)
    assert costs.in_proj_dim(CFG) == 8192 + 10240 + 128
    assert per["M"] == (4096 * 18560 + 8192 * 4096 + 5 * 10240 + 3 * 128
                        + 8192 + 4096) == 109_640_064
    assert per["*"] == 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096 == 35_655_680
    assert per["E"]["router"] == 4097 * 512
    assert per["E"]["rest"] == (2 * 4096 * 1024 + 2 * 4096 * 5376
                                + 4096) == 52_432_896


def test_a_steps_weights_at_93_hit_are_the_issues_7_1_gb():
    need = costs.streamed_bytes(CFG) + costs.routed_decode_bytes(CFG, 5 * 93)
    assert need / 1e9 == pytest.approx(7.12, abs=0.02)  # the issue's 7.1
    # 72% of it in held experts
    assert costs.routed_decode_bytes(CFG, 5 * 93) / need \
        == pytest.approx(0.72, abs=0.01)
    # with every held expert read whether hit or not it would be 9.05 GB
    assert (costs.streamed_bytes(CFG)
            + costs.routed_decode_bytes(CFG, 5 * 128)) / 1e9 \
        == pytest.approx(9.05, abs=0.02)


def test_the_memory_sum_of_the_issue():
    m = costs.memory_sum(CFG)
    assert m["weights"] / 1e9 == pytest.approx(9.32, abs=0.02)
    assert m["state"] / 1e9 == pytest.approx(1.02, abs=0.01)
    assert m["page_pool"] == 12289 * 1 * 2 * 16 * 256 * 2
    assert sum(m.values()) / 1e9 == pytest.approx(10.54, abs=0.03)
    assert costs.state_bytes(CFG) == {"ssm": 128 * 64 * 128 * 4,
                                      "conv": 3 * 10240 * 2}
    # the parameter count the program's own init gives (eval_shape, PR 43)
    counts = costs.param_counts(CFG)
    assert sum(counts.values()) == 4_648_163_712


def test_decode_bytes_by_hand():
    got = costs.decode_bytes(CFG, live_slots=30, kv_tokens=20_000,
                             experts_hit=5 * 93)
    want = (costs.streamed_bytes(CFG) + 5 * 93 * 11_010_048
            + 30 * 5 * 2 * (4_194_304 + 61_440) + 20_000 * 1 * 2 * 256 * 2)
    assert got == want
    assert costs.kv_read_bytes(CFG, 1000) == 1000 * 1024  # 1 KB a token


def test_prefill_flops_by_hand():
    one = costs.prefill_flops(CFG, 1, 0.25)
    routed = 5 * 2 * 22 * 0.25 * costs.expert_params(CFG)
    assert routed == costs.layers(CFG)["E"] * costs.routed_flops(CFG, 5.5)
    mixer = 5 * 2 * (4096 * 18560 + 8192 * 4096 + 4 * 10240
                     + 3 * 8192 * 128)
    attn = 2 * (2 * 4096 * 4096 + 2 * 4096 * 256) + 4 * 4096
    rest = 5 * 2 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376)
    head = 2 * 4096 * 32768
    assert one == pytest.approx(routed + mixer + attn + rest + head)


# ---- the readers ------------------------------------------------------------
def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def span(name, **args):
    return {"name": name, "start": 0.0, "end": 1.0, "args": args}


STATE_OP = "fusion f32[48,128,64,128]"
KERNEL = "tpu_custom_call f32[48,1024]"


def record(ms=14.0, steps=4, state_s=0.01, kernel_s=0.03):
    return {"trace": {
        "program_s": {"jit_llm_decode": [ms / 1e3] * steps,
                      "jit_llm_prefill_512": [0.05, 0.05]},
        "op_s": {STATE_OP: state_s, KERNEL: kernel_s,
                 "tpu_custom_call f32[512,1024]": 0.02,   # a prefill's call
                 "tpu_custom_call f32[48,32,256]": 1.0,   # the paged kernel
                 "tpu_custom_call f32[22528,2688]": 0.03,  # grouped: up
                 "tpu_custom_call f32[22528,1024]": 0.01,  # grouped: down
                 "tpu_custom_call f32[180224,1024]": 1.0,  # no such prefill
                 "fusion f32[1,128,64,128]": 1.0}}}       # not [slots, ...]


def steps(live, kv, hit, landed, n=4, held=640):
    return ([span("engine.decode.dispatch", state_slots=live, kv_tokens=kv)
             for _ in range(n)]
            + [span("engine.decode.fetch", experts_hit=hit,
                    experts_streamed=hit, experts_held=held,
                    local_choices=landed, choices=live * 5 * 22)
               for _ in range(n)])


def test_whole_step_roofline_counts_hit_experts_and_live_slots(monkeypatch):
    reader = common.load_module("layer_metrics", "hybrid_moe_decode_roofline")
    fake(monkeypatch, steps(30, 20_000, 465, 825))
    need = costs.decode_bytes(CFG, 30, 20_000, 465)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 819e9 / 0.014)
    assert 60 < reader.read(record(), CTX) < 75
    # every held expert and every slot (what a program that followed
    # nothing would move) would read 98% of a 14 ms step, and pass 100% on
    # a shorter one: the count is of the hit and the live
    all_of_it = costs.decode_bytes(CFG, 48, 20_000, 640)
    assert 100 * all_of_it / 819e9 / 0.014 > 95


def test_kernel_roofline_reads_the_two_dimensional_call(monkeypatch):
    reader = common.load_module("layer_metrics", "latent_moe_decode_roofline")
    fake(monkeypatch, steps(30, 20_000, 465, 825))
    # 465 x 11,010,048 bytes / 819 GB/s = 6.251 ms of 7.5 ms a step
    assert reader.read(record(), CTX) == pytest.approx(83.35, abs=0.01)


def test_prefill_mfu_counts_the_choices_that_landed(monkeypatch):
    reader = common.load_module("layer_metrics", "latent_moe_prefill_mfu")
    prefills = [span("engine.prefill", prompt_tokens=352, bucket=512),
                span("engine.prefill", prompt_tokens=400, bucket=512),
                span("engine.prefill", prompt_tokens=900, bucket=1024)]
    fake(monkeypatch, steps(30, 20_000, 465, 825) + prefills)
    share = 825 / (30 * 5 * 22)
    need = sum(costs.routed_flops(CFG, 5 * n * 22 * share)
               for n in (352, 400, 900))
    # the kernel's 512-row calls, and the grouped bucket's two matmuls
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 197e12 / (0.02 + 0.03 + 0.01))


def test_state_roofline_reads_the_state_shaped_operations(monkeypatch):
    reader = common.load_module("layer_metrics",
                                "nemotron_ssm_state_roofline")
    fake(monkeypatch, steps(30, 20_000, 465, 825))
    # 30 x 5 x 2 x 4,194,304 bytes / 819 GB/s = 1.5363 ms of 2.5 ms a step
    assert reader.read(record(), CTX) == pytest.approx(61.45, abs=0.01)


def test_the_two_counters(monkeypatch):
    fake(monkeypatch, steps(30, 20_000, 465, 825))
    hit = common.load_module("layer_metrics", "held_experts_hit_share")
    local = common.load_module("layer_metrics", "local_choice_share")
    assert hit.read(record(), CTX) == pytest.approx(100 * 465 / 640)
    assert local.read(record(), CTX) == pytest.approx(100 * 825 / 3300)


READERS = ["hybrid_moe_decode_roofline", "latent_moe_decode_roofline",
           "latent_moe_prefill_mfu", "nemotron_ssm_state_roofline",
           "held_experts_hit_share", "local_choice_share"]


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["no_trace", "parents_spans", "other_model",
                                  "no_peak"])
def test_nothing_to_read_is_none(monkeypatch, name, case):
    """A run with no profile, a program whose spans lack the new arguments
    (the parent of this PR, or another model), a configuration without
    these keys, a device with no peaks on file: None, and nothing raised."""
    reader = common.load_module("layer_metrics", name)
    spans = steps(30, 20_000, 465, 825) + [
        span("engine.prefill", prompt_tokens=352, bucket=512)]
    if case == "parents_spans":  # the old arguments alone
        spans = [span("engine.decode.dispatch", kv_tokens=10_000),
                 span("engine.decode.fetch", experts_hit=300,
                      experts_streamed=300),
                 span("engine.prefill", prompt_tokens=352, bucket=512)]
    fake(monkeypatch, spans)
    rec = {"trace": None} if case == "no_trace" else record()
    if case == "parents_spans":
        rec["trace"]["op_s"] = {"fusion f32[16,2048]": 1.0}
    ctx = dict(CTX)
    if case == "other_model":
        ctx["config"] = common.load_json("configs", "olmoe_1b_7b.json")
    if case == "no_peak":
        del ctx["peak"]
    counters = name in ("held_experts_hit_share", "local_choice_share")
    if counters and case in ("no_trace", "other_model", "no_peak"):
        assert reader.read(rec, ctx) is not None  # the spans alone
    else:
        assert reader.read(rec, ctx) is None


# ---- the driver's limits ----------------------------------------------------
def sound_check():
    return {"tokens": 8, "logprob_max_err": 0.01, "argmax_margin_max": 0.0,
            "branch_rel_err": {p: 0.005 for p in serve_hybrid_moe.PARTS},
            "choice_slack": 0.001, "choice_overlap": 0.99}


def test_within_holds_every_part_both_errors_and_the_choices():
    ref = common.load_traffic(TRAFFIC)["reference"]
    assert serve_hybrid_moe.within(sound_check(), ref)
    for spoil in ({"tokens": 7}, {"logprob_max_err": 10.0},
                  {"argmax_margin_max": 10.0}, {"choice_overlap": 0.3},
                  {"choice_slack": 0.5},
                  *({"branch_rel_err": {**sound_check()["branch_rel_err"],
                                        p: 5.0}}
                    for p in serve_hybrid_moe.PARTS)):
        assert not serve_hybrid_moe.within({**sound_check(), **spoil}, ref)


def test_the_stall_watch_names_a_loop_that_stood_still():
    """An engine whose step count stands still for 0.6 s with a slot
    decoding, then moves, then idles: one stretch is named, at the time it
    began, and an idle engine is no stall."""
    import time

    import numpy as np

    class Engine:
        _stats = {"steps": 0}
        _active = np.array([True, False])

    eng = Engine()
    watch = serve_hybrid_moe.StallWatch(eng)
    watch.STILL = 0.3
    began = time.time()
    watch.start()
    time.sleep(0.6)
    for _ in range(10):
        eng._stats["steps"] += 1
        time.sleep(0.03)
    eng._active = np.array([False, False])
    time.sleep(0.5)
    found = watch.report()
    assert len(found["still"]) == 1
    at, took = found["still"][0]
    assert abs(at - began) < 0.2 and 0.45 < took < 0.9
    assert all(took < 0.5 for _, took in found["late"])


def test_an_altered_answer_turns_correct_false():
    """The driver's own comparison at toy sizes: the engine's answer holds,
    the same answer with a token, a log-probability or a row's chosen
    experts altered does not."""
    import numpy as np

    from benchmark.rehearsal import rehearse
    from benchmark import run
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    _, cell, config, traffic = run.load_cell(
        CELL, rehearse.tiny_overrides(CELL))
    ref = common.load_module("reference", cell["config"])
    limits = traffic["reference"]
    s = config["serve"]
    model, params = build_model(s["model_kind"],
                                serve_decoder.model_kw(config), 11)
    eng = LLMEngine(model, params, max_slots=2, page_size=s["page_size"],
                    max_ctx=s["max_ctx"], chunk_tokens=1,
                    record_experts=True)
    try:
        prompt = serve_decoder.reference_prompt(limits["prompt_tokens"], 5,
                                                config["vocab_size"])
        got = eng.rollout(eng.submit(prompt, limits["new_tokens"],
                                     record_experts=True), timeout=120.0)
    finally:
        eng.close()
    sound = serve_hybrid_moe.compare(ref, config, model, params, prompt, got)
    assert serve_hybrid_moe.within(sound, limits), sound
    assert sound["choice_overlap"] == sound["paths_choose_alike"] == 1.0
    assert sound["choice_slack"] == 0.0
    lower = dict(got, logprobs=[
        got["logprobs"][0] - 2 * limits["logprob_tolerance"],
        *got["logprobs"][1:]])
    other = dict(got, tokens=[(got["tokens"][0] + 1) % config["vocab_size"],
                              *got["tokens"][1:]])
    short = {k: v[:-1] for k, v in got.items()
             if k in ("tokens", "logprobs", "experts")}
    # the last decode row's choices, every one of them an expert it did
    # not take: what a program that misroutes a row would hand out
    taken = set(got["experts"][-1, 0].tolist())
    strays = [e for e in range(config["router_experts"]) if e not in taken]
    misrouted = np.array(got["experts"])
    misrouted[-1, 0] = strays[:misrouted.shape[-1]]
    for altered in (lower, other, short, dict(got, experts=misrouted)):
        check = serve_hybrid_moe.compare(ref, config, model, params, prompt,
                                         altered)
        assert not serve_hybrid_moe.within(check, limits), check


def test_the_long_comparison_reaches_what_the_short_one_cannot():
    refs = serve_decoder.comparisons(common.load_traffic(TRAFFIC)["reference"])
    assert [r["prompt_tokens"] for r in refs] == [48, 900]
    for r in refs:
        assert r["new_tokens"] == 8
        assert 0 < r["logprob_tolerance"] < 2  # the logits' deviation is 1
        assert set(r["branch_rel_err_max"]) == set(serve_hybrid_moe.PARTS)
        assert all(0 < v < 1 for v in r["branch_rel_err_max"].values())
        assert 0.5 < r["choice_overlap_min"] < 1
        assert 0 < r["choice_slack_max"] < 0.1  # scores lie in (0, 1)
    assert refs[0]["limits_reason"].count("8-bit") >= 1


# ---- the cell and its traffic -----------------------------------------------
def test_the_cell_is_in_the_manifest_with_its_files():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert len(manifest["workloads"]) == 7 and len(manifest["configs"]) == 5
    assert manifest["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    cell = manifest["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3_super_120b", TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "48 slots" in cell["why"]
    entry = manifest["configs"][-1]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    # the contract's limit on every one-line text of the manifest
    for text in (entry["why"], entry["source"], *(
            m["layer"] for m in manifest["per_layer"])):
        assert 1 <= len(text) <= 200 and "\t" not in text, text
    traffic = common.load_traffic(cell["traffic"])
    assert common.load_module("drivers", traffic["driver"]) \
        is serve_hybrid_moe
    assert common.load_module("reference", cell["config"]) is not None
    mine = [m for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert set(READERS) <= {m["name"] for m in mine}
    assert [m["name"] for m in manifest["per_layer"][-6:]] == READERS
    judged = {m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    # six seeds' token_gap_p50_ms spread 3.3% (the step follows the experts
    # hit, which follow --seed): not judged on it, gap_p50_ms is its record
    assert judged == {"serve_tokens_per_s", "setup_s"}
    assert "gap_p50_ms" in {m["name"] for m in mine}
    for m in mine:
        assert common.load_module("layer_metrics", m["name"]) is not None
        assert m["moves"] in judged
        assert "roofline" not in m["name"] or m["unit"] == "%"


def test_the_traffic_is_the_issues_and_says_where_its_rate_comes_from():
    t = common.load_traffic(TRAFFIC)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.8, "min": 32, "max": 3072}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 128,
                                  "sigma": 0.6, "min": 16, "max": 512}
    assert (t["clients"], t["preroll_s"], t["max_total_tokens"]) == (
        6, 30, 4096)
    assert (t["trace_offset_s"], t["trace_s"]) == (2, 2)
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * t["knee_per_s"], rel=0.03)
    assert t["knee_note"].count("/s") >= 5


def test_every_seed_meets_one_realisation():
    t = common.load_traffic(TRAFFIC)
    a = loadgen.build_schedule(t, 3000000011, CFG["vocab_size"], 75.0)
    b = loadgen.build_schedule(t, 7, CFG["vocab_size"], 75.0)
    shape = lambda s: [(r["due_s"], len(r["prompt"]),  # noqa: E731
                        r["max_new_tokens"]) for r in s]
    assert shape(a) == shape(b)
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 4096 for r in a)
    assert all(0 <= tok < 32768 for r in a for tok in r["prompt"])
    digest = hashlib.sha256(json.dumps(shape(a)).encode()).hexdigest()[:16]
    assert (len(a), digest) == PINNED


PINNED = (581, "635b05d71a560a03")  # requests in 75 s at 8.0/s, digest


# ---- the rehearsal ------------------------------------------------------------
@pytest.mark.timeout(600)
def test_the_rehearsal_plays_the_seventh_cell_at_toy_sizes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearsal",
                                      "rehearse.py"), CELL, "--trace", "1"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=580)
    assert f"[rehearsal] {CELL} trace=1: ok" in out.stdout, out.stdout[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if "correct-but-for-the-device" in ln)
    assert "held_experts_hit_share" in line and "local_choice_share" in line
