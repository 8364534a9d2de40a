"""What PR 47 added to the benchmark, checked by hand-counted numbers: the
configuration file against the catalog's values, ``costs_linear_moe``
against ``jax.eval_shape`` of the program's own init (to the parameter) and
against the issue's sums, the five new readers on made-up records (among
them records whose share would pass 100% if unhit experts or free slots were
counted), the driver's limits (an altered answer turns ``correct`` false),
the pinned realisation of the cell's traffic, and the rehearsal of the cell
at toy sizes.  The cell and its entries are found by name: a later cell
moves nothing here.

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

from benchmark import (common, costs_linear_moe as costs,  # noqa: E402
                       loadgen, manifest_check, program_spans)
from benchmark.drivers import serve_decoder, serve_linear_moe  # noqa: E402

CFG = common.load_json("configs", "ling3_flash_vl.json")
CTX = {"config": CFG, "peak": {"hbm_bytes_per_s": 819e9,
                               "bf16_flops": 197e12}}
CELL = "ling3f_serve_reason"
TRAFFIC = "ling3f_reason_steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# config.json of inclusionAI/Ling-3.0-flash-VL: every width


PUBLISHED = {
    "hidden_size": 2560, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "moe_shared_expert_intermediate_size": 768,
    "num_attention_heads": 32, "head_dim": 128, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "q_lora_rank": None, "num_experts_per_tok": 8, "n_group": 8,
    "topk_group": 4, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "score_function": "sigmoid", "layer_group_size": 6,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "no_kda_lora": True, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 131072,
    "group_norm_size": 1, "linear_silu": True,
    "gated_attention_proj_granularity_type": "head_wise"}


# ---- the configuration file ------------------------------------------------
def test_every_published_width_is_as_published():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "num_experts", "vocab_size"]
    assert CFG["reduced_from"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    # the cut: the dense layer once and a whole period, the router's width
    # kept, the floors held
    assert (CFG["num_hidden_layers"], CFG["first_k_dense_replace"]) == (7, 1)
    assert costs.layers(CFG) == {"kda": 6, "mla": 1, "dense": 1, "moe": 6}
    assert CFG["router_experts"] == 512 and CFG["num_experts"] == 128
    assert CFG["num_experts"] >= 8
    assert CFG["vocab_size"] * 4 == CFG["reduced_from"]["vocab_size"]
    assert "4 chips share each layer" in CFG["deployment"]
    assert "35 layers" in CFG["deployment"]
    for key in ("full_layer_of_a_group", "use_qk_norm", "rope", "kda_gate",
                "conv_bias", "tie_word_embeddings", "mtp_head",
                "vision_tower", "clamped_swiglu", "expert_bias", "A_log",
                "dt_bias", "recurrent_state_dtype"):
        assert key in CFG["assumed"], key
    # the layers that are kept have no clamp to build
    assert not any(CFG["expert_swiglu_limit_list"][:7])
    assert not any(CFG["share_expert_swiglu_limit_list"][:7])


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_the_catalog_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CFG["source"])
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    for key in CFG["reduced"]:
        assert CFG["reduced_from"][key] == row["config"][key], key


def test_model_kw_is_built_from_the_file_s_keys():
    kw = serve_decoder.model_kw(CFG)
    assert kw["num_experts"] == 512 and kw["experts_held"] == 128
    assert kw["expert_offset"] == 0 and kw["vocab_size"] == 39296
    assert kw["kda_head_dim"] == 128 and kw["tiny"] is False
    from ray_tpu.models.ling_linear import LingLinear, LingLinearConfig

    kw.pop("tiny")
    c = LingLinearConfig(**kw)
    assert (c.num_layers, c.experts_held, c.num_experts) == (7, 128, 512)
    assert (c.num_kv_heads, c.head_dim, c.qk_head_dim) == (1, 576, 192)
    model = LingLinear(c)
    assert (model.kv_layers, model.state_layers, model.expert_layers) \
        == (1, 6, 6)


# ---- the cost functions -------------------------------------------------------
def test_the_parameter_count_is_the_program_s_own_to_the_parameter():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.ling_linear import LingLinear, LingLinearConfig

    kw = serve_decoder.model_kw(CFG)
    kw.pop("tiny")
    model = LingLinear(LingLinearConfig(**kw))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == sum(
        costs.param_counts(CFG).values()) == 5_169_285_248
    assert sum(x.size * x.dtype.itemsize for x in leaves) \
        == costs.memory_sum(CFG)["weights"] == 10_354_354_816
    state = sum(
        4 * int(jnp.prod(jnp.asarray(shape))) if name == "S"
        else 2 * int(jnp.prod(jnp.asarray(shape)))
        for name, (shape, _) in model.slot_state.items())
    assert state == sum(costs.state_bytes(CFG).values()) == 2_170_880


def test_the_parts_by_hand():
    per = costs.part_params(CFG)
    assert per["kda"] == (2560 * 12288 + 2560 * 4096 + 2 * 2560 * 32
                          + 4096 * 2560 + 4 * 12288 + 32 + 4096
                          + 128) == 52_646_048                 # the issue's 52.6M
    assert per["mla"] == (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256
                          + 4096 * 2560 + 512 + 192) == 31_883_968  # 31.9M
    assert per["dense"] == 3 * 2560 * 6144
    assert per["router"] == 2561 * 512
    assert costs.expert_params(CFG) == 3 * 2560 * 768 == 5_898_240  # 5.90M
    assert 128 * costs.expert_params(CFG) == 754_974_720       # 755.0M
    # a dense layer 99.8M; an expert layer outside its experts 59.9 / 39.1M
    assert per["kda"] + per["dense"] + per["norms"] == 99_837_088
    outside = per["router"] + per["shared"] + per["norms"]
    assert per["kda"] + outside == 59_860_640
    assert per["mla"] + outside == 39_098_560


def test_the_memory_sum_of_the_issue():
    m = costs.memory_sum(CFG)
    assert m["weights"] / 1e9 == pytest.approx(10.35, abs=0.01)
    # 48 x 512 + 1 pages x 16 rows x 640 columns x 2 B, K and V
    assert m["page_pool"] == 24577 * 16 * 640 * 2 * 2 == 1_006_673_920
    assert m["state"] == 48 * 6 * 2_170_880 == 625_213_440
    assert sum(m.values()) / 1e9 == pytest.approx(11.99, abs=0.01)


def test_decode_bytes_by_hand():
    streamed = costs.streamed_bytes(CFG)
    assert streamed == (costs.param_counts(CFG)["streamed"] * 2
                        + 6 * 2561 * 512 * 4)
    assert streamed / 1e9 == pytest.approx(1.093, abs=0.001)
    one = costs.decode_bytes(CFG, 35, 24_500, 324)
    routed = 324 * 5_898_240 * 2
    state = 35 * 6 * 2 * 2_170_880
    latent = 24_500 * 2 * 576 * 2
    assert one == pytest.approx(streamed + routed + state + latent)
    assert costs.state_step_bytes(CFG, 35) == 35 * 6 * 2 * 4 * 32 * 128 * 128
    assert costs.kv_read_bytes(CFG, 1000) == 1000 * 2304


def test_prefill_flops_by_hand():
    n, share = 1000, 0.25
    kda = 2 * (2560 * 16384 + 2 * 2560 * 32 + 4096 * 2560 + 4 * 12288
               + 4 * 4096 * 128)
    mla = 2 * (2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560)
    moe = 2 * (2560 * 512 + 3 * 2560 * 768) + 2 * 8 * share * 5_898_240
    pairs = n * (n + 1) / 2
    want = (n * (6 * kda + mla + 2 * 3 * 2560 * 6144 + 6 * moe)
            + 2 * 32 * (192 + 128) * pairs + 2 * 2560 * 39296)
    assert costs.prefill_flops(CFG, n, share) == pytest.approx(want)


# ---- the readers ------------------------------------------------------------
def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def span(name, **args):
    return {"name": name, "start": 0.0, "end": 1.0, "args": args}


def record(ms=11.0, steps=4, state_s=0.012, kernel_s=0.024, paged_s=0.002):
    return {"trace": {
        "program_s": {"jit_llm_decode": [ms / 1e3] * steps,
                      "jit_llm_prefill_512": [0.05, 0.05]},
        "op_s": {"fusion f32[48,32,128,128]": state_s / 2,
                 "select_fusion f32[48,32,128,128]": state_s / 2,
                 "tpu_custom_call f32[48,2560]": kernel_s,
                 "tpu_custom_call f32[512,2560]": 0.02,   # a prefill's call
                 "tpu_custom_call f32[48,32,640]": paged_s,
                 "tpu_custom_call f32[48,32,256]": 1.0,   # another pool
                 "fusion f32[1,32,128,128]": 1.0}}}       # not [slots, ...]


def steps(live, kv, hit, landed, n=4, held=768):
    return ([span("engine.decode.dispatch", state_slots=live, kv_tokens=kv)
             for _ in range(n)]
            + [span("engine.decode.fetch", experts_hit=hit,
                    experts_streamed=hit, experts_held=held,
                    local_choices=landed, choices=live * 6 * 8)
               for _ in range(n)])


def test_whole_step_roofline_counts_hit_experts_and_live_slots(monkeypatch):
    reader = common.load_module("layer_metrics", "linear_moe_decode_roofline")
    fake(monkeypatch, steps(35, 24_500, 324, 420))
    need = costs.decode_bytes(CFG, 35, 24_500, 324)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 819e9 / 0.011)
    assert 55 < reader.read(record(), CTX) < 70
    # every held expert and every slot (what a program that followed
    # nothing would move) would pass 100% of an 11 ms step: the count is
    # of the hit and the live
    all_of_it = costs.decode_bytes(CFG, 48, 24_500, 768)
    assert 100 * all_of_it / 819e9 / 0.011 > 100


def test_kernel_roofline_reads_the_two_dimensional_call(monkeypatch):
    reader = common.load_module("layer_metrics",
                                "swiglu_held_decode_roofline")
    fake(monkeypatch, steps(35, 24_500, 324, 420))
    # 324 x 11,796,480 bytes / 819 GB/s = 4.667 ms of 6 ms a step
    assert reader.read(record(), CTX) == pytest.approx(77.78, abs=0.01)


def test_state_roofline_sums_the_state_shaped_operations(monkeypatch):
    reader = common.load_module("layer_metrics", "kda_state_roofline")
    fake(monkeypatch, steps(35, 24_500, 324, 420))
    # 35 x 6 x 2 x 2,097,152 bytes / 819 GB/s = 1.0755 ms of 3 ms a step,
    # both fusions that carry the state's shape summed
    assert reader.read(record(), CTX) == pytest.approx(35.85, abs=0.01)


def test_latent_attention_roofline_reads_the_pool_wide_call(monkeypatch):
    reader = common.load_module("layer_metrics", "latent_paged_attn_roofline")
    fake(monkeypatch, steps(35, 24_500, 324, 420))
    # 24,500 x 2,304 bytes / 819 GB/s = 0.0689 ms of 0.5 ms a step
    assert reader.read(record(), CTX) == pytest.approx(13.78, abs=0.01)


def test_prefill_mfu_counts_real_rows_and_landed_choices(monkeypatch):
    reader = common.load_module("layer_metrics", "linear_moe_prefill_mfu")
    prefills = [span("engine.prefill", scanned_rows=352, padded_rows=160),
                span("engine.prefill", scanned_rows=400, padded_rows=112)]
    fake(monkeypatch, steps(35, 24_500, 324, 420) + prefills)
    share = 420 / (35 * 6 * 8)
    need = sum(costs.prefill_flops(CFG, n, share) for n in (352, 400))
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 197e12 / 0.1)


READERS = ["linear_moe_decode_roofline", "kda_state_roofline",
           "latent_paged_attn_roofline", "swiglu_held_decode_roofline",
           "linear_moe_prefill_mfu"]


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["no_trace", "parents_spans", "other_model",
                                  "no_peak"])
def test_nothing_to_read_is_none(monkeypatch, name, case):
    """A run with no profile, a program whose spans lack the arguments, a
    configuration of another family, a device with no peaks on file: None,
    and nothing raised."""
    reader = common.load_module("layer_metrics", name)
    spans = steps(35, 24_500, 324, 420) + [
        span("engine.prefill", scanned_rows=352, padded_rows=160)]
    if case == "parents_spans":
        spans = [span("engine.decode.dispatch"), span("engine.decode.fetch"),
                 span("engine.prefill", prompt_tokens=352, bucket=512)]
    fake(monkeypatch, spans)
    rec = {"trace": None} if case == "no_trace" else record()
    ctx = dict(CTX)
    if case == "other_model":
        ctx["config"] = common.load_json("configs",
                                         "nemotron3_super_120b.json")
    if case == "no_peak":
        del ctx["peak"]
    assert reader.read(rec, ctx) is None


def test_the_shared_counters_read_this_cell_too(monkeypatch):
    fake(monkeypatch, steps(35, 24_500, 324, 420))
    hit = common.load_module("layer_metrics", "held_experts_hit_share")
    local = common.load_module("layer_metrics", "local_choice_share")
    assert hit.read(record(), CTX) == pytest.approx(100 * 324 / 768)
    assert local.read(record(), CTX) == pytest.approx(100 * 420 / 1680)


# ---- the driver's limits ----------------------------------------------------
def sound_check():
    return {"tokens": 8, "logprob_max_err": 0.01, "argmax_margin_max": 0.0,
            "branch_rel_err": {p: 0.005 for p in serve_linear_moe.PARTS},
            "choice_slack": 0.001, "choice_overlap": 0.99}


def test_within_holds_every_part_both_errors_and_the_choices():
    ref = common.load_traffic(TRAFFIC)["reference"]
    assert serve_linear_moe.within(sound_check(), ref)
    for spoil in ({"tokens": 7}, {"logprob_max_err": 10.0},
                  {"argmax_margin_max": 10.0}, {"choice_overlap": 0.3},
                  {"choice_slack": 0.5},
                  *({"branch_rel_err": {**sound_check()["branch_rel_err"],
                                        p: 5.0}}
                    for p in serve_linear_moe.PARTS)):
        assert not serve_linear_moe.within({**sound_check(), **spoil}, ref)


def test_an_altered_answer_turns_correct_false():
    """The driver's own comparison at toy sizes: the engine's answer holds,
    the same answer with a token, a log-probability or a row's chosen
    experts altered does not; and the spread is told."""
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
    sound = serve_linear_moe.compare(ref, config, model, params, prompt, got)
    assert serve_linear_moe.within(sound, limits), sound
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
        check = serve_linear_moe.compare(ref, config, model, params, prompt,
                                         altered)
        assert not serve_linear_moe.within(check, limits), check
    told = serve_linear_moe.spread(
        model, params, serve_linear_moe.program_forward(
            model, params, serve_linear_moe.fed_rows(prompt, got)))
    assert 0 < told["alpha_quantiles"]["0.01"] <= 1.0
    assert told["even_share"] == 1 / 16
    assert 0 <= told["held_choice_share"] <= 1


def test_the_two_comparisons_are_the_issues():
    refs = serve_decoder.comparisons(common.load_traffic(TRAFFIC)["reference"])
    assert [(r["prompt_tokens"], r["new_tokens"]) for r in refs] \
        == [(48, 8), (2000, 32)]
    for r in refs:
        assert 0 < r["logprob_tolerance"] < 2  # the logits' deviation is 1
        assert set(r["branch_rel_err_max"]) == set(serve_linear_moe.PARTS)
        assert all(0 < v < 1 for v in r["branch_rel_err_max"].values())
        assert 0.5 < r["choice_overlap_min"] < 1
        assert 0 < r["choice_slack_max"] < 0.1  # scores lie in (0, 1)
    assert refs[0]["limits_reason"].count("8-bit") >= 1


# ---- the cell and its traffic -----------------------------------------------
def test_the_cell_is_in_the_manifest_with_its_entries_and_files():
    """The manifest stands with the cell, its configuration and its five
    readers in it; each is found by name."""
    manifest = manifest_check.load()
    assert manifest_check.faults(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0])
    assert names[at:at + len(READERS)] == list(READERS)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling3_flash_vl", TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "48 slots" in cell["why"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "ling3_flash_vl")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\t" not in text, text
    traffic = common.load_traffic(cell["traffic"])
    assert common.load_module("drivers", traffic["driver"]) \
        is serve_linear_moe
    assert common.load_module("reference", cell["config"]) is not None
    mine = [m for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert set(READERS) <= {m["name"] for m in mine}
    for name in READERS:  # this cell's alone
        assert next(m for m in mine if m["name"] == name)["workloads"] \
            == [CELL]
    judged = {m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert judged == {"serve_tokens_per_s", "setup_s"}
    assert {"gap_p50_ms", "held_experts_hit_share", "local_choice_share",
            "peak_hbm_share.serve", "device_idle_share.serve"} \
        <= {m["name"] for m in mine}
    for m in mine:
        assert common.load_module("layer_metrics", m["name"]) is not None
        assert m["moves"] in judged
        assert "roofline" not in m["name"] or m["unit"] == "%"


def test_the_traffic_is_the_issues_and_says_where_its_rate_comes_from():
    t = common.load_traffic(TRAFFIC)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                  "sigma": 1.0, "min": 32, "max": 4096}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 768,
                                  "sigma": 0.6, "min": 64, "max": 2048}
    assert (t["clients"], t["preroll_s"], t["max_total_tokens"]) == (
        6, 45, 6144)
    assert t["max_total_tokens"] <= CFG["serve"]["max_ctx"] == 8192
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * t["knee_per_s"], rel=0.03)
    assert t["knee_note"].count("/s") >= 4


def test_every_seed_meets_one_realisation():
    t = common.load_traffic(TRAFFIC)
    a = loadgen.build_schedule(t, 3000000011, CFG["vocab_size"], 90.0)
    b = loadgen.build_schedule(t, 7, CFG["vocab_size"], 90.0)
    shape = lambda s: [(r["due_s"], len(r["prompt"]),  # noqa: E731
                        r["max_new_tokens"]) for r in s]
    assert shape(a) == shape(b)
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 6144 for r in a)
    assert all(0 <= tok < 39296 for r in a for tok in r["prompt"])
    decoded = sum(r["max_new_tokens"] for r in a)
    assert decoded > 0.6 * (decoded + sum(len(r["prompt"]) for r in a))
    digest = hashlib.sha256(json.dumps(shape(a)).encode()).hexdigest()[:16]
    assert (len(a), digest) == PINNED


PINNED = (239, "646bee2af75fb0eb")  # requests in 90 s at 2.56/s, digest


# ---- the rehearsal ------------------------------------------------------------
@pytest.mark.timeout(600)
def test_the_rehearsal_plays_the_cell_at_toy_sizes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearsal",
                                      "rehearse.py"), CELL, "--trace", "1"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=580)
    assert f"[rehearsal] {CELL} trace=1: ok" in out.stdout, out.stdout[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if "correct-but-for-the-device" in ln)
    assert "held_experts_hit_share" in line and "local_choice_share" in line
