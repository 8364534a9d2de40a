"""What PR 56 added to the benchmark, checked by hand-counted numbers: the
configuration file against the catalog's values, ``costs_latent_moe``
against ``jax.eval_shape`` of the program's own init (to the parameter) and
against the issue's sums, the four new readers on made-up records (among
them records whose share would pass 100% if a row were counted as stored or
as read twice), the driver's limits and an altered answer that turns
``correct`` false, the traffic, and the rehearsal of the cell at toy sizes
with its tiny files.  The cell and its entries are found by name
(``test_glm5_cell.py``'s way): a later cell moves nothing here.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (common, costs_latent_moe as costs,  # noqa: E402
                       loadgen, manifest_check, program_spans)
from benchmark.drivers import serve_decoder, serve_latent_moe  # noqa: E402

CFG = common.load_json("configs", "sarvam_105b.json")
CTX = {"config": CFG, "peak": {"hbm_bytes_per_s": 819e9,
                               "bf16_flops": 197e12}}
CELL = "sarvam105b_serve_docreason"
TRAFFIC = "sarvam105b_docreason_steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("mla_paged_attn_roofline", "mla_decode_roofline",
           "mla_prefill_mfu", "mla_held_decode_roofline")
# config.json of sarvamai/sarvam-105b: every width
PUBLISHED = {
    "hidden_size": 4096, "intermediate_size": 16384,
    "moe_intermediate_size": 2048, "num_attention_heads": 64,
    "kv_lora_rank": 512, "q_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "head_dim": 576,
    "num_experts_per_tok": 8, "num_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    "moe_router_enable_expert_bias": True, "use_qk_norm": True,
    "rope_theta": 10000, "default_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "rms_norm_eps": 1e-06, "max_position_embeddings": 131072,
    "tie_word_embeddings": False, "model_type": "sarvam_mla"}


# ---- the configuration file ------------------------------------------------
def test_every_published_width_is_as_published():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert CFG["reduced_from"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    # the cut: the dense layer and seven layers after it, the router's
    # width kept, the floors held
    assert (CFG["num_hidden_layers"], CFG["first_k_dense_replace"]) == (8, 1)
    assert costs.layers(CFG) == {"attn": 8, "dense": 1, "moe": 7}
    assert CFG["router_experts"] == 128 and CFG["num_experts"] == 16
    assert CFG["num_experts"] >= 8
    assert CFG["vocab_size"] * 8 == CFG["reduced_from"]["vocab_size"]
    assert "8 chips share each layer" in CFG["deployment"]
    assert "4 pipeline stages" in CFG["deployment"]
    for key in ("layer_equations", "use_qk_norm", "scoring_func",
                "norm_topk_prob", "rope", "head_dim", "tie_word_embeddings"):
        assert key in CFG["assumed"], key
    assert "default_theta" in CFG["assumed"]["head_dim"]
    assert "rotate-half" in CFG["assumed"]["rope"]
    s = CFG["serve"]
    assert (s["max_slots"], s["page_size"], s["max_ctx"],
            s["chunk_tokens"]) == (32, 16, 16384, 1)


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


def _program_config():
    from ray_tpu.models.latent_moe import LatentMoEConfig

    kw = serve_decoder.model_kw(CFG)
    assert kw.pop("tiny") is False
    return LatentMoEConfig(**kw)


def test_model_kw_is_built_from_the_file_s_keys():
    from ray_tpu.models.latent_moe import LatentMoE
    from ray_tpu.ops.rope import softmax_mscale, yarn_correction_range

    c = _program_config()
    assert (c.num_layers, c.experts_held, c.num_experts,
            c.expert_offset) == (8, 16, 128, 0)
    assert (c.num_kv_heads, c.head_dim, c.qk_head_dim) == (1, 576, 192)
    assert c.vocab_size == 32768 and c.rope_theta == 10000
    assert c.rope_scaling.factor == 40
    assert yarn_correction_range(c.rope_scaling, 64, 10000.0) == (10, 23)
    assert softmax_mscale(c.rope_scaling) == pytest.approx(1.3689 ** 2,
                                                           rel=1e-4)
    assert LatentMoE(c).expert_layers == 7 and c.latent_cache


# ---- the cost functions ----------------------------------------------------
def test_the_parameter_count_is_the_program_s_own_to_the_parameter():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.latent_moe import LatentMoE

    model = LatentMoE(_program_config())
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == sum(
        costs.param_counts(CFG).values()) == 4_225_313_152
    assert sum(x.size * x.dtype.itemsize for x in leaves) \
        == costs.memory_sum(CFG)["weights"]


def test_the_parts_by_hand():
    per = costs.part_params(CFG)
    assert per["attn"] == (4096 * 12288 + 4096 * 576 + 512 * 16384
                           + 8192 * 4096 + 192 + 512) == 94_634_688
    assert costs.expert_params(CFG) == 25_165_824
    assert per["dense"] == 3 * 4096 * 16384 == 201_326_592
    assert per["shared"] == 25_165_824 and per["router"] == 524_288 + 128
    # an expert layer outside its routed experts, and the dense layer
    assert per["attn"] + per["shared"] + per["router"] + per["norms"] \
        == 120_333_120
    assert per["attn"] + per["dense"] + per["norms"] == 295_969_472
    assert costs.latent_width(CFG) == 576 and costs.stored_width(CFG) == 640


def test_the_memory_sum_of_the_issue():
    mem = costs.memory_sum(CFG)
    assert mem["weights"] == 4_225_313_152 * 2 + 7 * (524_288 + 128) * 2
    assert mem["weights"] == pytest.approx(8.45e9, rel=2e-3)
    # ONE pool: 32 x 1024 + 1 pages of 16 rows, 8 layers, 640 columns
    assert mem["page_pool"] == 32_769 * 16 * 8 * 640 * 2
    assert mem["page_pool"] == pytest.approx(5.37e9, rel=1e-3)
    assert mem["page_pool"] // (32_769 * 16) == 8 * 1280
    held = mem["weights"] + mem["page_pool"]
    assert 0.80 < held / 16.9e9 < 0.83
    # a second pool of [c | 0] rows would not fit beside the weights
    assert held + mem["page_pool"] > 16.9e9


def test_decode_bytes_by_hand():
    # 24 live slots of 9,000 rows, 60 of the 112 held experts hit
    kv = 24 * 9000
    streamed = costs.streamed_bytes(CFG)
    assert streamed == 2 * (8 * (94_634_688 + 8192) + 201_326_592
                            + 7 * 25_165_824 + 4096 + 32768 * 4096) \
        + 4 * 7 * (524_288 + 128)
    assert costs.rows_bytes(CFG, kv * 8) == kv * 8 * 1152
    assert costs.decode_bytes(CFG, kv, 60) == (
        streamed + 60 * 25_165_824 * 2 + kv * 8 * 1152)
    # the issue's arithmetic of the kernel: 139 kFLOP for 1,152 B a row
    assert costs.attend_flops(CFG, 1) == 2 * 64 * (576 + 512) == 139_264
    assert costs.attend_flops(CFG, 1) / costs.rows_bytes(CFG, 1) \
        == pytest.approx(120.9, abs=0.1)


def test_prefill_flops_by_hand():
    n, share = 8192, 1 / 8
    per_token = (8 * 2 * (94_634_688 - 192 - 512) + 2 * 201_326_592
                 + 7 * (2 * (4096 * 128 + 25_165_824)
                        + 2 * 8 * share * 25_165_824))
    pairs = n * (n + 1) / 2
    want = n * per_token + 8 * 2 * 64 * (192 + 128) * pairs \
        + 2 * 4096 * 32768
    assert costs.prefill_flops(CFG, n, share) == pytest.approx(want)
    # the attention's pairs are a third to a half of it at 8k-15k rows
    assert 0.3 < 8 * 2 * 64 * 320 * pairs / want < 0.5


# ---- the readers ------------------------------------------------------------
def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def span(name, **args):
    return {"name": name, "start": 0.0, "end": 1.0, "args": args}


def record(ms=14.0, steps=4, attend_s=0.016, experts_s=0.024):
    return {"trace": {
        "program_s": {"jit_llm_decode": [ms / 1e3] * steps,
                      "jit_llm_prefill_16384": [1.0],
                      "jit_llm_prefill_8192": [0.5]},
        "op_s": {"tpu_custom_call f32[32,64,512]": attend_s,  # the latent
                 "tpu_custom_call f32[32,4096]": experts_s,   # the experts'
                 "tpu_custom_call f32[32,64,640]": 1.0,  # a two-pool form's
                 "fusion f32[32,64,512]": 1.0,           # not a kernel
                 "tpu_custom_call f32[8192,4096]": 1.0}}}  # a prefill's


def steps(live, kv, hit, landed, n=4, held=112):
    return ([span("engine.decode.dispatch", kv_tokens=kv) for _ in range(n)]
            + [span("engine.decode.fetch", experts_hit=hit,
                    experts_streamed=hit, experts_held=held,
                    local_choices=landed, choices=live * 7 * 8)
               for _ in range(n)])


PREFILLS = [span("engine.prefill", prompt_tokens=12000, bucket=16384),
            span("engine.prefill", prompt_tokens=6000, bucket=8192)]


def test_latent_kernel_roofline_counts_a_row_once(monkeypatch):
    reader = common.load_module("layer_metrics", READERS[0])
    fake(monkeypatch, steps(24, 216_000, 60, 160))
    pairs = 216_000 * 8
    by_bytes = pairs * 1152 / 819e9
    by_flops = pairs * 139_264 / 197e12
    assert by_bytes > by_flops  # under the ridge: memory bounds it
    assert reader.read(record(), CTX) == pytest.approx(
        100 * by_bytes / 0.004)
    assert 55 < reader.read(record(), CTX) < 65
    # the row as stored (640 columns), or read as K and again as V, would
    # pass the count by a ninth and by a factor of two
    assert 1280 / 1152 > 1.11
    # a faster chip's memory would make the operations the bound
    ctx = {"config": CFG, "peak": {"hbm_bytes_per_s": 4e12,
                                   "bf16_flops": 197e12}}
    assert reader.read(record(), ctx) == pytest.approx(
        100 * by_flops / 0.004)


def test_whole_step_roofline_counts_weights_by_hit_and_rows_once(
        monkeypatch):
    reader = common.load_module("layer_metrics", READERS[1])
    fake(monkeypatch, steps(24, 216_000, 60, 160))
    need = costs.decode_bytes(CFG, 216_000, 60)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 819e9 / 0.014)
    assert 60 < reader.read(record(), CTX) < 75


def test_prefill_mfu_counts_real_rows(monkeypatch):
    reader = common.load_module("layer_metrics", READERS[2])
    fake(monkeypatch, steps(24, 216_000, 60, 168) + PREFILLS)
    share = 168 / (24 * 7 * 8)
    need = costs.prefill_flops(CFG, 12000, share) \
        + costs.prefill_flops(CFG, 6000, share)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 197e12 / 1.5)
    assert reader.read(record(), CTX) < 100
    # the buckets' padding would be counted as work otherwise
    assert costs.prefill_flops(CFG, 16384, share) \
        > 1.5 * costs.prefill_flops(CFG, 12000, share)


def test_held_experts_roofline_reads_the_kernel_by_its_shape(monkeypatch):
    reader = common.load_module("layer_metrics", READERS[3])
    fake(monkeypatch, steps(24, 216_000, 60, 160))
    need = 60 * 25_165_824 * 2
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 819e9 / 0.006)
    assert 55 < reader.read(record(), CTX) < 70


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["no_trace", "no_spans", "other_model",
                                  "no_peak"])
def test_nothing_to_read_is_none(monkeypatch, name, case):
    """A run with no profile, a program that recorded no spans, a
    configuration of another family (GLM-5's and Ling's also have a
    ``kv_lora_rank``), a device with no peaks on file: None, and nothing
    raised."""
    reader = common.load_module("layer_metrics", name)
    spans = steps(24, 216_000, 60, 160) + PREFILLS
    fake(monkeypatch, [] if case == "no_spans" else spans)
    rec = {"trace": None} if case == "no_trace" else record()
    ctx = dict(CTX)
    if case == "no_peak":
        del ctx["peak"]
    if case == "other_model":
        for other in ("ling3_flash_vl.json", "glm5_744b_a40b.json"):
            ctx["config"] = common.load_json("configs", other)
            assert reader.read(rec, ctx) is None
    assert reader.read(rec, ctx) is None


def test_the_shared_counters_read_this_cell_too(monkeypatch):
    fake(monkeypatch, steps(24, 216_000, 60, 160))
    hit = common.load_module("layer_metrics", "held_experts_hit_share")
    local = common.load_module("layer_metrics", "local_choice_share")
    assert hit.read(record(), CTX) == pytest.approx(100 * 60 / 112)
    assert local.read(record(), CTX) == pytest.approx(100 * 160 / 1344)


# ---- the driver's limits ----------------------------------------------------
def sound_check(long: bool):
    ref = common.load_traffic(TRAFFIC)["reference"]
    limits = ref["long"] if long else ref
    check = {"tokens": limits["new_tokens"], "logprob_max_err": 0.0,
             "argmax_margin_max": 0.0, "choice_slack": 0.0,
             "choice_overlap": 1.0}
    if not long:
        check["branch_rel_err"] = dict.fromkeys(serve_latent_moe.PARTS, 0.0)
    return check, limits


@pytest.mark.parametrize("long", [False, True])
def test_within_holds_every_limit_the_comparison_names(long):
    check, limits = sound_check(long)
    assert serve_latent_moe.within(check, limits)
    worse = {"tokens": check["tokens"] - 1,
             "logprob_max_err": limits["logprob_tolerance"] * 1.01,
             "argmax_margin_max": limits["logprob_tolerance"] * 1.01,
             "choice_slack": limits["choice_slack_max"] * 1.01,
             "choice_overlap": limits["choice_overlap_min"] - 0.01}
    for key, value in worse.items():
        assert not serve_latent_moe.within({**check, key: value},
                                           limits), key
    if not long:
        for part in serve_latent_moe.PARTS:
            off = dict(check["branch_rel_err"])
            off[part] = limits["branch_rel_err_max"][part] * 1.01
            assert not serve_latent_moe.within(
                {**check, "branch_rel_err": off}, limits), part


def test_the_two_comparisons_are_the_issues():
    ref = common.load_traffic(TRAFFIC)["reference"]
    assert (ref["prompt_tokens"], ref["new_tokens"]) == (48, 8)
    assert (ref["long"]["prompt_tokens"], ref["long"]["new_tokens"]) \
        == (12000, 8)
    # the long one: the 16,384 bucket with 4,384 rows of padding, every
    # decode step past YaRN's original context
    yarn = CFG["rope_scaling"]
    assert 8192 < 12000 <= 16384 and 16384 - 12000 == 4384
    assert 12000 > yarn["original_max_position_embeddings"]
    assert set(ref["branch_rel_err_max"]) == set(serve_latent_moe.PARTS)
    assert "branch_rel_err_max" not in ref["long"]
    for block in (ref, ref["long"]):
        assert len(block["why"]) > 100
    assert len(ref["limits_reason"]) > 200


@pytest.mark.timeout(600)
def test_an_altered_answer_is_not_correct():
    """The driver's comparison at tiny widths, on the CPU: the engine's own
    answer is ``within`` limits a thousand times tighter than the cell's
    (float32 on both sides); the same answer with one token's
    log-probability moved, with one row's experts swapped for others, or
    computed under a plain rope, is not."""
    import dataclasses

    import numpy as np

    from benchmark.reference import sarvam_105b as ref
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    model, params = build_model("latent_moe", {"dtype": "float32"}, seed=3)
    c = model.config
    cfg = {**{k: getattr(c, k) for k in (
        "rms_norm_eps", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "rope_theta", "num_experts_per_tok",
        "routed_scaling_factor", "expert_offset")},
        "rope_scaling": dataclasses.asdict(c.rope_scaling),
        "serve": {"page_size": 8}}
    limits = {"new_tokens": 6, "logprob_tolerance": 1e-4,
              "choice_slack_max": 1e-5, "choice_overlap_min": 0.99,
              "branch_rel_err_max": dict.fromkeys(serve_latent_moe.PARTS,
                                                  1e-4)}
    prompt = serve_decoder.reference_prompt(45, 5600000101, c.vocab_size)

    def answer(m):
        eng = LLMEngine(m, params, max_slots=2, page_size=8, max_ctx=128,
                        chunk_tokens=1, record_experts=True)
        try:
            return eng.rollout(eng.submit(prompt, 6, record_experts=True),
                               timeout=300.0)
        finally:
            eng.close()

    got = answer(model)
    check = serve_latent_moe.compare(ref, cfg, model, params, prompt, got,
                                     True)
    assert serve_latent_moe.within(check, limits), check
    moved = dict(got, logprobs=[got["logprobs"][0] - 0.01]
                 + got["logprobs"][1:])
    assert not serve_latent_moe.within(serve_latent_moe.compare(
        ref, cfg, model, params, prompt, moved, False), limits)
    experts = np.array(got["experts"])
    experts[7] = (experts[7] + 5) % c.num_experts
    swapped = serve_latent_moe.compare(
        ref, cfg, model, params, prompt, dict(got, experts=experts), False)
    assert swapped["choice_slack"] > limits["choice_slack_max"]
    assert not serve_latent_moe.within(swapped, limits)
    plain = type(model)(dataclasses.replace(c, rope_scaling=None))
    assert not serve_latent_moe.within(serve_latent_moe.compare(
        ref, cfg, model, params, prompt, answer(plain), False), limits)


# ---- the manifest and the traffic ------------------------------------------
def test_the_cell_is_in_the_manifest_with_its_entries_and_files():
    """The manifest stands with the cell, its configuration and its four
    readers in it; each is found by name."""
    manifest = manifest_check.load()
    assert manifest_check.faults(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0])
    assert names[at:at + len(READERS)] == list(READERS)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sarvam_105b", TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "32 slots" in cell["why"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "sarvam_105b")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\t" not in text, text
    traffic = common.load_traffic(cell["traffic"])
    assert common.load_module("drivers", traffic["driver"]) \
        is serve_latent_moe
    assert common.load_module("reference", cell["config"]) is not None
    mine = [m for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert set(READERS) <= {m["name"] for m in mine}
    for name in READERS:  # this cell's alone
        assert next(m for m in mine if m["name"] == name)["workloads"] \
            == [CELL]
    judged = {m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert judged == {"serve_tokens_per_s", "setup_s"}
    assert {"gap_p50_ms", "gap_p95_ms", "ttft_p95_ms", "engine_step_ms",
            "held_experts_hit_share", "local_choice_share",
            "peak_hbm_share.serve", "device_idle_share.serve",
            "queue_wait_p50_ms", "setup_compile_s",
            "compile_cache_hit_share"} <= {m["name"] for m in mine}
    for m in mine:
        assert common.load_module("layer_metrics", m["name"]) is not None
        assert m["moves"] in judged
        assert "roofline" not in m["name"] or m["unit"] == "%"
    # no other cell's own readers were handed this cell
    assert not any(m["name"].startswith(("sparse_", "dsa_", "kda_"))
                   for m in mine)


def test_the_traffic_is_the_issues_and_says_where_its_rate_comes_from():
    t = common.load_traffic(TRAFFIC)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                  "sigma": 0.5, "min": 2048, "max": 15360}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 384,
                                  "sigma": 0.6, "min": 64, "max": 1024}
    assert 2 <= t["clients"] <= 4
    assert (t["preroll_s"], t["max_total_tokens"]) == (30, 16384)
    assert t["max_total_tokens"] <= CFG["serve"]["max_ctx"] == 16384
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * t["knee_per_s"], rel=0.03)
    assert t["knee_note"].count("/s") >= 4


def test_every_seed_meets_one_realisation():
    t = common.load_traffic(TRAFFIC)
    a = loadgen.build_schedule(t, 3000000011, CFG["vocab_size"], 75.0)
    b = loadgen.build_schedule(t, 7, CFG["vocab_size"], 75.0)
    shape = lambda s: [(r["due_s"], len(r["prompt"]),  # noqa: E731
                        r["max_new_tokens"]) for r in s]
    assert shape(a) == shape(b) and a
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 16384 for r in a)
    assert all(2048 - 1024 <= len(r["prompt"]) <= 15360 for r in a)
    assert all(0 <= tok < 32768 for r in a for tok in r["prompt"])
    assert all(64 <= r["max_new_tokens"] <= 1024 for r in a)


# ---- the rehearsal ---------------------------------------------------------
def test_the_rehearsal_s_tiny_files_shrink_this_cell():
    from benchmark.rehearsal import rehearse

    over = rehearse.tiny_overrides(CELL)
    assert over["config"]["serve"]["max_ctx"] == 128
    yarn = over["config"]["rope_scaling"]
    long = over["traffic"]["reference"]["long"]
    # the long comparison's rows lie past tiny's original context too
    assert long["prompt_tokens"] > yarn["original_max_position_embeddings"]
    for name in ("config.sarvam_105b.json", "driver.serve_latent_moe.json"):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "rehearsal", "tiny", name))


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
