"""What PR 53 added to the benchmark, checked by hand-counted numbers: the
configuration file against the catalog's values, ``costs_sparse_moe``
against ``jax.eval_shape`` of the program's own init (to the parameter) and
against the issue's sums, the five new readers on made-up records (among
them records whose share would pass 100% if every cached row were counted
as read), the driver's limits, the traffic, and the rehearsal of the cell at
toy sizes with its tiny files.  The cell and its entries are found by name
(``test_ling_cell.py``'s way): a later cell moves nothing here.

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

from benchmark import (common, costs_sparse_moe as costs,  # noqa: E402
                       loadgen, manifest_check, program_spans)
from benchmark.drivers import serve_decoder, serve_sparse_moe  # noqa: E402

CFG = common.load_json("configs", "glm5_744b_a40b.json")
CTX = {"config": CFG, "peak": {"hbm_bytes_per_s": 819e9,
                               "bf16_flops": 197e12}}
CELL = "glm5_serve_longdoc"
TRAFFIC = "glm5_longdoc_steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# config.json of zai-org/GLM-5: every width
PUBLISHED = {
    "hidden_size": 6144, "intermediate_size": 12288,
    "moe_intermediate_size": 2048, "num_attention_heads": 64,
    "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048,
    "num_experts_per_tok": 8, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rope_interleave": True, "indexer_rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "rms_norm_eps": 1e-05, "max_position_embeddings": 202752,
    "num_nextn_predict_layers": 1, "model_type": "glm_moe_dsa"}


# ---- the configuration file ------------------------------------------------
def test_every_published_width_is_as_published():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    assert CFG["reduced_from"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880}
    # the cut: the dense layer once and four layers after it, the router's
    # width kept, the floors held
    assert (CFG["num_hidden_layers"], CFG["first_k_dense_replace"]) == (5, 1)
    assert costs.layers(CFG) == {"attn": 5, "dense": 1, "moe": 4}
    assert CFG["router_experts"] == 256 and CFG["n_routed_experts"] == 16
    assert CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 == CFG["reduced_from"]["vocab_size"]
    assert CFG["rope_theta"] == CFG["rope_parameters"]["rope_theta"]
    assert "16 chips share each layer" in CFG["deployment"]
    assert "other 73" in CFG["deployment"]
    for key in ("layer_equations", "index_key_norm", "index_rope",
                "index_scales", "index_inputs", "selection", "rope",
                "router", "tie_word_embeddings", "num_nextn_predict_layers"):
        assert key in CFG["assumed"], key
    s = CFG["serve"]
    assert (s["max_slots"], s["page_size"], s["max_ctx"],
            s["chunk_tokens"]) == (16, 16, 16384, 1)


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
    from ray_tpu.models.glm_dsa import GlmDsaConfig

    kw = serve_decoder.model_kw(CFG)
    assert kw.pop("tiny") is False
    return GlmDsaConfig(**kw)


def test_model_kw_is_built_from_the_file_s_keys():
    from ray_tpu.models.glm_dsa import GlmDsa

    c = _program_config()
    assert (c.num_layers, c.experts_held, c.num_experts,
            c.expert_offset) == (5, 16, 256, 0)
    assert (c.num_kv_heads, c.head_dim, c.qk_head_dim) == (1, 576, 256)
    assert (c.index_topk, c.index_n_heads, c.index_head_dim) == (2048, 32,
                                                                  128)
    assert c.vocab_size == 19360 and c.rope_theta == 1000000
    assert c.kv_lora_rank + c.index_head_dim == 640  # the V row, no pad
    assert GlmDsa(c).expert_layers == 4


# ---- the cost functions ----------------------------------------------------
def test_the_parameter_count_is_the_program_s_own_to_the_parameter():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.glm_dsa import GlmDsa

    model = GlmDsa(_program_config())
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == sum(
        costs.param_counts(CFG).values()) == 3_909_632_768
    assert sum(x.size * x.dtype.itemsize for x in leaves) \
        == costs.memory_sum(CFG)["weights"] == 7_831_850_496


def test_the_parts_by_hand():
    per = costs.part_params(CFG)
    assert per["attn"] == 165_022_208 and per["indexer"] == 9_371_904
    assert per["shared"] == costs.expert_params(CFG) == 37_748_736
    assert per["router"] == 1_573_120 and per["norms"] == 12_288
    outside = per["attn"] + per["indexer"] + per["shared"] \
        + per["router"] + per["norms"]
    assert outside == 213_728_256
    assert per["attn"] + per["indexer"] + per["norms"] + per["dense"] \
        == 400_898_816


def test_the_memory_sum_of_the_issue():
    m = costs.memory_sum(CFG)
    # 16,385 pages x 16 rows x 5 layers x 2 pools x 640 columns x 2 B: the
    # index key rides the V row, no third pool
    assert m["page_pool"] == 16_385 * 16 * 5 * 2 * 640 * 2 == 3_355_648_000
    assert 11.1e9 < sum(m.values()) < 11.3e9
    assert sum(m.values()) > 0.25 * 16.9e9


def test_decode_bytes_by_hand():
    # 8 slots at 9,000 cached rows, 5 layers: 360,000 index keys of 256 B;
    # 8 x 5 x 2,048 rows of 1,280 B selected; 20 held experts hit
    got = costs.decode_bytes(CFG, 360_000, 81_920, 20)
    counts = costs.param_counts(CFG)
    assert got == (counts["streamed"] * 2 + counts["router"] * 4
                   + 20 * 75_497_472 + 360_000 * 256 + 81_920 * 1280)
    # what dense attention would read of the same cache, K and V rows
    dense = 360_000 * 2 * 1280
    assert dense / (360_000 * 256 + 81_920 * 1280) > 4


def test_prefill_flops_by_hand():
    assert costs.selected_pairs(CFG, 100) == 5050
    assert costs.selected_pairs(CFG, 4096) == 2048 * 2049 / 2 + 2048 * 2048
    one = costs.prefill_flops(CFG, 1, 0.0)
    per = costs.part_params(CFG)
    tokenwise = (5 * 2 * (per["attn"] - 2560 + per["indexer"] - 256)
                 + 2 * per["dense"]
                 + 4 * 2 * (6144 * 256 + per["shared"]))
    assert one == tokenwise + 5 * (2 * 32 * 128 + 2 * 64 * 512) \
        + 2 * 6144 * 19360
    # at 12k rows the attention over min(t + 1, 2048) rows a query is well
    # under the dense causal attention's half-square
    n = 12_288
    sparse = 5 * 2 * 64 * 512 * costs.selected_pairs(CFG, n)
    assert sparse < 0.31 * (5 * 2 * 64 * 512 * n * (n + 1) / 2)


# ---- the readers ------------------------------------------------------------
def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def span(name, **args):
    return {"name": name, "start": 0.0, "end": 1.0, "args": args}


def record(ms=12.0, steps=4, index_s=0.004, attend_s=0.002):
    return {"trace": {
        "program_s": {"jit_llm_decode": [ms / 1e3] * steps,
                      "jit_llm_prefill_16384": [2.0, 2.0]},
        "op_s": {"tpu_custom_call f32[16,1,16384]": index_s,
                 "tpu_custom_call f32[16,6144]": 1.0,      # the experts'
                 # the gather by row under the name the chip gave it (my
                 # chip runs, PR 53: 2.59 ms of a 12.8 ms step), its mask,
                 # the scores and the softmax
                 "fusion bf16[32768,640]": attend_s / 2,
                 "fusion bf16[16,2048,640]": attend_s / 4,
                 "fusion f32[16,64,2048]": attend_s / 8,
                 "fusion bf16[16,64,2048]": attend_s / 8,
                 "fusion f32[16,64,512]": 1.0,             # not told by it
                 "sort f32[16,16385]": 1.0}}}


def steps(live, kv, read, hit, landed, n=4, held=64):
    return ([span("engine.decode.dispatch", kv_tokens=kv, index_rows=5 * kv)
             for _ in range(n)]
            + [span("engine.decode.fetch", experts_hit=hit,
                    experts_streamed=hit, experts_held=held,
                    local_choices=landed, choices=live * 4 * 8,
                    kv_rows_read=read) for _ in range(n)])


PREFILLS = [span("engine.prefill", prompt_tokens=9000, bucket=16384,
                 selecting_rows=6952),
            span("engine.prefill", prompt_tokens=5000, bucket=8192,
                 selecting_rows=2952)]


def test_whole_step_roofline_counts_scored_keys_and_selected_rows(
        monkeypatch):
    reader = common.load_module("layer_metrics", "sparse_mla_decode_roofline")
    fake(monkeypatch, steps(8, 72_000, 81_920, 20, 16))
    need = costs.decode_bytes(CFG, 360_000, 81_920, 20)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 819e9 / 0.012)
    assert 40 < reader.read(record(), CTX) < 60
    # every cached row read as dense attention reads it, K and V, would
    # pass what the selection's count allows by far
    dense = need + 360_000 * 2 * 1280
    assert dense / need > 1.15


def test_indexer_roofline_reads_the_kernel_by_its_shape(monkeypatch):
    reader = common.load_module("layer_metrics", "dsa_indexer_roofline")
    fake(monkeypatch, steps(8, 72_000, 81_920, 20, 16))
    # 360,000 keys x 256 B / 819 GB/s = 0.1125 ms of 1 ms a step
    assert reader.read(record(), CTX) == pytest.approx(11.25, abs=0.01)


def test_selected_row_roofline_sums_the_gather_and_the_softmax(monkeypatch):
    reader = common.load_module("layer_metrics", "sparse_paged_attn_roofline")
    fake(monkeypatch, steps(8, 72_000, 81_920, 20, 16))
    # 81,920 rows x 1,280 B / 819 GB/s = 0.128 ms of 0.5 ms a step
    assert reader.read(record(), CTX) == pytest.approx(25.6, abs=0.01)
    # the gather is the operation that reads those bytes: a trace that
    # holds the softmax alone has half the time and reads twice the share,
    # so the reader must have counted the gather in the line above
    softmax_only = record()
    del softmax_only["trace"]["op_s"]["fusion bf16[32768,640]"]
    assert reader.read(softmax_only, CTX) == pytest.approx(51.2, abs=0.02)


def test_prefill_mfu_counts_real_rows_and_selected_pairs(monkeypatch):
    reader = common.load_module("layer_metrics", "sparse_mla_prefill_mfu")
    fake(monkeypatch, steps(8, 72_000, 81_920, 20, 16) + PREFILLS)
    share = 16 / (8 * 4 * 8)
    need = sum(costs.prefill_flops(CFG, n, share) for n in (9000, 5000))
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 197e12 / 4.0)
    assert reader.read(record(), CTX) < 100


def test_selected_share_is_rows_read_over_rows_cached(monkeypatch):
    reader = common.load_module("layer_metrics", "dsa_selected_share")
    fake(monkeypatch, steps(8, 72_000, 81_920, 20, 16))
    assert reader.read(record(), {}) == pytest.approx(
        100 * 81_920 / 360_000)


READERS = ["sparse_mla_prefill_mfu", "sparse_mla_decode_roofline",
           "dsa_indexer_roofline", "sparse_paged_attn_roofline",
           "dsa_selected_share"]


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["no_trace", "parents_spans", "other_model",
                                  "no_peak"])
def test_nothing_to_read_is_none(monkeypatch, name, case):
    """A run with no profile, a program whose spans lack the arguments (the
    parent's), a configuration of another family, a device with no peaks on
    file: None, and nothing raised.  (``dsa_selected_share`` is a counter:
    it needs neither profile, peak nor configuration, only the spans.)"""
    reader = common.load_module("layer_metrics", name)
    spans = steps(8, 72_000, 81_920, 20, 16) + PREFILLS
    if case == "parents_spans":
        spans = [span("engine.decode.dispatch", kv_tokens=72_000),
                 span("engine.decode.fetch", experts_hit=3, experts_held=64,
                      local_choices=3, choices=256),
                 span("engine.prefill", prompt_tokens=352, bucket=512)]
    fake(monkeypatch, spans)
    rec = {"trace": None} if case == "no_trace" else record()
    ctx = dict(CTX)
    if case == "other_model":
        ctx["config"] = common.load_json("configs", "ling3_flash_vl.json")
    if case == "no_peak":
        del ctx["peak"]
    got = reader.read(rec, ctx)
    if name == "dsa_selected_share" and case != "parents_spans":
        assert got == pytest.approx(100 * 81_920 / 360_000)
    else:
        assert got is None


def test_the_shared_counters_read_this_cell_too(monkeypatch):
    fake(monkeypatch, steps(8, 72_000, 81_920, 20, 16))
    hit = common.load_module("layer_metrics", "held_experts_hit_share")
    local = common.load_module("layer_metrics", "local_choice_share")
    assert hit.read(record(), CTX) == pytest.approx(100 * 20 / 64)
    assert local.read(record(), CTX) == pytest.approx(100 * 16 / 256)


# ---- the driver's limits ----------------------------------------------------
def sound_check(long: bool):
    ref = common.load_traffic(TRAFFIC)["reference"]
    limits = ref["long"] if long else ref
    check = {"tokens": limits["new_tokens"], "logprob_max_err": 0.0,
             "argmax_margin_max": 0.0, "selection_agreement": 1.0,
             "decode_selection_agreement": 1.0, "index_score_err": 0.0, "choice_slack": 0.0,
             "choice_overlap": 1.0}
    if not long:
        check["branch_rel_err"] = dict.fromkeys(serve_sparse_moe.PARTS, 0.0)
    return check, limits


@pytest.mark.parametrize("long", [False, True])
def test_within_holds_every_limit_the_comparison_names(long):
    check, limits = sound_check(long)
    assert serve_sparse_moe.within(check, limits)
    worse = {"tokens": check["tokens"] - 1,
             "logprob_max_err": limits["logprob_tolerance"] * 1.01,
             "argmax_margin_max": limits["logprob_tolerance"] * 1.01,
             "choice_slack": limits["choice_slack_max"] * 1.01,
             "choice_overlap": limits["choice_overlap_min"] - 0.01}
    worse["decode_selection_agreement"] = \
        limits["selection_agreement_min"] - 0.01
    if long:
        worse["selection_agreement"] = \
            limits["selection_agreement_min"] - 0.01
        worse["index_score_err"] = limits["index_score_err_max"] * 1.01
    for key, value in worse.items():
        assert not serve_sparse_moe.within({**check, key: value},
                                           limits), key
    if not long:
        for part in serve_sparse_moe.PARTS:
            off = dict(check["branch_rel_err"])
            off[part] = limits["branch_rel_err_max"][part] * 1.01
            assert not serve_sparse_moe.within(
                {**check, "branch_rel_err": off}, limits), part


def test_the_two_comparisons_are_the_issues():
    ref = common.load_traffic(TRAFFIC)["reference"]
    assert (ref["prompt_tokens"], ref["new_tokens"]) == (48, 8)
    assert (ref["long"]["prompt_tokens"], ref["long"]["new_tokens"]) \
        == (9000, 8)
    # the short one is plain MLA (no row selects); the long one's
    # selection binds on 77% of its rows, in the 16,384 bucket
    assert ref["prompt_tokens"] + ref["new_tokens"] < CFG["index_topk"]
    assert round(100 * (9000 - 2048) / 9000) == 77
    assert set(ref["branch_rel_err_max"]) == set(serve_sparse_moe.PARTS)
    assert "branch_rel_err_max" not in ref["long"]
    assert 0 < ref["long"]["selection_agreement_min"] < 1
    for block in (ref, ref["long"]):
        assert len(block["why"]) > 100
    assert len(ref["limits_reason"]) > 200


# ---- the manifest and the traffic ------------------------------------------
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
        "glm5_744b_a40b", TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "16 slots" in cell["why"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "glm5_744b_a40b")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\t" not in text, text
    traffic = common.load_traffic(cell["traffic"])
    assert common.load_module("drivers", traffic["driver"]) \
        is serve_sparse_moe
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
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                  "sigma": 0.5, "min": 4096, "max": 15872}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 128,
                                  "sigma": 0.6, "min": 32, "max": 384}
    assert (t["clients"], t["preroll_s"], t["max_total_tokens"]) == (
        2, 30, 16384)
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
    assert all(4096 - 384 <= len(r["prompt"]) <= 15872 for r in a)
    assert all(0 <= tok < 19360 for r in a for tok in r["prompt"])
    # every decode step selects: no prompt is as short as index_topk
    assert min(len(r["prompt"]) for r in a) > CFG["index_topk"]


# ---- the rehearsal ---------------------------------------------------------
def test_the_rehearsal_s_tiny_files_shrink_this_cell():
    from benchmark.rehearsal import rehearse

    over = rehearse.tiny_overrides(CELL)
    assert over["config"]["index_topk"] == 16
    assert over["config"]["serve"]["max_ctx"] == 128
    long = over["traffic"]["reference"]["long"]
    assert long["prompt_tokens"] > over["config"]["index_topk"]  # it binds
    for name in ("config.glm5_744b_a40b.json",
                 "driver.serve_sparse_moe.json"):
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
    assert "held_experts_hit_share" in line and "dsa_selected_share" in line
