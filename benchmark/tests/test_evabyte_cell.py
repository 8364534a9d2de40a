"""What PR 61 added to the benchmark, checked by hand-counted numbers: the
configuration file against the catalog's values, ``costs_eva`` against
``jax.eval_shape`` of the program's own init (to the parameter) and against
the issue's sums (202,391,552 a layer; 1,630,932,992 in the cut; 256 pages a
slot), the five new readers on made-up records (among them records whose
share would pass 100% if a position were counted as a row), the driver's
limits and an altered answer that turns ``correct`` false, the traffic, and
the rehearsal of the cell at toy sizes with its tiny files.  The cell and its
entries are found by name (``test_sarvam_cell.py``'s way): a later cell moves
nothing here.

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

from benchmark import (common, costs_eva as costs, loadgen,  # noqa: E402
                       manifest_check, program_spans)
from benchmark.drivers import serve_decoder, serve_eva  # noqa: E402

CFG = common.load_json("configs", "evabyte_6b.json")
CTX = {"config": CFG, "peak": {"hbm_bytes_per_s": 819e9,
                               "bf16_flops": 197e12}}
CELL = "evabyte_serve_bytedoc"
TRAFFIC = "evabyte_bytedoc_steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("eva_paged_attn_roofline", "eva_decode_roofline",
           "eva_prefill_mfu", "eva_cache_rows_share",
           "eva_page_gather_roofline")
# config.json of EvaByte/EvaByte: every width, and the keys that name EVA
PUBLISHED = {
    "hidden_size": 4096, "intermediate_size": 11008,
    "num_attention_heads": 32, "num_key_value_heads": 32,
    "window_size": 2048, "chunk_size": 16, "num_pred_heads": 8,
    "vocab_size": 320, "max_position_embeddings": 32768,
    "rope_theta": 100000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "attention_class": "eva", "norm_add_unit_offset": True,
    "fp32_skip_add": True, "fp32_logits": True, "mixedp_attn": True,
    "fp32_ln": False, "attention_bias": False,
    "tie_word_embeddings": False, "model_type": "evabyte"}


# ---- the configuration file ------------------------------------------------
def test_every_published_width_is_as_published():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["reduced_from"] == {"num_hidden_layers": 32}
    assert CFG["num_hidden_layers"] == 8
    for key in ("pooling", "rope", "num_pred_heads", "precision_keys",
                "keys_that_build_nothing", "init"):
        assert CFG["assumed"][key]
    assert "4 pipeline stages" in CFG["deployment"]
    assert "LAST stage" in CFG["deployment"]
    assert "16 slots" in CFG["why_8_layers"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_the_catalog_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CFG["source"])
    assert row["name"] == "EvaByte"
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    for key in CFG["reduced"]:
        assert CFG["reduced_from"][key] == row["config"][key], key


def _program_config():
    from ray_tpu.models.eva_decoder import EvaDecoderConfig

    kw = serve_decoder.model_kw(CFG)
    assert kw.pop("tiny") is False
    return EvaDecoderConfig(**kw)


def test_model_kw_is_built_from_the_file_s_keys():
    from ray_tpu.models.eva_decoder import EvaDecoder

    c = _program_config()
    assert (c.num_layers, c.num_heads, c.num_kv_heads, c.head_dim) \
        == (8, 32, 32, 128)
    assert (c.window_size, c.chunk_size, c.num_pred_heads, c.vocab_size) \
        == (2048, 16, 8, 320)
    assert c.rope_theta == 100000 and c.rms_norm_eps == 1e-5
    s = CFG["serve"]
    m = EvaDecoder(c).cache_map(s["page_size"], s["max_ctx"])
    assert (m.pages_per_slot, m.rows_per_slot) == (256, 4096)
    assert s["page_size"] == CFG["chunk_size"] and s["max_slots"] == 16


# ---- the cost functions ----------------------------------------------------
def test_the_parameter_count_is_the_program_s_own_to_the_parameter():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.eva_decoder import EvaDecoder

    model = EvaDecoder(_program_config())
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == costs.total_params(CFG) \
        == 1_630_932_992
    assert sum(x.size * x.dtype.itemsize for x in leaves) \
        == costs.memory_sum(CFG)["weights"]


def test_the_parts_by_hand():
    assert costs.layer_params(CFG) == 202_391_552 == (
        4 * 4096 * 4096 + 2 * 32 * 128 + 3 * 4096 * 11008 + 2 * 4096)
    counts = costs.param_counts(CFG)
    assert counts == {"embedding": 1_310_720, "head": 10_485_760,
                      "head_read": 1_310_720,
                      "layers": 8 * 202_391_552 + 4096}
    assert costs.total_params({**CFG, "num_hidden_layers": 32}) \
        == 6_488_330_240
    assert costs.row_width(CFG) == 4096


def test_the_memory_sum_of_the_issue():
    got = costs.memory_sum(CFG)
    assert got["pages_per_slot"] == 256
    assert got["page_pool"] == (16 * 256 + 1) * 2_097_152 == 8_592_031_744
    assert got["weights"] == 3_261_865_984
    # one row a token could not be held at all
    assert got["dense_page_pool"] == (16 * 2048 + 1) * 2_097_152
    assert got["dense_page_pool"] > 68.7e9
    assert 11.8e9 < got["weights"] + got["page_pool"] < 11.9e9


def test_decode_bytes_by_hand():
    rows, closed = 18_000, 0.75
    want = ((8 * 202_391_552 + 4096 + 1_310_720) * 2
            + rows * 8 * 2 * 4096 * 2 + closed * 8 * 16 * 2 * 4096 * 2)
    assert costs.decode_bytes(CFG, rows, closed) == want
    # the weights are most of a step of 12 live slots, the rows a half more
    assert 0.5 < costs.rows_bytes(CFG, rows * 8) / costs.streamed_bytes(CFG) \
        < 0.8
    assert costs.attend_flops(CFG, 1) / costs.rows_bytes(CFG, 1) == 1.0


def test_prefill_flops_by_hand():
    # inside one window: the causal half and no summary
    assert costs.attention_pairs(CFG, 2048) == 2048 * 2049 / 2
    # 6,140 rows: two whole windows and 2,044 rows that see 256 summaries
    assert costs.attention_pairs(CFG, 6140) == (
        2 * 2048 * 2049 / 2 + 2044 * 2045 / 2
        + 128 * 2048 + 256 * 2044)
    n = 8192
    pairs = costs.attention_pairs(CFG, n)
    assert pairs == 4 * 2048 * 2049 / 2 + 128 * 2048 * (1 + 2 + 3)
    want = 8 * (n * 2 * (4 * 4096 * 4096 + 3 * 4096 * 11008)
                + 4 * 4096 * pairs + n * 8 * 4096) + 2 * 4096 * 320
    assert costs.prefill_flops(CFG, n) == pytest.approx(want)
    # a cache of one row a token would multiply four times the pairs there
    assert n * (n + 1) / 2 > 3.3 * pairs


# ---- the readers ------------------------------------------------------------
def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def span(name, **args):
    return {"name": name, "start": 0.0, "end": 1.0, "args": args}


def record(ms=8.0, steps=4, attend_s=0.012, gather_s=0.0004):
    return {"trace": {
        "program_s": {"jit_llm_decode": [ms / 1e3] * steps,
                      "jit_llm_prefill_8192": [0.25],
                      "jit_llm_prefill_16384": [0.55]},
        "op_s": {"tpu_custom_call f32[16,32,4096]": attend_s,  # the paged
                 "tpu_custom_call bf16[8,16,16,4096]": gather_s,  # gather
                 "tpu_custom_call f32[16,32,640]": 1.0,  # another family's
                 "fusion f32[16,32,4096]": 1.0,           # not a kernel
                 "tpu_custom_call bf16[4,2048,4096]": 1.0}}}  # the flash


def steps(ctx, rows, closed, n=4):
    return [span("engine.decode.dispatch", kv_tokens=rows, ctx_tokens=ctx,
                 summary_rows=rows // 3, window_rows=rows - rows // 3,
                 chunks_closed=closed, windows_closed=0) for _ in range(n)]


PREFILLS = [span("engine.prefill", prompt_tokens=12000, bucket=16384),
            span("engine.prefill", prompt_tokens=6140, bucket=8192)]


def test_paged_kernel_roofline_counts_the_rows_read_not_the_positions(
        monkeypatch):
    reader = common.load_module("layer_metrics", READERS[0])
    fake(monkeypatch, steps(108_000, 18_000, 1))
    pairs = 18_000 * 8
    by_bytes = pairs * 2 * 4096 * 2 / 819e9
    assert by_bytes > pairs * 4 * 4096 / 197e12  # memory bounds it
    assert reader.read(record(), CTX) == pytest.approx(
        100 * by_bytes / 0.003)
    assert 90 < reader.read(record(), CTX) < 100
    # the positions held, counted as rows, would pass 100% six times over
    assert 108_000 / 18_000 == 6


def test_whole_step_roofline_counts_weights_rows_and_closed_pages(
        monkeypatch):
    reader = common.load_module("layer_metrics", READERS[1])
    fake(monkeypatch, steps(108_000, 18_000, 1))
    need = costs.decode_bytes(CFG, 18_000, 1)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 819e9 / 0.008)
    assert 75 < reader.read(record(), CTX) < 90


def test_prefill_mfu_counts_real_rows(monkeypatch):
    reader = common.load_module("layer_metrics", READERS[2])
    fake(monkeypatch, steps(108_000, 18_000, 1) + PREFILLS)
    need = costs.prefill_flops(CFG, 12000) + costs.prefill_flops(CFG, 6140)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 197e12 / 0.8)
    assert reader.read(record(), CTX) < 100
    # the buckets' padding would be counted as work otherwise
    assert costs.prefill_flops(CFG, 8192) > 1.3 * costs.prefill_flops(
        CFG, 6140)


def test_cache_rows_share_is_rows_over_positions(monkeypatch):
    reader = common.load_module("layer_metrics", READERS[3])
    fake(monkeypatch, steps(108_000, 18_000, 1))
    assert reader.read(record(), CTX) == pytest.approx(100 / 6)
    # a program whose rows are its tokens says no ctx_tokens
    fake(monkeypatch, [span("engine.decode.dispatch", kv_tokens=5000)])
    assert reader.read(record(), CTX) is None


def test_page_gather_roofline_reads_the_kernel_by_its_shape(monkeypatch):
    reader = common.load_module("layer_metrics", READERS[4])
    fake(monkeypatch, steps(108_000, 18_000, 1))
    need = 1 * 8 * 16 * 2 * 4096 * 2
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 819e9 / 0.0001)
    assert reader.read(record(), CTX) < 10


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["no_trace", "no_spans", "other_model",
                                  "no_peak"])
def test_nothing_to_read_is_none(monkeypatch, name, case):
    """A run with no profile, a program that recorded no spans, a
    configuration of another family, a device with no peaks on file: None,
    and nothing raised (``eva_cache_rows_share`` reads counters alone: a
    profile, a peak and the family are nothing to it)."""
    reader = common.load_module("layer_metrics", name)
    spans = steps(108_000, 18_000, 1) + PREFILLS
    fake(monkeypatch, [] if case == "no_spans" else spans)
    rec = {"trace": None} if case == "no_trace" else record()
    ctx = dict(CTX)
    if case == "no_peak":
        del ctx["peak"]
    if case == "other_model":
        ctx["config"] = common.load_json("configs", "sarvam_105b.json")
    got = reader.read(rec, ctx)
    if name == "eva_cache_rows_share" and case != "no_spans":
        assert got == pytest.approx(100 / 6)
    else:
        assert got is None


def test_the_shared_counters_read_this_cell_too(monkeypatch):
    fake(monkeypatch, steps(108_000, 18_000, 1))
    reader = common.load_module("layer_metrics", "peak_hbm_share")
    rec = {"device": {"memory_peak_bytes": 15e9, "memory_limit_bytes": 16e9}}
    assert reader.read(rec, CTX) == pytest.approx(93.75)


# ---- the driver's comparison ------------------------------------------------
def sound_check(long: bool):
    return {"tokens": 12 if long else 8, "logprob_max_err": 0.03,
            "argmax_margin_max": 0.0,
            "branch_rel_err": {"attn": 0.008, "mlp": 0.018}}


@pytest.mark.parametrize("long", [False, True])
def test_within_holds_every_limit_the_comparison_names(long):
    ref = common.load_traffic(TRAFFIC)["reference"]
    limits = ref["long"] if long else ref
    check = sound_check(long)
    assert serve_eva.within(check, limits)
    tol = limits["logprob_tolerance"]
    for worse in ({"tokens": check["tokens"] - 1},
                  {"logprob_max_err": tol * 1.01},
                  {"argmax_margin_max": tol * 1.01},
                  {"branch_rel_err": {"attn": 0.008, "mlp": 1.01
                                      * limits["branch_rel_err_max"]["mlp"]}},
                  {"branch_rel_err": {"mlp": 0.018, "attn": 1.01
                                      * limits["branch_rel_err_max"]["attn"]}}
                  ):
        assert not serve_eva.within({**check, **worse}, limits), worse


def test_the_two_comparisons_are_the_issues():
    ref = common.load_traffic(TRAFFIC)["reference"]
    assert (ref["prompt_tokens"], ref["new_tokens"]) == (48, 8)
    long = ref["long"]
    assert (long["prompt_tokens"], long["new_tokens"]) == (6140, 12)
    # the answer crosses the end of window 2 and closes chunk 383 there
    assert 6140 < 6143 < 6144 <= 6140 + 12 - 1 and 6143 // 16 == 383
    for block in (ref, long):
        assert 0 < block["logprob_tolerance"] <= 0.15
        assert set(block["branch_rel_err_max"]) == set(serve_eva.PARTS)
    assert "limits_reason" in ref and "8-bit" in ref["limits_reason"]
    assert serve_decoder.comparisons(ref) == [ref, long]


@pytest.mark.timeout(600)
def test_an_altered_answer_is_not_correct():
    """The driver's comparison at tiny widths, on the CPU: the engine's own
    answer across a window's end is ``within`` limits a thousand times
    tighter than the cell's (float32 on both sides); the same answer with
    one token's log-probability moved, or computed with the last closed
    window's summaries missing, or with a chunk's mean in the learned
    pooling's place, is not."""
    from benchmark.reference import evabyte_6b as ref
    from benchmark.rehearsal import precision_probe_eva as probe
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    model, params = build_model("eva_decoder", {"dtype": "float32"}, seed=3)
    c = model.config
    cfg = {k: getattr(c, k) for k in (
        "num_hidden_layers", "rms_norm_eps", "num_attention_heads",
        "rope_theta", "window_size", "chunk_size", "num_pred_heads",
        "vocab_size")}
    limits = {"new_tokens": 12, "logprob_tolerance": 1e-4,
              "branch_rel_err_max": dict.fromkeys(serve_eva.PARTS, 1e-4)}
    prompt = serve_decoder.reference_prompt(188, 6100000101, c.vocab_size)

    def answer(m, p):
        eng = LLMEngine(m, p, max_slots=2, page_size=8, max_ctx=512,
                        chunk_tokens=1)
        try:
            return eng.rollout(eng.submit(prompt, 12), timeout=300.0)
        finally:
            eng.close()

    got = answer(model, params)
    check = serve_eva.compare(ref, cfg, model, params, prompt, got)
    assert serve_eva.within(check, limits), check
    assert check["rows"] == 199
    moved = dict(got, logprobs=[got["logprobs"][0] - 0.01]
                 + got["logprobs"][1:])
    assert not serve_eva.within(serve_eva.compare(
        ref, cfg, model, params, prompt, moved), limits)
    off_by_one = serve_eva.compare(
        ref, cfg, model, params, prompt,
        answer(probe.last_window_missing(model), params))
    assert off_by_one["logprob_max_err"] > 0.05
    assert not serve_eva.within(off_by_one, limits)
    flat = probe.mean_pooling(params)
    averaged = serve_eva.compare(
        ref, cfg, model, params, prompt, answer(model, flat),
        serve_eva.program_parts(model, flat, serve_eva.fed_rows(
            prompt, answer(model, flat))))
    assert averaged["branch_rel_err"]["attn"] > 0.01
    assert not serve_eva.within(averaged, limits)


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
        "evabyte_6b", TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "16 slots" in cell["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == "evabyte_6b")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\t" not in text, text
    traffic = common.load_traffic(cell["traffic"])
    assert common.load_module("drivers", traffic["driver"]) is serve_eva
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
            "peak_hbm_share.serve", "device_idle_share.serve",
            "queue_wait_p50_ms", "setup_compile_s", "reply_calls_per_step",
            "compile_cache_hit_share"} <= {m["name"] for m in mine}
    for m in mine:
        assert common.load_module("layer_metrics", m["name"]) is not None
        assert m["moves"] in judged
        assert "roofline" not in m["name"] or m["unit"] == "%"
    # no other cell's own readers were handed this cell
    assert not any(m["name"].startswith(
        ("sparse_", "dsa_", "kda_", "mla_", "held_", "local_"))
        for m in mine)


def test_the_traffic_is_the_issues_and_says_where_its_rate_comes_from():
    t = common.load_traffic(TRAFFIC)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                  "sigma": 0.6, "min": 2048, "max": 24576}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.6, "min": 128, "max": 2048}
    assert t["clients"] == 3
    assert (t["preroll_s"], t["max_total_tokens"]) == (30, 26624)
    assert t["max_total_tokens"] <= CFG["serve"]["max_ctx"] == 32768
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
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 26624 for r in a)
    assert all(2048 <= len(r["prompt"]) <= 24576 for r in a)
    assert all(0 <= tok < 320 for r in a for tok in r["prompt"])
    assert all(128 <= r["max_new_tokens"] <= 2048 for r in a)
    # every prompt spans one to twelve windows
    assert {len(r["prompt"]) // 2048 for r in a} <= set(range(1, 13))


# ---- the rehearsal ---------------------------------------------------------
def test_the_rehearsal_s_tiny_files_shrink_this_cell():
    from benchmark.rehearsal import rehearse

    over = rehearse.tiny_overrides(CELL)
    assert over["config"]["serve"]["max_ctx"] == 512
    assert over["config"]["serve"]["page_size"] \
        == over["config"]["chunk_size"] == 8
    long = over["traffic"]["reference"]["long"]
    # the long comparison's answer crosses a window's end at tiny sizes too
    window = over["config"]["window_size"]
    assert long["prompt_tokens"] < 3 * window \
        <= long["prompt_tokens"] + long["new_tokens"] - 1
    for name in ("config.evabyte_6b.json", "driver.serve_eva.json"):
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
    assert "eva_cache_rows_share" in line
