"""What PR 38 added to the benchmark, checked by hand-counted numbers: the
configuration file against the catalog's values, ``costs_hybrid`` against
the issue's sums (430.1M parameters a layer, 4.19 MB of state a slot a
layer, 12.9 GB held), the four new readers on made-up records (among them
records whose share would pass 100% if free slots, padding or whole pages
were counted), the driver's limits, and the pinned realisation of the
cell's traffic.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import hashlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import (common, costs_hybrid, loadgen,  # noqa: E402
                       program_spans)
from benchmark.drivers import serve_decoder, serve_hybrid  # noqa: E402

CFG = common.load_json("configs", "falcon_h1_34b.json")
CTX = {"config": CFG, "peak": {"hbm_bytes_per_s": 819e9,
                               "bf16_flops": 197e12}}
CELL = "falconh1_serve_steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# config.json of tiiuae/Falcon-H1-34B-Instruct, the keys that fix a shape or
# a number of the mathematics (the catalog's ``config``, where it is at hand)
PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 21504, "head_dim": 128,
    "num_attention_heads": 20, "num_key_value_heads": 4,
    "vocab_size": 261120, "max_position_embeddings": 262144,
    "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
    "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_expand": 2, "mlp_expansion_factor": 8,
    "rms_norm_eps": 1e-05, "rope_theta": 100000000000,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125,
    "key_multiplier": 0.011048543456039804,
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "mamba_norm_before_gate": False, "mamba_rms_norm": True,
    "tie_word_embeddings": False}


# ---- the configuration file ------------------------------------------------
def test_every_published_key_is_as_published():
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["reduced_from"] == {"num_hidden_layers": 72}
    assert 4 <= CFG["num_hidden_layers"] <= 6
    assert CFG["mamba_d_ssm"] == CFG["mamba_n_heads"] * CFG["mamba_d_head"]
    assert CFG["head_dim"] != CFG["hidden_size"] // CFG["num_attention_heads"]
    for key in ("assumed", "deployment"):
        assert CFG[key]


def test_the_file_holds_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert CFG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CFG.get(k) != v}
    assert differs == set(CFG["reduced"])


def test_model_kw_is_built_from_the_published_keys():
    kw = serve_decoder.model_kw(CFG)
    assert kw["head_dim"] == 128 and kw["num_kv_heads"] == 4
    assert kw["mamba_d_state"] == 256 and kw["tiny"] is False
    assert kw["ssm_multipliers"] == CFG["ssm_multipliers"]
    assert kw["max_position_embeddings"] == CFG["serve"]["max_ctx"]
    assert not [v for v in kw.values()
                if isinstance(v, str) and v.startswith("$")]


# ---- costs_hybrid -----------------------------------------------------------
def test_a_layer_by_hand():
    # attention: q and o 5120 x 2560 each, k and v 5120 x 512 each;
    # feed-forward 3 x 5120 x 21504; mixer: in_proj 5120 x 9248 (4096 +
    # 4096 + 512 + 512 + 32), out_proj 4096 x 5120, conv 4 x 5120 + 5120,
    # dt_bias, A_log and D 3 x 32, the gated norm 4096; two norms
    assert costs_hybrid.kv_width(CFG) == 512
    assert costs_hybrid.conv_dim(CFG) == 5120
    assert costs_hybrid.layer_params(CFG) == {
        "attention": 31_457_280, "ffn": 330_301_440,
        "mixer": 47_349_760 + 20_971_520 + 25_600 + 96 + 4096,
        "norms": 10_240}
    assert sum(costs_hybrid.layer_params(CFG).values()) == 430_120_032
    counts = costs_hybrid.param_counts(CFG)
    assert counts["embedding"] == 261120 * 5120 == 1_336_934_400
    assert counts["streamed"] == 6 * 430_120_032 + 5120 + 1_336_934_400


def test_a_slots_state_by_hand():
    # 32 heads x 128 x 256 float32; 3 rows of the 5120-wide conv input
    assert costs_hybrid.state_bytes(CFG) == {"ssm": 4_194_304,
                                             "conv": 30_720}
    assert costs_hybrid.state_step_bytes(CFG, 25) == 25 * 6 * 2 * 4_194_304


def test_the_memory_sum_of_the_issue():
    # weights 10.51 GB, pool 6,145 pages x 6 x 2 x 16 KB = 1.21 GB, state
    # 48 x 6 x 4.22 MB = 1.22 GB: 12.9 GB, 77% of the chip's 16.9
    got = costs_hybrid.memory_sum(CFG)
    assert got == {"weights": 2 * (6 * 430_120_032 + 5120
                                   + 2 * 1_336_934_400),
                   "page_pool": 6145 * 6 * 2 * 16 * 512 * 2,
                   "state": 48 * 6 * (4_194_304 + 30_720)}
    assert [round(v / 1e9, 2) for v in got.values()] == [10.51, 1.21, 1.22]
    assert 0.76 < sum(got.values()) / 16_909_336_064 < 0.78


def test_decode_bytes_by_hand():
    # 25 live slots, 10,000 cached rows: the streamed weights, the slots'
    # state and conv rows read and written, K and V at width 512
    got = costs_hybrid.hybrid_decode_bytes(CFG, 25, 10_000)
    assert got == (2 * (6 * 430_120_032 + 5120 + 1_336_934_400)
                   + 25 * 6 * 2 * 4_225_024
                   + 10_000 * 6 * 2 * 512 * 2) == 9_225_706_624


def test_prefill_flops_by_hand():
    # per token and layer 2 x (the matmul weights 430,084,096 + the conv's
    # 4 x 5120 taps + 3 multiply-adds on 4096 x 256 state elements);
    # 352 tokens see 62,128 pairs at 4 x 2560 each; the head once
    matmul = 31_457_280 + 330_301_440 + 47_349_760 + 20_971_520
    per_token = 2 * (matmul + 20_480 + 3_145_728)
    assert costs_hybrid.hybrid_prefill_flops(CFG, 352) == (
        6 * (352 * per_token + 10_240 * 62_128) + 2 * 5120 * 261120)


# ---- the readers ------------------------------------------------------------
def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def span(name, **args):
    return {"name": name, "start": 0.0, "end": 1.0, "args": args}


STATE_OP = "fusion f32[48,32,128,256]"
KERNEL = "tpu_custom_call f32[48,32,512]"


def record(ms=20.0, steps=4, state_s=0.01, kernel_s=0.004):
    return {"trace": {
        "program_s": {"jit_llm_decode": [ms / 1e3] * steps,
                      "jit_llm_prefill_512": [0.05, 0.05]},
        "op_s": {STATE_OP: state_s, KERNEL: kernel_s,
                 "tpu_custom_call f32[8192,1024]": 1.0,  # not the kernel
                 "fusion f32[1,32,128,256]": 1.0}}}      # not [slots, ...]


def steps(live, kv, n=4):
    return [span("engine.decode.dispatch", state_slots=live, kv_tokens=kv)
            for _ in range(n)]


def test_decode_roofline_counts_live_slots_only(monkeypatch):
    reader = common.load_module("layer_metrics", "hybrid_decode_roofline")
    fake(monkeypatch, steps(25, 10_000))
    # 9,225,706,624 bytes / 819 GB/s = 11.265 ms of a 20 ms step
    assert reader.read(record(), CTX) == pytest.approx(56.32, abs=0.01)
    # all 48 slots (what the program moves) would read 63.4%, and past
    # 100% on a step of 12 ms: the count is of the live ones
    fake(monkeypatch, steps(48, 10_000))
    assert reader.read(record(), CTX) == pytest.approx(63.44, abs=0.01)


def test_prefill_mfu_counts_real_rows_not_the_bucket(monkeypatch):
    reader = common.load_module("layer_metrics", "hybrid_prefill_mfu")
    fake(monkeypatch, [span("engine.prefill", scanned_rows=352,
                            padded_rows=160, prompt_tokens=352, bucket=512)
                       for _ in range(2)])
    need = 2 * costs_hybrid.hybrid_prefill_flops(CFG, 352)
    assert reader.read(record(), CTX) == pytest.approx(
        100 * need / 197e12 / 0.1)
    assert need / 2 < costs_hybrid.hybrid_prefill_flops(CFG, 512)


def test_state_roofline_reads_the_state_shaped_operations(monkeypatch):
    reader = common.load_module("layer_metrics", "ssm_state_roofline")
    fake(monkeypatch, steps(25, 10_000))
    # 25 x 6 x 2 x 4,194,304 bytes / 819 GB/s = 1.5363 ms of 2.5 ms a step
    assert reader.read(record(), CTX) == pytest.approx(61.45, abs=0.01)


def test_paged_attn_roofline_takes_the_published_head(monkeypatch):
    reader = common.load_module("layer_metrics", "hybrid_paged_attn_roofline")
    fake(monkeypatch, steps(25, 10_000))
    # 10,000 x 6 x 2 x 512 x 2 bytes / 819 GB/s = 0.15004 ms of 1 ms a step
    assert reader.read(record(), CTX) == pytest.approx(15.0, abs=0.01)


READERS = ["hybrid_decode_roofline", "hybrid_prefill_mfu",
           "ssm_state_roofline", "hybrid_paged_attn_roofline"]


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["no_trace", "no_spans", "other_model",
                                  "no_peak"])
def test_nothing_to_read_is_none(monkeypatch, name, case):
    """A run with no profile, a program whose spans lack the new arguments
    (the parent of this PR), a configuration with no mixer, a device with
    no peaks on file: None, and nothing raised."""
    reader = common.load_module("layer_metrics", name)
    spans = [] if case == "no_spans" else (
        steps(25, 10_000) + [span("engine.prefill", scanned_rows=352)])
    if case == "no_spans":  # the parent's spans: the old arguments alone
        spans = [span("engine.decode.dispatch", kv_tokens=10_000),
                 span("engine.prefill", prompt_tokens=352)]
    fake(monkeypatch, spans)
    rec = {"trace": None} if case == "no_trace" else record()
    ctx = dict(CTX)
    if case == "other_model":
        ctx["config"] = common.load_json("configs", "olmoe_1b_7b.json")
    if case == "no_peak":
        del ctx["peak"]
    if name == "hybrid_paged_attn_roofline" and case == "no_spans":
        assert reader.read(rec, ctx) is not None  # kv_tokens is the parent's
    else:
        assert reader.read(rec, ctx) is None


# ---- the driver's limits ----------------------------------------------------
def sound_check():
    return {"tokens": 8, "logprob_err_sigmas": 0.01,
            "argmax_margin_sigmas": 0.0,
            "branch_rel_err": {"mixer": 0.01, "attn": 0.01, "ffn": 0.01}}


def test_within_holds_every_branch_and_both_scale_free_errors():
    ref = common.load_traffic("falconh1_chat_steady")["reference"]
    assert serve_hybrid.within(sound_check(), ref)
    for spoil in ({"tokens": 7}, {"logprob_err_sigmas": 10.0},
                  {"argmax_margin_sigmas": 10.0},
                  *({"branch_rel_err": {**sound_check()["branch_rel_err"],
                                        b: 5.0}}
                    for b in serve_hybrid.BRANCHES)):
        assert not serve_hybrid.within({**sound_check(), **spoil}, ref)


def test_the_long_comparison_reaches_what_the_short_one_cannot():
    refs = serve_decoder.comparisons(
        common.load_traffic("falconh1_chat_steady")["reference"])
    assert [r["prompt_tokens"] for r in refs] == [48, 900]
    for r in refs:
        assert r["new_tokens"] == 8
        assert 0 < r["logprob_sigmas_max"] and 0 < r["branch_rel_err_max"] < 1
    assert refs[0]["limits_reason"].count("8-bit") >= 1


# ---- the cell and its traffic -----------------------------------------------
def test_the_cell_is_in_the_manifest_with_its_files():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon_h1_34b", "falconh1_chat_steady", 1)
    assert len(cell["why"]) <= 200
    traffic = common.load_traffic(cell["traffic"])
    assert common.load_module("drivers", traffic["driver"]) is serve_hybrid
    assert common.load_module("reference", cell["config"]) is not None
    mine = [m for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert set(READERS) <= {m["name"] for m in mine}
    assert not [m["name"] for m in mine if m["name"].startswith("moe_")]
    judged = {m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert judged == {"token_gap_p50_ms", "serve_tokens_per_s", "setup_s"}
    for m in mine:
        assert common.load_module("layer_metrics", m["name"]) is not None
        assert m["moves"] in judged


def test_the_traffic_is_the_issues_and_says_where_its_rate_comes_from():
    t = common.load_traffic("falconh1_chat_steady")
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                  "sigma": 0.8, "min": 16, "max": 1536}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 128,
                                  "sigma": 0.6, "min": 16, "max": 512}
    assert (t["clients"], t["preroll_s"], t["max_total_tokens"]) == (
        6, 30, 2048)
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * t["knee_per_s"], rel=0.03)
    assert t["knee_note"].count("/s") >= 4


def test_every_seed_meets_one_realisation():
    t = common.load_traffic("falconh1_chat_steady")
    a = loadgen.build_schedule(t, 3000000011, CFG["vocab_size"], 75.0)
    b = loadgen.build_schedule(t, 7, CFG["vocab_size"], 75.0)
    shape = lambda s: [(r["due_s"], len(r["prompt"]),  # noqa: E731
                        r["max_new_tokens"]) for r in s]
    assert shape(a) == shape(b)
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 2048 for r in a)
    digest = hashlib.sha256(json.dumps(shape(a)).encode()).hexdigest()[:16]
    assert (len(a), digest) == PINNED


PINNED = (755, "7084d9e41c8ae417")  # requests in 75 s at 11.2/s, digest
