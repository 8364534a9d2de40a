"""What PR 27 added to the benchmark, checked by hand-counted numbers:
``costs_moe``, the three ``moe_*`` readers on synthetic records (among them
records whose share would pass 100% if padding, unhit experts or whole
pages were counted), and the driver's ``$key`` binding and its comparisons (the pinned
realisation of its traffic is in ``test_steady_cells.py``).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, costs_moe, program_spans  # noqa: E402

CFG = common.load_json("configs", "olmoe_1b_7b.json")
CTX = {"config": CFG, "peak": {"hbm_bytes_per_s": 819e9,
                               "bf16_flops": 197e12}}
PARAMS = 3_562_604_544  # 7,125,209,088 bytes of bfloat16, as the chip holds


# ---- costs_moe ------------------------------------------------------------
def test_parameter_counts_by_hand():
    # one expert 3 x 2048 x 1024; 8 layers x 64 of them; a layer's other
    # weights: q, k, v, o 4 x 2048^2, router 2048 x 64, two norms and the
    # two halves of the QK-norm 4 x 2048; then the final norm and the head
    assert costs_moe.expert_params(CFG) == 6_291_456
    c = costs_moe.moe_param_counts(CFG)
    assert c == {"experts": 3_221_225_472, "embedding": 103_022_592,
                 "streamed": 8 * 16_916_480 + 2048 + 103_022_592}
    assert sum(c.values()) == PARAMS


def test_decode_bytes_by_hand():
    # 55 experts hit in each of 8 layers, 10,000 cached rows:
    # 476,712,960 + 440 x 12,582,912 + 10,000 x 8 x 2 x 2048 x 2
    got = costs_moe.moe_decode_bytes(CFG, 476_712_960, 440, 10_000)
    assert got == 476_712_960 + 5_536_481_280 + 655_360_000 == 6_668_554_240


def test_prefill_flops_by_hand():
    # per token and layer 2 x (4 d^2 + d E + 8 x 3 d f) = 134,479,872; 1000
    # tokens see 500,500 pairs at 4 d = 8192 each; the head once
    assert costs_moe.moe_prefill_flops(CFG, 1000) == (
        8 * (1000 * 134_479_872 + 8192 * 500_500) + 206_045_184
    ) == 1_108_845_789_184
    # 100 of them cached: 900 new tokens, each also sees the 100
    assert costs_moe.moe_prefill_flops(CFG, 1000, 100) == (
        8 * (900 * 134_479_872 + 8192 * (900 * 100 + 900 * 901 // 2))
        + 206_045_184)


# ---- the readers ----------------------------------------------------------
def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def span(name, **args):
    return {"name": name, "start": 0.0, "end": 1.0, "args": args}


def decode_record(ms, steps):
    return {"counters": {"param_count": PARAMS},
            "trace": {"program_s": {"jit_llm_decode": [ms / 1e3] * steps,
                                    "jit_llm_prefill_1024": [0.02]}}}


def test_decode_roofline_counts_only_the_experts_hit(monkeypatch):
    reader = common.load_module("layer_metrics", "moe_decode_roofline")
    # three steps: 200 of 512 experts hit, 1,000 cached rows, 4 ms each.
    # 476,712,960 + 200 x 12,582,912 + 1000 x 65,536 = 3,058,831,360 bytes
    # = 3.7348 ms: 93.37%.  With all 512 experts (what the masked form
    # read every step until PR 42; the program has no such form any more)
    # it would be 6,984,826,880 bytes: 213%.
    fake(monkeypatch, [span("engine.decode.fetch", experts_hit=200),
                       span("engine.decode.dispatch", kv_tokens=1000)] * 3)
    got = reader.read(decode_record(4.0, 3), CTX)
    assert got == pytest.approx(100 * 3_058_831_360 / 819e9 / 4e-3)
    assert got == pytest.approx(93.37, abs=0.01)
    # one step more in the profile than spans in the ring: means, not sums
    assert reader.read(decode_record(4.0, 4), CTX) == pytest.approx(got)


def test_prefill_mfu_counts_real_tokens_not_the_bucket(monkeypatch):
    reader = common.load_module("layer_metrics", "moe_prefill_mfu")
    # a 600-token prompt in the 1024 bucket, 5.5 ms on the device:
    # 657,525,571,584 operations = 3.3377 ms at the peak: 60.69%.  The
    # bucket's 1,024 rows would be 1,136,258,449,408: 104.9%.
    fake(monkeypatch, [span("engine.prefill", bucket=1024, prompt_tokens=600,
                            cached_tokens=0, request_id=1)])
    rec = {"trace": {"program_s": {"jit_llm_prefill_1024": [5.5e-3],
                                   "jit_llm_decode": [0.03] * 9}}}
    assert costs_moe.moe_prefill_flops(CFG, 600) == 657_525_571_584
    assert costs_moe.moe_prefill_flops(CFG, 1024) == 1_136_258_449_408
    assert reader.read(rec, CTX) == pytest.approx(60.69, abs=0.01)


def test_paged_attn_roofline_reads_the_paged_kernel_alone(monkeypatch):
    reader = common.load_module("layer_metrics", "moe_paged_attn_roofline")
    # 10 steps reading 8,000 cached rows each: 8000 x 8 x 2 x 2048 x 2 B =
    # 524,288,000 bytes = 0.64016 ms; the kernel took 0.8 ms a step: 80.02%.
    # With whole pages of the 16 slots' 4,096-token contexts (65,536 rows)
    # it would be 655%; with the grouped matmul's custom calls in the time,
    # 1.6%.
    rec = decode_record(30.0, 10)
    rec["trace"]["op_s"] = {
        "tpu_custom_call f32[16,16,2048]": 0.008,
        "tpu_custom_call f32[8192,1024]": 0.25,
        "tpu_custom_call f32[8192,2048]": 0.15,
        "fusion f32[16,16,2048]": 0.5}
    fake(monkeypatch, [span("engine.decode.dispatch", kv_tokens=k)
                       for k in (7000, 8000, 9000)])
    assert costs_moe.kv_read_bytes(CFG, 8000) == 524_288_000
    assert reader.read(rec, CTX) == pytest.approx(80.02, abs=0.01)
    del rec["trace"]["op_s"]["tpu_custom_call f32[16,16,2048]"]
    assert reader.read(rec, CTX) is None  # GPT-2's width, or no kernel


@pytest.mark.parametrize("name", ["moe_decode_roofline", "moe_prefill_mfu",
                                  "moe_paged_attn_roofline"])
@pytest.mark.parametrize("record,spans", [
    ({}, [span("engine.decode.fetch", experts_hit=9),
          span("engine.decode.dispatch", kv_tokens=9),
          span("engine.prefill", prompt_tokens=9)]),      # untraced
    (decode_record(4.0, 3), []),                          # an older program
    (decode_record(4.0, 3), [span("engine.decode.fetch"),  # no arguments:
                             span("engine.decode.dispatch", kv_tokens=9),
                             span("engine.prefill")]),     # the parent
], ids=["untraced", "no_spans", "no_arguments"])
def test_nothing_to_read_is_none(monkeypatch, name, record, spans):
    fake(monkeypatch, spans)
    assert common.load_module("layer_metrics", name).read(record, CTX) is None


# ---- the driver's binding -------------------------------------------------
def test_model_kw_is_built_from_the_published_keys():
    from benchmark.drivers import serve_decoder

    kw = serve_decoder.model_kw(CFG)
    assert kw["num_layers"] == 8 and kw["hidden_size"] == 2048
    assert kw["num_experts"] == 64 and kw["num_experts_per_tok"] == 8
    assert kw["expert_size"] == 1024 and kw["norm_topk_prob"] is False
    assert kw["tiny"] is False and kw["qk_norm"] is True
    assert kw["param_dtype"] == kw["dtype"] == "bfloat16"
    assert not [v for v in kw.values() if isinstance(v, str) and "$" in v]
    # the rehearsal shrinks the published keys, and the model with them
    tiny = common.load_json("rehearsal", "tiny", "config.olmoe_1b_7b.json")
    small = json.loads(json.dumps(CFG))
    common.merge(small, tiny)
    kw = serve_decoder.model_kw(small)
    assert kw["hidden_size"] == 64 and kw["param_dtype"] == "float32"
    assert kw["qk_norm"] is True


def test_the_long_comparison_reaches_what_the_short_one_cannot():
    """The short prompt stays in the few-rows form of the expert FFN and
    the smallest prefill program; the long one passes ``DENSE_MAX_ROWS``
    in both the engine's bucket and the program's plain forward, and each
    has a limit of its own that ``correct`` holds it to."""
    from benchmark.drivers import serve_decoder
    from ray_tpu.ops.moe import DENSE_MAX_ROWS

    short, long_ = serve_decoder.comparisons(
        common.load_traffic("olmoe_chat_steady2")["reference"])
    assert (short["prompt_tokens"], short["new_tokens"]) == (48, 8)
    assert short["prompt_tokens"] + short["new_tokens"] <= DENSE_MAX_ROWS
    assert DENSE_MAX_ROWS < long_["prompt_tokens"] <= 1024
    assert long_["prompt_tokens"] + long_["new_tokens"] <= CFG["serve"][
        "max_ctx"]
    good = {"tokens": 8, "logprob_max_err": 0.01, "argmax_margin_max": 0.0,
            "router_agreement": 0.9}
    for limits in (short, long_):
        assert serve_decoder.within(good, limits)
        for key in ("logprob_max_err", "argmax_margin_max"):
            assert not serve_decoder.within(
                {**good, key: limits["logprob_tolerance"] * 1.01}, limits)
        assert not serve_decoder.within({**good, "tokens": 7}, limits)
        # what 8-bit weights read at most on the chip (PERF.md section 6)
        assert not serve_decoder.within(
            {**good, "router_agreement": 0.625}, limits)
    assert serve_decoder.comparisons({"prompt_tokens": 4}) == [
        {"prompt_tokens": 4}]
