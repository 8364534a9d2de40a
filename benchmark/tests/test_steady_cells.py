"""What PR 36 added: one realisation a traffic file, the two steady serve
cells' pinned realisations, the client processes and ``play``'s arithmetic, the records ``gap_cut_share`` and
``ttft_p95_ms``, ``decode_step_roofline`` on the spans' ``kv_tokens``, and
the manifest's names against the files they stand for.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import hashlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import (common, loadgen, manifest_check,  # noqa: E402
                       program_spans)

MANIFEST = manifest_check.load()
SERVE = {"gpt2m_serve_steady": "serve_chat_steady",
         "olmoe_serve_steady2": "olmoe_chat_steady2"}


def digest(schedule) -> str:
    return hashlib.sha256(json.dumps(schedule).encode()).hexdigest()[:16]


# ---- one realisation ---------------------------------------------------------
@pytest.mark.parametrize("name,vocab", [("serve_chat_steady", 50257),
                                        ("olmoe_chat_steady2", 50304)])
def test_every_seed_meets_one_realisation(name, vocab):
    """Two seeds meet the same instants and the same lengths in the same
    order, and differ in every token id."""
    traffic = common.load_traffic(name)
    a = loadgen.build_schedule(traffic, 3000000011, vocab, 75.0)
    b = loadgen.build_schedule(traffic, 7, vocab, 75.0)
    shape = lambda s: [(r["due_s"], len(r["prompt"]),  # noqa: E731
                        r["max_new_tokens"]) for r in s]
    assert shape(a) == shape(b)
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    # another schedule_seed is another realisation of the same mix
    c = loadgen.build_schedule(dict(traffic, schedule_seed=11), 7, vocab, 75.0)
    assert shape(c) != shape(b)


PINNED = {
    "serve_chat_steady": (50257, 936, "e905683bea9377ad", 239365, 106156),
    "olmoe_chat_steady2": (50304, 454, "79ad5a1a9e188179", 337735, 69690),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_steady_cells_realisations_are_pinned(name):
    """The bounds of PR 36 were set on these instants and lengths (a
    pre-roll of 30 s and a window of 45), so they may not move; PR 45's
    re-rated OLMoE cell was read on its own."""
    vocab, count, want, prompts, answers = PINNED[name]
    a = loadgen.build_schedule(common.load_traffic(name), 3000000011, vocab,
                               75.0)
    assert (len(a), digest(a)) == (count, want)
    assert sum(len(r["prompt"]) for r in a) == prompts
    assert sum(r["max_new_tokens"] for r in a) == answers


@pytest.mark.parametrize("name", sorted(SERVE.values()))
def test_a_steady_file_says_where_its_rate_comes_from(name):
    """The file records the knee with the sweep's own lines and what gave
    out there, runs at four fifths of it, and says how many client processes
    hold the streams; the lengths are the retired cells'."""
    t = common.load_traffic(name)
    assert t["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * t["knee_per_s"], rel=0.03)
    assert t["knee_note"].count("/s") >= 4
    assert t["clients"] >= 2 and "permute" not in t
    assert (t["schedule_seed"], t["preroll_s"]) == (20260927, 30)
    want = {"serve_chat_steady": ((192, 0.8, 16, 768), (96, 0.6, 16, 256),
                                  1024),
            "olmoe_chat_steady2": ((512, 0.8, 32, 3072), (128, 0.6, 16, 512),
                                   4096)}[name]
    for got, w in zip((t["prompt_tokens"], t["output_tokens"]), want):
        assert (got["median"], got["sigma"], got["min"], got["max"]) == w
    assert t["max_total_tokens"] == want[2]


# ---- the client processes ---------------------------------------------------
class FakeHandle:
    """A replica that answers at once: ``submit_stream`` gives the request's
    number, ``next_chunk`` one token a call and then None; ``broken`` names
    a request whose stream raises, ``extra`` one that gets a token too
    many."""

    def __init__(self, broken=(), extra=()):
        self.left, self.broken, self.extra = [], set(broken), set(extra)

    def method(self, name):
        return self

    def remote(self, *args):
        if len(args) == 2 and isinstance(args[0], list):  # submit_stream
            rid = len(self.left)
            self.left.append(args[1] + (1 if args[0][0] in self.extra else 0))
            return ("value", rid, args[0][0])
        rid = args[0]
        if rid in self.broken:
            return ("raise", rid, None)
        self.left[rid] -= 1
        return ("value", [7] if self.left[rid] >= 0 else None, None)


def fake_get(ref, timeout=None):
    if isinstance(ref, list):
        return [fake_get(r) for r in ref]
    if ref[0] == "raise":
        raise RuntimeError("stream broke")
    return ref[1]


def requests(n, tokens=3):
    return [{"due_s": 0.02 * i, "prompt": [i], "max_new_tokens": tokens}
            for i in range(n)]


def played_by(handle, schedule, clients_n, monkeypatch):
    """``Fleet``'s split and merge, with the processes run in this one."""
    import time

    import ray_tpu
    from benchmark import clients

    monkeypatch.setattr(ray_tpu, "get", fake_get)
    shares = [list(enumerate(schedule))[c::clients_n]
              for c in range(clients_n)]
    parts = [clients.Streams(handle, share) for share in shares]
    t0 = time.time() + 0.05
    for part in parts:
        part.start(t0)
    time.sleep(0.05 + 0.02 * len(schedule) + 0.3)
    merged = {}
    for part in parts:
        merged.update(part.stop())
    return [merged[i] for i in range(len(merged))]


def test_streams_send_on_time_and_stamp_every_token(monkeypatch):
    schedule = requests(7)
    got = played_by(FakeHandle(), schedule, 3, monkeypatch)
    assert len(got) == 7
    for req, g in zip(schedule, got):
        assert g["error"] is None and len(g["stamps"]) == 3
        assert 0 <= g["sent_at"] - req["due_s"] < 0.05
        assert g["sent_at"] <= g["stamps"][0] <= g["stamps"][-1] <= g["done_at"]


def test_a_stream_that_breaks_is_its_requests_error(monkeypatch):
    schedule = requests(4)
    got = played_by(FakeHandle(broken={2}), schedule, 2, monkeypatch)
    assert [g["error"] is not None for g in got] == [False, False, True, False]
    assert "stream broke" in got[2]["error"] and got[2]["done_at"] is None


class FakeFleet:
    canned = None

    def __init__(self, handle, schedule, n):
        assert n == 2

    def start(self, t0):
        pass

    def stop(self):
        return FakeFleet.canned


def play_with(canned, schedule, monkeypatch, seconds=2.0, preroll=1.0):
    """``serve_lm.play`` over canned stamps: the arithmetic alone."""
    from benchmark import clients
    from benchmark.drivers import serve_lm

    FakeFleet.canned = canned
    monkeypatch.setattr(clients, "Fleet", FakeFleet)
    monkeypatch.setattr(loadgen, "build_schedule", lambda *a: schedule)
    monkeypatch.setattr(serve_lm.time, "sleep", lambda s: None)
    stats = iter([
        {"active": 0, "pending": 0},
        {"steps": 100, "avg_batch_occupancy": 0.10},
        {"steps": 300, "avg_batch_occupancy": 0.30, "active": 1,
         "pending": 0, "decode_cache_size": 1}])

    def call(method, *args):
        return {"arm": True, "disarm": 0,
                "step_stamps": [0.0, 0.004, 0.008]}.get(method) if (
            method != "stats") else next(stats)

    traffic = {"preroll_s": preroll, "clients": 2,
               "arrivals": {"rate_per_s": 1.0}}
    return serve_lm.play(None, call, traffic, 1, 100, seconds, False)


def stream(sent, stamps, done=None, error=None):
    return {"sent_at": sent, "stamps": stamps, "done_at": done, "error": error}


def test_play_reads_the_window_from_the_stamps(monkeypatch):
    schedule = [{"due_s": 0.5, "prompt": [1], "max_new_tokens": 3},
                {"due_s": 1.5, "prompt": [1], "max_new_tokens": 4},
                {"due_s": 2.9, "prompt": [1], "max_new_tokens": 2},
                {"due_s": 5.0, "prompt": [1], "max_new_tokens": 2}]
    canned = [stream(0.5, [0.9, 1.1, 1.2], 1.2),         # straddles w0 = 1
              stream(1.501, [1.6, 1.7, 1.8, 2.0], 2.0),  # inside the window
              stream(2.902, [3.1, 3.2], 3.2),            # first token after w1
              stream(None, [])]                          # never sent
    got = play_with(canned, schedule, monkeypatch)
    assert (got["attempted"], got["failed"]) == (3, 0)
    assert got["samples"]["gap_ms"] == pytest.approx([100, 100, 100, 200])
    assert got["samples"]["ttft_ms"] == pytest.approx([100.0, 200.0])
    c = got["counters"]
    assert c["tokens_in_window"] == 6 and c["tokens_per_s"] == 3.0
    assert c["offered_tokens_per_s"] == pytest.approx((4 + 2) / 2.0)
    assert (c["waiting_at_window_start"], c["waiting_at_window_end"]) == (0, 1)
    assert c["live_streams_mean"] == pytest.approx((0.2 + 0.4) / 2.0)
    assert c["lateness_p95_ms"] == pytest.approx(1.95, abs=0.01)
    # the window's own occupancy: (0.30 x 300 - 0.10 x 100) / 200 steps
    assert c["window_occupancy"] == pytest.approx(0.40)
    assert got["end_to_end"]["serve_tokens_per_s"] == 3.0


@pytest.mark.parametrize("fault", ["a_token_too_many", "a_token_missing",
                                   "a_stream_that_broke"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                               fault):
    """The rest of a run with the timed path broken underneath: the record
    says ``correct`` false, whatever the reference comparison found."""
    from benchmark.drivers import serve_lm

    schedule = [{"due_s": 1.2, "prompt": [1], "max_new_tokens": 3}] * 2
    good = stream(1.2, [1.3, 1.4, 1.5], 1.5)
    bad = {"a_token_too_many": stream(1.2, [1.3, 1.4, 1.5, 1.6]),
           "a_token_missing": stream(1.2, [1.3, 1.4], 1.4),
           "a_stream_that_broke": stream(1.2, [1.3], None, "RuntimeError()")}
    device = {"platform": "tpu", "param_count": 1}
    sound = play_with([good, good], schedule, monkeypatch)
    assert serve_lm.record(sound, device, {"tokens": 8}, {}, True)["correct"]
    broken = play_with([good, bad[fault]], schedule, monkeypatch)
    rec = serve_lm.record(broken, device, {"tokens": 8}, {}, True)
    assert rec["failed"] == 1 and rec["correct"] is False


# ---- the manifest's names ---------------------------------------------------
def test_the_manifest_stands_for_the_files_it_names():
    """Counted, not named (``manifest_check.faults``): every cell's files
    are there, four chips are asked for by one cell at least and a quarter
    at most, every listed name is a cell, no name of ``retired.txt`` is
    left.  What this file knows by name is only what it pins: the two
    steady cells and their traffic."""
    assert manifest_check.faults(MANIFEST) == []
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    for cell, traffic in SERVE.items():
        assert cells[cell]["traffic"] == traffic
    assert len({w["name"] for w in MANIFEST["workloads"]}) == len(cells)
    assert len({(w["config"], w["traffic"])
                for w in MANIFEST["workloads"]}) == len(cells)


def spoiled(change):
    manifest = json.loads(json.dumps(MANIFEST))
    change(manifest)
    return manifest_check.faults(manifest)


@pytest.mark.parametrize("change,says", [
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "four chips"),
    (lambda m: [w.update(chips=1) for w in m["workloads"]], "0 of "),
    (lambda m: m["workloads"][1].update(traffic="no_such_mix"),
     "no traffic file"),
    (lambda m: m["workloads"][1].update(config="no_such_config"),
     "no configuration file"),
    (lambda m: m["end_to_end"][1]["workloads"].append("olmoe_serve_steady"),
     "which is no cell"),
    (lambda m: m["per_layer"][0]["workloads"].append("a_cell_that_left"),
     "which is no cell"),
    (lambda m: m["workloads"][1].update(why="as olmoe_serve_chat was"),
     "retired name olmoe_serve_chat"),
    (lambda m: m["per_layer"][0].update(name="no_such_reader"), "no reader"),
    (lambda m: m["per_layer"][0].update(moves="env_steps_per_s"),
     "does not report"),
    (lambda m: m["configs"].append(dict(m["configs"][0], name="spare")),
     "used by no cell"),
], ids=["every_cell_on_four_chips", "no_cell_on_four_chips",
        "a_traffic_file_gone", "a_configuration_gone",
        "a_retired_cell_on_a_list",
        "an_unknown_cell_on_a_list", "a_retired_name_in_a_why",
        "a_metric_without_a_reader", "a_metric_that_moves_nothing_here",
        "a_configuration_no_cell_uses"])
def test_the_manifest_check_names_a_drift(change, says):
    found = spoiled(change)
    assert found and any(says in line for line in found), found


def test_the_retired_names_are_data():
    """``retired.txt``: a name a line before its remark, the retired cells
    and mixes of PRs 36 and 45 among them."""
    names = manifest_check.retired()
    assert {"olmoe_serve_steady", "olmoe_chat_steady",
            "gpt2m_serve_chat"} <= set(names)
    assert all(name and " " not in name and "#" not in name
               for name in names)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_name_of_a_cell_finds_its_file(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert os.path.exists(os.path.join(common.ROOT, entry["file"]))
    traffic = common.load_traffic(w["traffic"])
    assert common.load_module("drivers", traffic["driver"]) is not None
    assert common.load_module("reference", w["config"]) is not None
    ends = [m for m in MANIFEST["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]
    assert {"setup_s"} < {m["name"] for m in ends}
    layers = [m for m in MANIFEST["per_layer"] if cell in m["workloads"]]
    assert layers
    for m in layers:
        assert common.load_module("layer_metrics", m["name"]) is not None
        assert m["moves"] in {e["name"] for e in ends}


# ---- the two records --------------------------------------------------------
def test_gap_cut_share_by_hand():
    reader = common.load_module("layer_metrics", "gap_cut_share")
    # 80 plain gaps of 3 ms, 20 cut by a prefill to 12: the median is 3, the
    # cut at 4.5, and a fifth of the gaps lies above it
    rec = {"samples": {"gap_ms": [3.0] * 80 + [12.0] * 20}}
    assert reader.read(rec, {}) == pytest.approx(20.0)
    # a gap at exactly 1.5 medians is not cut
    assert reader.read({"samples": {"gap_ms": [2.0, 2.0, 3.0]}},
                       {}) == pytest.approx(0.0)
    assert reader.read({"samples": {"gap_ms": []}}, {}) is None
    assert reader.read({}, {}) is None


def test_gap_p95_is_the_tail_of_the_same_sample():
    reader = common.load_module("layer_metrics", "gap_p95_ms")
    rec = {"samples": {"gap_ms": [float(i) for i in range(101)]}}
    assert reader.read(rec, {}) == pytest.approx(95.0)
    assert reader.read(rec, {}) == loadgen.percentile(
        rec["samples"]["gap_ms"], 95)
    assert reader.read({"samples": {"gap_ms": []}}, {}) is None
    assert reader.read({}, {}) is None


def test_ttft_p95_by_hand():
    reader = common.load_module("layer_metrics", "ttft_p95_ms")
    rec = {"samples": {"ttft_ms": [float(i) for i in range(1, 102)]}}
    assert reader.read(rec, {}) == pytest.approx(96.0)
    assert common.load_module("layer_metrics", "ttft_p50_ms").read(
        rec, {}) == pytest.approx(51.0)
    assert reader.read({"samples": {"ttft_ms": []}}, {}) is None
    assert reader.read({}, {}) is None


# ---- decode_step_roofline on the spans' kv_tokens ---------------------------
GPT2 = common.load_json("configs", "gpt2_medium.json")
CTX = {"config": GPT2, "peak": {"hbm_bytes_per_s": 819e9}}


def dispatches(kv_tokens):
    return [{"name": "engine.decode.dispatch", "start": float(i),
             "end": i + 0.1, "args": {} if n is None else {"kv_tokens": n}}
            for i, n in enumerate(kv_tokens)]


def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def decode_record(step_s, prefill_s=()):
    return {"counters": {"param_count": 354_823_168},
            "trace": {"program_s": {"jit_llm_decode": list(step_s),
                                    "jit_llm_prefill_256": list(prefill_s)}}}


def test_decode_step_roofline_by_hand(monkeypatch):
    # 354,823,168 parameters in bfloat16 = 709,646,336 bytes; steps that
    # read 8,000, 10,000 and 12,000 cached rows: the mean 10,000 x 24 layers
    # x (K and V) x 1024 x 2 B = 983,040,000 bytes; 1,692,686,336 bytes at
    # 819 GB/s = 2.06677 ms; the program took 4 ms a step: 51.669%.  The
    # prefill programs, though there are more of them, are not the step.
    fake(monkeypatch, dispatches([8000, 10000, 12000]))
    reader = common.load_module("layer_metrics", "decode_step_roofline")
    rec = decode_record([0.004, 0.0045, 0.0035], [0.01] * 9)
    assert reader.read(rec, CTX) == pytest.approx(
        100 * 1_692_686_336 / 819e9 / 0.004)
    assert reader.read(rec, CTX) == pytest.approx(51.669, abs=1e-3)
    assert reader.read(rec, {"config": GPT2}) is None      # no peak
    # the slot count is no part of it: 32 slots or 16, the same bytes
    wide = json.loads(json.dumps(CTX))
    wide["config"]["serve"]["max_slots"] = 16
    assert reader.read(rec, wide) == reader.read(rec, CTX)


@pytest.mark.parametrize("record,kv_tokens", [
    ({}, [3000]),
    ({"trace": None, "counters": {"param_count": 9}}, [3000]),
    ({"counters": {"param_count": 9},
      "trace": {"program_s": {"jit_llm_prefill_256": [0.01]}}}, [3000]),
    (decode_record([0.004]), []),
    (decode_record([0.004]), [None]),
    ({"trace": {"program_s": {"jit_llm_decode": [0.004]}}}, [3000]),
], ids=["empty", "untraced", "no_decode_program", "no_spans", "no_argument",
        "no_param_count"])
def test_decode_step_roofline_nothing_to_read(monkeypatch, record, kv_tokens):
    fake(monkeypatch, dispatches(kv_tokens))
    reader = common.load_module("layer_metrics", "decode_step_roofline")
    assert reader.read(record, CTX) is None


def test_readers_that_pick_rows_by_slots_read_the_configuration(monkeypatch):
    """``moe_paged_attn_roofline`` names the kernel's row by
    ``serve.max_slots``: at another slot count it finds that row and no
    other."""
    cfg = common.load_json("configs", "olmoe_1b_7b.json")
    cfg["serve"]["max_slots"] = 24
    fake(monkeypatch, dispatches([8000]))
    rec = {"trace": {"program_s": {"jit_llm_decode": [0.01] * 10},
                     "op_s": {"tpu_custom_call f32[24,16,2048]": 0.008,
                              "tpu_custom_call f32[16,16,2048]": 0.5}}}
    got = common.load_module("layer_metrics", "moe_paged_attn_roofline").read(
        rec, {"config": cfg, "peak": {"hbm_bytes_per_s": 819e9}})
    assert got == pytest.approx(80.02, abs=0.01)


# ---- the numbers compared, in the result's line -----------------------------
def test_the_result_line_carries_the_numbers_compared_without_the_prose():
    from benchmark import run

    checks = {"tokens": 8, "logprob_max_err": 0.018, "logprob_tolerance": 0.05,
              "logprob_tolerance_reason": "a paragraph", "errors": [],
              "long": {"logprob_max_err": 0.02, "why": "another paragraph",
                       "router_agreement_min": 0.8}}
    assert run.compared(checks) == {
        "tokens": 8, "logprob_max_err": 0.018, "logprob_tolerance": 0.05,
        "errors": [], "long": {"logprob_max_err": 0.02,
                               "router_agreement_min": 0.8}}


# ---- trace_reduce: naming the idle gaps in linear time ------------------------
def test_covered_is_the_clipped_total():
    import random

    from benchmark import trace_reduce

    rng = random.Random(7)
    cuts = sorted(rng.sample(range(10_000), 400))
    spans = [[a, b] for a, b in zip(cuts[::2], cuts[1::2])]
    ends = [b for _, b in spans]
    for _ in range(300):
        a = rng.randrange(10_000)
        b = a + rng.randrange(1, 500)
        assert trace_reduce.covered(spans, ends, a, b) == trace_reduce.total(
            trace_reduce.clip(spans, a, b))
    assert trace_reduce.covered([], [], 0, 10) == 0
