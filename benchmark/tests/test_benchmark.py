"""Checks of the yardstick itself.  Run by hand, from the root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

They sit with the benchmark and not in ``tests/``: no later PR may change
them, and tier-1 does not collect them.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import common, costs, loadgen, trace_reduce  # noqa: E402


# ---- trace_reduce -------------------------------------------------------
def _hand_events():
    dev, host = "/device:TPU:0", "/host:CPU"
    ops, mods = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
    asyn = trace_reduce.ASYNC_LINE
    return [
        (host, "main", "bench:window", 0, 1000),
        (host, "main", "dispatch:step", 0, 150),
        (host, "main", "ingest", 400, 250),
        (dev, mods, "jit_step(123)", 100, 300),
        (dev, mods, "jit_step(123)", 600, 300),
        (dev, ops, "%fusion.1 = bf16[8,8]{1,0} fusion(...)", 100, 200),
        (dev, asyn, "%all-reduce-start.2 = f32[8] all-reduce-start(...)",
         250, 150),
        (dev, ops, "%fusion.7 = bf16[8,8]{1,0} fusion(...)", 600, 300),
        (dev, "Steps", "0", 0, 1000),
    ]


def test_reduce_busy_idle_ops_and_gaps():
    r = trace_reduce.reduce(_hand_events(), span_names=(
        "dispatch:step", "ingest"))
    assert r["window_s"] == 1000e-9
    # ops cover [100,300) and [600,900): 500 of 1000 ns busy; the collective
    # in flight on the asynchronous line keeps no core busy
    assert abs(r["busy_s"] - 500e-9) < 1e-15
    assert r["op_s"] == {"fusion bf16[8,8]": 500e-9}
    # the all-reduce runs [250,400); compute covers [100,300): 100 ns exposed
    assert abs(r["collective_exposed_s"] - 100e-9) < 1e-15
    assert r["program_s"] == {"jit_step": [300e-9, 300e-9]}
    gaps = dict(r["idle_gaps"])
    # [0,100) under dispatch:step; [300,600) mostly under ingest, which
    # covers [400,650); [900,1000) under nothing
    assert gaps == {"dispatch:step": 100e-9, "ingest": 300e-9,
                    "none": 100e-9}


def test_self_time_of_nested_operations():
    dev, ops = "/device:TPU:0", trace_reduce.OPS_LINE
    ev = [(dev, ops, "%while.1 = s32[] while(%x)", 0, 100),
          (dev, ops, "%fusion.1 = f32[4] fusion(%y)", 10, 30),
          (dev, ops, "%fusion.2 = f32[4] fusion(%y)", 50, 40)]
    r = trace_reduce.reduce(ev)
    assert r["op_s"] == {"while s32[]": 30e-9, "fusion f32[4]": 70e-9}
    assert abs(r["busy_s"] - 100e-9) < 1e-15


def test_reduce_without_window_span_uses_device_extremes():
    ev = [e for e in _hand_events() if e[2] != "bench:window"]
    r = trace_reduce.reduce(ev)
    assert r["window_s"] == 800e-9 and abs(r["busy_s"] - 500e-9) < 1e-15


def test_op_key_and_collectives_by_own_name():
    name = ('%h_2.5 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[192,1024,'
            '64]{2,1,0}) custom-call(bf16[192,1024,64]{2,1,0} %bitcast.1), '
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.op_key(name) == "tpu_custom_call bf16[192,1024,64]"
    assert trace_reduce.op_key(
        "%fusion.5.remat = (f32[12,1023]{1,0}, bf16[1]{0}) fusion(%x)"
    ) == "fusion.remat f32[12,1023]"
    assert trace_reduce.op_key("fusion.123") == "fusion"
    assert trace_reduce.is_collective("%all-reduce-done.3 = f32[8] x()")
    assert not trace_reduce.is_collective(
        "%fusion.9 = f32[8] fusion(f32[8] %all-reduce.2)")


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 20)]) == [
        (0, 2), (3, 5)]
    assert trace_reduce.clip([(0, 10), (20, 30)], 5, 25) == [
        (5, 10), (20, 25)]


def test_reduce_on_the_recorded_trace():
    """``trace_small.xplane.pb``: a real profile of three runs of a small
    jitted program (tanh(x @ x).sum() at 2048 x 2048, bf16) on the TPU
    v5 lite, each dispatched under ``dispatch:step`` and awaited under
    ``fetch``, inside one ``bench:window`` span (PR 23).  What the reducer
    said of it on the chip is what it must say of it here."""
    events = trace_reduce.events_from_xplane(
        os.path.join(HERE, "trace_small.xplane.pb"))
    r = trace_reduce.reduce(events, span_names=("dispatch:step", "fetch"))
    assert r["devices"] == 1
    assert abs(r["window_s"] - 0.00247068) < 1e-9
    assert abs(r["busy_s"] - 0.000153011) < 1e-9
    assert abs(r["op_s"]["fusion bf16[]"] - 0.000180393) < 1e-9
    assert [len(v) for v in r["program_s"].values()] == [2]
    assert r["idle_gaps"][0][0] == "fetch"
    assert abs(sum(g[1] for g in r["idle_gaps"]) + r["busy_s"]
               - r["window_s"]) < 1e-9


# ---- percentiles and gaps ----------------------------------------------
def test_percentile_interpolates():
    assert loadgen.percentile([1, 2, 3, 4], 50) == 2.5
    assert loadgen.percentile([10], 95) == 10
    assert abs(loadgen.percentile(list(range(101)), 95) - 95) < 1e-12
    assert loadgen.percentile([4, 1, 3, 2], 0) == 1


def test_gaps_are_cut_at_the_windows_edges():
    stamps = [0.5, 1.5, 2.5, 3.5, 4.5]
    # window [1, 4]: the gaps 1.5->2.5 and 2.5->3.5 lie inside; 0.5->1.5
    # begins before it and 3.5->4.5 ends after it
    assert loadgen.gaps_in_window(stamps, 1.0, 4.0) == [1.0, 1.0]
    assert loadgen.gaps_in_window(stamps, 0.0, 10.0) == [1.0] * 4
    assert loadgen.gaps_in_window([2.0], 0.0, 10.0) == []


# ---- costs, against hand-worked values for gpt2_medium -------------------
def test_costs_for_gpt2_medium():
    cfg = common.load_json("configs", "gpt2_medium.json")
    # 24 * 12 * 1024^2 + 50257 * 1024
    assert costs.gpt2_matmul_params(cfg) == 301989888 + 51463168
    # 24 layers * 4 * 1024 * 1025 / 2
    assert costs.gpt2_attention_flops_per_token(cfg, 1024) == 50380800
    # 3 * (2 * 353453056 + 50380800) = 2,271,860,736
    assert costs.gpt2_train_flops_per_token(cfg, 1024) == 2271860736
    c = costs.flash_train_cost(12, 1024, 16, 64)
    assert c["flops"] == 12 * 16 * 12 * 1024 * 1024 * 64 / 2  # 77.3 GFLOP
    assert c["bytes"] == 12 * 12 * 1024 * 16 * 64 * 2        # 302 MB
    peak = common.peak_for("TPU v5 lite")
    least = costs.roofline_seconds(c["flops"], c["bytes"], peak)
    assert least["bound"] == "compute"
    assert abs(least["seconds"] - c["flops"] / 197e12) < 1e-12
    # decode: 354,823,168 bf16 weights and 1000 live tokens
    need = costs.gpt2_decode_bytes(354823168 * 2, 1000, cfg)
    assert need == 709646336 + 1000 * 24 * 2 * 1024 * 2
    try:
        common.peak_for("TPU v9")
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


# ---- the load generator ---------------------------------------------------
def test_schedule_is_a_function_of_the_seed_alone():
    traffic = common.load_traffic("serve_chat_steady")
    a = loadgen.build_schedule(traffic, 3000000011, 50257, 75.0)
    b = loadgen.build_schedule(traffic, 3000000011, 50257, 75.0)
    c = loadgen.build_schedule(traffic, 7, 50257, 75.0)
    assert a == b
    # another seed: the same instants and the same lengths in the same
    # order, with other token ids
    assert [r["due_s"] for r in a] == [r["due_s"] for r in c]
    lens = lambda s: [(len(r["prompt"]), r["max_new_tokens"])  # noqa
                      for r in s]
    assert lens(a) == lens(c)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    for r in a:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert o["min"] <= r["max_new_tokens"] <= o["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= 1024
        assert 0 <= min(r["prompt"]) and max(r["prompt"]) < 50257
    # a shorter horizon is a prefix of the instants, not a redraw
    d = loadgen.build_schedule(traffic, 7, 50257, 30.0)
    assert [r["due_s"] for r in d] == [r["due_s"] for r in a][:len(d)]


def test_arrival_processes_are_found_by_name():
    import numpy as np

    traffic = common.load_traffic("serve_chat_steady")
    burst = dict(traffic, arrivals={"process": "gamma", "shape": 0.25,
                                    "rate_per_s": 1.0})
    a = loadgen.build_schedule(burst, 7, 50257, 100.0)
    gaps = np.diff([0.0] + [r["due_s"] for r in a])
    # the same mean rate, burstier: gamma gaps of shape k have a squared
    # coefficient of variation of 1/k
    assert 60 <= len(a) <= 140
    assert np.var(gaps) / np.mean(gaps) ** 2 > 2.0
    try:
        loadgen.build_schedule(
            dict(traffic, arrivals={"process": "nope", "rate_per_s": 1.0}),
            7, 50257, 10.0)
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown arrival process must be an error")


def test_shared_prefix_is_data():
    traffic = dict(common.load_traffic("serve_chat_steady"),
                   shared_prefix={"tokens": 64, "pool": 2})
    a = loadgen.build_schedule(traffic, 7, 50257, 60.0)
    plain = loadgen.build_schedule(common.load_traffic("serve_chat_steady"), 7,
                                   50257, 60.0)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in plain]
    heads = {tuple(r["prompt"][:16]) for r in a}
    assert len(heads) == 2
    even = [r for r in a[::2] if len(r["prompt"]) > 80]
    assert even[0]["prompt"][:64] == even[1]["prompt"][:64]
    assert even[0]["prompt"][64:80] != even[1]["prompt"][64:80]


def test_a_traffic_file_may_inherit_another():
    """``train_seq1k_dp4`` is ``train_seq1k`` by reference, so that the two
    train cells cannot drift apart."""
    base = common.load_traffic("train_seq1k")
    dp4 = common.load_traffic("train_seq1k_dp4")
    dp4.pop("note")
    assert dp4 == base


def test_rehearsal_presets_are_found_by_config_and_driver():
    from benchmark.rehearsal import rehearse

    o = rehearse.tiny_overrides("gpt2m_train_dp4")
    assert o["config"]["n_layer"] == 2 and o["traffic"]["seq"] == 128
    assert rehearse.tiny_overrides("gpt2m_serve_steady")["traffic"][
        "preroll_s"] == 2


if __name__ == "__main__":
    failed = 0
    for name, fn in sorted(globals().items()):
        if name.startswith("test_") and callable(fn):
            try:
                fn()
                print("ok  ", name)
            except Exception as e:  # noqa: BLE001
                failed += 1
                print("FAIL", name, repr(e))
    sys.exit(1 if failed else 0)
