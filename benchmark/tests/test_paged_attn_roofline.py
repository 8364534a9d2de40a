"""``paged_attn_roofline`` on a hand-made record and span list: the bytes by
hand, and nothing where there is nothing to read."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, program_spans  # noqa: E402

READER = common.load_module("layer_metrics", "paged_attn_roofline")
CTX = {"config": {"n_layer": 24, "n_embd": 1024,
                  "serve": {"dtype": "bfloat16"}},
       "peak": {"hbm_bytes_per_s": 819e9}}
KERNEL_40MS = {"trace": {"op_s": {"tpu_custom_call f32[16,16,1024]": 0.040,
                                  "fusion s32[804112]": 1.8}}}


def dispatches(kv_tokens):
    """One ``engine.decode.dispatch`` span a step; None: a program that
    does not say how many rows it read."""
    return [{"name": "engine.decode.dispatch", "start": 1.0 + i,
             "end": 1.1 + i, "args": {} if n is None else {"kv_tokens": n}}
            for i, n in enumerate(kv_tokens)]


def fake(monkeypatch, spans):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def test_bytes_by_hand(monkeypatch):
    # 50 steps that read 2,990 to 3,039 cached tokens, 150,725 in all:
    # x 24 layers x (K and V) x 1024 values x 2 bytes = 14,816,870,400
    # bytes, 18.0914 ms at 819 GB/s; the kernels took 40 ms: 45.229%.
    fake(monkeypatch, dispatches(range(2990, 3040))
         + [{"name": "engine.emit", "start": 0, "end": 1,
             "args": {"tokens": 9}}])
    got = READER.read(KERNEL_40MS, CTX)
    assert got == pytest.approx(100 * 14_816_870_400 / 819e9 / 0.040)
    assert got == pytest.approx(45.229, abs=1e-3)


@pytest.mark.parametrize("record,kv_tokens", [
    ({}, [3000]),                                      # no trace at all
    ({"trace": None}, [3000]),
    ({"trace": {"op_s": {"fusion s32[804112]": 1.8}}}, [3000]),  # no kernel
    (KERNEL_40MS, []),              # no spans: an older program
    (KERNEL_40MS, [None, None]),    # spans without the argument
], ids=["empty", "untraced", "no_kernel", "no_spans", "no_argument"])
def test_nothing_to_read_is_none(monkeypatch, record, kv_tokens):
    fake(monkeypatch, dispatches(kv_tokens))
    assert READER.read(record, CTX) is None


def test_no_peak_no_share(monkeypatch):
    fake(monkeypatch, dispatches([3000]))
    assert READER.read(KERNEL_40MS, {"config": CTX["config"]}) is None
