"""Reduction of a profiler trace to busy time, op times, kernel time and
idle gaps labelled by the host span the benchmark was in."""

import json
from pathlib import Path

import pytest

from harness import trace

FIXTURES = Path(__file__).parent / "fixtures"


def _load(name):
    return json.loads((FIXTURES / name).read_text())


def test_synthetic_trace_reduces_to_hand_computed_numbers():
    s = trace.reduce(_load("trace_synthetic.json"))
    assert s["window_s"] == pytest.approx(1.0)
    # Union of [0, .05] (clipped), [.1, .3], [.5, .65].
    assert s["busy_s"] == pytest.approx(0.4)
    assert [n for n, _ in s["device_ops"]][0] == "fusion.1"
    assert s["ops"]["fusion.1"] == {"count": 2, "seconds": pytest.approx(0.3)}
    assert s["ops"]["early"]["seconds"] == pytest.approx(0.05)
    assert [[n, pytest.approx(t)] for n, t in s["idle_gaps"]] == [
        ["bench.wait", 0.35], ["bench.flush", 0.2], ["bench.submit", 0.05]]
    assert trace.kernel_time(s, r"sturm") == {
        "count": 1, "seconds": pytest.approx(0.05)}
    assert trace.kernel_time(s, r"prod_diff") is None


def test_recorded_chip_trace_reduces_consistently():
    """0.8 ms of one b=64 refresh program on a TPU v5e, as ``load_events``
    keeps it: sub-2-us ops under one name, loops nesting their body ops."""
    events = _load("trace_chip_refresh.json")
    s = trace.reduce(events)
    assert s["window_s"] == pytest.approx(0.0008)
    assert 0 < s["busy_s"] <= s["window_s"]
    # Nested ops: the loop event covers its body's ops, so the busy union
    # is less than the sum of op times.
    assert s["busy_s"] < sum(e["seconds"] for e in s["ops"].values())
    assert s["busy_s"] == pytest.approx(0.000599584)
    assert s["device_ops"][0][0] == "while.235"
    assert s["device_ops"] == sorted(s["device_ops"], key=lambda x: -x[1])
    assert len(s["device_ops"]) == trace.TOP
    assert s["ops"][trace.SHORT_OPS]["count"] == 8186
    assert sum(t for _, t in s["idle_gaps"]) <= s["window_s"] - s["busy_s"]
    assert trace.kernel_time(s, r"^while\.")["count"] == 23


def test_op_name_is_the_hlo_instruction_name():
    text = ("%sturm_padded.1 = f32[64,8]{1,0:T(8,128)S(1)} custom-call("
            "f32[64,128]{1,0} %a), custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(text) == "sturm_padded.1"


def test_empty_window_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"window": [5.0, 5.0], "devices": {}, "host": []})
