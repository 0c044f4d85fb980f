"""The trace reduction on a small trace recorded on a v5e (two decode
ticks of the ACDC chat engine; ``data/trace_two_ticks.json``)."""

import json
import types
from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).parent / "data" / "trace_two_ticks.json"


def _profile():
    raw = json.loads(DATA.read_text())
    ev = lambda n, s, d: types.SimpleNamespace(  # noqa: E731
        name=n, start_ns=s, duration_ns=d)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=l["name"],
                                  events=[ev(*e) for e in l["events"]])
            for l in p["lines"]]) for p in raw["planes"]]), raw


@pytest.fixture(scope="module")
def reduced():
    prof, raw = _profile()
    return trace.reduce_profile(prof), raw


def _events(raw, plane, line):
    for p in raw["planes"]:
        if p["name"] == plane:
            for l in p["lines"]:
                if l["name"] == line:
                    return l["events"]
    return []


def test_window_is_the_harness_spans(reduced):
    red, raw = reduced
    ticks = [e for e in _events(raw, "/host:CPU", "python") if e[0] == "tick"]
    lo = min(s for _, s, _ in ticks)
    hi = max(s + d for _, s, d in ticks)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)


def test_busy_is_the_union_of_program_executions(reduced):
    red, raw = reduced
    ticks = [e for e in _events(raw, "/host:CPU", "python") if e[0] == "tick"]
    lo = min(s for _, s, _ in ticks)
    hi = max(s + d for _, s, d in ticks)
    mods = sorted(_events(raw, "/device:TPU:0", "XLA Modules"),
                  key=lambda e: e[1])
    busy, end = 0, lo
    for _, s, d in mods:
        s, e = max(s, end), min(s + d, hi)
        if e > s:
            busy, end = busy + e - s, e
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < red["busy_s"] <= red["window_s"]


def test_each_decode_call_sums_its_programs(reduced):
    red, raw = reduced
    host = _events(raw, "/host:CPU", "python")
    decodes = [(s, s + d) for n, s, d in host if n == "decode"]
    mods = _events(raw, "/device:TPU:0", "XLA Modules")
    want = [sum(d for _, s, d in mods if a <= s + d / 2 <= b) / 1e9
            for a, b in sorted(decodes)]
    assert red["programs"]["decode"] == pytest.approx(want)
    assert len(want) == 2 and all(w > 0.09 for w in want)


def test_kernels_are_told_apart_by_name(reduced):
    red, raw = reduced
    ticks = [e for e in _events(raw, "/host:CPU", "python") if e[0] == "tick"]
    lo = min(s for _, s, _ in ticks)
    hi = max(s + d for _, s, d in ticks)
    ops = [e for e in _events(raw, "/device:TPU:0", "XLA Ops")
           if lo <= e[1] <= hi]
    # the SELL and attention kernels nest nothing: self time = duration
    sell = sum(d for n, _, d in ops if n.startswith("%scaled_matmul_pallas"))
    attn = sum(d for n, _, d in ops
               if "tpu_custom_call" in n and "scaled_matmul" not in n)
    assert trace.kernel_seconds(red, "decode", "sell") == pytest.approx(
        sell / 1e9, rel=1e-6)
    assert trace.kernel_seconds(red, "decode", "paged_attn") == \
        pytest.approx(attn / 1e9, rel=1e-6)
    assert sell > 0 and attn > 0
    assert trace.unknown_kernels(red) == {}


def test_a_kernel_of_no_known_class_is_refused():
    """A kernel no file under ``bench/kernels`` names (here: the table
    without the paged-attention file) goes to ``unknown``, shows in the
    breakdown, and the kernel readers refuse the trace rather than count
    its time in another kernel's share."""
    prof, _ = _profile()
    table = [(p, c) for p, c in trace.kernel_table() if c != "paged_attn"]
    red = trace.reduce_profile(prof, table)
    assert list(trace.unknown_kernels(red)) == ["decode:unknown:closed_call"]
    assert any("unknown:closed_call" in k
               for k, _ in trace.breakdown(red)["device_ops"])
    with pytest.raises(ValueError, match="closed_call"):
        trace.kernel_seconds(red, "decode", "sell")


def test_self_times_take_out_nested_operations():
    ops = [("outer", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 4.0, 3.0),
           ("b_child", 5.0, 1.0), ("after", 11.0, 1.0)]
    own = {n: s for n, _, _, s in trace._self_times(ops)}
    assert own == {"outer": 5.0, "a": 2.0, "b": 2.0, "b_child": 1.0,
                   "after": 1.0}


def test_breakdown_lists_ops_and_gaps(reduced):
    red, _ = reduced
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "decode:sell:scaled_matmul_pallas"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(g > 0 for _, g in bd["idle_gaps"])
