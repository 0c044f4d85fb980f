"""The engine's host phases (``harness/host.py``) and their two readers,
on the recorded two-tick trace with engine phase spans added
(``data/trace_two_ticks_phases.json``); and the six earlier readers on
the recorded trace as it was recorded."""

import json
import types

import pytest

from harness import device, host, program, spec, trace
from test_trace import DATA, _profile

PHASES = DATA.parent / "trace_two_ticks_phases.json"
OLD_READERS = ("idle_share.serve", "decode_step_ms", "prefill_step_ms",
               "sell_roofline.decode", "paged_attn_roofline", "decode_mfu")
NS = 1e-9


def _with_phases():
    """The recorded profile with the derived fixture's spans added to its
    host line."""
    prof, _ = _profile()
    extra = json.loads(PHASES.read_text())
    assert extra["base"] == DATA.name and "derived" in extra["source"]
    (plane,) = [p for p in prof.planes if p.name == extra["plane"]]
    (line,) = [ln for ln in plane.lines if ln.name == extra["line"]]
    line.events += [types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
                    for n, s, d in extra["events"]]
    return prof


@pytest.fixture(scope="module")
def phased():
    return host.reduce_profile(_with_phases())


def _reader(name):
    return spec.load_module(spec.BENCH / "metrics" / f"{name}.py",
                            f"metric_{name}")


def test_a_record_per_tick_with_phase_self_times(phased):
    first, second = phased["ticks"]
    assert (first["decode"], first["prefills"]) == (True, 1)
    assert (second["decode"], second["prefills"]) == (True, 0)
    assert second["dur"] == pytest.approx((355650000 - 251240000) * NS)
    # the decode span's own time: less its three phases
    assert second["self"]["decode"] == pytest.approx(
        (355509534 - 252790187 - 100000 - 80000 - 102200000) * NS)
    # the tick's own time: less its seven phases
    assert second["self"]["engine.tick"] == pytest.approx(
        (104410000 - 10000 - 30000 - 90000 - 40000 - 102719347 - 80000
         - 30000) * NS)
    assert first["self"]["engine.admit"] == pytest.approx(100000 * NS)
    assert first["self"]["prefill"] == pytest.approx(
        (1550000 - 90000 - 190000 - 1230000) * NS)
    for t in (first, second):
        assert sum(t["self"].values()) == pytest.approx(t["dur"])
        assert t["total"]["engine.tick"] == t["dur"]


def test_idle_gaps_are_named_by_engine_phase(phased):
    names = [n for n, _ in phased["idle_longest"]]
    assert names == ["decode.wait", "decode.wait", "engine.tick",
                     "prefill.sample", "decode", "decode.wait",
                     "engine.commit"]
    assert phased["idle_longest"][0][1] == pytest.approx(
        (250758427 - 248467544) * NS)
    assert set(phased["idle"]) == {"decode.wait", "engine.tick",
                                   "prefill.sample", "decode",
                                   "engine.commit"}


def test_the_two_readers_by_hand(phased, monkeypatch):
    monkeypatch.setattr(host, "load", lambda: phased)
    # the decode-only tick: its duration less its decode.wait
    assert _reader("tick_host_ms").read({}) == pytest.approx(
        (104410000 - 102200000) * NS * 1e3)
    # the admitting tick: engine.admit less prefill.sample, one admission
    assert _reader("admit_host_ms").read({}) == pytest.approx(
        (1650000 - 1230000) * NS * 1e3)


def test_a_program_without_phases_reports_nothing(monkeypatch):
    prof, _ = _profile()
    red = host.reduce_profile(prof)
    assert red["ticks"] == []
    monkeypatch.setattr(host, "load", lambda: red)
    assert _reader("tick_host_ms").read({}) is None
    assert _reader("admit_host_ms").read({}) is None
    monkeypatch.setattr(host, "load", lambda: None)
    assert _reader("tick_host_ms").read({}) is None


def test_the_phases_leave_the_program_reduction_alone():
    """The added spans move no program, operation or busy time of
    ``trace.py``'s reduction."""
    old = trace.reduce_profile(_profile()[0])
    new = trace.reduce_profile(_with_phases())
    for key in ("window_s", "busy_s", "programs", "ops", "chips"):
        assert new[key] == old[key]


def test_the_earlier_readers_read_what_they_read_before():
    """The six readers of the recorded trace, at the ACDC cell's sizes and
    two ticks of 16 decoded tokens, equal what they read before the engine
    named its phases."""
    red = trace.reduce_profile(_profile()[0])
    assert red["window_s"] == 0.209070164
    assert red["busy_s"] == 0.19988909600000007
    assert red["programs"] == {"decode": [0.09994103800000007, 0.099948058]}
    red["harness_ticks"] = [
        {"decode_contexts": list(range(600 + i, 616 + i)), "prefills": 0}
        for i in range(2)]
    config = json.loads((spec.BENCH / "configs" / "qwen3_1_7b-acdc.json")
                        .read_text())
    run = {"trace": red, "records": {}, "sizes": program.sizes(config, False),
           "elem": 2, "peak": device.peaks("TPU v5 lite"), "e2e": {}}
    got = {m: _reader(m).read(run) for m in OLD_READERS}
    assert got == {"idle_share.serve": 4.391381258972915,
                   "decode_step_ms": 99.94454800000004,
                   "prefill_step_ms": None,
                   "sell_roofline.decode": 0.052916081643987624,
                   "paged_attn_roofline": 39.03304633635521,
                   "decode_mfu": 0.1060571539657254}


def test_the_longest_span_bounds_the_look_back():
    """A span that starts hundreds of spans before the time asked about
    is still found around it."""
    inner = [("decode.wait", 1.0 + i, 1.5 + i) for i in range(300)]
    spans = host.Spans([("engine.tick", 0.0, 400.0)] + inner)
    assert spans.around(350.0, ("engine.tick", "decode.wait"))[0] == \
        "engine.tick"
    assert spans.around(299.2, ("engine.tick", "decode.wait"))[0] == \
        "decode.wait"
    assert spans.around(500.0, ("engine.tick",)) is None

