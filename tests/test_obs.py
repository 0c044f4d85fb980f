"""Observability: metric registry semantics, span-trace well-formedness
under seeded chaos, and the engine's noop fast path.

Three layers of assertions:

* the metrics primitives (Counter/Gauge/Histogram, labels, snapshot/
  merge/Prometheus, the derived-gauge staleness fix, the dict shims);
* the tracer: contiguous per-request phase chains, exactly one terminal
  event per request, deterministic Chrome exports;
* the engine: obs OFF binds no tracer/exporter/tick hook and hands every
  profiler span site the one shared no-op (the documented noop path);
  greedy token streams are identical with obs and the profiler spans on
  and off;
* the tick's profiler phases (``obs/prof.py``): one ``engine.tick`` per
  tick holds its phases in order, ``decode`` and ``prefill`` keep the
  calls they held before the split, a speculative tick splits ``verify``
  the same way, and a real ``jax.profiler`` capture carries every name;
  a seeded FaultPlan chaos run over a virtual clock yields a complete,
  well-formed, replay-deterministic trace covering every finish reason
  the run produced — including the engineered ``timeout``, ``rejected``
  and ``preempted_limit`` terminals.
"""

import contextlib
import json

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.models import get_model
from repro.obs import Observability
from repro.obs.metrics import (CounterDict, JsonlExporter, Registry,
                               StatsView, merge_snapshots)
from repro.obs import prof as prof_mod
from repro.obs.prof import Prof, parse_tick_window
from repro.obs.trace import SpanTracer, instant_global, set_global_tracer
from repro.serving import Engine, FaultPlan, Request


class FakeClock:
    """Deterministic virtual clock (same shape as the resilience tests')."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# Metrics primitives.
# ---------------------------------------------------------------------------

def test_counter_gauge_basics_and_labels():
    reg = Registry()
    c = reg.counter("c_total", "a counter", labels=("route",))
    c.labels(route="a").inc()
    c.labels(route="a").inc(2)
    c.labels(route="b").inc()
    assert c.labels(route="a").value == 3
    assert c.labels(route="b").value == 1
    g = reg.gauge("g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    # get-or-create: same name+kind returns the same family
    assert reg.counter("c_total", labels=("route",)) is c
    with pytest.raises(ValueError):
        reg.gauge("c_total")            # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("c_total")          # label mismatch
    with pytest.raises(ValueError):
        c.labels(wrong="a")             # undeclared label name


def test_histogram_percentile_within_one_bin_width():
    reg = Registry()
    h = reg.histogram("lat_seconds")
    rs = np.random.RandomState(0)
    vals = rs.lognormal(mean=-3.0, sigma=1.0, size=2000)
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(vals.sum())
    for q in (1.0, 25.0, 50.0, 90.0, 99.0):
        hp = h.percentile(q)
        lp = float(np.percentile(vals, q))
        assert abs(hp - lp) <= max(h.bin_width(hp), h.bin_width(lp)), (
            f"p{q}: {hp} vs {lp}")


def test_histogram_under_overflow_and_reset():
    reg = Registry()
    h = reg.histogram("h", lo=1e-3, hi=1e0)
    h.observe(1e-9)                     # underflow
    h.observe(1e9)                      # overflow
    assert h.count == 2
    assert h.percentile(0.0) == h.lo
    assert h.percentile(100.0) == h.hi
    assert h.bin_width(1e9) == float("inf")
    h.reset()
    assert h.count == 0 and h.sum == 0.0
    assert h.percentile(50.0) is None


def test_derived_gauge_never_stale():
    reg = Registry()
    acc = reg.counter("accepted_total")
    drf = reg.counter("drafted_total")
    reg.derived_gauge("rate", lambda: acc.value / drf.value
                      if drf.value else 0.0)
    assert reg.snapshot()["gauges"]["rate"][""] == 0.0
    drf.inc(4)
    acc.inc(1)
    assert reg.snapshot()["gauges"]["rate"][""] == 0.25
    drf.inc(4)                          # rate recomputes even though acc
    assert reg.snapshot()["gauges"]["rate"][""] == 0.125   # didn't move
    with pytest.raises(ValueError):
        reg.derived_gauge("accepted_total", lambda: 0.0)   # name clash


def test_snapshot_deterministic_and_merge():
    def build():
        reg = Registry()
        reg.counter("c", labels=("k",)).labels(k="x").inc(2)
        reg.gauge("g").set(3)
        h = reg.histogram("h")
        for v in (0.01, 0.1, 0.1):
            h.observe(v)
        return reg

    a, b = build(), build()
    sa, sb = a.snapshot(), b.snapshot()
    assert json.dumps(sa, sort_keys=True) == json.dumps(sb, sort_keys=True)
    m = merge_snapshots(sa, sb)
    assert m["counters"]["c"]["k=x"] == 4            # counters add
    assert m["gauges"]["g"][""] == 3                 # gauges take rhs
    assert sum(m["histograms"]["h"][""]["counts"]) == 6
    assert m["histograms"]["h"][""]["sum"] == pytest.approx(0.42)
    # mismatched edge grids must refuse to merge
    other = Registry()
    other.histogram("h", lo=1e-2).observe(0.1)
    with pytest.raises(ValueError):
        merge_snapshots(sa, other.snapshot())


def test_prometheus_text_exposition():
    reg = Registry()
    reg.counter("req_total", "requests", labels=("route",)) \
        .labels(route="a").inc(2)
    reg.gauge("level").set(1)
    h = reg.histogram("lat", lo=0.1, hi=10.0, bins_per_decade=1)
    h.observe(0.5)
    h.observe(50.0)
    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert 'req_total{route="a"} 2' in text
    assert "level 1" in text
    assert 'lat_bucket{le="+Inf"} 2' in text
    assert "lat_count 2" in text
    # cumulative buckets are monotonically non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("lat_bucket")]
    assert cums == sorted(cums)


def test_jsonl_exporter(tmp_path):
    reg = Registry()
    c = reg.counter("n")
    path = tmp_path / "m.jsonl"
    exp = JsonlExporter(str(path), reg, every=10, clock=lambda: 42.0)
    for tick in range(25):
        c.inc()
        exp.maybe_export(tick)
    exp.close(25)
    lines = [json.loads(s) for s in path.read_text().splitlines()]
    assert [r["tick"] for r in lines] == [0, 10, 20, 25]
    assert lines[-1]["metrics"]["counters"]["n"][""] == 25
    assert all(r["t"] == 42.0 for r in lines)
    exp.close()                          # idempotent
    assert len(path.read_text().splitlines()) == 4


def test_counterdict_is_a_dict_shim():
    reg = Registry()
    fam = reg.counter("disp_total", labels=("route",))
    d = CounterDict(fam, ("fused", "gather"))
    d["fused"] += 1
    d["fused"] += 1
    d["gather"] += 1
    assert d["fused"] == 2
    assert dict(d) == {"fused": 2, "gather": 1}
    assert d == {"fused": 2, "gather": 1}
    assert list(d) == ["fused", "gather"]
    assert "fused" in d and "bogus" not in d
    with pytest.raises(KeyError):
        d["bogus"]
    # the same values are visible through the registry
    assert reg.snapshot()["counters"]["disp_total"]["route=fused"] == 2


def test_statsview_read_write_and_derived_read_only():
    reg = Registry()
    view = StatsView()
    c = reg.counter("x_total")
    view.bind("x", lambda: int(c.value), c.set)
    view.bind("rate", lambda: 0.5)      # no setter: derived
    view["x"] += 3
    assert view["x"] == 3 and c.value == 3
    assert view["rate"] == 0.5
    assert dict(view) == {"x": 3, "rate": 0.5}
    assert view.get("missing") is None
    with pytest.raises(TypeError):
        view["rate"] = 1.0              # derived keys reject assignment
    with pytest.raises(KeyError):
        view["missing"] = 1


# ---------------------------------------------------------------------------
# Tracer + prof units.
# ---------------------------------------------------------------------------

def test_tracer_phase_chain_and_terminal():
    clk = FakeClock()
    tr = SpanTracer(clock=clk)
    tr.req_phase(7, "queued")
    clk.t = 1.0
    tr.req_phase(7, "prefill", slot=0)
    clk.t = 3.0
    tr.req_phase(7, "decode")
    clk.t = 5.0
    tr.req_terminal(7, "length", tokens=4)
    spans = tr.spans_for(7)
    assert [s.name for s in spans] == ["queued", "prefill", "decode"]
    # contiguous: each span closes exactly where the next opens
    for a, b in zip(spans, spans[1:]):
        assert a.t1 == b.t0
    assert all(s.t1 >= s.t0 for s in spans)
    terms = tr.terminals_for(7)
    assert len(terms) == 1
    assert terms[0].name == "terminal:length"
    assert terms[0].args["finish_reason"] == "length"

    ct = tr.chrome_trace()
    json.dumps(ct)                       # must be valid JSON
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 3
    assert min(e["ts"] for e in ct["traceEvents"]
               if e["ph"] != "M") == 0.0  # ts is relative to first event


def test_global_tracer_hook():
    tr = SpanTracer(clock=lambda: 0.0)
    instant_global("allocator", "audit")     # no tracer: a no-op
    set_global_tracer(tr)
    try:
        instant_global("allocator", "audit", free=3)
    finally:
        set_global_tracer(None)
    instant_global("allocator", "audit")     # detached again
    assert len(tr.instants) == 1
    assert tr.instants[0].track == "allocator"
    assert tr.instants[0].args == {"free": 3}


def test_prof_disabled_is_shared_nullcontext():
    p = Prof(enabled=False)
    assert p.annotate("decode") is p.annotate("prefill")  # one shared obj
    with p.annotate("decode"):
        pass
    assert parse_tick_window("3:9") == (3, 9)
    for bad in ("9", "5:3", "-1:2", "a:b"):
        with pytest.raises(ValueError):
            parse_tick_window(bad)


# ---------------------------------------------------------------------------
# Engine integration.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke():
    cfg = registry.get_smoke_config("qwen3_1_7b")
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0), cfg)


def _reqs(cfg, n=4, seed=5, max_new=8, **kw):
    rs = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rs.randint(0, cfg.vocab_size,
                                      size=int(rs.randint(4, 12))).tolist(),
                    max_new_tokens=max_new, **kw)
            for i in range(n)]


#: every span a plain paged tick can open (``repro/obs/__init__.py``)
TICK_SPANS = {"engine.tick", "engine.expire", "engine.admit", "engine.map",
              "engine.rng", "engine.commit", "engine.pressure", "decode",
              "decode.inputs", "decode.launch", "decode.wait", "prefill",
              "prefill.inputs", "prefill.launch", "prefill.sample"}


class SpyProf(Prof):
    """A disabled ``Prof`` that notes what each site was handed."""

    def __init__(self):
        super().__init__(enabled=False)
        self.handed = []

    def annotate(self, name):
        ctx = super().annotate(name)
        self.handed.append((name, ctx))
        return ctx


def test_engine_off_is_structurally_noop(smoke):
    cfg, model, params = smoke
    eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                 max_prompt_len=16)
    assert eng._tracer is None
    assert eng._obs_tick is None
    assert not eng._prof.enabled
    assert not eng.obs.enabled
    # the registry is still live: stats reads go through it
    eng.stats["tokens_out"] += 2
    snap = eng.obs.registry.snapshot()
    assert snap["counters"]["serve_tokens_out_total"][""] == 2
    assert "serve_acceptance_rate" in snap["gauges"]
    # every span site of a paged tick is handed the one shared no-op
    spy = SpyProf()
    eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                 max_prompt_len=16, paged=True, block_size=8,
                 obs=Observability(prof=spy))
    assert not eng.obs.enabled
    eng.run(_reqs(cfg), max_ticks=400)
    assert {n for n, _ in spy.handed} == TICK_SPANS
    assert all(ctx is prof_mod._NULL for _, ctx in spy.handed)


def test_engine_streams_identical_with_obs_on(smoke):
    cfg, model, params = smoke
    runs = []
    for obs in (None, Observability(tracer=SpanTracer())):
        reqs = _reqs(cfg)
        eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                     max_prompt_len=16, obs=obs)
        eng.run(reqs, max_ticks=400)
        runs.append([r.generated for r in reqs])
    assert runs[0] == runs[1]


class RecordingProf(Prof):
    """An enabled ``Prof`` that records the span tree instead of writing
    ``TraceAnnotation``s; ``call`` adds the engine calls it wraps as
    leaves, so each span's contents can be read back."""

    def __init__(self):
        super().__init__(enabled=True)
        self.roots = []
        self._stack = []

    def _open(self, name):
        node = {"name": name, "in": []}
        (self._stack[-1]["in"] if self._stack else self.roots).append(node)
        return node

    @contextlib.contextmanager
    def annotate(self, name):
        self._stack.append(self._open(name))
        try:
            yield
        finally:
            self._stack.pop()

    def call(self, name, fn):
        def wrapped(*a, **kw):
            self._open("call:" + name)
            return fn(*a, **kw)
        return wrapped


def _spans(node):
    return [c["name"] for c in node["in"] if not c["name"].startswith("call:")]


def _calls(node):
    """The wrapped calls anywhere inside ``node``, in order."""
    out = []
    for c in node["in"]:
        out += [c["name"][5:]] if c["name"].startswith("call:") else _calls(c)
    return out


def _child(node, name):
    (c,) = [c for c in node["in"] if c["name"] == name]
    return c


def _cache(paged):
    return dict(paged=True, block_size=8) if paged else {}


def _recorded_run(smoke, paged=True, **kw):
    """An engine under a ``RecordingProf``, run tick by tick:
    ``(prof, ticks run, requests)``.  Five requests on two slots make
    ticks that admit and ticks that only decode."""
    cfg, model, params = smoke
    prof = RecordingProf()
    eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                 max_prompt_len=16, obs=Observability(prof=prof),
                 **_cache(paged), **kw)
    for name in ("_decode", "_decode_rng", "_prefill", "_sample",
                 "_admit_rng") + (() if paged else ("_insert",)):
        setattr(eng, name, prof.call(name, getattr(eng, name)))
    if paged:
        eng.allocator.alloc_slot = prof.call("alloc_slot",
                                             eng.allocator.alloc_slot)
    reqs = _reqs(cfg, n=5)
    for r in reqs:
        eng.submit(r)
    ticks = 0
    while eng.has_work:
        eng.tick()
        ticks += 1
        assert ticks < 400
    return prof, ticks, reqs


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_tick_phases_nest_and_keep_decode_and_prefill_contents(smoke,
                                                               paged):
    """One ``engine.tick`` per tick holds every span and call of it, its
    phases in order; ``decode`` and ``prefill`` hold the calls they held
    before the split (the decode key's ``fold_in`` stays outside)."""
    prof, ticks, _ = _recorded_run(smoke, paged)
    assert [r["name"] for r in prof.roots] == ["engine.tick"] * ticks
    mapped = ["engine.map"] if paged else []
    inputs = ["alloc_slot"] if paged else []
    launch = ["_prefill"] if paged else ["_prefill", "_insert"]
    admits = decodes = 0
    for tick in prof.roots:
        phases = _spans(tick)
        assert _calls(tick) and not [c for c in tick["in"]
                                     if c["name"].startswith("call:")]
        assert phases in (
            ["engine.expire", "engine.admit"] + mapped
            + ["engine.pressure"],
            ["engine.expire", "engine.admit"] + mapped
            + ["engine.rng", "decode", "engine.commit", "engine.pressure"])
        for pre in _child(tick, "engine.admit")["in"]:
            admits += 1
            assert pre["name"] == "prefill"
            assert _spans(pre) == ["prefill.inputs", "prefill.launch",
                                   "prefill.sample"]
            assert _calls(pre) == inputs + launch + ["_admit_rng",
                                                     "_sample"]
            assert _calls(_child(pre, "prefill.inputs")) == inputs
            assert _calls(_child(pre, "prefill.launch")) == launch
        if "decode" in phases:
            decodes += 1
            dec = _child(tick, "decode")
            assert _spans(dec) == ["decode.inputs", "decode.launch",
                                   "decode.wait"]
            assert _calls(dec) == ["_decode"]
            assert _calls(_child(dec, "decode.launch")) == ["_decode"]
            assert _calls(_child(tick, "engine.rng")) == ["_decode_rng"]
        for quiet in ["engine.expire", "engine.pressure"] + mapped:
            assert _calls(_child(tick, quiet)) == []
    assert admits == 5 and decodes > 5


def test_spec_tick_splits_verify_like_decode(smoke):
    """A speculative tick: ``engine.rng`` holds the tick's key, ``draft``
    and ``verify`` follow, and ``verify`` splits into inputs, launch and
    wait as ``decode`` does."""
    from repro.spec import ModelDraft

    cfg, _, params = smoke
    prof, _, _ = _recorded_run(smoke, spec_k=2,
                               draft=ModelDraft(cfg, params=params))
    spec_ticks = [t for t in prof.roots if "verify" in _spans(t)]
    assert spec_ticks
    for tick in spec_ticks:
        assert _spans(tick) == ["engine.expire", "engine.admit",
                                "engine.map", "engine.rng", "draft",
                                "verify", "engine.commit",
                                "engine.pressure"]
        assert _spans(_child(tick, "verify")) == [
            "verify.inputs", "verify.launch", "verify.wait"]
        assert _calls(_child(tick, "engine.rng")) == ["_decode_rng"]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_engine_streams_identical_with_prof_on(smoke, paged):
    cfg, model, params = smoke
    runs = []
    for prof in (Prof(enabled=False), Prof(enabled=True), RecordingProf()):
        reqs = _reqs(cfg, n=5)
        eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                     max_prompt_len=16, obs=Observability(prof=prof),
                     **_cache(paged))
        eng.run(reqs, max_ticks=400)
        runs.append([r.generated for r in reqs])
    assert runs[0] == runs[1] == runs[2]


def test_profiler_capture_names_every_tick_phase(smoke, tmp_path):
    """A ``jax.profiler`` capture of a few ticks carries every phase name
    on the host plane, which is where the benchmark's reduction reads
    them."""
    from jax.profiler import ProfileData

    cfg, model, params = smoke
    eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                 max_prompt_len=16, paged=True, block_size=8,
                 obs=Observability(prof=Prof(enabled=True)))
    eng.run(_reqs(cfg, n=1, max_new=2), max_ticks=50)     # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(_reqs(cfg, n=3, max_new=4), max_ticks=50)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert TICK_SPANS <= names


def test_engine_acceptance_rate_is_derived(smoke):
    cfg, model, params = smoke
    eng = Engine(model, cfg, params, n_slots=2, max_len=32,
                 max_prompt_len=16)
    assert eng.stats["acceptance_rate"] == 0.0
    eng.stats["drafted"] += 8
    eng.stats["accepted"] += 2
    assert eng.stats["acceptance_rate"] == 0.25
    eng.stats["drafted"] += 8            # recomputes without a spec tick
    assert eng.stats["acceptance_rate"] == 0.125
    with pytest.raises(TypeError):
        eng.stats["acceptance_rate"] = 0.9


def _chaos_run(smoke):
    """One seeded chaos run over a virtual clock; returns
    (requests, tracer, registry snapshot)."""
    cfg, model, params = smoke
    clock = FakeClock()
    fault = FaultPlan(seed=3, p_alloc_fail=0.08, p_spurious_stall=0.04,
                      nan_ticks=(5, 11), p_slow=0.05, slow_extra_s=123.0)
    obs = Observability(tracer=SpanTracer())
    eng = Engine(model, cfg, params, n_slots=3, max_len=48,
                 max_prompt_len=24, paged=True, block_size=8, n_blocks=10,
                 clock=clock, fault=fault, obs=obs)
    reqs = _reqs(cfg, n=6, seed=9, max_new=10)
    reqs[3].deadline_s = 0.5             # will expire mid-run
    reqs[4].max_preemptions = 0          # first preemption is terminal
    for r in reqs:
        eng.submit(r)
    for _ in range(300):
        if not eng.has_work:
            break
        eng.tick()
        clock.t += 0.05
    assert all(r.done for r in reqs)
    obs.close()
    return reqs, obs.tracer, obs.registry.snapshot()


def test_chaos_trace_complete_and_deterministic(smoke):
    reqs, tr, snap = _chaos_run(smoke)

    for r in reqs:
        spans = tr.spans_for(r.rid)
        assert spans, f"rid={r.rid}: no spans"
        assert spans[0].name == "queued"
        # contiguous, time-ordered, non-negative durations
        for s in spans:
            assert s.t1 >= s.t0
        for a, b in zip(spans, spans[1:]):
            assert a.t1 == b.t0, f"rid={r.rid}: gap between phases"
        # exactly one terminal event, agreeing with the request
        terms = tr.terminals_for(r.rid)
        assert len(terms) == 1, f"rid={r.rid}: {len(terms)} terminals"
        assert terms[0].name == f"terminal:{r.finish_reason}"
        # the terminal closes the chain: nothing opens after it
        assert all(s.t1 <= terms[0].t for s in spans)
        # a preempted request's backoff span follows its preempt instant
        preempts = [i for i in tr.instants
                    if i.track == f"req {r.rid}" and i.name == "preempt"]
        if preempts:
            backoffs = [s for s in spans if s.name == "backoff"]
            assert backoffs, f"rid={r.rid}: preempt without backoff span"

    # the chaos knobs must actually have fired to make this test count
    names = {i.name for i in tr.instants}
    assert "fault:corrupt_logits" in names
    assert "fault:slow_tick" in names
    # Chrome export is valid JSON with every request track named
    ct = tr.chrome_trace()
    json.dumps(ct)
    tracks = {e["args"]["name"] for e in ct["traceEvents"]
              if e["ph"] == "M"}
    assert {f"req {r.rid}" for r in reqs} <= tracks

    # replay determinism: same seeds + virtual clock => identical trace
    # and identical metrics snapshot
    reqs2, tr2, snap2 = _chaos_run(smoke)
    assert [r.finish_reason for r in reqs] == \
        [r.finish_reason for r in reqs2]
    assert json.dumps(ct, sort_keys=True) == \
        json.dumps(tr2.chrome_trace(), sort_keys=True)
    assert json.dumps(snap, sort_keys=True) == \
        json.dumps(snap2, sort_keys=True)


def test_engineered_terminals_timeout_rejected_preempted_limit(smoke):
    cfg, model, params = smoke

    # timeout: a queued request's SLO expires while another holds the slot
    clock = FakeClock()
    obs = Observability(tracer=SpanTracer())
    eng = Engine(model, cfg, params, n_slots=1, max_len=32,
                 max_prompt_len=16, clock=clock, obs=obs)
    hog = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=12)
    slo = Request(rid=1, prompt=[4, 5, 6], max_new_tokens=4,
                  deadline_s=0.5)
    eng.submit(hog)
    eng.tick()                           # hog admitted
    eng.submit(slo)
    clock.t = 2.0                        # past rid=1's deadline
    eng.tick()
    assert slo.finish_reason == "timeout"
    assert [i.name for i in obs.tracer.terminals_for(1)] == \
        ["terminal:timeout"]
    # the queued span still closed (no dangling open phase)
    assert obs.tracer.spans_for(1)[-1].t1 == 2.0

    # rejected: the ladder's shed rung bounds the queue
    obs = Observability(tracer=SpanTracer())
    eng = Engine(model, cfg, params, n_slots=1, max_len=32,
                 max_prompt_len=16, queue_bound=1, obs=obs)
    eng._set_level(len(eng._levels) - 1)           # force "shed"
    victims = _reqs(cfg, n=3, seed=11, max_new=2)
    for r in victims:
        eng.submit(r)
    shed = [r for r in victims if r.finish_reason == "rejected"]
    assert shed, "shed level + bounded queue produced no rejection"
    for r in shed:
        assert [i.name for i in obs.tracer.terminals_for(r.rid)] == \
            ["terminal:rejected"]

    # preempted_limit: a dry pool deadlock preempts the only active
    # request, whose requeue budget is zero
    obs = Observability(tracer=SpanTracer())
    eng = Engine(model, cfg, params, n_slots=1, max_len=64,
                 max_prompt_len=8, paged=True, block_size=4, n_blocks=3,
                 obs=obs)
    doomed = Request(rid=0, prompt=[1] * 6, max_new_tokens=30,
                     max_preemptions=0)
    eng.run([doomed], max_ticks=100)
    assert doomed.finish_reason == "preempted_limit"
    assert [i.name for i in obs.tracer.terminals_for(0)] == \
        ["terminal:preempted_limit"]
