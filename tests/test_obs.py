"""Observability layer (DESIGN.md section 12): span tracing, bounded
metrics, straggler detection, flocking telemetry.

Covers the exporter's structural invariants (X-span nesting, async
request chains, Chrome schema) with virtual-clock determinism, the
profiler annotations every span opens, the span tree inside a served
tick (dispatch, device wait, logits copy, compaction, slot installs), the
streaming histograms' exactness contract (means/totals identical to the
per-step values, quantiles within one bucket width of exact
percentiles on a recorded drain), the abort-reason split and
``prefix_evicted_refs`` accounting, the straggler monitor, end-to-end
trace<->metrics reconciliation on a real speculative drain, flocking
telemetry not perturbing served tokens, and the disabled path growing
nothing per tick.
"""
import json
import logging

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import GriffinConfig
from repro.models import decoder
from repro.obs.export import chrome_trace, validate_chrome_trace, write_trace
from repro.obs.registry import (
    Registry,
    exp_buckets,
    linear_buckets,
    validate_prometheus_text,
)
from repro.obs.stragglers import StepTimeMonitor
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving.metrics import ServingMetrics
from repro.serving.server import PagedServer


class FakeClock:
    """Deterministic monotone clock: every read advances 1 ms."""

    def __init__(self, start: float = 100.0, step: float = 1e-3):
        self.t = start
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def _drive(tr: Tracer) -> None:
    with tr.span("tick", tick=1):
        with tr.span("plan"):
            pass
        with tr.span("decode", batch=2):
            pass
        tr.instant("mark", foo=1)
    tr.abegin(5, "request", prompt_tokens=3)
    tr.ainstant(5, "first_token")
    tr.aend(5, "request", generated_tokens=4)
    tr.counter("pool", occupancy=0.5, decode_batch=2)


# ---------------------------------------------------------------------------
# Tracer + exporter
# ---------------------------------------------------------------------------

def test_tracer_virtual_clock_determinism():
    """Two recorders driven by identical virtual clocks produce
    byte-identical traces — timestamps are relative to the first event,
    nothing depends on ambient wall time."""
    traces = []
    for _ in range(2):
        tr = Tracer(clock=FakeClock())
        _drive(tr)
        traces.append(tr)
    assert traces[0].events == traces[1].events
    assert json.dumps(chrome_trace(traces[0]), sort_keys=True) \
        == json.dumps(chrome_trace(traces[1]), sort_keys=True)
    # relative timestamps: the first event anchors at 0
    assert min(e["ts"] for e in traces[0].events) == 0.0


def test_tracer_span_nesting_and_export_order():
    tr = Tracer(clock=FakeClock())
    _drive(tr)
    # raw buffer appends X events on exit (children first); the export
    # re-sorts by ts so viewers see parents first
    obj = chrome_trace(tr, meta={"case": "unit"})
    assert validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert obj["otherData"]["case"] == "unit"
    x = [e for e in evs if e["ph"] == "X"]
    names = [e["name"] for e in x]
    assert names == ["tick", "plan", "decode"]  # ts order = parent first
    tick = next(e for e in x if e["name"] == "tick")
    for child in x:
        if child is tick:
            continue
        assert child["ts"] >= tick["ts"]
        assert child["ts"] + child["dur"] <= tick["ts"] + tick["dur"]


def test_validator_catches_corruption():
    tr = Tracer(clock=FakeClock())
    _drive(tr)
    good = chrome_trace(tr)
    assert validate_chrome_trace(good) == []

    def corrupt(mutate):
        obj = json.loads(json.dumps(good))
        mutate(obj["traceEvents"])
        return validate_chrome_trace(obj)

    def overlap(evs):
        # two partially overlapping X spans on one tid
        evs.append({"ph": "X", "name": "a", "pid": 1, "tid": 9,
                    "ts": 0.0, "dur": 10.0})
        evs.append({"ph": "X", "name": "b", "pid": 1, "tid": 9,
                    "ts": 5.0, "dur": 10.0})

    assert any("overlaps" in e for e in corrupt(overlap))
    assert any("bad ph" in e for e in corrupt(
        lambda evs: evs.append({"ph": "?", "name": "x", "pid": 1,
                                "tid": 1, "ts": 0.0})))
    assert any("bad dur" in e for e in corrupt(
        lambda evs: evs.append({"ph": "X", "name": "x", "pid": 1,
                                "tid": 1, "ts": 0.0, "dur": -1.0})))
    assert any("begin events" in e for e in corrupt(
        lambda evs: evs.append({"ph": "b", "name": "request", "id": 5,
                                "cat": "request", "pid": 1, "tid": 1,
                                "ts": 0.0})))
    assert any("outside" in e for e in corrupt(
        lambda evs: evs.append({"ph": "n", "name": "late", "id": 5,
                                "cat": "request", "pid": 1, "tid": 1,
                                "ts": 1e12})))


class _AnnotationRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter
    and exit with the annotation's name."""
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.mark.parametrize("make,annotated", [
    (lambda: Tracer(clock=FakeClock(), annotate_jax=True), True),
    (lambda: Tracer(clock=FakeClock()), False),
    (lambda: NULL_TRACER, False),
], ids=["annotated", "not_annotated", "null"])
def test_spans_open_profiler_annotations(monkeypatch, make, annotated):
    """With ``annotate_jax`` each span is also a profiler annotation of
    its own name, opened and closed in nesting order, so the span tree
    lies on the device trace's clock; otherwise none is opened."""
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _AnnotationRecorder)
    monkeypatch.setattr(_AnnotationRecorder, "log", [])
    tr = make()
    _drive(tr)
    want = [("enter", "tick"), ("enter", "plan"), ("exit", "plan"),
            ("enter", "decode"), ("exit", "decode"), ("exit", "tick")]
    assert _AnnotationRecorder.log == (want if annotated else [])
    parents = {e["name"]: e.get("args", {}).get("parent")
               for e in tr.events if e["ph"] == "X"}
    if tr.enabled:
        assert parents == {"tick": None, "plan": "tick", "decode": "tick"}


def test_tracer_bounded_buffer_drops():
    tr = Tracer(clock=FakeClock(), max_events=3)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events) == 3
    assert tr.dropped == 7
    assert validate_chrome_trace(chrome_trace(tr)) == []


def test_write_trace_roundtrip(tmp_path):
    tr = Tracer(clock=FakeClock())
    _drive(tr)
    path = write_trace(tr, tmp_path / "t.json", meta={"k": "v"})
    obj = json.loads(path.read_text())
    assert validate_chrome_trace(obj) == []
    assert obj["otherData"]["k"] == "v"


# ---------------------------------------------------------------------------
# Registry + histograms
# ---------------------------------------------------------------------------

def test_registry_get_or_create_identity_and_conflicts():
    reg = Registry()
    c1 = reg.counter("hits", labels={"layer": "a"})
    c2 = reg.counter("hits", labels={"layer": "a"})
    assert c1 is c2
    assert reg.counter("hits", labels={"layer": "b"}) is not c1
    with pytest.raises(TypeError):
        reg.gauge("hits", labels={"layer": "a"})
    with pytest.raises(ValueError):
        c1.inc(-1.0)
    h = reg.histogram("lat", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("lat", buckets=(1.0, 3.0))  # conflicting bounds
    assert reg.histogram("lat", buckets=(1.0, 2.0)) is h
    assert len(reg) == 3  # hits{a}, hits{b}, lat — failed gets unregistered


def test_bucket_builders():
    assert linear_buckets(0.05, 1.0, 20)[0] == pytest.approx(0.05)
    assert linear_buckets(0.05, 1.0, 20)[-1] == pytest.approx(1.0)
    e = exp_buckets(1.0, 2.0, 4)
    assert e == (1.0, 2.0, 4.0, 8.0)
    with pytest.raises(ValueError):
        exp_buckets(0.0, 2.0, 3)


def test_histogram_exact_moments_and_quantile_bound():
    """sum/count/mean are exact (same float additions as a list), and
    the interpolated quantile is within one bucket width of the exact
    percentile."""
    rng = np.random.default_rng(3)
    vals = rng.uniform(0.0, 1.0, size=500)
    reg = Registry()
    h = reg.histogram("occ", buckets=linear_buckets(0.05, 1.0, 20))
    acc = 0.0
    for v in vals:
        h.observe(v)
        acc += float(v)
    assert h.sum == acc  # identical additions, identical order
    assert h.count == len(vals)
    assert h.mean == acc / len(vals)
    assert h.vmin == float(vals.min()) and h.vmax == float(vals.max())
    width = 0.05
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = float(np.percentile(vals, q * 100))
        assert abs(h.quantile(q) - exact) <= width + 1e-9, q
    # quantiles stay inside the observed range
    assert h.vmin <= h.quantile(0.0) <= h.quantile(1.0) <= h.vmax


def test_prometheus_text_validates_and_shape():
    reg = Registry()
    reg.counter("reqs", help="total requests").inc(3)
    reg.gauge("occ", labels={"pool": "kv"}).set(0.5)
    h = reg.histogram("lat", buckets=(0.1, 1.0), help="latency")
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.prometheus_text()
    assert validate_prometheus_text(text) == []
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text
    snap = reg.snapshot()["metrics"]
    hist = next(m for m in snap if m["name"] == "lat")
    assert hist["count"] == 3 and hist["buckets"][-1]["count"] == 3
    # malformed expositions are caught
    assert validate_prometheus_text("no_type_metric 1\n")
    assert validate_prometheus_text("# TYPE x histogram\nx_bucket 1\n")


# ---------------------------------------------------------------------------
# ServingMetrics: abort split, evicted refs, bounded gauges
# ---------------------------------------------------------------------------

def test_abort_reason_split():
    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0])
    for rid, (aborted, reason) in enumerate(
            [(True, "oom"), (True, "cancelled"), (False, "oom")]):
        m.on_submit(rid, prompt_tokens=4)
        m.on_finish(rid, aborted=aborted, reason=reason)
    s = m.summary()
    assert s["requests_aborted"] == 2.0
    assert s["requests_aborted_oom"] == 1.0
    assert s["requests_aborted_cancelled"] == 1.0
    assert s["requests_finished"] == 1.0
    assert m.requests[0].abort_reason == "oom"
    assert m.requests[1].abort_reason == "cancelled"
    assert m.requests[2].abort_reason is None


def test_prefix_evicted_refs_accumulate():
    """The satellite fix: ``on_prefix_evict`` used to drop its
    ``refs_released`` argument on the floor."""
    m = ServingMetrics(clock=lambda: 0.0)
    m.on_prefix_evict(3)
    m.on_prefix_evict(5)
    s = m.summary()
    assert s["prefix_evictions"] == 2.0
    assert s["prefix_evicted_refs"] == 8.0


def test_per_step_gauges_are_bounded_not_lists():
    """The tentpole memory fix: per-step gauges must not grow with
    uptime.  They are registry histograms now; feeding many steps keeps
    the registry size and bucket vectors constant."""
    m = ServingMetrics(clock=lambda: 0.0)
    n_metrics = len(m.registry)
    n_buckets = len(m.pool_occupancy.counts)
    for i in range(1000):
        m.on_step(0.5, 2, shared_pages=1, attn_bytes_read=4096.0)
    assert not isinstance(m.pool_occupancy, list)
    assert len(m.registry) == n_metrics
    assert len(m.pool_occupancy.counts) == n_buckets
    assert m.pool_occupancy.count == 1000
    assert m.summary()["pool_occupancy_mean"] == pytest.approx(0.5)


def test_disabled_tracer_allocates_nothing():
    """NULL_TRACER is the default: its buffer is an immutable empty
    tuple and span() returns one shared context manager."""
    m = ServingMetrics(clock=lambda: 0.0)
    assert m.tracer is NULL_TRACER
    m.on_submit(0, prompt_tokens=2)
    m.on_first_token(0)
    m.on_finish(0)
    assert NULL_TRACER.events == ()
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
    with pytest.raises((AttributeError, TypeError)):
        NULL_TRACER.events.append({})  # loud, not silent growth


# ---------------------------------------------------------------------------
# Straggler monitor
# ---------------------------------------------------------------------------

def test_straggler_tick_flagging_and_throttle(caplog):
    reg = Registry()
    mon = StepTimeMonitor(reg, window=32, threshold=3.0, min_ticks=4,
                          log_every=8)
    with caplog.at_level(logging.WARNING, logger="repro.obs.stragglers"):
        for _ in range(8):
            assert not mon.on_tick(1e-3)
        assert mon.on_tick(10e-3) is True  # 10x the 1ms median
        assert mon.on_tick(10e-3) is True  # counted again...
    assert mon.straggler_ticks.value == 2.0
    warns = [r for r in caplog.records if "straggler tick" in r.message]
    assert len(warns) == 1  # ...but logged once per log_every flags
    assert reg.histogram("serving_tick_seconds",
                         buckets=mon.tick_seconds.bounds).count == 10


# ---------------------------------------------------------------------------
# End-to-end: traced drains reconcile with metrics, hooks don't perturb
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, n, rng_seed=11):
    rng = np.random.default_rng(rng_seed)
    return [rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(12, 40))).astype(np.int32)
            for _ in range(n)]


def test_trace_reconciles_with_metrics_exactly(tiny, tmp_path):
    """The acceptance bar: a traced speculative drain's request spans
    carry the same TTFT, token counts, preemption and spec-round counts
    the ServingMetrics timelines report — exactly, not approximately."""
    cfg, params = tiny
    tr = Tracer()
    srv = PagedServer(cfg, params,
                      gcfg=GriffinConfig(sparsity=0.5, per_shard_topk=False),
                      page_size=8, num_pages=48, n_slots=2,
                      prefill_chunk=16, max_len=96, spec_k=3, tracer=tr)
    for i, p in enumerate(_prompts(cfg, 4)):
        srv.submit(p, max_new=8, rid=i)
    out = srv.drain()
    assert set(out) == {0, 1, 2, 3}

    obj = chrome_trace(tr)
    assert validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    begins = [e for e in evs if e["ph"] == "b" and e.get("cat") == "request"]
    ends = [e for e in evs if e["ph"] == "e" and e.get("cat") == "request"]
    assert {e["id"] for e in begins} == {0, 1, 2, 3}
    assert {e["id"] for e in ends} == {0, 1, 2, 3}
    for e in ends:
        r = srv.metrics.requests[e["id"]]
        a = e["args"]
        assert a["generated_tokens"] == r.generated_tokens
        assert a["ttft_s"] == r.ttft  # same clock read, bit-equal
        assert a["preemptions"] == r.preemptions
        assert a["spec_rounds"] == r.spec_rounds
        assert a["prefill_chunks"] == r.prefill_chunks
        assert a["aborted"] is False
        # the async end lands inside the begin..end window the
        # validator already checked; span args make it self-contained
        b = next(x for x in begins if x["id"] == e["id"])
        assert b["args"]["prompt_tokens"] == r.prompt_tokens
    # per-request instants match the timeline counters
    for rid, r in srv.metrics.requests.items():
        n_spec = sum(1 for e in evs if e["ph"] == "n"
                     and e.get("id") == rid and e["name"] == "spec_round")
        assert n_spec == r.spec_rounds
        n_first = sum(1 for e in evs if e["ph"] == "n"
                      and e.get("id") == rid and e["name"] == "first_token")
        assert n_first == 1
    # tick spans: one per scheduler step, matching the steps counter
    ticks = [e for e in evs if e["ph"] == "X" and e["name"] == "tick"]
    assert len(ticks) == int(srv.metrics.summary()["steps"])
    # artifact round-trips through the real writer
    path = write_trace(tr, tmp_path / "drain.json")
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    # exposition validates with the drain's numbers synced in
    assert validate_prometheus_text(srv.metrics.prometheus_text()) == []


def _inside(events, parent):
    """X events that lie inside ``parent``'s interval and name it as
    their enclosing span."""
    t0, t1 = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in events if e is not parent
            and t0 <= e["ts"] and e["ts"] + e["dur"] <= t1
            and e.get("args", {}).get("parent") == parent["name"]]


@pytest.mark.parametrize("tier", [0.5, 1.0])
def test_tick_span_tree(tiny, tier):
    """A traced drain splits each ``decode`` span into the dispatch, the
    wait for the device and the logits copy; a compacted tier records
    one ``compact`` per request, a ``slot_install`` whose ``installed``
    args count every slot written and whose ``width`` is the widest
    layer's union, and a ``ffn_live_share`` on every decode dispatch;
    the dense tier records none of them.  No pool counter and no
    straggler-host gauge."""
    cfg, params = tiny
    tr = Tracer()
    srv = PagedServer(cfg, params,
                      gcfg=GriffinConfig(sparsity=0.5, per_shard_topk=False),
                      page_size=8, num_pages=48, n_slots=2,
                      prefill_chunk=16, max_len=96, tracer=tr,
                      default_tier=tier)
    installs = []
    orig = srv._install_pruned

    def counting(slot, selection):
        installs.append(slot)
        orig(slot, selection)

    srv._install_pruned = counting
    prompts = _prompts(cfg, 4)
    for i, p in enumerate(prompts):
        srv.submit(p, max_new=6, rid=i)
    assert set(srv.drain()) == {0, 1, 2, 3}

    x = [e for e in tr.events if e["ph"] == "X"]
    decodes = [e for e in x if e["name"] == "decode"]
    assert decodes
    for d in decodes:
        kids = {e["name"] for e in _inside(x, d)}
        assert {"decode_dispatch", "device_wait", "logits_d2h"} <= kids
    # the first token's fetch is the only wait a prefill chunk makes
    first = [e for e in x if e["name"] == "device_wait"
             and e["args"]["parent"] == "prefill_chunk"]
    assert len(first) == len(prompts)
    compacts = [e for e in x if e["name"] == "compact"]
    slot_installs = [e for e in x if e["name"] == "slot_install"]
    shares = [e["args"].get("ffn_live_share") for e in x
              if e["name"] == "decode_dispatch"]
    if tier < 1.0:
        assert sorted(e["args"]["rid"] for e in compacts) == [0, 1, 2, 3]
        assert all(e["args"]["tier"] == tier for e in compacts)
        assert installs
        assert sum(e["args"]["installed"] for e in slot_installs) \
            == len(installs)
        assert all(e["args"]["parent"] == "decode" for e in slot_installs)
        # a union holds at least one request's k and at most every
        # neuron of the layer
        k = srv.gcfg.k_of(cfg.d_ff)
        assert all(k <= e["args"]["width"] <= cfg.d_ff
                   for e in slot_installs)
        assert shares and all(
            k / cfg.d_ff <= v <= 1.0 for v in shares), shares
    else:
        assert compacts == [] and slot_installs == [] and installs == []
        assert shares and all(v is None for v in shares)
    assert not any(e["ph"] == "C" for e in tr.events)
    assert "serving_straggler_hosts" not in srv.metrics.prometheus_text()
    assert validate_chrome_trace(chrome_trace(tr)) == []


def test_histogram_quantiles_on_recorded_drain(tiny):
    """Percentile agreement on real per-step data: wrap on_step to keep
    the exact per-tick values alongside the histograms."""
    cfg, params = tiny
    srv = PagedServer(cfg, params, page_size=8, num_pages=32, n_slots=2,
                      prefill_chunk=16, max_len=64)
    exact = {"occ": [], "batch": []}
    orig = srv.metrics.on_step

    def wrapped(pool_in_use_frac, decode_batch, **kw):
        exact["occ"].append(float(pool_in_use_frac))
        exact["batch"].append(float(decode_batch))
        orig(pool_in_use_frac, decode_batch, **kw)

    srv.metrics.on_step = wrapped
    for i, p in enumerate(_prompts(cfg, 4, rng_seed=13)):
        srv.submit(p, max_new=6, rid=i)
    srv.drain()
    m = srv.metrics
    assert m.pool_occupancy.count == len(exact["occ"])
    assert m.pool_occupancy.sum == sum(exact["occ"])  # exact, not approx
    assert m.summary()["pool_occupancy_mean"] == \
        sum(exact["occ"]) / len(exact["occ"])
    for q in (0.5, 0.95):
        est = m.pool_occupancy.quantile(q)
        ref = float(np.percentile(exact["occ"], q * 100))
        assert abs(est - ref) <= 0.05 + 1e-9  # one occupancy bucket
        est = m.decode_batch_sizes.quantile(q)
        ref = float(np.percentile(exact["batch"], q * 100))
        assert abs(est - ref) <= 1.0 + 1e-9  # unit batch buckets


def test_cancel_splits_abort_reasons_and_frees_pages(tiny):
    """Client-side cancel: pages come back, allocator invariants hold,
    and the abort lands in the ``cancelled`` bucket (the satellite fix
    — both reasons used to collapse into one counter)."""
    cfg, params = tiny
    srv = PagedServer(cfg, params, page_size=8, num_pages=32, n_slots=2,
                      prefill_chunk=16, max_len=64, prefix_cache=False)
    prompts = _prompts(cfg, 3, rng_seed=17)
    for i, p in enumerate(prompts):
        srv.submit(p, max_new=30, rid=i)
    # let request 0 reach decode, then cancel it mid-flight
    for _ in range(6):
        srv.step()
    assert srv.cancel(0) is True
    assert srv.cancel(0) is False  # already gone
    assert srv.cancel(99) is False  # unknown rid
    out = srv.drain()
    assert 0 not in out and set(out) == {1, 2}
    s = srv.metrics.summary()
    assert s["requests_aborted"] == 1.0
    assert s["requests_aborted_cancelled"] == 1.0
    assert s["requests_aborted_oom"] == 0.0
    assert srv.metrics.requests[0].abort_reason == "cancelled"
    assert srv.sched.alloc.num_in_use == 0
    srv.sched.alloc.check()


def test_cancel_latency_reconciles_histogram_and_trace():
    """PR 7 satellite: the disconnect→pages-freed gap must tell one
    story in two places — the ``serving_cancel_latency_s`` histogram
    and the request's async span (``disconnect`` instant + the
    ``cancel_latency_s`` arg on its end event) — bit-equal, on the
    shared injected clock."""
    from repro.serving.clock import FakeClock as ManualClock
    from repro.serving.frontend import CANCELLED, ServingFrontend
    from repro.serving.sim import SimServer

    clk = ManualClock(start=50.0)
    tr = Tracer(clock=clk)
    m = ServingMetrics(clock=clk, tracer=tr)
    srv = SimServer(metrics=m)
    fe = ServingFrontend(srv, clock=clk)
    h = fe.submit(np.arange(6, dtype=np.int32), 20)
    for _ in range(4):
        fe.tick()
        clk.advance(0.001)
    assert h.tokens  # mid-decode, not a pending cancel
    t_disc = clk()
    h.cancel()  # client disconnect: stamps t_disc on the timeline
    clk.advance(0.0035)  # gap until the next tick boundary
    fe.tick()  # abort lands here; latency = 0.0035
    assert h.state == CANCELLED
    tl = m.requests[h.rid]
    assert tl.disconnect_t == t_disc
    lat = tl.finish_t - tl.disconnect_t
    assert lat == pytest.approx(0.0035)
    assert m.cancel_latency.count == 1
    assert m.cancel_latency.sum == lat  # the same float, not a re-derivation
    evs = chrome_trace(tr)["traceEvents"]
    disc = [e for e in evs if e["ph"] == "n" and e["name"] == "disconnect"
            and e.get("id") == h.rid]
    assert len(disc) == 1
    end = next(e for e in evs if e["ph"] == "e"
               and e.get("cat") == "request" and e["id"] == h.rid)
    assert end["args"]["cancel_latency_s"] == lat
    # the span geometry agrees too: end - disconnect == latency (in us)
    assert end["ts"] - disc[0]["ts"] == pytest.approx(lat * 1e6)
    s = m.summary()
    assert s["requests_aborted_cancelled"] == 1.0
    assert s["cancel_latency_mean_s"] == lat
    assert validate_chrome_trace(chrome_trace(tr)) == []
    assert validate_prometheus_text(m.prometheus_text()) == []


def test_flocking_telemetry_does_not_perturb_serving(tiny):
    """The dense probe runs over live pools without donating them:
    outputs must be token-identical with telemetry on, gauges must be
    populated and bounded by layer cardinality."""
    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    prompts = _prompts(cfg, 3, rng_seed=19)

    def run(flocking_every):
        srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8,
                          num_pages=48, n_slots=2, prefill_chunk=16,
                          max_len=96, flocking_every=flocking_every)
        for i, p in enumerate(prompts):
            srv.submit(p, max_new=10, rid=i)
        return srv.drain(), srv

    out_off, _ = run(0)
    out_on, srv = run(2)
    assert out_off == out_on  # probe perturbed nothing
    assert srv.flocking is not None
    assert srv.flocking.probes.value > 0
    assert srv.flocking.last  # per-request aggregates kept post-finish
    for v in srv.flocking.last.values():
        assert 0.0 <= v["jaccard"] <= 1.0
        assert 0.0 <= v["angular"] <= 1.0
    jac = [m for m in srv.metrics.registry
           if m.name == "flocking_jaccard"]
    assert jac and all(0.0 <= g.value <= 1.0 for g in jac)
    # label cardinality is layers, not requests
    assert all(dict(g.labels).keys() == {"layer"} for g in jac)
    # per-request working state is dropped at finish
    assert srv.flocking.live_rids() == []


def test_flocking_requires_griffin(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError):
        PagedServer(cfg, params, gcfg=None, flocking_every=4)
