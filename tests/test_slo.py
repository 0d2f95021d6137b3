"""SLO observability: quantile sketch, Histogram.quantile, SLOReport,
flight recorder, serving step-segment timing, load_bench harness, and
the metric-name/docs drift guard."""

import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import observability as obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- quantile sketch --------------------------------------------------------

def _rank_value(xs_sorted, q):
    """The sample the sketch contract targets: rank max(1, ceil(q*n)) —
    numpy.percentile(..., method='inverted_cdf') (same 1e-9 fp slack as
    QuantileSketch.quantile)."""
    rank = max(1, int(math.ceil(q * len(xs_sorted) - 1e-9)))
    return xs_sorted[rank - 1]


def test_sketch_matches_numpy_percentile_random():
    rng = np.random.RandomState(0)
    x = rng.lognormal(mean=-3.0, sigma=1.2, size=5000)  # latency-shaped
    alpha = 0.02
    sk = obs.QuantileSketch(relative_accuracy=alpha)
    for v in x:
        sk.observe(v)
    xs = np.sort(x)
    for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99):
        est = sk.quantile(q)
        true = float(np.percentile(x, 100 * q, method="inverted_cdf"))
        assert true == _rank_value(xs, q)       # convention matches numpy
        assert abs(est - true) / true <= alpha + 1e-9, (q, est, true)
    # deep tail: same bound vs the rank sample directly (numpy's own
    # q*n float rounding picks the NEIGHBORING order statistic at
    # 0.999*5000, so the exact numpy cross-check stops at p99)
    est = sk.quantile(0.999)
    true = _rank_value(xs, 0.999)
    assert abs(est - true) / true <= alpha + 1e-9
    assert sk.count == 5000
    assert sk.mean() == pytest.approx(float(x.mean()))


def test_sketch_adversarial_all_equal_and_bimodal():
    # all-equal: one bucket; the observed-min/max clamp answers exactly
    sk = obs.QuantileSketch(relative_accuracy=0.01)
    for _ in range(1000):
        sk.observe(0.123)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert sk.quantile(q) == 0.123

    # two-point bimodal: every quantile resolves to one of the two modes
    # (rank rule — no numpy-style midpoint interpolation across the gap)
    a, b = 1e-3, 2.0
    sk2 = obs.QuantileSketch(relative_accuracy=0.01)
    x = [a] * 500 + [b] * 500
    for v in x:
        sk2.observe(v)
    xs = np.sort(np.asarray(x))
    for q in (0.25, 0.5, 0.75, 0.99):
        true = _rank_value(xs, q)
        assert abs(sk2.quantile(q) - true) / true <= 0.01 + 1e-9
    assert sk2.quantile(0.5) == pytest.approx(a, rel=0.01)   # rank 500
    assert sk2.quantile(0.75) == pytest.approx(b, rel=0.01)


def test_sketch_edge_cases():
    sk = obs.QuantileSketch()
    assert sk.quantile(0.5) is None and sk.mean() is None
    with pytest.raises(ValueError):
        sk.quantile(1.5)
    with pytest.raises(ValueError):
        obs.QuantileSketch(relative_accuracy=1.0)
    # sub-min_value observations collapse into the zero bucket and are
    # answered as ~0 (clock-skew 0-durations must not crash the log)
    sk.observe(0.0)
    sk.observe(5.0)
    assert sk.quantile(0.25) == 0.0
    assert sk.quantile(1.0) == pytest.approx(5.0, rel=0.01)


def test_sketch_count_above_bucket_granular():
    sk = obs.QuantileSketch(relative_accuracy=0.02)
    assert sk.count_above(0.5) == 0             # empty
    for v in (0.0, 0.0, 0.01, 0.2, 0.2, 5.0):
        sk.observe(v)
    assert sk.count_above(-1.0) == 6            # negative: everything
    assert sk.count_above(0.0) == 4             # zero bucket excluded
    # thresholds well clear of bucket edges: exact whole-bucket answers
    assert sk.count_above(0.1) == 3
    assert sk.count_above(1.0) == 1
    assert sk.count_above(100.0) == 0


def test_sketch_merge_matches_pooled_quantiles_property():
    """The Router.metrics_snapshot claim: merging per-replica sketches
    then asking a quantile is within relative_accuracy of the
    POOLED-sample quantile — same bound as one sketch over everything."""
    rng = np.random.RandomState(1)
    alpha = 0.02
    parts = [rng.lognormal(mean=-3.0, sigma=1.2, size=n)
             for n in (400, 1500, 900)]         # uneven replica loads
    sketches = []
    for x in parts:
        sk = obs.QuantileSketch(relative_accuracy=alpha)
        for v in x:
            sk.observe(v)
        sketches.append(sk)
    merged = obs.QuantileSketch(relative_accuracy=alpha)
    for sk in sketches:
        assert merged.merge(sk) is merged       # chains, folds in place
    pooled = np.sort(np.concatenate(parts))
    assert merged.count == len(pooled)
    for q in (0.05, 0.5, 0.9, 0.99):
        est = merged.quantile(q)
        true = _rank_value(pooled, q)
        assert abs(est - true) / true <= alpha + 1e-9, (q, est, true)
    # merge also folds the count_above surface the watchdog reads
    thresh = float(np.median(pooled) * 4)
    true_above = int((pooled > thresh).sum())
    assert merged.count_above(thresh) == pytest.approx(
        true_above, abs=max(2, int(0.05 * true_above)))


def test_sketch_merge_geometry_and_type_errors():
    a = obs.QuantileSketch(relative_accuracy=0.02)
    b = obs.QuantileSketch(relative_accuracy=0.05)
    with pytest.raises(ValueError, match="geometry"):
        a.merge(b)
    with pytest.raises(TypeError):
        a.merge({"not": "a sketch"})
    # the source sketch is read-only under merge: folding b into a
    # fresh same-geometry sketch leaves b intact
    c = obs.QuantileSketch(relative_accuracy=0.05)
    b.observe(1.0)
    c.merge(b)
    assert b.count == 1 and c.count == 1


def test_sketch_registry_get_or_create_export_conflict(tmp_path):
    r = obs.MetricsRegistry()
    s = r.sketch("serving.ttft_s")
    s.observe(0.05)
    s.observe(0.2)
    assert r.sketch("serving.ttft_s") is s          # get-or-create
    with pytest.raises(ValueError, match="relative_accuracy"):
        r.sketch("serving.ttft_s", relative_accuracy=0.1)
    # prometheus: summary exposition with quantile labels
    txt = r.prometheus_text()
    assert "# TYPE serving_ttft_s summary" in txt
    assert 'serving_ttft_s{quantile="0.99"}' in txt
    assert "serving_ttft_s_count 2" in txt
    # jsonl: the sketch line parses and carries the quantiles
    p = str(tmp_path / "m.jsonl")
    r.export_jsonl(p)
    (line,) = [json.loads(ln) for ln in open(p)]
    assert line["type"] == "sketch" and line["count"] == 2
    assert line["quantiles"]["0.99"] == pytest.approx(0.2, rel=0.02)


# ---- Histogram.quantile -----------------------------------------------------

def test_histogram_quantile_matches_prometheus_le_semantics():
    r = obs.MetricsRegistry()
    h = r.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (1.0, 2.0, 4.0):
        h.observe(v)        # boundary values land in their own le bucket
    # rank q=1/3 resolves inside the le=1.0 bucket; linear interpolation
    # from the 0 lower edge of the lowest bucket reaches the bound
    assert h.quantile(1 / 3) == pytest.approx(1.0)
    assert h.quantile(2 / 3) == pytest.approx(2.0)
    assert h.quantile(1.0) == pytest.approx(4.0)
    # mid-bucket: target 1.5 of 3 → le=2.0 bucket, uniform-within-bucket
    assert h.quantile(0.5) == pytest.approx(1.5)
    # a rank landing in the +Inf overflow returns the highest finite
    # bound — Prometheus histogram_quantile behavior
    h2 = r.histogram("lat2", buckets=(1.0, 2.0))
    h2.observe(100.0)
    assert h2.quantile(0.99) == 2.0
    assert r.histogram("lat3", buckets=(1.0,)).quantile(0.5) is None
    with pytest.raises(ValueError):
        h.quantile(1.5)


# ---- SLOReport --------------------------------------------------------------

def test_slo_report_goodput_token_weighted():
    rep = obs.SLOReport(ttft_slo_s=0.5, tpot_slo_s=0.1)
    assert rep.add(0.1, 0.01, tokens=90) is True
    assert rep.add(0.9, 0.01, tokens=10) is False        # TTFT miss
    assert rep.goodput == pytest.approx(0.9)             # token-weighted
    f = rep.bench_fields()
    assert f["goodput"] == pytest.approx(0.9)
    assert f["slo_ttft_s"] == 0.5 and f["slo_tpot_s"] == 0.1
    assert f["ttft_p50_s"] == pytest.approx(0.1, rel=0.02)
    assert f["tpot_p99_s"] == pytest.approx(0.01, rel=0.02)
    # a 1-token request has no decode steps: tpot=None can't miss TPOT
    assert rep.add(0.1, None, tokens=1) is True
    # TPOT miss also kills goodput
    assert rep.add(0.1, 0.5, tokens=1) is False
    # no target configured → goodput omitted, not a vacuous 1.0
    rep2 = obs.SLOReport()
    rep2.add(0.2, 0.05)
    f2 = rep2.bench_fields()
    assert "goodput" not in f2 and f2["ttft_p50_s"] > 0
    # ttft_s=None (a request that died before its first token, e.g. a
    # chunked-engine deadline sweep mid-prefill): no crash, excluded
    # from the TTFT percentiles, but a TTFT-SLO miss — it must drag
    # goodput down, not vanish from it
    rep3 = obs.SLOReport(ttft_slo_s=0.5)
    assert rep3.add(0.1, None, tokens=1) is True
    assert rep3.add(None, None, tokens=1) is False
    assert rep3.goodput == pytest.approx(0.5)
    assert rep3.bench_fields()["ttft_p99_s"] == pytest.approx(0.1,
                                                              rel=0.02)
    # without a TTFT target a None ttft cannot miss anything
    rep4 = obs.SLOReport(tpot_slo_s=0.1)
    assert rep4.add(None, 0.01) is True


def test_bench_schema_percentile_fields():
    rec = obs.bench_record("x tok/s", 1.0, "tokens/s", device="cpu",
                           ttft_p99_s=0.5, tpot_p50_s=0.01,
                           goodput=0.93, offered_rps=12.0,
                           slo_ttft_s=1.0)
    assert obs.validate_bench(rec) is rec
    base = {"schema": obs.BENCH_SCHEMA, "metric": "m", "value": 1,
            "unit": "u", "device": "d"}
    with pytest.raises(ValueError, match="goodput"):
        obs.validate_bench(dict(base, goodput=1.5))
    with pytest.raises(ValueError, match="ttft_p99_s"):
        obs.validate_bench(dict(base, ttft_p99_s="fast"))
    # None is fine for every optional percentile field (e.g. tpot of a
    # run whose requests were all single-token)
    assert obs.validate_bench(dict(base, tpot_p99_s=None))


# ---- flight recorder --------------------------------------------------------

def test_flight_ring_wraparound_keeps_last_n():
    fr = obs.FlightRecorder(capacity=4)
    assert fr.events() == [] and len(fr) == 0
    for i in range(3):
        fr.record({"i": i})
    assert [e["i"] for e in fr.events()] == [0, 1, 2]     # pre-wrap
    for i in range(3, 10):
        fr.record({"i": i})
    assert [e["i"] for e in fr.events()] == [6, 7, 8, 9]  # exactly last N
    assert len(fr) == 4 and fr.total_events == 10


def test_flight_dump_jsonl_and_auto_dump_gating(tmp_path):
    fr = obs.FlightRecorder(capacity=8)       # no path configured
    fr.record({"i": 0})
    assert fr.auto_dump("whatever") is None   # no-op without a path
    p = str(tmp_path / "f.jsonl")
    assert fr.dump_jsonl(p, reason="manual") == p
    lines = [json.loads(ln) for ln in open(p)]
    assert lines[0]["schema"] == obs.FLIGHT_SCHEMA
    assert lines[0]["reason"] == "manual" and lines[0]["events"] == 1
    assert lines[1] == {"i": 0}
    # auto_dump never raises — the engine calls it while re-raising
    # PoolExhausted / injected faults, and an I/O error here would
    # replace the real exception (dump_jsonl, the manual form, does)
    bad = str(tmp_path / "no_such_dir" / "f.jsonl")
    fr2 = obs.FlightRecorder(capacity=2, auto_dump_path=bad)
    fr2.record({"i": 1})
    assert fr2.auto_dump("x") is None
    with pytest.raises(OSError):
        fr2.dump_jsonl(bad)


def test_step_telemetry_overhead_bounded():
    """The per-step cost of the new instrumentation (clock reads,
    segment-histogram observes, sketch observe, ring write) measured
    directly: it must stay far below any decode step (hundreds of µs on
    TPU, ms on CPU) — the 'near-zero steady-state overhead' contract."""
    r = obs.MetricsRegistry()
    fr = obs.FlightRecorder(capacity=256)
    n = 5000
    t0 = time.perf_counter()
    for i in range(n):
        a = time.perf_counter()
        b = time.perf_counter()
        c = time.perf_counter()
        d = time.perf_counter()
        r.histogram("serving.step_admit_s").observe(b - a)
        r.histogram("serving.step_dispatch_s").observe(c - b)
        r.histogram("serving.step_sync_s").observe(d - c)
        r.sketch("serving.ttft_s").observe(1e-3)
        fr.record({"step": i, "ts": d, "active": 1, "queued": 0,
                   "admitted": [], "retired": [], "prefills": [],
                   "t_admit_s": b - a, "t_dispatch_s": c - b,
                   "t_sync_s": d - c})
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 200e-6, f"telemetry costs {per_step*1e6:.1f}µs/step"


# ---- serving engine: step segments, sketches, auto-dumps --------------------

def _tiny_llama(L=2):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=L,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return cfg, m


def _dump_sections(path):
    """Parse a flight JSONL file into (header, events) sections."""
    lines = [json.loads(ln) for ln in open(path)]
    out = []
    i = 0
    while i < len(lines):
        assert lines[i].get("kind") == "flight_dump", lines[i]
        n = lines[i]["events"]
        out.append((lines[i], lines[i + 1:i + 1 + n]))
        i += 1 + n
    return out


def test_engine_step_segments_flight_and_auto_dumps(tmp_path):
    """One engine, four contracts: (1) per-segment step timing lands in
    stats + histograms and TTFT/TPOT in the serving sketches; (2) every
    step records a flight event; (3) a deadline retirement and (4) a
    fired decode.dispatch fault / PoolExhausted each auto-dump a ring
    snapshot whose last events reconstruct the failing step."""
    from paddle_tpu import serving
    from paddle_tpu.resilience import faults

    dump = str(tmp_path / "flight.jsonl")
    cfg, m = _tiny_llama()
    rng = np.random.RandomState(0)
    p = rng.randint(3, 512, (9,))
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=64, prefix_caching=False,
                                flight_dump_path=dump)
    reg = obs.registry()
    ttft0 = reg.sketch("serving.ttft_s").count

    # -- (1)+(2): normal request -------------------------------------------
    rid = eng.submit(serving.Request(p, max_new_tokens=4))
    eng.drain(max_steps=50)
    st = eng.stats
    assert st["requests_admitted"] == 1
    assert st["step_prefill_s"] > 0 and st["step_dispatch_s"] > 0
    assert reg.sketch("serving.ttft_s").count == ttft0 + 1
    assert reg.histogram("serving.step_admit_s").count >= st["steps"]
    assert reg.histogram("serving.step_dispatch_s").count >= st["steps"]
    evts = eng.flight.events()
    assert len(evts) == eng.flight.total_events     # no wrap yet
    assert evts[0]["admitted"] == [rid]
    assert evts[0]["prefills"] == [[0, 16, 1]]
    assert evts[-1]["retired"] == [[rid, "length"]]
    assert all(e["t_admit_s"] >= 0 for e in evts)
    # every tick event carries BOTH clocks: wall ts (cross-process
    # timeline alignment) and monotonic ts_mono (the timeline builder
    # re-anchors on it, so ordering survives wall-clock steps)
    assert all(e["ts"] > 1e9 and e["ts_mono"] >= 0 for e in evts)
    assert [e["ts_mono"] for e in evts] \
        == sorted(e["ts_mono"] for e in evts)
    assert not os.path.exists(dump)     # nothing dumped on a clean run

    # -- (3): deadline retirement auto-dumps --------------------------------
    rd = eng.submit(serving.Request(p, max_new_tokens=4, deadline_s=1e-9))
    eng.step()
    assert eng.results[rd].finish == "deadline"
    secs = _dump_sections(dump)
    hdr, events = secs[-1]
    assert hdr["reason"] == "deadline_retirement"
    assert [rd, "deadline"] in events[-1]["retired"]

    # -- (4a): fired fault dumps, last event reconstructs the failing step --
    with faults.plan(faults.Fault("decode.dispatch", kind="raise", at=1)):
        rf = eng.submit(serving.Request(p, max_new_tokens=4))
        with pytest.raises(RuntimeError, match="injected fault"):
            eng.step()      # admit (index 0) passes, dispatch (1) fires
    secs = _dump_sections(dump)
    hdr, events = secs[-1]
    assert hdr["reason"] == "error:RuntimeError"
    last = events[-1]
    assert "injected fault" in last["err"]
    assert last["admitted"] == [rf]         # the tick's work is visible
    assert last["prefills"] and last["t_dispatch_s"] is None
    # the fault seam itself also dumped (before the engine's own dump)
    assert any(h["reason"] == "fault:decode.dispatch:raise"
               for h, _ in secs)
    # an aborted tick leaves no queued dump behind (a pending deadline
    # dump must not resurface under the wrong reason on the next tick)
    assert eng._dump_pending is None

    # -- (4b): PoolExhausted dumps (a pool smaller than one request) --------
    dump2 = str(tmp_path / "flight2.jsonl")
    eng2 = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                 max_seq_len=64, num_blocks=3,
                                 prefix_caching=False,
                                 flight_dump_path=dump2)
    with pytest.raises(serving.PoolExhausted):
        eng2.submit(serving.Request(rng.randint(3, 512, (33,)),
                                    max_new_tokens=4))
    hdr, _ = _dump_sections(dump2)[-1]
    assert hdr["reason"] == "pool_exhausted:submit"


# ---- SLO burn-rate watchdog -------------------------------------------------

class _TripSource:
    """Watchdog trip target: anything with a ``flight`` ring (the
    Router's shape)."""

    def __init__(self):
        self.flight = obs.FlightRecorder(capacity=16, name="tier")


def test_burn_watchdog_window_semantics_and_gauges():
    r = obs.MetricsRegistry()
    wd = obs.BurnRateWatchdog(ttft_slo_s=0.1, error_budget=0.1,
                              min_samples=10, registry=r)
    # replica-labeled series sum naturally — the tier shape
    s0 = r.sketch("serving.ttft_s", replica="0")
    s1 = r.sketch("serving.ttft_s", replica="1")
    for _ in range(4):
        s0.observe(0.01)
    # thin window (4 < min_samples): not judged, no gauge, stays OPEN
    st = wd.check()
    assert st == {"burn": {}, "tripped": []}
    assert r.series("serving.slo_ttft_burn_rate") == []
    # more samples: the still-open window now spans ALL 20 (1 violation
    # across both replicas) -> burn = (1/20)/0.1 = 0.5, gauged
    for _ in range(15):
        s1.observe(0.01)
    s1.observe(5.0)
    st = wd.check()
    assert st["burn"]["ttft"] == pytest.approx(0.5)
    assert st["tripped"] == []
    assert r.gauge("serving.slo_ttft_burn_rate").value == 0.5
    # no new samples: the NEXT window is empty -> thin again, the gauge
    # keeps its last judged value
    st = wd.check()
    assert st["burn"] == {} and wd.trips == 0
    assert r.gauge("serving.slo_ttft_burn_rate").value == 0.5


def test_burn_watchdog_trip_dumps_flight_and_timeline(tmp_path):
    r = obs.MetricsRegistry()
    wd = obs.BurnRateWatchdog(ttft_slo_s=0.1, tpot_slo_s=0.05,
                              error_budget=0.1, trip_burn=1.0,
                              min_samples=8, dump_dir=str(tmp_path),
                              registry=r)
    sk = r.sketch("serving.ttft_s")
    for _ in range(8):
        sk.observe(0.01)
    tp = r.sketch("serving.tpot_s")
    for _ in range(4):
        tp.observe(0.01)
        tp.observe(5.0)             # 50% TPOT violations: burn 5.0
    src = _TripSource()
    src.flight.record({"step": 0, "ts": time.time()})
    st = wd.check(source=src)
    assert st["tripped"] == ["tpot"]
    assert st["burn"]["ttft"] == pytest.approx(0.0)
    assert st["burn"]["tpot"] == pytest.approx(5.0)
    assert wd.trips == 1
    # the trip counter is UNLABELED (one tier-wide series)
    assert r.counter("serving.slo_watchdog_trips").value == 1
    # the tripping source's ring got the postmortem marker
    marks = [e for e in src.flight.events()
             if e.get("kind") == "slo_burn_trip"]
    assert len(marks) == 1 and marks[0]["tripped"] == ["tpot"]
    assert marks[0]["burn"]["tpot"] == pytest.approx(5.0)
    # and a Perfetto timeline slice of that ring was written
    assert st["timeline_path"] == str(tmp_path / "slo_trip_1.json")
    doc = json.load(open(st["timeline_path"]))
    assert isinstance(doc["traceEvents"], list)
    assert any(e.get("args", {}).get("name") == "tier"
               for e in doc["traceEvents"] if e["ph"] == "M")


def test_burn_watchdog_check_never_raises(tmp_path):
    """A broken dump sink must not kill the serving tick: dump_dir
    colliding with an existing FILE makes the trip dump fail, and
    check() still returns (trip counted, no timeline_path)."""
    blocked = tmp_path / "blocked"
    blocked.write_text("in the way")
    r = obs.MetricsRegistry()
    wd = obs.BurnRateWatchdog(ttft_slo_s=0.1, min_samples=4,
                              dump_dir=str(blocked), registry=r)
    sk = r.sketch("serving.ttft_s")
    for _ in range(4):
        sk.observe(5.0)             # 100% violations
    st = wd.check(source=_TripSource())
    assert st["tripped"] == ["ttft"] and wd.trips == 1
    assert "timeline_path" not in st


def test_burn_watchdog_constructor_validation():
    with pytest.raises(ValueError, match="at least one"):
        obs.BurnRateWatchdog()
    with pytest.raises(ValueError, match="error_budget"):
        obs.BurnRateWatchdog(ttft_slo_s=0.1, error_budget=0.0)
    with pytest.raises(ValueError, match="error_budget"):
        obs.BurnRateWatchdog(ttft_slo_s=0.1, error_budget=1.5)
    with pytest.raises(ValueError, match=">= 1"):
        obs.BurnRateWatchdog(ttft_slo_s=0.1, check_every=0)
    with pytest.raises(ValueError, match=">= 1"):
        obs.BurnRateWatchdog(ttft_slo_s=0.1, min_samples=0)


# ---- metric-name drift guard ------------------------------------------------

def test_metric_names_documented_in_observability_table():
    """Every serving.*/resilience.*/decode.* metric name created
    literally anywhere in paddle_tpu/ must appear in
    docs/OBSERVABILITY.md — the docs table cannot silently rot as call
    sites are added. (f-string names like resilience.{event} are
    intentionally outside the scan; their values are documented in the
    RESILIENCE.md table.)

    The check IS the tpu-lint ``metric-drift`` rule (one shared
    implementation in paddle_tpu.analysis.rules — this test and
    ``python -m paddle_tpu.analysis --check`` cannot fork); here it
    runs with suppressions and the baseline DISABLED, so the metric
    table can never rot behind an allow-pragma or a pin."""
    from paddle_tpu.analysis import lint, rules

    files = lint.package_sources(ROOT)
    names = rules.collect_metric_names(
        {p: sf.source for p, sf in files.items()})
    assert len(names) > 15, f"metric scan found only {sorted(names)}"
    res = lint.run_lint(ROOT, rules=("metric-drift",), files=files,
                        respect_suppressions=False,
                        respect_baseline=False)
    assert res.ok, "undocumented metrics:\n" + "\n".join(
        map(repr, res.findings))


# ---- load_bench smoke (open-loop harness, BENCH percentile fields) ----------

@pytest.mark.slow
def test_load_bench_smoke_emits_slo_percentiles(tmp_path):
    """`not slow` CI smoke: load_bench at tiny CPU scale (with the PR 8
    overload knobs armed: --shed bounded queue + a priority mix) must
    emit one schema-valid record per offered-load point carrying
    p50/p95/p99 TTFT+TPOT, goodput-under-SLO, the step-segment
    breakdown and the shed_rate/preemptions robustness fields, plus the
    final knee record with the full curve — and, with --timeline, a
    Perfetto trace-event export of the last sweep point."""
    tpath = str(tmp_path / "t.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "load_bench.py"),
         "--model", "llama-tiny", "--requests", "5", "--slots", "2",
         "--block_tokens", "16", "--min_prompt", "4", "--max_prompt",
         "12", "--min_new", "2", "--max_new", "6", "--loads", "0.5,2.0",
         "--slo_ttft_s", "30", "--slo_tpot_s", "30",
         "--shed", "--max_queue", "8",
         # chunked engine + bimodal prompt mix: the chunked-prefill
         # A/B surface (chunk_tokens/prefill_chunks record fields)
         "--chunk_tokens", "16", "--prompt_mix", "long",
         "--long_prompt", "40", "--long_frac", "0.4",
         "--priority_mix", "low:1,normal:2,high:1",
         "--timeline", tpath],
        capture_output=True, text=True, timeout=540, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    recs = [json.loads(ln) for ln in out.stdout.strip().splitlines()
            if ln.startswith("{")]
    assert len(recs) == 3           # 2 load points + the knee
    for rec in recs:
        obs.validate_bench(rec)
        assert rec["schema"] == obs.BENCH_SCHEMA
    for rec in recs[:2]:            # the >=2 offered-load points
        assert rec["unit"] == "tokens/s" and rec["value"] > 0
        assert rec["offered_rps"] > 0 and rec["achieved_rps"] > 0
        assert rec["ttft_p50_s"] > 0
        assert rec["ttft_p99_s"] >= rec["ttft_p95_s"] >= rec["ttft_p50_s"]
        assert rec["tpot_p99_s"] >= rec["tpot_p50_s"] > 0
        assert 0.0 <= rec["goodput"] <= 1.0
        assert set(rec["step_breakdown_s"]) == {
            "admit", "prefill", "dispatch", "sync", "commit", "tail"}
        # the robustness fields ride every point (small queue bound +
        # no deadlines here, so typically zero — presence and type are
        # the contract, schema-validated above)
        assert 0.0 <= rec["shed_rate"] <= 1.0
        assert rec["preemptions"] >= 0
        # chunked-prefill fields: the engine ran chunked and the
        # 40-token long prompts took >= 3 chunk programs each
        assert rec["chunk_tokens"] == 16
        assert rec["prefill_chunks"] >= 1
        assert rec["prompt_mix"] == "long"
    assert recs[0]["offered_rps"] < recs[1]["offered_rps"]
    knee = recs[2]
    assert knee["unit"] == "req/s" and len(knee["curve"]) == 2
    assert knee["slo_ttft_s"] == 30.0 and knee["knee_goodput"] == 0.9
    # --timeline rode along: the knee record names a Perfetto-loadable
    # trace-event export of the last sweep point
    assert knee["timeline_path"] == tpath
    assert knee["trace_count"] >= 1
    doc = json.load(open(tpath))
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert doc["otherData"]["trace_count"] == knee["trace_count"]
