"""Unified telemetry: metrics registry, request tracing, schemas,
roofline attribution, memory telemetry, profiler satellites."""

import json
import multiprocessing
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import observability as obs
from paddle_tpu.core.flags import set_flags
from paddle_tpu.inference import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "decode_synthetic.xplane.pb")


def tiny_llama(nkv=4):
    paddle_tpu.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=3,
                      num_heads=4, num_kv_heads=nkv, intermediate_size=256,
                      max_position_embeddings=512)
    return cfg, LlamaForCausalLM(cfg).bfloat16()


# ---- registry ---------------------------------------------------------------

def test_registry_counters_gauges_histograms(tmp_path):
    r = obs.MetricsRegistry()
    c = r.counter("req.total", route="decode")
    c.inc()
    c.inc(4)
    assert r.counter("req.total", route="decode") is c  # get-or-create
    assert c.value == 5
    r.gauge("tok_s").set(99.5)
    h = r.histogram("lat_s", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4 and h.counts == [1, 1, 1, 1]
    assert h.mean() == pytest.approx(5.555 / 4)

    # JSONL export: every line parses, one per metric
    p = str(tmp_path / "m.jsonl")
    n = r.export_jsonl(p, extra={"run": "t"})
    lines = [json.loads(l) for l in open(p)]
    assert n == len(lines) == 3
    assert all(l["run"] == "t" and "ts" in l for l in lines)

    # Prometheus text: histogram buckets are cumulative, +Inf == count
    txt = r.prometheus_text()
    assert 'req_total{route="decode"} 5' in txt
    assert "# TYPE lat_s histogram" in txt
    assert 'lat_s_bucket{le="+Inf"} 4' in txt
    assert 'lat_s_bucket{le="0.1"} 2' in txt
    # label values with quotes/backslashes are escaped per the
    # exposition format
    r.gauge("esc", metric='7" disk\\x').set(1)
    assert r'metric="7\" disk\\x"' in r.prometheus_text()


def test_prometheus_label_newline_escaped():
    """Exposition-format escaping regression: a hostile label value
    carrying a literal newline must be emitted as the two-character
    escape \\n — a raw newline inside a label value tears the line and
    poisons every scrape of the whole registry."""
    r = obs.MetricsRegistry()
    r.counter("req", reason='line1\nline2"x\\y').inc()
    txt = r.prometheus_text()
    lines = txt.splitlines()
    # the value never leaks a raw newline: one metric -> exactly TYPE
    # line + sample line, and the sample parses as a single line
    assert len(lines) == 2
    assert lines[1] == 'req{reason="line1\\nline2\\"x\\\\y"} 1'


def test_registry_view_stamps_labels_shared_storage():
    r = obs.MetricsRegistry()
    v = r.view(replica="0")
    assert v.backing is r and v.labels == {"replica": "0"}
    v.counter("serving.requests", finish="eos").inc(2)
    # storage stays in the backing registry: label-blind accessors and
    # get-or-create through the view both see the same object
    assert r.counter_total("serving.requests") == 2
    assert v.counter("serving.requests", finish="eos") \
        is r.counter("serving.requests", finish="eos", replica="0")
    # a caller's explicit label WINS over the view's stamp
    v.gauge("g", replica="7").set(1.0)
    assert [dict(m.labels) for m in r.series("g")] == [{"replica": "7"}]
    # histograms/sketches ride the same merge path
    v.histogram("h", buckets=(1.0,)).observe(0.5)
    v.sketch("s").observe(0.5)
    assert dict(r.series("h")[0].labels) == {"replica": "0"}
    assert dict(r.series("s", kind="sketch")[0].labels) \
        == {"replica": "0"}


def test_registry_series_accessor_filters_name_and_kind():
    r = obs.MetricsRegistry()
    r.counter("x", a="1").inc()
    r.counter("x", a="2").inc()
    r.gauge("x").set(3)
    r.counter("y").inc()
    assert len(r.series("x")) == 3
    assert len(r.series("x", kind="counter")) == 2
    assert [m.kind for m in r.series("x", kind="gauge")] == ["gauge"]
    assert r.series("nope") == []


def test_merged_across_collapses_label_per_kind():
    """merged_across('replica') unit semantics — the tier-merge rules:
    counters summed, histograms bucket-summed, sketches merged, gauges
    KEEP the label; label-free series pass through unchanged."""
    r = obs.MetricsRegistry()
    for i, n in ((0, 3), (1, 5)):
        r.counter("c", replica=str(i)).inc(n)
        r.gauge("q", replica=str(i)).set(n)
        h = r.histogram("h", buckets=(1.0, 2.0), replica=str(i))
        h.observe(0.5)
        h.observe(1.5)
        sk = r.sketch("s", replica=str(i))
        sk.observe(0.1 * (i + 1))
    r.counter("plain").inc(7)
    m = r.merged_across("replica")
    (c,) = m.series("c", kind="counter")
    assert c.value == 8 and "replica" not in dict(c.labels)
    (h,) = m.series("h", kind="histogram")
    assert h.count == 4 and h.counts == [2, 2, 0]
    (s,) = m.series("s", kind="sketch")
    assert s.count == 2 and s.min == pytest.approx(0.1) \
        and s.max == pytest.approx(0.2)
    gauges = {dict(g.labels)["replica"]: g.value
              for g in m.series("q", kind="gauge")}
    assert gauges == {"0": 3, "1": 5}
    (p,) = m.series("plain", kind="counter")
    assert p.value == 7
    # detached: bumping the merged copy leaves the source untouched
    c.inc(100)
    assert r.counter("c", replica="0").value == 3
    assert r.counter("c", replica="1").value == 5


def test_trace_is_reentrant():
    with obs.trace(registry=obs.MetricsRegistry()) as outer:
        with obs.trace(registry=obs.MetricsRegistry()) as inner:
            assert obs.active_tracer() is inner
        # inner exit restores the ENCLOSING tracer, not None
        assert obs.active_tracer() is outer
        with outer.span("x"):
            pass
    assert obs.active_tracer() is None
    assert [s.name for s in outer.spans] == ["x"]


def test_histogram_bucket_conflict_raises():
    r = obs.MetricsRegistry()
    r.histogram("lat", buckets=(0.1, 1.0)).observe(0.5)
    assert r.histogram("lat", buckets=(0.1, 1.0)).count == 1  # same: ok
    assert r.histogram("lat").count == 1     # unspecified: existing
    with pytest.raises(ValueError, match="buckets"):
        r.histogram("lat", buckets=(1.0, 60.0))


def test_registry_default_labels():
    r = obs.MetricsRegistry()
    r.set_default_labels(rank=3)
    r.counter("x").inc()
    snap = r.snapshot()
    assert snap[0]["labels"] == {"rank": "3"}
    # per-call labels ride on top of defaults
    r.gauge("y", phase="decode").set(1)
    labels = [s["labels"] for s in r.snapshot() if s["name"] == "y"]
    assert labels == [{"rank": "3", "phase": "decode"}]


# ---- profiler satellites ----------------------------------------------------

def test_step_timer_none_before_any_step():
    from paddle_tpu.profiler import StepTimer
    t = StepTimer(model_flops_per_token=1000.0, warmup=0)
    assert t.mean_step_time() is None
    assert t.tokens_per_sec(100) is None       # was ZeroDivisionError
    assert t.mfu(100, peak=1e12) is None
    with t:
        pass
    assert t.tokens_per_sec(100) is not None


def _mp_log_lines(rank, path, n):
    from paddle_tpu.profiler import MetricsLogger
    ml = MetricsLogger(path, mirror_to_registry=False)
    pad = "x" * 512
    for i in range(n):
        ml.log(rank=rank, step=i, pad=pad)


@pytest.mark.slow
def test_metrics_logger_multiprocess_lines(tmp_path):
    """Concurrent per-rank writers on ONE path: every line must parse
    (single O_APPEND write per line — no interleaved partial JSON)."""
    path = str(tmp_path / "m.jsonl")
    ctx = multiprocessing.get_context("spawn")
    procs = [ctx.Process(target=_mp_log_lines, args=(r, path, 25))
             for r in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join()
        assert p.exitcode == 0
    lines = open(path).read().splitlines()
    assert len(lines) == 50
    recs = [json.loads(l) for l in lines]     # raises on a torn line
    assert {r["rank"] for r in recs} == {0, 1}


def test_profiler_scheduler_overshoot_and_atexit(monkeypatch, tmp_path):
    from paddle_tpu import profiler as prof_mod
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    p = prof_mod.Profiler(scheduler=(2, 4), log_dir=str(tmp_path))
    p.step()                      # 1: outside window
    p.step()                      # 2: start
    assert calls == ["start"] and p._active
    p._step = 9                   # simulate a counter jump PAST end
    p.step()                      # 10 >= 4: must stop, not leave open
    assert calls == ["start", "stop"] and not p._active
    # a MANUAL start after the window stays under the caller's control
    p.start()
    p.step()
    assert p._active and calls[-1] == "start"
    p.stop()

    # atexit guard closes a trace left open at process exit
    p2 = prof_mod.Profiler(log_dir=str(tmp_path))
    p2.start()
    assert p2._active
    p2._atexit_stop()
    assert not p2._active and calls[-1] == "stop"


# ---- xplane fixture + roofline ---------------------------------------------

def _fixture_log_dir(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run0"
    d.mkdir(parents=True)
    shutil.copy(FIXTURE, str(d / "host0.xplane.pb"))
    return str(tmp_path)


def test_xplane_fixture_parses(tmp_path):
    from paddle_tpu.profiler import xplane
    log_dir = _fixture_log_dir(tmp_path)
    planes = xplane.load_latest(log_dir)
    assert {p.name for p in planes} == {"/device:TPU:0 (synthetic)",
                                        "/host:CPU (synthetic)"}
    rows = xplane.op_summary(planes, exclude_lines=("XLA Modules",))
    by_name = {r["name"]: r for r in rows}
    assert by_name["fused_decode.kernel.fusion.1"]["total_ms"] == \
        pytest.approx(3.2)
    assert by_name["dot_general.3"]["calls"] == 10
    # module rollups excluded; host plane skipped with device_only
    assert "jit_run(...)" not in by_name
    assert "decode.request" not in by_name


def test_roofline_report_from_fixture(tmp_path):
    from paddle_tpu import profiler
    log_dir = _fixture_log_dir(tmp_path)
    plan = {
        "hbm_gbps": 819.0, "peak_tflops": 197.0, "steps": 10,
        "phases": [
            {"name": "decode_kernel", "match": ["fused_decode"],
             "bytes_per_step": 0.2e9},
            {"name": "glue_matmul", "match": ["dot"],
             "flops_per_step": 1e9},
            {"name": "cache_append", "match": ["dynamic-update"],
             "bytes_per_step": 0.04e9},
        ],
    }
    rep = profiler.roofline_report(log_dir, plan)
    rows = {r["phase"]: r for r in rep["rows"]}
    dk = rows["decode_kernel"]
    assert dk["measured_ms_per_step"] == pytest.approx(0.32)
    assert dk["roofline_ms_per_step"] == pytest.approx(0.2442, rel=1e-3)
    assert dk["frac_of_roofline"] == pytest.approx(0.763, rel=1e-2)
    assert dk["bound"] == "dma"
    assert dk["residual_ms_per_step"] == pytest.approx(0.0758, rel=1e-2)
    gm = rows["glue_matmul"]
    assert gm["bound"] == "matmul"
    assert gm["measured_ms_per_step"] == pytest.approx(0.08)
    ca = rows["cache_append"]
    assert ca["measured_ms_per_step"] == pytest.approx(0.04)
    # argmax + copy land in "other" (0.02 + 0.04 ms/step)
    assert rep["other_ms_per_step"] == pytest.approx(0.06)
    assert "decode_kernel" in rep["table"] and "%roof" in rep["table"]


def test_build_xspace_roundtrip(tmp_path):
    """The synthetic encoder emits bytes this module's parser reads back
    verbatim — guards the checked-in fixture's generator."""
    from paddle_tpu.profiler import xplane
    planes = [("/device:TPU:0 (x)", [
        ("ops", 42, [("alpha", 7, 1000, 3), ("beta", 8, 2000, 1)])])]
    path = xplane.write_xspace(planes, str(tmp_path), run="r", host="h")
    assert path.endswith(".xplane.pb")
    parsed = xplane.parse_xspace(path)
    assert parsed[0].name == "/device:TPU:0 (x)"
    line = parsed[0].lines[0]
    assert line.name == "ops" and line.timestamp_ns == 42
    assert [(e.name, e.offset_ps, e.duration_ps, e.occurrences)
            for e in line.events] == [("alpha", 7, 1000, 3),
                                      ("beta", 8, 2000, 1)]


# ---- traced generate() ------------------------------------------------------

def _traced_vs_plain(model, prompt, reg, **gen_kw):
    model._generate_jit_cache = {}
    out_plain = generate(model, prompt, temperature=0.0, **gen_kw)
    with obs.trace(registry=reg, decode_chunk=4) as t:
        out_traced = generate(model, prompt, temperature=0.0, **gen_kw)
    assert np.asarray(out_plain).tolist() == np.asarray(out_traced).tolist()
    spans = t.span_dicts()
    obs.validate_spans(spans, require_request=True)
    return spans


@pytest.mark.slow
def test_generate_spans_llama_interpret_kernel():
    """Under FLAGS_pallas_interpret the REAL Pallas decode kernel runs
    on CPU and traced generate() emits schema-valid spans with
    TTFT/TPOT/tokens-per-sec — token-exact vs the untraced
    single-dispatch program (bf16 cache), then the int8-cache request
    traced-only (its token parity is pinned by test_fused_decode).
    Slow lane: interpret-kernel parity is pinned by the slow twins in
    test_fused_decode/test_serving; the not-slow spans coverage rides
    the jnp-reference arch tests above."""
    set_flags({"FLAGS_pallas_interpret": True})
    try:
        cfg, m = tiny_llama(nkv=4)      # MHA: dkv=128 → kernel-eligible
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 9)))
        reg = obs.MetricsRegistry()
        spans = _traced_vs_plain(m, prompt, reg, max_new_tokens=10)
        req = next(s for s in spans if s["name"] == "decode.request")
        assert req["attrs"]["arch"] == "llama" and req["attrs"]["fused"]
        assert req["attrs"]["kv_cache_dtype"] == "bfloat16"
        assert req["attrs"]["ttft_s"] > 0
        assert req["attrs"]["tpot_s"] > 0
        assert req["attrs"]["tokens_per_sec"] > 0
        # chunked: ceil(9/4) decode chunks, all parented to the request
        chunks = [s for s in spans if s["name"] == "decode.chunk"]
        assert len(chunks) == 3
        assert all(s["parent"] == "decode.request" for s in chunks)
        assert reg.histogram("decode.ttft_seconds").count == 1
        assert reg.counter("decode.tokens").value == 2 * 10

        # int8 KV cache through the same interpret-mode kernel
        with obs.trace(registry=obs.MetricsRegistry(),
                       decode_chunk=4) as t8:
            generate(m, prompt, max_new_tokens=10, temperature=0.0,
                     cache_dtype=jnp.int8)
        spans8 = t8.span_dicts()
        obs.validate_spans(spans8, require_request=True)
        req8 = next(s for s in spans8 if s["name"] == "decode.request")
        assert req8["attrs"]["kv_cache_dtype"] == "int8"
        # int8 cache holds half the bytes of the bf16 layout
        assert req8["attrs"]["kv_cache_bytes"] \
            == req["attrs"]["kv_cache_bytes"] // 2
    finally:
        set_flags({"FLAGS_pallas_interpret": False})


def test_generate_spans_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
    paddle_tpu.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    g = GPTPretrainModel(cfg)
    g.eval()
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 7)))
    # traced-only (gpt traced-vs-untraced parity rides the same machinery
    # the llama test pins; skipping the untraced twin saves a compile)
    with obs.trace(registry=obs.MetricsRegistry(), decode_chunk=4) as t:
        out = generate(g, prompt, max_new_tokens=8, temperature=0.0)
    assert out.shape == (2, 15)
    spans = t.span_dicts()
    obs.validate_spans(spans, require_request=True)
    req = next(s for s in spans if s["name"] == "decode.request")
    assert req["attrs"]["arch"] == "gpt"


@pytest.mark.slow
def test_generate_spans_moe_bf16_and_int8():
    # slow lane: moe traced/untraced token parity is sibling-covered by
    # test_fused_decode's moe cases; span-schema coverage stays not-slow
    # via the llama/gpt arch tests above
    from paddle_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    paddle_tpu.seed(0)
    cfg = MixtralConfig(vocab_size=256, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_position_embeddings=256,
                        num_experts=8, top_k=2)
    m = MixtralForCausalLM(cfg)
    m.eval()
    prompt = jnp.asarray(np.random.RandomState(2).randint(0, 256, (2, 5)))
    reg = obs.MetricsRegistry()
    spans = _traced_vs_plain(m, prompt, reg, max_new_tokens=8)
    req = next(s for s in spans if s["name"] == "decode.request")
    assert req["attrs"]["arch"] == "moe"
    assert req["attrs"]["kv_cache_dtype"] == "bfloat16"
    # int8 cache: spans only (token parity int8-vs-bf16 is pinned by
    # test_fused_decode; skipping the untraced twin saves a compile —
    # tier-1 budget)
    with obs.trace(registry=obs.MetricsRegistry(), decode_chunk=4) as t:
        generate(m, prompt, max_new_tokens=8, temperature=0.0,
                 cache_dtype=jnp.int8)
    spans8 = t.span_dicts()
    obs.validate_spans(spans8, require_request=True)
    req8 = next(s for s in spans8 if s["name"] == "decode.request")
    assert req8["attrs"]["kv_cache_dtype"] == "int8"
    assert req8["attrs"]["kv_cache_bytes"] \
        == req["attrs"]["kv_cache_bytes"] // 2


@pytest.mark.slow
def test_generate_spans_layered_fallback():
    """The non-fused (layered scan) path traces too (traced-only: the
    split-scan machinery's token parity is pinned by the llama test).
    Slow lane: the layered path itself is sibling-covered by the
    resilience OOM-ladder tests."""
    set_flags({"FLAGS_fused_decode": False})
    try:
        cfg, m = tiny_llama()
        m._generate_jit_cache = {}
        prompt = jnp.asarray([[1, 2, 3]])
        with obs.trace(registry=obs.MetricsRegistry(),
                       decode_chunk=4) as t:
            out = generate(m, prompt, max_new_tokens=6, temperature=0.0)
        assert out.shape == (1, 9)
        spans = t.span_dicts()
        obs.validate_spans(spans, require_request=True)
        req = next(s for s in spans if s["name"] == "decode.request")
        assert req["attrs"]["fused"] is False
    finally:
        set_flags({"FLAGS_fused_decode": True})


@pytest.mark.slow
def test_stacked_generate_traced_spans():
    # slow lane: stacked token parity is sibling-covered by the stacked
    # decoder tests; span-schema coverage stays not-slow via the arch
    # tests above
    from paddle_tpu.inference.stacked import StackedLlamaDecoder
    cfg, m = tiny_llama(nkv=2)
    dec = StackedLlamaDecoder.from_state_dict(
        cfg, m.state_dict(include_buffers=False))
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 512, (2, 9)))
    out_plain = dec.generate(prompt, max_new_tokens=10, temperature=0.0)
    reg = obs.MetricsRegistry()
    with obs.trace(registry=reg, decode_chunk=4) as t:
        out_traced = dec.generate(prompt, max_new_tokens=10,
                                  temperature=0.0)
    assert np.asarray(out_plain).tolist() == np.asarray(out_traced).tolist()
    spans = t.span_dicts()
    obs.validate_spans(spans, require_request=True)
    req = next(s for s in spans if s["name"] == "decode.request")
    assert req["attrs"]["arch"] == "llama-stacked"
    assert reg.counter("decode.tokens").value == 2 * 10


def test_untraced_generate_stays_single_dispatch():
    """No tracer attached → the decode stays ONE jitted program (the <1%
    overhead contract: the only telemetry cost is the active_tracer()
    read) and no traced twin is compiled."""
    cfg, m = tiny_llama()
    prompt = jnp.asarray([[1, 2, 3, 4]])
    generate(m, prompt, max_new_tokens=5, temperature=0.0)
    keys = list(m._generate_jit_cache)
    assert len(keys) == 1 and "traced" not in keys[0]
    assert obs.active_tracer() is None


# ---- schemas ----------------------------------------------------------------

def test_bench_schema_validates_and_mirrors():
    rec = obs.bench_record("x tok/s", 12.5, "tokens/s", device="cpu",
                           timing="wall", batch=2)
    assert rec["schema"] == obs.BENCH_SCHEMA
    assert obs.validate_bench(rec) is rec
    g = obs.registry().gauge("bench.value", metric="x tok/s",
                             unit="tokens/s")
    assert g.value == 12.5


def test_bench_schema_rejects_and_lists_all_problems():
    with pytest.raises(ValueError) as ei:
        obs.validate_bench({"metric": 7, "value": "fast",
                            "unit": "tokens/s", "device": "cpu",
                            "schema": obs.BENCH_SCHEMA})
    msg = str(ei.value)
    assert "metric" in msg and "value" in msg        # both reported
    with pytest.raises(ValueError, match="schema"):
        obs.validate_bench({"schema": "bogus/v9", "metric": "m",
                            "value": 1, "unit": "u", "device": "d"})
    with pytest.raises(ValueError, match="roofline_plan"):
        obs.validate_bench({"schema": obs.BENCH_SCHEMA, "metric": "m",
                            "value": 1, "unit": "u", "device": "d",
                            "roofline_plan": {"phases": []}})


def test_roofline_plan_validation():
    good = {"hbm_gbps": 819.0, "steps": 4,
            "phases": [{"name": "a", "match": ["x"],
                        "bytes_per_step": 1.0}]}
    assert obs.validate_roofline_plan(good) is good
    with pytest.raises(ValueError, match="hbm_gbps"):
        obs.validate_roofline_plan({"phases": [{"name": "a",
                                                "match": ["x"]}]})
    with pytest.raises(ValueError, match="match"):
        obs.validate_roofline_plan(
            {"hbm_gbps": 1.0, "phases": [{"name": "a", "match": "x"}]})


# ---- memory telemetry -------------------------------------------------------

def test_memory_telemetry_gauges():
    x = jnp.ones((256, 256), jnp.float32)  # keep a live buffer around
    reg = obs.MetricsRegistry()
    snap = obs.memory.record_memory(registry=reg)
    assert snap["live_array_bytes"] >= x.nbytes
    assert reg.gauge("memory.live_array_bytes").value == \
        snap["live_array_bytes"]


def test_executable_memory_analysis():
    reg = obs.MetricsRegistry()
    fn = jax.jit(lambda a, b: a @ b + 1.0)
    arg = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = fn.lower(arg, arg).compile()
    out = obs.memory.record_executable_memory(compiled, registry=reg,
                                              name="mm")
    if out is not None:           # backend exposes memory_analysis
        assert out["argument_bytes"] > 0
        assert reg.gauge("executable.argument_bytes",
                         name="mm").value == out["argument_bytes"]


# ---- fleet per-rank tagging -------------------------------------------------

def test_fleet_init_tags_rank(monkeypatch):
    from paddle_tpu.parallel import fleet
    from paddle_tpu.parallel.topology import set_hybrid_communicate_group
    monkeypatch.setenv("PADDLE_TRAINER_ID", "7")
    try:
        fleet.init(is_collective=True)
        assert obs.registry().default_labels.get("rank") == "7"
        c = obs.registry().counter("tagged.test")
        assert dict(c.labels).get("rank") == "7"
    finally:
        set_hybrid_communicate_group(None)
        obs.registry().reset()


# ---- decode_bench smoke (unified BENCH schema end-to-end) -------------------

@pytest.mark.slow
def test_decode_bench_smoke_emits_valid_schema(tmp_path):
    """decode_bench in tiny-CPU mode must emit a schema-valid BENCH
    record with an embedded roofline plan, and the plan must drive
    scale_report's roofline join. Slow lane: the shared BENCH-schema
    emit path keeps a `not slow` smoke via serving_bench below."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "decode_bench.py"),
         "--traced", "--reps", "1",
         "--report_plan", str(tmp_path / "plan.json")],
        capture_output=True, text=True, timeout=540, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    obs.validate_bench(rec)
    assert rec["schema"] == obs.BENCH_SCHEMA
    assert rec["unit"] == "tokens/s" and rec["value"] > 0
    obs.validate_roofline_plan(rec["roofline_plan"])
    obs.validate_roofline_plan(json.load(open(tmp_path / "plan.json")))
    # --traced rode along: the request span's metrics are in the record
    rs = rec["request_span"]
    assert rs["ttft_s"] > 0 and rs["tokens_per_sec"] > 0
    assert rs["kv_cache_dtype"] == "bfloat16"
    assert rec["memory"]["live_array_bytes"] > 0


# ---- serving_bench smoke (continuous-batching A/B, BENCH schema) ------------

@pytest.mark.slow
def test_serving_bench_smoke_emits_valid_schema(tmp_path):
    """`not slow` CI smoke: serving_bench in tiny-CPU mode must emit TWO
    schema-valid BENCH records — static first, then continuous carrying
    the A/B fields (speedup, occupancy, pad-waste, prefix-hit). The
    engine side runs CHUNKED (--chunk_tokens 16) so the not-slow lane
    exercises the chunked-prefill scheduler end to end; the >=1.5x
    speedup itself is a full-size claim (the default b=8 mixed-length
    run documented in docs/SERVING.md), not asserted at this toy scale
    where per-step dispatch overhead dominates. The engine side also
    runs SPECULATIVE (--speculate 2) so the not-slow lane exercises
    the verify-dispatch scheduler and the spec schema fields end to
    end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "serving_bench.py"),
         "--model", "llama-tiny", "--block_tokens", "16",
         "--requests", "6", "--slots", "2", "--min_prompt", "4",
         "--max_prompt", "12", "--min_new", "2", "--max_new", "8",
         "--sys_prompt_len", "16", "--reps", "1",
         "--chunk_tokens", "16", "--speculate", "2",
         "--timeline", str(tmp_path / "t.json")],
        capture_output=True, text=True, timeout=540, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2
    static, cont = lines
    for rec in lines:
        obs.validate_bench(rec)
        assert rec["schema"] == obs.BENCH_SCHEMA
        assert rec["unit"] == "tokens/s" and rec["value"] > 0
        assert 0.0 <= rec["occupancy"] <= 1.0
    assert static["mode"] == "static" and cont["mode"] == "continuous"
    assert static["pad_waste_frac"] == pytest.approx(
        1 - static["occupancy"], abs=1e-3)
    assert cont["speedup_vs_static"] > 0
    # the shared 16-token system prefix is one full 16-token block:
    # every request after the first reuses it
    assert cont["prefix_hit_rate"] > 0.5
    assert cont["prefill_tokens_reused"] > 0
    assert cont["ttft_p50_s"] > 0
    # chunked engine side: every prefill ran through chunk programs
    assert cont["chunk_tokens"] == 16
    assert cont["prefill_chunks"] >= 1
    # speculative engine side: the typed-optional spec fields are
    # present and valid (acceptance on this random toy mix is usually
    # 0 — the value is not the claim, the schema is)
    assert cont["speculate_k"] == 2
    assert cont["proposer"] == "ngram"
    assert 0.0 <= cont["acceptance_rate"] <= 1.0
    assert isinstance(cont["accepted_len_hist"], dict)
    assert sum(cont["accepted_len_hist"].values()) >= 1
    # --timeline rode along: the continuous record names a Perfetto
    # trace-event export covering the engine run's flight ring
    assert cont["timeline_path"] == str(tmp_path / "t.json")
    assert cont["trace_count"] >= 1
    doc = json.load(open(cont["timeline_path"]))
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert doc["otherData"]["trace_count"] == cont["trace_count"]
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "i"} <= phases        # tracks, segments, instants
