"""What PR 27 brought for arch ``xing4``: the byte and flop functions
against sums done by hand, the readers of ``layer_metrics/xing4.py`` on
hand-made observations and on a trace made here, and the new cell's
rehearsal."""

import glob
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from harness import opcount, opcount_xing4, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4.0-29b-a4b.chat-4k"


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_xing4", os.path.join(BENCH, "layer_metrics", "xing4.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


readers = _load()


def config():
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_dims_and_weight_bytes_by_hand():
    d = opcount.dims(config())
    assert (d["layers"], d["dense_layers"], d["moe_layers"]) == (7, 1, 6)
    assert d["cache_lanes"] == 576 and d["experts"] == 64 and d["top_k"] == 4
    # 7 layers x 576 values x 2 bytes: 8064 B a token
    assert opcount_xing4.latent_bytes_per_token(d) == 8064
    # ISSUE 27's arithmetic: MLA 28.4 M, two mixers 0.69 M, a dense FFN
    # 99.1 M, an expert 11.01 M, a router 0.23 M, the head 469.8 M
    mla = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
           + 32 * 128 * 3584)
    assert mla == 28_409_856
    mixers = 2 * 4 * 3584 * 24
    expert = 3 * 3584 * 1024
    assert expert == 11_010_048
    want = 2 * (7 * (mla + mixers) + 3 * 3584 * 9216
                + 6 * (expert * (1 + 64) + 3584 * 64) + 131072 * 3584)
    assert opcount_xing4.step_weight_bytes(d, touched=64) == want
    # all 64 experts a layer: 10.1 GB a step; embedding rows are looked up
    assert want == pytest.approx(10.14e9, rel=0.01)
    assert (opcount_xing4.step_weight_bytes(d, 64)
            - opcount_xing4.step_weight_bytes(d, 55)) == 9 * 6 * expert * 2


def test_kernel_calls_by_hand():
    d = opcount.dims(config())
    # 40 rows that read 60,000 cached rows between them, one layer
    got = opcount_xing4.mla_decode_call(d, rows=40, attended_tokens=60_000)
    seen = 60_040
    assert got["bytes"] == (seen * 576 * 2
                            + 40 * 32 * (576 * 2 + 512 * 4))
    assert got["flops"] == 2 * 32 * (576 + 512) * seen
    # memory-bound by a wide margin at 819 GB/s against 197 TFLOP/s
    assert got["bytes"] / 819e9 > got["flops"] / 197e12
    # 40 rows, 58 experts touched, one expert layer
    moe = opcount_xing4.moe_ffn_call(d, rows=40, touched=58)
    assert moe["bytes"] == (58 * 3 * 3584 * 1024 * 2
                            + 40 * (2 * 3584 * 2 + 64 * 4))
    assert moe["flops"] == 40 * 4 * 3 * 2 * 3584 * 1024
    assert moe["bytes"] == pytest.approx(1.277e9, rel=0.01)


def test_touched_share_reader():
    cfg = config()
    stats = dict(moe_layer_steps=600, moe_experts_touched=33_000,
                 moe_rows=600 * 160, moe_rows_max=600 * 7)
    got = readers.moe_touched_share(dict(stats=stats, config=cfg))
    assert got["value"] == pytest.approx(100 * 33_000 / (64 * 600))
    assert got["rows_max_over_mean"] == pytest.approx(7 / (160 / 64))
    # the parent, or a llama engine: no counters, nothing to read
    assert readers.moe_touched_share(dict(stats=dict(steps=5),
                                          config=cfg)) is None
    assert readers.moe_touched_share(dict(
        stats=dict(moe_layer_steps=0, moe_experts_touched=0, moe_rows=0,
                   moe_rows_max=0), config=cfg)) is None


def test_readers_find_nothing_without_a_trace_or_the_kernels():
    obs = dict(stats=dict(steps=5), config=config(), trace_steps=(0, 5),
               all_requests=[])
    for reader in (readers.mla_decode_roofline, readers.moe_ffn_roofline,
                   readers.outside_kernels_share):
        assert reader(obs) is None
    # a trace of another program: ops, but neither kernel
    red = trace_reduce.Reduced(window_s=1.0, busy_s=0.5, gaps=[], n_devices=1,
                               by_name={"fused_paged_decode_step": (0.4, 50)})
    obs.update(trace=red, trace_path=None)
    for reader in (readers.mla_decode_roofline, readers.moe_ffn_roofline,
                   readers.outside_kernels_share):
        assert reader(obs) is None


def test_rooflines_from_a_hand_made_trace_and_commit_spans(tmp_path):
    """The kernels' seconds from a reduced trace, the touched experts
    from ``serving.step.commit`` spans in a trace written here."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(10):     # ten landed programs, 6 expert layers each
        ann = jax.profiler.TraceAnnotation("serving.step.commit")
        with ann:
            jnp.ones(4).block_until_ready()
            ann.set_metadata(retired=0, moe_layer_steps=6,
                             moe_experts_touched=6 * 50, moe_rows_max=6 * 7,
                             moe_rows=6 * 4 * 30)
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    assert readers._commit_counters(path) == dict(
        moe_layer_steps=60, moe_experts_touched=3000, moe_rows_max=420,
        moe_rows=7200)
    cfg = config()
    d = opcount.dims(cfg)
    # 60 MoE calls of 2 ms, 70 MLA calls of 0.3 ms in a busy 0.2 s
    red = trace_reduce.Reduced(
        window_s=0.25, busy_s=0.2, gaps=[], n_devices=1,
        by_name={"moe_grouped_ffn_decode": (0.120, 60),
                 "mla_paged_decode": (0.021, 70),
                 "fusion.1 bf16[64,131072]": (0.059, 10)})
    result = types.SimpleNamespace(tokens=[0] * 12)
    req = dict(prompt_len=1000, finish_step=10, result=result)
    obs = dict(stats={}, config=cfg, trace=red, trace_path=path,
               trace_steps=(0, 10), all_requests=[req] * 30,
               peaks=dict(flops_bf16=197e12, hbm_bytes_per_s=819e9))
    moe = readers.moe_ffn_roofline(obs)
    per = opcount_xing4.moe_ffn_call(d, rows=30, touched=50)
    assert moe["bound"] == "hbm" and moe["calls"] == 60
    assert moe["mean_touched"] == 50 and moe["mean_rows"] == 30
    assert moe["value"] == pytest.approx(
        100 * per["bytes"] / 819e9 / 0.002)
    assert 0 < moe["value"] < 100
    mla = readers.mla_decode_roofline(obs)
    assert mla["calls"] == 70 and mla["mean_rows"] == 30
    assert 0 < mla["value"] < 100
    out = readers.outside_kernels_share(obs)
    assert out["value"] == pytest.approx(100 * (1 - 0.141 / 0.2))


def _plane(name, lines):
    """A text-proto plane: lines {line name: [(start_ns, end_ns, event
    name), ...]}."""
    ids, body = {}, ""
    for line, events in lines.items():
        rows = ""
        for s, e, n in events:
            ids.setdefault(n, len(ids) + 1)
            rows += (f"    events {{ metadata_id: {ids[n]} offset_ps: "
                     f"{s * 1000} duration_ps: {(e - s) * 1000} }}\n")
        body += (f'  lines {{ name: "{line}" timestamp_ns: 0\n{rows}  }}\n')
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}\n' for n, i in ids.items())
    return f'planes {{ name: "{name}"\n{body}{meta}}}\n'


def test_outside_kernels_share_is_the_step_programs(tmp_path):
    """Two runs of the step program (each: 10 us of XLA, one MLA call of
    20 us, one MoE call of 60 us, 10 us of XLA) around one run of a
    prefill program of 300 us: the share is the step program's own 20 %,
    whatever the prefill costs."""
    from jax.profiler import ProfileData
    k_mla = "%mla_paged_decode.3 = bf16[64,32,512] custom-call(bf16[1] %a)"
    k_moe = "%moe_grouped_ffn_decode.5 = bf16[64,3584] custom-call(bf16[1] %a)"
    xla = "%fusion.7 = f32[64,3584] fusion(f32[64,3584] %b)"
    rag = "%ragged-dot.1 = bf16[1024,1024] ragged-dot(bf16[1024,3584] %c)"

    def step(t):
        return [(t, t + 10, xla), (t + 10, t + 30, k_mla),
                (t + 30, t + 90, k_moe), (t + 90, t + 100, xla)]

    text = _plane("/device:TPU:0", {
        "XLA Modules": [(1000, 1100, "jit_impl(1)"), (1200, 1500, "jit_impl(2)"),
                        (1600, 1700, "jit_impl(1)")],
        "XLA Ops": step(1000) + [(1200, 1500, rag)] + step(1600)}) + _plane(
        "/host:CPU", {"bench": [(900, 1800, "bench.window")]})
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    got = readers._program_seconds(str(path))
    assert got["step_runs"] == 2
    assert got["step_s"] == pytest.approx(200e-9 * 1e3 * 1e-3)
    assert got["kernels_s"] == pytest.approx(160e-9)
    assert got["other_s"] == pytest.approx(300e-9)
    red = trace_reduce.reduce(trace_reduce.load(str(path)))
    cfg = config()
    result = types.SimpleNamespace(tokens=[0] * 12)
    obs = dict(stats={}, config=cfg, trace=red, trace_path=str(path),
               trace_steps=(0, 2), peaks=dict(flops_bf16=197e12,
                                              hbm_bytes_per_s=819e9),
               all_requests=[dict(prompt_len=1000, finish_step=10,
                                  result=result)] * 30)
    out = readers.outside_kernels_share(obs)
    assert out["scope"] == "step program"
    assert out["value"] == pytest.approx(20.0)
    assert out["every_program_share"] == pytest.approx(100 * (1 - 160 / 500))
    assert out["other_programs_s"] == pytest.approx(300e-9)
    assert out["step_ms"] == pytest.approx(1e-4)
    # no commit span in this trace: the experts touched are not known,
    # so the step's share of the HBM peak is left out
    assert "step_hbm_share" not in out


def test_agreement_counts_the_most_decided_rows():
    from harness import reference_xing4 as ref
    rule = dict(decided_share=0.25, margin=0.1, max_share=0.1, min_rows=3)
    n = 200
    decided = np.linspace(0.05, 0.0, n)         # row 0 the furthest from a tie
    margins = np.zeros(n)
    margins[60:] = 3.0                  # flipped rows, all near a tie: excused
    margins[:5] = 0.5                   # five of the 50 most decided: allowed
    got = ref.agreement(margins, decided, rule)
    assert (got["decided_rows"], got["over"], got["allowed"]) == (50, 5, 5)
    assert got["holds"] and got["worst_margin"] == 3.0
    assert got["worst_margin_decided"] == 0.5
    assert got["ladder"]["0.25"] == [50, 5, 5, 5]
    assert got["ladder"]["1.0"][:3] == [200, 145, 145]
    margins[5] = 0.11                   # a sixth: more than a tenth of 50
    assert not ref.agreement(margins, decided, rule)["holds"]
    # a short request: three rows are allowed whatever the share
    short = ref.agreement(np.array([1.0, 1.0, 1.0, 0, 0, 0, 0, 0] * 2),
                          np.arange(16, 0, -1.0), dict(rule, decided_share=1))
    assert short["allowed"] == 3 and not short["holds"]


def test_logits_at_refuses_a_request_that_breaks_the_agreement():
    """The tiny model in float32: the reference's own argmax tokens hold
    the rule; another token at every row breaks it and the logits come
    back non-finite, which ``serve.check_outputs`` reports as not
    correct."""
    import jax.numpy as jnp
    from harness import model, reference_xing4 as ref
    cfg = model.effective_config(config(), rehearse=True)
    mdl = model.build_model(cfg)
    state = model.make_state(mdl.state_dict(include_buffers=False), 5,
                             cfg["init_std"], jnp.float32)
    rng = np.random.default_rng(0)
    p, n, n_out, s = 12, 6, 8, 32
    ids = np.zeros((1, s), np.int32)
    ids[0, :p] = rng.integers(3, cfg["vocab_size"], p)
    pos = np.zeros(n_out, np.int32)
    pos[:n] = np.arange(p - 1, p + n - 1)
    plain = dict(cfg)
    rule = plain.pop("reference_agreement")
    for i in range(n):                  # greedy tokens of the reference itself
        lg = ref.logits_at(state, jnp.asarray(ids), jnp.asarray(pos), plain)
        ids[0, p + i] = int(np.asarray(lg)[i].argmax())
    strict = dict(cfg, reference_agreement=dict(rule, decided_share=1.0,
                                                min_rows=0))
    good = np.asarray(ref.logits_at(state, jnp.asarray(ids),
                                    jnp.asarray(pos), strict))
    assert np.isfinite(good).all()
    np.testing.assert_array_equal(good[:n].argmax(-1), ids[0, p:p + n])
    ids[0, p + 1:p + n] = (ids[0, p + 1:p + n] + 1) % cfg["vocab_size"]
    bad = np.asarray(ref.logits_at(state, jnp.asarray(ids),
                                   jnp.asarray(pos), strict))
    assert not np.isfinite(bad).any()


@pytest.mark.slow
def test_rehearsal_of_the_new_cell_is_correct():
    """Four minutes on the CPU: 14 prefill programs of a tiny model."""
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2700000123", "--seconds", "3", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert "engine.moe_touched_share" in line["metrics"]
