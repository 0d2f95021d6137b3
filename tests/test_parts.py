"""The one vocabulary of model parts (``paddle_tpu.profiler.parts``) in
every program a cell runs: each matmul, convolution and kernel of a tiny
engine's (and a tiny train step's) compiled program lies in exactly one
part, and the programs carry their names."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import optimizer, serving
from paddle_tpu.profiler.parts import PARTS, PREFIX, part, part_of

HEAVY = re.compile(r" (dot|convolution|custom-call)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# XLA's own custom calls (a CPU top-k, a sort's comparator) are no kernels
KERNEL_TARGETS = ("tpu_custom_call", "__gpu$", "mosaic")


def tiny(plan):
    paddle_tpu.seed(0)
    if plan in ("llama", "gpt"):
        if plan == "llama":
            from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
            cfg, cls = LlamaConfig.tiny(), LlamaForCausalLM
        else:
            from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
            cfg, cls = GPTConfig.tiny(), GPTPretrainModel
        opts = {}
    elif plan == "xing4":
        from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
        cfg = Xing4Config.tiny(num_nextn_predict_layers=0,
                               hc_sinkhorn_iters=2)
        cls, opts = Xing4ForCausalLM, {}
    elif plan == "deepseek_v2":
        from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                   DeepseekV2ForCausalLM)
        cfg = DeepseekV2Config.tiny(experts_held=8, expert_offset=8)
        cls, opts = DeepseekV2ForCausalLM, {}
    else:
        from paddle_tpu.models.minicpm_sala import (MiniCPMSALAConfig,
                                                    MiniCPMSALAForCausalLM)
        cfg, cls = MiniCPMSALAConfig.tiny(), MiniCPMSALAForCausalLM
        opts = {"prefix_caching": False}
    m = cls(cfg)
    m.eval()
    return cfg, m, opts


def hlo_text(lowered):
    """The program as JAX hands it to XLA, with each op's framework name:
    what the parts decide. (XLA's CPU passes rewrite a batched ``dot``
    into new instructions that carry no metadata, so the compiled text
    of this sandbox is no witness; on the chip the trace is.)"""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string(opts)


HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
CALLEE = re.compile(r" call\(.*to_apply=%([\w.\-]+)")


def heavy_ops(text):
    """(opcode, full op_name) of every dot, convolution and kernel
    custom-call of a program's text, once a call site. JAX lowers a scan
    body, a ``fori_loop`` body and an inner ``jax.jit`` as functions of
    their own whose ops are named from the function's start; XLA's
    inliner then puts the call's own name in front
    (``jit(f)/part.ffn/while/body/closed_call`` + ``/dot_general``),
    which is the name a device trace shows. This does the same."""
    ops, sites, comp = {}, {}, None
    for line in text.splitlines():
        head = HEADER.match(line)
        if head:
            comp = head.group(1)
            ops[comp] = []
            continue
        m, name = HEAVY.search(line), OP_NAME.search(line)
        name = name.group(1) if name else ""
        callee = CALLEE.search(line)
        if callee:
            sites.setdefault(callee.group(1), []).append((comp, name))
        elif m and (m.group(1) != "custom-call"
                    or any(t in line for t in KERNEL_TARGETS)):
            ops[comp].append((m.group(1), name))

    def prefixes(c):
        if c not in sites:          # the entry, a loop's or a cond's region
            return [""]
        return [f"{p}{call}/" for caller, call in sites[c]
                for p in prefixes(caller)]

    return [(opcode, p + name) for c, found in ops.items()
            for opcode, name in found for p in prefixes(c)]


def assert_one_part_each(text, what):
    ops = heavy_ops(text)
    assert ops, f"{what}: no dot, convolution or kernel in the program"
    for opcode, name in ops:
        found = [p for p in re.findall(re.escape(PREFIX) + r"([a-z_]+)",
                                       name) if p in PARTS]
        assert len(set(found)) == 1, (what, opcode, name)


@pytest.mark.parametrize(
    "plan", ["llama", "gpt", "xing4", "deepseek_v2", "minicpm_sala"])
def test_every_matmul_of_an_engines_programs_lies_in_one_part(plan):
    cfg, m, opts = tiny(plan)
    rng = np.random.default_rng(0)
    with serving.ServingEngine(m, max_slots=2, block_tokens=8,
                               max_seq_len=128, **opts) as eng:
        n = 70 if plan == "minicpm_sala" else 9    # past dense_len: selects
        eng.submit(serving.Request(
            rng.integers(3, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=3))
        eng.drain()
        programs = eng.lowered_programs()
        assert {k[0] for k in programs} == {"step", "prefill"}
        for key, low in programs.items():
            text = hlo_text(low)
            assert text.startswith(f"HloModule jit_serving_{key[0]},"), \
                text[:80]
            assert_one_part_each(text, (plan, key))


def test_every_matmul_of_a_train_step_lies_in_one_part():
    from paddle_tpu.parallel import fleet
    from paddle_tpu.parallel.strategy import DistributedStrategy
    cfg, m, _ = tiny("gpt")
    m.train()
    strategy = DistributedStrategy()
    fleet.init(is_collective=True, strategy=strategy,
               devices=jax.devices()[:1])
    opt = optimizer.AdamW(learning_rate=1e-3,
                          grad_clip=optimizer.ClipGradByGlobalNorm(1.0))
    step_fn, _ = fleet.make_train_step(
        m, opt, lambda logits, b: m.loss(logits, b["labels"]),
        strategy=strategy)
    text = hlo_text(step_fn.lower(2, 16))
    assert text.startswith("HloModule jit_train_step,"), text[:80]
    assert_one_part_each(text, "train step")
    names = [n for _, n in heavy_ops(text)]
    # the backward pass holds the part of the forward op it transposes
    assert any("transpose(" in n and part_of(n) == "ffn" for n in names)
    assert {part_of(n) for n in names} >= {"attn_in", "attn", "attn_out",
                                           "ffn", "head"}
    assert "part.loss" in text and "part.optimizer" in text


def test_a_name_outside_the_vocabulary_is_refused():
    assert len(PARTS) <= 16 and len(set(PARTS)) == len(PARTS)
    with pytest.raises(ValueError, match="not a part"):
        with part("x.y"):
            pass
    with pytest.raises(ValueError, match="do not nest"):
        with part("attn"):
            with part("ffn"):
                pass
    with part("ffn"):       # the refusal above left nothing open
        y = jnp.ones(2) * 2
    assert float(y[0]) == 2.0
    assert part_of("jit(f)/while/body/part.attn_in/dot_general") == "attn_in"
    assert part_of("jit(f)/transpose(jvp(part.ffn))/mul") == "ffn"
    assert part_of("jit(f)/part.nothing/part.head/mul") == "head"
    assert part_of("jit(f)/mul") is None and part_of("") is None


# ---- the reader: self seconds by program and part ---------------------------

@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    """``benchmark/tests/parts_fixture.py``'s small trace (its docstring
    has the times): metadata stats, three programs, a ``while`` holding
    two children, an op without a part."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "parts_fixture", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "tests", "parts_fixture.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    d = tmp_path_factory.mktemp("parts") / "plugins" / "profile" / "run0"
    d.mkdir(parents=True)
    return mod.write(str(d / "host0.xplane.pb"))


def test_an_ops_metadata_stats_are_reachable(trace):
    from paddle_tpu.profiler import xplane
    device, host = xplane.parse_xspace(trace)
    ops = {e.name: e for line in device.lines if line.name == "XLA Ops"
           for e in line.events}
    assert ops["fusion.1"].meta.stats["tf_op"].endswith(
        "part.attn_in/dot_general:")
    assert ops["fusion.1"].meta.name.startswith("%fusion.1 = bf16[4,8]")
    assert "tf_op" not in ops["while.5"].meta.stats
    assert ops["while.5"].meta.stats["shape_with_layout"].startswith("(s32[]")
    assert [e.name for e in host.lines[0].events] == ["bench.window"]
    # only the device plane's two lines, where that is all that is asked
    only, = xplane.parse_xspace(trace, xplane.is_device_plane, {"XLA Ops"})
    assert [len(line.events) for line in only.lines] == [0, 18]


def test_self_seconds_by_program_and_part(trace):
    from paddle_tpu.profiler import xplane
    table = xplane.parts_report(trace)
    ns = lambda x: pytest.approx(x * 1e-9, rel=1e-9)
    pre = table["jit_serving_prefill"]
    # the while's 7000 ns hold 5000 of its children's: 2000 are its own
    assert pre["unscoped"] == {"while.5 (s32[], bf16[4,8])": ns(2000),
                               "copy.3 bf16[4,8]": ns(500)}
    assert pre["parts"] == {"attn_in": [ns(2000), 0], "attn": [ns(3000), 0],
                            "head": [ns(1000), 0]}
    assert pre["device_s"] == ns(8500) and pre["span_s"] == ns(10000)
    assert pre["kernel_s"] == ns(3000) and pre["kernels"] == {
        "attn": ns(3000)}
    step = table["jit_serving_step"]
    assert (step["runs"], step["cut"]) == (3, 0)
    assert step["parts"] == {"ffn": [ns(4600), ns(500)],
                             "layers": [ns(6000), 0]}
    train = table["jit_train_step"]
    assert train["parts"]["ffn"] == [ns(2000), ns(3000)]
    assert train["parts"]["attn"] == [ns(1000), ns(1000)]
    assert train["kernel_s"] == ns(2000) and train["device_s"] == ns(8000)
    assert table["(no program)"]["unscoped"] == {"copy.3 bf16[4,8]": ns(400)}
    # a window: the run that starts before it is cut, with its op
    cut = xplane.parts_report(trace, 500, 45000)
    assert (cut["jit_serving_step"]["runs"],
            cut["jit_serving_step"]["cut"]) == (2, 1)
    assert cut["jit_serving_step"]["parts"]["ffn"] == [ns(4000), ns(500)]
    assert "jit_train_step" not in cut
    for row in table.values():
        assert row["device_s"] == pytest.approx(
            sum(map(sum, row["parts"].values()))
            + sum(row["unscoped"].values()))


def test_the_summary_prints_the_table_by_part(trace):
    import os
    from paddle_tpu.profiler import Profiler
    text = Profiler(log_dir=os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(trace))))).summary()
    assert "/device:TPU:0: device time by program and part" in text
    assert "jit_serving_prefill: 1 runs, 0.009 device ms a run" in text
    assert "Mosaic kernels 35.3 %" in text
    row = next(l for l in text.splitlines() if l.startswith("  attn_in"))
    assert row.split() == ["attn_in", "0.002", "23.5", "0.000", "0.000"]
    assert "while.5 (s32[], bf16[4,8])" in text
