"""What PR 34 brought for arch ``minicpm_sala``: the configuration's
cut, ``opcount_sala``'s counts against sums done by hand, the readers of
``layer_metrics/sala.py`` on made-up observations, the reference in
blocks against itself whole, and the new cell's rehearsal."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import opcount, opcount_sala
from harness import reference_minicpm_sala as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "minicpm-sala.longdoc-32k"


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_sala", os.path.join(BENCH, "layer_metrics", "sala.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config():
    with open(os.path.join(BENCH, "configs", "minicpm-sala.json")) as f:
        return json.load(f)


def test_published_widths_are_unchanged_and_the_cut_is_stated():
    cfg = config()
    widths = dict(hidden_size=4096, intermediate_size=16384, head_dim=128,
                  num_attention_heads=32, num_key_value_heads=2,
                  lightning_nh=32, lightning_nkv=32, lightning_head_dim=128,
                  vocab_size=73448, scale_emb=12, scale_depth=1.4,
                  dim_model_base=256, rope_theta=10000)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types",
                              "max_position_embeddings"]
    pub = cfg["published"]
    assert pub["num_hidden_layers"] == 32 == len(pub["mixer_types"])
    assert cfg["mixer_types"] == pub["mixer_types"][:8] + pub[
        "mixer_types"][24:]
    assert cfg["mixer_types"].count("minicpm4") == 4
    assert cfg["mixer_types"].count("lightning-attn") == 12
    assert pub["mixer_types"].count("minicpm4") * 3 == pub[
        "mixer_types"].count("lightning-attn")
    assert cfg["scale_depth_layers"] == 32      # the PUBLISHED depth
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["config_kwargs"]
    assert (kw["num_layers"], kw["mixer_types"], kw["scale_depth_layers"],
            kw["sparse_config"]) == (16, cfg["mixer_types"], 32,
                                     cfg["sparse_config"])
    assert cfg["rehearse"]["config_kwargs"]["sparse_config"] == cfg[
        "sparse_config"]
    d = opcount.dims(cfg)
    assert (d["sparse_layers"], d["lightning_layers"]) == (4, 12)
    # bf16 weights of the cut: 4 x 253.8 M + 12 x 285.2 M + 601.7 M
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    light = 5 * 4096 * 4096 + 3 * 4096 * 16384
    total = 4 * sparse + 12 * light + 2 * 73448 * 4096
    assert round(total / 1e6) == 5039 and round(2 * total / 1e9, 2) == 10.08


def test_the_free_text_of_benchmark_json_fits_its_limits():
    """The driver refuses the file before any run over a ``why``,
    ``source`` or ``layer`` that is not 1 to 200 printable characters on
    one line (this PR's first ``why`` had 203)."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    texts = [(e["name"], key, e[key])
             for group, keys in (("configs", ("why", "source")),
                                 ("workloads", ("why",)),
                                 ("per_layer", ("layer",)))
             for e in bench[group] for key in keys]
    assert any(name == "minicpm-sala" for name, _, _ in texts)
    assert any(name == CELL for name, _, _ in texts)
    for name, key, text in texts:
        assert 1 <= len(text) <= 200 and text.isprintable(), (name, key)


def test_blocks_and_keys_a_query_reads_by_hand():
    d = opcount.dims(config())
    # under dense_len: every block up to the query's own
    assert opcount_sala.blocks_read(d, 1) == 1
    assert opcount_sala.blocks_read(d, 8192) == 128
    assert opcount_sala.keys_read(d, 8192) == 8192
    # past it: the first block, 32 of the window, the top 64
    assert opcount_sala.blocks_read(d, 8193) == 97
    assert opcount_sala.blocks_read(d, 34816) == 97
    # 96 whole blocks and the query's own, in which 8193 is token 1
    assert opcount_sala.keys_read(d, 8193) == 96 * 64 + 1
    assert opcount_sala.keys_read(d, 8256) == 97 * 64
    assert opcount_sala.compressed_keys(d, 8193) == (8193 - 32) // 16 + 1
    assert opcount_sala.compressed_keys(d, 31) == 0


def test_kernel_counts_by_hand():
    d = opcount.dims(config())
    # lightning decode, 16 rows: a state is 32 x 128 x 128 float32 = 2 MiB,
    # read and written; q, k, v bf16 and o float32 are 32 x 128 each
    got = opcount_sala.lightning_decode_call(d, 16)
    assert got["bytes"] == 16 * (2 * 2097152 + 32 * 128 * (3 * 2 + 4))
    assert got["flops"] == 16 * 32 * 5 * 128 * 128
    # sparse decode, one row that sees 10000 tokens: 624 compressed keys
    # of 256 values, 96 blocks and 16 tokens of k and v for 2 groups
    got = opcount_sala.sparse_decode_call(d, [10000])
    ck, keys = (10000 - 32) // 16 + 1, 96 * 64 + 16
    assert ck == 624 and (10000 - 1) % 64 + 1 == 16
    assert got["bytes"] == 2 * 2 * 128 * (ck + 2 * keys) + 32 * 128 * 6
    assert got["flops"] == 2 * 32 * 128 * ck + 4 * 32 * 128 * keys
    # a dense row scores no compressed key
    got = opcount_sala.sparse_decode_call(d, [100])
    assert got["flops"] == 4 * 32 * 128 * 100
    # sparse prefill of 8194 tokens: the causal triangle up to 8192, then
    # 96 blocks and 1 and 2 tokens
    got = opcount_sala.sparse_prefill_call(d, 8194)
    keys = 8192 * 8193 // 2 + (96 * 64 + 1) + (96 * 64 + 2)
    assert got["flops"] == 4 * 32 * 128 * keys
    assert got["bytes"] == 8194 * (32 * 128 * 6 + 2 * 2 * 128 * 2)
    # lightning prefill of 10000 true tokens of one request
    got = opcount_sala.lightning_prefill_call(d, 10000)
    assert got["flops"] == 10000 * 32 * 5 * 128 * 128
    assert got["bytes"] == 10000 * 4096 * 10 + 2 * 2097152


class _Red:
    window_s, busy_s = 3.0, 2.0

    def __init__(self, by_name):
        self.by_name = by_name


def test_readers_on_made_up_observations():
    sala = _load()
    cfg = config()
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}

    class Res:
        tokens = list(range(11))
    # one request of 9000 prompt tokens whose 10 decode steps are steps
    # 1..10; steps 5..10 are traced: n = 9005 .. 9010
    recs = [dict(result=Res(), finish_step=10, prompt_len=9000)]
    obs = dict(config=cfg, peaks=peaks, all_requests=recs,
               trace_steps=(4, 10), trace=_Red({
                   "lightning_decode": (72 * 1e-5, 72),
                   "sparse_select": (24 * 2e-6, 24),
                   "sparse_paged_decode": (24 * 1e-5, 24),
                   "fusion.1 bf16[16,4096]": (1.0, 10)}),
               stats=dict(sparse_blocks_read=97 * 8 * 6,
                          sparse_blocks_visible=141 * 8 * 6,
                          sparse_dense_rows=0, lightning_rows=72, steps=6))
    d = opcount.dims(cfg)
    got = sala.lightning_decode_roofline(obs)
    per = opcount_sala.lightning_decode_call(d, 1.0)
    assert got["calls"] == 72 and got["mean_rows"] == 1.0
    assert got["value"] == pytest.approx(
        100 * per["bytes"] * 72 / 819e9 / (72 * 1e-5))
    got = sala.sparse_decode_roofline(obs)
    need = opcount_sala.sparse_decode_call(d, np.arange(9005, 9011))
    assert got["mean_blocks_read"] == 97 and got["bound"] == "hbm"
    assert got["value"] == pytest.approx(
        100 * need["bytes"] * 4 / 819e9 / (24 * 2e-6 + 24 * 1e-5))
    assert sala.sparse_read_share(obs)["value"] == pytest.approx(
        100 * 97 / 141)
    got = sala.mixer_share(obs)
    assert got["value"] == pytest.approx(
        100 * (72e-5 + 48e-6 + 24e-5) / 2.0)
    assert got["prefill_share"] == 0.0
    # the parent, a CPU run: nothing to read, nothing raised
    bare = dict(obs, trace=None, peaks=None, trace_path=None,
                stats=dict(steps=6))
    for reader in (sala.lightning_decode_roofline, sala.mixer_share,
                   sala.sparse_decode_roofline, sala.sparse_read_share,
                   sala.lightning_prefill_roofline,
                   sala.sparse_prefill_roofline):
        assert reader(bare) is None
    other = dict(obs, trace=_Red({"mla_paged_decode": (1.0, 3)}))
    assert sala.mixer_share(other) is None
    assert sala.lightning_decode_roofline(other) is None


def test_the_reference_in_blocks_is_the_reference_whole():
    """Token rows and query rows a block at a time (what fits the chip at
    34,816 positions) against one block of everything."""
    import jax
    import jax.numpy as jnp
    from harness import model
    cfg = model.effective_config(config(), rehearse=True)
    cfg["sparse_config"] = dict(kernel_size=8, kernel_stride=4, block_size=8,
                                topk=2, window_size=16, init_blocks=1,
                                dense_len=64)
    shapes = {}
    H, C, F, V = 4 * 16, 64, 96, 1000
    for i, kind in enumerate(cfg["mixer_types"]):
        p = f"model.layers.{i}."
        kvw = H if kind == "lightning-attn" else 2 * 16
        for name, shape in (("self_attn.q_proj", (C, H)),
                            ("self_attn.k_proj", (C, kvw)),
                            ("self_attn.v_proj", (C, kvw)),
                            ("self_attn.o_gate", (C, H)),
                            ("self_attn.o_proj", (H, C)),
                            ("self_attn.q_norm", (16,)),
                            ("self_attn.k_norm", (16,)),
                            ("mlp.gate_proj", (C, F)), ("mlp.up_proj", (C, F)),
                            ("mlp.down_proj", (F, C)),
                            ("input_layernorm", (C,)),
                            ("post_attention_layernorm", (C,))):
            shapes[p + name + ".weight"] = shape
        if kind == "lightning-attn":
            shapes[p + "self_attn.o_norm.weight"] = (H,)
    shapes.update({"model.embed_tokens.weight": (V, C),
                   "model.norm.weight": (C,), "lm_head.weight": (C, V)})
    state = model.make_state(
        {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()},
        7, 0.3, jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (1, 200), 3, V)
    pos = jnp.arange(150, 190)
    whole = ref.logits_at(state, ids, pos, dict(
        cfg, reference_rows=4096, reference_query_rows=4096))
    blocked = ref.logits_at(state, ids, pos, dict(
        cfg, reference_rows=64, reference_query_rows=32))
    assert whole.shape == (40, V)
    assert float(jnp.abs(whole - blocked).max()) < 1e-5
    # causal: what follows the last position asked for changes nothing
    ids2 = ids.at[0, 190:].set(5)
    again = ref.logits_at(state, ids2, pos, cfg)
    assert float(jnp.abs(whole - again).max()) < 1e-5


@pytest.mark.slow
def test_rehearsal_of_the_new_cell_is_correct():
    """Minutes on the CPU: 12 prefill programs of a tiny model over 10k
    to 32k positions."""
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3400000123", "--seconds", "3", "--trace", "1",
         "--rehearse", "--rate-rps", "0.5"],
        capture_output=True, text=True, timeout=3000)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    # a CPU takes 20 s over one tiny 20k-token prefill, so a 3 s window
    # holds no decode step and the step's metrics have nothing to read
    assert line["metrics"]["programs.compiles_in_window"]["value"] == 0
