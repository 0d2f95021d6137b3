"""What PR 32 brought for arch ``deepseek_v2``: ``reference_deepseek_v2.
dims`` under the byte and flop functions that were there, against sums
done by hand for one chip's share; the reader of ``engine.moe_local_
share``; the reference's veto; and the new cell's rehearsal."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import opcount, opcount_xing4, opcount_xing4_prefill

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "deepseek-v2.chat-4k"


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_moe_share", os.path.join(BENCH, "layer_metrics", "moe_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config():
    with open(os.path.join(BENCH, "configs", "deepseek-v2.json")) as f:
        return json.load(f)


def test_published_widths_are_unchanged_and_the_cut_is_stated():
    cfg = config()
    widths = dict(hidden_size=5120, intermediate_size=12288,
                  moe_intermediate_size=1536, num_attention_heads=128,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                  q_lora_rank=1536, kv_lora_rank=512, router_experts=160,
                  n_group=8, topk_group=3, num_experts_per_tok=6,
                  routed_scaling_factor=16, n_shared_experts=2)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "max_position_embeddings"]
    assert cfg["published"] == dict(
        num_hidden_layers=60, n_routed_experts=160, vocab_size=102400,
        max_position_embeddings=163840)
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 40, 25600)
    # the share is whole router groups, and the program is told the same
    kw = cfg["model"]["config_kwargs"]
    assert cfg["router_experts"] // cfg["n_group"] == 20
    assert cfg["n_routed_experts"] % 20 == 0 and cfg["expert_offset"] % 20 == 0
    assert (kw["n_routed_experts"], kw["experts_held"], kw["expert_offset"],
            kw["vocab_size"], kw["num_layers"]) == (160, 40, 0, 25600, 5)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert "four chips share each layer" in cfg["deployment"]
    assert cfg["rehearse"]["n_group"] == 8 and cfg["rehearse"][
        "topk_group"] == 3
    assert cfg["rehearse"]["n_routed_experts"] * 4 == cfg["rehearse"][
        "router_experts"]            # a share of 2 groups of 8


def test_dims_and_weight_bytes_by_hand():
    d = opcount.dims(config())
    assert (d["layers"], d["dense_layers"], d["moe_layers"]) == (5, 1, 4)
    assert d["cache_lanes"] == 576 and d["experts"] == 40 and d["top_k"] == 6
    assert d["streams"] == 0 and d["vocab"] == 25600 and d["shared"] == 2
    # 5 layers x 576 values x 2 bytes
    assert opcount_xing4.latent_bytes_per_token(d) == 5760
    # ISSUE 32's table: MLA 149.2 M a layer, an expert 23.59 M, the
    # shared experts 47.2 M, the dense FFN 188.7 M, the head 131.1 M
    mla = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
           + 128 * 128 * 5120)
    assert mla == 149_225_472
    expert = 3 * 5120 * 1536
    assert expert == 23_592_960
    # what a step streams with every held expert touched: everything
    # but the embedding (rows are looked up); the router counted at the
    # held width (the reader's 40 for its 160 columns: 4.9 MB of 10 GB)
    want = 2 * (5 * mla + 3 * 5120 * 12288
                + 4 * (expert * (2 + 40) + 5120 * 40) + 25600 * 5120)
    assert opcount_xing4.step_weight_bytes(d, touched=40) == want
    assert want == pytest.approx(10.06e9, rel=0.005)
    # held in all: + the embedding and the router's other 120 columns
    held = want + 2 * (25600 * 5120 + 4 * 5120 * 120)
    assert held == pytest.approx(10.33e9, rel=0.005)
    assert (opcount_xing4.step_weight_bytes(d, 40)
            - opcount_xing4.step_weight_bytes(d, 34)) == 6 * 4 * expert * 2


def test_kernel_calls_for_a_share_by_hand():
    d = opcount.dims(config())
    # 50 rows that read 70,000 cached rows between them, one layer: at
    # 128 heads the call sits at the v5e's ridge (240 flops a byte)
    got = opcount_xing4.mla_decode_call(d, rows=50, attended_tokens=70_000)
    seen = 70_050
    assert got["bytes"] == seen * 576 * 2 + 50 * 128 * (576 * 2 + 512 * 4)
    assert got["flops"] == 2 * 128 * (576 + 512) * seen
    assert got["flops"] / got["bytes"] == pytest.approx(197e12 / 819e9,
                                                        rel=0.2)
    # a step of 50 rows: 300 picks, 75 of them here, 34 experts touched.
    # The reader hands the call rows = picks here / top_k
    moe = opcount_xing4.moe_ffn_call(d, rows=75 / 6, touched=34)
    assert moe["flops"] == 75 * 3 * 2 * 5120 * 1536
    assert moe["bytes"] == pytest.approx(34 * 3 * 5120 * 1536 * 2, rel=1e-3)
    # a wave of 3,584 positions: 4 calls, 21,504 picks a call of which a
    # quarter here; all 40 held experts taken as touched
    rows = 4 * 5376
    pre = opcount_xing4_prefill.moe_prefill_calls(d, calls=4,
                                                  routed_rows=rows)
    assert pre["bytes"] == (4 * 40 * 3 * 5120 * 1536 * 2
                            + rows * 2 * 5120 * 2)
    assert pre["flops"] == rows * 3 * 2 * 5120 * 1536
    assert opcount_xing4_prefill.every_expert_touched(d, rows / 4)
    # the smallest bucket's 384 picks a call still count as every expert
    assert opcount_xing4_prefill.every_expert_touched(d, 256 * 6 / 4)
    assert not opcount_xing4_prefill.every_expert_touched(d, 319)


def test_local_share_reader():
    reader = _load().moe_local_share
    stats = dict(moe_layer_steps=400, moe_picks=400 * 300,
                 moe_rows=400 * 75, moe_experts_touched=400 * 34,
                 moe_rows_max=400 * 6)
    got = reader(dict(stats=stats))
    assert got["value"] == pytest.approx(25.0)
    assert got["picks_a_layer_step"] == 300
    assert got["rows_here_a_layer_step"] == 75
    # a model that holds every expert counts no picks; the parent none
    assert reader(dict(stats=dict(moe_layer_steps=400, moe_rows=9))) is None
    assert reader(dict(stats=dict(steps=5))) is None
    assert reader(dict(stats=dict(moe_layer_steps=0, moe_picks=0,
                                  moe_rows=0))) is None


def test_logits_at_refuses_a_request_that_breaks_the_agreement():
    """The tiny share in float32: the reference's own argmax tokens hold
    the rule; another token at every row breaks it and the logits come
    back non-finite, which ``serve.check_outputs`` reports as not
    correct."""
    import jax.numpy as jnp
    from harness import model, reference_deepseek_v2 as ref
    cfg = model.effective_config(config(), rehearse=True)
    mdl = model.build_model(cfg)
    state = model.make_state(mdl.state_dict(include_buffers=False), 5,
                             cfg["init_std"], jnp.float32)
    assert state["model.layers.1.mlp.gate.weight"].shape[1] == 32
    assert state["model.layers.1.mlp.experts.w_gate"].shape[0] == 8
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
    assert np.isfinite(good).all() and good.shape[1] == cfg["vocab_size"]
    np.testing.assert_array_equal(good[:n].argmax(-1), ids[0, p:p + n])
    ids[0, p + 1:p + n] = (ids[0, p + 1:p + n] + 1) % cfg["vocab_size"]
    bad = np.asarray(ref.logits_at(state, jnp.asarray(ids),
                                   jnp.asarray(pos), strict))
    assert not np.isfinite(bad).any()


@pytest.mark.slow
def test_rehearsal_of_the_new_cell_is_correct():
    """Minutes on the CPU: 14 prefill programs of a tiny model. At 2
    requests a second: this sandbox's CPU takes seconds over a tiny
    3.5k-token prefill, and a window's requests have to drain."""
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3200000123", "--seconds", "3", "--trace", "1",
         "--rehearse", "--rate-rps", "2"],
        capture_output=True, text=True, timeout=1500)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert "engine.moe_touched_share" in line["metrics"]
    assert "engine.moe_local_share" in line["metrics"]
