"""Required work and roofline arithmetic against hand-computed shapes."""
import dataclasses
import types

import pytest
from chipbench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from chipbench import work
from chipbench.layer_metrics import _roofline
from chipbench.peaks import PEAKS, peaks_for
from chipbench.trace import Summary

SHAPE = work.Shape(layers=2, d=256, heads=4, kv_heads=2, head_dim=64,
                   d_ff=512, vocab=1000, bits=4, block=128, kv_bytes=1)


def test_parity_rank():
    assert work.parity_rank(4096, 4096, 128) == 16
    assert work.parity_rank(12288, 4096, 128) == 24
    assert work.parity_rank(8, 8, 128) == 1


def test_linear_totals_by_hand():
    # q 256x256, k/v 128x256, o 256x256, gate/up 512x256, down 256x512
    macs = 256 * 256 * 2 + 128 * 256 * 2 + 512 * 256 * 3
    assert SHAPE.linear_macs() == 2 * macs
    assert [(n, o, i) for n, o, i, _ in SHAPE.linears()] == [
        ("q", 256, 256), ("k", 128, 256), ("v", 128, 256), ("o", 256, 256),
        ("gate", 512, 256), ("up", 512, 256), ("down", 256, 512)]
    assert {L for *_, L in SHAPE.linears()} == {2}
    ranks = {(256, 256): 1, (128, 256): 1, (512, 256): 1, (256, 512): 1}
    per_layer = sum(n * k // 2 + 4 * ranks[(n, k)] * (n + k)
                    for _, n, k, _ in SHAPE.linears())
    assert SHAPE.linear_weight_bytes() == 2 * per_layer
    assert SHAPE.linears(decode=True) == SHAPE.linears()
    assert SHAPE.moe_layers == 0


def test_serve_work_counts_live_rows_only():
    # one request: prompt 20 (chunk 16 -> chunks [0,16) and [16,20)),
    # 5 tokens served; one cut mid-prompt (adds nothing); 3 chunk steps
    # and 7 decode steps ran
    w = work.serve_work(SHAPE, [(20, 5, True), (40, 0, False)], 16, 3, 7)
    assert w["rows.prefill"] == 20 and w["rows.decode"] == 4
    assert w["qmatmul.prefill"].flops == 2 * 20 * SHAPE.linear_macs()
    assert w["qmatmul.decode"].flops == 2 * 4 * SHAPE.linear_macs()
    assert w["qmatmul.decode"].bytes == (7 * SHAPE.linear_weight_bytes()
                                         + 4 * SHAPE.linear_act_bytes())
    causal = sum(i + 1 for i in range(20))
    assert w["attn.prefill"].flops == 4 * 4 * 64 * causal * 2
    ctx = sum(20 + j for j in range(1, 5))     # decode j attends 20 + j
    assert w["attn.decode"].flops == 4 * 4 * 64 * ctx * 2
    kv_tok = 2 * 2 * (64 + 4)
    assert w["attn.decode"].bytes == 2 * (ctx * kv_tok + 4 * 2 * 4 * 64 * 2)
    assert w["head"].flops == 2 * 5 * 256 * 1000
    assert not [k for k in w if k.startswith("experts.")]


def test_peaks_are_keyed_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s) == (
        197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        peaks_for("cpu")
    assert all(v.source for v in PEAKS.values())


@pytest.mark.parametrize("slowdown", [1.0, 1.5, 10.0])
@pytest.mark.parametrize("served", [2, 300])
def test_roofline_share_never_exceeds_100(slowdown, served):
    p = peaks_for("TPU v5 lite")
    w = work.serve_work(SHAPE, [(200, served, True)] * 3, 16, 13, served)
    least = sum(work.least_seconds(w[k], p.bf16_flops, p.hbm_bytes_per_s)
                for k in ("qmatmul.prefill", "qmatmul.decode"))
    ops = {"lords_matmul_pallas.4": [least * slowdown, 1.0],
           "fusion.1": [5.0, 1.0]}
    ctx = types.SimpleNamespace(
        peaks=p, obs={"work": w},
        trace=Summary(window_s=9.0, busy_s=8.0, devices=1, ops=ops, gaps=[]))
    s = _roofline.share(ctx, ("lords_matmul_pallas",),
                        ("qmatmul.prefill", "qmatmul.decode"))
    assert s == pytest.approx(100.0 / slowdown)
    assert s <= 100.0 + 1e-9
    ctx.trace.ops = {"fusion.1": [5.0, 1.0]}
    assert _roofline.share(ctx, ("lords_matmul_pallas",),
                           ("qmatmul.decode",)) is None


def test_qwen_file_phases_are_pinned():
    """The Qwen cell's required work on a fixed window, as the benchmark
    counted it before latent attention and experts were counted: every
    phase to the unit."""
    import json

    cfg = json.loads((ROOT / "chipbench" / "configs" / "qwen3-8b-nf4.json")
                     .read_text())
    reqs = [(96, 147, True), (250, 146, True), (32, 1, True),
            (200, 0, False), (129, 2, True), (256, 140, True)]
    w = work.serve_work(work.shape_of(cfg), reqs, 128, 2, 146)
    got = {k: (v.flops, v.bytes) if isinstance(v, work.Work) else v
           for k, v in w.items()}
    assert got == {
        "qmatmul.prefill": (10599241089024, 11541528576),
        "qmatmul.decode": (5987251519488, 540996452352),
        "attn.prefill": (45911900160, 591740928),
        "attn.decode": (69024743424, 9151934976),
        "head": (542671634432, 184209637376),
        "model": (17244100886528, 0),
        "rows.prefill": 763, "rows.decode": 431}


# three layers: one leading dense, two with 8 experts (2 a token) and 2
# shared; latent attention, q projected directly
LATENT = work.Shape(layers=3, d=64, heads=4, kv_heads=4, d_ff=128, vocab=256,
                    bits=4, block=32, kv_bytes=1, kv_lora=16, nope=16,
                    rope=8, v_dim=16, experts=8, top_k=2, moe_ff=32,
                    shared=2, dense_first=1)


def test_latent_expert_shape_by_hand():
    s = LATENT
    assert (s.moe_layers, s.dense_layers) == (2, 1)
    assert [x[:3] for x in s.linears()] == [
        ("q", 96, 64), ("kv_a", 24, 64), ("kv_b", 128, 16), ("o", 64, 64),
        ("gate", 128, 64), ("up", 128, 64), ("down", 64, 128),
        ("shared_gate", 64, 64), ("shared_up", 64, 64),
        ("shared_down", 64, 64)]
    assert [x[3] for x in s.linears()] == [3] * 4 + [1] * 3 + [2] * 3
    attn = 96 * 64 + 24 * 64 + 128 * 16 + 64 * 64
    assert s.linear_macs() == 3 * attn + 3 * 128 * 64 + 2 * 3 * 64 * 64
    # decode applies kv_b absorbed: not a quantized linear there
    assert s.linear_macs(decode=True) == s.linear_macs() - 3 * 128 * 16
    assert s.expert_macs == 3 * 32 * 64
    rank = work.parity_rank(32, 64, 32)
    assert s.expert_bytes == 3 * (32 * 64 // 2 + 4 * rank * 96)
    with_q_lora = dataclasses.replace(s, q_lora=8)
    assert [x[:3] for x in with_q_lora.linears()][:2] == [
        ("q_a", 8, 64), ("q_b", 96, 8)]


def test_latent_expert_serve_work_by_hand():
    s = LATENT
    eb, ea = s.expert_bytes, s.expert_act_bytes
    w = work.serve_work(s, [(20, 5, True)], 16, 3, 7)
    assert w["experts.prefill"].flops == 2 * 20 * 2 * 3 * 64 * 32 * 2
    assert w["experts.decode"].flops == 2 * 4 * 2 * 3 * 64 * 32 * 2
    # no count from the engine: top_k experts per expert layer and step
    assert w["experts.prefill"].bytes == 3 * 2 * 2 * eb + 20 * 2 * ea * 2
    assert w["experts.decode"].bytes == 7 * 2 * 2 * eb + 4 * 2 * ea * 2
    hit = work.serve_work(s, [(20, 5, True)], 16, 3, 7,
                          {"prefill": 40, "decode": 50})
    assert hit["experts.prefill"].bytes == 40 * eb + 20 * 2 * ea * 2
    assert hit["experts.decode"].bytes == 50 * eb + 4 * 2 * ea * 2
    # absorbed decode over 20 + j keys, j = 1..4: latents 16, rope 8
    ctx = sum(20 + j for j in range(1, 5))
    assert w["attn.decode"].flops == 2 * 4 * ctx * (2 * 16 + 8) * 3
    assert w["attn.decode"].bytes == 3 * (ctx * (16 + 4 + 2 * 8)
                                          + 4 * 4 * 40 * 2)
    # up-projected prefill: chunks [0, 16) and [16, 20)
    causal = sum(i + 1 for i in range(20))
    assert w["attn.prefill"].flops == 2 * 4 * (16 + 8 + 16) * causal * 3
    assert w["attn.prefill"].bytes == 3 * (2 * 16 * 4 * 40 * 2) + 3 * (
        16 * 36 + 2 * 4 * 4 * 40 * 2)
    absorb = 2 * 4 * 3 * 4 * 16 * (16 + 16)
    router = 2 * 24 * 2 * 64 * 8
    phases = ("qmatmul.prefill", "qmatmul.decode", "attn.prefill",
              "attn.decode", "head", "experts.prefill", "experts.decode")
    assert w["model"].flops == sum(w[k].flops for k in phases) + absorb \
        + router


def test_deepseek_v2_lite_active_macs():
    """DeepSeek-V2-Lite at its published keys: per token, the multiply-adds
    of every quantized linear it runs, kv_b and its 6 routed experts
    included: attention 27 × (q 2048→3072, kv_a 2048→576, kv_b 512→4096,
    o 2048→2048), one dense layer of 10944, 26 expert layers of 6 routed
    and 2 shared experts of 1408."""
    cfg = {"num_hidden_layers": 27, "hidden_size": 2048,
           "num_attention_heads": 16, "num_key_value_heads": 16,
           "intermediate_size": 10944, "vocab_size": 102400,
           "q_lora_rank": None, "kv_lora_rank": 512,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "v_head_dim": 128, "n_routed_experts": 64,
           "num_experts_per_tok": 6, "moe_intermediate_size": 1408,
           "n_shared_experts": 2, "first_k_dense_replace": 1,
           "moe_layer_freq": 1,
           "quantization": {"codebook": "nf4", "block_size": 128,
                            "kv_cache_dtype": "int8"}}
    s = work.shape_of(cfg)
    active = s.linear_macs() + s.moe_layers * s.top_k * s.expert_macs
    assert active == 2_238_185_472
    assert (s.moe_layers, s.dense_layers) == (26, 1)
    assert s.expert_bytes == 4_574_208
    assert s.layers * work._kv_token_bytes(s) == 17_388
    with pytest.raises(ValueError, match="moe_layer_freq 1, not 2"):
        work.shape_of(dict(cfg, moe_layer_freq=2))
    # every key read above is one the configuration check holds
    assert set(cfg) - {"quantization"} <= work.SHAPE_KEYS


@pytest.mark.parametrize("hits_per_layer_step", [2, 5, 8])
@pytest.mark.parametrize("slowdown", [1.0, 3.0])
def test_expert_share_never_exceeds_100(hits_per_layer_step, slowdown):
    """Against a kernel that takes ``slowdown`` times the least time of the
    experts it really read, the lower bound of top_k hits reads a share no
    higher than the true count's, which reads 100 / slowdown."""
    p = peaks_for("TPU v5 lite")
    reqs = [(40, 30, True)] * 4
    steps = {"prefill": 3, "decode": 29}
    true = {k: n * LATENT.moe_layers * hits_per_layer_step
            for k, n in steps.items()}
    w_true = work.serve_work(LATENT, reqs, 16, 3, 29, true)
    w_low = work.serve_work(LATENT, reqs, 16, 3, 29)
    phases = ("experts.prefill", "experts.decode")
    least = sum(work.least_seconds(w_true[k], p.bf16_flops,
                                   p.hbm_bytes_per_s) for k in phases)
    for w, want in ((w_true, 100.0 / slowdown), (w_low, None)):
        ctx = types.SimpleNamespace(
            peaks=p, obs={"work": w},
            trace=Summary(window_s=9.0, busy_s=8.0, devices=1,
                          ops={"experts_pallas.1": [least * slowdown, 1.0]},
                          gaps=[]))
        s = _roofline.share(ctx, ("experts_pallas",), phases)
        assert s <= 100.0 / slowdown + 1e-9
        if want is not None:
            assert s == pytest.approx(want)
    for k in phases:
        assert w_low[k].bytes <= w_true[k].bytes
        assert w_low[k].flops == w_true[k].flops
