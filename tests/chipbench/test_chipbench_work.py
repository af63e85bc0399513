"""Required work and roofline arithmetic against hand-computed shapes."""
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
    assert SHAPE.linear_macs == 2 * macs
    ranks = {(256, 256): 1, (128, 256): 1, (512, 256): 1, (256, 512): 1}
    per_layer = sum(n * k // 2 + 4 * ranks[(n, k)] * (n + k)
                    for _, n, k in SHAPE.linears())
    assert SHAPE.linear_weight_bytes == 2 * per_layer


def test_serve_work_counts_live_rows_only():
    # one request: prompt 20 (chunk 16 -> chunks [0,16) and [16,20)),
    # 5 tokens served; one cut mid-prompt (adds nothing); 3 chunk steps
    # and 7 decode steps ran
    w = work.serve_work(SHAPE, [(20, 5, True), (40, 0, False)], 16, 3, 7)
    assert w["rows.prefill"] == 20 and w["rows.decode"] == 4
    assert w["qmatmul.prefill"].flops == 2 * 20 * SHAPE.linear_macs
    assert w["qmatmul.decode"].flops == 2 * 4 * SHAPE.linear_macs
    assert w["qmatmul.decode"].bytes == (7 * SHAPE.linear_weight_bytes
                                         + 4 * SHAPE.linear_act_bytes)
    causal = sum(i + 1 for i in range(20))
    assert w["attn.prefill"].flops == 4 * 4 * 64 * causal * 2
    ctx = sum(20 + j for j in range(1, 5))     # decode j attends 20 + j
    assert w["attn.decode"].flops == 4 * 4 * 64 * ctx * 2
    kv_tok = 2 * 2 * (64 + 4)
    assert w["attn.decode"].bytes == 2 * (ctx * kv_tok + 4 * 2 * 4 * 64 * 2)
    assert w["head"].flops == 2 * 5 * 256 * 1000


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
