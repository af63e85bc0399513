"""The trace reduction on a small synthetic trace: busy union, idle share,
op attribution and the labels of idle gaps."""
import types

import pytest
from chipbench_tiny import ROOT  # noqa: F401

from chipbench import trace
from chipbench.trace import Event, Line, Plane


def _trace():
    host = Plane("/host:CPU", [
        Line("python", [Event(trace.WINDOW, 1000, 10000),
                        Event("engine.py:_run_chunk", 5100, 1800),
                        Event("engine.py:run", 900, 10200),
                        Event("time.sleep", 8200, 2200)])])
    ops = [Event("%while.2 = (s32[], bf16[4]) while(%tuple.1)", 2000, 6000),
           Event("%lords_matmul_pallas.3 = bf16[32,4096] custom-call("
                 "bf16[32,4096] %fusion.2)", 2000, 2000),
           # reads the kernel's output: not the kernel
           Event("%fusion.7 = bf16[32,4096] fusion(bf16[32,4096] "
                 "%lords_matmul_pallas.3)", 3000, 2000),
           Event("%custom-call.9 = f32[32,1024] custom-call(%q.1)", 7000,
                 1000, {"tf_op": "jit(step)/jit(attn_decode_gqa_paged_pallas)"
                        "/pallas_call"}),
           Event("%fusion.8 = f32[8] fusion(%p.1)", 10500, 1500),  # past
           Event("%fusion.9 = f32[8] fusion(%p.2)", 200, 300)]     # before
    dev = Plane("/device:TPU:0", [Line("XLA Modules", [
        Event("jit_step", 1500, 9000)]), Line("XLA Ops", ops)])
    return [host, dev]


def test_busy_union_and_idle_share():
    s = trace.reduce(_trace())
    assert s.window_s == pytest.approx(10000e-9)
    # [2000, 8000) (the while spans its body) + [10500, 11000) inside
    # [1000, 11000)
    assert s.busy_s == pytest.approx(6500e-9)
    from chipbench.layer_metrics import device_idle_share
    idle = device_idle_share.read(types.SimpleNamespace(trace=s))
    assert idle == pytest.approx(35.0)


def test_op_attribution_and_clipping():
    s = trace.reduce(_trace())
    assert s.seconds_matching(("lords_matmul_pallas",)) == \
        pytest.approx(2000e-9)
    assert s.seconds_matching(("attn_decode_gqa_paged_pallas",)) == \
        pytest.approx(1000e-9)
    assert s.ops["fusion.8"][0] == pytest.approx(500e-9)
    assert s.ops["fusion.7"][0] == pytest.approx(2000e-9)
    assert "fusion.9" not in s.ops
    assert not any(k.startswith("while") for k in s.ops)


def test_gaps_longest_first_with_host_labels():
    s = trace.reduce(_trace())
    assert [g[1] for g in s.gaps] == pytest.approx([2500e-9, 1000e-9])
    assert [g[0] for g in s.gaps] == ["time.sleep", "no host activity"]


def test_reduce_needs_the_window_span():
    host, dev = _trace()
    host.lines[0].events = host.lines[0].events[1:]
    with pytest.raises(ValueError):
        trace.reduce([host, dev])
