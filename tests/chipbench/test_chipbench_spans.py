"""Readers of the engine's spans (``chunk_step_ms``, ``engine_self_ms``) and
the scope reduction of ``tools/scopes.py``, on synthetic inputs and on a
tiny CPU run of the batch cell."""
import types
from pathlib import Path

import pytest
from chipbench_tiny import ROOT, tiny_root

from chipbench import harness, trace
from chipbench.trace import Event, Line, Plane

scopes = harness.load_module(ROOT / "chipbench" / "tools" / "scopes.py")


def _reader(name):
    return harness._reader("layer_metrics", name)


def _ctx(spans=None):
    stats = {} if spans is None else {"spans": spans}
    return types.SimpleNamespace(obs={"stats": stats})


def _rec(count, ms, self_ms):
    return {"count": count, "ms": ms, "self_ms": self_ms}


SPANS = {
    "engine.start": _rec(1, 0.5, 0.5),
    "engine.tick": _rec(5, 9000.0, 2.0),
    "engine.intake": _rec(5, 1.0, 1.0),
    "engine.claim": _rec(4, 2.0, 2.0),
    "engine.pack": _rec(4, 1.5, 1.5),
    "engine.step.chunk": _rec(2, 7560.0, 0.2),
    "engine.step.burst": _rec(2, 1400.0, 0.1),
    "engine.dispatch": _rec(4, 40.0, 40.0),
    "engine.fetch": _rec(4, 8919.9, 8919.9),
    "engine.commit": _rec(4, 3.0, 3.0),
    "engine.finish": _rec(1, 4.0, 4.0),
}


@pytest.mark.parametrize("metric,want", [
    ("chunk_step_ms.batch", 3780.0),
    # self ms of all but dispatch/fetch: 0.5+2+1+2+1.5+0.2+0.1+3+4, over
    # four launches
    ("engine_self_ms.batch", 14.3 / 4),
])
def test_span_readers(metric, want):
    r = _reader(metric)
    assert r.read(_ctx(SPANS)) == pytest.approx(want)
    # the parent's engine has no spans: nothing to read, nothing raised
    assert r.read(_ctx()) is None
    assert r.read(_ctx({"engine.tick": _rec(1, 1.0, 1.0)})) is None


def test_span_readers_on_a_tiny_run(tmp_path):
    root = tiny_root(Path(tmp_path))
    cell = harness.load_cell("tiny.batch", root=root)
    ctx = harness.RunContext(cell=cell, seed=2**31 + 11, seconds=2.0,
                             backend="ref")
    ctx.model_cfg = harness.model_config(cell.cfg)
    job = harness.load_module(harness.HERE / "jobs" / "serve.py").Job(ctx)
    job.setup(2.0)
    job.window(2.0)
    ctx.obs = job.observe()
    st = ctx.obs["stats"]
    assert st["compiles"] == 0
    assert _reader("chunk_step_ms.batch").read(ctx) == pytest.approx(
        st["prefill_ms"] / st["chunk_steps"])
    own = _reader("engine_self_ms.batch").read(ctx)
    assert 0 < own < ctx.obs["window_s"] * 1e3


PATH = ("jit(generate_step)/step_paged_generate_g8/while/body/closed_call/"
        "while/body/closed_call/attn/qmatmul/jit(lords_matmul_pallas)/"
        "pallas_call")


@pytest.mark.parametrize("op_path,want", [
    (PATH, "step_paged_generate_g8/attn/qmatmul"),
    ("jit(chunk_step)/step_chunk_prefill/while/body/closed_call/attn/"
     "kv_window/gather;jit(chunk_step)/step_chunk_prefill/mlp/add",
     "step_chunk_prefill/attn/kv_window"),
    ("jit(generate_step)/step_paged_generate_g1/while/body/closed_call/"
     "sample/argmax", "step_paged_generate_g1/sample"),
    ("jit(_split)/threefry2x32", ""),
    ("", ""),
])
def test_scope_path(op_path, want):
    assert scopes.scope_path(op_path) == want


def _ops():
    """Two devices alike: a while op spanning its body, a kernel, glue in
    the qmatmul scope, a kv store and an op past the window."""
    evs = [
        (1000, 6000, "%while.1 = while(%t)",
         {"long_name": "jit(s)/step_x/while"}),
        (1000, 2000, "%lords_matmul_pallas.3 = custom-call()",
         {"long_name": "jit(s)/step_x/attn/qmatmul/"
                       "jit(lords_matmul_pallas)/pallas_call",
          "hlo_op": "lords_matmul_pallas.3"}),
        (3000, 1000, "%fusion.4 = fusion()",
         {"long_name": "jit(s)/step_x/attn/qmatmul/pad"}),
        (4000, 3000, "%scatter.5 = scatter()",
         {"long_name": "jit(s)/step_x/attn/kv_store/scatter"}),
        (9000, 4000, "%copy.6 = copy()", {}),
    ]
    return {"/device:TPU:0": evs, "/device:TPU:1": evs}


def test_scope_seconds_and_model_self_share():
    ops = _ops()
    assert scopes.path_stat(ops) == "long_name"
    secs = scopes.scope_seconds(ops, (0, 10000), "long_name")
    assert secs == pytest.approx({
        "step_x/attn/qmatmul": 3000e-9, "step_x/attn/kv_store": 3000e-9,
        scopes.NO_SCOPE: 1000e-9})      # the copy, cut at the window
    assert scopes.top_scopes(secs, 1) == pytest.approx(
        {"step_x": 6000e-9, scopes.NO_SCOPE: 1000e-9})
    assert scopes.model_self_share(secs) == pytest.approx(50.0)
    assert scopes.model_self_share({scopes.NO_SCOPE: 1.0}) is None
    assert scopes.scope_seconds(ops, (0, 10000), None) == pytest.approx(
        {scopes.NO_SCOPE: 7000e-9})


def test_scope_stat_leaves_the_harness_reduction_as_it_is():
    """A path under a stat of its own moves no key of ``Summary.ops``."""
    host = Plane("/host:CPU", [Line("python", [
        Event(trace.WINDOW, 0, 10000), Event("engine.tick", 0, 8500),
        Event("engine.commit", 7900, 500)])])

    def planes(with_path):
        evs = [Event(name, s, d, ({"long_name": st["long_name"]}
                                  if with_path and "long_name" in st else {}))
               for s, d, name, st in _ops()["/device:TPU:0"]]
        return [host, Plane("/device:TPU:0", [Line("XLA Ops", evs)])]

    plain, scoped = trace.reduce(planes(False)), trace.reduce(planes(True))
    assert plain == scoped
    assert "lords_matmul_pallas.3" in plain.ops
    gaps = scopes.idle_gaps(_ops(), (0, 10000))
    assert gaps == [(7000, 9000), (0, 1000)]
    assert scopes.engine_overlaps(planes(True), gaps[0]) == [
        ["engine.commit", 500, 500], ["engine.tick", 1500, 8500]]


def _pb(*fields):
    """Protobuf bytes of (field number, int | bytes | str) pairs."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_device_ops_read_metadata_stats(tmp_path):
    """The op path lives on the event's metadata (``tf_op``), which the
    XSpace reader joins to the event's own stats; host planes are skipped."""
    stat_md = [_pb((1, 1), (2, _pb((1, 1), (2, "tf_op")))),
               _pb((1, 2), (2, _pb((1, 2), (2, "device_duration_ps"))))]
    ev_md = _pb((1, 7), (2, _pb(
        (1, 7), (2, "%fusion.4 = fusion()"),
        (5, _pb((1, 1), (5, "jit(s)/step_x/attn/kv_store/scatter:"))))))
    event = _pb((1, 7), (2, 3_000_000), (3, 2_000_000),
                (4, _pb((1, 2), (3, 2_000_000))))
    lines = [_pb((2, "XLA Modules"), (3, 100), (4, event)),
             _pb((2, "XLA Ops"), (3, 100), (4, event))]
    dev = _pb((2, "/device:TPU:0"), *[(3, ln) for ln in lines], (4, ev_md),
              *[(5, m) for m in stat_md])
    host = _pb((2, "/host:CPU"), (3, _pb((2, "python"), (4, event))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, dev)))
    ops = scopes.device_ops(str(path))
    assert ops == {"/device:TPU:0": [(3100.0, 2000.0, "%fusion.4 = fusion()", {
        "tf_op": "jit(s)/step_x/attn/kv_store/scatter:",
        "device_duration_ps": "2000000"})]}
    assert scopes.path_stat(ops) == "tf_op"
