"""Crash-safe layer-streaming PTQ — the resume contract, asserted.

The invariant under test everywhere: whatever happens mid-run (kill at a
block boundary, kill inside a shard write, kill between shard and ledger
commit, bitrot on a published shard, an OOM spike, a preemption), a
``resume=True`` re-run finishes with an artifact **bit-identical** to an
uninterrupted run, reusing every block it can prove valid and recomputing
exactly the ones it can't.
"""
import json
import os

import numpy as np
import pytest

from repro.ptq_stream import (
    Ledger,
    MemoryBudget,
    MemoryBudgetExceeded,
    ResidualMLPSource,
    StreamPlan,
    audit_artifact,
    quantize_dense_blocks,
    read_shard,
    stream_quantize,
)
from repro.ptq_stream.shards import digest_array, shard_name, write_shard
from repro.robustness import NO_FAULTS, FaultPlan, InjectedFault

N_BLOCKS = 3


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    return ResidualMLPSource.create(
        str(tmp_path_factory.mktemp("model")),
        num_blocks=N_BLOCKS, d=48, d_ff=64, tokens=16, seed=0)


@pytest.fixture(scope="module")
def plan():
    return StreamPlan(block_size=16, rank=3, refine_steps=6)


@pytest.fixture(scope="module")
def reference(source, plan, tmp_path_factory):
    """One clean streamed run: (out_dir, summary, per-block shard trees)."""
    out = str(tmp_path_factory.mktemp("ref"))
    summary = stream_quantize(source, out, plan)
    shards = [read_shard(os.path.join(out, shard_name(i)))
              for i in range(N_BLOCKS)]
    return out, summary, shards


def _assert_identical(ref_shards, out_dir):
    for i, want in enumerate(ref_shards):
        got = read_shard(os.path.join(out_dir, shard_name(i)))
        assert sorted(got) == sorted(want), f"block {i}: key set differs"
        for k in want:
            np.testing.assert_array_equal(got[k], want[k],
                                          err_msg=f"block {i} key {k}")


# ---------------------------------------------------------------------------
# clean path
# ---------------------------------------------------------------------------


def test_clean_run_completes_with_clean_audit(source, plan, reference):
    out, summary, _ = reference
    assert summary["status"] == "complete"
    assert summary["blocks_done"] == N_BLOCKS
    aud = audit_artifact(out, source, plan)
    assert aud["clean"], aud
    assert all(b["ok"] for b in aud["blocks"])


def test_streamed_equals_in_memory_bit_identical(source, plan, reference):
    """The tentpole claim: streaming one block at a time produces the same
    packed codes, factors and propagated activations as holding the whole
    dense model in memory."""
    _, summary, shards = reference
    ref, x_digest = quantize_dense_blocks(source, plan)
    for i in range(N_BLOCKS):
        assert sorted(shards[i]) == sorted(ref[i])
        for k in ref[i]:
            np.testing.assert_array_equal(shards[i][k], ref[i][k],
                                          err_msg=f"block {i} key {k}")
    assert summary["x_final_digest"] == x_digest


def test_ledger_chains_activation_digests(reference):
    out, _, _ = reference
    led = Ledger(out)
    assert led.load() and led.status == "complete"
    ents = led.entries
    assert len(ents) == N_BLOCKS
    for prev, cur in zip(ents, ents[1:]):
        assert cur["x_in"] == prev["x_out"]


def test_resume_of_complete_run_reuses_everything(source, plan, reference):
    out, _, shards = reference
    s = stream_quantize(source, out, plan, resume=True)
    assert s["status"] == "complete"
    assert s["reused"] == N_BLOCKS and s["recomputed"] == []
    _assert_identical(shards, out)


# ---------------------------------------------------------------------------
# kill + resume parity at every block boundary
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("boundary", range(N_BLOCKS))
def test_kill_at_every_boundary_resumes_bit_identical(
        source, plan, reference, tmp_path, boundary):
    _, _, shards = reference
    out = str(tmp_path / "run")
    faults = FaultPlan(boundary, {"ptq.kill_at_block": {"at": (boundary,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(source, out, plan, faults=faults)
    s = stream_quantize(source, out, plan, resume=True)
    assert s["status"] == "complete"
    assert s["reused"] == boundary, "pre-kill blocks must be reused"
    assert s["recomputed"] == list(range(boundary, N_BLOCKS))
    _assert_identical(shards, out)
    assert audit_artifact(out, source, plan)["clean"]


def test_kill_mid_shard_write_leaves_no_stray_state(
        source, plan, reference, tmp_path):
    _, _, shards = reference
    out = str(tmp_path / "run")
    faults = FaultPlan(0, {"ptq.kill_mid_write": {"at": (1,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(source, out, plan, faults=faults)
    assert any(".tmp" in n for n in os.listdir(out)), "kill left no temp"
    s = stream_quantize(source, out, plan, resume=True)
    assert s["stray_tmp_removed"] >= 1
    assert not any(".tmp" in n for n in os.listdir(out))
    _assert_identical(shards, out)


def test_kill_between_shard_and_ledger_commit(source, plan, reference,
                                              tmp_path):
    """A published-but-unjournaled shard is re-done — to the same bytes."""
    _, _, shards = reference
    out = str(tmp_path / "run")
    faults = FaultPlan(0, {"ptq.kill_before_commit": {"at": (1,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(source, out, plan, faults=faults)
    led = Ledger(out)
    assert led.load() and len(led.entries) == 1  # block 1 never journaled
    assert os.path.exists(os.path.join(out, shard_name(1)))
    s = stream_quantize(source, out, plan, resume=True)
    assert s["recomputed"] == [1, 2]
    _assert_identical(shards, out)


# ---------------------------------------------------------------------------
# corruption detection
# ---------------------------------------------------------------------------


def test_corrupt_shard_detected_and_only_that_block_redone(
        source, plan, reference, tmp_path):
    _, _, shards = reference
    out = str(tmp_path / "run")
    faults = FaultPlan(0, {"ptq.corrupt_shard": {"at": (1,)},
                           "ptq.kill_at_block": {"at": (2,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(source, out, plan, faults=faults)
    aud = audit_artifact(out, source, plan)
    assert not aud["clean"]
    assert aud["blocks"][0]["ok"] and not aud["blocks"][1]["ok"]
    s = stream_quantize(source, out, plan, resume=True)
    assert s["reused"] == 1 and s["recomputed"] == [1, 2]
    _assert_identical(shards, out)
    assert audit_artifact(out, source, plan)["clean"]


def test_hand_corrupted_ledger_falls_back_to_fresh_run(
        source, plan, reference, tmp_path):
    _, _, shards = reference
    out = str(tmp_path / "run")
    stream_quantize(source, out, plan)
    with open(os.path.join(out, "ledger.json"), "w") as f:
        f.write("{torn")
    s = stream_quantize(source, out, plan, resume=True)
    assert s["status"] == "complete"
    _assert_identical(shards, out)
    assert audit_artifact(out, source, plan)["clean"]


def test_resume_refuses_mismatched_plan(source, plan, tmp_path):
    out = str(tmp_path / "run")
    faults = FaultPlan(0, {"ptq.kill_at_block": {"at": (1,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(source, out, plan, faults=faults)
    other = StreamPlan(block_size=16, rank=3, refine_steps=7)
    with pytest.raises(ValueError, match="different quantization plan"):
        stream_quantize(source, out, other, resume=True)


def test_resume_refuses_ledger_of_another_pack_layout(source, plan, tmp_path):
    """A ledger whose plan predates the pack-layout tag (its shards hold
    codes in the earlier interleaved layout, with CRCs that still match) is
    refused on resume and by the audit, not decoded in the wrong order."""
    out = str(tmp_path / "run")
    faults = FaultPlan(0, {"ptq.kill_at_block": {"at": (1,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(source, out, plan, faults=faults)
    path = os.path.join(out, "ledger.json")
    with open(path) as f:
        data = json.load(f)
    assert data["plan"].pop("pack_layout") == "planes"
    with open(path, "w") as f:
        json.dump(data, f)
    with pytest.raises(ValueError, match="different quantization plan"):
        stream_quantize(source, out, plan, resume=True)
    aud = audit_artifact(out, source, plan)
    assert not aud["clean"] and "different quantization plan" in aud["reason"]


# ---------------------------------------------------------------------------
# transient IO + shard write protocol
# ---------------------------------------------------------------------------


def test_transient_oserror_is_retried_to_completion(source, plan, reference,
                                                    tmp_path):
    _, _, shards = reference
    out = str(tmp_path / "run")
    faults = FaultPlan(0, {"ptq.transient_oserror": {"at": (0, 2)}})
    s = stream_quantize(source, out, plan, faults=faults)
    assert s["status"] == "complete"
    assert faults.fired("ptq.transient_oserror") == 2
    _assert_identical(shards, out)


def test_write_shard_crc_matches_disk_content(tmp_path):
    tree = {"up/q": np.arange(24, dtype=np.uint8).reshape(4, 6),
            "up/b": np.linspace(-1, 1, 8, dtype=np.float32).reshape(4, 2)}
    name, crc = write_shard(str(tmp_path), 0, tree)
    got = read_shard(str(tmp_path / name))
    crc2 = 0
    for k in sorted(got):
        import zlib

        crc2 = zlib.crc32(k.encode(), crc2)
        crc2 = digest_array(got[k], crc2)
    assert crc == crc2


def test_digest_array_separates_dtype_and_shape():
    a = np.zeros(8, np.float32)
    assert digest_array(a) != digest_array(a.astype(np.int32))
    assert digest_array(a) != digest_array(a.reshape(2, 4))


# ---------------------------------------------------------------------------
# memory budget watchdog
# ---------------------------------------------------------------------------


def test_budget_watchdog_diagnostic_lists_charges():
    b = MemoryBudget(100)
    b.charge("x", 60)
    with pytest.raises(MemoryBudgetExceeded) as e:
        b.charge("y", 50)
    msg = str(e.value)
    assert "x=60" in msg and "y=50" in msg and "110 > 100" in msg


def test_budget_peak_and_release():
    b = MemoryBudget(None)
    b.charge("a", 10)
    with b.hold("t", 90):
        pass
    b.release("a")
    assert b.peak == 100 and b.live() == {}


def test_stream_under_budget_smaller_than_dense(tmp_path):
    src = ResidualMLPSource.create(str(tmp_path / "m"), num_blocks=6, d=48,
                                   d_ff=64, tokens=16, seed=1)
    plan = StreamPlan(block_size=16, rank=3, refine_steps=6,
                      memory_budget=int(src.dense_bytes() * 0.9))
    s = stream_quantize(src, str(tmp_path / "out"), plan)
    assert s["status"] == "complete"
    assert s["peak_bytes"] <= plan.memory_budget < src.dense_bytes()


def test_impossible_budget_fails_fast_with_diagnostic(source, tmp_path):
    plan = StreamPlan(block_size=16, rank=3, refine_steps=6,
                      memory_budget=1024)
    with pytest.raises(MemoryBudgetExceeded, match="live charges"):
        stream_quantize(source, str(tmp_path / "out"), plan)


def test_oom_spike_trips_watchdog_then_resumes_identical(
        source, reference, tmp_path):
    _, _, shards = reference
    plan_b = StreamPlan(block_size=16, rank=3, refine_steps=6,
                        memory_budget=1 << 20)
    out = str(tmp_path / "run")
    faults = FaultPlan(0, {"ptq.oom_spike": {"at": (5,)}})
    with pytest.raises(MemoryBudgetExceeded, match="oom_spike"):
        stream_quantize(source, out, plan_b, faults=faults)
    s = stream_quantize(source, out, plan_b, resume=True)
    assert s["status"] == "complete"
    _assert_identical(shards, out)


# ---------------------------------------------------------------------------
# preemption + pre-transforms
# ---------------------------------------------------------------------------


class _Guard:
    def __init__(self, after):
        self.n = 0
        self.after = after

    @property
    def preempted(self):
        self.n += 1
        return self.n > self.after


def test_preemption_stops_gracefully_then_resumes(source, plan, reference,
                                                  tmp_path):
    _, _, shards = reference
    out = str(tmp_path / "run")
    s = stream_quantize(source, out, plan, guard=_Guard(after=1))
    assert s["status"] == "preempted"
    assert 0 < s["blocks_done"] < N_BLOCKS
    led = Ledger(out)
    assert led.load() and led.status == "in_progress"
    s = stream_quantize(source, out, plan, resume=True)
    assert s["status"] == "complete"
    _assert_identical(shards, out)


@pytest.mark.parametrize("pre", ["smooth", "smoothrot"])
def test_pretransforms_stream_and_resume_bit_identical(source, tmp_path, pre):
    plan = StreamPlan(block_size=16, rank=3, refine_steps=6, pretransform=pre)
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    stream_quantize(source, a, plan)
    if pre == "smoothrot":  # rotation artifacts carry the basis change
        tree = read_shard(os.path.join(a, shard_name(0)))
        assert "up/c" in tree and "up/signs" in tree
    faults = FaultPlan(0, {"ptq.kill_mid_write": {"at": (1,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(source, b, plan, faults=faults)
    stream_quantize(source, b, plan, resume=True)
    ref = [read_shard(os.path.join(a, shard_name(i)))
           for i in range(N_BLOCKS)]
    _assert_identical(ref, b)
    assert audit_artifact(b, source, plan)["clean"]


def test_changed_calibration_invalidates_whole_chain(plan, tmp_path):
    """Same weights, different calibration seed -> fingerprint mismatch
    (the ledger refuses silently mixing two calibration histories)."""
    a = ResidualMLPSource.create(str(tmp_path / "m"), num_blocks=2, d=48,
                                 d_ff=64, tokens=16, seed=3)
    out = str(tmp_path / "out")
    faults = FaultPlan(0, {"ptq.kill_at_block": {"at": (1,)}})
    with pytest.raises(InjectedFault):
        stream_quantize(a, out, plan, faults=faults)
    meta = json.load(open(os.path.join(str(tmp_path / "m"), "source.json")))
    meta["seed"] = 4
    json.dump(meta, open(os.path.join(str(tmp_path / "m"), "source.json"),
                         "w"))
    b = ResidualMLPSource(str(tmp_path / "m"))
    with pytest.raises(ValueError, match="different model/source"):
        stream_quantize(b, out, plan, resume=True)
