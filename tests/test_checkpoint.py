"""Checkpointer round-trips — packed-code bit-exactness, retention,
manifest robustness — plus the sharded save/restore layout and the
PreemptionGuard → checkpoint → restore integration path.

Single-device cases run in tier-1; the `multidevice` cases (per-shard
save files, sharded train resume) need the 8-way forced host mesh
(make test-multidevice)."""
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from multidevice_compat import dp_tp_mesh, multidevice, single_mesh, tp_mesh
from repro.checkpoint import Checkpointer
from repro.configs import ShapeCfg, get_config, smoke_variant
from repro.distributed.fault_tolerance import PreemptionGuard
from repro.launch.train import run_training


def _quant_state(seed=0):
    """A LoRDS-shaped tree: packed uint8 codes + f32 factors + step."""
    key = jax.random.PRNGKey(seed)
    return {
        "params": {
            "q": jax.random.randint(key, (64, 16), 0, 255).astype(jnp.uint8),
            "b": jax.random.normal(key, (64, 3)),
            "a": jax.random.normal(key, (3, 32)),
            "emb": jax.random.normal(key, (8, 4), jnp.bfloat16),
        },
        "data_step": 7,
    }


# ---------------------------------------------------------------------------
# single-device round-trips
# ---------------------------------------------------------------------------


def test_packed_codes_roundtrip_bit_exact(tmp_path):
    ck = Checkpointer(str(tmp_path))
    state = _quant_state()
    ck.save(3, state)
    r = ck.restore(state)
    np.testing.assert_array_equal(np.asarray(r["params"]["q"]),
                                  np.asarray(state["params"]["q"]))
    assert np.asarray(r["params"]["q"]).dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(r["params"]["b"]),
                                  np.asarray(state["params"]["b"]))
    assert int(np.asarray(r["data_step"])) == 7


def test_bf16_leaves_roundtrip_bit_exact(tmp_path):
    ck = Checkpointer(str(tmp_path))
    state = _quant_state()
    ck.save(1, state)
    r = ck.restore(state)
    got = np.asarray(r["params"]["emb"])
    want = np.asarray(state["params"]["emb"])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint16), want.view(np.uint16))


def test_keep3_gc_prunes_oldest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    for s in (1, 2, 3, 4, 5):
        ck.save(s, _quant_state())
    assert ck.all_steps() == [3, 4, 5]
    assert ck.latest_step() == 5


def test_keep_zero_disables_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=0)
    for s in (1, 2):
        ck.save(s, _quant_state())
    assert ck.all_steps() == [1, 2]


def test_latest_step_survives_corrupt_manifest(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(4, _quant_state())
    with open(tmp_path / "MANIFEST.json", "w") as f:
        f.write("{not json")
    assert ck.latest_step() == 4
    # and restore still works off the recovered step
    assert ck.restore(_quant_state()) is not None


def test_latest_step_partial_manifest_ignores_gcd_steps(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(2, _quant_state())
    with open(tmp_path / "MANIFEST.json", "w") as f:
        json.dump({"steps": [2, 9], "latest": 9}, f)  # 9 never materialized
    assert ck.latest_step() == 2


def test_latest_step_manifest_wrong_type(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(6, _quant_state())
    with open(tmp_path / "MANIFEST.json", "w") as f:
        json.dump([1, 2, 3], f)  # valid JSON, wrong shape
    assert ck.latest_step() == 6


def test_empty_dir_restore_returns_none(tmp_path):
    ck = Checkpointer(str(tmp_path))
    assert ck.latest_step() is None
    assert ck.restore(_quant_state()) is None


def test_v1_layout_read_compat(tmp_path):
    """Checkpoints written by the pre-sharding layout (flat `names` list)
    must keep restoring."""
    state = {"w": jnp.arange(12.0).reshape(3, 4), "data_step": 5}
    leaves, _ = jax.tree_util.tree_flatten(state)
    step_dir = tmp_path / "step_8"
    os.makedirs(step_dir)
    names = []
    for i, leaf in enumerate(leaves):
        name = f"leaf_{i:05d}_p0.npy"
        np.save(step_dir / name, np.asarray(leaf))
        names.append(name)
    with open(step_dir / "spec.json", "w") as f:
        json.dump({"treedef": "legacy", "names": names, "step": 8,
                   "num_leaves": len(names)}, f)
    ck = Checkpointer(str(tmp_path))
    r = ck.restore(state)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(state["w"]))


def test_packed_codes_of_another_layout_are_refused(tmp_path):
    """A checkpoint whose spec records no pack layout (or another one) and
    holds uint8 packed codes is refused; one without codes still restores."""
    ck = Checkpointer(str(tmp_path))
    state = _quant_state()
    ck.save(3, state)
    path = tmp_path / "step_3" / "spec.json"
    spec = json.loads(path.read_text())
    assert spec.pop("pack_layout") == "planes"
    path.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="pack layout None"):
        ck.restore(state)
    floats = {"b": state["params"]["b"], "data_step": 7}
    ck.save(4, floats)
    path = tmp_path / "step_4" / "spec.json"
    spec = json.loads(path.read_text())
    del spec["pack_layout"]
    path.write_text(json.dumps(spec))
    np.testing.assert_array_equal(np.asarray(ck.restore(floats)["b"]),
                                  np.asarray(floats["b"]))


def test_manifest_records_pspecs_unsharded(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _quant_state())
    specs = ck.saved_pspecs()
    assert specs is not None and all(s is None for s in specs)


def test_structure_mismatch_raises(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _quant_state())
    bad = _quant_state()
    bad["params"]["extra"] = jnp.zeros(2)
    with pytest.raises(ValueError):
        ck.restore(bad)


# ---------------------------------------------------------------------------
# preemption-guard integration
# ---------------------------------------------------------------------------


def test_preemption_guard_checkpoint_restore_smoke(tmp_path):
    """The production exit path: SIGTERM flips the guard mid-loop, the loop
    checkpoints and stops, a fresh 'process' restores exactly there."""
    ck = Checkpointer(str(tmp_path))
    guard = PreemptionGuard(signals=(signal.SIGUSR1,))
    try:
        state = _quant_state()
        stopped_at = None
        for step in range(10):
            state["data_step"] = step + 1
            if step == 2:
                os.kill(os.getpid(), signal.SIGUSR1)
            if guard.preempted:
                ck.save(step + 1, state)
                stopped_at = step + 1
                break
        assert stopped_at == 3  # handler runs before the same-step poll
        r = Checkpointer(str(tmp_path)).restore(_quant_state())
        assert int(np.asarray(r["data_step"])) == stopped_at
    finally:
        guard.restore()


# ---------------------------------------------------------------------------
# sharded save/restore (8-way forced host mesh)
# ---------------------------------------------------------------------------


def _sharded_state(mesh):
    row = NamedSharding(mesh, P("model", None))
    rep = NamedSharding(mesh, P())
    s = _quant_state()
    s["params"]["q"] = jax.device_put(s["params"]["q"], row)
    s["params"]["b"] = jax.device_put(s["params"]["b"], row)
    s["params"]["a"] = jax.device_put(s["params"]["a"], rep)
    s["params"]["emb"] = jax.device_put(s["params"]["emb"], rep)
    return s


@multidevice
def test_sharded_save_writes_per_shard_files_and_pspecs(tmp_path):
    mesh = dp_tp_mesh()  # 2×4: codes split 4-way, replicated over data
    state = _sharded_state(mesh)
    ck = Checkpointer(str(tmp_path))
    ck.save(5, state)
    with open(tmp_path / "step_5" / "spec.json") as f:
        spec = json.load(f)
    assert spec["version"] == 2
    sharded = [e for e in spec["leaves"] if e.get("indices")]
    # q and b row-shard 4-way; replication over 'data' must NOT double the
    # shard files (distinct index windows only)
    assert {len(e["files"]) for e in sharded} == {4}
    assert all("'model'" in e["pspec"] for e in sharded)
    reps = [e for e in spec["leaves"] if not e.get("indices")]
    assert reps, "replicated factors should save as single files"


@multidevice
def test_sharded_roundtrip_bit_exact_same_mesh(tmp_path):
    mesh = dp_tp_mesh()
    state = _sharded_state(mesh)
    ck = Checkpointer(str(tmp_path))
    ck.save(1, state)
    sh = jax.tree.map(lambda x: x.sharding, state["params"])
    r = ck.restore(state, shardings={"params": sh,
                                     "data_step": NamedSharding(mesh, P())})
    for k in ("q", "b", "a"):
        np.testing.assert_array_equal(np.asarray(r["params"][k]),
                                      np.asarray(state["params"][k]))
        assert r["params"][k].sharding.spec == state["params"][k].sharding.spec


@multidevice
def test_sharded_elastic_restore_other_mesh(tmp_path):
    """Save on 2×4, restore onto 1×8 (scale-out of the model axis) and onto
    a single device (scale-in) — same bits either way."""
    mesh = dp_tp_mesh()
    state = _sharded_state(mesh)
    ck = Checkpointer(str(tmp_path))
    ck.save(1, state)

    mesh8 = tp_mesh()
    row8 = NamedSharding(mesh8, P("model", None))
    rep8 = NamedSharding(mesh8, P())
    sh8 = {"params": {"q": row8, "b": row8, "a": rep8, "emb": rep8},
           "data_step": rep8}
    r8 = ck.restore(state, shardings=sh8)
    np.testing.assert_array_equal(np.asarray(r8["params"]["q"]),
                                  np.asarray(state["params"]["q"]))
    assert len(r8["params"]["q"].sharding.device_set) == 8

    r1 = ck.restore(state)  # no shardings: reassembled host arrays
    np.testing.assert_array_equal(np.asarray(r1["params"]["q"]),
                                  np.asarray(state["params"]["q"]))


@multidevice
def test_sharded_train_save_restore_resume_bit_exact(tmp_path):
    """The acceptance-criterion path: a data+tensor-parallel PEFT step
    checkpoints sharded (per-shard codes, replicated factors), restores
    onto the same mesh, and the resumed run is bit-exact with an
    uninterrupted one."""
    cfg = smoke_variant(get_config("llama3-8b")).with_(
        num_layers=2, d_model=64)
    shape = ShapeCfg("t", 32, 4, "train")
    mesh = dp_tp_mesh()

    out_a = run_training(cfg, shape, steps=4, lr=1e-3, mesh=mesh,
                         log_every=1000)

    ckdir = str(tmp_path / "ck")
    run_training(cfg, shape, steps=2, lr=1e-3, mesh=mesh, ckpt_dir=ckdir,
                 ckpt_every=2, log_every=1000)
    # the checkpoint itself must be sharded: some leaf saved as >1 file
    ck = Checkpointer(ckdir)
    specs = ck.saved_pspecs()
    assert any(s and "'model'" in s for s in specs), specs
    out_b = run_training(cfg, shape, steps=2, lr=1e-3, mesh=mesh,
                         ckpt_dir=ckdir, ckpt_every=100, log_every=1000)

    la = jax.tree.leaves(out_a["trainable"])
    lb = jax.tree.leaves(out_b["trainable"])
    assert la and len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# ---------------------------------------------------------------------------
# transient-IO retry (PR 7): every save/load file op runs behind
# retry_on_transient — flaky filesystems don't kill runs, permanent
# failures still raise after the bounded budget
# ---------------------------------------------------------------------------


class _FlakyIO:
    """np.save stand-in that raises OSError for the first ``n`` calls."""

    def __init__(self, n):
        self.remaining = n
        self.calls = 0
        self._real = np.save

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if self.remaining > 0:
            self.remaining -= 1
            raise OSError("simulated transient IO failure")
        return self._real(*args, **kwargs)


def test_save_retries_transient_oserror(tmp_path, monkeypatch):
    ck = Checkpointer(str(tmp_path), io_retries=2, io_backoff=0.0)
    state = _quant_state()
    flaky = _FlakyIO(2)
    monkeypatch.setattr(np, "save", flaky)
    ck.save(1, state)          # 2 transient failures absorbed by retries
    monkeypatch.undo()
    assert flaky.remaining == 0 and flaky.calls > 2
    r = ck.restore(jax.tree.map(np.zeros_like, state))
    np.testing.assert_array_equal(np.asarray(r["params"]["q"]),
                                  np.asarray(state["params"]["q"]))
    np.testing.assert_array_equal(np.asarray(r["params"]["b"]),
                                  np.asarray(state["params"]["b"]))


def test_load_retries_transient_oserror(tmp_path, monkeypatch):
    ck = Checkpointer(str(tmp_path), io_retries=2, io_backoff=0.0)
    state = _quant_state()
    ck.save(3, state)
    real_load = np.load
    fails = {"n": 2}

    def flaky_load(*args, **kwargs):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("simulated transient read failure")
        return real_load(*args, **kwargs)

    monkeypatch.setattr(np, "load", flaky_load)
    r = ck.restore(jax.tree.map(np.zeros_like, state))
    monkeypatch.undo()
    assert fails["n"] == 0
    np.testing.assert_array_equal(np.asarray(r["params"]["q"]),
                                  np.asarray(state["params"]["q"]))


def test_save_raises_after_retry_budget(tmp_path, monkeypatch):
    """Permanent IO failure: the bounded retry budget is spent, the error
    propagates, and no committed checkpoint appears (atomicity holds —
    the tmp dir never got renamed into place)."""
    ck = Checkpointer(str(tmp_path), io_retries=1, io_backoff=0.0)
    flaky = _FlakyIO(10**6)
    monkeypatch.setattr(np, "save", flaky)
    with pytest.raises(OSError, match="transient"):
        ck.save(1, _quant_state())
    monkeypatch.undo()
    assert flaky.calls == 2        # first try + io_retries=1
    assert ck.latest_step() is None


def test_kill_mid_save_keeps_previous_checkpoint_restorable(tmp_path):
    """An injected crash partway through ``save`` (ckpt.save_crash, fired
    mid-leaf-loop) must leave the previous step as ``latest_step()`` and
    fully restorable — the atomic tmp-dir protocol never exposes a torn
    checkpoint."""
    from repro.robustness import FaultPlan, InjectedFault

    state = _quant_state()
    faults = FaultPlan(0, {"ckpt.save_crash": {"at": (6,)}})  # 2nd save,
    ck = Checkpointer(str(tmp_path), faults=faults)           # leaf 2 of 5
    ck.save(1, state)
    with pytest.raises(InjectedFault):
        ck.save(2, _quant_state(seed=1))
    assert ck.latest_step() == 1
    r = ck.restore(state)
    np.testing.assert_array_equal(np.asarray(r["params"]["q"]),
                                  np.asarray(state["params"]["q"]))
    # the half-written attempt is only a .tmp dir; a retried save wins
    assert os.path.isdir(str(tmp_path / "step_2.tmp"))
    ck.save(2, _quant_state(seed=1))
    assert ck.latest_step() == 2
    assert ck.restore(state, step=2) is not None
