"""Streaming-PTQ chaos benchmark — resume parity at every block boundary.

The ``bench_chaos`` pattern applied to the quantization pipeline: run the
layer-streaming PTQ once clean, then re-run it under injected faults and
*assert* the crash-safety contract instead of just recording numbers:

  * **boundary sweep** — for *every* block boundary b, kill a fresh run at
    b, resume it, and require (i) the resumed artifact is bit-identical to
    the clean run's shards, (ii) blocks < b were reused (never recomputed),
    and (iii) the post-resume ledger/checksum audit is clean;
  * **mid-write / pre-commit kills** — the same contract when the kill
    lands inside a shard write (stray temp file) or between a published
    shard and its ledger entry (un-journaled work is re-done, to the same
    bytes);
  * **bitrot** — a corrupted published shard is detected by the resume
    audit and exactly that block is recomputed;
  * **memory watchdog** — an injected allocation spike trips
    :class:`MemoryBudgetExceeded` (fail fast, diagnosable), and the run
    still resumes to the identical artifact afterwards.

  * **sharded drill** (forced 8 host devices, run in a subprocess so the
    device count can be forced before jax initializes) — the data-parallel
    sharded pipeline killed at *every* block boundary resumes bit-identical
    to the uninterrupted **single-host** run, including once across a mesh
    shrink (killed on 2×4, resumed on 1×4, and once resumed with no mesh at
    all): the canonical chunked math makes the mesh pure placement, so
    bytes never depend on the device count — not even across a crash.

Writes ``BENCH_ptq_stream.json`` with the scenario records and the peak
streaming footprint vs the dense model size.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

import benchmarks.common  # noqa: F401  (sets REPRO_CPU_EXEC before jax use)

from repro.ptq_stream import (
    MemoryBudgetExceeded,
    ResidualMLPSource,
    StreamPlan,
    audit_artifact,
    read_shard,
    stream_quantize,
)
from repro.ptq_stream.shards import shard_name
from repro.robustness import FaultPlan, InjectedFault

_MODEL = dict(num_blocks=4, d=64, d_ff=128, tokens=32, seed=0)


def _shards(directory: str, n: int) -> list[dict]:
    return [read_shard(os.path.join(directory, shard_name(i)))
            for i in range(n)]


def _identical(ref: list[dict], directory: str) -> bool:
    got = _shards(directory, len(ref))
    return all(
        sorted(a) == sorted(b) and all(np.array_equal(a[k], b[k]) for k in a)
        for a, b in zip(ref, got))


def _expect_kill(src, out, plan, faults):
    try:
        stream_quantize(src, out, plan, faults=faults)
    except InjectedFault:
        return True
    return False


def run_scenarios(root: str) -> dict:
    src = ResidualMLPSource.create(os.path.join(root, "model"), **_MODEL)
    plan = StreamPlan(block_size=32, rank=4, refine_steps=10)
    n = src.num_blocks

    clean_dir = os.path.join(root, "clean")
    clean = stream_quantize(src, clean_dir, plan)
    assert clean["status"] == "complete", clean
    ref = _shards(clean_dir, n)
    results = {"clean": {"peak_bytes": clean["peak_bytes"],
                         "dense_bytes": src.dense_bytes(),
                         "wall_s": clean["wall_s"]},
               "boundary_sweep": [], "scenarios": {}}

    # -- kill + resume at EVERY block boundary ------------------------------
    for b in range(n):
        out = os.path.join(root, f"kill_b{b}")
        faults = FaultPlan(b, {"ptq.kill_at_block": {"at": (b,)}})
        assert _expect_kill(src, out, plan, faults), f"kill at {b} never fired"
        s = stream_quantize(src, out, plan, resume=True)
        rec = {"boundary": b, "reused": s["reused"],
               "recomputed": s["recomputed"],
               "bit_identical": _identical(ref, out),
               "audit_clean": audit_artifact(out, src, plan)["clean"]}
        assert rec["bit_identical"], f"boundary {b}: artifact diverged"
        assert rec["audit_clean"], f"boundary {b}: dirty audit"
        assert s["reused"] == b, (
            f"boundary {b}: expected {b} reused blocks, got {s['reused']}")
        results["boundary_sweep"].append(rec)

    # -- kill inside the shard write / before the ledger commit -------------
    for name, point in [("mid_write", "ptq.kill_mid_write"),
                        ("pre_commit", "ptq.kill_before_commit")]:
        out = os.path.join(root, name)
        faults = FaultPlan(7, {point: {"at": (n // 2,)}})
        assert _expect_kill(src, out, plan, faults), f"{name} never fired"
        s = stream_quantize(src, out, plan, resume=True)
        rec = {"reused": s["reused"], "recomputed": s["recomputed"],
               "stray_tmp_removed": s["stray_tmp_removed"],
               "bit_identical": _identical(ref, out),
               "audit_clean": audit_artifact(out, src, plan)["clean"]}
        assert rec["bit_identical"] and rec["audit_clean"], (name, rec)
        results["scenarios"][name] = rec

    # -- bitrot on a published shard ----------------------------------------
    out = os.path.join(root, "bitrot")
    faults = FaultPlan(3, {"ptq.corrupt_shard": {"at": (1,)},
                           "ptq.kill_at_block": {"at": (n - 1,)}})
    assert _expect_kill(src, out, plan, faults)
    pre = audit_artifact(out, src, plan)
    s = stream_quantize(src, out, plan, resume=True)
    rec = {"audit_caught_corruption": not pre["clean"],
           "recomputed": s["recomputed"],
           "bit_identical": _identical(ref, out),
           "audit_clean": audit_artifact(out, src, plan)["clean"]}
    assert rec["audit_caught_corruption"], "bitrot escaped the audit"
    assert 1 in rec["recomputed"], rec
    assert rec["bit_identical"] and rec["audit_clean"], rec
    results["scenarios"]["bitrot"] = rec

    # -- injected memory spike trips the watchdog, run still resumes --------
    out = os.path.join(root, "oom")
    budget = int(clean["peak_bytes"] * 1.2)
    plan_b = StreamPlan(block_size=32, rank=4, refine_steps=10,
                        memory_budget=budget)
    oom_raised = False
    try:
        stream_quantize(src, out, plan_b,
                        faults=FaultPlan(5, {"ptq.oom_spike": {"at": (9,)}}))
    except MemoryBudgetExceeded as e:
        oom_raised = "live charges" in str(e)
    s = stream_quantize(src, out, plan_b, resume=True)
    rec = {"oom_diagnostic": oom_raised, "budget": budget,
           "peak_bytes": s["peak_bytes"],
           "bit_identical": _identical(ref, out)}
    assert rec["oom_diagnostic"], "oom spike produced no diagnostic"
    assert rec["bit_identical"], rec
    results["scenarios"]["oom_spike"] = rec
    return results


def dist_drill(root: str) -> dict:
    """Forced-8-device sharded kill/resume/mesh-shrink drill (see module
    docstring).  Must run in a process whose jax sees >= 8 devices."""
    import jax

    from repro.launch.mesh import make_host_mesh

    if jax.device_count() < 8:
        raise RuntimeError(
            f"dist drill needs 8 devices, found {jax.device_count()} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before the first jax import")
    src = ResidualMLPSource.create(os.path.join(root, "model"), **_MODEL)
    plan = StreamPlan(block_size=32, rank=4, refine_steps=10)
    n = src.num_blocks

    # the oracle is the *single-host* run: every sharded variant below must
    # reproduce these bytes exactly
    clean_dir = os.path.join(root, "clean_single")
    clean = stream_quantize(src, clean_dir, plan)
    assert clean["status"] == "complete", clean
    ref = _shards(clean_dir, n)

    full = os.path.join(root, "sharded_full")
    s = stream_quantize(src, full, plan, mesh=make_host_mesh(data=2, model=4))
    assert s["status"] == "complete" and _identical(ref, full), (
        "uninterrupted sharded run diverged from single-host bytes")
    results = {"devices": jax.device_count(), "sharded_parity": True,
               "boundary_sweep": [], "mesh_shrink": {}}

    # kill the 2x4 sharded run at EVERY block boundary; resume on the same
    # mesh — bytes must match the single-host oracle and prefixes reuse
    for b in range(n):
        out = os.path.join(root, f"dist_kill_b{b}")
        faults = FaultPlan(b, {"ptq.kill_at_block": {"at": (b,)}})
        killed = False
        try:
            stream_quantize(src, out, plan, faults=faults,
                            mesh=make_host_mesh(data=2, model=4))
        except InjectedFault:
            killed = True
        assert killed, f"dist kill at {b} never fired"
        s = stream_quantize(src, out, plan, resume=True,
                            mesh=make_host_mesh(data=2, model=4))
        rec = {"boundary": b, "reused": s["reused"],
               "recomputed": s["recomputed"],
               "bit_identical": _identical(ref, out),
               "audit_clean": audit_artifact(out, src, plan)["clean"]}
        assert rec["bit_identical"], f"dist boundary {b}: bytes diverged"
        assert rec["audit_clean"], f"dist boundary {b}: dirty audit"
        assert s["reused"] == b, (b, s["reused"])
        results["boundary_sweep"].append(rec)

    # mid-mesh-shrink: killed on 2x4, resumed on 1x4 (half the devices
    # gone), then a second drill resumed with no mesh at all — a crash plus
    # an elastic reshard still lands on the oracle bytes
    for name, resume_mesh in (("to_1x4", make_host_mesh(data=1, model=4)),
                              ("to_single", None)):
        out = os.path.join(root, f"shrink_{name}")
        faults = FaultPlan(17, {"ptq.kill_at_block": {"at": (n // 2,)}})
        killed = False
        try:
            stream_quantize(src, out, plan, faults=faults,
                            mesh=make_host_mesh(data=2, model=4))
        except InjectedFault:
            killed = True
        assert killed
        s = stream_quantize(src, out, plan, resume=True, mesh=resume_mesh)
        rec = {"reused": s["reused"], "recomputed": s["recomputed"],
               "bit_identical": _identical(ref, out),
               "audit_clean": audit_artifact(out, src, plan)["clean"]}
        assert rec["bit_identical"], f"mesh shrink {name}: bytes diverged"
        assert rec["audit_clean"] and s["reused"] == n // 2, (name, rec)
        results["mesh_shrink"][name] = rec
    return results


def dist_drill_subprocess() -> dict:
    """Run :func:`dist_drill` in a child process with 8 forced host devices
    (the parent's jax is already initialized with 1)."""
    # a host-device drill: the child stays on the CPU even where the parent
    # holds an accelerator (a second process cannot share the chip)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    with tempfile.TemporaryDirectory() as root:
        out_json = os.path.join(root, "dist_drill.json")
        subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_ptq_stream",
             "--dist-drill", root, "--json", out_json],
            env=env, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        with open(out_json) as f:
            return json.load(f)


def run(report):
    """benchmarks.run entry point -> BENCH_ptq_stream.json."""
    with tempfile.TemporaryDirectory() as root:
        results = run_scenarios(root)
    results["dist_drill"] = dist_drill_subprocess()
    c = results["clean"]
    report("ptq_stream/clean", c["wall_s"] * 1e6,
           f"peak_bytes={c['peak_bytes']} dense_bytes={c['dense_bytes']}")
    for rec in results["boundary_sweep"]:
        report(f"ptq_stream/kill_b{rec['boundary']}", 0.0,
               f"reused={rec['reused']} redone={len(rec['recomputed'])} "
               f"bit_identical={rec['bit_identical']}")
    for name, rec in results["scenarios"].items():
        report(f"ptq_stream/{name}", 0.0,
               f"bit_identical={rec['bit_identical']}")
    dd = results["dist_drill"]
    report("ptq_stream/dist_drill", 0.0,
           f"devices={dd['devices']} sharded_parity={dd['sharded_parity']} "
           f"boundaries={len(dd['boundary_sweep'])} "
           f"all_bit_identical="
           f"{all(r['bit_identical'] for r in dd['boundary_sweep'])} "
           f"mesh_shrink_ok="
           f"{all(r['bit_identical'] for r in dd['mesh_shrink'].values())}")
    with open("BENCH_ptq_stream.json", "w") as f:
        json.dump(results, f, indent=1)
    report("ptq_stream/json", 0.0, "wrote BENCH_ptq_stream.json")


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--dist-drill", default=None, metavar="ROOT",
                    help="run only the forced-8-device sharded drill into "
                         "ROOT (needs XLA_FLAGS host device forcing)")
    ap.add_argument("--json", default=None,
                    help="with --dist-drill: write the drill record here")
    args = ap.parse_args(argv)
    if args.dist_drill is not None:
        results = dist_drill(args.dist_drill)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(results, f, indent=1)
        print(f"[bench_ptq_stream] dist drill: {len(results['boundary_sweep'])}"
              f" boundaries + {len(results['mesh_shrink'])} mesh-shrink "
              "resumes, all bit-identical to the single-host run")
        return

    def _p(name, us, derived):
        print(f"{name},{us:.1f},{derived}")
    run(_p)


if __name__ == "__main__":
    main()
