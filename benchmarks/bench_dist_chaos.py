"""Distributed chaos benchmark — elastic recovery drills (PR 10).

Everything here runs under a forced-8-device host mesh in a *subprocess*
(the device count must be set before jax initializes), and every scenario
**self-asserts** its recovery invariant before any number is reported —
the emitted ``BENCH_dist_chaos.json`` is a proof-of-recovery artifact, not
a scoreboard:

  * **train/device_loss** — an injected ``dist.device_loss`` mid-run
    rebuilds a smaller host mesh (2×4 → 1×4), elastically restores from
    the latest checkpoint, reseeks the data iterator, and finishes; the
    final loss must land within tolerance of the fault-free run.
  * **train/desync** — a per-replica digest divergence injected at the
    comparison point is detected within one ``desync_every`` interval and
    rolled back to the latest checkpoint; the run still completes.
  * **train/host_crash** — ``dist.host_crash`` kills the run with no
    graceful save; a fresh ``run_training`` on the same ``ckpt_dir``
    resumes from the latest checkpoint and completes.
  * **engine/device_loss** — the serving engine absorbs a device loss via
    elastic mesh rebuild + param reshard + full recompute, and its output
    tokens stay **bit-identical** to the single-mesh run.
  * **engine/collective_timeout + straggler** — injected collective
    timeouts ride the retry/requeue path; per-shard straggler injections
    are flagged by the watchdog in ``stats['straggler_flags']``.
  * **ptq/sharded kill+resume** — the data-parallel streaming PTQ killed
    at a block boundary and resumed across a mesh shrink reproduces the
    single-host bytes exactly (the full boundary sweep lives in
    ``bench_ptq_stream``; this drill repeats the crash-plus-shrink case so
    the dist-chaos artifact is self-contained).

Run directly (``python -m benchmarks.bench_dist_chaos``) or through the
registry (``python -m benchmarks.run dist_chaos``); either way the parent
process only orchestrates and the asserting child writes
``BENCH_dist_chaos.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

_TRAIN_STEPS = 6


def _drills(root: str) -> dict:
    """The in-child body: every scenario asserts its invariant."""
    import jax
    import numpy as np

    from repro.configs import ShapeCfg, get_config, smoke_variant
    from repro.launch.engine import Engine, Request
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import run_training
    from repro.models import model_init, split_tree
    from repro.ptq_stream import (
        ResidualMLPSource,
        StreamPlan,
        audit_artifact,
        read_shard,
        stream_quantize,
    )
    from repro.ptq_stream.shards import shard_name
    from repro.robustness import FaultPlan, InjectedFault

    assert jax.device_count() >= 8, (
        f"dist chaos needs 8 forced devices, found {jax.device_count()}")
    results: dict = {"devices": jax.device_count(), "invariants": []}

    def invariant(name: str, ok: bool, detail: str):
        results["invariants"].append(
            {"name": name, "ok": bool(ok), "detail": detail})
        assert ok, f"invariant violated: {name} — {detail}"

    # ---- training ---------------------------------------------------------
    cfg = smoke_variant(get_config("llama3-8b")).with_(num_layers=2,
                                                       d_model=64)
    shape = ShapeCfg("t", 32, 4, "train")
    ref = run_training(cfg, shape, steps=_TRAIN_STEPS, lr=1e-3,
                       log_every=1000)
    ref_loss = float(ref["losses"][-1])

    out = run_training(cfg, shape, steps=_TRAIN_STEPS, lr=1e-3,
                       log_every=1000, mesh=make_host_mesh(data=2, model=4),
                       faults=FaultPlan(0, {"dist.device_loss": {"at": (3,)}}),
                       ckpt_dir=os.path.join(root, "ck_dl"), ckpt_every=2)
    loss = float(out["losses"][-1])
    tol = 0.15 * abs(ref_loss) + 0.05
    invariant(
        "train_device_loss_elastic_restore",
        out["status"] == "complete" and out["mesh_rebuilds"] == 1
        and out["resharded_restores"] == 1 and abs(loss - ref_loss) <= tol,
        f"status={out['status']} rebuilds={out['mesh_rebuilds']} "
        f"restores={out['resharded_restores']} final_mesh="
        f"{out['final_mesh']} loss={loss:.4f} vs fault-free {ref_loss:.4f} "
        f"(tol {tol:.4f})")
    results["train_device_loss"] = {
        "mesh_rebuilds": out["mesh_rebuilds"],
        "lost_devices": out["lost_devices"],
        "resharded_restores": out["resharded_restores"],
        "final_mesh": out["final_mesh"], "loss": loss, "ref_loss": ref_loss}

    out = run_training(
        cfg, shape, steps=_TRAIN_STEPS, lr=1e-3, log_every=1000,
        mesh=make_host_mesh(data=2, model=4), desync_every=2,
        faults=FaultPlan(0, {"dist.replica_desync":
                             {"prob": 1.0, "max_fires": 1, "only_index": 1}}),
        ckpt_dir=os.path.join(root, "ck_ds"), ckpt_every=1)
    invariant(
        "train_desync_detected_and_rolled_back",
        out["status"] == "complete" and out["desyncs_detected"] == 1
        and out["desync_rollbacks"] == 1,
        f"status={out['status']} detected={out['desyncs_detected']} "
        f"rollbacks={out['desync_rollbacks']} (interval=2 steps)")
    results["train_desync"] = {"detected": out["desyncs_detected"],
                               "rollbacks": out["desync_rollbacks"]}

    ck_hc = os.path.join(root, "ck_hc")
    crashed = False
    try:
        run_training(cfg, shape, steps=_TRAIN_STEPS, lr=1e-3, log_every=1000,
                     ckpt_dir=ck_hc, ckpt_every=2,
                     faults=FaultPlan(0, {"dist.host_crash": {"at": (3,)}}))
    except InjectedFault:
        crashed = True
    out = run_training(cfg, shape, steps=_TRAIN_STEPS, lr=1e-3,
                       log_every=1000, ckpt_dir=ck_hc, ckpt_every=2)
    invariant(
        "train_host_crash_resume",
        crashed and out["status"] == "complete",
        f"crashed={crashed} resume_status={out['status']} "
        f"resume_losses={len(out['losses'])}")
    results["train_host_crash"] = {"resumed_losses": len(out["losses"])}

    # ---- engine -----------------------------------------------------------
    ecfg = smoke_variant(get_config("llama3-8b")).with_(
        num_layers=2, d_model=64, kv_cache_dtype="int8")
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), ecfg))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, ecfg.vocab_size, (p,)).astype(np.int32)
               for p in (10, 6, 13)]
    geom = dict(slots=2, total_pages=12, page_size=8, max_pages=4, chunk=16,
                burst=4, kernel_backend="interpret", params=params)

    def reqs():
        return [Request(rid=i, tokens=p, max_new=5, arrival=0.0)
                for i, p in enumerate(prompts)]

    base = Engine(ecfg, **geom).run(reqs(), timeout_s=600)
    base_toks = {r["rid"]: r["tokens"] for r in base["records"]}

    eng = Engine(ecfg, mesh=make_host_mesh(data=2, model=4),
                 faults=FaultPlan(0, {"dist.device_loss": {"at": (3,)}}),
                 **geom)
    st = eng.run(reqs(), timeout_s=600)
    toks = {r["rid"]: r["tokens"] for r in st["records"]}
    invariant(
        "engine_device_loss_tokens_bit_identical",
        st["all_completed"] and st["mesh_rebuilds"] == 1
        and st["page_audit"]["ok"] and toks == base_toks,
        f"statuses={st['statuses']} rebuilds={st['mesh_rebuilds']} "
        f"lost={st['lost_devices']} audit_ok={st['page_audit']['ok']} "
        f"identical={toks == base_toks}")
    results["engine_device_loss"] = {
        "mesh_rebuilds": st["mesh_rebuilds"],
        "lost_devices": st["lost_devices"],
        "resharded_restores": st["resharded_restores"]}

    st = Engine(ecfg, faults=FaultPlan(
        0, {"dist.collective_timeout": {"at": (1,)},
            "dist.straggler": {"prob": 0.3, "delay_s": 0.05,
                               "max_fires": 3}}), **geom
                ).run(reqs(), timeout_s=600)
    toks = {r["rid"]: r["tokens"] for r in st["records"]}
    injected_flags = [f for f in st["straggler_flags"] if f["injected"]]
    invariant(
        "engine_collective_timeout_and_straggler",
        st["all_completed"] and st["collective_timeouts"] == 1
        and bool(injected_flags) and toks == base_toks,
        f"collective_timeouts={st['collective_timeouts']} "
        f"straggler_flags={len(injected_flags)} identical={toks == base_toks}")
    results["engine_faults"] = {
        "collective_timeouts": st["collective_timeouts"],
        "straggler_flags": len(injected_flags)}

    # ---- sharded streaming PTQ: crash + mesh shrink ----------------------
    src = ResidualMLPSource.create(os.path.join(root, "ptq_model"),
                                   num_blocks=4, d=64, d_ff=128, tokens=32,
                                   seed=0)
    plan = StreamPlan(block_size=32, rank=4, refine_steps=10)
    ref_dir = os.path.join(root, "ptq_single")
    stream_quantize(src, ref_dir, plan)
    out_dir = os.path.join(root, "ptq_sharded")
    killed = False
    try:
        stream_quantize(src, out_dir, plan,
                        faults=FaultPlan(17, {"ptq.kill_at_block":
                                              {"at": (2,)}}),
                        mesh=make_host_mesh(data=2, model=4))
    except InjectedFault:
        killed = True
    s = stream_quantize(src, out_dir, plan, resume=True,
                        mesh=make_host_mesh(data=1, model=4))
    identical = all(
        all(np.array_equal(a[k], b[k]) for k in a)
        for a, b in ((read_shard(os.path.join(ref_dir, shard_name(i))),
                      read_shard(os.path.join(out_dir, shard_name(i))))
                     for i in range(src.num_blocks)))
    invariant(
        "ptq_sharded_kill_mesh_shrink_bit_identical",
        killed and s["status"] == "complete" and s["reused"] == 2
        and identical and audit_artifact(out_dir, src, plan)["clean"],
        f"killed={killed} status={s['status']} reused={s['reused']} "
        f"bit_identical={identical} (killed on 2x4, resumed on 1x4, "
        "oracle = single host)")
    results["ptq_sharded"] = {"reused": s["reused"],
                              "recomputed": s["recomputed"],
                              "bit_identical": identical}
    return results


def child_main(argv):
    root, out_json = argv
    results = _drills(root)
    with open(out_json, "w") as f:
        json.dump(results, f, indent=1)
    ok = sum(1 for i in results["invariants"] if i["ok"])
    print(f"[bench_dist_chaos] {ok}/{len(results['invariants'])} "
          "recovery invariants hold")


def run_subprocess() -> dict:
    # a host-device drill: the child stays on the CPU even where the parent
    # holds an accelerator (a second process cannot share the chip)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    with tempfile.TemporaryDirectory() as root:
        out_json = os.path.join(root, "dist_chaos.json")
        subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_dist_chaos",
             "--child", root, out_json],
            env=env, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        with open(out_json) as f:
            return json.load(f)


def run(report):
    """benchmarks.run entry point -> BENCH_dist_chaos.json."""
    results = run_subprocess()
    for inv in results["invariants"]:
        report(f"dist_chaos/{inv['name']}", 0.0,
               f"ok={inv['ok']} {inv['detail']}")
    with open("BENCH_dist_chaos.json", "w") as f:
        json.dump(results, f, indent=1)
    report("dist_chaos/json", 0.0,
           f"wrote BENCH_dist_chaos.json ({len(results['invariants'])} "
           "self-asserted invariants)")


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--child"]:
        child_main(argv[1:])
        return

    def _p(name, us, derived):
        print(f"{name},{us:.1f},{derived}")
    run(_p)


if __name__ == "__main__":
    main()
