"""End-to-end training driver (PEFT / QAT / full) with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --steps 300 \
        --d-model 256 --layers 4 ...   # reduced dims for CPU runs

Production path: real mesh via ``make_production_mesh``, checkpoint/restore
via ``repro.checkpoint``, preemption-safe, straggler-monitored, deterministic
restartable data pipeline.  On a CPU host it runs reduced configs end to
end (examples/finetune_peft.py drives a ~100M-param model this way).
"""
from __future__ import annotations

import argparse
import os
import time

import jax

if jax.default_backend() == "cpu":
    os.environ.setdefault("REPRO_CPU_EXEC", "1")
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import Checkpointer
from repro.configs import SHAPES, ShapeCfg, get_config, smoke_variant
from repro.core import peft
from repro.data import SyntheticLM, make_batch_iterator
from repro.distributed.desync import desync_spread, replica_digests
from repro.distributed.fault_tolerance import PreemptionGuard, StragglerMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import build_plan
from repro.models import model_init, split_tree
from repro.optim import adamw_init


def run_training(cfg, shape_cfg, *, steps: int, lr: float = 1e-4,
                 ckpt_dir: str | None = None, ckpt_every: int = 50,
                 mesh=None, seed: int = 0, log_every: int = 10,
                 num_microbatches: int | None = None,
                 kernel_backend: str | None = None,
                 faults=None, grad_guard: bool = True,
                 rollback_after: int = 3, spike_factor: float = 10.0,
                 spike_warmup: int = 10, desync_every: int = 0,
                 max_mesh_rebuilds: int = 4, collective_retries: int = 2,
                 io_retries: int = 2, io_backoff: float = 0.05,
                 io_jitter: float = 0.0) -> dict:
    """Train ``cfg`` for ``steps``; returns final metrics + loss history.

    ``kernel_backend`` pins the quantized-matmul dispatch backend for the
    whole step — forward *and* backward: on the fused backends
    (pallas/interpret) QAT and PEFT steps run the fused custom-VJP kernels
    end to end and never materialize Ŵ (None = ambient default).

    ``mesh`` may be a real data×tensor-parallel mesh: the step then runs
    sharded (codes + B rows over 'model', dB/dA psum-reduced by the fused
    VJPs), checkpoints save per-shard, and restore resharding onto the
    plan's NamedShardings keeps resume bit-exact.

    **Hardening** (``grad_guard=True``): every update runs through
    :func:`repro.optim.guarded_update` behind a per-step spike threshold —
    ``spike_factor`` × an EMA of accepted grad norms (disabled for the
    first ``spike_warmup`` accepted steps).  A non-finite or spiking
    gradient *skips* the update in-graph (params + optimizer state
    untouched, counted in ``skipped_steps``); after ``rollback_after``
    consecutive skips the loop restores the latest checkpoint — optimizer
    state and data position included — and resumes from there
    (``rollbacks``).  ``faults`` (a :class:`repro.robustness.FaultPlan`)
    can force the detector via the ``train.grad_spike`` point: on a fire
    the threshold drops to -1 so that step is guaranteed to skip —
    deterministic detector-path coverage without needing a batch that
    organically produces NaNs.  Threaded as a traced scalar, so the guard
    never recompiles.

    **Elastic recovery** (the ``dist.*`` fault points, all zero-cost under
    ``NO_FAULTS``): on ``dist.device_loss`` the loop rebuilds a smaller
    host mesh (data axis halves first — weight shards must still fit, per
    ``elastic_mesh_shape``), re-jits the step plan, and reshards state onto
    it — from the latest checkpoint when one exists (elastic restore +
    data-iterator reseek, counted in ``resharded_restores``), else by
    ``device_put`` of the live state.  ``dist.collective_timeout`` retries
    the step launch (bounded by ``collective_retries``);
    ``dist.host_crash`` raises :class:`InjectedFault` with no graceful
    save — the crash drill resumes via a fresh ``run_training`` on the same
    ``ckpt_dir``.  ``desync_every`` > 0 enables the cross-replica state
    digest (:mod:`repro.distributed.desync`) every N completed steps: any
    spread quarantines the run and rolls back to the latest checkpoint
    (no checkpoint → status ``quarantined``, run stops).  Recovery
    counters (``mesh_rebuilds``, ``lost_devices``, ``resharded_restores``,
    ``desyncs_detected``, ``desync_rollbacks``, ``collective_timeouts``)
    come back in the results dict.
    """
    from repro.robustness import NO_FAULTS, InjectedFault
    faults = faults or NO_FAULTS
    mesh = mesh or make_host_mesh()

    def _build(m):
        plan = build_plan(cfg, m, shape_cfg, lr=lr,
                          num_microbatches=num_microbatches,
                          kernel_backend=kernel_backend,
                          grad_guard=grad_guard)
        step_jit = jax.jit(plan.step_fn, in_shardings=plan.in_shardings,
                           out_shardings=plan.out_shardings,
                           donate_argnums=plan.donate_argnums)
        ckpt_sh = {"trainable": plan.in_shardings[0],
                   "opt": plan.in_shardings[2],
                   "data_step": NamedSharding(m, PartitionSpec())}
        return plan, step_jit, ckpt_sh

    plan, step_jit, ckpt_sh = _build(mesh)
    print(f"[train] plan {plan.name} mode={plan.meta['mode']} "
          f"kernels={plan.meta['kernel_backend']} "
          f"mesh={plan.meta['sharding']['mesh']}")

    key = jax.random.PRNGKey(seed)
    values, _ = split_tree(model_init(key, cfg))
    trainable, frozen = peft.partition(values, cfg.quant)
    opt = adamw_init(trainable)

    ckpt = (Checkpointer(ckpt_dir, io_retries=io_retries,
                         io_backoff=io_backoff, io_jitter=io_jitter)
            if ckpt_dir else None)
    start_step = 0
    if ckpt is not None:
        # restore straight onto the plan's shardings: on a multi-device mesh
        # the per-shard .npy files land back on their devices (bit-exact
        # resume); on the 1×1 host mesh this degenerates to device_put
        restored = ckpt.restore({"trainable": trainable, "opt": opt,
                                 "data_step": 0}, shardings=ckpt_sh)
        if restored is not None:
            trainable, opt = restored["trainable"], restored["opt"]
            start_step = int(restored["data_step"])
            print(f"[train] resumed from step {start_step}")

    source = SyntheticLM(cfg.vocab_size, shape_cfg.seq_len,
                         shape_cfg.global_batch, seed=seed)
    it = make_batch_iterator(source, start_step)

    guard = PreemptionGuard()
    mon = StragglerMonitor()
    losses = []
    gnorm_ema = None
    accepted = 0
    consecutive_skips = 0
    skipped_steps = 0
    rollbacks = 0
    done = 0
    status = "complete"
    mesh_rebuilds = 0
    lost_devices = 0
    resharded_restores = 0
    desyncs_detected = 0
    desync_rollbacks = 0
    collective_timeouts = 0
    straggler_injected: list[tuple[int, int]] = []
    dist_on = faults.enabled  # skip every dist.* consult under NO_FAULTS

    def _restore_latest(reason: str):
        """Elastic restore of the latest checkpoint onto the *current*
        plan's shardings + data-iterator reseek; returns True on success."""
        nonlocal trainable, opt, it, gnorm_ema, accepted, consecutive_skips
        if ckpt is None or ckpt.latest_step() is None:
            return False
        restored = ckpt.restore(
            {"trainable": trainable, "opt": opt, "data_step": 0},
            shardings=ckpt_sh)
        trainable, opt = restored["trainable"], restored["opt"]
        it = make_batch_iterator(source, int(restored["data_step"]))
        gnorm_ema, accepted, consecutive_skips = None, 0, 0
        print(f"[train] {reason} — restored step "
              f"{int(restored['data_step'])}", flush=True)
        return True

    rebuild = False
    while done < steps and status == "complete":
        if rebuild:
            # device loss: shrink the mesh (data axis first — the model
            # axis is sized so weight shards fit) and reshard onto it.
            shape = dict(mesh.shape)
            data, model = shape.get("data", 1), shape.get("model", 1)
            if data > 1:
                new_data, new_model = max(1, data // 2), model
            else:
                new_data, new_model = data, max(1, model // 2)
            lost_devices += data * model - new_data * new_model
            mesh = make_host_mesh(data=new_data, model=new_model)
            plan, step_jit, ckpt_sh = _build(mesh)
            mesh_rebuilds += 1
            print(f"[train] device loss — rebuilt mesh "
                  f"{data}x{model} -> {new_data}x{new_model}", flush=True)
            if _restore_latest("elastic restore"):
                resharded_restores += 1
            else:
                # no checkpoint yet: reshard the live state onto the new
                # mesh (elastic device_put — bytes unchanged)
                trainable = jax.device_put(trainable, plan.in_shardings[0])
                frozen = jax.device_put(frozen, plan.in_shardings[1])
                opt = jax.device_put(opt, plan.in_shardings[2])
            rebuild = False
        n_data = dict(mesh.shape).get("data", 1)
        with mesh:
            while done < steps:
                if dist_on and faults.fires("dist.device_loss") \
                        and mesh.devices.size > 1 \
                        and mesh_rebuilds < max_mesh_rebuilds:
                    rebuild = True
                    break
                if dist_on and faults.fires("dist.host_crash"):
                    # whole-process crash: no graceful save — the driver
                    # restarts run_training on the same ckpt_dir
                    raise InjectedFault(
                        f"injected host crash at step count {done}")
                step, batch = next(it)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                mon.start_step()
                if dist_on:
                    for s in range(n_data):  # per-shard straggler streams
                        if faults.fires("dist.straggler", index=s):
                            straggler_injected.append((step, s))
                if grad_guard:
                    if faults.fires("train.grad_spike"):
                        thr = -1.0          # detector fires unconditionally
                    elif gnorm_ema is None or accepted < spike_warmup:
                        thr = float("inf")  # no baseline yet
                    else:
                        thr = spike_factor * gnorm_ema
                    args = (trainable, frozen, opt, batch, jnp.float32(thr))
                else:
                    args = (trainable, frozen, opt, batch)
                attempts = 0
                while dist_on and faults.fires("dist.collective_timeout"):
                    collective_timeouts += 1
                    attempts += 1
                    if attempts > collective_retries:
                        raise InjectedFault(
                            "collective timeout persisted past "
                            f"{collective_retries} retries (step {step})")
                trainable, opt, metrics = step_jit(*args)
                loss = float(metrics["loss"])
                skipped = bool(
                    float(metrics.get("update_skipped", 0.0)) > 0.5)
                mon.end_step(step)
                done += 1
                if skipped:
                    skipped_steps += 1
                    consecutive_skips += 1
                    print(f"[train] step {step:5d} SKIPPED "
                          f"(grad_norm {float(metrics['grad_norm']):.3g} "
                          f"> threshold {thr:.3g})", flush=True)
                    if consecutive_skips >= rollback_after:
                        if _restore_latest(
                                f"{rollback_after} consecutive skips"):
                            rollbacks += 1
                    continue
                consecutive_skips = 0
                gn = float(metrics["grad_norm"])
                if np.isfinite(gn):
                    gnorm_ema = gn if gnorm_ema is None \
                        else 0.9 * gnorm_ema + 0.1 * gn
                    accepted += 1
                losses.append(loss)
                if step % log_every == 0:
                    print(f"[train] step {step:5d} loss {loss:.4f}",
                          flush=True)
                if ckpt is not None and (step + 1) % ckpt_every == 0:
                    ckpt.save(step + 1,
                              {"trainable": trainable, "opt": opt,
                               "data_step": step + 1})
                if desync_every > 0 and done % desync_every == 0:
                    digests = replica_digests((trainable, opt), n_data,
                                              faults=faults, step=step)
                    if desync_spread(digests) > 0.0:
                        desyncs_detected += 1
                        if _restore_latest("replica desync detected"):
                            desync_rollbacks += 1
                        else:
                            status = "quarantined"
                            print("[train] desync with no checkpoint — "
                                  "quarantining run", flush=True)
                            break
                if guard.preempted:
                    print("[train] preemption signal — checkpoint & "
                          "clean exit")
                    if ckpt is not None:
                        ckpt.save(step + 1,
                                  {"trainable": trainable, "opt": opt,
                                   "data_step": step + 1})
                    status = "preempted"
                    break
    return {"losses": losses, "trainable": trainable, "frozen": frozen,
            "straggler_flags": mon.flags, "skipped_steps": skipped_steps,
            "rollbacks": rollbacks, "status": status,
            "mesh_rebuilds": mesh_rebuilds, "lost_devices": lost_devices,
            "resharded_restores": resharded_restores,
            "desyncs_detected": desyncs_detected,
            "desync_rollbacks": desync_rollbacks,
            "collective_timeouts": collective_timeouts,
            "straggler_injected": straggler_injected,
            "final_mesh": dict(mesh.shape)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny shape (CPU)")
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mode", default=None, choices=["peft", "qat"],
                    help="override cfg.quant.mode for this run")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["pallas", "interpret", "ref", "dense"],
                    help="pin the fused-kernel dispatch backend (fwd + bwd)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="host mesh shape, e.g. 2x4 (needs that many visible "
                         "devices; on CPU force them via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--desync-every", type=int, default=0,
                    help="cross-replica state-digest cadence in steps "
                         "(0 = off)")
    ap.add_argument("--io-retries", type=int, default=2,
                    help="checkpoint IO retry attempts")
    ap.add_argument("--io-backoff", type=float, default=0.05,
                    help="checkpoint IO retry backoff base (s)")
    ap.add_argument("--io-jitter", type=float, default=0.0,
                    help="decorrelated-jitter fraction for IO retries "
                         "(0 = deterministic exponential)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
        shape = ShapeCfg("smoke", args.seq_len or 128,
                         args.global_batch or 8, "train")
    else:
        shape = SHAPES[args.shape]
        if args.seq_len or args.global_batch:
            shape = ShapeCfg(shape.name, args.seq_len or shape.seq_len,
                             args.global_batch or shape.global_batch, "train")
    if args.mode:
        cfg = cfg.with_(quant=cfg.quant.with_(mode=args.mode))
    mesh = None
    if args.mesh:
        data, model = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_host_mesh(data=data, model=model)
    t0 = time.time()
    out = run_training(cfg, shape, steps=args.steps, lr=args.lr,
                       ckpt_dir=args.ckpt_dir, mesh=mesh,
                       kernel_backend=args.kernel_backend,
                       desync_every=args.desync_every,
                       io_retries=args.io_retries,
                       io_backoff=args.io_backoff,
                       io_jitter=args.io_jitter)
    dt = time.time() - t0
    print(f"[train] done: {len(out['losses'])} steps in {dt:.1f}s; "
          f"loss {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
