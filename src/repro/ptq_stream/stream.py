"""Layer-streaming PTQ: sequential, crash-safe, memory-bounded.

``stream_quantize(source, out_dir, plan)`` processes one transformer block
at a time — materialize the dense block, capture per-matrix calibration
activations, LoRDS-refine S = BA against them (``core.ptq.ptq_refine``
with the activation-weighted loss), publish the packed codes as an atomic
checksummed shard, journal the block in the ledger, propagate the
calibration activations through the *quantized* block (GPTQ-style), and
free.  Dense weights for at most one block ever exist, enforced — not
assumed — by a :class:`MemoryBudget` watchdog that fails fast with a
per-charge diagnostic instead of silently swapping.

Crash-safety contract (asserted by tests/test_ptq_stream.py and the
``ptq-stream-smoke`` CI job):

  * a run killed at any block boundary, mid-shard-write, or between a
    shard landing and its ledger commit, resumes (``resume=True``) to an
    artifact **bit-identical** to an uninterrupted run;
  * resume trusts nothing: every prior block's shard is re-digested
    against the ledger CRC, and the activation chain is re-propagated and
    checked digest-by-digest — any mismatch (corrupt shard, changed
    calibration set) re-does exactly the invalid block and then keeps
    re-validating, so one flipped bit costs one block, not the run;
  * :class:`~repro.distributed.fault_tolerance.PreemptionGuard` flips a
    graceful stop at the next block boundary (status ``preempted``; the
    ledger stays resumable);
  * transient ``OSError`` during shard IO is retried
    (``retry_on_transient``), bounded.

Fault-injection points (``repro.robustness.FaultPlan``): ``ptq.kill_at_block``,
``ptq.kill_mid_write``, ``ptq.kill_before_commit``, ``ptq.corrupt_shard``,
``ptq.transient_oserror``, ``ptq.oom_spike``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import zlib

import jax.numpy as jnp
import numpy as np

from repro.core.ptq import ptq_refine_chunked, virtual_shards
from repro.core.baselines import (
    hadamard_signs,
    hadamard_transform,
    smooth_scales,
)
from repro.core.quantize import PACK_LAYOUT, dequantize_codes, unpack_codes
from repro.core.scaling import scale_matrix
from repro.distributed.sharding import row_shard
from repro.kernels import dispatch
from repro.ptq_stream.ledger import Ledger
from repro.ptq_stream.shards import (
    digest_array,
    read_shard,
    shard_digest,
    write_shard,
)
from repro.robustness import NO_FAULTS, InjectedFault

__all__ = ["StreamPlan", "MemoryBudget", "MemoryBudgetExceeded",
           "stream_quantize", "quantize_dense_blocks", "audit_artifact",
           "calibration_moments", "allocate_from_artifact"]


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Everything that determines the quantized artifact's bytes.

    ``memory_budget`` / ``refine_overhead`` are *execution* knobs — they
    gate whether a run is allowed to proceed, never what it computes — so
    they are excluded from the ledger fingerprint: resuming under a
    different budget is legal and still bit-identical.
    """

    codebook: str = "nf4"
    block_size: int = 32
    rank: int | None = None
    extra_rank: int = 0
    # per-matrix mixed-precision overrides from the sensitivity allocator
    # (core.allocate): ((matrix_name, codebook, rank), ...).  Overrides
    # determine the artifact bytes, so they are fingerprinted.
    overrides: tuple = ()
    refine_steps: int = 40
    lr: float = 0.05
    seed: int = 0
    pretransform: str = "none"      # none | smooth | smoothrot
    smooth_alpha: float = 0.5
    act_weighted: bool = True       # col_weight = E[x_j^2] in refinement
    # Fixed virtual-shard count for the canonical chunked arithmetic
    # (calibration matmuls, E[x²] folds, ptq_refine_chunked).  Part of the
    # numerical program — fingerprinted — so a run is bit-identical on any
    # physical device count: a mesh changes where chunks live, never what
    # is computed.  Per-dim counts clamp to the largest divisor
    # (core.ptq.virtual_shards).
    calib_shards: int = 8
    memory_budget: int | None = None  # bytes; None = unenforced
    refine_overhead: int = 6        # transient f32 copies charged per refine
    # shard/ledger IO retry policy (execution knobs, not fingerprinted)
    io_retries: int = 2
    io_backoff: float = 0.02
    io_jitter: float = 0.0          # 0 = deterministic exponential backoff

    def __post_init__(self):
        if self.pretransform not in ("none", "smooth", "smoothrot"):
            raise ValueError(f"unknown pretransform {self.pretransform!r}")
        object.__setattr__(
            self, "overrides",
            tuple((str(n), str(cb), None if r is None else int(r))
                  for n, cb, r in self.overrides))

    def codebook_for(self, name: str) -> str:
        for n, cb, _ in self.overrides:
            if n == name:
                return cb
        return self.codebook

    def rank_for(self, name: str):
        for n, _, r in self.overrides:
            if n == name:
                # a None rank in an override means "codebook only": the
                # matrix keeps the plan-wide rank policy
                return self.rank if r is None else r
        return self.rank

    def with_allocation(self, alloc) -> "StreamPlan":
        """Fold a :class:`repro.core.allocate.AllocPlan` into per-matrix
        overrides (keyed by the allocator's layer names)."""
        ov = tuple((l.name, l.codebook, l.rank) for l in alloc.layers)
        return dataclasses.replace(self, overrides=ov)

    def fingerprint(self) -> dict:
        fp = {"codebook": self.codebook, "block_size": self.block_size,
              "rank": self.rank, "extra_rank": self.extra_rank,
              "refine_steps": self.refine_steps, "lr": self.lr,
              "seed": self.seed, "pretransform": self.pretransform,
              "smooth_alpha": self.smooth_alpha,
              "act_weighted": self.act_weighted,
              "calib_shards": self.calib_shards,
              # a ledger of shards packed in another layout is refused
              "pack_layout": PACK_LAYOUT}
        if self.overrides:  # absent for uniform plans: fingerprint-stable
            fp["overrides"] = [list(o) for o in self.overrides]
        return fp


def _block_seed(plan_seed: int, block: int) -> int:
    return zlib.crc32(f"{plan_seed}/{block}".encode())


def _mat_seed(plan_seed: int, block: int, name: str) -> int:
    return zlib.crc32(f"{plan_seed}/{block}/{name}".encode())


# ---------------------------------------------------------------------------
# memory-budget watchdog
# ---------------------------------------------------------------------------


class MemoryBudgetExceeded(RuntimeError):
    """The streaming invariant broke: fail fast, never swap silently."""


class MemoryBudget:
    """Explicit byte accounting for everything the pipeline materializes.

    Every dense block, activation capture, and refine temporary is charged
    under a name; exceeding ``limit`` raises :class:`MemoryBudgetExceeded`
    whose message lists the live charges — the diagnostic names exactly
    which allocation broke the streaming invariant.  ``ptq.oom_spike``
    injects a phantom allocation of the full limit so chaos tests exercise
    the failure path deterministically.
    """

    def __init__(self, limit: int | None, faults=NO_FAULTS):
        self.limit = limit
        self.faults = faults
        self._live: dict[str, int] = {}
        self.peak = 0

    def charge(self, name: str, nbytes: int):
        self._live[name] = self._live.get(name, 0) + int(nbytes)
        total = sum(self._live.values())
        self.peak = max(self.peak, total)
        phantom = 0
        if self.limit is not None and self.faults.fires("ptq.oom_spike"):
            phantom = self.limit
            self._live["injected/oom_spike"] = phantom
        if self.limit is not None and total + phantom > self.limit:
            diag = ", ".join(f"{k}={v}" for k, v in sorted(
                self._live.items(), key=lambda kv: -kv[1]))
            self._live.pop("injected/oom_spike", None)
            raise MemoryBudgetExceeded(
                f"memory budget exceeded: {total + phantom} > "
                f"{self.limit} bytes while charging {name!r} "
                f"(+{nbytes}); live charges: {diag}")

    def release(self, name: str):
        self._live.pop(name, None)

    def release_prefix(self, prefix: str):
        for k in [k for k in self._live if k.startswith(prefix)]:
            del self._live[k]

    @contextlib.contextmanager
    def hold(self, name: str, nbytes: int):
        self.charge(name, nbytes)
        try:
            yield
        finally:
            self.release(name)

    def live(self) -> dict:
        return dict(self._live)


# ---------------------------------------------------------------------------
# per-matrix / per-block quantization (shared by streamed + in-memory paths)
# ---------------------------------------------------------------------------


def _col_weight(xm: jnp.ndarray, chunks: int = 1) -> jnp.ndarray:
    """E[x_j²] + eps with *canonical chunked* token reduction: the token
    axis is split into ``chunks`` fixed virtual shards whose partial sums
    fold in shard order, so the bytes never depend on physical sharding."""
    x = jnp.asarray(xm, jnp.float32)
    t = x.shape[0]
    ns = virtual_shards(t, chunks)
    parts = jnp.sum(x.reshape(ns, t // ns, -1) ** 2, axis=1)
    acc = parts[0]
    for i in range(1, ns):
        acc = acc + parts[i]
    return acc / jnp.float32(t) + 1e-6


def _quantize_matrix(w, xm, plan: StreamPlan, seed: int,
                     name: str = "", mesh=None) -> dict:
    """One matrix through Alg. 1 under the plan's pre-transform; returns the
    flat artifact arrays ({q, b, a[, c, signs], xsq}).

    The refine runs :func:`ptq_refine_chunked` over ``plan.calib_shards``
    virtual row shards; when ``mesh`` is given the rows (chunk axis) are
    placed data-parallel across it — placement only, identical bytes.
    ``xsq`` is the original-basis E[x_j²] moment, stored for the
    sensitivity allocator (core.allocate) to consume later.
    """
    w = jnp.asarray(w, jnp.float32)
    cs = plan.calib_shards
    xsq = _col_weight(xm, cs)
    kw = dict(codebook_name=plan.codebook_for(name),
              block_size=plan.block_size,
              rank=plan.rank_for(name), extra_rank=plan.extra_rank,
              steps=plan.refine_steps, lr=plan.lr,
              nshard=virtual_shards(w.shape[0], cs))
    w_in = row_shard(w, mesh)
    if plan.pretransform == "smoothrot":
        c = smooth_scales(w, xm, plan.smooth_alpha)
        signs = hadamard_signs(w.shape[1], seed)
        w_work = hadamard_transform(w * c[None, :], signs)
        x_work = hadamard_transform(
            jnp.asarray(xm, jnp.float32) / c[None, :], signs)
        colw = _col_weight(x_work, cs) if plan.act_weighted else None
        res = ptq_refine_chunked(row_shard(w_work, mesh),
                                 col_weight=colw, **kw)
        return {"q": res.q_packed, "b": res.b, "a": res.a,
                "c": c, "signs": signs, "xsq": xsq}
    colw = _col_weight(xm, cs) if plan.act_weighted else None
    if plan.pretransform == "smooth":
        c = smooth_scales(w, xm, plan.smooth_alpha)
        res = ptq_refine_chunked(w_in, col_weight=colw, channel_scale=c,
                                 **kw)
    else:
        res = ptq_refine_chunked(w_in, col_weight=colw, **kw)
    return {"q": res.q_packed, "b": res.b, "a": res.a, "xsq": xsq}


def _dequant_matrix(mats: dict, plan: StreamPlan,
                    name: str = "") -> np.ndarray:
    """Ŵ in the original basis from one matrix's artifact arrays."""
    cb = plan.codebook_for(name)
    codes = unpack_codes(jnp.asarray(mats["q"]), cb)
    s = scale_matrix(jnp.asarray(mats["b"]), jnp.asarray(mats["a"]))
    w_hat = dequantize_codes(codes, s, cb)
    if "c" in mats:  # smoothrot: rotate back, un-smooth
        signs = jnp.asarray(mats["signs"], jnp.float32)
        c = jnp.asarray(mats["c"], jnp.float32)
        w_hat = hadamard_transform(w_hat) * signs[None, :] / c[None, :]
    return np.asarray(w_hat, np.float32)


def _quantize_block(weights: dict, calib: dict, plan: StreamPlan,
                    block: int, budget: MemoryBudget | None = None,
                    mesh=None) -> tuple[dict, dict]:
    """Quantize every matrix of one block; returns (flat shard tree, Ŵ)."""
    flat, w_hat = {}, {}
    for name in sorted(weights):
        w = np.asarray(weights[name], np.float32)
        ctx = (budget.hold(f"block{block}/refine",
                           plan.refine_overhead * w.nbytes)
               if budget is not None else contextlib.nullcontext())
        with ctx:
            mats = _quantize_matrix(w, calib[name], plan,
                                    _mat_seed(plan.seed, block, name),
                                    name=name, mesh=mesh)
        for k, v in mats.items():
            flat[f"{name}/{k}"] = np.asarray(v)
        w_hat[name] = _dequant_matrix(mats, plan, name=name)
        if budget is not None:
            budget.charge(f"block{block}/artifact",
                          sum(v.nbytes for v in mats.values()))
            budget.charge(f"block{block}/dequant", w_hat[name].nbytes)
    return flat, w_hat


def _unflatten(tree: dict) -> dict:
    """{'up/q': ...} -> {'up': {'q': ...}} (shard layout -> per-matrix)."""
    out: dict[str, dict] = {}
    for k, v in tree.items():
        name, key = k.rsplit("/", 1)
        out.setdefault(name, {})[key] = v
    return out


# ---------------------------------------------------------------------------
# streaming pipeline
# ---------------------------------------------------------------------------


def _try_reuse(out_dir: str, entry: dict, plan: StreamPlan, source, x,
               budget: MemoryBudget, mesh=None):
    """Validate one ledger entry against disk + the activation chain.

    Returns (ok, x_out, reason).  On ok the block's work is skipped and the
    propagated activations come from the *stored* shard — the same bytes a
    fresh run would have produced (verify-on-write proved it)."""
    path = os.path.join(out_dir, entry["shard"])
    try:
        crc = shard_digest(path)
    except Exception:
        return False, None, "shard missing/unreadable"
    if crc != entry["crc32"]:
        return False, None, "shard checksum mismatch"
    if digest_array(x) != entry["x_in"]:
        return False, None, "input-activation digest mismatch"
    mats = _unflatten(read_shard(path))
    i = entry["block"]
    w_hat = {}
    for name, m in mats.items():
        w_hat[name] = _dequant_matrix(m, plan, name=name)
        budget.charge(f"block{i}/dequant", w_hat[name].nbytes)
    x_out = source.block_apply(w_hat, x, chunks=plan.calib_shards,
                               mesh=mesh)
    budget.release_prefix(f"block{i}/")
    if digest_array(x_out) != entry["x_out"]:
        return False, None, "output-activation digest mismatch"
    return True, x_out, None


def stream_quantize(source, out_dir: str, plan: StreamPlan, *,
                    resume: bool = False, faults=None, guard=None,
                    mesh=None) -> dict:
    """Run (or resume) the streaming pipeline; returns a summary dict.

    ``faults``: a :class:`repro.robustness.FaultPlan` consulted at the
    ``ptq.*`` points.  ``guard``: anything with a ``preempted`` property
    (:class:`PreemptionGuard`) — checked at block boundaries.

    ``mesh``: optional ``jax.sharding.Mesh`` — the calibration matmuls and
    the ``ptq_refine_chunked`` inner loop run data-parallel over it (rows /
    tokens placed across every mesh axis, ``dispatch.shard_scope``
    active).  The mesh is an *execution* knob: the plan's fixed
    ``calib_shards`` virtual-shard arithmetic makes the artifact bytes
    identical on any device count, so a sharded run killed at a block
    boundary may resume on a smaller mesh (or a single host) and still
    converge to the bit-identical artifact.
    """
    faults = faults or NO_FAULTS
    t_start = time.monotonic()
    ledger = Ledger(out_dir, io_retries=plan.io_retries,
                    io_backoff=plan.io_backoff)
    budget = MemoryBudget(plan.memory_budget, faults)
    plan_fp, source_fp = plan.fingerprint(), source.fingerprint()

    if resume and ledger.load():
        if ledger.entries:
            ledger.check_fingerprint(plan_fp, source_fp)
        ledger.mark_in_progress()
    else:
        ledger.start(plan_fp, source_fp)
    stray = ledger.cleanup_stray_tmp()

    x = np.asarray(source.calibration_inputs(), np.float32)
    budget.charge("calib/x", x.nbytes)

    reused, recomputed = 0, []
    n = source.num_blocks
    scope = (dispatch.shard_scope(mesh) if mesh is not None
             else contextlib.nullcontext())
    with scope:
        for i in range(n):
            entry = ledger.entry(i)
            if entry is not None:
                ok, x_out, _reason = _try_reuse(out_dir, entry, plan,
                                                source, x, budget, mesh=mesh)
                if ok:
                    x = x_out
                    reused += 1
                    continue
                # invalid entry: fall through and re-do exactly this block —
                # deterministic recompute restores the original bytes, so
                # later entries stay reusable via the digest chain.
            if guard is not None and guard.preempted:
                return {"status": "preempted", "blocks_done": i,
                        "num_blocks": n, "reused": reused,
                        "recomputed": recomputed, "stray_tmp_removed": stray,
                        "peak_bytes": budget.peak,
                        "wall_s": time.monotonic() - t_start}
            if faults.fires("ptq.kill_at_block"):
                raise InjectedFault(f"killed at block boundary {i}")

            t0 = time.monotonic()
            weights = source.load_block(i)
            budget.charge(f"block{i}/dense",
                          sum(np.asarray(v).nbytes
                              for v in weights.values()))
            calib = source.calib_inputs(weights, x,
                                        chunks=plan.calib_shards, mesh=mesh)
            budget.charge(f"block{i}/calib",
                          sum(np.asarray(v).nbytes for v in calib.values()))

            flat, w_hat = _quantize_block(weights, calib, plan, i, budget,
                                          mesh=mesh)
            shard, crc = write_shard(out_dir, i, flat, faults=faults,
                                     io_retries=plan.io_retries,
                                     io_backoff=plan.io_backoff,
                                     io_jitter=plan.io_jitter)
            x_out = source.block_apply(w_hat, x, chunks=plan.calib_shards,
                                       mesh=mesh)
            new_entry = {"block": i, "status": "done", "shard": shard,
                         "crc32": crc, "x_in": digest_array(x),
                         "x_out": digest_array(x_out),
                         "seed": _block_seed(plan.seed, i),
                         "wall_s": round(time.monotonic() - t0, 4)}
            if faults.fires("ptq.kill_before_commit"):
                # shard published but never journaled: resume re-does the
                # block
                raise InjectedFault(
                    f"killed before ledger commit (block {i})")
            if entry is None:
                ledger.append(new_entry)
            else:
                ledger.replace(i, new_entry)
            recomputed.append(i)
            budget.release_prefix(f"block{i}/")
            budget.release("calib/x")
            budget.charge("calib/x", x_out.nbytes)
            x = x_out

    ledger.complete()
    return {"status": "complete", "blocks_done": n, "num_blocks": n,
            "reused": reused, "recomputed": recomputed,
            "stray_tmp_removed": stray, "peak_bytes": budget.peak,
            "x_final_digest": digest_array(x),
            "wall_s": time.monotonic() - t_start}


# ---------------------------------------------------------------------------
# in-memory reference path (the one-shot core.ptq equivalent)
# ---------------------------------------------------------------------------


def quantize_dense_blocks(source, plan: StreamPlan) -> tuple[list[dict], int]:
    """One-shot in-memory PTQ: all dense blocks held at once, same per-matrix
    math as the streamed path (shared ``_quantize_block``).  Returns
    (per-block flat artifact trees, final activation digest) — the oracle
    the streamed artifact must match bit for bit."""
    blocks = [source.load_block(i) for i in range(source.num_blocks)]
    x = np.asarray(source.calibration_inputs(), np.float32)
    out = []
    for i, weights in enumerate(blocks):
        calib = source.calib_inputs(weights, x, chunks=plan.calib_shards)
        flat, w_hat = _quantize_block(weights, calib, plan, i)
        out.append({k: np.asarray(v) for k, v in flat.items()})
        x = source.block_apply(w_hat, x, chunks=plan.calib_shards)
    return out, digest_array(x)


# ---------------------------------------------------------------------------
# audit
# ---------------------------------------------------------------------------


def audit_artifact(out_dir: str, source, plan: StreamPlan) -> dict:
    """Read-only ledger/checksum audit of a streamed artifact.

    Re-digests every shard against its ledger CRC and re-propagates the
    calibration activations through the stored quantized blocks, checking
    the digest chain end to end.  Returns ``{"clean": bool, "blocks":
    [{block, ok, reason}, ...], "status": ledger status}``.
    """
    ledger = Ledger(out_dir)
    if not ledger.load():
        return {"clean": False, "status": "missing",
                "blocks": [], "reason": "no readable ledger"}
    report = {"status": ledger.status, "blocks": []}
    try:
        ledger.check_fingerprint(plan.fingerprint(), source.fingerprint())
    except ValueError as e:
        return {**report, "clean": False, "reason": str(e)}
    budget = MemoryBudget(None)
    x = np.asarray(source.calibration_inputs(), np.float32)
    clean = ledger.status == "complete"
    for i in range(source.num_blocks):
        entry = ledger.entry(i)
        if entry is None:
            report["blocks"].append(
                {"block": i, "ok": False, "reason": "missing ledger entry"})
            clean = False
            break
        ok, x_out, reason = _try_reuse(out_dir, entry, plan, source, x,
                                       budget)
        report["blocks"].append({"block": i, "ok": ok, "reason": reason})
        if not ok:
            clean = False
            break
        x = x_out
    report["clean"] = clean
    return report


# ---------------------------------------------------------------------------
# calibration moments -> sensitivity allocator
# ---------------------------------------------------------------------------


def calibration_moments(out_dir: str) -> dict:
    """Per-matrix E[x_j²] moments stored by a streamed run.

    Reads the ``xsq`` arrays out of every journaled shard and averages them
    per matrix name across blocks — the override system (StreamPlan /
    ``core.allocate``) keys layers by matrix name, so the result plugs
    straight into ``allocate(..., col_weights=calibration_moments(dir))``.
    Returns ``{}`` when no ledger/shards exist (or none carry moments):
    callers then fall back to plain weight-MSE sensitivity
    (``col_weight=None`` — the documented fallback parity).
    """
    ledger = Ledger(out_dir)
    if not ledger.load():
        return {}
    sums: dict[str, np.ndarray] = {}
    counts: dict[str, int] = {}
    for entry in ledger.entries:
        path = os.path.join(out_dir, entry["shard"])
        try:
            tree = read_shard(path)
        except Exception:
            continue
        for k, v in tree.items():
            if k.endswith("/xsq"):
                name = k[:-len("/xsq")]
                arr = np.asarray(v, np.float64)
                if name in sums and sums[name].shape == arr.shape:
                    sums[name] = sums[name] + arr
                    counts[name] += 1
                elif name not in sums:
                    sums[name] = arr
                    counts[name] = 1
    return {name: (sums[name] / counts[name]).astype(np.float32)
            for name in sums}


def allocate_from_artifact(weights: dict, budget_bytes: int, out_dir: str,
                           **kw):
    """Sensitivity allocation driven by a streamed run's calibration ledger.

    Feeds :func:`calibration_moments` (the E[x_j²] each matrix was actually
    calibrated against) into ``core.allocate`` as per-layer ``col_weights``.
    Layer names match moments exactly or by their ``.../<matrix>`` suffix
    (streamed moments are per matrix *kind*, shared across blocks).  A layer
    with no usable moment — missing, or shaped for a different fan-in —
    falls back to plain weight-MSE sensitivity (``col_weight=None``), so an
    artifact with no moments reproduces ``allocate(...)`` exactly.
    """
    from repro.core.allocate import allocate

    moments = calibration_moments(out_dir)
    col = {}
    for name, w in weights.items():
        m = moments.get(name)
        if m is None:
            m = moments.get(name.rsplit("/", 1)[-1])
        if m is not None and m.shape == (w.shape[1],):
            col[name] = m
    return allocate(weights, budget_bytes, col_weights=col, **kw)
