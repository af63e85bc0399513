"""Runs one cell of ``BENCHMARK.json`` once and prints its result.

Everything that belongs to one configuration, traffic mix, cell, job kind
or metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

    chipbench/configs/<config>.json          sizes, quantization, reference
    chipbench/references/<reference>.py      the plain float32 reference
    chipbench/traffic/<traffic>.json         the mix (one general generator)
    chipbench/workloads/<cell>.json          the cell's own numbers and limits
    chipbench/jobs/<job>.py                  set-up, window, observe, check
    chipbench/end_to_end/<metric>.py         reader of an end-to-end metric
    chipbench/layer_metrics/<metric>.py      reader of a per-layer metric

A reader module has ``read(ctx) -> float | None``; ``None`` leaves the
metric out of the line.  Readers are looked up by the metric's full name
first, then by the part before its first dot, so ``decode_step_ms.batch``
and a later ``decode_step_ms.<cell kind>`` share ``decode_step_ms.py``.

A run: check the devices (a TPU, as many chips as the cell asks for, a
device kind with published peaks), check the configuration file key by key
against what the program states (``published.py``), set up (timed as
``setup_s``), run the window (under the profiler when ``trace``), read the
peak memory, free the program's state, run the correctness check, read the
metrics, print the counters, then the compared numbers on standard error
and the JSON line last on standard output.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from chipbench import published

__all__ = ["ROOT", "Cell", "RunContext", "NoChip", "load_cell",
           "load_module", "load_reference", "model_config", "judge",
           "run_cell", "main"]

ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"
# environment switches that would move a step off the fused chip path
STEERING_ENV = ("REPRO_INTERPRET_KERNELS", "REPRO_KERNEL_BACKEND",
                "REPRO_AUTOTUNE_CACHE", "REPRO_CPU_EXEC",
                "REPRO_BF16_ELEMWISE")
PYTHON_TRACER_LEVEL = 1     # python function spans label the idle gaps
BREAKDOWN_TOP = 10


class NoChip(RuntimeError):
    """No TPU, too few chips, or a device without published peaks."""


@dataclasses.dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    backend: str
    model_cfg: object = None
    peaks: object = None
    setup_s: float = 0.0
    obs: dict = None
    trace: object = None

    @property
    def cfg(self) -> dict:
        return self.cell.cfg

    @property
    def mix(self) -> dict:
        return self.cell.mix


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT, spec: dict | None = None) -> Cell:
    spec = spec if spec is not None else _read_json(root / "BENCHMARK.json")
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    here = root / "chipbench"
    cfg = _read_json(here / "configs" / f"{w['config']}.json")
    mix = _read_json(here / "traffic" / f"{w['traffic']}.json")
    cell_file = here / "workloads" / f"{name}.json"
    mix = _merge(mix, _read_json(cell_file))
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name=name, config=w["config"], traffic=w["traffic"],
                chips=int(w["chips"]), cfg=cfg, mix=mix, end_to_end=e2e,
                per_layer=layer)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(kind: str, metric: str):
    d = HERE / kind
    for stem in (metric, metric.split(".")[0]):
        p = d / f"{stem}.py"
        if p.exists():
            return load_module(p)
    raise FileNotFoundError(f"no reader for {metric!r} under {d}")


def load_reference(cfg: dict):
    """The reference class the configuration names."""
    return load_module(HERE / "references" / f"{cfg['reference']}.py"
                       ).Reference


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry it names, with the dense sizes, norm and quantization settings
    taken from the file (``head_dim`` absent or null: the program's d /
    heads).  Every other published key of the file must agree with what
    the program states (``published.check``), or the run stops here,
    before set-up."""
    from repro.configs import get_config

    base = get_config(cfg["registry"])
    q = cfg["quantization"]
    quant = base.quant.with_(
        method="lords", codebook=q["codebook"], block_size=q["block_size"],
        mode=q["mode"], rank=None if q["rank"] == "parity" else q["rank"])
    model_cfg = base.with_(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        kv_cache_dtype=q["kv_cache_dtype"], quant=quant)
    published.check(cfg, model_cfg)
    return model_cfg


def _devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def _memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def _traced(enabled: bool):
    """The window under the profiler, inside a host span named WINDOW;
    yields a holder whose ``summary`` is the reduced trace afterwards."""
    import jax

    from chipbench import trace

    holder = type("Traced", (), {"summary": None})()
    if not enabled:
        yield holder
        return
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = PYTHON_TRACER_LEVEL
    try:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                yield holder
        finally:
            jax.profiler.stop_trace()
        holder.summary = trace.reduce(trace.load(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _breakdown(ctx, readers) -> dict:
    """Device time by kernel family (the roofline readers' op lists) and by
    unattributed op, and the longest idle gaps with their host labels."""
    fams = {}
    for r in readers:
        fam = getattr(r, "FAMILY", None)
        if fam:
            fams[fam] = r.OPS
    totals: dict = {}
    for key, (sec, _) in ctx.trace.ops.items():
        fam = next((f for f, pats in fams.items()
                    if any(p in key for p in pats)), None)
        name = f"family:{fam}" if fam else key[:160]
        totals[name] = totals.get(name, 0.0) + sec
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:BREAKDOWN_TOP]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[g[0][:160], g[1]]
                          for g in ctx.trace.gaps[:BREAKDOWN_TOP]]}


def judge(job, control: bool = False):
    """(correct, checks) of a job whose window has run: every compared
    number within its limit.  ``control`` judges the job's control (the
    reference in the precision below the configuration's) in the
    program's place; it must come out not correct."""
    checks = job.check(control=control)
    return all(v <= lim for _, v, lim in checks), checks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             backend: str = "pallas", require_tpu: bool = True,
             control: bool = False, log=print) -> dict:
    """One run of ``cell``; returns the result object (the JSON line).
    ``control`` judges the control in the program's place (never in the
    benchmark's own runs)."""
    import jax

    from chipbench.peaks import peaks_for

    devs = _devices(cell.chips) if require_tpu else jax.devices()
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, backend=backend)
    if require_tpu:
        try:
            ctx.peaks = peaks_for(devs[0].device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from None
    ctx.model_cfg = model_config(cell.cfg)
    job = load_module(HERE / "jobs" / f"{cell.mix['job']}.py").Job(ctx)

    t0 = time.perf_counter()
    job.setup(seconds)
    ctx.setup_s = time.perf_counter() - t0
    with _traced(trace) as traced:
        job.window(seconds)
    ctx.trace = traced.summary
    mem_peak = _memory_peak(devs[: cell.chips])
    ctx.obs = job.observe()
    job.free()
    correct, checks = judge(job, control)
    for line in getattr(job, "detail", []):
        log(f"checked: {line}")

    entries = cell.per_layer if trace else cell.end_to_end
    kind = "layer_metrics" if trace else "end_to_end"
    readers, metrics = [], {}
    for m in entries:
        r = _reader(kind, m["name"])
        readers.append(r)
        v = r.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    obs = ctx.obs
    log(f"compile counts before the window {obs['compiles_before']}, "
        f"after {obs['compiles_after']}")
    log(f"requests due {obs['due']}, admitted {obs['admitted']}, completed "
        f"{obs['completed']}, cut {obs['cut']}; window {obs['window_s']:.3f}"
        f" s; setup {ctx.setup_s:.3f} s; checked tokens "
        f"{getattr(job, 'checked_tokens', 0)}")
    for line in obs.get("lines", []):
        log(line)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = _breakdown(ctx, readers)
    result["checks"] = {n: {"value": float(v), "limit": float(lim)}
                        for n, v, lim in checks}
    return result


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    steering = [v for v in STEERING_ENV if os.environ.get(v)]
    if steering:
        print(f"refusing to run with {steering} set: each can move a step "
              "off the fused chip path", file=sys.stderr)
        return 2
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (NoChip, published.ConfigMismatch) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
