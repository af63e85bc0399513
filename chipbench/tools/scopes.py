"""Where a traced window's device time goes by named scope, what the engine
was doing in its idle gaps, and what tracing costs.

    python3 chipbench/tools/scopes.py <cell> <seed> <seconds> <out>

Sets the cell up once, then runs its window three times on the same engine
and requests: under the profiler with its Python tracer, first after set-up
as in a ``--trace 1`` run (``traced_py1``), with the profiler off
(``untraced``), and under the profiler without the Python tracer
(``traced_py0``).  Writes ``<out>/scopes_<cell>.json``
after each run: per run, output tokens per second, the engine's spans and
compile count and the two span readers; per traced run, the stats found on
the device op events, device seconds per scope path, the model's self share,
the harness's own reduction, and the ten longest idle gaps, each with the
harness's label and the engine spans overlapping it.  Each traced run's
``.xplane.pb`` is kept beside it, gzipped, when it is under ``KEEP_BYTES``.
"""
import gzip
import json
import os
import re
import shutil
import struct
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

KEEP_BYTES = 24 << 20
# the program's named scopes (launch/steps.py, models/, kernels/dispatch.py)
SCOPE = re.compile(r"^(step_\w+|qattention_\w+|embed|attn|mamba|mlstm|slstm"
                   r"|mlp|final_norm_head|sample|kv_store|kv_window|qmatmul)$")
KERNEL_ENTRY = re.compile(r"^(qmatmul|qattention_\w+)$")
NO_SCOPE = "(no scope)"


def scope_path(op_path: str) -> str:
    """The named scopes of an op path, outermost first (``jit(...)``,
    control flow and the op's own name dropped); a fusion's joined path
    (``a;b``) counts by its first part."""
    parts = op_path.split(";", 1)[0].split("/")[:-1]
    return "/".join(c for c in parts if SCOPE.match(c))


def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: varints as int, every
    other wire type as its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _map(entries: list) -> dict:
    """A protobuf map<int64, message> from its entries' bytes."""
    out = {}
    for e in entries:
        kv = dict(_fields(e))
        out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def _stat(buf: bytes, stat_names: dict) -> tuple:
    """(name, value as str) of an XStat."""
    name, value = None, ""
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = str(struct.unpack("<d", v)[0])
        elif f in (3, 4):
            value = str(v)
        elif f == 5:
            value = v.decode("utf-8", "replace")
        elif f == 6:
            value = repr(v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def device_ops(path: str) -> dict:
    """{device plane: [(start ns, duration ns, name, {stat: str})]} of the
    ``XLA Ops`` lines of an ``.xplane.pb``, with every stat of the event and
    of its event metadata (where the compiler's op path, ``tf_op``, lives:
    ``jax.profiler.ProfileData`` shows only the event's own stats).  Reads
    the XSpace protobuf directly (tsl/profiler/protobuf/xplane.proto)."""
    from chipbench import trace

    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for num, plane in _fields(space):
        if num != 1:                                   # XSpace.planes
            continue
        fields: dict = {}
        for f, v in _fields(plane):
            fields.setdefault(f, []).append(v)
        name = fields.get(2, [b""])[0].decode()
        if not trace._DEVICE.match(name):
            continue
        stat_names = {k: dict(_fields(v)).get(2, b"").decode()
                      for k, v in _map(fields.get(5, [])).items()}
        meta = {}
        for k, v in _map(fields.get(4, [])).items():   # XEventMetadata
            md = {"name": "", "stats": {}}
            for f, x in _fields(v):
                if f == 2:
                    md["name"] = x.decode("utf-8", "replace")
                elif f == 5:
                    sk, sv = _stat(x, stat_names)
                    md["stats"][sk] = sv
            meta[k] = md
        evs = []
        for line in fields.get(3, []):                  # XLine
            lf: dict = {}
            for f, v in _fields(line):
                lf.setdefault(f, []).append(v)
            if lf.get(2, [b""])[0].decode() != trace._OP_LINE:
                continue
            t0 = lf.get(3, [0])[0]
            for ev in lf.get(4, []):                    # XEvent
                e = {"stats": {}}
                for f, v in _fields(ev):
                    if f == 4:
                        sk, sv = _stat(v, stat_names)
                        e["stats"][sk] = sv
                    else:
                        e[f] = v
                md = meta.get(e.get(1), {"name": "", "stats": {}})
                evs.append((t0 + e.get(2, 0) / 1e3, e.get(3, 0) / 1e3,
                            md["name"], {**md["stats"], **e["stats"]}))
        out[name] = evs
    return out


def path_stat(ops: dict) -> str | None:
    """The stat whose values carry op paths with the program's step scopes:
    the one that holds a ``step_*`` scope on the most events."""
    hits: dict = {}
    for evs in ops.values():
        for *_, stats in evs:
            for k, v in stats.items():
                if scope_path(v + "/op").startswith("step_"):
                    hits[k] = hits.get(k, 0) + 1
    return max(hits, key=hits.get) if hits else None


def scope_seconds(ops: dict, window: tuple, stat: str | None) -> dict:
    """Device seconds per scope path inside ``window`` (ns), averaged over
    the devices; control-flow ops, which span their bodies, are left out as
    in the harness's ``ops``."""
    from chipbench import trace

    t0, t1 = window
    secs: dict = {}
    for evs in ops.values():
        for start, dur, name, stats in evs:
            s, e = max(start, t0), min(start + dur, t1)
            if e <= s or trace._container(
                    trace.op_key(trace.Event(name, start, dur))):
                continue
            key = scope_path(stats.get(stat, "")) if stat else ""
            key = key or NO_SCOPE
            secs[key] = secs.get(key, 0.0) + (e - s) * 1e-9
    n = max(len(ops), 1)
    return {k: v / n for k, v in secs.items()}


def top_scopes(secs: dict, depth: int = 2) -> dict:
    """Device seconds per scope path cut to its first ``depth`` scopes."""
    out: dict = {}
    for k, v in secs.items():
        cut = "/".join(k.split("/")[:depth])
        out[cut] = out.get(cut, 0.0) + v
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def model_self_share(secs: dict) -> float | None:
    """Device time in ``step_*`` scopes outside every ``qmatmul`` and
    ``qattention_*`` scope, over device time in ``step_*`` scopes, in %."""
    steps = {k: v for k, v in secs.items() if k.startswith("step_")}
    total = sum(steps.values())
    if total <= 0:
        return None
    kernels = sum(v for k, v in steps.items()
                  if any(KERNEL_ENTRY.match(c) for c in k.split("/")))
    return 100.0 * (total - kernels) / total


def idle_gaps(ops: dict, window: tuple, top: int = 10) -> list:
    """The ``top`` longest [start, end) ns intervals of the window in which
    no op ran on the first device: ``trace.reduce``'s gaps, with their
    place in the window."""
    from chipbench import trace

    t0, t1 = window
    first = ops[sorted(ops)[0]] if ops else []
    ivs = trace._union((max(s, t0), min(s + d, t1)) for s, d, *_ in first
                       if min(s + d, t1) > max(s, t0))
    edges = [t0] + [x for iv in ivs for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return sorted(gaps, key=lambda g: g[0] - g[1])[:top]


def engine_overlaps(planes, gap: tuple) -> list:
    """[name, overlap ns, duration ns] of every ``engine.*`` host event
    overlapping ``gap``, the shortest (innermost) first."""
    from chipbench import trace

    s, e = gap
    out = []
    for p in planes:
        if trace._DEVICE.match(p.name):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if not ev.name.startswith("engine."):
                    continue
                ov = min(ev.end, e) - max(ev.start, s)
                if ov > 0:
                    out.append([ev.name, ov, ev.dur])
    return sorted(out, key=lambda r: r[2])


def traced_report(tdir: str, top: int = 10) -> tuple:
    """(report, path of its ``.xplane.pb``) of one kept trace directory;
    the report holds what the module docstring lists."""
    import glob

    from chipbench import trace

    (path,) = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    planes = trace.load(tdir)
    summ = trace.reduce(planes, top=top)
    window = trace._window(planes)
    ops = device_ops(path)
    names: dict = {}
    for evs in ops.values():
        for *_, stats in evs:
            for k, v in stats.items():
                names.setdefault(k, v[:300])
    stat = path_stat(ops)
    secs = scope_seconds(ops, window, stat)
    gaps = []
    for g in idle_gaps(ops, window, top):
        gaps.append({"ms": (g[1] - g[0]) * 1e-6,
                     "at_ms": (g[0] - window[0]) * 1e-6,
                     "harness_label": trace._host_label(planes, *g, window),
                     "engine": [[n, ov * 1e-6, d * 1e-6] for n, ov, d
                                in engine_overlaps(planes, g)]})
    return {"xplane_bytes": os.path.getsize(path), "op_stats": names,
            "scope_stat": stat, "window_s": summ.window_s,
            "busy_s": summ.busy_s, "scopes": dict(sorted(
                secs.items(), key=lambda kv: -kv[1])),
            "top_scopes": top_scopes(secs),
            "model_self_share": model_self_share(secs),
            "ops_top": sorted(([k, v[0]] for k, v in summ.ops.items()),
                              key=lambda kv: -kv[1])[:25],
            "harness_gaps": summ.gaps, "gaps": gaps}, path


def main(cell_name, seed, seconds, out):
    import jax

    from chipbench import harness, trace
    from chipbench.peaks import peaks_for

    cell = harness.load_cell(cell_name)
    devs = harness._devices(cell.chips)
    ctx = harness.RunContext(cell=cell, seed=seed, seconds=seconds,
                             backend="pallas")
    ctx.peaks = peaks_for(devs[0].device_kind)
    ctx.model_cfg = harness.model_config(cell.cfg)
    job = harness.load_module(harness.HERE / "jobs"
                              / f"{cell.mix['job']}.py").Job(ctx)
    t0 = time.perf_counter()
    job.setup(seconds)
    report = {"cell": cell_name, "seed": seed, "device": devs[0].device_kind,
              "setup_s": time.perf_counter() - t0, "runs": {}}
    readers = {m: harness._reader(kind, m) for kind, m in (
        ("end_to_end", "output_tokens_per_s"),
        ("layer_metrics", "engine_self_ms.batch"),
        ("layer_metrics", "chunk_step_ms.batch"))}
    dest = os.path.join(out, f"scopes_{cell_name}.json")
    for run, level in (("traced_py1", 1), ("untraced", None),
                       ("traced_py0", 0)):
        tdir = tempfile.mkdtemp(prefix="scopes_")
        t0 = time.perf_counter()
        if level is None:
            job.window(seconds)
        else:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = level
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(trace.WINDOW):
                    job.window(seconds)
            finally:
                jax.profiler.stop_trace()
        ctx.obs = job.observe()
        st = ctx.obs["stats"]
        rec = {m: r.read(ctx) for m, r in readers.items()}
        rec.update(window_s=ctx.obs["window_s"], lines=ctx.obs["lines"],
                   spans=st.get("spans"), compiles=st.get("compiles"),
                   run_s=time.perf_counter() - t0)
        if level is not None:
            rep, path = traced_report(tdir)
            rec.update(rep, reduce_s=time.perf_counter() - t0 - rec["run_s"])
            if os.path.getsize(path) < KEEP_BYTES:
                with open(path, "rb") as f, gzip.open(os.path.join(
                        out, f"scopes_{cell_name}_{run}.xplane.pb.gz"),
                        "wb") as g:
                    shutil.copyfileobj(f, g)
        shutil.rmtree(tdir, ignore_errors=True)
        report["runs"][run] = rec
        with open(dest, "w") as f:
            json.dump(report, f, indent=1, default=str)
        print(json.dumps({run: {k: rec.get(k) for k in (
            "output_tokens_per_s", "engine_self_ms.batch",
            "chunk_step_ms.batch", "compiles", "scope_stat",
            "model_self_share", "run_s", "reduce_s")}}), flush=True)


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
         os.path.abspath(sys.argv[4]))
