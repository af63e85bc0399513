"""Look at one trace by hand: runs a cell once with ``--trace 1`` semantics,
keeps the profiler's files under ``<out>/trace_<cell>/`` and writes what
the reduction sees (device planes and lines, the top ops with their stats,
the idle gaps and their host labels) to ``<out>/trace_<cell>.txt``.

    python3 chipbench/tools/inspect_trace.py <cell> <seed> <seconds> <out>
"""
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(cell_name, seed, seconds, out):
    from chipbench import harness, trace

    tdir = os.path.join(out, f"trace_{cell_name}")
    os.makedirs(tdir, exist_ok=True)
    harness.tempfile.mkdtemp = lambda prefix="": tdir
    harness.shutil.rmtree = lambda *a, **k: None
    res = harness.run_cell(harness.load_cell(cell_name), seed, seconds, True)
    planes = trace.load(tdir)
    with open(os.path.join(out, f"trace_{cell_name}.txt"), "w") as f:
        for p in planes:
            f.write(f"PLANE {p.name}: " + ", ".join(
                f"{ln.name}({len(ln.events)})" for ln in p.lines) + "\n")
        for p in planes:
            if not trace._DEVICE.match(p.name):
                continue
            for ln in p.lines:
                f.write(f"-- {p.name} / {ln.name}: first events\n")
                for ev in ln.events[:15]:
                    f.write(f"   {ev.name} {ev.dur:.0f}ns {ev.stats}\n")
        s = trace.reduce(planes, top=25)
        f.write(f"window {s.window_s} busy {s.busy_s}\n")
        for k, v in sorted(s.ops.items(), key=lambda kv: -kv[1][0])[:60]:
            f.write(f"OP {v[0]:.6f}s x{v[1]:.0f} {k[:400]}\n")
        for g in s.gaps:
            f.write(f"GAP {g[1]:.6f}s {g[0]}\n")
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
         os.path.abspath(sys.argv[4]))
