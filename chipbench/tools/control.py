"""Readings that set a serving cell's limit, on the chip at the cell's own
size and load: for each seed, the window runs once and the harness's own
comparison (``harness.judge``) judges it twice, the program's served
tokens and, for the first ``--control`` seeds, the control in the
program's place.  One process and one engine; each seed draws its own
weights and requests.  Prints one JSON line per seed.

    python3 chipbench/tools/control.py <cell> <seconds> --control 3 \\
        <seed> <seed> ...
"""
import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _reading(job, control):
    from chipbench import harness

    correct, checks = harness.judge(job, control=control)
    return {"correct": correct,
            "checks": {n: [float(v), float(lim)] for n, v, lim in checks},
            "detail": job.detail}


def main(argv=None):
    import jax

    from chipbench import harness, traffic, weights

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seconds", type=float)
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.cell)
    harness._devices(cell.chips)
    job = None
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        ctx = harness.RunContext(cell=cell, seed=seed, seconds=args.seconds,
                                 backend="pallas")
        ctx.model_cfg = harness.model_config(cell.cfg)
        if job is None:
            job = harness.load_module(harness.HERE / "jobs" / "serve.py"
                                      ).Job(ctx)
            job.setup(args.seconds)
        else:
            job.ctx = ctx
            job.params = job.eng.params = None
            gc.collect()
            job.params = weights.draw(ctx.model_cfg, seed)
            job.eng.params = jax.device_put(
                job.params, job.eng.chunk_plan.in_shardings[0])
            job.reqs = traffic.make_requests(job.mix, seed,
                                             cell.cfg["vocab_size"],
                                             args.seconds)
        job.window(args.seconds)
        out = {"seed": seed, "statuses": job.stats["statuses"],
               "program": _reading(job, False)}
        out["tokens"] = job.checked_tokens
        if i < args.control:
            out["control"] = _reading(job, True)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    main()
