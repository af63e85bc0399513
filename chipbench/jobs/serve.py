"""Serving job: the paged continuous-batching ``Engine`` under a traffic mix.

Set-up draws the weights, builds the engine at the geometry the mix states
(the same for every seed, so every seed runs the same compiled programs)
and warms its three step programs.  The window is one ``Engine.run`` over
the seed's requests: a backlog is cut when the window closes, keeping the
partial output of requests in flight; open-loop arrivals come only inside
the window and are drained up to ``drain_cap_s`` after it.

``correct`` compares what the window served: a sample of served requests
drawn from the seed, the longest among them, is run through the float32
reference (prompt plus served tokens), and the widest gap by which a
served token's logit lies below the reference's best at its position must
stay under the cell's limit.  Served tokens of a request the window cut are
checked like those of a completed one: they are what the timed path
produced.  ``check(control=True)`` judges the float8 reference's first
choices at the same positions instead: the comparison must fail it.
"""
from __future__ import annotations

import gc

import numpy as np

from chipbench import traffic, weights, work

__all__ = ["Job"]

_PAD = 256      # reference sequences pad to a multiple of this (causal: the
                # padding follows every checked position and changes none)
UNSERVED = 1e30


class Job:
    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.mix
        self.geo = self.mix["engine"]

    # ---- set-up -----------------------------------------------------------

    def geometry(self):
        g, mix = self.geo, self.mix
        page, chunk = g["page_size"], g["chunk"]
        pmax, omax = mix["prompt"]["max"], mix["output"]["max"]
        longest = max(-(-pmax // chunk) * chunk, pmax + omax - 1)
        max_pages = -(-longest // page)
        pool = -(-g["pool_tokens_per_slot"] // page)
        return dict(slots=g["slots"], page_size=page, chunk=chunk,
                    burst=g["burst"], max_pages=max_pages,
                    total_pages=g["slots"] * pool + 1)

    def setup(self, seconds: float):
        from repro.launch.engine import Engine

        ctx = self.ctx
        self.params = weights.draw(ctx.model_cfg, ctx.seed)
        self.reqs = traffic.make_requests(self.mix, ctx.seed,
                                          ctx.cfg["vocab_size"], seconds)
        self.g = self.geometry()
        g = self.g
        self.eng = Engine(ctx.model_cfg, slots=g["slots"],
                          total_pages=g["total_pages"],
                          page_size=g["page_size"], max_pages=g["max_pages"],
                          chunk=g["chunk"], burst=g["burst"],
                          kernel_backend=ctx.backend, params=self.params,
                          seed=0)
        meta = self.eng.chunk_plan.meta
        if ctx.backend == "pallas" and meta.get("attention") != "fused":
            raise RuntimeError(f"chunk plan is not on the fused path: {meta}")
        self.eng.warmup()
        self.compiles_before = self.eng.compile_counts()

    # ---- window -----------------------------------------------------------

    def window(self, seconds: float):
        from repro.launch.engine import Request

        arr = self.mix["arrivals"]
        cap = float(arr.get("drain_cap_s", 0.0))
        reqs = [Request(r.rid, r.prompt, r.max_new, arrival=r.arrival)
                for r in self.reqs]
        self.stats = self.eng.run(reqs, timeout_s=seconds + cap)
        self.compiles_after = self.eng.compile_counts()

    # ---- what the readers see -------------------------------------------

    def observe(self) -> dict:
        st = self.stats
        recs = st["records"]
        by_rid = {r.rid: r for r in self.reqs}
        rows = [(len(by_rid[r["rid"]].prompt), len(r["tokens"]),
                 r["first_token"] is not None) for r in recs]
        shape = work.shape_of(self.ctx.cfg)
        wk = work.serve_work(shape, rows, self.g["chunk"], st["chunk_steps"],
                             st["decode_steps"], st.get("experts_hit"))
        statuses = st["statuses"]
        backlog = self.mix["arrivals"]["kind"] == "backlog"
        admitted = sum(r["admitted"] is not None for r in recs)
        completed = statuses.get("completed", 0)
        errors = statuses.get("failed", 0) + statuses.get("rejected", 0)
        host_ms = st["wall_s"] * 1e3 - st["prefill_ms"] - st["decode_ms"]
        return {
            "records": recs, "stats": st, "window_s": st["wall_s"],
            "slots": self.g["slots"], "chunk": self.g["chunk"],
            "work": wk, "shape": shape,
            "due": len(self.reqs), "admitted": admitted,
            "completed": completed,
            "cut": sum(r["status"] == "timeout" for r in recs),
            "attempted": admitted if backlog else len(self.reqs),
            "failed": errors if backlog else len(self.reqs) - completed,
            "compiles_before": self.compiles_before,
            "compiles_after": self.compiles_after,
            "lines": [f"chunk steps {st['chunk_steps']} in "
                      f"{st['prefill_ms']:.1f} ms, decode steps "
                      f"{st['decode_steps']} in {st['decode_ms']:.1f} ms, "
                      f"the rest of the window {host_ms:.1f} ms"],
        }

    def free(self):
        """Drop the engine (its page pools) before the reference runs; the
        drawn weights stay for it."""
        self.eng = None
        gc.collect()

    # ---- correct ------------------------------------------------------------

    def sample(self) -> list:
        """(prompt, served tokens) of the checked requests: the one with the
        most served tokens, then others in a seed-drawn order until the mix's
        token target or request count is reached."""
        chk = self.mix["check"]
        by_rid = {r.rid: r for r in self.reqs}
        served = [r for r in self.stats["records"] if r["tokens"]]
        if not served:
            return []
        longest = max(served, key=lambda r: (len(r["tokens"]),
                                             len(by_rid[r["rid"]].prompt)))
        rest = [r for r in served if r is not longest]
        order = traffic.seed_rng(self.ctx.seed, 3).permutation(len(rest))
        picked, total = [longest], len(longest["tokens"])
        for i in order:
            if total >= chk["tokens"] or len(picked) >= chk["requests"]:
                break
            picked.append(rest[i])
            total += len(rest[i]["tokens"])
        return [(by_rid[r["rid"]].prompt, np.asarray(r["tokens"], np.int64))
                for r in picked]

    def reference_logits(self, picked, control: bool = False):
        """Reference logits at every served position of ``picked``."""
        from chipbench.harness import load_reference

        ref = load_reference(self.ctx.cfg)(self.ctx.cfg,
                                           weights.flat(self.params))
        seqs, rows = [], []
        for prompt, toks in picked:
            seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
            n = len(seq)
            seqs.append(np.pad(seq, (0, -n % _PAD)))
            rows.append(np.arange(len(prompt) - 1, n))
        return ref.logits(seqs, rows, control=control)

    def check(self, control: bool = False) -> list:
        """[(name, value, limit)]: every number compared, with its limit.

        ``control`` puts the reference in float8 in the program's place:
        at each position of the same prompts and served tokens, the token
        it puts first is judged instead of the served one."""
        limits = self.mix["limits"]
        st = self.stats
        picked = self.sample()
        widest = UNSERVED   # nothing served: nothing to vouch for
        self.detail = []
        if picked:
            ref = self.reference_logits(picked)
            if control:
                low = self.reference_logits(picked, control=True)
                judged = [lg.argmax(-1) for lg in low]
            else:
                judged = [t for _, t in picked]
            widest = 0.0
            for lg, t, (prompt, served) in zip(ref, judged, picked):
                gap = lg.max(-1) - lg[np.arange(len(t)), t]
                i = int(np.argmax(gap))
                widest = max(widest, float(gap[i]))
                self.detail.append(
                    f"prompt {len(prompt)}, served {len(served)} "
                    f"({len(set(served.tolist()))} distinct); widest gap "
                    f"{float(gap[i]):.4f} at token {i}: judged {int(t[i])},"
                    f" reference's best {int(lg[i].argmax())}")
        self.checked_tokens = sum(len(t) for _, t in picked)
        return [("widest_logit_gap", widest, limits["widest_logit_gap"]),
                ("step_failures", st["step_failures"], 0),
                ("page_audit_issues", len(st["page_audit"]["issues"]), 0)]
