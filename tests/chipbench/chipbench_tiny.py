"""A tiny copy of the benchmark's cells for CPU tests: the real traffic
mixes and configuration with their sizes cut to what a test run holds."""
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_root(tmp: Path, limit: float = 0.05) -> Path:
    """A benchmark root under ``tmp`` with cells ``tiny.batch`` (the
    batch-decode mix) and ``tiny.chat`` (the same sizes under open-loop
    Poisson arrivals): 2 layers of width 64, prompts 8-40, outputs 2-12."""
    here = ROOT / "chipbench"
    (tmp / "chipbench").mkdir(parents=True, exist_ok=True)
    for d in ("configs", "traffic", "workloads"):
        (tmp / "chipbench" / d).mkdir(exist_ok=True)
    c = json.loads((here / "configs" / "qwen3-8b-nf4.json").read_text())
    c.update(name="tiny", hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=256)
    c["quantization"] = dict(c["quantization"], block_size=32)
    (tmp / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(c))
    batch = json.loads((here / "traffic" / "batch-decode.json").read_text())
    chat = dict(batch, arrivals={"kind": "poisson", "rate_per_s": None,
                                 "drain_cap_s": 30})
    for t, m in (("batch-decode", batch), ("chat-poisson", chat)):
        m = dict(m)
        m["prompt"] = dict(m["prompt"], median=20, min=8, max=40)
        m["output"] = dict(m["output"], median=6, min=2, max=12)
        m["engine"] = dict(m["engine"], slots=4, chunk=16, burst=4,
                           pool_tokens_per_slot=52)
        if m["arrivals"]["kind"] == "backlog":
            m["arrivals"] = dict(m["arrivals"], requests=16, block=4)
        m["check"] = {"requests": 3, "tokens": 20}
        (tmp / "chipbench" / "traffic" / f"{t}.json").write_text(
            json.dumps(m))
    lim = {"limits": {"widest_logit_gap": limit}}
    (tmp / "chipbench" / "workloads" / "tiny.batch.json").write_text(
        json.dumps(lim))
    (tmp / "chipbench" / "workloads" / "tiny.chat.json").write_text(
        json.dumps(dict(lim, arrivals={"rate_per_s": 4.0})))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        dict(name="tiny.batch", config="tiny", traffic="batch-decode",
             chips=1, why="tiny"),
        dict(name="tiny.chat", config="tiny", traffic="chat-poisson",
             chips=1, why="tiny")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.chat" if "chat" in w else "tiny.batch"
                              for w in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def cpu_env() -> dict:
    """The environment of a child process that must find no TPU."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("REPRO_")}
    env["JAX_PLATFORMS"] = "cpu"
    return env
