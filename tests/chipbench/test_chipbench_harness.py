"""The harness finds a cell's files by name, picks the metrics each cell
reports, and refuses to run without a TPU."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from chipbench_tiny import ROOT, cpu_env, tiny_root

from chipbench import harness


def test_workload_file_is_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    wl = root / "chipbench" / "workloads" / "tiny.chat.json"
    wl.write_text(json.dumps({"arrivals": {"rate_per_s": 3.5},
                              "limits": {"widest_logit_gap": 0.25}}))
    cell = harness.load_cell("tiny.chat", root=root)
    assert cell.mix["arrivals"] == {"kind": "poisson", "rate_per_s": 3.5,
                                    "drain_cap_s": 30}
    assert cell.mix["limits"] == {"widest_logit_gap": 0.25}
    assert cell.cfg["name"] == "tiny" and cell.chips == 1
    with pytest.raises(KeyError):
        harness.load_cell("tiny.absent", root=root)


def test_each_benchmark_cell_resolves_with_its_metrics():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        assert all(m["moves"] in e2e for m in cell.per_layer)
        for m in cell.end_to_end:
            assert harness._reader("end_to_end", m["name"])
        for m in cell.per_layer:
            assert harness._reader("layer_metrics", m["name"])
        assert harness.load_reference(cell.cfg)
        assert harness.model_config(cell.cfg).num_layers == \
            cell.cfg["num_hidden_layers"]


def test_readers_are_found_by_full_then_base_name():
    a = harness._reader("layer_metrics", "decode_step_ms.batch")
    b = harness._reader("layer_metrics", "decode_step_ms.chat")
    assert a.read.__doc__ == b.read.__doc__
    assert a.__file__.endswith("decode_step_ms.py")


def _run(cwd):
    cmd = [sys.executable, "chipbench/run.py", "--workload",
           "qwen3-8b-nf4.batch-decode", "--seed", str(2**31 + 3),
           "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=cpu_env(), capture_output=True,
                          text=True, timeout=300)


def _json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not _json_lines(p.stdout)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _json_lines(p.stdout)
