"""Named scopes on the paged step plans: the lowered HLO of each plan the
engine runs names its ops by step, model part and kernel entry point, no
scope name holds a kernel name the benchmark's readers match, and the
compiled program is the unscoped one: the same instructions on the same
operands once metadata and instruction names are set aside."""
import contextlib
import re
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (
    build_paged_generate_plan,
    build_prefill_chunk_plan,
)

ROOT = Path(__file__).resolve().parents[1]

_MODEL = ("embed", "attn", "kv_store", "qmatmul", "mlp", "final_norm_head",
          "sample")
# plan -> scopes its lowered HLO must carry
PLANS = {
    "chunk": ("step_chunk_prefill", "kv_window", "qattention_chunk_prefill")
    + _MODEL,
    "g1": ("step_paged_generate_g1", "qattention_paged_decode") + _MODEL,
    "g4": ("step_paged_generate_g4", "qattention_paged_decode") + _MODEL,
}
_META = re.compile(r',? metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_FRAMES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                     r"\n(?:\d+ .*\n)*\n?", re.M)
_DEF = re.compile(r"^\s*(?:ROOT |ENTRY )?%?([\w.\-]+)(?: = | \()", re.M)


def _reader_ops() -> set:
    """Every ``OPS`` name pattern of the benchmark's per-layer readers."""
    sys.path.insert(0, str(ROOT))
    try:
        from chipbench.harness import load_module
        return {op for p in (ROOT / "chipbench" / "layer_metrics").glob(
                    "*.py") for op in getattr(load_module(p), "OPS", ())}
    finally:
        sys.path.remove(str(ROOT))


def _plan(name, backend="interpret"):
    cfg = smoke_variant(get_config("llama3-8b")).with_(
        num_layers=2, kv_cache_dtype="int8")
    kw = dict(slots=2, total_pages=12, page_size=8, max_pages=4,
              kernel_backend=backend)
    if name == "chunk":
        return build_prefill_chunk_plan(cfg, make_host_mesh(), chunk=16,
                                        **kw)
    return build_paged_generate_plan(cfg, make_host_mesh(),
                                      gen=int(name[1:]), **kw)


def _lowered(plan):
    return jax.jit(plan.step_fn).lower(*plan.abstract_args)


def canonical(hlo: str) -> str:
    """Compiled HLO text without metadata, stack frames or instruction
    names: each defined name becomes its order of definition.  XLA names
    some instructions after their source locations, which scopes extend."""
    hlo = _FRAMES.sub("", _META.sub("", hlo))
    names = list(dict.fromkeys(_DEF.findall(hlo)))
    ids = {n: f"v{i}" for i, n in enumerate(names)}
    pat = re.compile(r"(?<![\w.\-])%?(" + "|".join(
        map(re.escape, sorted(names, key=len, reverse=True)))
        + r")(?![\w.\-])")
    return pat.sub(lambda m: ids[m.group(1)], hlo)


@pytest.mark.parametrize("name", sorted(PLANS))
def test_step_plan_scopes_are_metadata(name, monkeypatch):
    ops = _reader_ops()
    assert {"lords_matmul_pallas", "attn_decode_gqa_paged_pallas"} <= ops
    for scope in PLANS[name]:
        assert not any(op in scope for op in ops), scope

    lowered = _lowered(_plan(name))
    paths = re.findall(r'op_name="([^"]*)"',
                       lowered.as_text(dialect="hlo", debug_info=True))
    parts = {c for p in paths for c in re.split(r"[/;]", p)}
    assert set(PLANS[name]) <= parts, sorted(set(PLANS[name]) - parts)

    scoped = lowered.compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda n: contextlib.nullcontext())
    bare = _lowered(_plan(name)).compile().as_text()
    assert not any(s in bare for s in PLANS[name][:1])
    assert canonical(scoped) == canonical(bare)
