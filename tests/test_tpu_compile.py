"""The main-path Pallas kernels compile for a TPU v5e at llama3-8b widths.

Nothing runs: each kernel is lowered and compiled by the installed TPU
compiler for a *described* v5e chip (no accelerator needed), which refuses
what interpret mode cannot see — unaligned blocks, scoped-VMEM overruns,
layouts Mosaic cannot lower.  Shapes are llama3-8b's (d_model 4096, d_ff
14336, 32 heads / 8 KV heads of 128), nf4 codes at the parity rank of
block 128, with the tiles dispatch picks for them.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import QuantSpec, quantize
from repro.kernels import dispatch
from repro.kernels.attn_decode import (
    attn_decode_gqa_paged_pallas,
    attn_decode_gqa_pallas,
)
from repro.kernels.attn_prefill import attn_prefill_pallas
from repro.kernels.block_matmul import block_matmul_pallas
from repro.kernels.lords_decode import lords_decode_pallas
from repro.kernels.lords_grad import lords_grad_pallas
from repro.kernels.lords_matmul import lords_matmul_pallas
from repro.kernels.lords_matmul_t import lords_matmul_t_pallas

CODEBOOK = "nf4"
# (N, K) of the llama3-8b linears: q/o, k/v, gate/up, down
LINEARS = [(4096, 4096), (1024, 4096), (14336, 4096), (4096, 14336)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compilation cache off: such
    entries could not be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args, **static):
    text = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not a fallback
    return text


def _lords_operands(sharding, m, n, k, x_dtype):
    r = QuantSpec(block_size=128).lords_rank(n, k)
    ps = quantize.pack_spec(CODEBOOK)
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                sharding=sharding)
    return (sd((m, k), x_dtype), sd((n, ps.packed_width(k)), jnp.uint8),
            sd((n, r), jnp.float32), sd((r, k), jnp.float32))


@pytest.mark.parametrize("n,k", LINEARS)
def test_lords_decode_compiles(one_chip, n, k):
    x, q, b, a = _lords_operands(one_chip, 8, n, k, jnp.bfloat16)
    _, bn, bk = dispatch.tile_for("lords", 8, n, k, CODEBOOK, jnp.bfloat16)
    _compile(lords_decode_pallas, x, q, b, a, codebook_name=CODEBOOK,
             bn=bn, bk=bk)


@pytest.mark.parametrize("n,k", LINEARS)
def test_lords_matmul_compiles(one_chip, n, k):
    x, q, b, a = _lords_operands(one_chip, 256, n, k, jnp.bfloat16)
    bm, bn, bk = dispatch.tile_for("lords", 256, n, k, CODEBOOK,
                                   jnp.bfloat16)
    _compile(lords_matmul_pallas, x, q, b, a, codebook_name=CODEBOOK,
             bm=bm, bn=bn, bk=bk)


@pytest.mark.parametrize("n,k", LINEARS)
def test_lords_backward_compiles(one_chip, n, k):
    """dx (transposed matmul) and dB/dA (grad reduction) of a PEFT step."""
    x, q, b, a = _lords_operands(one_chip, 256, n, k, jnp.float32)
    g = jax.ShapeDtypeStruct((256, n), jnp.float32, sharding=one_chip)
    bm, bn, bk = dispatch.tile_for("lords_t", 256, n, k, CODEBOOK,
                                   jnp.float32)
    tiles = dict(codebook_name=CODEBOOK, bm=bm, bn=bn, bk=bk)
    _compile(lords_matmul_t_pallas, g, q, b, a, **tiles)
    _compile(lords_grad_pallas, x, g, q, b, a, **tiles)


def test_block_matmul_compiles(one_chip):
    """The block-wise nf4 baseline kernel (block 64) at the q/o width."""
    n = k = 4096
    bs = 64
    ps = quantize.pack_spec(CODEBOOK)
    bm, bn, bk = dispatch.tile_for("blockwise", 256, n, k, CODEBOOK,
                                   jnp.bfloat16, block_size=bs)
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    _compile(block_matmul_pallas, sd((256, k), jnp.bfloat16),
             sd((n, ps.packed_width(k)), jnp.uint8),
             sd((n, k // bs), jnp.float32), block_size=bs,
             codebook_name=CODEBOOK, bm=bm, bn=bn, bk=bk)


def test_attention_compiles(one_chip):
    """Flash prefill (bf16), the contiguous int8-cache decode, and the paged
    decode over an int8 page pool at the batch-decode cell's geometry (32
    slots, windows of 144 pages of 16, a 1,793-page pool) with 8 KV heads,
    and with the 4 and 2 a chip holds when tensor parallelism splits them.
    The pools are row-major, as the engine stores them; at 8 and 4 heads
    the kernel reads the code pools in place, with no relayout or copy (2
    int8 heads are padded to a 4-row tile, so XLA relayouts that pool into
    the kernel's page-rows view; the scale pools' one-row-per-page view is
    a relayout at every head count)."""
    from jax.experimental.layout import Format, Layout

    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    b, s, nh, nkv, hd = 1, 512, 32, 8, 128
    bq, bkv = dispatch.attn_tile_for("prefill", s, nh, hd, jnp.bfloat16,
                                     (128, 128))
    _compile(attn_prefill_pallas, sd((b, s, nh, hd), jnp.bfloat16),
             sd((b, s, nkv, hd), jnp.bfloat16),
             sd((b, s, nkv, hd), jnp.bfloat16), sd((b, s), jnp.int32),
             sd((b, s), jnp.int32), logit_scale=hd ** -0.5, bq=bq, bkv=bkv)
    slots, page, cap = 8, 16, 640
    _compile(attn_decode_gqa_pallas, sd((slots, nkv, 8, hd), jnp.bfloat16),
             sd((slots, cap, nkv, hd), jnp.int8),
             sd((slots, cap, nkv, hd), jnp.int8),
             sd((slots, cap), jnp.float32), sd((slots, cap, nkv), jnp.float32),
             sd((slots, cap, nkv), jnp.float32), logit_scale=hd ** -0.5,
             bs=128)
    rm = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=Format(Layout(tuple(range(len(shape)))),
                                   one_chip))
    slots, pages, pool = 32, 144, 1793
    for heads in (8, 4, 2):
        text = _compile(attn_decode_gqa_paged_pallas,
                        rm((slots, pages), jnp.int32),
                        rm((slots, heads, 8, hd), jnp.bfloat16),
                        rm((pool, page, heads, hd), jnp.int8),
                        rm((pool, page, heads, hd), jnp.int8),
                        rm((slots,), jnp.int32),
                        rm((pool, page, heads), jnp.float32),
                        rm((pool, page, heads), jnp.float32),
                        logit_scale=hd ** -0.5)
        if heads == 2:
            continue
        # the code pools reach the kernel as the parameters they are: no
        # instruction but a bitcast or a move to another memory space (the
        # same layout) touches them
        code_pool = f"s8[{pool},{page},{heads},{hd}]"

        def layouts(line):
            return {re.sub(r"S\(\d+\)", "", lay) for lay in re.findall(
                re.escape(code_pool) + r"(\{[^}]*\})", line)}

        lines = [ln for ln in text.splitlines() if code_pool in ln]
        stored = set().union(*(layouts(ln) for ln in lines
                               if "parameter(" in ln))
        assert len(stored) == 1
        assert not [ln for ln in lines if not any(
            op in ln for op in ("parameter(", "bitcast(", "tpu_custom_call",
                                "HloModule", "ENTRY"))
            and not (("copy-start(" in ln or "copy-done(" in ln)
                     and layouts(ln) == stored)]


def _kernel_bodies_without_locations(hlo: str) -> str:
    """Each Mosaic kernel body (MLIR bytecode in the custom call's backend
    config) replaced by its text without debug locations: a named scope
    around a launch reaches only those locations."""
    import base64
    import re

    from jax._src.lib import tpu  # noqa: F401 — registers the tpu dialect
    from jax._src.lib.mlir import ir

    def strip(m):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return '"body":"%s"' % mod.operation.get_asm(
                enable_debug_info=False).replace('"', "'")

    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', strip, hlo)


def test_paged_decode_step_scopes_compile_away(topo, one_chip, monkeypatch):
    """The paged decode step at llama3-8b widths (2 layers, 4 slots)
    compiles for the v5e to the unscoped program: the same instructions,
    operands and kernels once metadata and instruction names are set
    aside."""
    import contextlib

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
    from test_named_scopes import canonical

    from repro.configs import get_config
    from repro.launch.steps import build_paged_generate_plan

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    rep = NamedSharding(mesh, PartitionSpec())
    cfg = get_config("llama3-8b").with_(num_layers=2, kv_cache_dtype="int8")

    def compiled():
        plan = build_paged_generate_plan(
            cfg, mesh, slots=4, gen=1, total_pages=33, page_size=16,
            max_pages=8, kernel_backend="pallas")
        args = tuple(
            jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), a, sh)
            if sh is not None else jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=rep), a)
            for a, sh in zip(plan.abstract_args, plan.in_shardings))
        text = jax.jit(plan.step_fn, out_shardings=plan.out_shardings,
                       donate_argnums=(2,)).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return canonical(_kernel_bodies_without_locations(text))

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda n: contextlib.nullcontext())
    assert compiled() == scoped
