"""The work a step requires, counted from the configuration's shapes and
the live token counts, whatever kernel or tile does it.

Rows of dead slots, padding, decode steps past a request's last token and
the kernels' own dequantization work count as nothing: a change that
removes them raises a share, and cannot push it past 100%.  Where a phase
holds many steps, the least time is taken over the phase's totals
(``max(flops / peak, bytes / bandwidth)``), which is at most the sum over
the steps, so a share can only be understated.

Layers by kind, as a published ``config.json`` lays them out: attention is
grouped-query (``head_dim``, d / heads where absent) or latent (MLA:
``kv_lora_rank`` and its head sizes); the MLP is a dense SwiGLU of
``intermediate_size`` in the first ``first_k_dense_replace`` layers and a
mixture of experts in every layer after them (``moe_layer_freq`` 1):
``num_experts_per_tok`` of ``n_routed_experts`` routed SwiGLUs of
``moe_intermediate_size``, plus the shared experts as one SwiGLU of
``n_shared_experts × moe_intermediate_size``.  The routed experts are
their own phases (``experts.*``); every other quantized linear is under
``qmatmul.*``.  ``SHAPE_KEYS`` names every published key the count reads:
the configuration check (``published.py``) lets none of them through
unless the program states it with the file's value.
"""
from __future__ import annotations

import dataclasses

__all__ = ["SHAPE_KEYS", "Shape", "Work", "shape_of", "parity_rank",
           "serve_work", "least_seconds"]

_BITS = {"nf4": 4, "int4": 4, "fp4": 4, "nf3": 3, "nf2": 2, "int2": 2,
         "int8": 8}


SHAPE_KEYS = frozenset({
    "num_hidden_layers", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "num_experts_per_tok",
    "moe_intermediate_size", "n_shared_experts", "first_k_dense_replace",
    "moe_layer_freq"})


def parity_rank(n: int, k: int, block: int) -> int:
    """LoRDS rank at parameter parity with block-wise scales of ``block``
    (paper, Appendix A): floor(n·k / (block·(n + k))), at least 1."""
    return max(n * k // (block * (n + k)), 1)


def _swiglu(prefix: str, width: int, d: int):
    return ((f"{prefix}gate", width, d), (f"{prefix}up", width, d),
            (f"{prefix}down", d, width))


@dataclasses.dataclass(frozen=True, kw_only=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    d_ff: int                # dense SwiGLU width (intermediate_size)
    vocab: int
    bits: int
    block: int
    kv_bytes: int            # bytes per stored KV element (1: int8, 2: bf16)
    head_dim: int | None = None   # grouped-query head size; None: d / heads
    # latent attention (MLA); kv_lora 0 means grouped-query attention
    q_lora: int | None = None     # None: q projected directly from d
    kv_lora: int = 0
    nope: int = 0
    rope: int = 0
    v_dim: int = 0
    # mixture of experts; experts 0 means a dense SwiGLU in every layer
    experts: int = 0
    top_k: int = 0
    moe_ff: int = 0
    shared: int = 0
    dense_first: int = 0     # first_k_dense_replace

    @property
    def hd(self) -> int:
        return self.head_dim or self.d // self.heads

    @property
    def mla(self) -> bool:
        return self.kv_lora > 0

    @property
    def moe_layers(self) -> int:
        """Layers whose MLP is the expert layer: all after dense_first."""
        return self.layers - self.dense_first if self.experts else 0

    @property
    def dense_layers(self) -> int:
        return self.layers - self.moe_layers

    def attn_linears(self, decode: bool = False):
        """(name, n_out, k_in) of one layer's attention projections.  MLA's
        kv_b (latent to per-head K and V) is applied only in prefill:
        decode absorbs it into q and the output, as model work."""
        d = self.d
        if not self.mla:
            q, kv = self.heads * self.hd, self.kv_heads * self.hd
            return (("q", q, d), ("k", kv, d), ("v", kv, d), ("o", d, q))
        nh, qk = self.heads, self.nope + self.rope
        q = ((("q", nh * qk, d),) if self.q_lora is None else
             (("q_a", self.q_lora, d), ("q_b", nh * qk, self.q_lora)))
        kv_b = () if decode else (
            ("kv_b", nh * (self.nope + self.v_dim), self.kv_lora),)
        return q + (("kv_a", self.kv_lora + self.rope, d),) + kv_b + (
            ("o", d, nh * self.v_dim),)

    def linears(self, decode: bool = False):
        """(name, n_out, k_in, layers): every quantized linear of a step but
        the routed experts, with the number of layers that hold it."""
        out = [(n, o, i, self.layers)
               for n, o, i in self.attn_linears(decode)]
        if self.d_ff and self.dense_layers:
            out += [(n, o, i, self.dense_layers)
                    for n, o, i in _swiglu("", self.d_ff, self.d)]
        if self.shared and self.moe_layers:
            out += [(n, o, i, self.moe_layers) for n, o, i in
                    _swiglu("shared_", self.shared * self.moe_ff, self.d)]
        return tuple(out)

    def expert_linears(self):
        """(name, n_out, k_in) of one routed expert."""
        return _swiglu("expert_", self.moe_ff, self.d)

    def _packed(self, n: int, k: int) -> int:
        """Packed codes plus f32 B and A of one quantized linear."""
        r = parity_rank(n, k, self.block)
        return n * k * self.bits // 8 + 4 * r * (n + k)

    def linear_macs(self, decode: bool = False) -> int:
        """Multiply-adds of the ``linears`` for one token."""
        return sum(L * n * k for _, n, k, L in self.linears(decode))

    def linear_weight_bytes(self, decode: bool = False) -> int:
        """Packed bytes of the ``linears``: what one step reads."""
        return sum(L * self._packed(n, k)
                   for _, n, k, L in self.linears(decode))

    def linear_act_bytes(self, decode: bool = False) -> int:
        """bf16 activations in and out of the ``linears``, per row."""
        return sum(L * 2 * (n + k) for _, n, k, L in self.linears(decode))

    @property
    def expert_macs(self) -> int:
        return sum(n * k for _, n, k in self.expert_linears())

    @property
    def expert_bytes(self) -> int:
        """Packed bytes of one routed expert."""
        return sum(self._packed(n, k) for _, n, k in self.expert_linears())

    @property
    def expert_act_bytes(self) -> int:
        return sum(2 * (n + k) for _, n, k in self.expert_linears())


def shape_of(cfg: dict) -> Shape:
    """The sizes of a configuration file (published key names)."""
    q = cfg["quantization"]
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("expert layers are counted at moe_layer_freq 1, "
                         f"not {cfg['moe_layer_freq']!r}")
    kw = {}
    if cfg.get("kv_lora_rank"):
        kw.update(q_lora=cfg.get("q_lora_rank"), kv_lora=cfg["kv_lora_rank"],
                  nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                  v_dim=cfg["v_head_dim"])
    if cfg.get("n_routed_experts"):
        kw.update(experts=cfg["n_routed_experts"],
                  top_k=cfg["num_experts_per_tok"],
                  moe_ff=cfg["moe_intermediate_size"],
                  shared=cfg.get("n_shared_experts") or 0,
                  dense_first=cfg.get("first_k_dense_replace") or 0)
    return Shape(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                 heads=cfg["num_attention_heads"],
                 kv_heads=cfg["num_key_value_heads"],
                 head_dim=cfg.get("head_dim"), d_ff=cfg["intermediate_size"],
                 vocab=cfg["vocab_size"], bits=_BITS[q["codebook"]],
                 block=q["block_size"],
                 kv_bytes=1 if q["kv_cache_dtype"] == "int8" else 2, **kw)


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, other: "Work") -> "Work":
        self.flops += other.flops
        self.bytes += other.bytes
        return self


def least_seconds(w: Work, peak_flops: float, peak_bw: float) -> float:
    return max(w.flops / peak_flops, w.bytes / peak_bw)


def _kv_token_bytes(s: Shape) -> int:
    """One stored token of one layer's cache: for grouped-query attention
    K and V codes plus, for int8, one f32 scale per head and tensor; for
    MLA the latent's codes, its f32 scale for int8, and the bf16 rope
    key."""
    scale = 4 if s.kv_bytes == 1 else 0
    if s.mla:
        return s.kv_lora * s.kv_bytes + scale + 2 * s.rope
    return 2 * s.kv_heads * (s.hd * s.kv_bytes + scale)


def _attn_prefill(s: Shape, c0: int, q: int, causal: int,
                  kv_tok: int) -> Work:
    """One chunk of ``q`` rows after ``c0`` cached tokens, all layers: the
    cached context read once, the chunk's own K and V and its q and output
    in bf16.  MLA attends in the up-projected form."""
    nh = s.heads
    if s.mla:
        qk_v = s.nope + s.rope + s.v_dim
        return Work(flops=2 * nh * qk_v * causal * s.layers,
                    bytes=s.layers * (c0 * kv_tok + 2 * q * nh * qk_v * 2))
    hd = s.hd
    return Work(flops=4 * nh * hd * causal * s.layers,
                bytes=s.layers * (c0 * kv_tok
                                  + q * 2 * s.kv_heads * hd * 2
                                  + q * 2 * nh * hd * 2))


def _attn_decode(s: Shape, steps: int, ctx: int, kv_tok: int) -> Work:
    """``steps`` decode rows attending ``ctx`` keys in all, all layers: the
    live context read once a step, q and output in bf16.  MLA attends in
    the absorbed form, over the latents and the rope key."""
    nh = s.heads
    if s.mla:
        width = 2 * s.kv_lora + s.rope
        return Work(flops=2 * nh * ctx * width * s.layers,
                    bytes=s.layers * (ctx * kv_tok + steps * nh * width * 2))
    hd = s.hd
    return Work(flops=4 * nh * hd * ctx * s.layers,
                bytes=s.layers * (ctx * kv_tok + steps * 2 * nh * hd * 2))


def serve_work(s: Shape, requests, chunk: int, chunk_steps: int,
               decode_steps: int, experts_hit: dict | None = None) -> dict:
    """Required work of a serving window, per phase and kernel family.

    ``requests``: (prompt_len, tokens_served, prefilled) per request, where
    ``prefilled`` says its whole prompt went through chunked prefill in the
    window (a request cut mid-prompt adds nothing).  ``chunk_steps`` and
    ``decode_steps`` are the model passes the engine ran: each reads every
    weight once.  ``experts_hit`` (``{"prefill": n, "decode": n}``), where
    the engine counts it, is the number of distinct routed experts the
    live rows were sent to, summed over steps and expert layers; without
    it each expert layer of each step is taken to read ``top_k`` experts,
    the least it can.

    Returns Work for ``qmatmul.prefill``, ``qmatmul.decode``,
    ``attn.prefill``, ``attn.decode``, ``head`` and ``model`` (all
    required model FLOPs, the numerator of an MFU), plus the live row
    counts; with experts, ``experts.prefill`` and ``experts.decode`` too.
    """
    pre_rows = dec_rows = heads = 0
    attn_pre, attn_dec = Work(), Work()
    kv_tok = _kv_token_bytes(s)
    for plen, served, prefilled in requests:
        if prefilled:
            pre_rows += plen
            for c0 in range(0, plen, chunk):
                c1 = min(c0 + chunk, plen)
                q = c1 - c0
                # causal: query i attends keys 0..i
                causal = (c0 + 1 + c1) * q // 2
                attn_pre += _attn_prefill(s, c0, q, causal, kv_tok)
        if served >= 1:
            heads += served
        if served >= 2 and prefilled:
            steps = served - 1
            dec_rows += steps
            # decode step j (1..steps) attends plen + j keys
            ctx = steps * plen + steps * (steps + 1) // 2
            attn_dec += _attn_decode(s, steps, ctx, kv_tok)
    q_pre = Work(flops=2 * pre_rows * s.linear_macs(),
                 bytes=chunk_steps * s.linear_weight_bytes()
                 + pre_rows * s.linear_act_bytes())
    q_dec = Work(flops=2 * dec_rows * s.linear_macs(decode=True),
                 bytes=decode_steps * s.linear_weight_bytes(decode=True)
                 + dec_rows * s.linear_act_bytes(decode=True))
    head = Work(flops=2 * heads * s.d * s.vocab,
                bytes=(chunk_steps + decode_steps) * s.vocab * s.d * 2)
    out = {"qmatmul.prefill": q_pre, "qmatmul.decode": q_dec,
           "attn.prefill": attn_pre, "attn.decode": attn_dec, "head": head}
    extra = 0
    if s.mla:
        # decode applies kv_b absorbed: its multiply-adds, as model work
        extra += 2 * dec_rows * s.layers * s.heads * s.kv_lora * (
            s.nope + s.v_dim)
    if s.moe_layers:
        hit = experts_hit or {}
        for phase, rows, steps in (("prefill", pre_rows, chunk_steps),
                                   ("decode", dec_rows, decode_steps)):
            hits = hit.get(phase, steps * s.moe_layers * s.top_k)
            out[f"experts.{phase}"] = Work(
                flops=2 * rows * s.top_k * s.expert_macs * s.moe_layers,
                bytes=hits * s.expert_bytes
                + rows * s.top_k * s.expert_act_bytes * s.moe_layers)
        # the router: d × experts logits per row and expert layer
        extra += 2 * (pre_rows + dec_rows) * s.moe_layers * s.d * s.experts
    out["model"] = Work(flops=sum(w.flops for w in out.values()) + extra)
    out["rows.prefill"], out["rows.decode"] = pre_rows, dec_rows
    return out
