"""The work a step requires, counted from the configuration's shapes and
the live token counts, whatever kernel or tile does it.

Rows of dead slots, padding, decode steps past a request's last token and
the kernels' own dequantization work count as nothing: a change that
removes them raises a share, and cannot push it past 100%.  Where a phase
holds many steps, the least time is taken over the phase's totals
(``max(flops / peak, bytes / bandwidth)``), which is at most the sum over
the steps, so a share can only be understated.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Shape", "Work", "shape_of", "parity_rank", "serve_work",
           "least_seconds"]

_BITS = {"nf4": 4, "int4": 4, "fp4": 4, "nf3": 3, "nf2": 2, "int2": 2,
         "int8": 8}


def parity_rank(n: int, k: int, block: int) -> int:
    """LoRDS rank at parameter parity with block-wise scales of ``block``
    (paper, Appendix A): floor(n·k / (block·(n + k))), at least 1."""
    return max(n * k // (block * (n + k)), 1)


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    bits: int
    block: int
    kv_bytes: int            # bytes per stored KV element (1: int8, 2: bf16)

    def linears(self):
        """(name, n_out, k_in) of one layer's quantized linears."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return (("q", q, self.d), ("k", kv, self.d), ("v", kv, self.d),
                ("o", self.d, q), ("gate", self.d_ff, self.d),
                ("up", self.d_ff, self.d), ("down", self.d, self.d_ff))

    @property
    def linear_macs(self) -> int:
        """Multiply-adds of all quantized linears for one token."""
        return self.layers * sum(n * k for _, n, k in self.linears())

    @property
    def linear_weight_bytes(self) -> int:
        """Packed codes plus f32 B and A of every quantized linear."""
        total = 0
        for _, n, k in self.linears():
            r = parity_rank(n, k, self.block)
            total += n * k * self.bits // 8 + 4 * r * (n + k)
        return self.layers * total

    @property
    def linear_act_bytes(self) -> int:
        """bf16 activations in and out of every quantized linear, per row."""
        return self.layers * sum(2 * (n + k) for _, n, k in self.linears())


def shape_of(cfg: dict) -> Shape:
    """The sizes of a configuration file (published key names)."""
    q = cfg["quantization"]
    return Shape(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                 heads=cfg["num_attention_heads"],
                 kv_heads=cfg["num_key_value_heads"],
                 head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                 vocab=cfg["vocab_size"], bits=_BITS[q["codebook"]],
                 block=q["block_size"],
                 kv_bytes=1 if q["kv_cache_dtype"] == "int8" else 2)


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
    """One stored token of one layer's K and V: codes plus, for int8, one
    f32 scale per head and tensor."""
    scale = 4 if s.kv_bytes == 1 else 0
    return 2 * s.kv_heads * (s.head_dim * s.kv_bytes + scale)


def serve_work(s: Shape, requests, chunk: int, chunk_steps: int,
               decode_steps: int) -> dict:
    """Required work of a serving window, per phase and kernel family.

    ``requests``: (prompt_len, tokens_served, prefilled) per request, where
    ``prefilled`` says its whole prompt went through chunked prefill in the
    window (a request cut mid-prompt adds nothing).  ``chunk_steps`` and
    ``decode_steps`` are the model passes the engine ran: each reads every
    weight once.

    Returns Work for ``qmatmul.prefill``, ``qmatmul.decode``,
    ``attn.prefill``, ``attn.decode``, ``head`` and ``model`` (all
    required model FLOPs, the numerator of an MFU), plus the live row
    counts.
    """
    pre_rows = dec_rows = heads = 0
    attn_pre, attn_dec = Work(), Work()
    hd, nh = s.head_dim, s.heads
    kv_tok = _kv_token_bytes(s)
    for plen, served, prefilled in requests:
        if prefilled:
            pre_rows += plen
            for c0 in range(0, plen, chunk):
                c1 = min(c0 + chunk, plen)
                q = c1 - c0
                # causal: query i attends keys 0..i
                causal = (c0 + 1 + c1) * q // 2
                attn_pre += Work(
                    flops=4 * nh * hd * causal * s.layers,
                    bytes=s.layers * (c0 * kv_tok
                                      + q * 2 * s.kv_heads * hd * 2
                                      + q * 2 * nh * hd * 2))
        if served >= 1:
            heads += served
        if served >= 2 and prefilled:
            steps = served - 1
            dec_rows += steps
            # decode step j (1..steps) attends plen + j keys
            ctx = steps * plen + steps * (steps + 1) // 2
            attn_dec += Work(
                flops=4 * nh * hd * ctx * s.layers,
                bytes=s.layers * (ctx * kv_tok + steps * 2 * nh * hd * 2))
    wb = s.linear_weight_bytes
    q_pre = Work(flops=2 * pre_rows * s.linear_macs,
                 bytes=chunk_steps * wb + pre_rows * s.linear_act_bytes)
    q_dec = Work(flops=2 * dec_rows * s.linear_macs,
                 bytes=decode_steps * wb + dec_rows * s.linear_act_bytes)
    head = Work(flops=2 * heads * s.d * s.vocab,
                bytes=(chunk_steps + decode_steps) * s.vocab * s.d * 2)
    model = Work(flops=q_pre.flops + q_dec.flops + head.flops
                 + attn_pre.flops + attn_dec.flops)
    return {"qmatmul.prefill": q_pre, "qmatmul.decode": q_dec,
            "attn.prefill": attn_pre, "attn.decode": attn_dec,
            "head": head, "model": model,
            "rows.prefill": pre_rows, "rows.decode": dec_rows}

