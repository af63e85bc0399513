"""LoRDS kernels: roofline share of every quantized linear's kernel
(``lords_matmul`` for M > 8 rows, ``lords_decode`` for M <= 8), prefill and
decode phases each bound by the larger of FLOPs / peak and
bytes / bandwidth."""
from chipbench.layer_metrics._roofline import share

FAMILY = "qmatmul"
OPS = ("lords_matmul_pallas", "lords_decode_pallas")


def read(ctx):
    return share(ctx, OPS, ("qmatmul.prefill", "qmatmul.decode"))
