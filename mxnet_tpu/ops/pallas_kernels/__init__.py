"""Hand-written TPU Pallas kernels for the hot ops.

The reference's hot path was mshadow expression templates + cuDNN
(`src/operator/fully_connected-inl.h`, `cudnn_convolution-inl.h`).  On TPU
XLA already fuses elementwise chains into matmuls/convs; these kernels cover
the cases where explicit VMEM blocking beats XLA's default schedule —
attention above all (the S x S score matrix must never touch HBM).

`flash_attention` (training and prefill's full-sequence attention),
`fused_ce` (the softmax cross-entropy head), `layer_norm`,
`paged_attention` (serving's single-token decode attention over the paged
K/V pool, read in place: `ops.attention.paged_decode_attention`), and
`expert_ffn` (serving's routed experts, one call a layer:
`ops.moe.held_share`).

Every kernel has a pure-jnp blockwise fallback with identical math, used on
non-TPU backends (the 8-device CPU test mesh) and as the reference in tests.
"""
# module aliases first: the function re-exports below shadow the
# submodule names on the package, so kernel-internal consumers (tests,
# preflight, diagnostics) import these instead of importlib workarounds
from . import flash_attention as flash_attention_mod
from . import fused_ce as fused_ce_mod
from . import paged_attention as paged_attention_mod
from .flash_attention import flash_attention
from .fused_ce import fused_softmax_ce

__all__ = ["flash_attention", "fused_softmax_ce",
           "flash_attention_mod", "fused_ce_mod", "paged_attention_mod"]
