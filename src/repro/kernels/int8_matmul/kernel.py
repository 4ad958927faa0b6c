"""Int8 weight-dequantizing matmul — TPU Pallas.

The quantized edge-serving path (repro.serving.quantize, the TFLite-on-Pi
analog) computes y = x @ (q * scale) with q int8 and a per-output-channel
f32 scale.  Fusing the dequantization into the matmul halves (vs bf16) /
quarters (vs f32) the weight HBM traffic — the dominant cost of small-batch
edge inference — and applies the scale once per output column after the
K-loop instead of once per weight.

Tiling: (block_m, block_n) output tiles on the first two grid axes; the K
dimension is the last grid axis, streamed through VMEM in block_k slices
into an f32 accumulator scratch (zeroed at the first K step, scaled and
written out at the last).  A dimension no larger than its block is taken
whole — a full-dim block is always a legal Mosaic tile — so the
forecaster's (K, N) weights (5x160, 40x160, 40x10, 10x1) run as one block
each, with no padding.  The scale rides a 2-D (1, block_n) block.  int8
weights are converted to f32 in VREGs right before the MXU dot (TPU int8
MXU paths need quantized activations too; weight-only quantization keeps
activations f32/bf16, which is what the forecaster accuracy test pins).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), q_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def int8_matmul(
    x: jax.Array,  # (M, K) float
    q: jax.Array,  # (K, N) int8
    scale: jax.Array,  # (N,) f32
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ (q * scale)``.  ``interpret=None`` resolves via
    ``repro.kernels.default_interpret()``: compiled Mosaic on a real TPU
    backend, the Pallas interpreter elsewhere."""
    interpret = default_interpret() if interpret is None else interpret
    M, K = x.shape
    K2, N = q.shape
    if K != K2 or scale.shape != (N,):
        raise ValueError(f"int8_matmul shapes disagree: x {x.shape}, "
                         f"q {q.shape}, scale {scale.shape}")
    bm, bn = min(block_m, M), min(block_n, N)
    bk = min(block_k, K)
    # pad every dim to its block multiple (zero padding is exact here)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pn or pk:
        q = jnp.pad(q, ((0, pk), (0, pn)))
    if pn:
        scale = jnp.pad(scale, (0, pn))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    out = pl.pallas_call(
        _kernel,
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, q, scale.reshape(1, Np))
    return out[:M, :N]
