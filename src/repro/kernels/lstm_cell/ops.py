"""jit'd public ops for the fused LSTM kernels.

``lstm_sequence`` is the entry point the model layer rides
(``repro.models.lstm.forward`` with ``cfg.use_pallas``): fused-sequence
forward, and — via ``jax.custom_vjp`` — a fused Pallas backward, so both
inference *and* the speed layer's cached train step
(``repro.training.compiled.CompiledForecaster``) run kernels end to end.
``lstm_sequence_scan`` is the pre-fusion baseline kept for benchmarks and
the gradient-equivalence oracle tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.lstm_cell.kernel import (
    lstm_cell,
    lstm_sequence_bwd,
    lstm_sequence_fused,
    lstm_sequence_fwd_train,
)


def lstm_step(x_t, h, c, wx, wh, b, interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return lstm_cell(x_t, h, c, wx, wh, b, interpret=interp)


# ---------------------------------------------------------------------------
# lstm_sequence: fused forward + fused backward under one custom VJP
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lstm_sequence(x, wx, wh, b, interpret):
    h, _ = lstm_sequence_fused(x, wx, wh, b, interpret=interpret)
    return h


def _lstm_sequence_fwd(x, wx, wh, b, interpret):
    """Differentiated forward: the residual-emitting kernel.  Residuals are
    the post-activation gates and the full c/h sequences (all f32,
    time-major), so the backward kernel reconstructs the recurrence without
    re-running any matmul."""
    gates, c_seq, h_seq = lstm_sequence_fwd_train(x, wx, wh, b,
                                                  interpret=interpret)
    h = h_seq[-1].astype(x.dtype)
    return h, (x, gates, c_seq, h_seq, wx, wh, b)


def _lstm_sequence_bwd(interpret, res, dh):
    x, gates, c_seq, h_seq, wx, wh, b = res
    dc = jnp.zeros_like(dh, dtype=jnp.float32)  # only final h is a primal out
    dx, dwx, dwh, db = lstm_sequence_bwd(
        x, gates, c_seq, h_seq, wx, wh, dh.astype(jnp.float32), dc,
        interpret=interpret)
    return (dx.astype(x.dtype), dwx.astype(wx.dtype), dwh.astype(wh.dtype),
            db.astype(b.dtype))


_lstm_sequence.defvjp(_lstm_sequence_fwd, _lstm_sequence_bwd)


@partial(jax.jit, static_argnames=("interpret",))
def lstm_sequence(x, wx, wh, b, interpret: bool | None = None):
    """Fused full-sequence LSTM: x (B, T, F) -> final hidden (B, H).

    Shapes/dtypes: ``x`` is (batch, time, features) in f32 or bf16; ``wx`` is
    (F, 4H), ``wh`` (H, 4H), ``b`` (4H,) with Keras gate order (i, f, g, o);
    the result is (B, H) in ``x.dtype`` (compute is f32 inside the kernel).

    Forward: one fused-sequence ``pallas_call`` per batch tile — the time
    loop runs inside the kernel with the (F+H, 4H) weights VMEM-resident
    across all T steps, replacing the per-timestep kernel-launch scan.

    Backward: a ``jax.custom_vjp`` pairing ``lstm_sequence_fwd_train`` (same
    fused forward, additionally emitting gate/state residuals) with the
    fused reverse-time kernel ``lstm_sequence_bwd`` — so differentiating
    through this op (the speed layer's per-window train step) also runs one
    kernel launch per batch tile instead of autodiff-through-scan, which
    would not lower through a compiled Mosaic ``pallas_call`` at all.
    Gradients match autodiff through ``lstm_sequence_scan`` to f32 tolerance
    (oracle test in ``tests/test_kernels.py``).

    ``interpret=None`` resolves via ``repro.kernels.default_interpret()``:
    compiled Mosaic on a real TPU backend, the Pallas interpreter (kernel
    body as traced jnp on the host backend) elsewhere — semantics are
    identical, so CPU CI validates the exact TPU code path.

    Callers: ``repro.models.lstm.forward`` (``use_pallas``), and through it
    the compiled speed-layer hot path and both executors; benchmarked by
    ``benchmarks/bench_hotpath.py``.
    """
    interp = default_interpret() if interpret is None else interpret
    return _lstm_sequence(x, wx, wh, b, interp)


@partial(jax.jit, static_argnames=("interpret",))
def lstm_sequence_scan(x, wx, wh, b, interpret: bool | None = None):
    """The pre-fusion path — ``lax.scan`` over the per-step cell kernel (one
    launch per timestep).  Kept as the launch-overhead baseline the kernel
    tests and benchmarks compare the fused path against; its autodiff (in
    interpret mode) is also the gradient oracle the fused custom VJP is
    asserted against."""
    interp = default_interpret() if interpret is None else interpret
    B = x.shape[0]
    H = wh.shape[0]
    h = jnp.zeros((B, H), x.dtype)
    c = jnp.zeros((B, H), x.dtype)

    def step(carry, xt):
        h, c = carry
        h, c = lstm_cell(xt, h, c, wx, wh, b, interpret=interp)
        return (h, c), None

    (h, c), _ = jax.lax.scan(step, (h, c), x.transpose(1, 0, 2))
    return h
