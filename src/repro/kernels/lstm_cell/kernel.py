"""Fused LSTM kernels: one step (``lstm_cell``) and a whole sequence
(``lstm_sequence_fused``).

The paper's speed layer re-trains a small LSTM inside every 30 s window, so
the recurrence is the latency-critical inner loop.  On TPU the win is fusing
the two matmuls (x@Wx + h@Wh -> one (B, 4H) gate pre-activation) with the
gate nonlinearities and state update in one VMEM-resident kernel: the
weights (F+H, 4H) stay in VMEM and the (B, 4H) intermediate never
round-trips to HBM.

``lstm_cell`` fuses one timestep.  Scanning it over time (the old
``ops.lstm_sequence``) still paid one kernel launch per step and re-staged
the weights every launch.  ``lstm_sequence_fused`` moves the time loop
*inside* a single ``pallas_call``: the (T, bb, F) input block and both
weight blocks are resident for all T steps, the h/c carry lives in
registers/VMEM, and only the final state is written out — one launch per
batch tile for the whole sequence.

Training differentiates through the same fused recurrence:
``lstm_sequence_fwd_train`` is the forward that additionally materializes the
per-step residuals the backward needs (post-activation gates, cell and hidden
sequences), and ``lstm_sequence_bwd`` runs the reverse-time loop in one
``pallas_call`` — producing dx per batch tile and accumulating the weight
gradients (dwx, dwh, db) across the batch grid into broadcast output blocks.
``ops.lstm_sequence`` stitches the pair into a ``jax.custom_vjp`` so the
speed layer's cached train step runs fused kernels end to end instead of
autodiff-through-scan (reverse-mode AD does not lower through a compiled
Mosaic ``pallas_call`` anyway).

Layout: the sequence kernels run **time-major** — the wrappers hand them
(T, B, F) and the residuals stay (T, B, ·) between the forward and the
backward — so step ``t`` reads and writes whole (bb, ·) tiles through ref
indexing (``x_ref[t]``, ``h_out[t] = ...``) on the leading dim.  Mosaic has
no lowering for a value-level dynamic slice, and a per-step slice of a
batch-major (bb, T, F) block would cut across the (8, 128) tiling.  Bias
blocks are 2-D (1, 4H) for the same reason: a 1-D block takes XLA's 1-D
tiling, which Mosaic need not share.

Tiling: grid over batch tiles; weights are broadcast blocks (index_map pins
them to block 0).  MXU alignment: for the paper model (H=40, F=5, T=5) the
shapes are tiny and the kernel is bandwidth-trivial; for wider LSTMs choose
block_b and H multiples of the 8x128 lanes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

_F32 = jnp.float32


def _split_gates(z, h_dim):
    i = jax.nn.sigmoid(z[:, :h_dim])
    f = jax.nn.sigmoid(z[:, h_dim : 2 * h_dim])
    g = jnp.tanh(z[:, 2 * h_dim : 3 * h_dim])
    o = jax.nn.sigmoid(z[:, 3 * h_dim :])
    return i, f, g, o


def _preact(x, h, wx, wh, b):
    """The fused (bb, 4H) gate pre-activation x@Wx + h@Wh + b."""
    z = jnp.dot(x, wx, preferred_element_type=_F32)
    return z + jnp.dot(h, wh, preferred_element_type=_F32) + b


def _cell_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref, h_out, c_out):
    h = h_ref[...].astype(_F32)
    c = c_ref[...].astype(_F32)
    z = _preact(x_ref[...].astype(_F32), h, wx_ref[...].astype(_F32),
                wh_ref[...].astype(_F32), b_ref[...].astype(_F32))
    i, f, g, o = _split_gates(z, h.shape[-1])
    c_new = f * c + i * g
    h_out[...] = (o * jnp.tanh(c_new)).astype(h_out.dtype)
    c_out[...] = c_new.astype(c_out.dtype)


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def lstm_cell(x, h, c, wx, wh, b, *, block_b: int = 128,
              interpret: bool | None = None):
    """One fused LSTM step.  x: (B, F); h, c: (B, H) -> (h', c').

    ``interpret=None`` resolves via ``repro.kernels.default_interpret()``:
    compiled Mosaic on a real TPU backend, interpreter elsewhere."""
    interpret = default_interpret() if interpret is None else interpret
    B, F = x.shape
    H = h.shape[-1]
    bb = min(block_b, B)
    grid = (pl.cdiv(B, bb),)
    return pl.pallas_call(
        _cell_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, F), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((F, 4 * H), lambda i: (0, 0)),  # weights: broadcast
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), h.dtype),
            jax.ShapeDtypeStruct((B, H), c.dtype),
        ],
        interpret=interpret,
    )(x, h, c, wx, wh, b.reshape(1, 4 * H))


def _seq_specs(T, bb, F, H):
    """Time-major input block plus the broadcast weight and bias blocks."""
    return [
        pl.BlockSpec((T, bb, F), lambda i: (0, i, 0)),
        pl.BlockSpec((F, 4 * H), lambda i: (0, 0)),  # weights: broadcast
        pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
        pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
    ]


def _sequence_kernel(x_ref, wx_ref, wh_ref, b_ref, h_out, c_out):
    """Whole-sequence LSTM for one batch tile: time loop inside the kernel,
    weights read once and VMEM-resident across all T steps."""
    T, bb, _ = x_ref.shape                    # (T, bb, F), time-major
    wx = wx_ref[...].astype(_F32)             # (F, 4H)
    wh = wh_ref[...].astype(_F32)             # (H, 4H)
    b = b_ref[...].astype(_F32)               # (1, 4H)
    H = wh.shape[0]

    def step(t, carry):
        h, c = carry
        i, f, g, o = _split_gates(
            _preact(x_ref[t].astype(_F32), h, wx, wh, b), H)
        c = f * c + i * g
        return o * jnp.tanh(c), c

    h0 = jnp.zeros((bb, H), _F32)
    h, c = jax.lax.fori_loop(0, T, step, (h0, h0))
    h_out[...] = h.astype(h_out.dtype)
    c_out[...] = c.astype(c_out.dtype)


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def lstm_sequence_fused(x, wx, wh, b, *, block_b: int = 128,
                        interpret: bool | None = None):
    """Fused full-sequence LSTM.  x: (B, T, F) -> final (h, c), each (B, H).

    One ``pallas_call`` per batch-tile grid step covers all T timesteps —
    versus T launches (and T weight re-stagings) for the scanned per-cell
    kernel this replaces."""
    interpret = default_interpret() if interpret is None else interpret
    B, T, F = x.shape
    H = wh.shape[0]
    bb = min(block_b, B)
    grid = (pl.cdiv(B, bb),)
    return pl.pallas_call(
        _sequence_kernel,
        grid=grid,
        in_specs=_seq_specs(T, bb, F, H),
        out_specs=[
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), x.dtype),
            jax.ShapeDtypeStruct((B, H), x.dtype),
        ],
        interpret=interpret,
    )(x.transpose(1, 0, 2), wx, wh, b.reshape(1, 4 * H))


# ---------------------------------------------------------------------------
# Training pair: residual-emitting forward + fused backward
# ---------------------------------------------------------------------------


def _sequence_train_kernel(x_ref, wx_ref, wh_ref, b_ref,
                           gates_out, c_out, h_out):
    """Forward identical to ``_sequence_kernel`` but materializing the
    backward's residuals step by step: post-activation gates (T, bb, 4H) and
    the full cell and hidden state sequences (T, bb, H) — all f32, so the
    VJP reconstructs the recurrence without re-running any matmul."""
    T, bb, _ = x_ref.shape
    wx = wx_ref[...].astype(_F32)
    wh = wh_ref[...].astype(_F32)
    b = b_ref[...].astype(_F32)
    H = wh.shape[0]

    def step(t, carry):
        h, c = carry
        i, f, g, o = _split_gates(
            _preact(x_ref[t].astype(_F32), h, wx, wh, b), H)
        c = f * c + i * g
        h = o * jnp.tanh(c)
        gates_out[t] = jnp.concatenate([i, f, g, o], axis=-1)
        c_out[t] = c
        h_out[t] = h
        return h, c

    h0 = jnp.zeros((bb, H), _F32)
    jax.lax.fori_loop(0, T, step, (h0, h0))


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def lstm_sequence_fwd_train(x, wx, wh, b, *, block_b: int = 128,
                            interpret: bool | None = None):
    """Residual-emitting forward for the custom VJP.  x: (B, T, F) ->
    time-major residuals (gates (T, B, 4H), c_seq (T, B, H), h_seq
    (T, B, H)), all f32; the primal output is ``h_seq[-1]``."""
    interpret = default_interpret() if interpret is None else interpret
    B, T, F = x.shape
    H = wh.shape[0]
    bb = min(block_b, B)
    grid = (pl.cdiv(B, bb),)
    return pl.pallas_call(
        _sequence_train_kernel,
        grid=grid,
        in_specs=_seq_specs(T, bb, F, H),
        out_specs=[
            pl.BlockSpec((T, bb, 4 * H), lambda i: (0, i, 0)),
            pl.BlockSpec((T, bb, H), lambda i: (0, i, 0)),
            pl.BlockSpec((T, bb, H), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, 4 * H), _F32),
            jax.ShapeDtypeStruct((T, B, H), _F32),
            jax.ShapeDtypeStruct((T, B, H), _F32),
        ],
        interpret=interpret,
    )(x.transpose(1, 0, 2), wx, wh, b.reshape(1, 4 * H))


def _sequence_bwd_kernel(x_ref, gates_ref, c_ref, h_ref, wx_ref, wh_ref,
                         dh_ref, dc_ref, dx_out, dwx_out, dwh_out, db_out):
    """Reverse-time loop for one batch tile.  dx is written per tile and
    step; the weight gradients are *accumulated across the batch grid*:
    their output blocks are pinned to block 0, zeroed on the first grid
    step, and read-modify-written at every step of every later one (the TPU
    grid is sequential, so revisited output blocks persist — the standard
    reduction pattern).

    Batch padding rows are exactly zero in every input (the ops wrapper pads
    with zeros), which makes their dz — and hence their contribution to the
    accumulated weight gradients — exactly zero too."""
    T = x_ref.shape[0]
    wx = wx_ref[...].astype(_F32)             # (F, 4H)
    wh = wh_ref[...].astype(_F32)             # (H, 4H)
    H = wh.shape[0]
    # contract the leading (batch) dims: x_t^T @ dz without a transpose op
    tn = (((0,), (0,)), ((), ()))
    # contract the trailing (gate) dims: dz @ W^T without a transpose op
    nt = (((1,), (1,)), ((), ()))

    @pl.when(pl.program_id(0) == 0)
    def _init_weight_grads():
        dwx_out[...] = jnp.zeros_like(dwx_out)
        dwh_out[...] = jnp.zeros_like(dwh_out)
        db_out[...] = jnp.zeros_like(db_out)

    def step(s, carry):
        dh, dc = carry
        t = T - 1 - s
        t_prev = jnp.maximum(t - 1, 0)
        g4 = gates_ref[t]                     # (bb, 4H) f32
        i, f = g4[:, :H], g4[:, H : 2 * H]
        g, o = g4[:, 2 * H : 3 * H], g4[:, 3 * H :]
        c_t = c_ref[t]
        first = t == 0
        c_prev = jnp.where(first, 0.0, c_ref[t_prev])
        h_prev = jnp.where(first, 0.0, h_ref[t_prev])

        tanh_c = jnp.tanh(c_t)
        do = dh * tanh_c
        dct = dc + dh * o * (1.0 - tanh_c * tanh_c)
        dz = jnp.concatenate(
            [dct * g * i * (1.0 - i),            # d z_i
             dct * c_prev * f * (1.0 - f),       # d z_f
             dct * i * (1.0 - g * g),            # d z_g
             do * o * (1.0 - o)],                # d z_o
            axis=-1)                             # (bb, 4H)

        x_t = x_ref[t].astype(_F32)
        dwx_out[...] += jax.lax.dot_general(x_t, dz, tn,
                                            preferred_element_type=_F32)
        dwh_out[...] += jax.lax.dot_general(h_prev, dz, tn,
                                            preferred_element_type=_F32)
        db_out[...] += jnp.sum(dz, axis=0, keepdims=True)
        dx_out[t] = jax.lax.dot_general(
            dz, wx, nt, preferred_element_type=_F32).astype(dx_out.dtype)
        dh = jax.lax.dot_general(dz, wh, nt, preferred_element_type=_F32)
        return dh, dct * f

    jax.lax.fori_loop(0, T, step, (dh_ref[...].astype(_F32),
                                   dc_ref[...].astype(_F32)))


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def lstm_sequence_bwd(x, gates, c_seq, h_seq, wx, wh, dh, dc, *,
                      block_b: int = 128, interpret: bool | None = None):
    """Fused backward pass over the whole sequence.

    Inputs are the primal ``x`` (B, T, F) plus the time-major residuals
    ``lstm_sequence_fwd_train`` emitted and the cotangents of the final
    ``(h, c)``; returns ``(dx (B, T, F), dwx (F, 4H), dwh (H, 4H), db
    (4H,))``, all f32.  The batch is zero-padded to a tile multiple here so
    padded rows contribute exact zeros to the grid-accumulated weight
    gradients."""
    interpret = default_interpret() if interpret is None else interpret
    B, T, F = x.shape
    H = wh.shape[0]
    bb = min(block_b, B)
    pad = (-B) % bb
    x = x.transpose(1, 0, 2)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        gates = jnp.pad(gates, ((0, 0), (0, pad), (0, 0)))
        c_seq = jnp.pad(c_seq, ((0, 0), (0, pad), (0, 0)))
        h_seq = jnp.pad(h_seq, ((0, 0), (0, pad), (0, 0)))
        dh = jnp.pad(dh, ((0, pad), (0, 0)))
        dc = jnp.pad(dc, ((0, pad), (0, 0)))
    Bp = B + pad
    grid = (Bp // bb,)
    dx, dwx, dwh, db = pl.pallas_call(
        _sequence_bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, bb, F), lambda i: (0, i, 0)),
            pl.BlockSpec((T, bb, 4 * H), lambda i: (0, i, 0)),
            pl.BlockSpec((T, bb, H), lambda i: (0, i, 0)),
            pl.BlockSpec((T, bb, H), lambda i: (0, i, 0)),
            pl.BlockSpec((F, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((T, bb, F), lambda i: (0, i, 0)),
            pl.BlockSpec((F, 4 * H), lambda i: (0, 0)),   # accumulated
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),   # accumulated
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),   # accumulated
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, Bp, F), _F32),
            jax.ShapeDtypeStruct((F, 4 * H), _F32),
            jax.ShapeDtypeStruct((H, 4 * H), _F32),
            jax.ShapeDtypeStruct((1, 4 * H), _F32),
        ],
        interpret=interpret,
    )(x, gates, c_seq, h_seq, wx, wh, dh, dc)
    return dx[:, :B].transpose(1, 0, 2), dwx, dwh, db.reshape(4 * H)
