"""Compile-once training hot path for the speed layer.

The legacy ``fit`` (``train_loop.py``) rebuilds ``jax.jit(make_train_step)``
on every call, so every 30 s stream window pays a fresh XLA trace+compile,
and its Python minibatch loop pays ``epochs x steps`` device dispatches.
That is exactly the cost the paper's Table-3 latency claim says the speed
layer cannot afford: at the edge the steady-state per-window cost is the
quantity that matters, not the cold start.

``CompiledForecaster`` makes the per-window path compile exactly once and
stay dispatch-light forever after:

* **one executable per shape bucket** — windows are padded up to a small
  set of fixed shape buckets (``bucket_examples``: the next power-of-two
  multiple of ``batch_size``), with a per-example validity mask threaded
  into the model's ``loss_fn`` so padding never biases the gradient.  Every
  window of the stream therefore hits the same compiled executable, and the
  ragged final batch the legacy iterator dropped is trained on.
* **one dispatch per fit** — the whole fit (epoch permutations, minibatch
  gather, ``epochs x steps`` optimizer updates) is a single jitted
  ``lax.scan`` over a device-resident pre-permuted epoch index tensor,
  instead of a Python loop dispatching one step at a time.
* **donated buffers** — params and optimizer state are donated
  (``donate_argnums``) so the update runs in place where the backend
  supports it.
* **counted retraces** — every cache entry counts its actual traces (the
  Python body only runs when XLA traces it), so benchmarks and regression
  tests can assert that windows 2..N of a shape bucket perform zero new
  traces.

``FleetForecaster`` lifts the same hot path to a *fleet* of streams: the
whole fleet's speed models train in **one** device dispatch per window — a
vmapped cold-start fit over a stacked leading stream axis, cached per
(stream-count bucket, shape bucket).  Stream-count padding works exactly
like batch padding: padded stream slots carry an all-zero validity mask, so
they contribute zero loss and zero gradient and their (discarded) params
never move.

The fleet hot path is memory-resident across windows:

* **staged device buffers** — each window's examples are written into a
  persistent per-(stream bucket, shape bucket) staging buffer and shipped
  in one transfer, instead of re-padding and re-``np.stack``-ing a fresh
  fleet batch every window (``staging_allocs`` counts buffer allocations;
  after a bucket's first window it stays flat).
* **device-resident stacked params** — ``train_fleet`` returns lazy
  :class:`FleetParamView`\\ s over the stacked fit output; per-stream host
  pytrees materialize only when something actually needs one (a model-topic
  publish, a byte count), while the serving path (``predict_fleet``) reads
  the stacked tree directly with zero re-stacking.  The optimizer state is
  donated through the train step: each window's fit consumes the previous
  window's opt-state buffers in place.
* **a local device mesh** — when the process exposes more than one device
  (a TPU slice, or CPU cores surfaced via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` as
  ``benchmarks/bench_fleet.py`` does), the stacked stream axis is sharded
  across the largest power-of-two device prefix that divides the stream
  bucket.  All of it — the mesh, the stacked-batch sharding, and the
  leaf-wise shardings of the donated opt-state carry — resolves through
  ``repro.distributed.sharding``'s logical-axis rules (``stream_mesh`` /
  ``stream_sharding`` / ``fleet_param_shardings``), the same
  divisibility-aware table the model zoo shards under, so staged host
  buffers, the fit executable, and ``predict_fleet`` serving all carry
  explicit shardings from one place.  Per-stream numerics are bitwise
  identical to the single-device vmap — streams never interact — but the
  fleet fit and the fleet predict run data-parallel across the mesh.
* **O(1) host dispatches per window** — the per-stream init/perm key
  derivation (``split``/``fold_in`` per stream, O(S) device round-trips)
  is one batched jitted dispatch over the stacked key rows, and per-stream
  param materialization (a publish boundary, a byte count) is one
  ``device_get`` of the stacked tree that every sibling
  :class:`FleetParamView` slices from, instead of S separate
  slice-and-transfer chains.

``predict_fleet`` is the serving-side counterpart of ``train_fleet``: the
whole fleet's per-stream predictions in **one** vmapped dispatch, cached
per (stream bucket, inference shape bucket), with the same stream/batch
padding discipline (padded slots and padded rows are sliced away before
anything observable).
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.distributed.sharding import (
    fleet_param_shardings,
    stream_mesh_size,
    stream_sharding,
)
from repro.models.model import Model
from repro.training.optimizer import Optimizer, adamw
from repro.training.train_loop import make_train_step

Params = Any


def bucket_examples(n: int, batch_size: int) -> int:
    """Fixed-shape bucket for an ``n``-example window: the next power-of-two
    multiple of ``batch_size``.  Buckets grow geometrically, so a stream of
    arbitrary window sizes touches only O(log n) compiled executables, and
    the paper's fixed-size windows (150/250 records) always reuse one."""
    if n <= 0:
        raise ValueError(f"cannot bucket an empty window (n={n})")
    per = max(1, math.ceil(n / batch_size))
    return batch_size * (1 << max(0, math.ceil(math.log2(per))))


def pad_to_bucket(data: Dict[str, np.ndarray], nb: int) -> Dict[str, np.ndarray]:
    """Zero-pad every array's leading dim to ``nb`` and attach a f32 validity
    ``mask`` (1 for real examples, 0 for padding)."""
    n = len(next(iter(data.values())))
    if n > nb:
        raise ValueError(f"window of {n} examples exceeds bucket {nb}")
    out = {}
    for k, v in data.items():
        v = np.asarray(v)
        if n < nb:
            pad = np.zeros((nb - n,) + v.shape[1:], v.dtype)
            v = np.concatenate([v, pad], axis=0)
        out[k] = v
    mask = np.zeros((nb,), np.float32)
    mask[:n] = 1.0
    out["mask"] = mask
    return out


def _next_pow2(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(n, 1))))


def bucket_streams(s: int) -> int:
    """Stream-count bucket for an ``s``-stream fleet training batch: the next
    power of two.  Like shape buckets, stream-count buckets grow
    geometrically, so a fleet of any size — or any drift-gated *subset* of
    it — touches only O(log S) compiled fleet executables."""
    if s <= 0:
        raise ValueError(f"cannot bucket an empty fleet (s={s})")
    return _next_pow2(s)


def stream_mesh_devices(sb: int) -> List[Any]:
    """The device prefix the fleet's stacked stream axis shards over: the
    largest power of two that both divides the stream bucket ``sb`` and
    fits the local device count (``distributed.sharding.stream_mesh_size``
    owns the arithmetic — a bucket smaller than the host's device count
    caps at its own pow2 divisor, never an indivisible sharding).  One
    device (the tests' configuration) degrades to no sharding."""
    devs = jax.devices()
    return devs[:stream_mesh_size(sb, len(devs))]


class _FleetStack:
    """Owner of one fleet fit's stacked, device-resident params pytree.
    ``stacked`` keeps a leading stream-bucket axis (possibly sharded across
    the local mesh); views slice it lazily, from a host copy materialized
    **once** for the whole bucket."""

    __slots__ = ("stacked", "_host")

    def __init__(self, stacked: Params):
        self.stacked = stacked
        self._host: Optional[Params] = None

    def dim(self) -> int:
        return int(jax.tree_util.tree_leaves(self.stacked)[0].shape[0])

    def host(self) -> Params:
        """The stacked tree on the host (cached): one ``device_get`` per
        fit output, however many of its streams materialize — the publish
        fan-out at S=1k is S numpy slice views of this copy, not S
        per-stream device slice-and-transfer chains."""
        if self._host is None:
            self._host = jax.tree_util.tree_map(
                np.asarray, jax.device_get(self.stacked))
        return self._host


class FleetParamView:
    """One stream's params inside a device-resident stacked fleet pytree.

    Semantically this *is* the per-stream params tree — it registers as a
    pytree whose flatten materializes the slice, so ``tree_map``, ``jit``,
    byte counts and ``quantize_tree`` all see the ordinary per-stream tree
    — but materialization is lazy: until a publish boundary (or any other
    consumer) flattens it, no per-stream host pytree exists, and
    ``predict_fleet`` recognizes sibling views of one stacked buffer and
    serves the whole fleet from it with zero re-stacking.

    A view keeps its owner's stacked tree alive even after materializing
    (the zero-restack serving path needs it); a long-lived straggler view
    therefore pins its fit's whole stacked tree — a deliberate trade at
    speed-model scale, where a stacked fleet tree is a few hundred KB."""

    __slots__ = ("owner", "slot", "_tree")

    def __init__(self, owner: _FleetStack, slot: int):
        self.owner = owner
        self.slot = slot
        self._tree: Optional[Params] = None

    def tree(self) -> Params:
        """The materialized per-stream params pytree (cached): host numpy
        views sliced from the owner's one batched ``device_get`` — the
        first materialization of *any* sibling pays the transfer once for
        the whole bucket."""
        if self._tree is None:
            j = self.slot
            self._tree = jax.tree_util.tree_map(lambda a: a[j],
                                                self.owner.host())
        return self._tree

    # the per-stream tree's mapping surface, for eager callers that index
    # params directly (e.g. model.loss_fn outside jit)
    def __getitem__(self, key):
        return self.tree()[key]

    def keys(self):
        return self.tree().keys()


jax.tree_util.register_pytree_node(
    FleetParamView,
    lambda v: ((v.tree(),), None),
    lambda aux, ch: ch[0],
)


def materialize_params(params: Params) -> Params:
    """Resolve a (possibly lazy) per-stream params handle to a plain
    pytree.  Plain trees pass through untouched."""
    return params.tree() if isinstance(params, FleetParamView) else params


def _staging_buffer(cache: Dict[Tuple, np.ndarray], key: Tuple,
                    shape: Tuple[int, ...], dtype) -> Tuple[np.ndarray, bool]:
    """Get-or-allocate a persistent host staging buffer; returns the buffer
    and whether this call allocated it (the caller counts allocations)."""
    buf = cache.get(key)
    if buf is not None:
        return buf, False
    buf = np.zeros(shape, dtype)
    cache[key] = buf
    return buf, True


def _make_epoch_scan(model: Model, opt: Optimizer, epochs: int,
                     batch_size: int, nb: int):
    """The pure epoch-scan fit body shared by the single-stream and fleet
    trainers: the whole fit (per-epoch permutations, minibatch gather,
    ``epochs x steps`` optimizer updates) is one ``lax.scan`` over a
    device-resident pre-permuted epoch index tensor."""
    steps = nb // batch_size
    train_step = make_train_step(model, opt)

    def epoch_scan_fit(params, opt_state, x, y, mask, rng):
        perms = jax.vmap(lambda k: jax.random.permutation(k, nb))(
            jax.random.split(rng, epochs))
        idx = perms.reshape(epochs * steps, batch_size)

        def body(carry, ib):
            params, opt_state = carry
            batch = {"x": x[ib], "y": y[ib], "mask": mask[ib]}
            params, opt_state, metrics = train_step(params, opt_state,
                                                    batch)
            return (params, opt_state), metrics["loss"]

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), idx)
        return params, opt_state, losses

    return epoch_scan_fit


class CompiledForecaster:
    """Speed-layer trainer with a compile-once, dispatch-light hot path.

    Matches the ``Forecaster`` protocol (``train(data, params, key) ->
    (params, wall_s)``; ``predict(params, x) -> np.ndarray``) so it drops
    into ``SpeedTraining`` / both executors unchanged.  The jitted epoch-scan
    executable is cached per shape bucket — model, optimizer, epochs and
    batch size are fixed per instance, so the effective cache key is
    (model, optimizer, batch shape); warm and cold starts share the same
    executable.

    The model's ``loss_fn`` must honor an optional per-example ``mask`` key
    in the batch (as ``repro.models.lstm.loss_fn`` does) whenever a window
    needs padding; the first padded window of each bucket runs a one-time
    numeric check and raises if the mask is ignored, so a mask-blind model
    can never be silently biased toward its padding.
    """

    def __init__(
        self,
        model: Model,
        *,
        epochs: int,
        batch_size: int,
        lr: float = 1e-3,
        opt: Optional[Optimizer] = None,
        warm_start: bool = False,
        predict_fn: Optional[Callable[[Params, jax.Array], jax.Array]] = None,
    ):
        self.model = model
        self.epochs = int(epochs)
        self.batch_size = int(batch_size)
        self.warm_start = warm_start
        self.opt = opt or adamw(lr)
        self._fit_cache: Dict[int, Callable] = {}
        self._trace_counts: Dict[int, int] = {}
        self._mask_checked: set = set()
        self._init_fn = jax.jit(model.init)
        self._opt_init = jax.jit(self.opt.init)
        self._predict_raw = predict_fn
        self._predict_traces: Dict[int, int] = {}
        if predict_fn is not None:
            traces = self._predict_traces

            def counted_predict(params, x):
                # executes only while XLA traces — counts real retraces per
                # inference shape bucket (a new params *structure*, e.g. an
                # int8 QTensor tree, traces its bucket once more)
                traces[x.shape[0]] = traces.get(x.shape[0], 0) + 1
                return predict_fn(params, x)

            self._predict_fn: Optional[Callable] = jax.jit(counted_predict)
        else:
            self._predict_fn = None
        self._predict_bufs: Dict[Tuple, np.ndarray] = {}
        self._dequant_cache: Optional[Tuple[Any, Params]] = None
        self.staging_allocs = 0
        self.last_losses: Optional[np.ndarray] = None

    # -- compile-cache introspection ----------------------------------------

    @property
    def retrace_count(self) -> int:
        """Total XLA traces of the fit executable across all shape buckets."""
        return sum(self._trace_counts.values())

    @property
    def cache_size(self) -> int:
        return len(self._fit_cache)

    def trace_counts(self) -> Dict[int, int]:
        """Per-shape-bucket XLA trace counts."""
        return dict(self._trace_counts)

    def predict_trace_counts(self) -> Dict[int, int]:
        """Per-inference-shape-bucket XLA trace counts of the predict
        executable."""
        return dict(self._predict_traces)

    # -- the cached fit executable ------------------------------------------

    def _fit_fn(self, nb: int) -> Callable:
        """One executable per bucket ``nb``; warm and cold starts share it
        (params enter as an argument either way)."""
        fn = self._fit_cache.get(nb)
        if fn is not None:
            return fn
        scan_fit = _make_epoch_scan(self.model, self.opt, self.epochs,
                                    self.batch_size, nb)
        counts = self._trace_counts
        counts.setdefault(nb, 0)

        def epoch_scan_fit(params, opt_state, x, y, mask, rng):
            # executes only while XLA traces — counts real retraces
            counts[nb] += 1
            return scan_fit(params, opt_state, x, y, mask, rng)

        fn = jax.jit(epoch_scan_fit, donate_argnums=(0, 1))
        self._fit_cache[nb] = fn
        return fn

    def _check_mask_honored(self, data: Dict[str, np.ndarray],
                            padded: Dict[str, np.ndarray], params: Params,
                            nb: int) -> None:
        """One-time (per bucket) guard: when a window actually needed
        padding, the masked loss on the padded batch must equal the plain
        loss on the unpadded batch.  A model whose ``loss_fn`` ignores the
        validity mask would otherwise silently average its padding rows into
        every gradient."""
        n = len(next(iter(data.values())))
        if n == nb or nb in self._mask_checked:
            return
        plain, _ = self.model.loss_fn(
            params, {k: jnp.asarray(v) for k, v in data.items()})
        masked, _ = self.model.loss_fn(
            params, {k: jnp.asarray(v) for k, v in padded.items()})
        if not np.allclose(np.asarray(plain), np.asarray(masked),
                           rtol=1e-4, atol=1e-6):
            raise ValueError(
                "model.loss_fn ignores the per-example validity 'mask': "
                f"padded-batch loss {float(masked):.6g} != unpadded loss "
                f"{float(plain):.6g}. Fixed-shape bucketing would bias "
                "training toward the padding; thread batch['mask'] into the "
                "loss as repro.models.lstm.loss_fn does.")
        self._mask_checked.add(nb)

    # -- Forecaster protocol -------------------------------------------------

    def train(self, data: Dict[str, np.ndarray], params: Optional[Params],
              key: jax.Array) -> Tuple[Params, float]:
        t0 = time.perf_counter()
        n = len(next(iter(data.values())))
        nb = bucket_examples(n, self.batch_size)
        init_key, perm_key = jax.random.split(key)
        warm = self.warm_start and params is not None
        if warm:
            # an int8-synced serving model (QTensor leaves) can seed a warm
            # start, but training runs in float: dequantize first
            from repro.serving.quantize import dequantize_tree

            params = dequantize_tree(params)
            # the fit executable donates its params buffer; the caller-held
            # tree (the serving model) must survive, so warm starts hand the
            # executable a private copy
            params = jax.tree_util.tree_map(jnp.array, params)
        else:
            params = self._init_fn(init_key)
        opt_state = self._opt_init(params)
        padded = pad_to_bucket(data, nb)
        self._check_mask_honored(data, padded, params, nb)
        params, _, losses = self._fit_fn(nb)(
            params, opt_state,
            jnp.asarray(padded["x"]), jnp.asarray(padded["y"]),
            jnp.asarray(padded["mask"]), perm_key)
        jax.block_until_ready(params)
        self.last_losses = np.asarray(losses)
        return params, time.perf_counter() - t0

    def _stage_predict(self, x: np.ndarray) -> np.ndarray:
        """Pad ``x`` up to its shape bucket in a persistent per-bucket host
        staging buffer — a ragged final batch costs one row copy plus a pad
        memset, never a fresh concatenate allocation, so steady-state
        serving neither retraces nor re-stages."""
        n = x.shape[0]
        nb = _next_pow2(n)  # bucket inference shapes too: O(log n) compiles
        key = (nb,) + x.shape[1:] + (x.dtype.str,)
        buf, allocated = _staging_buffer(self._predict_bufs, key,
                                         (nb,) + x.shape[1:], x.dtype)
        self.staging_allocs += allocated
        np.copyto(buf[:n], x)
        buf[n:] = 0
        return buf

    def _serving_params(self, params: Params) -> Params:
        """The params tree the predict executable actually serves.

        On a real TPU an int8 ``QTensor`` tree serves as-is: the fused
        dequant-accumulate ``int8_matmul`` kernel is the fast path.  On an
        interpret-mode backend (CPU CI, this container) the per-scan-step
        int8 recurrent matmul runs through the Pallas interpreter and a
        quantized predict *trailed* the float one ~1.6x (the gap
        BENCH_hotpath flagged); there the sync payload is still int8 — the
        4x transfer saving is the point of quantized sync — but serving
        dequantizes once per synced model and reuses the float executable,
        so steady-state int8 predict matches float exactly.  The cache is
        identity-keyed on the params object: the serving model is stable
        between model syncs, so every predict after the first is a pure
        cache hit (``BENCH_hotpath.json`` gates the ratio)."""
        hit = self._dequant_cache
        if hit is not None and hit[0] is params:
            # steady-state serving: same installed model as last predict —
            # no leaf scan, no backend probe
            return hit[1]
        from repro.kernels import default_interpret

        if not default_interpret():
            return params
        from repro.serving.quantize import QTensor, dequantize_tree

        is_q = lambda v: isinstance(v, QTensor)
        if not any(is_q(l) for l in
                   jax.tree_util.tree_leaves(params, is_leaf=is_q)):
            return params
        deq = dequantize_tree(params)
        self._dequant_cache = (params, deq)
        return deq

    def predict(self, params: Params, x: np.ndarray) -> np.ndarray:
        if self._predict_fn is None:
            raise ValueError("CompiledForecaster built without a predict_fn")
        x = np.asarray(x)
        n = x.shape[0]
        buf = self._stage_predict(x)
        params = self._serving_params(params)
        return np.asarray(self._predict_fn(params, jnp.asarray(buf)))[:n]


class FleetForecaster:
    """Fleet-axis trainer: one speed model per stream, the whole fleet fit
    in **one device dispatch** per window.

    Wraps a single-stream :class:`CompiledForecaster` (exposed as
    ``.single``, and via delegating ``train``/``predict`` so a
    ``FleetForecaster`` satisfies the ``Forecaster`` protocol anywhere a
    single-stream trainer is expected).  ``train_fleet`` stacks the fleet's
    padded windows along a new leading stream axis and runs a vmapped
    cold-start fit — per-stream param init, optimizer init, and the shared
    epoch-scan body — inside a single jitted executable, cached per
    (stream-count bucket, shape bucket):

    * the per-stream key derivation (``init_key, perm_key = split(key)``)
      is byte-identical to the single-stream path, so stream ``i`` of a
      fleet fit trains from the same init, with the same minibatch
      permutations, as a sequential ``CompiledForecaster.train`` given the
      same key — fleet-vs-sequential parity is a numerical (vmap batching)
      tolerance, not a semantic difference;
    * the stream axis is padded up to ``bucket_streams(s)`` with zero-data,
      all-zero-mask slots, exactly like batch padding: a padded slot's loss
      and gradient are exactly zero, so its (discarded) params never move
      and the optimizer's global-norm clip is unaffected;
    * streams whose windows fall in different *shape* buckets are grouped,
      one dispatch per group — a homogeneous fleet (the paper's fixed-size
      windows) always trains in exactly one;
    * a single-stream group (s == 1) delegates to the wrapped
      ``CompiledForecaster``, keeping the single-stream path byte-identical
      to the pre-fleet code.

    ``train_dispatches`` counts fit-executable invocations (what
    ``benchmarks/bench_fleet.py`` asserts is one per window for a
    homogeneous fleet); ``trace_counts`` exposes per-bucket XLA traces so
    the zero-retrace-after-first-window property stays testable.

    The hot path is memory-resident across windows (see the module
    docstring): window data is staged into persistent stacked buffers and
    shipped in one transfer per tensor, the previous window's optimizer
    state is donated back into the fit executable, the stacked fit output
    stays device-resident behind lazy :class:`FleetParamView` handles, and
    both the fit and ``predict_fleet`` shard the stream axis across the
    local device mesh when one exists.  ``predict_fleet`` serves the whole
    fleet's per-stream predictions in one dispatch (``predict_dispatches``
    counts them; ``predict_trace_counts`` exposes the per-bucket traces).
    """

    def __init__(
        self,
        model: Model,
        *,
        epochs: int,
        batch_size: int,
        lr: float = 1e-3,
        opt: Optional[Optimizer] = None,
        predict_fn: Optional[Callable[[Params, jax.Array], jax.Array]] = None,
        devices: Optional[Sequence[Any]] = None,
    ):
        self.single = CompiledForecaster(
            model, epochs=epochs, batch_size=batch_size, lr=lr, opt=opt,
            predict_fn=predict_fn)
        self.model = model
        # the devices the stream axis may shard over (default: all local
        # devices); one device pins the fleet to it, unsharded
        self.devices = list(devices) if devices is not None else None
        self.epochs = int(epochs)
        self.batch_size = int(batch_size)
        self.opt = self.single.opt
        self._fleet_cache: Dict[Tuple[int, int], Callable] = {}
        self._trace_counts: Dict[Tuple[int, int], int] = {}
        self._carry_cache: Dict[int, Callable] = {}
        # persistent host staging buffers, stacked opt-state carries, and
        # stream shardings, all keyed per bucket — the device-resident state
        self._train_bufs: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
        self._opt_carry: Dict[Tuple[int, int], Any] = {}
        self._shardings: Dict[int, Optional[NamedSharding]] = {}
        self._key_cache: Dict[int, Callable] = {}
        self._predict_cache: Dict[int, Callable] = {}
        self._predict_traces: Dict[Tuple[int, int], int] = {}
        self._predict_bufs: Dict[Tuple, np.ndarray] = {}
        self._stack_tree_cache: Dict[Tuple, Tuple] = {}
        self._staging_allocs = 0
        self.train_dispatches = 0
        self.predict_dispatches = 0
        # per-stream minibatch-loss trajectories of the last train_fleet call
        self.last_losses: Optional[List[Optional[np.ndarray]]] = None

    # -- Forecaster protocol (the fleet's single-stream view) ----------------

    def train(self, data: Dict[str, np.ndarray], params: Optional[Params],
              key: jax.Array) -> Tuple[Params, float]:
        return self.single.train(data, params, key)

    def predict(self, params: Params, x: np.ndarray) -> np.ndarray:
        return self.single.predict(params, x)

    # -- compile-cache introspection ----------------------------------------

    @property
    def retrace_count(self) -> int:
        """Fleet-executable XLA traces across all (stream, shape) buckets
        (the delegated single-stream path counts its own)."""
        return sum(self._trace_counts.values())

    @property
    def cache_size(self) -> int:
        return len(self._fleet_cache)

    def trace_counts(self) -> Dict[Tuple[int, int], int]:
        """Per-(stream-count bucket, shape bucket) XLA trace counts."""
        return dict(self._trace_counts)

    def predict_trace_counts(self) -> Dict[Tuple[int, int], int]:
        """Per-(stream bucket, inference shape bucket) XLA trace counts of
        the fleet predict executable."""
        return dict(self._predict_traces)

    @property
    def staging_allocs(self) -> int:
        """Total host staging-buffer allocations (fleet train + fleet
        predict + the wrapped single-stream trainer's predict buffers).
        Steady-state windows of a known bucket allocate nothing: data is
        re-staged into the same buffers, never re-stacked."""
        return self._staging_allocs + self.single.staging_allocs

    # -- the device mesh and the staged buffers ------------------------------

    def _stream_sharding(self, sb: int) -> Optional[NamedSharding]:
        """The stream-axis sharding for bucket ``sb`` over the local device
        mesh, or None on a single device — resolved through
        ``distributed.sharding.stream_sharding`` (the logical-axis rules
        with divisibility-aware fallback), cached per bucket.  Streams are
        independent, so sharding the stacked axis is pure data parallelism
        — bitwise the same per-stream numerics as the unsharded vmap."""
        if sb not in self._shardings:
            self._shardings[sb] = stream_sharding(sb, self.devices)
        return self._shardings[sb]

    def _put(self, a: np.ndarray, sb: int):
        shard = self._stream_sharding(sb)
        return jnp.asarray(a) if shard is None else jax.device_put(a, shard)

    def _train_staging(self, sb: int, nb: int,
                       data0: Dict[str, np.ndarray],
                       key0) -> Dict[str, np.ndarray]:
        """The persistent stacked staging buffers for one (stream bucket,
        shape bucket): x/y/mask plus the per-stream base-key rows and
        pad-slot fold ids the batched key derivation consumes.  Allocated
        once per bucket (counted), refilled in place every window."""
        bufs = self._train_bufs.get((sb, nb))
        if bufs is None:
            # one bundle of arrays per bucket, counted as one allocation
            karr = np.asarray(key0)
            bufs = {"mask": np.zeros((sb, nb), np.float32),
                    "k0": np.zeros((sb,) + karr.shape, karr.dtype),
                    "fid": np.zeros((sb,), np.int32)}
            for k, v in data0.items():
                v = np.asarray(v)
                bufs[k] = np.zeros((sb, nb) + v.shape[1:], v.dtype)
            self._train_bufs[(sb, nb)] = bufs
            self._staging_allocs += 1
        return bufs

    def _key_fn(self, sb: int) -> Callable:
        """The cached batched key-derivation executable for stream bucket
        ``sb``: one jitted dispatch turns the fleet's stacked base keys
        into the per-stream (init, perm) key rows — byte-identical to the
        per-stream ``split``/``fold_in`` chain, without its O(S) device
        round-trips — laid out on the stream mesh."""
        fn = self._key_cache.get(sb)
        if fn is None:
            def derive(keys, fold_ids):
                def one(k, fid):
                    # pad slots (fid > 0) derive from the group's first key
                    # exactly as the per-stream path did: fold_in then split
                    k = jnp.where(fid > 0, jax.random.fold_in(k, fid), k)
                    ik, pk = jax.random.split(k)
                    return ik, pk

                return jax.vmap(one)(keys, fold_ids)

            shard = self._stream_sharding(sb)
            kw = ({} if shard is None
                  else {"in_shardings": shard, "out_shardings": shard})
            fn = jax.jit(derive, **kw)
            self._key_cache[sb] = fn
        return fn

    # -- the cached fleet-fit executable ------------------------------------

    def fleet_fit_fn(self, sb: int, nb: int) -> Callable:
        """The cached jitted fleet-fit executable for (stream bucket ``sb``,
        shape bucket ``nb``) — what ``train_fleet`` dispatches; ``.lower()``
        it to read its HLO."""
        cache_key = (sb, nb)
        fn = self._fleet_cache.get(cache_key)
        if fn is not None:
            return fn
        scan_fit = _make_epoch_scan(self.model, self.opt, self.epochs,
                                    self.batch_size, nb)
        init = self.model.init
        opt_init = self.opt.init
        counts = self._trace_counts
        counts.setdefault(cache_key, 0)

        def cold_fit(init_key, perm_key, x, y, mask):
            params = init(init_key)
            opt_state = opt_init(params)
            params, opt_state, losses = scan_fit(params, opt_state, x, y,
                                                 mask, perm_key)
            return params, opt_state, losses

        def fleet_fit(opt_carry, init_keys, perm_keys, x, y, mask):
            # executes only while XLA traces — counts real retraces.
            # ``opt_carry`` is the previous window's stacked opt state: its
            # value is dead (every window cold-starts from init_keys), but
            # donating it lets XLA alias this window's opt-state output into
            # the same buffers, so the optimizer state stays resident in one
            # allocation across the run.  Params are NOT donated — the
            # stacked fit output is the fleet's live serving state
            # (FleetParamView slices it lazily) and must survive the next
            # window's fit.
            counts[cache_key] += 1
            return jax.vmap(cold_fit)(init_keys, perm_keys, x, y, mask)

        # every input and output carries a leading stream-bucket axis, so on
        # a mesh ONE explicit sharding pins them all — without it, GSPMD is
        # free to lay the first window's carry out differently from the
        # fit's own opt output, forcing a second lowering at window 1
        shard = self._stream_sharding(sb)
        kw = ({} if shard is None
              else {"in_shardings": shard, "out_shardings": shard})
        fn = jax.jit(fleet_fit, donate_argnums=(0,), keep_unused=True, **kw)
        self._fleet_cache[cache_key] = fn
        return fn

    def _carry_init_fn(self, sb: int) -> Callable:
        """One-time (per stream bucket) builder of the initial stacked
        opt-state carry the donated fit consumes.  On a mesh the carry's
        leaves get explicit per-leaf shardings from the axis-rules table
        (``fleet_param_shardings``: stream axis sharded, per-stream model
        dims replicated per ``PARAM_AXES``) — the layout the fit's own opt
        output keeps, so window 1's donation never forces a relayout."""
        fn = self._carry_cache.get(sb)
        if fn is None:
            init, opt_init = self.model.init, self.opt.init
            vmapped = jax.vmap(lambda k: opt_init(init(k)))
            shard = self._stream_sharding(sb)
            if shard is None:
                kw = {}
            else:
                keys_shape = jax.eval_shape(
                    lambda: jax.random.split(jax.random.PRNGKey(0), sb))
                carry_shape = jax.eval_shape(vmapped, keys_shape)
                kw = {"out_shardings": fleet_param_shardings(
                    carry_shape, shard.mesh)}
            fn = jax.jit(vmapped, **kw)
            self._carry_cache[sb] = fn
        return fn

    # -- the fleet fit -------------------------------------------------------

    def train_fleet(self, datas: Sequence[Dict[str, np.ndarray]],
                    keys: Sequence[jax.Array]
                    ) -> Tuple[List[Params], float]:
        """Cold-start fit of one speed model per stream; returns the
        per-stream params (same order as ``datas``) and the total wall.

        Multi-stream groups return lazy :class:`FleetParamView` handles
        over the device-resident stacked fit output — semantically the
        per-stream trees (they flatten to them), materialized only when a
        consumer actually needs one; a single-stream group returns its
        plain tree from the delegated single-stream path.

        ``keys[i]`` plays exactly the role ``key`` plays in
        ``CompiledForecaster.train`` for stream ``i``."""
        t0 = time.perf_counter()
        if len(datas) != len(keys):
            raise ValueError(f"{len(datas)} windows but {len(keys)} keys")
        out: List[Optional[Params]] = [None] * len(datas)
        if not datas:
            return [], 0.0
        groups: Dict[int, List[int]] = {}
        for i, d in enumerate(datas):
            n = len(next(iter(d.values())))
            groups.setdefault(bucket_examples(n, self.batch_size), []).append(i)
        losses: List[Optional[np.ndarray]] = [None] * len(datas)
        for nb, idxs in sorted(groups.items()):
            if len(idxs) == 1:
                # byte-identical single-stream path (no vmap, no S padding)
                i = idxs[0]
                out[i], _ = self.single.train(datas[i], None, keys[i])
                losses[i] = self.single.last_losses
                self.train_dispatches += 1
                continue
            for i, l in zip(idxs, self._fit_group(nb, idxs, datas, keys, out)):
                losses[i] = l
        self.last_losses = losses
        return out, time.perf_counter() - t0

    def _fit_group(self, nb: int, idxs: List[int],
                   datas: Sequence[Dict[str, np.ndarray]],
                   keys: Sequence[jax.Array],
                   out: List[Optional[Params]]) -> np.ndarray:
        s = len(idxs)
        sb = bucket_streams(s)
        bufs = self._train_staging(sb, nb, datas[idxs[0]], keys[idxs[0]])
        for j, i in enumerate(idxs):
            d = datas[i]
            n = len(next(iter(d.values())))
            for k, v in d.items():
                bufs[k][j, :n] = np.asarray(v)
                bufs[k][j, n:] = 0
            bufs["mask"][j, :n] = 1.0
            bufs["mask"][j, n:] = 0.0
            bufs["k0"][j] = np.asarray(keys[i])
        for k in datas[idxs[0]]:
            # stream-axis padding: zero data + all-zero validity mask, so
            # the slot's loss/grad are exactly zero (any key gives a fine
            # inert init; fold_in keeps it deterministic)
            bufs[k][s:] = 0
        bufs["mask"][s:] = 0.0
        bufs["k0"][s:] = np.asarray(keys[idxs[0]])
        bufs["fid"][:s] = 0
        bufs["fid"][s:] = np.arange(1, sb - s + 1, dtype=np.int32)
        # one batched dispatch derives every stream's (init, perm) keys —
        # the same split/fold_in chain the sequential path runs per stream
        ik_d, pk_d = self._key_fn(sb)(bufs["k0"], bufs["fid"])
        padded0 = {k: bufs[k][0] for k in list(datas[idxs[0]]) + ["mask"]}
        self._check_mask_honored(datas[idxs[0]], padded0, nb, ik_d)
        carry = self._opt_carry.pop((sb, nb), None)
        if carry is None:
            carry = self._carry_init_fn(sb)(ik_d)
        params_S, opt_S, losses_S = self.fleet_fit_fn(sb, nb)(
            carry, ik_d, pk_d,
            self._put(bufs["x"], sb), self._put(bufs["y"], sb),
            self._put(bufs["mask"], sb))
        self._opt_carry[(sb, nb)] = opt_S
        jax.block_until_ready(params_S)
        self.train_dispatches += 1
        owner = _FleetStack(params_S)
        for j, i in enumerate(idxs):
            out[i] = FleetParamView(owner, j)
        return np.asarray(losses_S)[:s]

    # -- one-dispatch fleet inference ----------------------------------------

    def predict_fleet_fn(self, sb: int) -> Callable:
        """The cached vmapped predict executable for stream bucket ``sb`` —
        what ``predict_fleet`` dispatches (jit's own cache handles the
        inference shape buckets; the traced body counts real retraces per
        (sb, nb))."""
        fn = self._predict_cache.get(sb)
        if fn is None:
            pf = self.single._predict_raw
            traces = self._predict_traces

            def fleet_predict(params_S, x_S):
                # executes only while XLA traces — counts real retraces (a
                # new params structure, e.g. an int8 QTensor tree, traces
                # its bucket once more)
                k = (sb, x_S.shape[1])
                traces[k] = traces.get(k, 0) + 1
                return jax.vmap(pf)(params_S, x_S)

            fn = jax.jit(fleet_predict)
            self._predict_cache[sb] = fn
        return fn

    def _stack_fleet_params(self, params_seq: List[Params], sb: int
                            ) -> Tuple[Params, bool]:
        """The stacked params pytree for one fleet predict: sibling
        :class:`FleetParamView`\\ s of one stacked fit output in slot order
        are served from it directly (zero re-stacking, and already laid
        out on the stream mesh — the common ungated serving path);
        anything else stacks the materialized per-stream trees leaf-wise,
        repeating stream 0 into the padded slots (their predictions are
        sliced away).  Returns the stacked tree and whether it lives on
        the stream mesh (so the staged batch can be shipped to match)."""
        first = params_seq[0]
        if isinstance(first, FleetParamView):
            owner = first.owner
            if (all(isinstance(p, FleetParamView) and p.owner is owner
                    and p.slot == j for j, p in enumerate(params_seq))
                    and owner.dim() == sb):
                return owner.stacked, True
        # an identical params sequence (the shared batch model every window,
        # a gated fleet's unchanged serving set) reuses its stacked tree —
        # the cache holds the sequence itself, so the ids in the key stay
        # valid, and the identity re-check makes id reuse harmless
        ck = (sb,) + tuple(id(p) for p in params_seq)
        hit = self._stack_tree_cache.get(ck)
        if hit is not None and all(a is b for a, b in zip(hit[0],
                                                          params_seq)):
            return hit[1], False
        trees = [materialize_params(p) for p in params_seq]
        trees += [trees[0]] * (sb - len(trees))
        stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)
        if len(self._stack_tree_cache) >= 16:
            self._stack_tree_cache.clear()
        self._stack_tree_cache[ck] = (list(params_seq), stacked)
        return stacked, False

    def predict_fleet(self, params_seq: Sequence[Params],
                      xs: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Per-stream predictions for the whole fleet in **one** vmapped
        device dispatch: stream ``i``'s batch ``xs[i]`` under its own
        params ``params_seq[i]``.

        Batches are padded to a common inference shape bucket (persistent
        staging buffer, padded rows sliced away per stream) and the stream
        axis to its stream bucket, exactly mirroring ``train_fleet``; the
        stacked tree and the staged batch shard across the local device
        mesh when one exists.  Per-stream results match
        ``CompiledForecaster.predict`` to vmap-batching tolerance (<=1e-6;
        ``bench_fleet`` tracks it), and a one-stream call delegates to it
        byte-identically.  Int8 ``QTensor`` trees (the fleet's quantized
        sync path) stack like any pytree and run the batched
        ``int8_matmul`` kernel under vmap."""
        if self.single._predict_raw is None:
            raise ValueError("FleetForecaster built without a predict_fn")
        params_seq = list(params_seq)
        xs = [np.asarray(x) for x in xs]
        if len(params_seq) != len(xs):
            raise ValueError(f"{len(params_seq)} param trees but "
                             f"{len(xs)} stream batches")
        S = len(xs)
        if S == 0:
            return []
        if S == 1:
            # byte-identical single-stream path (no vmap, no S padding)
            return [self.single.predict(params_seq[0], xs[0])]
        ns = [x.shape[0] for x in xs]
        nb = _next_pow2(max(max(ns), 1))
        sb = bucket_streams(S)
        stacked, on_mesh = self._stack_fleet_params(params_seq, sb)
        key = (sb, nb) + xs[0].shape[1:] + (xs[0].dtype.str,)
        buf, allocated = _staging_buffer(
            self._predict_bufs, key, (sb, nb) + xs[0].shape[1:],
            xs[0].dtype)
        self._staging_allocs += allocated
        for j, x in enumerate(xs):
            np.copyto(buf[j, :ns[j]], x)
            buf[j, ns[j]:] = 0  # only the padding tail, not the whole buffer
        buf[S:] = 0  # padded stream slots
        x_dev = self._put(buf, sb) if on_mesh else jnp.asarray(buf)
        preds = self.predict_fleet_fn(sb)(stacked, x_dev)
        self.predict_dispatches += 1
        preds = np.asarray(preds)
        return [preds[j, :ns[j]] for j in range(S)]

    def _check_mask_honored(self, data: Dict[str, np.ndarray],
                            padded: Dict[str, np.ndarray], nb: int,
                            init_keys: jax.Array) -> None:
        """One-time (per shape bucket) mask guard, same contract as the
        single-stream trainer's; shares its dedup set so a bucket checked by
        either path is checked once.  A window that exactly fills its
        bucket needs no padding and no check (and must not pay the
        throwaway init — or even slicing row 0 off the stacked key array —
        every window)."""
        n = len(next(iter(data.values())))
        if n == nb or nb in self.single._mask_checked:
            return
        params = self.single._init_fn(init_keys[0])
        self.single._check_mask_honored(data, padded, params, nb)
