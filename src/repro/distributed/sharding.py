"""Logical-axis sharding with divisibility-aware fallback.

Two planes:

* **Activations** — models call ``shard(x, "batch", "seq", "embed")`` at key
  points; inside a ``use_mesh_rules(mesh, rules)`` context this becomes a GSPMD
  sharding constraint, otherwise it is a no-op (so the same model code runs on
  one CPU device in tests).

* **Parameters** — ``param_shardings(params, mesh, rules)`` derives a
  ``NamedSharding`` pytree from parameter *names* via the ``PARAM_AXES`` table
  (every parameter in the model zoo has a registered leaf name).  ``fsdp``
  maps to the (pod, data) axes — ZeRO-3-style weight sharding, a beyond-paper
  necessity for the trillion-parameter config; ``tp`` maps to the model axis.

Resolution handles the assigned archs' awkward dimensions: a logical axis is
dropped (replicated) when the dim is not divisible by the mesh axes, and a
mesh axis is never used twice in one spec (first dim wins) — e.g. grok-1's 8
experts cannot split a 16-way model axis, so experts replicate and the expert
FFN keeps tensor parallelism; kimi-k2's 384 experts take the model axis and
its tiny per-expert FFN stays unsharded.

* **The stream mesh** — the fleet hot path (``training/compiled.py``)
  stacks S independent streams along a leading axis and shards it across
  the local devices: pure data parallelism, bitwise-identical per-stream
  numerics.  ``stream_mesh(sb)`` builds the 1-D mesh (capped at the
  largest power-of-two divisor of the stream bucket, so a 2-stream bucket
  on an 8-device host gets a 2-device mesh rather than an indivisible
  sharding), ``stream_sharding(sb)`` resolves the stacked-batch spec
  through the same divisibility-aware ``logical_to_spec``, and
  ``fleet_param_shardings`` derives the stacked params/opt-state specs
  leaf-wise (leading ``stream`` axis sharded, per-stream LSTM leaves
  replicated per ``PARAM_AXES``).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical name -> preferred mesh axis names (in priority order, used jointly
# when all divide, else greedily)
Rules = Dict[str, Tuple[str, ...]]

DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    "tp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "seq": (),  # sequence unsharded by default; hillclimb may override
    "embed": (),
    "stack": (),  # scan-stacked layer dim
    "state": (),
    # the fleet's stacked stream axis (training/compiled.py): independent
    # streams, sharded data-parallel over the 1-D stream mesh
    "stream": ("stream",),
}


@dataclass
class AxisRules:
    rules: Rules = field(default_factory=lambda: dict(DEFAULT_RULES))

    def resolve(self, name: Optional[str]) -> Tuple[str, ...]:
        if name is None:
            return ()
        return tuple(self.rules.get(name, ()))


_local = threading.local()


def _ctx():
    return getattr(_local, "ctx", None)


def _auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  The rules here are GSPMD
    sharding constraints, which may name only Auto axes — and
    ``jax.make_mesh`` makes Explicit ones."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@contextmanager
def use_mesh_rules(mesh: Mesh, rules: Optional[AxisRules] = None):
    prev = _ctx()
    _local.ctx = (_auto_axes(mesh), rules or AxisRules())
    try:
        yield
    finally:
        _local.ctx = prev


def logical_to_spec(
    names: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Optional[AxisRules] = None,
) -> P:
    """Map logical dim names to a PartitionSpec, enforcing divisibility and
    never reusing a mesh axis."""
    rules = rules or AxisRules()
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set = set()
    spec = []
    for name, dim in zip(names, shape):
        cands = [a for a in rules.resolve(name) if a in axis_size and a not in used]
        chosen: Tuple[str, ...] = ()
        if cands:
            # prefer the full joint product, else greedy prefix, else singles
            prod = 1
            joint = []
            for a in cands:
                if dim % (prod * axis_size[a]) == 0:
                    joint.append(a)
                    prod *= axis_size[a]
            if joint:
                chosen = tuple(joint)
            else:
                for a in cands:
                    if dim % axis_size[a] == 0:
                        chosen = (a,)
                        break
        used.update(chosen)
        if len(chosen) == 0:
            spec.append(None)
        elif len(chosen) == 1:
            spec.append(chosen[0])
        else:
            spec.append(tuple(chosen))
    return P(*spec)


def shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Activation sharding constraint (no-op outside a mesh context)."""
    ctx = _ctx()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_to_spec(names, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter axes table (leaf-name keyed; trailing dims; leading stack dims of
# scan-over-layers params are padded with "stack")
# ---------------------------------------------------------------------------

PARAM_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings / heads
    "tok_embed": ("vocab", "fsdp"),
    "out_head": ("fsdp", "vocab"),
    "pos_embed": (None, "fsdp"),
    "proj_in": (None, "fsdp"),  # modality projector (frontend_dim, embed)
    # norms (1-D, replicated)
    "attn_norm": (None,),
    "mlp_norm": (None,),
    "final_norm": (None,),
    "cross_norm": (None,),
    "norm_beta": (None,),
    # attention
    "wq": ("fsdp", "tp"),
    "wk": ("fsdp", "tp"),
    "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    "bq": ("tp",),
    "bk": ("tp",),
    "bv": ("tp",),
    # dense MLP
    "w_in": ("fsdp", "tp"),
    "w_gate": ("fsdp", "tp"),
    "w_out": ("tp", "fsdp"),
    # MoE
    "router": ("fsdp", "experts"),
    "we_in": ("experts", "fsdp", "tp"),
    "we_gate": ("experts", "fsdp", "tp"),
    "we_out": ("experts", "tp", "fsdp"),
    # RWKV6 time/channel mix
    "w_r": ("fsdp", "tp"),
    "w_k": ("fsdp", "tp"),
    "w_v": ("fsdp", "tp"),
    "w_g": ("fsdp", "tp"),
    "w_o": ("tp", "fsdp"),
    "mix_lora_a": ("fsdp", None),
    "mix_lora_b": (None, None, "fsdp"),
    "decay_lora_a": ("fsdp", None),
    "decay_lora_b": (None, "fsdp"),
    "decay_base": ("fsdp",),
    "bonus": ("heads", None),
    "mix_base": (None, "fsdp"),
    "ln_x": (None,),
    "ck_mix": (None, "fsdp"),
    "ck_in": ("fsdp", "tp"),
    "ck_out": ("tp", "fsdp"),
    "ck_rec": ("fsdp", "tp"),
    # SSM (Mamba2)
    "in_proj": ("fsdp", "tp"),
    "conv_w": (None, "tp"),
    "conv_b": ("tp",),
    "A_log": ("tp",),
    "D": ("tp",),
    "dt_bias": ("tp",),
    "ssm_norm": ("tp",),
    "out_proj": ("tp", "fsdp"),
    # zamba2 shared-block concat projector
    "shared_down": ("fsdp", None),
    # LSTM forecaster (tiny; replicated)
    "kernel": (None, None),
    "recurrent": (None, None),
    "bias": (None,),
    "dense_w": (None, None),
    "dense_b": (None,),
    "head_w": (None, None),
    "head_b": (None,),
}

_STACK_PARENTS = ("layers", "enc_layers", "dec_layers")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_axes_for(path_str: str, ndim: int) -> Tuple[Optional[str], ...]:
    leaf = path_str.split("/")[-1]
    if leaf not in PARAM_AXES:
        raise KeyError(
            f"parameter {path_str!r} has no PARAM_AXES entry; register its "
            f"leaf name {leaf!r}"
        )
    axes = PARAM_AXES[leaf]
    # pad leading stacked-layer dims
    n_lead = ndim - len(axes)
    if n_lead < 0:
        # param used unstacked somewhere (e.g. shared block): trim left pads
        axes = axes[-ndim:]
        n_lead = 0
    lead = tuple("stack" for _ in range(n_lead))
    return lead + tuple(axes)


def param_shardings(params, mesh: Mesh, rules: Optional[AxisRules] = None):
    rules = rules or AxisRules()

    def one(path, x):
        ps = _path_str(path)
        names = param_axes_for(ps, x.ndim)
        return NamedSharding(mesh, logical_to_spec(names, x.shape, mesh, rules))

    return jax.tree_util.tree_map_with_path(one, params)


def spec_tree(params, mesh: Mesh, rules: Optional[AxisRules] = None):
    """PartitionSpec pytree (for in_shardings=...)."""
    rules = rules or AxisRules()

    def one(path, x):
        ps = _path_str(path)
        names = param_axes_for(ps, x.ndim)
        return logical_to_spec(names, x.shape, mesh, rules)

    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# The stream mesh: the fleet hot path's stacked stream axis
# ---------------------------------------------------------------------------

STREAM_AXIS = "stream"


def largest_pow2_divisor(n: int) -> int:
    """The largest power of two dividing ``n`` (n & -n)."""
    if n <= 0:
        raise ValueError(f"need a positive dim, got {n}")
    return n & -n


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def stream_mesh_size(sb: int, n_devices: int) -> int:
    """How many local devices the stacked stream axis of bucket ``sb``
    shards over: the largest power of two that both divides ``sb`` and fits
    the device count.  Pure arithmetic so the awkward cases are unit-
    testable without reconfiguring XLA: a bucket *smaller* than the host's
    device count (2 streams on 8 devices) caps at the bucket's own pow2
    divisor instead of producing an indivisible sharding, a non-pow2
    device count (6 host cores) uses its pow2 floor, and a non-pow2 bucket
    (nothing upstream produces one today, but nothing here assumes that)
    caps at *its* pow2 divisor."""
    return min(largest_pow2_divisor(sb), _pow2_floor(n_devices))


def stream_mesh(sb: int, devices: Optional[Sequence[Any]] = None
                ) -> Optional[Mesh]:
    """The 1-D ``("stream",)`` mesh for stream bucket ``sb`` over the local
    devices (or an explicit device list), or ``None`` when it would be a
    single device (no sharding: the tests' one-CPU configuration)."""
    devs = list(devices) if devices is not None else jax.devices()
    d = stream_mesh_size(sb, len(devs))
    if d <= 1:
        return None
    return Mesh(np.asarray(devs[:d]), (STREAM_AXIS,))


def stream_batch_spec(sb: int, mesh: Mesh,
                      rules: Optional[AxisRules] = None) -> P:
    """The stacked-batch PartitionSpec for a leading stream axis of ``sb``,
    resolved through the divisibility-aware ``logical_to_spec`` (an
    indivisible bucket degrades to replicated instead of erroring);
    trailing per-stream dims replicate."""
    return logical_to_spec((STREAM_AXIS,), (sb,), mesh, rules)


def stream_sharding(sb: int, devices: Optional[Sequence[Any]] = None,
                    rules: Optional[AxisRules] = None
                    ) -> Optional[NamedSharding]:
    """The ``NamedSharding`` every stacked fleet tensor of stream bucket
    ``sb`` carries — staged batches, init/perm key rows, the donated
    opt-state carry, the fit's stacked params output, and the
    ``predict_fleet`` serving batch all resolve through this one helper —
    or ``None`` on a single device."""
    mesh = stream_mesh(sb, devices)
    if mesh is None:
        return None
    return NamedSharding(mesh, stream_batch_spec(sb, mesh, rules))


def fleet_param_shardings(stacked, mesh: Mesh,
                          rules: Optional[AxisRules] = None):
    """NamedSharding pytree for a *stacked* fleet params/opt-state tree
    (leading stream-bucket axis): the stream axis shards per the rules and
    the trailing per-stream axes resolve through ``PARAM_AXES`` (the LSTM
    forecaster's leaves are registered replicated — each stream's whole
    model lives on its shard).  Leaves without a ``PARAM_AXES`` entry (an
    optimizer's step counter, loss trajectories) replicate their trailing
    dims."""
    rules = rules or AxisRules()

    def one(path, x):
        try:
            trailing = param_axes_for(_path_str(path), x.ndim - 1)
        except KeyError:
            trailing = (None,) * (x.ndim - 1)
        names = (STREAM_AXIS,) + tuple(trailing)
        return NamedSharding(mesh, logical_to_spec(names, x.shape, mesh,
                                                   rules))

    return jax.tree_util.tree_map_with_path(one, stacked)


def fleet_rules() -> AxisRules:
    """The axis rules the fleet hot path trains/serves under (the default
    table: ``stream`` -> the stream mesh axis, model dims replicated)."""
    return AxisRules()
