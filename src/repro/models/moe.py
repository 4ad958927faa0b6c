"""Mixture-of-experts layer with two expert-parallel strategies.

* ``onehot`` — Switch-Transformer capacity dispatch via one-hot einsums over
  sequence sub-groups.  GSPMD-friendly, differentiable, memory O(tokens * E *
  C / groups); the right choice for coarse MoE (grok-1: 8 experts) and all
  reduced/smoke configs.

* ``shard_map`` — fine-grained expert parallelism for large expert counts
  (kimi-k2: 384 experts).  Experts are sharded over the ``model`` mesh axis;
  tokens (batch-sharded over pod/data, replicated over model) are dispatched
  locally with a sort + capacity scatter, each device computes only its local
  experts, and a ``psum`` over ``model`` recombines the per-token expert sums.
  Expert weights are additionally FSDP-sharded over (pod, data) and gathered
  per layer inside the shard_map body (ZeRO-3) — without this the 1T-param
  config cannot even hold its weights.

Both paths compute the Switch load-balancing auxiliary loss.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models import nn

Params = Dict[str, Any]


def init_moe(key, path: str, cfg: ModelConfig, n_stack: Optional[int] = None) -> Params:
    moe = cfg.moe
    dt = jnp.dtype(cfg.param_dtype)
    d, f, E = cfg.d_model, moe.d_ff_expert, moe.n_experts

    def mk(name, *shape_dims):
        lead = () if n_stack is None else (n_stack,)
        # use stacked_dense_init-compatible normal init
        std = shape_dims[-2] ** -0.5
        w = jax.random.truncated_normal(
            nn._path_key(key, f"{path}/{name}"), -2.0, 2.0,
            lead + shape_dims, jnp.float32,
        )
        return (w * std).astype(dt)

    p = {
        "router": mk("router", d, E),
        "we_in": mk("we_in", E, d, f),
        "we_out": mk("we_out", E, f, d),
    }
    if nn.is_gated(cfg.mlp_variant):
        p["we_gate"] = mk("we_gate", E, d, f)
    if moe.n_shared_experts > 0:
        fs = f * moe.n_shared_experts
        p["w_in"] = mk("w_in", d, fs)
        p["w_out"] = mk("w_out", fs, d)
        if nn.is_gated(cfg.mlp_variant):
            p["w_gate"] = mk("w_gate", d, fs)
    return p


def _route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """Router probabilities and top-k selection.  x: (..., d)."""
    moe = cfg.moe
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, moe.top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    return probs, top_p, top_idx


def _aux_loss(cfg: ModelConfig, probs: jax.Array, top_idx: jax.Array) -> jax.Array:
    """Switch load-balance loss: E * sum_e f_e * P_e."""
    E = cfg.moe.n_experts
    sel = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # (..., k, E)
    frac_tokens = jnp.mean(jnp.sum(sel, axis=-2).reshape(-1, E), axis=0)
    mean_prob = jnp.mean(probs.reshape(-1, E), axis=0)
    return E * jnp.sum(frac_tokens * mean_prob)


def _expert_ffn(cfg: ModelConfig, p: Params, xe: jax.Array) -> jax.Array:
    """Dense per-expert FFN.  xe: (E, C, d) -> (E, C, d)."""
    h = jnp.einsum("ecd,edf->ecf", xe, p["we_in"].astype(xe.dtype))
    gate = None
    if "we_gate" in p:
        gate = jnp.einsum("ecd,edf->ecf", xe, p["we_gate"].astype(xe.dtype))
    h = nn.mlp_act(h, cfg.mlp_variant, gate)
    return jnp.einsum("ecf,efd->ecd", h, p["we_out"].astype(xe.dtype))


# ---------------------------------------------------------------------------
# Path 1: one-hot capacity dispatch (GSPMD)
# ---------------------------------------------------------------------------


def moe_onehot(cfg: ModelConfig, p: Params, x: jax.Array,
               group: int = 0, no_drop: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    no_drop=True sets capacity to the worst case (every token in the group
    routed to one expert), making the layer composition-independent — used by
    the inference paths so decode == prefill == full forward exactly.
    Training keeps the Switch capacity factor (drops are faithful behavior).
    """
    moe = cfg.moe
    B, S, d = x.shape
    E, k = moe.n_experts, moe.top_k
    g = min(group or moe.dispatch_group, S)
    nG = S // g if S % g == 0 else 1
    if S % g != 0:
        g = S
    if no_drop:
        cap = g * k
    else:
        cap = max(1, int(g * k * moe.capacity_factor / E))

    xg = x.reshape(B * nG, g, d)
    probs, top_p, top_idx = _route(cfg, p["router"], xg)  # (N, g, k)
    aux = _aux_loss(cfg, probs, top_idx)

    sel = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # (N, g, k, E)
    # position of each (token, slot) within its expert queue
    pos_in_e = jnp.cumsum(sel.reshape(B * nG, g * k, E), axis=1) - 1.0
    pos_in_e = pos_in_e.reshape(B * nG, g, k, E)
    keep = (pos_in_e < cap) & (sel > 0)
    cap_oh = jax.nn.one_hot(pos_in_e.astype(jnp.int32), cap, dtype=jnp.float32)
    # dispatch: (N, g, E, C)
    dispatch = jnp.einsum("ngke,ngkec->ngec", sel * keep, cap_oh)
    combine = jnp.einsum("ngk,ngke,ngkec->ngec", top_p, sel * keep, cap_oh)
    dispatch = shd.shard(dispatch, "batch", None, "experts", None)

    xe = jnp.einsum("ngec,ngd->necd", dispatch, xg.astype(jnp.float32))
    xe = xe.reshape(B * nG * E, cap, d)  # flatten for expert matmul grouping
    xe = xe.reshape(B * nG, E, cap, d).astype(x.dtype)
    # merge group dim into capacity for a single (E, N*C, d) expert matmul
    xe2 = xe.transpose(1, 0, 2, 3).reshape(E, B * nG * cap, d)
    xe2 = shd.shard(xe2, "experts", None, None)
    ye2 = _expert_ffn(cfg, p, xe2)
    ye = ye2.reshape(E, B * nG, cap, d).transpose(1, 0, 2, 3)

    out = jnp.einsum("ngec,necd->ngd", combine, ye.astype(jnp.float32))
    out = out.reshape(B, S, d).astype(x.dtype)
    return out, aux


# ---------------------------------------------------------------------------
# Path 2: shard_map expert parallelism (fine-grained MoE)
# ---------------------------------------------------------------------------


def _local_ep_body(cfg: ModelConfig, model_axis: str, fsdp_axes, x, p):
    """Per-device body. x: (B_loc, S, d) local tokens (replicated over model).

    Two partitionings of the expert compute over the model axis:
    * fine-grained (E >= n_shards, divisible): experts sharded — each rank
      holds E/n_shards experts and scatters only its own tokens' slots
      (kimi-k2: 384 experts / 16 ranks).
    * coarse (E < n_shards): experts replicated, the expert FFN dim is
      sharded — every rank processes all E experts on an f-slice and the
      closing psum combines partial FFN sums (grok-1: 8 experts, 16 ranks).
      This is the sort-scatter replacement for the one-hot dispatch einsum
      (see EXPERIMENTS.md §Perf).
    Either way weight d/f dims are additionally FSDP-sharded over fsdp_axes
    and gathered here per layer (ZeRO-3).
    """
    moe = cfg.moe
    B, S, d = x.shape
    k = moe.top_k
    E = moe.n_experts
    E_loc = p["we_in"].shape[0]
    experts_sharded = E_loc < E
    n_shards = jax.lax.axis_size(model_axis)
    my_shard = jax.lax.axis_index(model_axis)

    # gather FSDP-sharded expert weights for this layer (ZeRO-3 gather)
    def gather(w):
        if fsdp_axes:
            w = jax.lax.all_gather(w, fsdp_axes, axis=1, tiled=True)
        return w

    we_in = gather(p["we_in"])
    we_out = p["we_out"]
    if fsdp_axes:
        we_out = jax.lax.all_gather(we_out, fsdp_axes, axis=2, tiled=True)
    we_gate = gather(p["we_gate"]) if "we_gate" in p else None
    router_w = p["router"]
    if fsdp_axes:
        router_w = jax.lax.all_gather(router_w, fsdp_axes, axis=0, tiled=True)

    probs, top_p, top_idx = _route(cfg, router_w, x)  # (B, S, k)
    aux = _aux_loss(cfg, probs, top_idx)

    T = B * S
    flat_idx = top_idx.reshape(T * k)
    flat_w = top_p.reshape(T * k)
    tok_of_slot = jnp.arange(T * k, dtype=jnp.int32) // k

    # rank of each slot within its expert via sort
    order = jnp.argsort(flat_idx)
    sorted_e = flat_idx[order]
    counts = jnp.bincount(flat_idx, length=E)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    rank = jnp.zeros((T * k,), jnp.int32).at[order].set(rank_sorted)

    cap = max(1, int(T * k * moe.capacity_factor / E))
    if experts_sharded:
        local_e = flat_idx - my_shard * E_loc  # expert index on this shard
        mine = (local_e >= 0) & (local_e < E_loc) & (rank < cap)
    else:
        local_e = flat_idx  # all experts local (f-dim is sharded instead)
        mine = rank < cap
    # scatter local tokens into (E_loc, cap, d)
    xf = x.reshape(T, d)
    src = jnp.take(xf, tok_of_slot, axis=0)  # (T*k, d)
    buf = jnp.zeros((E_loc, cap, d), x.dtype)
    e_idx = jnp.where(mine, local_e, 0)
    c_idx = jnp.where(mine, rank, 0)
    src = jnp.where(mine[:, None], src, 0)
    buf = buf.at[e_idx, c_idx].add(src)

    pp = {"we_in": we_in, "we_out": we_out}
    if we_gate is not None:
        pp["we_gate"] = we_gate
    ye = _expert_ffn(cfg, pp, buf)  # (E_loc, cap, d)

    # gather back: each slot reads its expert output if local, weighted
    out_slot = ye[e_idx, c_idx] * jnp.where(mine, flat_w, 0.0)[:, None].astype(ye.dtype)
    out = jnp.zeros((T, d), jnp.float32).at[tok_of_slot].add(
        out_slot.astype(jnp.float32)
    )
    # combine expert contributions across model shards
    out = jax.lax.psum(out, model_axis)
    aux = jax.lax.pmean(aux, model_axis)
    return out.reshape(B, S, d).astype(x.dtype), aux


def moe_shard_map(cfg: ModelConfig, p: Params, x: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map; requires an active mesh context."""
    ctx = shd._ctx()
    if ctx is None:
        return moe_onehot(cfg, p, x)
    mesh, rules = ctx
    axis_names = mesh.axis_names
    model_axis = "model" if "model" in axis_names else axis_names[-1]
    fsdp_axes = tuple(a for a in ("pod", "data") if a in axis_names)
    E = cfg.moe.n_experts
    n_model = mesh.devices.shape[axis_names.index(model_axis)]
    f = cfg.moe.d_ff_expert
    if E % n_model == 0:
        # fine-grained: experts over the model axis
        w_in_spec = P(model_axis, fsdp_axes or None, None)
        w_out_spec = P(model_axis, None, fsdp_axes or None)
    elif f % n_model == 0:
        # coarse: experts replicated, expert-FFN dim over the model axis
        w_in_spec = P(None, fsdp_axes or None, model_axis)
        w_out_spec = P(None, model_axis, fsdp_axes or None)
    else:
        return moe_onehot(cfg, p, x)

    batch_spec = P(fsdp_axes if fsdp_axes else None, None, None)
    in_specs = (
        batch_spec,
        {
            "router": P(fsdp_axes or None, None),
            "we_in": w_in_spec,
            "we_out": w_out_spec,
            **({"we_gate": w_in_spec} if "we_gate" in p else {}),
        },
    )
    out_specs = (batch_spec, P())
    pp = {kk: p[kk] for kk in ("router", "we_in", "we_out", "we_gate") if kk in p}

    fn = jax.shard_map(
        lambda xx, params: _local_ep_body(cfg, model_axis, fsdp_axes, xx, params),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    out, aux = fn(x, pp)
    return out, aux


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def apply_moe(cfg: ModelConfig, p: Params, x: jax.Array,
              ep_mode: Optional[str] = None, no_drop: bool = False
              ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch to the right EP strategy; adds the shared-expert path."""
    moe = cfg.moe
    mode = ep_mode
    if mode is None:
        if moe.ep_mode != "auto" and x.shape[1] > 1:
            mode = moe.ep_mode
        else:
            mode = ("shard_map" if moe.n_experts > 16 and x.shape[1] > 1
                    else "onehot")
    # exact (no-drop) one-hot dispatch is only feasible for coarse MoE;
    # fine-grained MoE serving stays capacity-based (documented drop risk)
    if no_drop and moe.n_experts > 64:
        no_drop = False
    if no_drop:
        mode = "onehot"
    if mode == "shard_map":
        out, aux = moe_shard_map(cfg, p, x)
    else:
        out, aux = moe_onehot(cfg, p, x, no_drop=no_drop)
    if moe.n_shared_experts > 0:
        h = nn.dense(x, p["w_in"])
        gate = nn.dense(x, p["w_gate"]) if "w_gate" in p else None
        h = nn.mlp_act(h, cfg.mlp_variant, gate)
        out = out + nn.dense(h, p["w_out"])
    return out, aux * moe.router_aux_loss
