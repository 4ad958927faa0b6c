"""Model dispatcher: one uniform interface over the whole zoo.

``get_model(cfg)`` returns a ``Model`` whose functions have the signatures the
launcher, dry-run, serving engine and hybrid-learning core all consume:

    init(key)                        -> params
    loss_fn(params, batch)           -> (loss, metrics)
    prefill(params, batch, max_len)  -> (last_logits, cache)
    decode_step(params, batch, cache)-> (logits, cache)
    init_cache(batch_size, max_len)  -> cache pytree

``input_specs(cfg, shape)`` emits jax.ShapeDtypeStruct stand-ins for every
model input of a given input shape — weak-type-correct, shardable, no device
allocation — exactly what ``jax.jit(...).lower(**specs)`` needs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Params]
    loss_fn: Callable[[Params, Batch], Tuple[jax.Array, Dict[str, jax.Array]]]
    prefill: Optional[Callable[..., Tuple[jax.Array, Params]]]
    decode_step: Optional[Callable[[Params, Batch, Params], Tuple[jax.Array, Params]]]
    init_cache: Optional[Callable[[int, int], Params]]
    # a model that trains with dropout reads a per-step key, batch["rng"]
    dropout: float = 0.0
    # steps ahead that each prediction row answers at once
    horizon: int = 1


def get_model(cfg: ModelConfig) -> Model:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        from repro.models import transformer as m

        return Model(
            cfg=cfg,
            init=lambda key: m.init_params(cfg, key),
            loss_fn=lambda p, b: m.loss_fn(cfg, p, b),
            prefill=lambda p, b, max_len=None: m.prefill(cfg, p, b, max_len),
            decode_step=lambda p, b, c: m.decode_step(cfg, p, b, c),
            init_cache=lambda bsz, ml: m.init_cache(cfg, bsz, ml),
        )
    if fam == "ssm":  # rwkv6
        from repro.models import rwkv as m

        return Model(
            cfg=cfg,
            init=lambda key: m.init_params(cfg, key),
            loss_fn=lambda p, b: m.loss_fn(cfg, p, b),
            prefill=lambda p, b, max_len=None: m.prefill(cfg, p, b, max_len),
            decode_step=lambda p, b, c: m.decode_step(cfg, p, b, c),
            init_cache=lambda bsz, ml: m.init_cache(cfg, bsz, ml),
        )
    if fam == "hybrid":
        from repro.models import hybrid_arch as m

        return Model(
            cfg=cfg,
            init=lambda key: m.init_params(cfg, key),
            loss_fn=lambda p, b: m.loss_fn(cfg, p, b),
            prefill=lambda p, b, max_len=None: m.prefill(cfg, p, b, max_len),
            decode_step=lambda p, b, c: m.decode_step(cfg, p, b, c),
            init_cache=lambda bsz, ml: m.init_cache(cfg, bsz, ml),
        )
    if fam == "audio":
        from repro.models import encdec as m

        return Model(
            cfg=cfg,
            init=lambda key: m.init_params(cfg, key),
            loss_fn=lambda p, b: m.loss_fn(cfg, p, b),
            prefill=lambda p, b, max_len=None: m.prefill(cfg, p, b, max_len),
            decode_step=lambda p, b, c: m.decode_step(cfg, p, b, c),
            init_cache=lambda bsz, ml: m.init_cache(cfg, bsz, ml),
        )
    if fam == "lstm":
        from repro.models import lstm as m

        return Model(
            cfg=cfg,
            init=lambda key: m.init_params(cfg, key),
            loss_fn=lambda p, b: m.loss_fn(cfg, p, b),
            prefill=None,
            decode_step=None,
            init_cache=None,
        )
    if fam == "tft":
        from repro.models import tft as m

        return Model(
            cfg=cfg,
            init=lambda key: m.init_params(cfg, key),
            loss_fn=lambda p, b: m.loss_fn(cfg, p, b),
            prefill=None,
            decode_step=None,
            init_cache=None,
            dropout=cfg.tft.dropout,
            horizon=cfg.tft.horizon,
        )
    raise ValueError(f"unknown family {fam!r}")


# ---------------------------------------------------------------------------
# ShapeDtypeStruct input specs (the dry-run pattern)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for the step function of this input shape.

    train   -> kwargs of loss/train step: {"batch": {...}}
    prefill -> kwargs of prefill step:    {"batch": {...}}
    decode  -> kwargs of decode step:     {"batch": {...}, "cache": {...}}
    """
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "lstm":
        c = cfg.lstm
        return {
            "batch": {
                "x": _sds((B, c.lag, c.n_features), cfg.dtype),
                "y": _sds((B, c.out_dim), cfg.dtype),
            }
        }
    if cfg.family == "tft":
        c = cfg.tft
        return {
            "batch": {
                "x": _sds((B, c.steps, c.n_channels), cfg.dtype),
                "y": _sds((B, c.horizon), cfg.dtype),
            }
        }

    def token_batch(seq_len):
        b: Dict[str, Any] = {"tokens": _sds((B, seq_len), jnp.int32)}
        if cfg.frontend is not None:
            fe = cfg.frontend
            b["prefix_embed"] = _sds((B, fe.n_prefix_tokens, fe.embed_dim), cfg.dtype)
        return b

    if shape.kind == "train":
        # VLM prefix counts toward the sequence budget
        text_len = S - (cfg.frontend.n_prefix_tokens
                        if cfg.family == "vlm" and cfg.frontend else 0)
        b = token_batch(text_len)
        b["targets"] = _sds((B, text_len), jnp.int32)
        return {"batch": b}

    if shape.kind == "prefill":
        text_len = S - (cfg.frontend.n_prefix_tokens
                        if cfg.family == "vlm" and cfg.frontend else 0)
        return {"batch": token_batch(text_len)}

    if shape.kind == "decode":
        model = get_model(cfg)
        cache = jax.eval_shape(lambda: model.init_cache(B, S))
        batch = {"token": _sds((B, 1), jnp.int32), "pos": _sds((B,), jnp.int32)}
        if cfg.family == "audio":
            # cross K/V + memory positions live in the cache already
            pass
        return {"batch": batch, "cache": cache}

    raise ValueError(shape.kind)
