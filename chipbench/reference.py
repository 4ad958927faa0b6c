"""The plain reference: the paper's forecaster (LSTM(40) -> Dense(10, ReLU)
-> Dense(1), lag 5, 5 features) and its training, weighting and serving
semantics, written from the description in plain ``jax.numpy``.  It imports
nothing of the program.

What it must share with the program to follow the same trajectory is
semantics, not code: the seeded key chains (a stream's root is
``fold_in(key, i)``, window ``w`` trains with the ``w``-th ``split`` of that
chain, and each fit splits its key into an init key and a permutation key);
the init (truncated normal at fan-in scale, one key per weight folded from
an MD5 hash of the weight's path, Keras's unit forget-gate bias); the
minibatching (each epoch a permutation of the window padded to a power-of-two
multiple of the batch, padding masked out of the mean); and AdamW with
global-norm clipping at 1.0.

``dtype`` is the precision of the parameters, the activations and the
gradients; the optimizer keeps float32 moments and update math either way,
as the program does for any parameter type.  ``float32`` is the configuration's precision (the
matmuls at the chip's default precision, as the program runs them);
``bfloat16`` is the control, the step below it.
"""
from __future__ import annotations

import hashlib
import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np



def bucket(n: int, batch: int) -> int:
    """The padded window: the next power-of-two multiple of ``batch``."""
    per = max(1, math.ceil(n / batch))
    return batch * (1 << max(0, math.ceil(math.log2(per))))


def _path_key(key, path: str):
    h = int.from_bytes(hashlib.md5(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h)


def init(key, cfg: Dict, dtype) -> Dict:
    H, F, D = cfg["hidden"], cfg["n_features"], cfg["dense"]

    def w(path, fan_in, shape, std=None):
        z = jax.random.truncated_normal(_path_key(key, path), -2.0, 2.0,
                                        shape, jnp.float32)
        return (z * (std if std is not None else fan_in ** -0.5)).astype(dtype)

    bias = jnp.zeros((4 * H,), jnp.float32).at[H:2 * H].set(1.0)
    return {
        "lstm": {"kernel": w("lstm/kernel", F, (F, 4 * H)),
                 "recurrent": w("lstm/recurrent", H, (H, 4 * H), H ** -0.5),
                 "bias": bias.astype(dtype)},
        "dense": {"dense_w": w("dense/dense_w", H, (H, D)),
                  "dense_b": jnp.zeros((D,), dtype)},
        "head": {"head_w": w("head/head_w", D, (D, 1)),
                 "head_b": jnp.zeros((1,), dtype)},
    }


def forward(p: Dict, x):
    """x: (B, lag, F) -> (B, 1): the LSTM over the lag steps (gates in the
    order input, forget, cell, output), then the two dense layers."""
    lp = p["lstm"]
    H = lp["recurrent"].shape[0]
    x = x.astype(lp["kernel"].dtype)
    h0 = jnp.zeros((x.shape[0], H), x.dtype)

    def step(carry, x_t):
        h, c = carry
        z = x_t @ lp["kernel"] + h @ lp["recurrent"] + lp["bias"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    (h, _), _ = jax.lax.scan(step, (h0, h0), x.transpose(1, 0, 2))
    d = jax.nn.relu(h @ p["dense"]["dense_w"] + p["dense"]["dense_b"])
    return d @ p["head"]["head_w"] + p["head"]["head_b"]


def loss(p, x, y, mask):
    """Mean squared error over the real (unmasked) examples."""
    err = forward(p, x) - y.astype(p["head"]["head_b"].dtype)
    m = mask.astype(err.dtype)[:, None]
    return jnp.sum(err * err * m) / jnp.maximum(jnp.sum(m), 1.0)


def adam_step(p, mu, nu, step, g, lr):
    """AdamW (b1 0.9, b2 0.999, eps 1e-8, no decay) on gradients clipped to
    a global norm of 1.0.  Moments and the update are float32 whatever the
    parameters' type; the result is cast back to it."""
    f32 = jnp.float32
    gnorm = jnp.sqrt(sum(jnp.sum(l.astype(f32) ** 2)
                         for l in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-9))
    step = step + 1
    c1 = 1 - 0.9 ** step.astype(f32)
    c2 = 1 - 0.999 ** step.astype(f32)

    def one(p, m, v, g):
        g = g.astype(f32) * scale
        m = 0.9 * m + (1 - 0.9) * g
        v = 0.999 * v + (1 - 0.999) * g * g
        new = p.astype(f32) - lr * ((m / c1) / (jnp.sqrt(v / c2) + 1e-8))
        return new.astype(p.dtype), m, v

    out = jax.tree_util.tree_map(one, p, mu, nu, g)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), step


def fit(init_key, perm_key, x, y, mask, *, cfg, epochs, batch, dtype):
    """One cold-start fit; returns (params, the loss of every step)."""
    nb = x.shape[0]
    p = init(init_key, cfg, dtype)
    zeros = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), p)
    perms = jax.vmap(lambda k: jax.random.permutation(k, nb))(
        jax.random.split(perm_key, epochs))
    idx = perms.reshape(-1, batch)
    lr = float(cfg["lr"])

    def body(carry, ib):
        p, mu, nu, step = carry
        l, g = jax.value_and_grad(loss)(p, x[ib], y[ib], mask[ib])
        p, mu, nu, step = adam_step(p, mu, nu, step, g, lr)
        return (p, mu, nu, step), l

    (p, _, _, _), losses = jax.lax.scan(
        body, (p, zeros, zeros, jnp.zeros((), jnp.int32)), idx)
    return p, losses


@partial(jax.jit, static_argnames=("cfg_items", "epochs", "batch", "dtype"))
def fleet_fit(keys, x, y, mask, *, cfg_items, epochs, batch, dtype):
    """Every stream's fit, vmapped: keys (S, 2) are each stream's window
    key, split into the init and the permutation key."""
    cfg = dict(cfg_items)

    def one(k, x, y, m):
        ik, pk = jax.random.split(k)
        return fit(ik, pk, x, y, m, cfg=cfg, epochs=epochs, batch=batch,
                   dtype=dtype)

    return jax.vmap(one)(keys, x, y, mask)


@jax.jit
def fleet_predict(params, x):
    return jax.vmap(forward)(params, x)


def cfg_items(cfg: Dict) -> Tuple:
    keys = ("hidden", "n_features", "dense", "lr")
    return tuple((k, cfg[k]) for k in keys)


def pad(data: Dict[str, np.ndarray], nb: int):
    """Zero-pad a window to ``nb`` rows with its validity mask."""
    n = len(data["x"])
    x = np.zeros((nb,) + data["x"].shape[1:], np.float32)
    y = np.zeros((nb, 1), np.float32)
    m = np.zeros((nb,), np.float32)
    x[:n], y[:n], m[:n] = data["x"], data["y"], 1.0
    return x, y, m


def key_chains(root, n_streams: int, n_windows: int) -> np.ndarray:
    """(S, W, 2) uint32: stream ``i``'s root is ``fold_in(root, i)``; window
    ``w`` takes the second half of the ``w``-th split of that chain."""
    cur = jax.vmap(lambda i: jax.random.fold_in(root, i))(
        jnp.arange(n_streams))
    out = []
    for _ in range(n_windows):
        both = jax.vmap(jax.random.split)(cur)
        cur = both[:, 0]
        out.append(both[:, 1])
    return np.asarray(jnp.stack(out, axis=1))


def dwa(ps, pb, y) -> float:
    """Paper Algorithm 1 for two models, in closed form: the weight of the
    speed model that minimises the RMSE of ``w ps + (1 - w) pb`` on the
    previous window, clipped to [0, 1]; 0.5 when the two agree."""
    ps, pb, y = (np.asarray(a, np.float64).ravel() for a in (ps, pb, y))
    d = ps - pb
    den = float(d @ d)
    if den < 1e-18:
        return 0.5
    return min(max(float((y - pb) @ d / den), 0.0), 1.0)


def answer(predict_one, ctx: np.ndarray, kind: int, horizon: int,
           scale: float, offset: float):
    """One forecast query: point (kind 0), a ``horizon``-step rollout that
    writes each prediction into channel 0 of the next lag row (kind 1), or
    a what-if on the perturbed context (kind 2)."""
    ctx = np.array(ctx, np.float32)
    if kind == 2:
        ctx = ctx * scale + offset
    out = []
    for _ in range(horizon):
        v = float(predict_one(ctx))
        out.append(v)
        nxt = ctx[-1].copy()
        nxt[0] = v
        ctx = np.concatenate([ctx[1:], nxt[None]], axis=0)
    return out
