"""What the two plain references share: the snapshot tables, flax's key
derivation, the optimizer written out, and the precision switch.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision.  Nothing
here imports ``dragonfly2_tpu``; what it needs of the program's behaviour
(the sampler seed of the neighbour table, the AdamW schedule, the Huber
loss) is written from the equations.  flax and jax are libraries, not the
program: the weights and dropout masks are drawn with the same library
calls from the same seeds, so a reference makes its own and they equal the
program's bit for bit (``tests/test_reference_vs_flax.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax.core.scope import LazyRng

KEEP_F32 = "f32"          # the reference proper
CONTROL_FP8 = "fp8"       # the control: every Dense in fp8 (e4m3, per-tensor scale)
FAULT_HALF = "half_batch" # a planted fault: half the batch left out


# -- snapshot tables ---------------------------------------------------------


def neighbor_table(n: int, src, dst, feat, k: int):
    """For each node the (at most) ``k`` probers of it, with the edge
    feature.  Over-degree nodes keep the first ``k`` of their in-edges in
    the order of ``default_rng(0).permutation`` over the edge list (the
    sampler the snapshot is specified with); the rest is padding."""
    src, dst = np.asarray(src), np.asarray(dst)
    feat = np.asarray(feat, np.float32).reshape(len(src), -1)
    rank = np.empty(len(src), np.int64)
    rank[np.random.default_rng(0).permutation(len(src))] = np.arange(len(src))
    order = np.lexsort((rank, dst))                   # by node, then sampler rank
    d = dst[order]
    first = np.searchsorted(d, d, side="left")        # start of each node's run
    slot = np.arange(len(d)) - first
    keep = slot < k
    rows, cols, eid = d[keep], slot[keep], order[keep]
    indices = np.zeros((n, k), np.int32)
    mask = np.zeros((n, k), np.float32)
    feats = np.zeros((n, k, feat.shape[1]), np.float32)
    indices[rows, cols] = src[eid]
    mask[rows, cols] = 1.0
    feats[rows, cols] = feat[eid]
    return indices, mask, feats


def hop_features(x, indices, mask, edge_feats, hops: int):
    """[N, D] host features -> [N, D*(1+2*hops)+2]: per hop the masked mean
    and the inverse-RTT-weighted mean of the previous hop's mean, then the
    normalised degree and the mean RTT."""
    x = np.asarray(x, np.float32)
    m = mask[..., None].astype(np.float32)
    denom = np.maximum(m.sum(1), 1.0)
    rtt = edge_feats[..., :1].astype(np.float32)
    w = m / (1.0 + np.maximum(rtt, 0.0))
    w_denom = np.maximum(w.sum(1), 1e-6)
    parts, h = [x], x
    for _ in range(hops):
        nbr = h[indices]
        mean = (nbr * m).sum(1) / denom
        parts += [mean, (nbr * w).sum(1) / w_denom]
        h = mean
    parts += [m.sum(1) / m.shape[1], (rtt * m).sum(1) / denom]
    return np.concatenate(parts, -1).astype(np.float32)


# -- the libraries' random streams -------------------------------------------


def flax_key(base, *path):
    """The key flax hands the module at ``path`` (names, then the call
    count within that scope) from a collection's base key."""
    return LazyRng.create(base, *path).as_jax_rng()


def dense_init(base, path, fan_in: int, fan_out: int):
    """``nn.Dense`` defaults: LeCun-normal kernel (key 1), zero bias."""
    kernel = jax.nn.initializers.lecun_normal()(
        flax_key(base, *path, 1), (fan_in, fan_out), jnp.float32
    )
    return {"kernel": kernel, "bias": jnp.zeros((fan_out,), jnp.float32)}


def keep_mask(step_key, path, shape, rate: float):
    return jax.random.bernoulli(flax_key(step_key, *path), 1.0 - rate, shape)


# -- arithmetic ---------------------------------------------------------------


def _round_fp8(x, dtype, top: float):
    """Round to an 8-bit float under a per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


_E4M3 = (jnp.float8_e4m3fn, 448.0)     # operands
_E5M2 = (jnp.float8_e5m2, 57344.0)     # gradients


def _dot(x, w):
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _fp8_dot(x, w):
    """A matmul as an fp8 path runs it: operands in e4m3 forward and
    backward, the incoming gradient in e5m2, float32 accumulation."""
    return _dot(_round_fp8(x, *_E4M3), _round_fp8(w, *_E4M3))


def _fp8_dot_fwd(x, w):
    xq, wq = _round_fp8(x, *_E4M3), _round_fp8(w, *_E4M3)
    return _dot(xq, wq), (xq, wq)


def _fp8_dot_bwd(res, g):
    xq, wq = res
    gq = _round_fp8(g, *_E5M2)
    lead = xq.reshape(-1, xq.shape[-1])
    return _dot(gq, wq.T), _dot(lead.T, gq.reshape(-1, gq.shape[-1]))


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def dense(p, x, variant: str):
    dot = _fp8_dot if variant == CONTROL_FP8 else _dot
    return dot(x, p["kernel"]) + p["bias"]


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def dropout(x, mask, rate: float):
    return jnp.where(mask, x / (1.0 - rate), 0.0)


def huber_sum(pred, target, delta: float = 1.0):
    a = jnp.abs(pred - target)
    q = jnp.minimum(a, delta)
    return jnp.sum(0.5 * q * q + delta * (a - q))


# -- AdamW under global-norm clipping and a linear warm-up ----------------------


def flatten(tree, prefix=""):
    out = {}
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, name))
        else:
            out[name] = v
    return out


@jax.jit
def _adamw(params, mu, nu, grads, t, lr, b1=0.9, b2=0.999, eps=1e-8, wd=1e-4):
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.where(gnorm < 1.0, 1.0, 1.0 / gnorm)
    grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
        params, mu, nu,
    )
    return params, mu, nu


def follow(params, grad_fn, steps: int, train: dict):
    """``steps`` AdamW steps from ``params``.  ``grad_fn(params, t)`` gives
    the mean loss and its gradient at step ``t``.  Returns each step's
    loss and, by leaf, the norms the comparison reads: the first moment
    (the clipped gradients as the optimizer took them) and the change."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu, p = zeros, zeros, params
    losses, first_grad, mean_residual = [], None, []
    for t in range(steps):
        loss, grads = grad_fn(p, t)
        if first_grad is None:
            first_grad = grads
        # The one-element leaf is the output bias; its gradient is the mean
        # of the loss's derivative over the batch (the mean clipped residual).
        mean_residual.append(sum(
            abs(float(g.reshape(()))) for g in jax.tree_util.tree_leaves(grads) if g.size == 1
        ))
        # Linear warm-up from 0 (the comparison never leaves it).
        if t >= train["warmup_steps"]:
            raise ValueError("the reference follows the warm-up only")
        lr = train["learning_rate"] * t / train["warmup_steps"]
        p, mu, nu = _adamw(
            p, mu, nu, grads, jnp.float32(t), jnp.float32(lr),
            wd=train["weight_decay"],
        )
        losses.append(float(loss))
    norm = lambda tree: {k: float(jnp.linalg.norm(v)) for k, v in flatten(tree).items()}
    change = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
    return {
        "init_params": jax.tree_util.tree_map(np.asarray, params),
        "losses": losses,
        "mean_residual": sum(mean_residual) / len(mean_residual),
        "moment": {k: np.asarray(v) for k, v in flatten(mu).items()},
        "moment_norm": norm(mu),
        "second_moment_norm": norm(nu),
        "change_norm": norm(change),
        "first_grad_norm": norm(first_grad),
    }
