"""Plain ResNet-18 split training: the reference for the fleet's passes.

ResNet-18 as in He et al. 2016 (Table 1): a 7x7/2 stem and 3x3/2 max
pool, four stages of two basic blocks at 64/128/256/512 channels, global
average pool and a dense head, with GroupNorm (8 groups) in place of
BatchNorm as the configuration states.  The network is cut after stage
unit ``cut`` (satellite segment A before it, ground segment B after);
each segment clips its own gradient to global norm ``grad_clip`` and
takes an SGD-momentum step, which is what the two sides of a split do.

Straight ``jax.numpy`` in one dtype: float32 at ``highest`` matmul
precision for the reference, bfloat16 for the control.  Weights come
from the seed: every leaf in the sorted order of the parameter tree
draws from its own split of ``key(seed)``, convolution and dense weights
as N(0, 1) / sqrt(fan_in) with ``fan_in`` the second-to-last dimension,
biases and norm shifts at 0, norm scales at 1.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

STAGES = ["stem", "s1b1", "s1b2", "s2b1", "s2b2", "s3b1", "s3b2", "s4b1",
          "s4b2", "head"]
STRIDE = {"s2b1": 2, "s3b1": 2, "s4b1": 2}
CHANNELS = {"s1": (64, 64), "s2": (64, 128), "s3": (128, 256),
            "s4": (256, 512)}


def _conv(cin, cout, k):
    return {"w": ((k, k, cin, cout), "normal"), "b": ((cout,), "zeros")}


def _norm(c):
    return {"scale": ((c,), "ones"), "bias": ((c,), "zeros")}


def shapes(n_classes: int) -> Dict:
    """The parameter tree as ``(shape, init)`` leaves."""
    tree = {"stem": {"conv": _conv(3, 64, 7), "gn": _norm(64)},
            "head": {"w": ((512, n_classes), "normal"),
                     "b": ((n_classes,), "zeros")}}
    for s, (cin, cout) in CHANNELS.items():
        for i, c_in in ((1, cin), (2, cout)):
            blk = {"conv1": _conv(c_in, cout, 3), "gn1": _norm(cout),
                   "conv2": _conv(cout, cout, 3), "gn2": _norm(cout)}
            if c_in != cout:
                blk["down"] = _conv(c_in, cout, 1)
            tree[f"{s}b{i}"] = blk
    return tree


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init(key, n_classes: int, dtype=jnp.float32) -> Dict:
    leaves, treedef = jax.tree.flatten(shapes(n_classes), is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (shape, kind), key in zip(leaves, keys):
        if kind == "zeros":
            out.append(jnp.zeros(shape, dtype))
        elif kind == "ones":
            out.append(jnp.ones(shape, dtype))
        else:
            w = jax.random.normal(key, shape, jnp.float32)
            out.append((w * (1.0 / np.sqrt(shape[-2]))).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def split(params: Dict, cut: int) -> Tuple[Dict, Dict]:
    return ({k: params[k] for k in STAGES[:cut]},
            {k: params[k] for k in STAGES[cut:]})


def param_bits(n_classes: int, cut: int) -> float:
    """Segment A's weights in bits at 32 bits a value."""
    a, _ = split(shapes(n_classes), cut)
    leaves = jax.tree.leaves(a, is_leaf=_is_leaf)
    return 32.0 * sum(int(np.prod(shape)) for shape, _ in leaves)


def _conv2d(p, x, stride):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _group_norm(p, x, groups=8, eps=1e-5):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def _block(p, x, stride):
    h = jax.nn.relu(_group_norm(p["gn1"], _conv2d(p["conv1"], x, stride)))
    h = _group_norm(p["gn2"], _conv2d(p["conv2"], h, 1))
    if "down" in p:
        x = _conv2d(p["down"], x, stride)
    return jax.nn.relu(x + h)


def apply(params: Dict, x, lo: int, hi: int):
    """Stages [lo, hi) of the network."""
    for name in STAGES[lo:hi]:
        p = params[name]
        if name == "stem":
            x = jax.nn.relu(_group_norm(p["gn"], _conv2d(p["conv"], x, 2)))
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
        elif name == "head":
            x = x.mean(axis=(1, 2)) @ p["w"] + p["b"]
        else:
            x = _block(p, x, STRIDE.get(name, 1))
    return x


def loss_fn(pa, pb, images, labels, cut: int):
    z = apply(pa, images, 0, cut)
    logits = apply(pb, z, cut, len(STAGES)).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def _clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def make_pass(data, cut: int, lr: float, momentum: float, grad_clip: float,
              dtype, n_steps: int, half_batch: bool = False):
    """A jitted pass of ``n_steps`` SGD steps on satellite ``sat``'s
    batches ``idx0 .. idx0 + n_steps - 1``: returns the new state, the
    step losses and the first step's gradients."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state, sat, idx0):
        def step(carry, j):
            (pa, pb, ma, mb), g0 = carry
            batch = data(sat, idx0 + j)
            images, labels = batch["images"].astype(dtype), batch["labels"]
            if half_batch:
                half = labels.shape[0] // 2
                images, labels = images[:half], labels[:half]
            loss, (ga, gb) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                pa, pb, images, labels, cut)
            ga, gb = _clip(ga, grad_clip), _clip(gb, grad_clip)
            ma = jax.tree.map(lambda m, g: (momentum * m + g).astype(dtype),
                              ma, ga)
            mb = jax.tree.map(lambda m, g: (momentum * m + g).astype(dtype),
                              mb, gb)
            pa = jax.tree.map(lambda p, m: (p - lr * m).astype(dtype),
                              pa, ma)
            pb = jax.tree.map(lambda p, m: (p - lr * m).astype(dtype),
                              pb, mb)
            g0 = jax.tree.map(lambda a, b: jnp.where(j == 0, a, b),
                              (ga, gb), g0)
            return ((pa, pb, ma, mb), g0), loss

        g0 = jax.tree.map(jnp.zeros_like, state[:2])
        (state, g0), losses = jax.lax.scan(step, (state, g0),
                                           jnp.arange(n_steps))
        return state, losses, g0

    return run


def leaf_paths(tree: Dict, prefix: str) -> List[Tuple[str, object]]:
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        out.append((prefix + "/" + "/".join(str(k.key) for k in path), leaf))
    return out


def train(data, *, seed: int, n_classes: int, cut: int, lr: float,
          momentum: float, grad_clip: float, passes: List[Tuple[int, int,
                                                                int]],
          dtype=jnp.float32, half_batch: bool = False,
          precision: str = "highest") -> dict:
    """Follow ``passes`` = ``[(sat, idx0, n_steps), ...]`` from the seed's
    weights.  Returns the mean loss of each pass, the norm of each leaf's
    change over all of them and of its first gradient, keyed by path."""
    with jax.default_matmul_precision(precision):
        params = jax.jit(init, static_argnums=(1, 2))(
            jax.random.key(seed), n_classes, dtype)
        pa, pb = split(params, cut)
        p0 = {k: np.asarray(v, np.float32) for k, v in
              leaf_paths(pa, "a") + leaf_paths(pb, "b")}
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)   # noqa: E731
        state = (pa, pb, zeros(pa), zeros(pb))
        fns: Dict[int, object] = {}
        losses, grad_norms = [], None
        for sat, idx0, n in passes:
            if n not in fns:
                fns[n] = make_pass(data, cut, lr, momentum, grad_clip,
                                   dtype, n, half_batch)
            state, step_losses, first = fns[n](state, jnp.uint32(sat),
                                               jnp.uint32(idx0))
            losses.append(float(np.mean(np.asarray(step_losses,
                                                   np.float64))))
            if grad_norms is None:
                ga, gb = first
                grad_norms = {k: float(np.linalg.norm(
                    np.asarray(v, np.float32)))
                    for k, v in leaf_paths(ga, "a") + leaf_paths(gb, "b")}
        pa, pb = state[0], state[1]
        delta = {k: float(np.linalg.norm(np.asarray(v, np.float32) - p0[k]))
                 for k, v in leaf_paths(pa, "a") + leaf_paths(pb, "b")}
    return {"pass_loss": losses, "delta_norm": delta,
            "first_grad_norm": grad_norms}
