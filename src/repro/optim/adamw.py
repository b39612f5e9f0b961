"""From-scratch AdamW with global-norm clipping and per-leaf update masks.

API mirrors the init/update transform style so the trainer stays functional:

    opt = AdamW(lr=schedule.warmup_cosine(...), weight_decay=0.1)
    state = opt.init(params)
    params, state = opt.apply(params, grads, state, mask=mask)

The `mask` pytree (True = update) is how Instant-3D's *different update
frequencies* (paper §3.3) reach the optimizer: on color-frozen iterations the
color grid's moments and parameters are left untouched, exactly like the
accelerator skipping that branch's back-propagation.

Moments are kept in f32 regardless of param dtype (bf16-safe); per-parameter
lr scaling supports Instant-NGP's grid-vs-MLP lr split.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..obs import trace as _trace


class AdamWState(NamedTuple):
    step: jnp.ndarray  # int32 scalar
    m: Any             # pytree like params, f32
    v: Any             # pytree like params, f32


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale, tree), norm


class AdamW:
    def __init__(
        self,
        lr: float | Callable,
        b1: float = 0.9,
        b2: float = 0.999,
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        clip_norm: float | None = None,
        lr_scale_fn: Callable[[tuple], float] | None = None,
    ):
        """lr may be a float or a step->lr schedule.  lr_scale_fn maps a leaf
        path (tuple of keys) to a multiplicative lr factor (e.g. hash grids
        at 1.0, MLPs at 0.1 as in Instant-NGP)."""
        self.lr = lr if callable(lr) else (lambda step, _lr=lr: jnp.asarray(_lr, jnp.float32))
        self.b1, self.b2, self.eps = b1, b2, eps
        self.weight_decay = weight_decay
        self.clip_norm = clip_norm
        self.lr_scale_fn = lr_scale_fn

    def init(self, params) -> AdamWState:
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return AdamWState(
            step=jnp.zeros((), jnp.int32),
            m=jax.tree.map(zeros, params),
            v=jax.tree.map(zeros, params),
        )

    def apply(self, params, grads, state: AdamWState, mask=None):
        """Returns (new_params, new_state).  mask: pytree of bools, True=update."""
        with _trace.stage("optimizer/adam", cat="optim"):
            return self._apply(params, grads, state, mask)

    def _apply(self, params, grads, state: AdamWState, mask):
        if self.clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, self.clip_norm)

        step = state.step + 1
        lr_t = self.lr(step)
        b1, b2 = self.b1, self.b2
        bias1 = 1.0 - b1 ** step.astype(jnp.float32)
        bias2 = 1.0 - b2 ** step.astype(jnp.float32)

        if mask is None:
            mask = jax.tree.map(lambda _: True, params)

        paths_scales = None
        if self.lr_scale_fn is not None:
            flat, _ = jax.tree_util.tree_flatten_with_path(params)
            # normalize DictKey/SequenceKey entries to plain strings
            as_str = lambda k: str(getattr(k, "key", getattr(k, "idx", k)))
            paths_scales = [
                self.lr_scale_fn(tuple(as_str(k) for k in path)) for path, _ in flat
            ]

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        flat_mask = treedef.flatten_up_to(mask)

        new_p, new_m, new_v = [], [], []
        for i, (p, g, m, v, upd) in enumerate(zip(flat_p, flat_g, flat_m, flat_v, flat_mask)):
            g32 = g.astype(jnp.float32)
            m1 = b1 * m + (1 - b1) * g32
            v1 = b2 * v + (1 - b2) * jnp.square(g32)
            scale = paths_scales[i] if paths_scales is not None else 1.0
            update = lr_t * scale * (m1 / bias1) / (jnp.sqrt(v1 / bias2) + self.eps)
            if self.weight_decay:
                update = update + lr_t * scale * self.weight_decay * p.astype(jnp.float32)
            p1 = (p.astype(jnp.float32) - update).astype(p.dtype)
            # masked leaves keep params AND moments frozen (branch skipped)
            new_p.append(jnp.where(upd, p1, p))
            new_m.append(jnp.where(upd, m1, m))
            new_v.append(jnp.where(upd, v1, v))

        return (
            treedef.unflatten(new_p),
            AdamWState(step, treedef.unflatten(new_m), treedef.unflatten(new_v)),
        )
