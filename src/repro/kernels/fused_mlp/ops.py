"""Jitted wrappers for the fused NeRF MLPs with backend routing + padding.

Routing resolves through the `repro.kernels` KernelBackend registry:
`backend=None` uses the process default; strings ("ref", "pallas",
"pallas-interpret", "pallas-tpu", "auto") are accepted as explicit overrides.

The Pallas kernels are forward-only; to keep pallas backends trainable the
wrappers carry a custom VJP built by ONE shared `_make_mlp_op` (the 2- and
3-layer ops used to duplicate the whole fwd/bwd plumbing).  What the VJP
keeps live between forward and backward follows `residual_policy`:

* "recompute" (default): residuals are the op INPUTS only — the backward is
  `jax.vjp` of the jnp reference over them, re-running the forward chain.
  Nothing beyond the already-live inputs is stashed (in particular `x` and
  `w1` are kept once, as aliases, not copied per layer).
* "stash": additionally keep each hidden layer's PRE-activation (the
  smallest set that lets the backward skip every hidden-layer matmul — the
  relu masks and post-activations fall out elementwise).  The backward
  chains `jax.vjp` over the reference chain split at those stashed
  pre-activations; since the split pieces compose to the exact primitive
  sequence of the whole-chain reference, the gradients are BIT-identical to
  "recompute" — the policy trades residual bandwidth for backward FLOPs,
  never numerics.

A fused backward kernel is a future optimization — see ROADMAP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from . import ref

RESIDUAL_POLICIES = ("stash", "recompute")


def _pad_rows(x, multiple):
    n = x.shape[0]
    if n % multiple == 0:
        return x, n
    pad = multiple - n % multiple
    return jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)]), n


def _resolve(backend):
    from .. import resolve_backend
    return resolve_backend(backend, op="mlp")


# the reference chains split at the pre-activations: mlp2 == _relu_lin(_lin(
# x, w1, b1), w2, b2) primitive-for-primitive (mlp3 adds one more _relu_lin),
# so chaining the pieces' jax.vjp at stashed pre-activations applies the same
# pullbacks, in the same order, to the same values as whole-chain jax.vjp.

def _lin(x, w, b):
    return x.astype(jnp.float32) @ w.astype(jnp.float32) + b


def _relu_lin(z, w, b):
    return _lin(jnp.maximum(z, 0.0), w, b)


@functools.lru_cache(maxsize=None)
def _make_mlp_op(n_layers: int, block_rows: int, interpret: bool,
                 residual_policy: str):
    """Custom-VJP pallas MLP op: op(x, w1, b1, ..., wN, bN) -> out.

    One builder for both depths (n_layers in {2, 3}); cached so every call
    site with the same static config shares one op instance (stable jit
    caches, no re-tracing).
    """
    if residual_policy not in RESIDUAL_POLICIES:
        raise ValueError(f"residual_policy must be one of {RESIDUAL_POLICIES}")
    ref_fn = ref.mlp2 if n_layers == 2 else ref.mlp3
    kernel_fn = _kernel.fused_mlp2 if n_layers == 2 else _kernel.fused_mlp3

    @jax.custom_vjp
    def op(x, *params):
        xp, n = _pad_rows(x, block_rows)
        out = kernel_fn(xp, *params, block_rows=block_rows, interpret=interpret)
        return out[:n]

    def op_fwd(x, *params):
        out = op(x, *params)
        if residual_policy == "recompute":
            return out, (None, (x, *params))
        zs = [_lin(x, params[0], params[1])]
        for i in range(1, n_layers - 1):
            zs.append(_relu_lin(zs[-1], params[2 * i], params[2 * i + 1]))
        return out, (tuple(zs), (x, *params))

    def op_bwd(res, g):
        zs, inputs = res
        if zs is None:
            _, vjp = jax.vjp(ref_fn, *inputs)
            return vjp(g)
        x, *params = inputs
        grads = [None] * len(inputs)
        for i in reversed(range(1, n_layers)):
            _, vjp = jax.vjp(_relu_lin, zs[i - 1], params[2 * i], params[2 * i + 1])
            g, grads[1 + 2 * i], grads[2 + 2 * i] = vjp(g)
        _, vjp = jax.vjp(_lin, x, params[0], params[1])
        grads[0], grads[1], grads[2] = vjp(g)
        return tuple(grads)

    op.defvjp(op_fwd, op_bwd)
    return op


def mlp2(x, w1, b1, w2, b2, *, backend=None,
         block_rows: int = _kernel.DEFAULT_BLOCK_ROWS,
         residual_policy: str = "recompute"):
    be = _resolve(backend)
    if be.use_pallas:
        op = _make_mlp_op(2, block_rows, be.interpret, residual_policy)
        return op(x, w1, b1, w2, b2)
    return ref.mlp2(x, w1, b1, w2, b2)


def mlp3(x, w1, b1, w2, b2, w3, b3, *, backend=None,
         block_rows: int = _kernel.DEFAULT_BLOCK_ROWS,
         residual_policy: str = "recompute"):
    be = _resolve(backend)
    if be.use_pallas:
        op = _make_mlp_op(3, block_rows, be.interpret, residual_policy)
        return op(x, w1, b1, w2, b2, w3, b3)
    return ref.mlp3(x, w1, b1, w2, b2, w3, b3)
