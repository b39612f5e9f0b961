"""Pallas TPU kernel: fused small-MLP forward (MLP-unit analogue).

The paper pairs a systolic-array MLP unit with an adder-tree unit for tiny
output channels.  On TPU the MXU *is* the systolic array; the win to port is
not the adder tree but the fusion: all layers of the 64-wide MLP execute in
one kernel with weights resident in VMEM, so activations never round-trip to
HBM between layers (tiny-cuda-nn's "fully fused MLP", TPU edition).

Blocking: grid over rows of x; weight operands use constant index maps so
they are loaded into VMEM once and reused across all row blocks.  Matmul
dims are zero-padded to MXU-friendly multiples of 128 by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 512


def _lin(x, w_ref, b_ref):
    # float32 at HIGHEST: the kernel agrees with the float32 `ref` oracle
    # instead of rounding its operands to bfloat16 on the MXU
    return jnp.dot(x, w_ref[...].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32) + b_ref[...]


def _mlp2_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    h = jnp.maximum(_lin(x, w1_ref, b1_ref), 0.0)
    o_ref[...] = _lin(h, w2_ref, b2_ref).astype(o_ref.dtype)


def _mlp3_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    h1 = jnp.maximum(_lin(x, w1_ref, b1_ref), 0.0)
    h2 = jnp.maximum(_lin(h1, w2_ref, b2_ref), 0.0)
    o_ref[...] = _lin(h2, w3_ref, b3_ref).astype(o_ref.dtype)


def _full(shape):
    return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_mlp2(x, w1, b1, w2, b2, *, block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool):
    n, d_in = x.shape
    h = w1.shape[1]
    d_out = w2.shape[1]
    assert n % block_rows == 0
    return pl.pallas_call(
        _mlp2_kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d_in), lambda i: (i, 0)),
            _full((d_in, h)), _full((1, h)),
            _full((h, d_out)), _full((1, d_out)),
        ],
        out_specs=pl.BlockSpec((block_rows, d_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d_out), jnp.float32),
        interpret=interpret,
    )(x, w1, b1.reshape(1, -1), w2, b2.reshape(1, -1))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_mlp3(x, w1, b1, w2, b2, w3, b3, *, block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool):
    n, d_in = x.shape
    h1 = w1.shape[1]
    h2 = w2.shape[1]
    d_out = w3.shape[1]
    assert n % block_rows == 0
    return pl.pallas_call(
        _mlp3_kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d_in), lambda i: (i, 0)),
            _full((d_in, h1)), _full((1, h1)),
            _full((h1, h2)), _full((1, h2)),
            _full((h2, d_out)), _full((1, d_out)),
        ],
        out_specs=pl.BlockSpec((block_rows, d_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d_out), jnp.float32),
        interpret=interpret,
    )(x, w1, b1.reshape(1, -1), w2, b2.reshape(1, -1), w3, b3.reshape(1, -1))
