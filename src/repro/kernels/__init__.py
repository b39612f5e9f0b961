"""Pallas TPU kernels for the perf-critical compute of Instant-3D.

Each subpackage ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper with backend routing), ref.py (pure-jnp oracle used both for
allclose validation and as the CPU/autodiff path).

Routing is decided here and nowhere else.  Every ops module resolves its
backend through `resolve_backend(backend, op=...)`; the one user-facing knob
is the process-wide request, set via `set_backend(...)`, the `REPRO_BACKEND`
env var, or left on "auto".  `TPU_LOWERING` says, per op, whether its Pallas
kernel lowers for the TPU at `FieldConfig()` widths, and why not where it
does not (tests/test_tpu_compile.py compiles every op it marks as lowering);
`TPU_MAX_WIDTH` bounds the rows an op's TPU kernel takes, so the route
follows the rows' width where the op passes it.

Canonical backends:

  ref              pure jnp — CPU production path and the autodiff oracle
  pallas-interpret Pallas kernels in interpreter mode (validation on CPU)
  pallas-tpu       compiled Pallas kernels (requires a TPU jax backend)

Aliases accepted anywhere a backend name is taken: "auto" (per op: pallas-tpu
on a TPU where the op lowers, ref everywhere else) and "pallas" (pallas-tpu on
a TPU, pallas-interpret elsewhere).  Asking for pallas-tpu, directly or
through "pallas", on an op without a TPU lowering raises when the op is
built; nothing falls back silently.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class KernelBackend:
    """Resolved routing decision shared by every ops module.

    use_pallas: route to the Pallas kernel (vs the jnp reference).
    interpret:  run the Pallas kernel in interpreter mode (non-TPU hosts).
    """
    name: str
    use_pallas: bool
    interpret: bool


REF = KernelBackend("ref", use_pallas=False, interpret=False)
PALLAS_INTERPRET = KernelBackend("pallas-interpret", use_pallas=True, interpret=True)
PALLAS_TPU = KernelBackend("pallas-tpu", use_pallas=True, interpret=False)

_CANONICAL = {b.name: b for b in (REF, PALLAS_INTERPRET, PALLAS_TPU)}

# op -> None where its Pallas kernel lowers for a TPU v5e at FieldConfig()
# widths (L=16, F=2, T_D=2^18, ~200k points), else the compiler's refusal.
TPU_LOWERING: dict[str, str | None] = {
    "mlp": None,
    "composite": None,
    "hash_encode": (
        "output block (B,1,F) of the (N,L,F) result breaks the (8,128) block "
        "rule; one whole level table per VMEM block pads to 128 MiB at "
        "T=2^18 against 16 MiB of scoped VMEM; corner reads are a dynamic "
        "vector gather"
    ),
    "fused_encode": (
        "same whole-level VMEM table and output block as hash_encode, plus "
        "an in-kernel argsort (sort has no Mosaic lowering)"
    ),
    "fused_step": (
        "forward sorts in-kernel (sort has no Mosaic lowering) over whole "
        "level tables in VMEM; backward gathers dynamically from, and keeps "
        "resident, both full (L,T,F) gradient tables"
    ),
    "bum_scatter": None,
}

# op -> widest row (the last dimension) its TPU kernel takes.  Under `auto`
# wider rows take `ref`, as an op without a TPU lowering does, and an
# explicit pallas-tpu request for them raises.
TPU_MAX_WIDTH: dict[str, int] = {
    "bum_scatter": 8,   # 3F bfloat16 pieces in 24 sublanes; the hash grids' F=2 rows, not an LM embedding's
}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def available_backends() -> tuple[str, ...]:
    """Capability detection: which canonical backends can run on this host."""
    return ("ref", "pallas-interpret") + (("pallas-tpu",) if _on_tpu() else ())


def _check_name(name: str) -> str:
    name = name.lower()
    if name not in ("auto", "pallas") and name not in _CANONICAL:
        raise ValueError(
            f"unknown backend {name!r}; expected one of "
            f"{tuple(_CANONICAL)} or aliases ('auto', 'pallas')"
        )
    if name in _CANONICAL and name not in available_backends():
        raise ValueError(
            f"backend {name!r} unavailable on this host; have {available_backends()}"
        )
    return name


def resolve_backend(backend: str | KernelBackend | None = None, *,
                    op: str, width: int | None = None) -> KernelBackend:
    """Map a backend request (None => process default) to the KernelBackend
    that `op` runs on, for rows `width` wide where the op's TPU kernel
    takes rows up to `TPU_MAX_WIDTH[op]`.  Raises for an unknown op or
    backend, for a backend this host cannot run, and for pallas-tpu on an
    op without a TPU lowering at that width."""
    if op not in TPU_LOWERING:
        raise ValueError(f"unknown kernel op {op!r}; have {tuple(TPU_LOWERING)}")
    why = TPU_LOWERING[op]
    if why is None and width is not None and width > TPU_MAX_WIDTH.get(op, width):
        why = f"the TPU kernel takes rows up to {TPU_MAX_WIDTH[op]} wide, not {width}"
    if isinstance(backend, KernelBackend):
        be = backend
    else:
        name = _check_name(get_backend() if backend is None else backend)
        if name == "auto":
            be = PALLAS_TPU if _on_tpu() and why is None else REF
        elif name == "pallas":
            be = PALLAS_TPU if _on_tpu() else PALLAS_INTERPRET
        else:
            be = _CANONICAL[name]
    if be is PALLAS_TPU and why is not None:
        raise ValueError(
            f"op {op!r} has no Pallas TPU lowering ({why}); "
            f"use backend 'auto' or 'ref'"
        )
    return be


def routing(backend: str | None = None) -> dict[str, str]:
    """op -> canonical backend name each op resolves to for `backend`
    (None => process default).  What the entry points print."""
    return {op: resolve_backend(backend, op=op).name for op in TPU_LOWERING}


_default: str | None = None


def get_backend() -> str:
    """The process-wide backend request (the single user-facing knob)."""
    global _default
    if _default is None:
        _default = _check_name(os.environ.get("REPRO_BACKEND", "auto"))
    return _default


def set_backend(backend: str) -> str:
    """Set the process-wide backend request; returns its canonical name.

    Binding times differ by op: hash-grid encoders bake routing (forward
    AND merged-backward) at construction, while MLP/composite ops resolve
    at trace time — and already-compiled jitted functions are never
    invalidated by this call.  Changing the backend mid-session therefore
    yields a mix of old and new routing; set it once, before building
    models or tracing any step function.
    """
    global _default
    _default = _check_name(backend)
    return _default


from . import hash_encode, grid_update, fused_mlp, volume_render, fused_path  # noqa: F401,E402
