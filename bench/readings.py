"""What the metric readers share: MFU, kernel lookups and the idle share.

What a unit of work holds (points, FLOPs, rows of a kernel call) each
driver says for its own entry point."""
from __future__ import annotations

from bench import counts

# the Pallas MLP kernels: the trace names each call after the jitted
# function that issues it, under the transforms around it
# (`%jvp_jit_fused_mlp2__.3 = f32[...] custom-call(...)` in a training step,
# `%vmap_jit_fused_mlp3__.2 = ...` in a served render)
MLP_KERNELS = {"fused_mlp2": "density", "fused_mlp3": "color"}


def mfu(run):
    """Model FLOPs per second of the window over the chips' bf16 peak, in %."""
    if not run.window.get("units"):
        return None
    peak = counts.peaks(run.device_kind)
    chips = run.cell.workload["chips"]
    flops = run.driver.window_flops(run.window)
    return 100.0 * flops / run.window["seconds"] / (chips * peak["bf16_flops_per_s"])


def kernel_of(e) -> str | None:
    """Which Pallas MLP kernel a device op is, from its instruction name."""
    name = e.name.split(" = ", 1)[0]
    return next((k for k in MLP_KERNELS if k in name), None)


def mlp_roofline(run):
    """Least time of the MLP kernel calls in the window over their device
    time, in %: each call's bound is the larger of its FLOPs over the bf16
    peak and its bytes over the HBM bandwidth."""
    if run.trace is None:
        return None
    peak = counts.peaks(run.device_kind)
    heads = counts.mlp_layers(run.cell.config["field"])
    rows = run.driver.kernel_rows()
    least = spent = 0.0
    for e in run.trace.op_events():
        k = kernel_of(e)
        if k is None:
            continue
        least += counts.roofline_seconds(*counts.mlp_kernel_cost(heads[MLP_KERNELS[k]], rows),
                                         peak)
        spent += e.dur * 1e-9
    return 100.0 * least / spent if spent > 0 else None


def idle_share(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def corner_stream_rows(run) -> int:
    """Rows of the hash grid's per-corner stream in one training step: every
    point's 8 corners at every level, one grid's worth."""
    return run.driver.points_per_unit() * 8 * run.cell.config["field"]["n_levels"]


def on_corner_stream(e, rows: int) -> bool:
    """Whether a device op reads or writes an array of `rows` rows: its HLO
    text (the trace's name for it) shows that leading dimension."""
    return f"[{rows}," in e.name or f"[{rows}]" in e.name
