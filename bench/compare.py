"""The numbers that decide `correct`: the program's readings against the reference's.

Training: each checked step's loss; the first gradient as the optimizer got
it; the parameters' change over the checked steps.  Gradient and change are
compared leaf by leaf as the gap between the two norms, measured against
the reference's norm of that leaf or of the median leaf, whichever is
larger, and the worst leaf is reported.  Leaves whose reference gradient is
under a thousandth of the median leaf's take no part (a frozen colour grid
on the first step has none at all).

Rendering: the largest gap of any pixel's colour and depth over the checked
views.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
QUIET = 1e-3          # a leaf whose reference gradient is under this x the median's


def leaves(tree, prefix="") -> dict:
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(leaves(tree[k], f"{prefix}{k}/"))
        return out
    return {prefix.rstrip("/"): np.asarray(tree, np.float64)}


def _norms(tree) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in leaves(tree).items()}


def _worst(prog: dict, ref: dict, keys) -> tuple[float, str]:
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog: {losses, first_grads, params}; ref: the same plus `init`, the
    seed parameters both started from, and `grad_norms`, each step's
    reference gradient norms by leaf."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))

    g_ref = _norms(ref["first_grads"])
    med = float(np.median(list(g_ref.values())))
    g_keys = [k for k, n in g_ref.items() if n >= QUIET * med]
    grad_gap, grad_leaf = _worst(_norms(prog["first_grads"]), g_ref, g_keys)

    moved = {k: max(step[k] for step in ref["grad_norms"]) for k in g_ref}
    med_moved = float(np.median(list(moved.values())))
    c_keys = [k for k, n in moved.items() if n >= QUIET * med_moved]
    init = leaves(ref["init"])
    d_prog = {k: float(np.linalg.norm(v - init[k])) for k, v in leaves(prog["params"]).items()}
    d_ref = {k: float(np.linalg.norm(v - init[k])) for k, v in leaves(ref["params"]).items()}
    change_gap, change_leaf = _worst(d_prog, d_ref, c_keys)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "change_gap": change_gap,
            "_grad_leaf": grad_leaf, "_change_leaf": change_leaf,
            "_left_out": sorted(set(g_ref) - set(c_keys))}


def render_numbers(prog: dict, ref: dict) -> dict:
    rgb_gap = depth_gap = 0.0
    for got, want in zip(prog["views"], ref["views"]):
        if got is None:
            return {"rgb_gap": float("inf"), "depth_gap": float("inf")}
        rgb_gap = max(rgb_gap, float(np.max(np.abs(np.asarray(got[0]) - want[0]))))
        depth_gap = max(depth_gap, float(np.max(np.abs(np.asarray(got[1]) - want[1]))))
    return {"rgb_gap": rgb_gap, "depth_gap": depth_gap}


def limits(cell: str) -> dict:
    """{number: limit} for a cell, from `bench/limits/<cell>.json`."""
    spec = json.loads((HERE / "limits" / f"{cell}.json").read_text())
    return {k: float(v["limit"]) for k, v in spec["numbers"].items()}


def judge(numbers: dict, lim: dict) -> tuple[bool, list]:
    """correct iff every limited number is finite and within its limit."""
    rows = [(k, numbers.get(k, float("nan")), v) for k, v in sorted(lim.items())]
    ok = all(np.isfinite(x) and x <= v for _, x, v in rows)
    return bool(ok), rows
