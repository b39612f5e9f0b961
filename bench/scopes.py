"""Program scopes of device ops, and device idle time inside program spans.

The program names the stages of its compiled code with
`repro.obs.trace.stage`, a `jax.named_scope`: XLA keeps the scope path in
each HLO instruction's `op_name` metadata (`jit(member_steps)/while/body/
closed_call/transpose(jvp(pipeline/shade))/hash_grid/bwd/...`).  A TPU trace
carries that path as the `tf_op` stat of the op's event metadata, which the
trace loader does not keep (`ProfileData` yields an event's own stats only);
what it keeps is the event's name, the instruction's HLO text without its
metadata.  So the path is read back from the programs themselves: every
program a run executes is in the persistent compile cache, and the optimized
HLO of each maps an instruction's text to its `op_name`.

An op is under a scope when the scope's name appears in its path as whole
path components, inside transform wrappers too (`transpose(jvp(...))`,
`vmap(...)`).  Where one instruction text stands for several paths across
the cached programs, the op is under a scope only if every one of them is.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import time

# the stages the program names; the breakdown keys an op by the outermost
# and the innermost of them on its path
STAGES = ("pipeline/sample", "pipeline/cull", "pipeline/redistribute",
          "pipeline/compact", "pipeline/shade", "pipeline/composite",
          "hash_grid/fwd", "hash_grid/bwd", "hash_grid/bwd/stream",
          "grid_update/sort", "grid_update/merge", "grid_update/commit",
          "kernels/fused_step/fwd", "kernels/fused_step/bwd", "optimizer/adam")
# the program's host spans around device work; the breakdown gives the
# device idle inside each
HOST_SPANS = ("trainer/step", "trainer/sample", "trainer/log_sync",
              "serve3d/render_prepare", "serve3d/render_dispatch", "serve3d/render_readback")
# the names of the program's `repro.obs` spans, as the profiler shows them
_PROGRAM_SPAN = re.compile(r"^(trainer|serve3d|pipeline|kernels|hash_grid|grid_update|optimizer)/")

_HEAD = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")
_META = re.compile(r', metadata=\{op_name="((?:[^"\\]|\\.)*)"[^}]*\}')
_ANY_META = re.compile(r", metadata=\{[^}]*\}")


def _text(line: str) -> str:
    return re.sub(r"^\s*(?:ROOT\s+)?", "", line).strip()


def head(text: str) -> str | None:
    """An instruction's name, result shape and opcode, from its HLO text."""
    m = _HEAD.match(text)
    return " ".join(m.groups()) if m else None


class Programs:
    """Instruction text -> the `op_name` paths it carries in the programs."""

    def __init__(self):
        self.by_text: dict = {}
        self.by_head: dict = {}
        self.modules = 0

    def add_hlo(self, hlo: str) -> None:
        """Reads every instruction line of one module's HLO text printed
        with metadata and operand shapes."""
        self.modules += 1
        for line in hlo.splitlines():
            m = _META.search(line)
            if m is None:
                continue
            path = m.group(1).replace('\\"', '"')
            bare = _text(_ANY_META.sub("", line))
            self.by_text.setdefault(bare, set()).add(path)
            h = head(bare)
            if h is not None:
                self.by_head.setdefault(h, set()).add(path)

    def paths(self, name: str) -> set:
        """The paths of a trace event's instruction: by its whole text, else
        by its name, result shape and opcode."""
        found = self.by_text.get(_text(name))
        if found is None:
            found = self.by_head.get(head(name) or "", set())
        return found


def _scope_re(scope: str):
    return re.compile(rf"(?:^|[/(;]){re.escape(scope)}(?:$|[/):;])")


def in_scope(path: str, scope: str) -> bool:
    return _scope_re(scope).search(path) is not None


def paths_of(e, programs: Programs | None) -> set:
    """The op's program paths: the `tf_op` stat where the event carries it,
    else the compiled programs' metadata for its instruction."""
    path = e.stats.get("tf_op")
    if path:
        return {str(path)}
    return programs.paths(e.name) if programs is not None else set()


def scope_of(e, programs: Programs | None = None) -> str | None:
    """The op's program path, where it is known and unique."""
    found = paths_of(e, programs)
    return next(iter(found)) if len(found) == 1 else None


def under(e, scope: str, programs: Programs | None) -> bool:
    found = paths_of(e, programs)
    return bool(found) and all(in_scope(p, scope) for p in found)


# ---- the compiled programs, from the persistent compile cache ----

def _print_options():
    from jax._src.lib import xla_client as xc
    opts = xc._xla.HloPrintOptions()
    opts.print_metadata = True
    opts.print_operand_shape = True
    opts.print_backend_config = False
    return opts


def cache_dir() -> str | None:
    import jax
    return jax.config.jax_compilation_cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR")


def from_cache(path: str | None, chips: int) -> Programs | None:
    """Every program in the compile cache at `path`, deserialized onto the
    first `chips` devices (or one) and printed; None when nothing loads."""
    if not path or not os.path.isdir(path):
        return None
    import jax
    from jax._src import compilation_cache as cc
    from jax._src.lib import xla_client as xc

    devs = jax.devices()
    lists = [xc.DeviceList(tuple(devs[:n])) for n in sorted({chips, 1}, reverse=True)
             if n <= len(devs)]
    client, opts = devs[0].client, _print_options()
    progs, failed, t0 = Programs(), 0, time.perf_counter()
    for f in sorted(glob.glob(os.path.join(path, "*-cache"))):
        try:
            with open(f, "rb") as fh:
                ser, _ = cc.extract_executable_and_time(cc.decompress_executable(fh.read()))
        except Exception:
            failed += 1
            continue
        for dl in lists:
            try:
                ex = client.deserialize_executable(ser, dl, None)
            except Exception:
                continue
            for mod in ex.hlo_modules():
                progs.add_hlo(mod.to_string(opts))
            del ex
            break
        else:
            failed += 1
    print(f"bench: scopes: {progs.modules} programs from the compile cache in "
          f"{time.perf_counter() - t0:.1f}s, {failed} unread", file=sys.stderr)
    return progs if progs.modules else None


_PROGRAMS: dict = {}


def programs(run) -> Programs | None:
    """The run's compiled programs, read once per trace."""
    key = id(run.trace)
    if key not in _PROGRAMS:
        try:
            _PROGRAMS[key] = from_cache(cache_dir(), run.cell.workload["chips"])
        except Exception as e:          # a reader reads nothing; it never fails the run
            print(f"bench: scopes: compile cache unreadable: {e!r}", file=sys.stderr)
            _PROGRAMS[key] = None
    return _PROGRAMS[key]


# ---- readings ----

def scope_seconds(trace, scope: str, progs: Programs | None) -> float:
    """Device seconds of the window's ops under `scope`, per chip."""
    s = sum(e.dur for e in trace.op_events() if under(e, scope, progs))
    return s * 1e-9 / max(1, len(trace.planes))


def scope_ms(run, scope: str):
    """Device ms per unit of work under `scope`; None when no op is."""
    if run.trace is None or not run.window.get("units"):
        return None
    s = scope_seconds(run.trace, scope, programs(run))
    return 1e3 * s / run.window["units"] if s > 0 else None


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted unions of intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside_seconds(trace, names) -> float | None:
    """Device-idle seconds of the window that fall inside host spans named
    in `names` (each instant counted once, however the spans nest),
    averaged over the chips; None when no such span is in the window."""
    from bench.devtrace import union
    spans = union((max(e.start, trace.t0), min(e.start + e.dur, trace.t1))
                  for e in trace.host if e.name in names)
    spans = [(a, b) for a, b in spans if b > a]
    if not spans or not trace.planes:
        return None
    inside = sum(b - a for a, b in spans)
    idle = [inside - _overlap(spans, union((e.start, e.start + e.dur) for e in trace.ops[p]))
            for p in trace.planes]
    return sum(idle) / len(idle) * 1e-9


def idle_inside_ms(run, names):
    """Device-idle ms per unit inside the named host spans."""
    if run.trace is None or not run.window.get("units"):
        return None
    s = idle_inside_seconds(run.trace, names)
    return None if s is None else 1e3 * s / run.window["units"]


def stage_key(paths: set) -> str:
    """`outermost > innermost` named stage on the op's path(s)."""
    if not paths:
        return "(no program path)"
    if len(paths) > 1:
        keys = {stage_key({p}) for p in paths}
        return keys.pop() if len(keys) == 1 else "(ambiguous)"
    path = next(iter(paths))
    at: dict = {}                       # start -> the longest stage named there
    for s in STAGES:
        for m in _scope_re(s).finditer(path):
            if len(s) > len(at.get(m.start(), "")):
                at[m.start()] = s
    if not at:
        return "(no scope)"
    outer, inner = at[min(at)], at[max(at)]
    return outer if outer == inner else f"{outer} > {inner}"


def _top(by: dict, n: int) -> list:
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(run, top: int = 15) -> dict | None:
    """Device seconds of the window per chip: by `stage_key`, with the
    Pallas MLP kernels named apart (the top `top`, and the rest summed); the
    ops under no stage by their path, or their HLO text where they have
    none; and the device idle inside each of the program's host spans."""
    if run.trace is None:
        return None
    from bench import devtrace, readings
    progs, chips = programs(run), max(1, len(run.trace.planes))
    by: dict = {}
    outside: dict = {}
    for e in run.trace.op_events():
        found = paths_of(e, progs)
        key = stage_key(found)
        kernel = readings.kernel_of(e)
        if kernel:
            key = f"{key} | {kernel}"
        by[key] = by.get(key, 0.0) + e.dur * 1e-9 / chips
        if key in ("(no program path)", "(no scope)"):
            what = f"{key} {min(found) if found else devtrace.op_label(e)[:120]}"
            outside[what] = outside.get(what, 0.0) + e.dur * 1e-9 / chips
    ranked = _top(by, len(by))
    idle = {name: idle_inside_seconds(run.trace, {name}) for name in HOST_SPANS}
    spans = sum(1 for e in run.trace.host if _PROGRAM_SPAN.match(e.name))
    return {"scopes": ranked[:top], "rest_s": sum(v for _, v in ranked[top:]),
            "ops_s": sum(by.values()), "busy_s": run.trace.busy_s,
            "outside": _top(outside, 8),
            "idle_in_spans": {k: v for k, v in idle.items() if v is not None},
            "program_spans": spans}
