"""Program scopes of device ops and device idle inside program spans: the
readers of `hash_grid_*_ms`, `optimizer_ms.train` and `serve_idle_ms.render`,
on small hand-built traces like `test_bench_devtrace.small_trace`, and the
program paths read back from a compile cache written here on the CPU."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import devtrace, harness, scopes
from bench.devtrace import Ev

ROOT = Path(__file__).resolve().parents[2]
DEV = "/device:TPU:0"
HOST = "/host:CPU"
STEP = "jit(member_steps)/while/body/closed_call"


def ev(plane, line, name, start, dur, **stats):
    return Ev(plane, line, name, float(start), float(dur), stats)


def op(name, start, dur, path=None):
    return ev(DEV, "XLA Ops", name, start, dur, **({"tf_op": path} if path else {}))


def scoped_trace():
    """A 1000 ns window, two units: the backward's ops (one under a
    `transpose(jvp(...))` wrapper), a `vmap(...)`-wrapped forward gather, an
    optimizer op, an op under no scope and one the trace names no path for;
    host spans for the render's phases, `prepare` nested in `dispatch`."""
    return [
        ev(HOST, "python", "bench/window", 0, 1000),
        ev(HOST, "python", "serve3d/render_dispatch", 50, 300),
        ev(HOST, "python", "serve3d/render_prepare", 100, 100),
        ev(HOST, "python", "serve3d/render_readback", 700, 200),
        ev(HOST, "python", "serve3d/render_group", 0, 1000),
        op("fusion.1", 0, 150, f"{STEP}/transpose(jvp(pipeline/shade))/hash_grid/bwd/"
                               "jit(merged_scatter_add)/grid_update/merge/scatter-add:"),
        op("fusion.2", 300, 100, f"{STEP}/transpose(jvp(pipeline/shade))/hash_grid/bwd/"
                                 "stream/mul:"),
        op("gather.3", 400, 50, f"{STEP}/jvp(pipeline/shade)/vmap(hash_grid/fwd)/gather:"),
        op("fusion.4", 450, 20, f"{STEP}/optimizer/adam/mul:"),
        op("fusion.5", 600, 100, f"{STEP}/jvp(pipeline/composite)/cumsum:"),
        op("copy.6", 750, 50),
    ]


def run_of(events, units=2):
    trace = devtrace.reduce(events, ["0"])
    return SimpleNamespace(trace=trace, window={"units": units},
                           cell=SimpleNamespace(workload={"chips": 1}))


@pytest.fixture
def no_cache(monkeypatch):
    """Readers find no compile cache: only the trace's own stats name paths."""
    monkeypatch.setattr(scopes, "programs", lambda run: None)


def test_scope_of_reads_the_path_the_tpu_trace_carries():
    e = scoped_trace()[5]
    assert scopes.scope_of(e).endswith("grid_update/merge/scatter-add:")
    assert scopes.scope_of(scoped_trace()[-1]) is None


@pytest.mark.parametrize("scope,ms", [
    ("hash_grid/bwd", 250e-6 / 2),          # merge + stream, under transpose(jvp(...))
    ("hash_grid/fwd", 50e-6 / 2),           # vmap(...)-wrapped
    ("optimizer/adam", 20e-6 / 2),
    ("grid_update/merge", 150e-6 / 2),
    ("hash_grid/bwd/stream", 100e-6 / 2),
])
def test_ms_per_unit_under_a_scope(no_cache, scope, ms):
    assert scopes.scope_ms(run_of(scoped_trace()), scope) == pytest.approx(ms)


def test_a_scope_matches_whole_path_components():
    assert scopes.in_scope("a/transpose(jvp(hash_grid/bwd))/mul", "hash_grid/bwd")
    assert not scopes.in_scope("a/hash_grid/bwdx/mul", "hash_grid/bwd")
    assert not scopes.in_scope("a/xhash_grid/bwd/mul", "hash_grid/bwd")
    assert not scopes.in_scope("a/hash_grid/bwd/stream/mul", "hash_grid/fwd")


def test_absent_scope_or_no_trace_reads_none(no_cache):
    assert scopes.scope_ms(run_of(scoped_trace()), "pipeline/redistribute") is None
    assert scopes.scope_ms(SimpleNamespace(trace=None, window={"units": 2}),
                           "hash_grid/bwd") is None
    # the parent's program names no scope: every reader reads nothing
    bare = [e._replace(stats={}) for e in scoped_trace()]
    for name in ("hash_grid_bwd_ms.train", "hash_grid_fwd_ms.train", "optimizer_ms.train",
                 "hash_grid_fwd_ms.render"):
        assert harness.reader(name)(run_of(bare)) is None


def test_idle_inside_spans_counts_nested_spans_once(no_cache):
    # spans: dispatch [50,350] holding prepare [100,200], readback [700,900];
    # busy [0,150] [300,470] [600,700] [750,800]; idle inside them:
    # [150,300] + [700,750] + [800,900]
    r = run_of(scoped_trace())
    assert scopes.idle_inside_seconds(r.trace, {"serve3d/render_dispatch",
                                                "serve3d/render_prepare",
                                                "serve3d/render_readback"}) \
        == pytest.approx(300e-9)
    assert harness.reader("serve_idle_ms.render")(r) == pytest.approx(300e-6 / 2)


def test_idle_inside_absent_spans_reads_none(no_cache):
    events = [e for e in scoped_trace() if not e.name.startswith("serve3d/render_")
              or e.name == "serve3d/render_group"]
    assert harness.reader("serve_idle_ms.render")(run_of(events)) is None


def test_breakdown_by_outer_and_inner_stage(no_cache):
    bd = scopes.breakdown(run_of(scoped_trace()))
    by = dict(bd["scopes"])
    assert by["pipeline/shade > grid_update/merge"] == pytest.approx(150e-9)
    assert by["pipeline/shade > hash_grid/bwd/stream"] == pytest.approx(100e-9)
    assert by["pipeline/shade > hash_grid/fwd"] == pytest.approx(50e-9)
    assert by["optimizer/adam"] == pytest.approx(20e-9)
    assert by["pipeline/composite"] == pytest.approx(100e-9)
    assert by["(no program path)"] == pytest.approx(50e-9)
    assert bd["ops_s"] == pytest.approx(470e-9)
    assert dict(bd["outside"]) == {"(no program path) copy.6": pytest.approx(50e-9)}
    assert bd["idle_in_spans"] == {"serve3d/render_prepare": pytest.approx(50e-9),
                                   "serve3d/render_dispatch": pytest.approx(150e-9),
                                   "serve3d/render_readback": pytest.approx(150e-9)}
    assert bd["program_spans"] == 4


def test_an_instruction_text_shared_by_programs_counts_only_where_they_agree():
    progs = scopes.Programs()
    progs.add_hlo('  %fusion.7 = f32[8,2]{1,0} fusion(f32[8,2]{1,0} %p), kind=kLoop, '
                  'calls=%c, metadata={op_name="jit(a)/hash_grid/bwd/grid_update/merge/add"}\n'
                  '  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %q), '
                  'metadata={op_name="jit(a)/optimizer/adam/mul" source_file="x.py"}')
    progs.add_hlo('  %fusion.7 = f32[8,2]{1,0} fusion(f32[8,2]{1,0} %p), kind=kLoop, '
                  'calls=%c, metadata={op_name="jit(b)/hash_grid/bwd/grid_update/commit/add"}')
    e = op("%fusion.7 = f32[8,2]{1,0} fusion(f32[8,2]{1,0} %p), kind=kLoop, calls=%c", 0, 10)
    assert scopes.under(e, "hash_grid/bwd", progs)
    assert not scopes.under(e, "grid_update/merge", progs)
    assert scopes.scope_of(e, progs) is None and scopes.stage_key(progs.paths(e.name)) \
        == "(ambiguous)"
    # an event text printed otherwise still finds the instruction by its head
    e2 = op("%copy.1 = f32[8]{0} copy(%q)", 0, 10)
    assert scopes.scope_of(e2, progs) == "jit(a)/optimizer/adam/mul"


def test_program_paths_come_back_from_a_compile_cache(tmp_path):
    """A program compiled into a fresh cache is read back with the scopes
    its `stage`s wrote into the op metadata."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.obs import trace
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

        @jax.jit
        def step(table, idx):
            with trace.stage("hash_grid/fwd"):
                f = table[idx]
            with trace.stage("optimizer/adam"):
                return table - 0.1 * jnp.sin(f).sum()

        step(jnp.ones((64, 2)), jnp.arange(8)).block_until_ready()
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True, timeout=300)
    progs = scopes.from_cache(str(tmp_path), 1)
    assert progs is not None and progs.modules >= 1
    paths = {p for found in progs.by_text.values() for p in found}
    assert any(scopes.in_scope(p, "hash_grid/fwd") and "gather" in p for p in paths)
    assert any(scopes.in_scope(p, "optimizer/adam") for p in paths)
    # each instruction is found again by the text a trace names it with
    text = next(t for t, found in progs.by_text.items()
                if any(scopes.in_scope(p, "optimizer/adam") for p in found))
    assert scopes.under(op(text, 0, 1), "optimizer/adam", progs)
    assert scopes.from_cache(str(tmp_path / "absent"), 1) is None
