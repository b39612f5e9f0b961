"""repro.obs: span semantics, Chrome-trace schema, metrics, disabled no-ops."""
import importlib.util
import json
import threading
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.obs import export, metrics, trace

REPO = Path(__file__).resolve().parent.parent


def _load_check_trace():
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "tools" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def obs_on():
    """Enable obs with clean buffers; restore the prior state afterwards."""
    was = trace.enabled()
    trace.set_enabled(True)
    obs.reset()
    yield
    obs.reset()
    trace.set_enabled(was)


# ---- spans ----


def test_span_nesting_depth_and_timing(obs_on):
    with trace.span("outer", cat="t"):
        with trace.span("inner", cat="t"):
            pass
    evs = trace.events()
    assert [e.name for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert (inner.depth, outer.depth) == (1, 0)
    # inner is contained in outer on the shared timeline
    assert outer.ts_us <= inner.ts_us
    assert inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us + 1e-3


def test_span_depth_restored_on_exception(obs_on):
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    with trace.span("after"):
        pass
    assert trace.events()[-1].depth == 0


def test_span_thread_attribution(obs_on):
    def worker():
        with trace.span("in-thread"):
            pass

    t = threading.Thread(target=worker, name="obs-worker")
    t.start()
    t.join()
    with trace.span("in-main"):
        pass
    by_name = {e.name: e for e in trace.events()}
    assert by_name["in-thread"].thread_name == "obs-worker"
    assert by_name["in-thread"].tid != by_name["in-main"].tid


def test_traced_decorator_and_instant(obs_on):
    @trace.traced("deco/fn", cat="t")
    def fn(x):
        return x + 1

    assert fn(1) == 2
    trace.instant("marker", args={"k": 1})
    names = [e.name for e in trace.events()]
    assert names == ["deco/fn", "marker"]
    assert trace.events()[1].dur_us is None


def test_ring_buffer_bounded(obs_on):
    trace.configure(buffer_size=8)
    for i in range(20):
        with trace.span(f"s{i}"):
            pass
    evs = trace.events()
    assert len(evs) == 8 and evs[0].name == "s12"
    trace.configure(buffer_size=262144)


# ---- disabled-mode guarantees ----


def test_disabled_span_is_shared_noop_singleton():
    was = trace.enabled()
    trace.set_enabled(False)
    try:
        n0 = len(trace.events())
        s1 = trace.span("a")
        s2 = trace.span("b", cat="x", args={"big": 1})
        assert s1 is trace.NULL and s2 is trace.NULL  # no allocation
        with s1:
            pass
        trace.instant("nope")
        trace.record("nope", 0.0, 1.0)
        assert len(trace.events()) == n0  # nothing recorded
    finally:
        trace.set_enabled(was)


def test_env_knob_parsing():
    for off in ("", "0", "off", "false", "no", "NO", " Off "):
        assert trace._env_enabled(off) is False
    for on in ("1", "on", "true", "jax", "yes"):
        assert trace._env_enabled(on) is True


# ---- metrics ----


def test_histogram_quantiles_match_numpy(rng):
    h = metrics.Histogram(window=512)
    vals = rng.standard_normal(257).tolist()
    for v in vals:
        h.observe(v)
    for q in (0.0, 0.25, 0.5, 0.95, 0.99, 1.0):
        np.testing.assert_allclose(h.quantile(q), np.quantile(vals, q),
                                   rtol=1e-12, atol=1e-12)


def test_histogram_window_bounded_lifetime_counts():
    h = metrics.Histogram(window=4)
    for v in range(10):
        h.observe(v)
    assert h.count == 10 and h.total == sum(range(10))
    assert h.values() == [6.0, 7.0, 8.0, 9.0]  # window keeps the recent tail


def test_registry_typed_and_deterministic():
    reg = metrics.Registry()
    reg.counter("a.count").inc(3)
    reg.gauge("a.gauge").set(1.5)
    reg.histogram("a.hist").observe(2.0)
    with pytest.raises(TypeError):
        reg.gauge("a.count")  # name keeps its kind
    s1, s2 = reg.snapshot(), reg.snapshot()
    assert s1 == s2
    assert json.dumps(s1, sort_keys=True) == json.dumps(s2, sort_keys=True)
    assert list(s1) == sorted(s1)
    assert s1["a.count"] == {"type": "counter", "value": 3}
    reg.reset()
    assert reg.snapshot() == {}


# ---- chrome-trace export ----


def test_chrome_trace_schema_roundtrip(obs_on, tmp_path):
    ct = _load_check_trace()
    with trace.span("outer", cat="t", args={"k": "v"}):
        with trace.span("inner", cat="t"):
            pass
    trace.instant("mark")
    path = tmp_path / "trace.json"
    export.dump_trace(str(path))
    doc = json.loads(path.read_text())
    assert ct.check(doc, require=["outer", "inner", "mark"]) == []
    # spot-check the event grammar the validator enforces
    X = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in X} == {"outer", "inner"}
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in X)
    args = {e["name"]: e["args"] for e in X}
    assert args["outer"]["k"] == "v" and args["inner"]["depth"] == 1

    # and the validator actually rejects malformed documents
    assert ct.check({"traceEvents": [{"name": "x"}]}) != []
    assert ct.check(doc, require=["absent/span"]) != []


def test_metrics_export_and_formatting(obs_on, tmp_path):
    metrics.counter("x.count").inc(2)
    metrics.histogram("x.lat").observe(5.0)
    path = tmp_path / "metrics.json"
    export.dump_metrics(str(path), extra={"run": "test"})
    doc = json.loads(path.read_text())
    assert doc["meta"] == {"run": "test"}
    assert doc["metrics"]["x.count"]["value"] == 2
    text = export.format_metrics(doc)
    assert "x.count" in text and "x.lat" in text and "count=1" in text
    assert export.format_metrics(doc, prefix="x.lat").count("\n") == 0


# ---- instrumented surfaces ----


def test_fused_step_spans_at_trace_time(obs_on, rng):
    import jax
    import jax.numpy as jnp
    from repro.core import Field, FieldConfig

    field = Field(FieldConfig(n_levels=2, max_resolution=16,
                              log2_table_density=8, log2_table_color=6,
                              hidden=16))
    params = field.init(jax.random.PRNGKey(0))
    pts = jnp.asarray(rng.random((32, 3)), jnp.float32)
    dirs = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)

    def loss(p):
        sigma, rgb = field.query_step(p, pts, dirs)
        return jnp.mean(sigma) + jnp.mean(rgb)

    jax.grad(loss)(params)
    names = [e.name for e in trace.events()]
    assert "kernels/fused_step/fwd" in names
    assert "kernels/fused_step/bwd" in names


def test_dedup_stats_folds_into_registry(obs_on, rng):
    from repro.kernels.fused_path import ref as fp_ref

    pts = rng.random((64, 3)).astype(np.float32)
    stats = fp_ref.dedup_stats(pts, (4, 8), (True, True), 512, block_points=32)
    g = metrics.REGISTRY.get("fused_path.dedup.unique_ratio_block")
    assert g is not None and g.value == pytest.approx(stats["unique_ratio_block"])


def test_serve3d_service_metrics_and_trace(obs_on, tmp_path):
    from repro.core import FieldConfig, TrainerConfig, occupancy
    from repro.core.rendering import RenderConfig
    from repro.data import build_dataset
    from repro.serve3d import ReconstructionService

    rcfg = RenderConfig(n_samples=8)
    fcfg = FieldConfig(n_levels=2, max_resolution=32, log2_table_density=10,
                       log2_table_color=8, hidden=16)
    ocfg = occupancy.OccupancyConfig(resolution=16, update_interval=4,
                                     warmup_steps=2)
    tcfg = TrainerConfig(n_rays=64, render=rcfg, occ=ocfg, eval_chunk=144)

    svc = ReconstructionService(slice_iters=8, max_cohort=None)
    for seed in range(2):
        _scene, ds = build_dataset(seed=seed, n_views=2, h=12, w=12,
                                   cfg=rcfg, gt_samples=24)
        sid = svc.submit_scene(ds, fcfg, tcfg, target_iters=8, seed=seed)
        svc.request_render(sid, ds.poses[0])
    svc.run(max_quanta=20)

    doc = svc.metrics()
    snap = doc["metrics"]
    lat = snap["serve3d.render.latency_ms"]
    assert lat["count"] == 2
    assert all(lat[q] is not None for q in ("p50", "p95", "p99"))
    assert snap["serve3d.snapshots_published"]["value"] >= 2
    assert snap["serve3d.render.ttfuv_s.scene-000"]["value"] > 0
    render = doc["meta"]["service"]["telemetry"]["render"]
    assert render["count"] == 2 and render["p99_ms"] >= render["p50_ms"]
    assert set(render["ttfuv_s"]) == {"scene-000", "scene-001"}
    assert doc["meta"]["service"]["snapshots"]["scene-000"] >= 1

    ct = _load_check_trace()
    path = svc.dump_trace(str(tmp_path / "serve.json"))
    trace_doc = json.loads(Path(path).read_text())
    assert ct.check(trace_doc, require=[
        "serve3d/quantum", "serve3d/slice", "serve3d/snapshot_publish",
        "serve3d/render_drain", "serve3d/render_group",
        "trainer/step_compile", "trainer/occ_update",
        "pipeline/sample", "pipeline/shade", "pipeline/composite",
    ]) == []


# ---- device scopes: stages named in the compiled program's op metadata ----


def test_stage_records_a_host_span_only_when_on():
    was = trace.enabled()
    try:
        obs.reset()
        trace.set_enabled(False)
        with trace.stage("hash_grid/bwd", cat="kernels"):
            pass
        assert trace.events() == []
        trace.set_enabled(True)
        with trace.stage("hash_grid/bwd", cat="kernels"):
            with trace.stage("hash_grid/bwd/stream", cat="kernels"):
                pass
        assert [(e.name, e.cat, e.depth) for e in trace.events()] == [
            ("hash_grid/bwd/stream", "kernels", 1), ("hash_grid/bwd", "kernels", 0)]
    finally:
        obs.reset()
        trace.set_enabled(was)


# tiny dense training cell: N = 16 rays x 6 samples = 96 points, L = 2, so
# the corner stream has 96 x 8 x 2 = 1536 rows (tables hold 512 and 128)
_N_RAYS, _N_SAMPLES, _LEVELS = 16, 6, 2
_STREAM_ROWS = _N_RAYS * _N_SAMPLES * 8 * _LEVELS
_TRAIN_SCOPES = ("pipeline/sample", "pipeline/cull", "pipeline/shade", "pipeline/composite",
                 "hash_grid/fwd", "hash_grid/bwd", "hash_grid/bwd/stream", "grid_update/sort",
                 "grid_update/merge", "grid_update/commit", "optimizer/adam")
_RENDER_SCOPES = ("pipeline/sample", "pipeline/cull", "pipeline/redistribute",
                  "pipeline/compact", "pipeline/shade", "pipeline/composite", "hash_grid/fwd")


def _field_cfg():
    from repro.core import FieldConfig
    return FieldConfig(n_levels=_LEVELS, max_resolution=16, log2_table_density=8,
                       log2_table_color=6, hidden=16)


def _train_step_hlo(freeze_color: bool) -> str:
    """The tiny dense step's optimized HLO, compiled afresh."""
    import jax
    import jax.numpy as jnp
    from repro.core import Field, Instant3DTrainer, TrainerConfig
    from repro.core import trainer as trainer_lib
    from repro.core.rendering import RayBatch, RenderConfig

    fcfg = _field_cfg()
    cfg = TrainerConfig(n_rays=_N_RAYS, render=RenderConfig(n_samples=_N_SAMPLES),
                        use_occupancy=False)
    state = jax.eval_shape(Instant3DTrainer(Field(fcfg), cfg).init, jax.random.PRNGKey(0))
    shape = jax.ShapeDtypeStruct
    member = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: shape((1,) + a.shape, a.dtype), tree)
    args = (member(state.params), member(state.opt_state),
            RayBatch(*[shape((1, _N_RAYS, 3), jnp.float32)] * 3),
            shape((_N_RAYS, _N_SAMPLES), jnp.float32), member(state.occ_state.density_ema))
    trainer_lib._COHORT_STEP_CACHE.clear()
    step = trainer_lib.cohort_step_fn(fcfg, cfg, freeze_color, False, None, False, 1)
    return step.lower(*args).compile().as_text()


def _op_names(hlo: str) -> list:
    import re
    return re.findall(r'op_name="([^"]*)"', hlo)


def _in_scope(path: str, scope: str) -> bool:
    import re
    return re.search(rf"(?:^|[/(;]){re.escape(scope)}(?:$|[/):;])", path) is not None


@pytest.mark.parametrize("freeze_color", [False, True], ids=["both_grids", "color_frozen"])
def test_train_step_names_every_stage_in_op_metadata(freeze_color):
    names = _op_names(_train_step_hlo(freeze_color))
    missing = [s for s in _TRAIN_SCOPES if not any(_in_scope(n, s) for n in names)]
    assert missing == []
    # the grid-update stages run inside the hash grid's backward (paths from
    # the step's root; a scatter's reducer region names only its own op)
    assert all(_in_scope(n, "hash_grid/bwd") for n in names
               if n.startswith("jit(") and _in_scope(n, "grid_update/merge"))


def test_served_render_chunk_names_its_stages_in_op_metadata():
    import jax
    import jax.numpy as jnp
    from repro.core import Field, occupancy
    from repro.core import trainer as trainer_lib
    from repro.core.rendering import RenderConfig

    fcfg, rcfg = _field_cfg(), RenderConfig(n_samples=8)
    occ = occupancy.OccupancyConfig(resolution=16)
    g, chunk, spr = 2, 32, 2
    params = jax.eval_shape(Field(fcfg).init, jax.random.PRNGKey(0))
    shape = jax.ShapeDtypeStruct
    args = (jax.tree.map(lambda a: shape((g,) + a.shape, a.dtype), params),
            shape((g, chunk, 3), jnp.float32), shape((g, chunk, 3), jnp.float32),
            shape((chunk, 8), jnp.float32), shape((g, occ.resolution ** 3), jnp.float32),
            shape((g,), jnp.int32))
    fn = trainer_lib.batched_redistributed_render_fn(fcfg, rcfg, occ, chunk, g, spr)
    names = _op_names(fn.lower(*args).compile().as_text())
    assert [s for s in _RENDER_SCOPES if not any(_in_scope(n, s) for n in names)] == []
    assert not any(_in_scope(n, "hash_grid/bwd") for n in names)


def test_corner_stream_ops_lie_under_the_hash_grid_backward():
    import re
    hlo = _train_step_hlo(False)
    stream = [line for line in hlo.splitlines()
              if re.search(rf"= \(?[a-z0-9]+\[{_STREAM_ROWS}[,\]]", line) and "op_name=" in line]
    assert stream
    outside = [line.strip()[:160] for line in stream
               if not _in_scope(re.search(r'op_name="([^"]*)"', line).group(1),
                                "hash_grid/bwd")]
    assert outside == []


def test_stages_change_nothing_but_op_metadata(monkeypatch):
    """With `stage` a no-op the step compiles to the same optimized HLO, op
    metadata and the source-location tables aside: a stage costs nothing
    on the device and nothing in a cached execution."""
    import contextlib
    import re

    def bare(hlo):
        return re.sub(r", metadata=\{[^}]*\}", "", hlo.split("\nFileNames")[0])

    texts = []
    for off in (False, True):
        if off:
            monkeypatch.setattr(trace, "stage", lambda *a, **k: contextlib.nullcontext())
        texts.append(_train_step_hlo(False))
    assert any("hash_grid/bwd" in n for n in _op_names(texts[0]))
    assert not any("hash_grid/bwd" in n for n in _op_names(texts[1]))
    assert bare(texts[0]) == bare(texts[1])
