"""RenderService: batched novel-view rendering across concurrent sessions.

Requests target a *session* (scene), not a parameter blob: the service
resolves each request against the session's latest published snapshot at
drain time, so renders always see a consistent, fully-trained-up-to-step-N
view while training continues on the live buffers.

Coalescing: pending requests are grouped by (field config, render config,
image geometry); each group stacks the per-session snapshot params into one
leading batch axis and renders through a jitted ``vmap`` of the *same*
fixed-chunk dense-pipeline renderer that ``Instant3DTrainer.render_image``
uses (both are built by ``repro.core.trainer.make_render_chunk``; this
module's cache adds the padded group size to the per-(field config, render
config, chunk) key).  Group sizes are bucketed to powers of two (padding
repeats the last request) so the number of distinct compiled batch shapes
stays O(log N) per geometry.

A request whose session has not published a snapshot yet stays queued — the
train -> snapshot -> serve pipeline never renders from uninitialized or
half-written params.

Redistributed serving (``samples_per_ray``): sessions registered with a
per-ray sample budget are rendered through the RenderPipeline's
redistribute stage (2b) instead of dense — the snapshot's occupancy EMA
rebuilds the session's bitfield, the dense candidate liveness becomes each
ray's probe, and only S' = samples_per_ray redistributed samples per ray
are shaded.  At S' = S/4 the PR 4 render sweep shows equal PSNR, so p50
latency drops with the shaded point count; and because a redistributing
trainer marches the same quadrature, served views stop paying the
train/eval quadrature mismatch.  ``samples_per_ray=None`` keeps the dense
path (which remains the fallback for snapshots without occupancy).

Device routing (docs/SERVING.md): with a `DevicePlacement` attached, every
coalesced group is keyed by — and executed on — the device holding its
sessions' training state (`jax.default_device` around the batched call), so
serving load follows the session sharding instead of piling onto device 0.
Groups never straddle devices; snapshots are host-side, so routing changes
*where* pixels are computed, never their values.

Snapshot levels / progressive streaming: a render request carries a
``level`` — level 0 renders full resolution and waits for a full (level-0)
snapshot; level k > 0 renders at h>>k and is answerable by a *preview*
snapshot, which sessions publish early in life (see `SnapshotStore`).  The
level rides the group key (distinct compiled shapes) and the result.

Async serving plane (``start_async``): a dedicated daemon thread drives the
drain loop so render latency stops being gated by the in-flight training
slice — XLA releases the GIL while a slice executes, so the serving thread
coalesces, dispatches and collects groups concurrently with training.
Ordering contract: requests are still answered from atomically-published
snapshots (never live buffers), per-drain results stay request-id ordered,
and pixels are bit-identical to a synchronous drain against the same
snapshot version — only *when* a drain runs moves off the quantum loop.
Queue and result handoff are lock-protected; `poll_results` returns
everything the plane has finished since the last poll.

Degradation ladder (the fault-tolerance surface; see docs/ROBUSTNESS.md):

* **deadlines** — a request may carry ``deadline_s`` (or inherit
  ``default_deadline_s``); a request still queued past its deadline is
  answered with a typed `RenderError("deadline_expired")` at the next
  drain, never silently dropped and never left to hang.
* **overload shedding** — when the queue exceeds ``shed_threshold``, the
  drain halves every redistributed session's per-ray sample budget (floor
  2) for that drain: quality degrades *before* any request is dropped.
* **group-failure retry** — an exception inside a batched render (device
  fault, injected ``render_fail``) re-queues the group's requests for the
  next drain; after ``max_attempts`` a request gets a typed
  `RenderError("render_failed")`.
* **staleness** — results for sessions the guard rolled back or
  quarantined carry ``stale=True``: the pixels are real, from the last
  *good* published snapshot, but training is behind where a healthy
  session would be.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field as dc_field
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rendering
# the batched render caches live with the trainer's eval renderers (one
# compiled entry serves both `Instant3DTrainer.evaluate` and this service —
# the bit-for-bit eval==served contract); re-exported here for existing
# importers of serve3d.render
from ..core.trainer import (  # noqa: F401
    batched_redistributed_render_fn, batched_render_fn, image_rays,
)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..testing import faults
from .snapshot import SnapshotStore


def _pow2_bucket(n: int) -> int:
    return 1 << (n - 1).bit_length()


@dataclass
class _SessionGeom:
    field_cfg: Any
    render_cfg: rendering.RenderConfig
    h: int
    w: int
    focal: float
    eval_chunk: int
    occ_cfg: Any = None            # OccupancyConfig for bitfield reconstruction
    samples_per_ray: int | None = None  # None => dense serving
    redistribute_v3: bool = False  # density-weighted ragged serving (stage 2b v3)


@dataclass
class RenderRequest:
    request_id: int
    session_id: str
    pose: np.ndarray
    submitted_at: float = dc_field(default_factory=obs_trace.clock)
    deadline_s: float | None = None   # None = no per-request deadline
    attempts: int = 0                 # failed batched-render attempts so far
    level: int = 0                    # 0 = full res; k > 0 = preview at h>>k


class RenderResult(NamedTuple):
    request_id: int
    session_id: str
    rgb: np.ndarray       # (H, W, 3)
    depth: np.ndarray     # (H, W)
    snapshot_version: int
    snapshot_step: int
    latency_s: float
    # the snapshot is the last *good* one but training has fallen behind
    # (guard rollback/quarantine) — pixels are valid, freshness is not
    stale: bool = False
    level: int = 0        # resolution level the pixels were rendered at
    device: Any = None    # the device the batched render's output came from


class RenderError(NamedTuple):
    """Typed failure answer: a request that cannot be served errors out
    deterministically instead of hanging in the queue."""
    request_id: int
    session_id: str
    error: str            # "deadline_expired" | "render_failed"
    latency_s: float


class RenderService:
    def __init__(self, store: SnapshotStore, latency_window: int = 4096,
                 default_deadline_s: float | None = None,
                 shed_threshold: int | None = None,
                 max_attempts: int = 2,
                 placement=None):
        """default_deadline_s: deadline inherited by requests submitted
        without one (None = requests never expire, the prior behavior).
        shed_threshold: queue depth above which a drain halves every
        redistributed session's sample budget (None = never shed).
        max_attempts: batched-render tries per request before it errors.
        placement: a `DevicePlacement` — render groups then execute on the
        device holding their sessions' training state (resolved at drain
        time, so a device move re-routes automatically)."""
        self.store = store
        self.default_deadline_s = default_deadline_s
        self.shed_threshold = shed_threshold
        self.max_attempts = int(max_attempts)
        self.placement = placement
        self._geom: dict[str, _SessionGeom] = {}
        self._queue: list[RenderRequest] = []
        self._next_id = 0
        self._stale: set[str] = set()   # sessions the guard marked degraded
        # async serving plane: queue/results handoff is lock-protected; the
        # drain itself also serializes (one drain at a time, async or sync)
        self._lock = threading.RLock()
        self._drain_mutex = threading.Lock()   # one drain at a time
        self._async_thread: threading.Thread | None = None
        self._async_stop = threading.Event()
        self._async_wake = threading.Event()
        self._async_results: list = []
        self._draining = False
        # degradation telemetry (always live, like the latency histograms)
        self.expired = 0
        self.failed = 0
        self.last_failure: str | None = None
        self.shed_drains = 0
        self.drains = 0
        # per-session serving telemetry, backed by obs Histograms (bounded
        # window -> a long-lived service doesn't grow per-request forever;
        # percentiles come from the recent window, counts are lifetime).
        # These objects are always live — `latency_stats()` keeps working
        # with REPRO_OBS off; the knob only gates the *global-registry*
        # mirror recorded at drain time.  (The compile caches are keyed by
        # config/chunk/pow2-group, not by session, so their size is bounded
        # by config diversity.)
        self.latency_window = int(latency_window)
        self.latencies: dict[str, obs_metrics.Histogram] = {}
        self.served: dict[str, int] = {}
        # TTFUV: register -> first served view, per session.  (bench_serve3d
        # additionally defines a PSNR-thresholded, GT-based TTFUV; this is
        # the service-side analogue with "usable" = "first snapshot-backed
        # render delivered".)
        self._registered_at: dict[str, float] = {}
        self.ttfuv_s: dict[str, float] = {}

    # ---- registration / submission ----

    def register_session(self, session_id: str, field_cfg, render_cfg,
                         h: int, w: int, focal: float, eval_chunk: int = 4096,
                         occ_cfg=None, samples_per_ray: int | None = None,
                         redistribute_v3: bool = False):
        """samples_per_ray: serve this session through the redistributed
        render path at that per-ray point budget (requires occ_cfg so the
        snapshot's EMA can be thresholded into a bitfield); None serves
        dense.  redistribute_v3: spend that budget density-weighted and
        unevenly across each chunk's rays (stage 2b v3) instead of the
        fixed per-ray split."""
        if samples_per_ray is not None and occ_cfg is None:
            raise ValueError("samples_per_ray needs occ_cfg for the bitfield")
        self._geom[session_id] = _SessionGeom(
            field_cfg, render_cfg, int(h), int(w), float(focal), int(eval_chunk),
            occ_cfg=occ_cfg,
            samples_per_ray=None if samples_per_ray is None else int(samples_per_ray),
            redistribute_v3=bool(redistribute_v3),
        )
        self._registered_at.setdefault(session_id, obs_trace.clock())

    def submit(self, session_id: str, pose: np.ndarray,
               deadline_s: float | None = None, level: int = 0) -> int:
        """level 0 renders full resolution from a full snapshot; level k > 0
        renders the cheap h>>k preview and is answerable by a preview
        snapshot (progressive streaming)."""
        if session_id not in self._geom:
            raise KeyError(f"unknown session {session_id!r}")
        with self._lock:
            req = RenderRequest(self._next_id, session_id, np.asarray(pose),
                                deadline_s=(deadline_s if deadline_s is not None
                                            else self.default_deadline_s),
                                level=int(level))
            self._next_id += 1
            self._queue.append(req)
        self._async_wake.set()
        return req.request_id

    def mark_stale(self, session_id: str, stale: bool = True) -> None:
        """Guard hook: results for this session carry ``stale=True`` until a
        healthy publish clears it."""
        if stale:
            self._stale.add(session_id)
        else:
            self._stale.discard(session_id)

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    # ---- async serving plane ----

    @property
    def async_active(self) -> bool:
        return self._async_thread is not None and self._async_thread.is_alive()

    @property
    def idle(self) -> bool:
        """No drain in flight and no undelivered async results."""
        with self._lock:
            return not self._draining and not self._async_results

    def start_async(self, poll_s: float = 0.002) -> None:
        """Spawn the serving thread: it drains whenever work is pending
        (woken by `submit`/`notify`), accumulating results for
        `poll_results`.  Idempotent."""
        if self.async_active:
            return
        self._async_stop.clear()

        def _serve():
            while not self._async_stop.is_set():
                self._async_wake.wait(timeout=0.1)
                self._async_wake.clear()
                while self.pending and not self._async_stop.is_set():
                    results = self.drain()
                    if results:
                        with self._lock:
                            self._async_results.extend(results)
                    if self.pending:
                        # remaining requests await a publish; yield until
                        # the next notify instead of spinning
                        if not self._async_wake.wait(timeout=poll_s):
                            break
                        self._async_wake.clear()

        self._async_thread = threading.Thread(
            target=_serve, name="serve3d-render", daemon=True)
        self._async_thread.start()

    def notify(self) -> None:
        """Wake the serving thread (a snapshot landed, work may be ready)."""
        self._async_wake.set()

    def stop_async(self, wait: bool = True) -> None:
        if self._async_thread is None:
            return
        self._async_stop.set()
        self._async_wake.set()
        if wait:
            # generous: a first-contact drain may be tracing several
            # per-device renderers and must be allowed to finish cleanly
            self._async_thread.join(timeout=120.0)
        self._async_thread = None

    def poll_results(self) -> list:
        """Everything the async plane finished since the last poll."""
        with self._lock:
            out, self._async_results = self._async_results, []
        return out

    # ---- serving ----

    def drain(self) -> list:
        """Serve every pending request whose session has a published
        snapshot; requests without one stay queued for the next drain.
        Returns `RenderResult`s plus typed `RenderError`s for requests past
        their deadline or past ``max_attempts`` failed renders.  Thread-safe
        and serialized: the sync quantum loop and the async plane never
        drain concurrently."""
        with self._drain_mutex:
            with self._lock:
                self._draining = True
            try:
                with obs_trace.span("serve3d/render_drain", cat="serve3d",
                                    args={"pending": self.pending}):
                    results = self._drain()
            finally:
                with self._lock:
                    self._draining = False
        if obs_trace.enabled():
            obs_metrics.gauge("serve3d.render.queue_depth").set(self.pending)
        return results

    def _drain(self) -> list:
        self.drains += 1
        now = obs_trace.clock()
        results: list = []
        obs_on = obs_trace.enabled()
        with self._lock:
            queue, self._queue = self._queue, []

        # expiry first: a request past its deadline gets a typed error even
        # if its session never publishes — expiry is how waiting requests
        # are guaranteed to terminate
        keep: list[RenderRequest] = []
        for req in queue:
            if req.deadline_s is not None and \
                    now - req.submitted_at > req.deadline_s:
                self.expired += 1
                if obs_on:
                    obs_metrics.counter("serve3d.render.expired").inc()
                results.append(RenderError(req.request_id, req.session_id,
                                           "deadline_expired",
                                           now - req.submitted_at))
            else:
                keep.append(req)

        # full-res requests wait for a full (level-0) snapshot; preview
        # requests take the best snapshot available (preview or full)
        ready: list[tuple[RenderRequest, Any]] = []
        waiting: list[RenderRequest] = []
        for req in keep:
            snap = (self.store.latest(req.session_id, level=0) if req.level == 0
                    else self.store.latest(req.session_id))
            if snap is None:
                waiting.append(req)
            else:
                ready.append((req, snap))
        with self._lock:
            self._queue.extend(waiting)

        # overload shedding: past the threshold, degrade quality (halve the
        # redistributed sample budget this drain) before dropping anything
        shed = self.shed_threshold is not None and len(ready) > self.shed_threshold
        if shed:
            self.shed_drains += 1
            if obs_on:
                obs_metrics.counter("serve3d.render.shed_drains").inc()
                obs_trace.instant("serve3d/render_shed", cat="serve3d",
                                  args={"ready": len(ready)})

        # coalesce by compiled geometry + placement: same field/render
        # config + image dims + serving path (dense vs redistributed at a
        # given budget) + resolution level + the device the group runs on —
        # groups never straddle devices
        groups: dict[tuple, list[tuple[RenderRequest, Any]]] = {}
        for req, snap in ready:
            g = self._geom[req.session_id]
            spr = g.samples_per_ray
            if shed and spr is not None:
                spr = max(2, spr // 2)
            dev = (self.placement.device(req.session_id)
                   if self.placement is not None else None)
            key = (g.field_cfg, g.render_cfg, g.h, g.w, g.focal, g.eval_chunk,
                   g.occ_cfg, spr, g.redistribute_v3, req.level, dev)
            groups.setdefault(key, []).append((req, snap))

        for key, items in groups.items():
            try:
                results.extend(self._render_group(*key, items))
            except Exception as e:
                # batched render died (device fault / injected render_fail):
                # re-queue the group's requests for another attempt, then
                # answer the exhausted ones with a typed error; the cause
                # stays visible in latency_stats()["last_failure"]
                self.last_failure = f"{type(e).__name__}: {e}"
                requeue = []
                for req, _snap in items:
                    req.attempts += 1
                    if req.attempts < self.max_attempts:
                        requeue.append(req)
                        continue
                    self.failed += 1
                    if obs_on:
                        obs_metrics.counter("serve3d.render.failed").inc()
                    results.append(RenderError(
                        req.request_id, req.session_id, "render_failed",
                        obs_trace.clock() - req.submitted_at))
                with self._lock:
                    self._queue.extend(requeue)
        results.sort(key=lambda r: r.request_id)
        return results

    def _render_group(self, field_cfg, render_cfg, h, w, focal, eval_chunk,
                      occ_cfg, samples_per_ray, redistribute_v3, level,
                      device, items) -> list[RenderResult]:
        with obs_trace.span("serve3d/render_group", cat="serve3d",
                            args={"group": len(items),
                                  "redistribute": samples_per_ray is not None,
                                  "v3": bool(redistribute_v3),
                                  "level": int(level),
                                  "device": str(device) if device is not None
                                  else None}):
            return self._render_group_inner(
                field_cfg, render_cfg, h, w, focal, eval_chunk,
                occ_cfg, samples_per_ray, redistribute_v3, level, device, items)

    def _render_group_inner(self, field_cfg, render_cfg, h, w, focal,
                            eval_chunk, occ_cfg, samples_per_ray,
                            redistribute_v3, level, device,
                            items) -> list[RenderResult]:
        inj = faults.check("serve3d.render_group",
                           session=items[0][0].session_id)
        if inj is not None and inj.kind == "render_fail":
            raise faults.InjectedFault("injected batched-render failure")
        # preview levels render the same scene at h>>level — the cheap view
        # of the progressive-streaming ladder
        if level > 0:
            h = max(1, h >> level)
            w = max(1, w >> level)
        g_real = len(items)
        g_pad = _pow2_bucket(g_real)
        padded = items + [items[-1]] * (g_pad - g_real)

        # groups execute on the device that holds their sessions' training
        # state (render routing); None = process default, the N=1 path
        dev_ctx = (jax.default_device(device) if device is not None
                   else contextlib.nullcontext())
        with dev_ctx:
            # the view's host phases, each its own span so a device-idle gap
            # inside the render group is put down to one of them
            with obs_trace.span("serve3d/render_prepare", cat="serve3d"):
                origins, dirs = [], []
                n = chunk = None
                for req, _snap in padded:
                    o, d, n, chunk = image_rays(req.pose, h, w, focal, eval_chunk)
                    origins.append(o)
                    dirs.append(d)
                origins = jnp.stack(origins)   # (G, n_pad, 3)
                dirs = jnp.stack(dirs)
                params = jax.tree.map(
                    lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                    *[snap.params for _req, snap in padded],
                )
                ts = rendering.sample_ts(None, chunk, render_cfg)

                # redistributed path needs every snapshot to carry occupancy; a
                # params-only snapshot (external publisher) falls back to dense
                redistribute = (samples_per_ray is not None
                                and all(snap.occ is not None for _req, snap in padded))
                if redistribute:
                    occ_ema = jnp.stack(
                        [jnp.asarray(snap.occ[0]) for _req, snap in padded])
                    occ_step = jnp.asarray(
                        [int(snap.occ[1]) for _req, snap in padded], jnp.int32)
                    fn_r = batched_redistributed_render_fn(
                        field_cfg, render_cfg, occ_cfg, chunk, g_pad, samples_per_ray,
                        redistribute_v3=bool(redistribute_v3))
                    fn = lambda p, o, d, t: fn_r(p, o, d, t, occ_ema, occ_step)
                else:
                    fn = batched_render_fn(field_cfg, render_cfg, chunk, g_pad)

            with obs_trace.span("serve3d/render_dispatch", cat="serve3d"):
                rgb_chunks, dep_chunks = [], []
                for i in range(0, origins.shape[1], chunk):
                    rgb_c, dep_c = fn(params, origins[:, i:i + chunk],
                                      dirs[:, i:i + chunk], ts)
                    rgb_chunks.append(rgb_c)
                    dep_chunks.append(dep_c)
            (ran_on,) = rgb_chunks[0].devices()
            with obs_trace.span("serve3d/render_readback", cat="serve3d"):
                rgb = np.asarray(jnp.concatenate(rgb_chunks, axis=1))[:, :n]
                dep = np.asarray(jnp.concatenate(dep_chunks, axis=1))[:, :n]

        now = obs_trace.clock()
        obs_on = obs_trace.enabled()
        out = []
        for gi, (req, snap) in enumerate(items):
            lat = now - req.submitted_at
            sid = req.session_id
            hist = self.latencies.get(sid)
            if hist is None:
                hist = self.latencies[sid] = obs_metrics.Histogram(
                    window=self.latency_window)
            hist.observe(lat)
            first = sid not in self.ttfuv_s
            if first and sid in self._registered_at:
                self.ttfuv_s[sid] = now - self._registered_at[sid]
            self.served[sid] = self.served.get(sid, 0) + 1
            if obs_on:
                obs_metrics.counter("serve3d.render.served").inc()
                obs_metrics.histogram("serve3d.render.latency_ms").observe(lat * 1e3)
                if first and sid in self.ttfuv_s:
                    obs_metrics.gauge(f"serve3d.render.ttfuv_s.{sid}").set(
                        self.ttfuv_s[sid])
            out.append(RenderResult(
                request_id=req.request_id,
                session_id=req.session_id,
                rgb=rgb[gi].reshape(h, w, 3),
                depth=dep[gi].reshape(h, w),
                snapshot_version=snap.version,
                snapshot_step=snap.step,
                latency_s=lat,
                stale=sid in self._stale,
                level=int(level),
                device=ran_on,
            ))
        return out

    # ---- telemetry ----

    def latency_stats(self) -> dict:
        """Percentiles over the recent latency window; counts are lifetime.

        Quantiles use the obs Histogram definition (numpy linear
        interpolation) over the union of the per-session windows."""
        merged = obs_metrics.Histogram(
            window=self.latency_window * max(1, len(self.latencies)))
        for h in self.latencies.values():
            for v in h.values():
                merged.observe(v)
        degraded = {
            "expired": self.expired,
            "failed": self.failed,
            "last_failure": self.last_failure,
            "shed_fraction": self.shed_drains / self.drains if self.drains else 0.0,
            "stale_sessions": sorted(self._stale),
        }
        if merged.count == 0:
            return {"count": 0, "degraded": degraded}
        return {
            "count": sum(self.served.values()),
            "degraded": degraded,
            "p50_ms": merged.quantile(0.50) * 1e3,
            "p95_ms": merged.quantile(0.95) * 1e3,
            "p99_ms": merged.quantile(0.99) * 1e3,
            "max_ms": max(merged.values()) * 1e3,
            "per_session": dict(self.served),
            "ttfuv_s": dict(self.ttfuv_s),
        }
