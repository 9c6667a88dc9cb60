"""Batched inference engine: queue → micro-batch → bucket → executable.

The runtime counterpart of the compile-once / shape-stable discipline
the training side already enforces (jaxlint JX105/JX110): a background
dispatcher thread drains a bounded request queue into per-model
micro-batches, pads each batch with zero rows up to a fixed bucket
ladder (default 1/4/16/64), and runs a pre-compiled, input-donated,
mesh-sharded forward per ``(model, bucket, dtype, weights
fingerprint)`` from the
:class:`~deepvision_tpu.serve.compile_cache.CompileCache` — eagerly
warmed at startup so no request ever pays a trace. This is the MLPerf
serving recipe (PAPERS.md "Scale MLPerf-0.6 models on Google TPU-v3
Pods"): sustained accelerator utilization comes from keeping a fixed
set of hot executables fed with full batches.

Guarantees (mirroring ``data/prefetch.DevicePrefetcher``'s contract
style):

- **pad isolation** — padded rows are zero inputs whose outputs are
  sliced away before postprocess; they can never leak into a result
  (per-example forwards: eval-mode BN uses running stats, so rows are
  independent).
- **bounded latency or shed** — admission control
  (``admission.AdmissionController``) rejects work with a retry-after
  hint once the queue saturates, instead of queueing into unbounded
  latency.
- **deadline honesty** — a request whose deadline passes while queued
  resolves with ``TimeoutError``, never a late (or wrong) answer.
- **clean shutdown** — ``close()`` stops and joins the dispatcher and
  fails any still-pending futures; no threads or orphaned requests
  leak.
- **crash containment** — the dispatcher runs under a supervisor
  (``_supervise``): an unexpected exception in the loop body fails
  every queued AND in-flight future with the error immediately (no
  client ever hangs until deadline expiry), is counted in telemetry
  (``dispatcher_crashes``/``dispatcher_restarts``), and the loop
  restarts with capped exponential backoff while :meth:`health`
  degrades to ``"recovering"`` (``/healthz`` serves 503) — the
  resilience/ contract: recover from routine faults, loudly.

Every request resolves a ``concurrent.futures.Future``; telemetry
(``telemetry.ServeTelemetry``) attributes each request's wall time to
queue-wait / device-time / e2e and tracks the pad overhead per batch.
"""

from __future__ import annotations

import queue
import sys
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Iterable

import numpy as np

from deepvision_tpu.obs.distributed import flight_dump
from deepvision_tpu.obs.trace import get_tracer, startup_phase
from deepvision_tpu.serve.admission import AdmissionController, ShedError
from deepvision_tpu.serve.compile_cache import CompileCache
from deepvision_tpu.serve.models import ServedModel
from deepvision_tpu.serve.telemetry import ServeTelemetry

__all__ = ["InferenceEngine", "ShedError"]

_WAKE = object()  # queue sentinel: wake the dispatcher without a request


class _Request:
    __slots__ = ("model", "x", "future", "t_submit", "deadline", "trace",
                 "session", "seq")

    def __init__(self, model: str, x, deadline: float | None,
                 trace: str | None = None, session: str | None = None,
                 seq: int | None = None):
        self.model = model
        self.x = x
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.deadline = deadline
        # distributed trace id (obs/distributed.py): stamped on the
        # replica-side queue/device/postprocess spans so one request's
        # timeline assembles across the router and replica processes
        self.trace = trace
        # stateful streams (serve/sessions.py): stream id + frame seq;
        # None for the stateless paths
        self.session = session
        self.seq = seq


class InferenceEngine:
    """Multi-model batched inference over one device mesh.

    ``models``: ServedModel instances (or a name->model dict). The
    bucket ladder applies to every model that doesn't carry its own
    (StableHLO artifacts are pinned to their exported batch). Every
    bucket must be divisible by the mesh's data-axis size — the batch
    dim is sharded over it.

    ``batch_window_s``: after the first request of a batch arrives, how
    long the dispatcher waits for the bucket to fill before running a
    partial (padded) batch. 0 trades padding for latency; saturation
    traffic fills buckets regardless via the backlog.

    ``pipelines``: built :class:`~deepvision_tpu.serve.pipeline.Pipeline`
    DAGs to serve beside the models. Each binds to the engine's shared
    compile cache + mesh and then rides the SAME queue/bucket/admission
    path as a model — ``submit(x, model=<pipeline name>)`` just works,
    and ``warm()`` compiles every stage of every pipeline end-to-end.

    ``freeze_cache``: freeze the compile cache after warmup — any
    request-time miss raises instead of tracing, proving no request
    (pipeline or plain) can ever pay a hidden compile.

    Multi-tenancy (``serve/tenancy.py``): ``store`` (an
    ``ArtifactStore`` or a directory path) warms executables from
    disk and exports trace-compiled ones back; ``residency_bytes``
    caps resident weight bytes with LRU eviction to host;
    ``tenant_quota`` / ``slo_class`` thread per-tenant admission
    isolation into the :class:`AdmissionController`. :meth:`hot_swap`
    replaces one tenant's weights under live load with zero drops.
    """

    @startup_phase("engine")
    def __init__(
        self,
        models: Iterable[ServedModel] | dict[str, ServedModel],
        *,
        mesh=None,
        buckets: tuple[int, ...] = (1, 4, 16, 64),
        max_queue: int = 256,
        per_model_limit: int | None = None,
        batch_window_s: float = 0.0,
        warmup: bool = True,
        cache_entries: int = 64,
        telemetry: ServeTelemetry | None = None,
        fault_injector=None,
        restart_backoff_s: float = 0.05,
        restart_backoff_max_s: float = 5.0,
        pipelines: Iterable = (),
        freeze_cache: bool = False,
        store=None,
        residency_bytes: int | None = None,
        tenant_quota: dict[str, int] | None = None,
        slo_class: dict[str, str] | None = None,
    ):
        if isinstance(models, dict):
            self._models = dict(models)
        else:
            self._models = {m.name: m for m in models}
        if not self._models:
            raise ValueError("engine needs at least one ServedModel")
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError(f"bucket ladder must be sorted unique, "
                             f"got {buckets}")
        if mesh is None:
            from deepvision_tpu.core.mesh import create_mesh

            mesh = create_mesh(1, 1)  # single-device default: serving a
            # host; pass an explicit mesh to shard batches over chips
        self._mesh = mesh
        self.buckets = tuple(buckets)
        self._cache = CompileCache(max_entries=cache_entries)
        for p in pipelines:
            if p.name in self._models:
                raise ValueError(
                    f"pipeline {p.name!r} collides with a served model")
            # bind before _check_ladders: divisibility is checked for
            # every STAGE ladder, not just the pipeline's entry ladder
            p.bind(self._cache, self._mesh, self.buckets)
            self._models[p.name] = p
        self._check_ladders()
        self.telemetry = telemetry if telemetry is not None \
            else ServeTelemetry()
        self._admission = AdmissionController(
            max_queue=max_queue, per_model_limit=per_model_limit,
            tenant_quota=tenant_quota, slo_class=slo_class)
        self._window = batch_window_s
        self._poll_s = 0.05
        self._q: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._paused = threading.Event()
        # dispatcher supervision state: per-model backlog + the batch
        # currently in the loop's hands live on the INSTANCE so a crash
        # handler can fail every one of their futures (a local would
        # strand them un-resolvable — clients hang to deadline expiry)
        self._pending: dict[str, list[_Request]] = {
            name: [] for name in self._models}
        self._in_flight: list[_Request] = []
        # packed host batches, kept for the engine's life (`_stage`):
        # (bucket, input shape, input dtype) -> [buffer, rows the last
        # batch in it wrote]
        self._staging: dict[tuple, list] = {}
        self._recovering = threading.Event()
        # guards _recover_until: written by the supervisor thread,
        # read by health() probes from any thread (jaxlint JX118 — the
        # Event alone orders the write but a linter, and the next
        # maintainer, should not have to prove publication order)
        self._health_lock = threading.Lock()
        self._recover_until = 0.0  # monotonic end of the backoff window
        self._injector = fault_injector
        self._restart_backoff_s = restart_backoff_s
        self._restart_backoff_max_s = restart_backoff_max_s
        self._backoff_reset_s = 5.0  # healthy-for-this-long resets backoff
        self.warmup_s = 0.0
        if store is not None and not hasattr(store, "get"):
            from deepvision_tpu.serve.artifact_store import ArtifactStore

            store = ArtifactStore(store, log=self._log)
        self._store = store
        # (model, bucket, dtype, fp) keys whose executables came off
        # disk instead of a trace — the respawn-without-compile-storm
        # evidence ``stats()`` reports and bench pins
        self._from_store: set = set()
        from deepvision_tpu.serve.tenancy import TenancyManager

        self._tenancy = TenancyManager(
            self._mesh, budget_bytes=residency_bytes, log=self._log)
        self._adopt_tenants()
        if warmup:
            self.warm()
            if freeze_cache:
                # warmed end-to-end (pipelines included): any later
                # miss is a hidden request-time compile — fail loudly
                self._cache.freeze()
        self._thread = threading.Thread(
            target=self._dispatcher_main, name="serve-dispatch", daemon=True
        )
        self._thread.start()

    # -- setup -----------------------------------------------------------
    def _check_ladders(self) -> None:
        from deepvision_tpu.core.mesh import axis_size

        n_data = axis_size(self._mesh)
        for m in self._models.values():
            for b in self.ladder(m):
                if b % n_data:
                    raise ValueError(
                        f"bucket {b} for model {m.name!r} is not "
                        f"divisible by the mesh data axis ({n_data}); "
                        "batches are sharded over it")

    @staticmethod
    def _log(*args, **kw) -> None:
        # tenancy/store chatter goes to stderr: stdout is the JSONL
        # protocol stream when serve.py hosts this engine
        print(*args, file=sys.stderr, **kw)

    def _adopt_tenants(self) -> None:
        """Register every weight-carrying model (pipeline/stateful
        STAGE models included — shared objects with the plain serving
        path) with the tenancy manager: one fingerprint + one
        replicated device placement + a weights edition each, so
        per-batch calls never re-place (or worse, re-transfer) params
        and eviction/hot-swap have their seam."""
        for m in self._models.values():
            if getattr(m, "is_pipeline", False) \
                    or getattr(m, "is_stateful", False):
                # a pipeline's own variables are None; its STAGE models
                # carry the weights
                for sm in m.stage_models().values():
                    self._tenancy.adopt(sm)
            else:
                self._tenancy.adopt(m)

    def _tenant_names(self, served) -> list[str]:
        if getattr(served, "is_pipeline", False) \
                or getattr(served, "is_stateful", False):
            return list(served.stage_models())
        return [served.name]

    def _model_key(self, m, bucket: int) -> tuple:
        """Compile-cache key: ``(model, bucket, dtype, weights
        fingerprint)``. The fingerprint pins an executable to the
        weights generation it was compiled against — after a hot-swap
        the key changes, so a stale executable can never silently pair
        with new weights. Pipelines/stateful wrappers key their front
        door ``"static"``: their weights live in the per-stage cache
        entries, which carry the stage fingerprints."""
        fp = getattr(m, "weights_fingerprint", None)
        return (m.name, bucket, m.dtype_str,
                fp() if fp is not None else "static")

    def ladder(self, model: ServedModel) -> tuple[int, ...]:
        return model.buckets if model.buckets else self.buckets

    def warm(self) -> None:
        """Eagerly compile every (model, bucket) executable so no
        request ever pays a trace; time recorded in ``warmup_s``.

        Precompiled (StableHLO-artifact) runners additionally execute
        once on a zero batch fed through the EXACT request path
        (``device_put`` with the batch sharding): ``jax.export``
        serializes StableHLO, not machine code, so the deserialized
        callable compiles for the local backend on first call — and it
        specializes on the input's placement, so a numpy-fed warmup
        would leave the device-array-fed request path still cold.
        Without this, the engine's "no request pays a compile" contract
        silently broke for artifacts (measured as a multi-second stall
        of the first request burst on every fresh replica).

        With an artifact store attached, every storeable (model,
        bucket) first tries the disk: a verified StableHLO blob under
        this mesh + weights fingerprint deserializes into the cache
        (``install``, no miss counted) instead of paying the trace —
        the respawn path PR 6 measured stops re-compiling. Misses
        trace-compile as before and are exported back into the store,
        so the first replica of a fleet populates it for the rest."""
        import jax

        from deepvision_tpu.core.mesh import data_sharding

        t0 = time.perf_counter()
        for m in self._models.values():
            for bucket in self.ladder(m):
                key = self._model_key(m, bucket)
                runner = None
                if self._store is not None and self._storeable(m):
                    runner = self._load_store_runner(m, bucket)
                    if runner is not None:
                        self._cache.install(key, runner)
                        self._from_store.add(key)
                from_store = runner is not None
                if runner is None:
                    runner = self._cache.get_or_build(
                        key,
                        lambda m=m, bucket=bucket: m.compile_for(
                            bucket, self._mesh),
                    )
                    if self._store is not None and self._storeable(m):
                        self._save_store_entry(m, bucket)
                if from_store or m.precompiled is not None \
                        or getattr(m, "is_pipeline", False) \
                        or getattr(m, "is_stateful", False):
                    # pipelines zero-execute too: their runners thread
                    # eager device ops (chunk slice/pad/concat, dict
                    # re-packing) between stage executables, and any
                    # StableHLO artifact — pre-exported or store-loaded
                    # — backend-compiles on first call AND specializes
                    # on input placement, so the zero batch feeds
                    # through the exact request path
                    x = np.zeros((bucket, *m.input_shape), m.input_dtype)
                    xd = jax.device_put(
                        x, data_sharding(self._mesh, x.ndim))
                    try:
                        jax.device_get(runner(xd))
                    except Exception as e:
                        if not from_store:
                            raise
                        # the blob deserialized but cannot EXECUTE on
                        # this backend (e.g. a custom call without
                        # serialization-compat guarantees): reject it
                        # so future respawns skip it, and trace-compile
                        # — the store must never make warmup fail, only
                        # faster. No re-export: the same program just
                        # proved un-runnable from serialized form here.
                        self._log(
                            f"[artifact-store] {m.name}@{bucket}: "
                            f"stored program failed to execute ({e}); "
                            "rejecting + re-tracing")
                        self._reject_store_entry(m, bucket,
                                                 reason=str(e))
                        self._cache.drop_where(
                            lambda k, key=key: k == key)
                        self._from_store.discard(key)
                        self._cache.get_or_build(
                            key,
                            lambda m=m, bucket=bucket: m.compile_for(
                                bucket, self._mesh),
                        )
        if self._store is not None:
            # a tenant whose ENTIRE ladder deserialized from the store
            # serves programs with the weights baked in as constants:
            # nothing reads its edition at call time, so the adopted
            # device copy is released to host and the tenant leaves
            # the residency budget's LRU (an eviction could not free
            # baked constants anyway). Partially store-warmed models
            # keep their edition resident — their trace-compiled
            # buckets read it. A later hot-swap compiles edition-
            # backed runners and re-enters residency management.
            for m in self._models.values():
                if not self._storeable(m):
                    continue
                keys = [self._model_key(m, b) for b in self.ladder(m)]
                if all(k in self._from_store for k in keys):
                    self._tenancy.release_to_baked(m, len(keys))
        self.warmup_s = round(time.perf_counter() - t0, 3)

    def _storeable(self, m) -> bool:
        """Models whose request program the artifact store can carry:
        plain weight-backed forwards. Pipelines re-assemble from their
        (storeable) stages' trace path, pre-exported artifacts already
        ARE serialized programs, and stateful wrappers hold live
        device state no AOT blob can bake in."""
        return (not getattr(m, "is_pipeline", False)
                and not getattr(m, "is_stateful", False)
                and getattr(m, "precompiled", None) is None
                and getattr(m, "variables", None) is not None)

    def _load_store_runner(self, m, bucket: int):
        """Verified store bytes -> runner, or None (miss / corrupt —
        the store quarantined it — / undeserializable): the caller
        falls back to trace-compile, so the store never makes warmup
        *fail*, only faster."""
        from deepvision_tpu.export import deserialize_exported
        from deepvision_tpu.serve.artifact_store import mesh_desc

        data = self._store.get(
            model=m.name, bucket=bucket, dtype=m.dtype_str,
            mesh=mesh_desc(self._mesh),
            fingerprint=m.weights_fingerprint())
        if data is None:
            return None
        try:
            return deserialize_exported(data)
        except Exception as e:
            self._log(f"[artifact-store] {m.name}@{bucket}: "
                      f"deserialize failed ({e}); re-tracing")
            return None

    def _save_store_entry(self, m, bucket: int) -> None:
        """Best-effort export into the store — a full disk must never
        take serving down with it."""
        from deepvision_tpu.serve.artifact_store import mesh_desc

        try:
            self._store.put(
                m.export_bytes(bucket), model=m.name, bucket=bucket,
                dtype=m.dtype_str, mesh=mesh_desc(self._mesh),
                fingerprint=m.weights_fingerprint())
        except Exception as e:
            self._log(f"[artifact-store] export {m.name}@{bucket} "
                      f"failed: {e}")

    def _reject_store_entry(self, m, bucket: int, *,
                            reason: str) -> None:
        """Quarantine a store entry that deserialized but could not
        execute here — best-effort, like every store write."""
        from deepvision_tpu.serve.artifact_store import mesh_desc

        try:
            self._store.reject(
                model=m.name, bucket=bucket, dtype=m.dtype_str,
                mesh=mesh_desc(self._mesh),
                fingerprint=m.weights_fingerprint(), reason=reason)
        except Exception as e:
            self._log(f"[artifact-store] reject {m.name}@{bucket} "
                      f"failed: {e}")

    # -- tenancy ---------------------------------------------------------
    def hot_swap(self, name: str, variables=None, *,
                 workdir: str | None = None,
                 perturb: float | None = None) -> dict:
        """Zero-drop weight hot-swap for one tenant. Runs on the
        CALLER's thread: the new weights are staged and the whole
        bucket ladder pre-compiled off the dispatch path, then the
        tenant's weights edition flips atomically between batches —
        requests already dispatched against the pre-swap executables
        drain on the pre-swap weights (their runners keep their
        compile-time edition), and nothing is ever dropped.

        Exactly one source: ``variables`` (a ready pytree),
        ``workdir`` (restore the latest checkpoint), or ``perturb``
        (current weights + a float constant — the smoke-drill path:
        guarantees a new fingerprint without a second checkpoint).

        Pipelines that use this model as a STAGE keep serving the
        weights they warmed with (their DAG runners captured the old
        edition at compile time) until re-registered — the front-door
        path for ``name`` swaps; DAGs are deliberately immutable."""
        served = self._models.get(name)
        if served is None:
            raise ValueError(f"unknown model {name!r}; serving "
                             f"{sorted(self._models)}")
        if getattr(served, "is_pipeline", False) \
                or getattr(served, "is_stateful", False):
            kind = ("pipeline" if getattr(served, "is_pipeline", False)
                    else "stateful wrapper")
            raise ValueError(
                f"{name!r} is a {kind}; hot-swap targets its stage "
                "models' front doors")
        if served.variables is None:
            raise ValueError(
                f"{name!r} is a StableHLO artifact (weights baked into "
                "the program); register a new artifact instead")
        if sum(v is not None for v in (variables, workdir, perturb)) != 1:
            raise ValueError(
                "pass exactly one of variables=, workdir=, perturb=")
        if workdir is not None:
            from deepvision_tpu.serve.models import (
                _state_variables,
                model_geometry,
                restore_state,
            )

            size, ch = model_geometry(name)
            state = restore_state(
                name, workdir, np.zeros((1, size, size, ch), np.float32))
            variables = _state_variables(state)
        if perturb is not None:
            import jax

            def _nudge(a):
                a = np.asarray(a)
                if np.issubdtype(a.dtype, np.floating):
                    return (a + perturb).astype(a.dtype)
                return a

            variables = jax.tree_util.tree_map(
                _nudge, served.edition.variables)
        result = self._tenancy.swap(
            served, variables, ladder=self.ladder(served),
            mesh=self._mesh, cache=self._cache,
            key_fn=self._model_key)
        if result.get("unchanged"):
            # same-fingerprint swap: the live ladder already pairs
            # with these exact bytes — nothing installed, nothing
            # dropped, nothing to re-export
            return result
        if self._from_store:
            # the swap dropped any store-warmed (baked-weights)
            # runners for this tenant; stats must stop claiming them
            self._from_store = {
                k for k in self._from_store if k[0] != name}
        if self._store is not None and self._storeable(served):
            # keep the store current: a replica respawned after the
            # swap warms the NEW fingerprint from disk
            for bucket in self.ladder(served):
                self._save_store_entry(served, bucket)
        return result

    def _bucket_runner(self, served, bucket: int):
        """The cached executable for (model, bucket) with swap
        consistency: if a hot-swap flips the weights edition between
        the key read and the cache lookup, retry — the runner an
        executable key names must always pair with the weights
        generation in that key (satellite-bugfix contract)."""
        while True:
            key = self._model_key(served, bucket)
            runner = self._cache.get_or_build(
                key, lambda: served.compile_for(bucket, self._mesh))
            if key == self._model_key(served, bucket):
                return runner

    @property
    def tenancy(self):
        """The engine's :class:`~deepvision_tpu.serve.tenancy.
        TenancyManager` (always present; budget-less by default) —
        ``serve.py`` prints its grep-stable summary line at exit."""
        return self._tenancy

    # -- client surface --------------------------------------------------
    def submit(self, x, model: str | None = None, *,
               timeout_s: float | None = None,
               trace: str | None = None,
               session: str | None = None,
               seq: int | None = None) -> Future:
        """Enqueue one example (no batch dim) for ``model``; returns a
        Future resolving to the task's result dict. Raises
        :class:`ShedError` immediately when admission rejects, and
        ``ValueError`` on shape/model mismatch (fail fast, not in the
        dispatcher). ``trace`` is the request's distributed trace id
        (propagated from the router over ``X-DVTPU-Trace``): the
        per-request queue/device/postprocess spans carry it.

        Stateful models (``serve/sessions.py``) additionally require
        ``session`` (stream id) + ``seq`` (frame number): the session's
        device state threads through this same admission/deadline path,
        and a NEW session is shed here when the store is at capacity."""
        if model is None:
            if len(self._models) != 1:
                raise ValueError(
                    f"engine hosts {sorted(self._models)}; pass model=")
            (model,) = self._models  # the single-model host default
        served = self._models.get(model)
        if served is None:
            raise ValueError(f"unknown model {model!r}; serving "
                             f"{sorted(self._models)}")
        if self._stop.is_set():
            raise RuntimeError("engine is closed")
        x = np.asarray(x, dtype=served.input_dtype)
        if x.shape != served.input_shape:
            raise ValueError(
                f"{model!r} expects input shape {served.input_shape}, "
                f"got {x.shape}")
        if getattr(served, "is_stateful", False):
            if session is None or seq is None:
                raise ValueError(
                    f"stateful model {model!r} requires session= and "
                    "seq= on submit")
            seq = int(seq)
            if seq < 0:
                raise ValueError(f"seq must be >= 0, got {seq}")
            try:
                # capacity sheds NEW sessions at the door; existing
                # streams keep their state (never a silent reset)
                served.store.admit(session)
            except ShedError:
                self.telemetry.record_shed()
                raise
        elif session is not None:
            raise ValueError(
                f"model {model!r} is stateless; session=/seq= is only "
                "valid for stateful models")
        try:
            self._admission.admit(model)
        except ShedError:
            self.telemetry.record_shed()
            raise
        self.telemetry.record_submit()
        req = _Request(
            model, x,
            deadline=(time.perf_counter() + timeout_s
                      if timeout_s is not None else None),
            trace=trace, session=session, seq=seq)
        self._q.put(req)
        if self._stop.is_set():
            # raced close(): the dispatcher's exit drain may already
            # have passed — make sure this future resolves either way.
            # Releaser = whoever resolves the future, exactly once
            # (same rule as _resolve_dropped), so the slot is never
            # double-released when both sides race.
            try:
                req.future.set_exception(RuntimeError("engine closed"))
            except InvalidStateError:
                pass  # dispatcher's drain resolved (and released)
            else:
                self._admission.release(model)
        return req.future

    def _session_stores(self) -> dict:
        """name -> SessionStore for every stateful model."""
        return {name: m.store for name, m in self._models.items()
                if getattr(m, "is_stateful", False)}

    def stats(self) -> dict:
        """JSON-able state for ``/stats`` and the bench report."""
        out = {
            "models": sorted(self._models),
            "pipelines": {
                name: m.requests_served
                for name, m in sorted(self._models.items())
                if getattr(m, "is_pipeline", False)},
            "buckets": list(self.buckets),
            "warmup_s": self.warmup_s,
            "health": self.health(),
            "queue": self._admission.stats(),
            "cache": self._cache.stats(),
            "tenancy": self._tenancy.stats(),
            "warmed_from_store": sorted(
                f"{k[0]}@{k[1]}" for k in self._from_store),
            "telemetry": self.telemetry.snapshot(),
        }
        if self._store is not None:
            out["artifact_store"] = self._store.stats()
        stores = self._session_stores()
        if stores:
            out["sessions"] = {name: s.stats()
                               for name, s in sorted(stores.items())}
        return out

    def health(self) -> dict:
        """Liveness for ``/healthz``: ``"recovering"`` while the
        supervisor sits in a post-crash backoff window (the CLI serves
        503 then — load balancers should drain, not route), ``"ok"``
        otherwise. Crash/restart counts ride along so a probe can tell
        self-healed from never-faulted."""
        recovering = self._recovering.is_set()
        out = {
            "status": "recovering" if recovering else "ok",
            "dispatcher_crashes": self.telemetry.dispatcher_crashes,
            "dispatcher_restarts": self.telemetry.dispatcher_restarts,
        }
        if recovering:
            # when to re-probe: the rest of the backoff window — the
            # /healthz 503 carries it as Retry-After so load balancers
            # re-probe on schedule instead of hammering or forgetting
            with self._health_lock:
                until = self._recover_until
            out["retry_after_s"] = round(
                max(0.05, until - time.monotonic()), 3)
        stores = self._session_stores()
        if stores:
            # stateful-serving liveness: live streams, device bytes
            # pinned by their state, and the worst-case snapshot age
            # (how much replay a crash right now would need)
            agg = [s.stats() for s in stores.values()]
            ages = [a["snapshot_age_s"] for a in agg
                    if a["snapshot_age_s"] is not None]
            out["sessions"] = {
                "live": sum(a["live"] for a in agg),
                "pinned_bytes": sum(a["pinned_bytes"] for a in agg),
                "snapshot_age_s": max(ages) if ages else None,
            }
        return out

    # pause/resume: used by drains and tests that need deterministic
    # queue buildup (backpressure, deadline expiry) without sleeping on
    # a compile race
    def pause(self) -> None:
        self._paused.set()

    def resume(self) -> None:
        self._paused.clear()
        self._q.put(_WAKE)

    # -- dispatcher ------------------------------------------------------
    def _dispatcher_main(self) -> None:
        """The dispatcher thread's body: the supervised loop, and on its
        way out (``close()`` joins it) the staging buffers go with the
        only thread that ever touched them."""
        try:
            self._supervise()
        finally:
            self._staging.clear()
            self.telemetry.stage_bytes.set(0)

    def _supervise(self) -> None:
        """Run the dispatch loop under crash supervision: an unexpected
        exception (anything ``_run_batch``'s per-batch containment did
        not absorb) fails every queued and in-flight future with the
        error — immediately, not at deadline expiry — then the loop
        restarts after a capped exponential backoff. ``health()``
        reports ``"recovering"`` for the backoff window. Backoff resets
        once a loop incarnation survives ``_backoff_reset_s``, so an
        engine that crashes once a day never escalates to max delay."""
        backoff = self._restart_backoff_s
        while True:
            t0 = time.monotonic()
            try:
                self._dispatch_loop()
                return  # clean close(): loop drained and exited
            except BaseException as e:
                self.telemetry.record_dispatcher_crash()
                # black box first: the flight recorder's ring holds the
                # spans/metric deltas leading up to exactly this moment
                flight_dump("dispatcher_crash")
                n = self._fail_all_pending(RuntimeError(
                    f"dispatcher crashed: {type(e).__name__}: {e}"))
                print(f"[serve-supervisor] dispatcher crashed "
                      f"({type(e).__name__}: {e}); failed {n} pending "
                      f"request(s); restarting in {backoff:.2f}s",
                      file=sys.stderr, flush=True)
                if self._stop.is_set():
                    # closing: drain anything submitted since the crash
                    self._fail_all_pending(RuntimeError("engine closed"))
                    return
                if time.monotonic() - t0 > self._backoff_reset_s:
                    backoff = self._restart_backoff_s
                with self._health_lock:
                    self._recover_until = time.monotonic() + backoff
                self._recovering.set()
                self._stop.wait(backoff)  # close() wakes this instantly
                self._recovering.clear()
                if self._stop.is_set():
                    self._fail_all_pending(RuntimeError("engine closed"))
                    return
                backoff = min(backoff * 2, self._restart_backoff_max_s)
                self.telemetry.record_dispatcher_restart()

    def _phase(self, name: str, args: dict | None = None):
        """One phase of the dispatcher's cycle (``wait``,
        ``fill_window``, ``pack``, ``device_put``, ``resolve``; the
        sixth, ``device``, goes through ``record_batch``): a span whose
        one pair of clock reads feeds the phase's ``serve_<name>_time``
        histogram always, the ring tracer when it is on, and a
        ``serve/<name>`` profiler annotation when a profile runs. The
        phases are flat and consecutive on the dispatcher thread; what
        lies between two of them is microseconds."""
        return get_tracer().timed(
            name, cat="serve", args=args,
            observe=self.telemetry.phase_time[name].record)

    def _dispatch_loop(self) -> None:
        pending = self._pending
        rr = list(self._models)  # round-robin cursor over models
        while not self._stop.is_set():
            if self._paused.is_set():
                # stop-responsive pause poll (jaxlint JX113): a bare
                # time.sleep here would hold close() hostage to the
                # poll tick instead of waking on the stop event
                self._stop.wait(0.002)
                continue
            if any(pending.values()):
                self._drain_inbound(pending, block=False)
            else:
                # nothing pending: blocked until a request arrives (or
                # the poll tick) is the cycle's `wait` phase
                with self._phase("wait"):
                    self._drain_inbound(pending, block=True)
            if self._stop.is_set() or self._paused.is_set():
                continue
            name = self._next_model(pending, rr)
            if name is None:
                continue
            served = self._models[name]
            ladder_max = max(self.ladder(served))
            self._fill_window(pending, name, ladder_max)
            reqs = pending[name][:ladder_max]
            del pending[name][:ladder_max]
            if getattr(served, "is_stateful", False):
                # one frame per session per batch: the compiled update
                # reads each row's PRE-batch slate, so two frames of one
                # stream in a batch would both read stale state. Later
                # frames return to the FRONT of the backlog in arrival
                # order — per-stream FIFO holds across the deferral.
                seen: set[str] = set()
                keep: list[_Request] = []
                defer: list[_Request] = []
                for r in reqs:
                    if r.session in seen:
                        defer.append(r)
                    else:
                        seen.add(r.session)
                        keep.append(r)
                if defer:
                    pending[name][:0] = defer
                    reqs = keep
            # visible to the crash handler from the moment they leave
            # the backlog: a crash anywhere past the slice (deadline
            # expiry included) must fail THESE futures too, or their
            # clients hang and their admission slots leak
            self._in_flight = reqs
            live = self._expire(reqs)
            if live:
                self._in_flight = live
                if self._injector is not None:
                    self._injector.check_dispatch()  # chaos site
                self._run_batch(served, live)
            self._in_flight = []
        # drain: fail anything still queued/pending so no caller blocks
        # forever on a future the dispatcher will never resolve
        self._drain_inbound(pending, block=False)
        for reqs in pending.values():
            for r in reqs:
                self._resolve_dropped(r)
            reqs.clear()

    def _fail_all_pending(self, exc: BaseException) -> int:
        """Resolve every queued + in-flight future with ``exc`` (counted
        as failures, admission slots released); -> how many."""
        n = 0
        self._drain_inbound(self._pending, block=False)
        for r in self._in_flight:
            n += self._fail_request(r, exc)
        self._in_flight = []
        for reqs in self._pending.values():
            for r in reqs:
                n += self._fail_request(r, exc)
            reqs.clear()
        return n

    def _fail_request(self, r: _Request, exc: BaseException) -> int:
        # releaser = whoever resolves the future, exactly once (the
        # raced-close branch of submit() follows the same rule)
        try:
            r.future.set_exception(exc)
        except InvalidStateError:
            return 0  # already resolved (and released) elsewhere
        self.telemetry.record_failure()
        self._admission.release(r.model)
        return 1

    def _drain_inbound(self, pending, block: bool) -> None:
        try:
            item = (self._q.get(timeout=self._poll_s) if block
                    else self._q.get_nowait())
        except queue.Empty:
            return
        while True:
            if item is not _WAKE:
                pending[item.model].append(item)
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return

    @staticmethod
    def _next_model(pending, rr: list[str]) -> str | None:
        for _ in range(len(rr)):
            name = rr.pop(0)
            rr.append(name)
            if pending[name]:
                return name
        return None

    def _fill_window(self, pending, name: str, ladder_max: int) -> None:
        """Give the queue up to ``batch_window_s`` (from the oldest
        pending request) to fill the largest bucket before running a
        padded partial batch."""
        if self._window <= 0:
            return
        until = pending[name][0].t_submit + self._window
        with self._phase("fill_window"):
            while len(pending[name]) < ladder_max \
                    and not self._stop.is_set():
                remaining = until - time.perf_counter()
                if remaining <= 0:
                    return
                try:
                    item = self._q.get(timeout=remaining)
                except queue.Empty:
                    return
                if item is not _WAKE:
                    pending[item.model].append(item)

    def _expire(self, reqs: list[_Request]) -> list[_Request]:
        now = time.perf_counter()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                try:
                    r.future.set_exception(TimeoutError(
                        f"deadline expired after "
                        f"{now - r.t_submit:.3f}s in queue"))
                except InvalidStateError:
                    continue  # raced close() resolved (and released) it
                self.telemetry.record_timeout()
                self._admission.release(r.model)
            else:
                live.append(r)
        return live

    def _bucket_for(self, served: ServedModel, n: int) -> int:
        for b in self.ladder(served):
            if b >= n:
                return b
        return max(self.ladder(served))

    def _stage(self, served, bucket: int, rows: list) -> np.ndarray:
        """The packed host batch of one dispatch: ``rows`` copied into
        rows ``0 .. n`` of a staging buffer the engine keeps, rows
        ``n .. bucket`` exact zeros (pad isolation does not lean on a
        model's rows being independent). One buffer per ``(bucket,
        input shape, input dtype)`` — two tenants of one shape share
        it — made on first use, held until ``close()`` ends the
        dispatcher (``_dispatcher_main``): a fresh ``np.zeros`` every
        batch paid page faults on its first touch (4.4 ms a 4.4 MB row
        of YOLOv3-608; chip run, PR 25) and an unmapping at the batch's
        end. Only ``n .. <rows the last batch wrote>`` is zeroed again.

        INVARIANT: the buffer handed out is written again by the next
        ``_stage`` call of its key, so a batch must have its results on
        the host (or be dead) before the next one packs. The serial
        dispatcher holds that by construction — ``jax.device_get(
        runner(xd))`` returns before the next ``pack``, and a crashed
        loop restarts from a failed batch. Packing batch n+1 while
        batch n runs needs two buffers a key, not this one shared. The
        CPU backend's ``device_put`` is zero-copy for an aligned numpy
        array (``may_alias=False`` does not change that), so ``xd`` can
        BE this buffer there; no executable forwards its input buffer
        into an output (a zero-copy buffer cannot be donated), which
        tests/test_serve.py pins: a result a client holds never shares
        memory with the buffer."""
        key = (bucket, tuple(served.input_shape),
               np.dtype(served.input_dtype))
        slot = self._staging.get(key)
        if slot is None:
            slot = self._staging[key] = [
                np.zeros((bucket, *served.input_shape),
                         served.input_dtype), 0]
            self.telemetry.record_stage(held_bytes=sum(
                s[0].nbytes for s in self._staging.values()))
        else:
            self.telemetry.record_stage()
        buf, last_n = slot
        n = slot[1] = len(rows)
        for i, x in enumerate(rows):
            buf[i] = x
        if last_n > n:
            buf[n:last_n] = 0
        return buf

    def _run_batch(self, served: ServedModel, reqs: list[_Request]) -> None:
        import jax

        from deepvision_tpu.core.mesh import data_sharding

        if getattr(served, "is_stateful", False):
            self._run_stateful_batch(served, reqs)
            return
        n = len(reqs)
        bucket = self._bucket_for(served, n)
        tracer = get_tracer()
        batch_args = {"model": served.name, "bucket": bucket, "rows": n}
        traces = [r.trace for r in reqs if r.trace]
        with self._phase("pack", batch_args) as sp:
            x = self._stage(served, bucket, [r.x for r in reqs])
        t_dispatch = sp.t0
        try:
            with self._phase("device_put", {"bucket": bucket}):
                # residency first: a cold tenant's weights come back to
                # the device (and LRU victims leave) BEFORE the
                # executable runs
                for tn in self._tenant_names(served):
                    self._tenancy.ensure_resident(tn)
                runner = self._bucket_runner(served, bucket)
                xd = jax.device_put(x, data_sharding(self._mesh, x.ndim))
            # the half of the distributed request timeline that runs on
            # the replica's chip. It measures completed compute:
            # device_get drains the dispatch before the end stamp, the
            # JX112/JX117 contract. `serve_device_time` gets the span's
            # own seconds
            with tracer.timed(
                    "device", cat="serve",
                    args={**batch_args,
                          **({"traces": traces} if traces else {})},
                    ) as sp_dev:
                host = jax.device_get(runner(xd))
        except Exception as e:  # device/compile failure: fail the batch
            for r in reqs:
                r.future.set_exception(e)
                self.telemetry.record_failure()
                self._admission.release(r.model)
            return
        with self._phase("resolve", {"rows": n}):
            self._resolve_batch(served, reqs, host, bucket, t_dispatch,
                                sp_dev.dur, traces)
            # the batch's device input and fetched outputs go inside
            # the phase, so their release is on the cycle's clock; the
            # packed host array is the engine's staging buffer and
            # stays (`_stage`)
            del xd, host

    def _resolve_batch(self, served, reqs, host, bucket: int,
                       t_dispatch: float, t_dev: float,
                       traces: list) -> None:
        """The `resolve` phase of a stateless batch: counters, then per
        request the host post-process, ``set_result`` (which runs the
        client's callbacks on this thread) and the admission release."""
        n = len(reqs)
        self.telemetry.record_batch(bucket=bucket, rows=n, device_s=t_dev)
        self._admission.observe_batch(t_dev, n)
        is_pipeline = getattr(served, "is_pipeline", False)
        expired: set[int] = set()
        if is_pipeline:
            served.record_served(n)
            # deadline honesty holds mid-DAG too: a multi-stage run can
            # outlive a request's deadline after queue-time expiry
            # passed it — resolve TimeoutError (exactly once; the
            # try/except is the same releaser rule as _expire), never a
            # late answer
            t_now = time.perf_counter()
            for r in reqs:
                if r.deadline is not None and t_now > r.deadline:
                    try:
                        r.future.set_exception(TimeoutError(
                            f"deadline expired mid-pipeline after "
                            f"{t_now - r.t_submit:.3f}s"))
                    except InvalidStateError:
                        continue
                    self.telemetry.record_timeout()
                    self._admission.release(r.model)
                    expired.add(id(r))
        tracer = get_tracer()
        if tracer.active:
            # retroactive per-request spans from the stamps this loop
            # already takes (obs/trace.py record_span — same
            # perf_counter clock)
            if is_pipeline:
                # one span per DAG stage, stamped with every request
                # trace id in the batch: the trace ids flow router ->
                # replica_queue -> device -> stage:<node> -> postprocess
                # in a single Perfetto timeline (trace_merge
                # --assert-flow proves the crossing)
                for stage_name, s0, s1 in served.take_stage_stamps():
                    tracer.record_span(
                        f"stage:{stage_name}", s0, s1, cat="serve",
                        args={"pipeline": served.name,
                              "stage": stage_name,
                              **({"traces": traces} if traces else {})})
            for r in reqs:
                if r.trace:
                    tracer.record_span(
                        "replica_queue", r.t_submit, t_dispatch,
                        cat="serve",
                        args={"trace": r.trace, "model": served.name})
        now = time.perf_counter()
        for i, r in enumerate(reqs):
            if id(r) in expired:
                continue  # resolved TimeoutError above, slot released
            t_pp = time.perf_counter()
            try:
                result = served.postprocess(host, i)
            except Exception as e:
                r.future.set_exception(e)
                self.telemetry.record_failure()
            else:
                r.future.set_result(result)
                self.telemetry.record_request(
                    queue_wait_s=t_dispatch - r.t_submit,
                    e2e_s=now - r.t_submit)
            if r.trace and tracer.active:
                tracer.record_span(
                    "postprocess", t_pp, time.perf_counter(),
                    cat="serve", args={"trace": r.trace})
            self._admission.release(r.model)

    def _run_stateful_batch(self, served, reqs: list[_Request]) -> None:
        """Dispatch one batch of a stateful model (TrackingPipeline):
        disposition each frame through the SessionStore, answer
        duplicates idempotently, then run the detect and interpolate
        sub-batches as separate compiled programs. State stays on
        device — ONE ``device_get`` of the batch OUTPUT per sub-batch,
        never a per-frame round trip on state leaves (the JX128
        contract); the only state fetch is the store's on-cadence
        snapshot inside ``commit``."""
        store = served.store
        t_dispatch = time.perf_counter()
        frames = [(r, store.begin_frame(r.session, r.seq,
                                        served.detect_every))
                  for r in reqs]
        dup = [(r, f) for r, f in frames if f.action == "duplicate"]
        detect = [(r, f) for r, f in frames
                  if f.action == "apply" and f.run_detect]
        interp = [(r, f) for r, f in frames
                  if f.action == "apply" and not f.run_detect]
        now = time.perf_counter()
        for r, _f in dup:
            # idempotent replay/retry answer: seq already applied, no
            # recompute, no state touched (same exactly-once releaser
            # rule as everywhere else)
            try:
                r.future.set_result({"session": r.session, "seq": r.seq,
                                     "replayed": True,
                                     "state_reset": False})
            except InvalidStateError:
                continue
            self.telemetry.record_request(
                queue_wait_s=t_dispatch - r.t_submit,
                e2e_s=now - r.t_submit)
            self._admission.release(r.model)
        for group, mode in ((detect, "detect"), (interp, "interp")):
            if group:
                self._run_stateful_group(
                    served, store, group, mode, t_dispatch)

    def _run_stateful_group(self, served, store, group,
                            mode: str, t_dispatch: float) -> None:
        import jax
        import jax.numpy as jnp

        from deepvision_tpu.core.mesh import data_sharding

        n = len(group)
        bucket = self._bucket_for(served, n)
        tracer = get_tracer()
        batch_args = {"model": served.name, "bucket": bucket, "rows": n}
        traces = [r.trace for r, _f in group if r.trace]
        with self._phase("pack", batch_args):
            x = self._stage(served, bucket, [r.x for r, _f in group])
        try:
            with self._phase("device_put", {"bucket": bucket}):
                for tn in self._tenant_names(served):
                    self._tenancy.ensure_resident(tn)
                runner = self._bucket_runner(served, bucket)
                zero = runner.zero_slates()
                # stack per-session device rows (zero rows for
                # fresh/reset streams and padding) into the batched
                # slate pytree
                slates = {
                    k: jnp.stack([
                        group[i][1].entry.state[k]
                        if i < n and group[i][1].entry.state is not None
                        else zero[k][i]
                        for i in range(bucket)])
                    for k in zero}
                xd = jax.device_put(x, data_sharding(self._mesh, x.ndim))
            with tracer.timed(
                    "device", cat="serve",
                    args={**batch_args, "mode": mode,
                          "sessions": [r.session for r, _f in group],
                          **({"traces": traces} if traces else {})},
                    ) as sp_dev:
                if mode == "detect":
                    new_slates, out = runner.update(slates,
                                                    runner.detect(xd))
                else:
                    new_slates, out = runner.advance(slates)
                host = jax.device_get(out)  # ONE host sync for the batch
        except Exception as e:  # device/compile failure: fail the group
            for r, _f in group:
                self._fail_request(r, e)
            return
        with self._phase("resolve", {"rows": n}):
            self._resolve_stateful_group(
                served, store, group, mode, host, new_slates, bucket,
                t_dispatch, sp_dev.dur)

    def _resolve_stateful_group(self, served, store, group, mode: str,
                                host, new_slates, bucket: int,
                                t_dispatch: float, t_dev: float) -> None:
        """The `resolve` phase of a stateful group: counters, then per
        frame the state commit, the answer and the admission release."""
        n = len(group)
        self.telemetry.record_batch(bucket=bucket, rows=n, device_s=t_dev)
        self._admission.observe_batch(t_dev, n)
        tracer = get_tracer()
        now = time.perf_counter()
        for i, (r, f) in enumerate(group):
            # commit state FIRST: the stream's lineage advances even if
            # this answer expired — the client's retry then dedupes as
            # an idempotent duplicate instead of forking the stream
            row = {k: new_slates[k][i] for k in new_slates}
            store.commit(r.session, r.seq, row)
            if r.deadline is not None and now > r.deadline:
                # deadline honesty mid-batch (same rule as pipelines):
                # never a late answer
                try:
                    r.future.set_exception(TimeoutError(
                        f"deadline expired mid-batch after "
                        f"{now - r.t_submit:.3f}s"))
                except InvalidStateError:
                    continue
                self.telemetry.record_timeout()
                self._admission.release(r.model)
                continue
            t_pp = time.perf_counter()
            try:
                result = served.postprocess(host, i)
                # deterministic merge: identical across restore paths —
                # the chaos drill's twin-run equality leans on this
                result["session"] = r.session
                result["seq"] = r.seq
                result["detected"] = mode == "detect"
                result["state_reset"] = bool(f.reset)
            except Exception as e:
                self._fail_request(r, e)
                continue
            try:
                r.future.set_result(result)
            except InvalidStateError:
                pass
            else:
                self.telemetry.record_request(
                    queue_wait_s=t_dispatch - r.t_submit,
                    e2e_s=now - r.t_submit)
                self._admission.release(r.model)
            if r.trace and tracer.active:
                # session id on the span: per-session flows assemble in
                # the merged Perfetto timeline
                tracer.record_span(
                    "replica_queue", r.t_submit, t_dispatch, cat="serve",
                    args={"trace": r.trace, "model": served.name,
                          "session": r.session})
                tracer.record_span(
                    "postprocess", t_pp, time.perf_counter(), cat="serve",
                    args={"trace": r.trace, "session": r.session})

    def _resolve_dropped(self, r: _Request) -> None:
        self._fail_request(r, RuntimeError("engine closed"))

    # -- lifecycle -------------------------------------------------------
    def close(self, timeout: float = 10.0, *,
              abandon_sessions: bool = False) -> None:
        """Stop the dispatcher and join its thread; pending futures fail
        with RuntimeError('engine closed'). Idempotent.

        Stateful stores flush a final snapshot per dirty session on a
        graceful close; ``abandon_sessions=True`` drops device state
        WITHOUT flushing — crash semantics for in-process replica
        kills, so recovery genuinely runs off the cadence snapshots."""
        if self._stop.is_set():
            return
        self._stop.set()
        self._paused.clear()
        self._q.put(_WAKE)
        self._thread.join(timeout)
        stores = {id(s): s for s in self._session_stores().values()}
        for s in stores.values():
            if abandon_sessions:
                s.abandon()
            else:
                s.flush()

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
