#!/usr/bin/env python
"""Serving CLI — the batched inference engine behind two zero-dep
surfaces (``deepvision_tpu/serve/``):

    # stdin-JSONL (default): one JSON request per line, responses to stdout
    serve.py -m lenet5=runs/lenet5
    {"id": 1, "model": "lenet5", "input": [[...32x32x1 floats...]]}
    -> {"id": 1, "model": "lenet5", "result": {...}, "ms": 4.2}

    # HTTP (stdlib http.server, no new deps)
    serve.py --http 8080 -m resnet50=runs/resnet50 -m yolov3=runs/yolov3
    POST /v1/predict   {"model": "resnet50", "input": [[...]]}  -> result
    GET  /stats        engine telemetry + cache + queue snapshot (JSON)
    GET  /metrics      Prometheus text exposition from the obs registry
                       (serve_* counters/quantiles + mem_* gauges)
    GET  /healthz      "ok" once warmup completed

    # serve a StableHLO artifact from predict.py export
    serve.py --artifact lenet5=lenet5.stablehlo

    # token generation (a hybrid token model, greedy): stdin-JSONL
    serve.py -m olmo_hybrid_tiny --slots 4 --max-len 3072
    {"id": 1, "tokens": [5, 17, 3], "max_new_tokens": 8}
    -> {"id": 1, "result": {"tokens": [...]}, "ms": 41.0}

    # serving FLEET: router front tier over N child-process replicas
    # (health-gated balancing, failover, circuit breaker, autoscaling)
    serve.py --fleet 2 -m lenet5 --http 8080
    serve.py --fleet 2 --fleet-max 4 --slo lenet5=0.5 -m lenet5

``-m name[=workdir]`` is repeatable (multi-model host); every model's
(bucket) executables compile at startup, so the first request is as
fast as the thousandth. Saturation returns 429/shed responses with a
``retry_after`` hint instead of unbounded queueing.

In ``--fleet N`` mode this process never touches jax: it spawns N
copies of itself (``serve.py --http 0 --port-file ...``) as replicas
and routes over them (``deepvision_tpu/serve/router.py``). ``--faults``
then arms the ROUTER's chaos sites (``replica_kill`` / ``replica_slow``
— a scheduled kill is a real SIGKILL), and the exit path prints the
grep-stable ``[router] failovers=N ...`` line the router smoke gate
asserts.
"""

from __future__ import annotations

import argparse
import json
import sys
from concurrent.futures import TimeoutError as _FutureTimeout
from pathlib import Path

import numpy as np

from deepvision_tpu.serve.admission import ShedError


def _parse_spec(spec: str) -> tuple[str, str | None]:
    name, _, workdir = spec.partition("=")
    return name, (workdir or None)


def _parse_tenant_map(specs, *, flag: str, cast):
    """``NAME=VALUE`` repeatable flags -> dict (tenant isolation maps:
    ``--tenant-quota lenet5=8``, ``--slo-class lenet5=gold``)."""
    out = {}
    for spec in specs or []:
        name, sep, val = spec.partition("=")
        if not sep or not name:
            sys.exit(f"bad {flag} spec {spec!r}; want NAME=VALUE")
        try:
            out[name] = cast(val)
        except ValueError as e:
            sys.exit(f"bad {flag} spec {spec!r}: {e}")
    return out or None


def build_engine(args):
    from deepvision_tpu.serve import InferenceEngine, from_stablehlo
    from deepvision_tpu.serve.models import load_served

    import contextlib

    models = []
    # restore chatter ("restored epoch N" / "no checkpoint found") goes
    # to stderr: stdout is the JSONL response stream in --stdin mode
    with contextlib.redirect_stdout(sys.stderr):
        for spec in args.model or []:
            name, workdir = _parse_spec(spec)
            models.append(load_served(
                name, workdir, num_classes=args.num_classes,
                top_k=args.top, score_thresh=args.score))
        for spec in args.artifact or []:
            name, path = _parse_spec(spec)
            if path is None:
                name, path = None, name
            models.append(from_stablehlo(path, name=name,
                                         top_k=args.top))
    if not models and not getattr(args, "track", None):
        sys.exit("no models: pass -m NAME[=WORKDIR], --artifact, "
                 "or --track")
    buckets = tuple(int(b) for b in args.buckets.split(","))
    mesh, buckets = _serving_mesh(buckets)
    pipelines = []
    if getattr(args, "pipelines", None):
        from deepvision_tpu.serve.pipeline import (
            Pipeline,
            load_pipeline_specs,
        )

        by_name = {m.name: m for m in models}
        for path in args.pipelines:
            for spec in load_pipeline_specs(path):
                # validates structure + every DAG edge's avals here,
                # before any compile — a bad spec kills startup, not a
                # request
                pipelines.append(Pipeline(spec, by_name))
    injector = None
    if args.faults:
        from deepvision_tpu.resilience import FaultInjector

        injector = FaultInjector(args.faults, seed=args.fault_seed)
        print(f"fault injection armed: {args.faults!r}", file=sys.stderr)
    if getattr(args, "track", None):
        # stateful tracking stream: --track MODEL[:K] serves a
        # TrackingPipeline named "track" over detect-model MODEL
        # ("synth" builds the weight-free synthetic detector). Session
        # state is device-resident per stream; crash-safe snapshots
        # land under --session-dir so a respawned/surviving replica
        # restores migrated streams.
        import tempfile

        from deepvision_tpu.serve.sessions import (
            SessionStore,
            TrackingPipeline,
            synthetic_detector,
        )

        det_name, _, k = args.track.partition(":")
        by_name = {m.name: m for m in models}
        if det_name in by_name:
            det = by_name[det_name]
        elif det_name == "synth":
            det = synthetic_detector()
            models.append(det)
        else:
            sys.exit(f"--track {args.track!r}: no model named "
                     f"{det_name!r} (pass -m, or use 'synth')")
        sdir = args.session_dir or tempfile.mkdtemp(
            prefix="dvtpu-sessions-")
        print(f"session snapshots -> {sdir} "
              f"(cadence {args.snapshot_every} frames)", file=sys.stderr)
        store = SessionStore(
            capacity=args.session_capacity, ttl_s=args.session_ttl_s,
            snapshot_dir=sdir, snapshot_every=args.snapshot_every,
            injector=injector)
        models.append(TrackingPipeline(
            "track", det, store,
            detect_every=int(k) if k else 4))
    print(f"serving {[m.name for m in models]}"
          f"{' pipelines ' + str([p.name for p in pipelines]) if pipelines else ''}"
          f" buckets={buckets} on {mesh.devices.size} device(s); "
          "compiling...", file=sys.stderr)
    engine = InferenceEngine(
        models, mesh=mesh, buckets=buckets, max_queue=args.max_queue,
        per_model_limit=args.per_model_limit,
        batch_window_s=args.batch_window_ms / 1e3,
        fault_injector=injector,
        pipelines=pipelines,
        # pipelines warm end-to-end, so the cache can be FROZEN: any
        # later miss (a hidden request-time compile) raises instead of
        # silently costing tail latency
        freeze_cache=bool(pipelines),
        store=getattr(args, "store", None),
        residency_bytes=(int(args.residency_mb * 1024 * 1024)
                         if getattr(args, "residency_mb", None)
                         else None),
        tenant_quota=_parse_tenant_map(
            getattr(args, "tenant_quota", None),
            flag="--tenant-quota", cast=int),
        slo_class=_parse_tenant_map(
            getattr(args, "slo_class", None),
            flag="--slo-class", cast=str),
    )
    stats = engine.stats()
    from_store = stats.get("warmed_from_store") or []
    print(f"warmup done in {engine.warmup_s}s "
          f"({stats['cache']['entries']} executables"
          + (f", {len(from_store)} from store" if from_store else "")
          + ")",
          file=sys.stderr)
    return engine


def _serving_mesh(buckets: tuple[int, ...]):
    """-> (mesh, ladder) with all devices on the data axis.

    Batches shard over the data axis, so every bucket must divide by
    the device count — on a multi-chip host the requested ladder is
    ADAPTED rather than the mesh degraded: buckets below the device
    count are raised to it, indivisible ones are rounded up to the
    next multiple (the default 1/4/16/64 on 8 chips becomes 8/16/64).
    Only a ladder that cannot be adapted (no devices?) falls back to a
    single-device mesh."""
    import jax

    from deepvision_tpu.core.mesh import create_mesh

    n = len(jax.devices())
    if n > 1:
        adapted = tuple(sorted({((b + n - 1) // n) * n for b in buckets}))
        if adapted != buckets:
            print(f"ladder {buckets} adapted to {adapted} for the "
                  f"{n}-device data axis", file=sys.stderr)
        return create_mesh(n, 1), adapted
    return create_mesh(1, 1), buckets


def build_fleet(args):
    """Router front tier over ``args.fleet`` child-process replicas —
    no jax in this process; each replica is this same CLI in
    single-engine HTTP mode on an ephemeral port."""
    from deepvision_tpu.serve.replica import (
        process_replica_factory,
        replica_argv,
    )
    from deepvision_tpu.serve.router import AutoscaleConfig, FleetRouter
    from deepvision_tpu.startup import probe_devices

    if not (args.model or args.artifact or args.track):
        sys.exit("no models: pass -m NAME[=WORKDIR], --artifact, "
                 "or --track")
    session_dir = None
    if args.track:
        # replicas must SHARE the snapshot dir: on a replica death the
        # router re-pins orphaned streams to a survivor, which restores
        # each stream's slate from the newest snapshot the dead replica
        # wrote here
        import tempfile

        session_dir = args.session_dir or tempfile.mkdtemp(
            prefix="dvtpu-sessions-")
        print(f"session snapshots (shared across replicas) -> "
              f"{session_dir}", file=sys.stderr)
    child_argv = replica_argv(
        args.model or [], artifact_specs=args.artifact or [],
        buckets=args.buckets,
        # shared AOT store: replica #1 traces and populates it, every
        # later (re)spawn warms from disk — the respawn compile storm
        # PR 6 measured is paid once per fleet, not once per process
        store=args.store,
        extra=(["--num-classes", str(args.num_classes)]
               if args.num_classes is not None else [])
        + ["--top", str(args.top), "--score", str(args.score),
           "--max-queue", str(args.max_queue),
           "--batch-window-ms", str(args.batch_window_ms),
           "--timeout-s", str(args.timeout_s)]
        + [a for spec in (args.tenant_quota or [])
           for a in ("--tenant-quota", spec)]
        + [a for spec in (args.slo_class or [])
           for a in ("--slo-class", spec)]
        + (["--residency-mb", str(args.residency_mb)]
           if args.residency_mb else [])
        + [a for path in (args.pipelines or [])
           for a in ("--pipelines", path)]
        + (["--track", args.track, "--session-dir", session_dir,
            "--session-capacity", str(args.session_capacity),
            "--session-ttl-s", str(args.session_ttl_s),
            "--snapshot-every", str(args.snapshot_every)]
           if args.track else [])
        + (["--trace-spool", args.trace_spool]
           if args.trace_spool else []))

    fleet_max = args.fleet_max or args.fleet
    # what the replicas will run on, asked in a child that has exited
    # before the first replica starts (this process stays off jax). On
    # a TPU host each replica gets a chip of its own, and a fleet larger
    # than the host's chips is refused here — a second process on a
    # held chip would otherwise wait out its startup timeout.
    try:
        devices = probe_devices()
        # each replica spools/dumps under its slot id, so the merged
        # fleet trace names its pid rows r1/r2/...
        factory = process_replica_factory(
            lambda sid: child_argv + ["--obs-role", sid],
            replicas=fleet_max, devices=devices)
    except (RuntimeError, ValueError) as e:
        sys.exit(f"--fleet {args.fleet}"
                 + (f" --fleet-max {args.fleet_max}"
                    if args.fleet_max else "") + f": {e}")
    print(f"replicas run on {devices['count']} x {devices['kind']} "
          f"({devices['platform']})", file=sys.stderr)

    injector = None
    if args.faults:
        from deepvision_tpu.resilience import FaultInjector

        injector = FaultInjector(args.faults, seed=args.fault_seed)
        print(f"fault injection armed (router sites): {args.faults!r}",
              file=sys.stderr)
    slo = {}
    for spec in args.slo or []:
        name, _, sec = spec.partition("=")
        try:
            slo[name] = float(sec)
        except ValueError:
            sys.exit(f"bad --slo spec {spec!r}; want NAME=SECONDS")
    autoscale = None
    if fleet_max > args.fleet:
        autoscale = AutoscaleConfig(min_replicas=args.fleet,
                                    max_replicas=fleet_max)
    models = [(_parse_spec(s)[0]) for s in args.model or []]
    if args.pipelines:
        # pipeline NAMES are routable like models; spec parsing is pure
        # json (the router process never imports jax — each replica
        # builds/validates/warms its own DAGs)
        from deepvision_tpu.serve.pipeline import load_pipeline_specs

        models += [spec.name for path in args.pipelines
                   for spec in load_pipeline_specs(path)]
    if args.track:
        # the tracking pipeline (and, for "synth", its generated
        # detector) are routable names each replica builds itself
        models.append("track")
        det_name = args.track.partition(":")[0]
        if det_name not in models:
            models.append(det_name)
    print(f"starting fleet of {args.fleet} replica(s) "
          f"({models or args.artifact}); replicas compile in "
          "parallel...", file=sys.stderr)
    router = FleetRouter(
        factory, replicas=args.fleet, models=models, slo=slo or None,
        default_deadline_s=args.timeout_s, max_queue=args.max_queue,
        per_model_limit=args.per_model_limit, autoscale=autoscale,
        hedge_after_s=args.hedge_after, fault_injector=injector,
        session_replay_window=args.session_replay_window,
        # tenant isolation at the FLEET front door too: a noisy tenant
        # sheds here before it can crowd any replica's queue
        tenant_quota=_parse_tenant_map(
            args.tenant_quota, flag="--tenant-quota", cast=int),
        slo_class=_parse_tenant_map(
            args.slo_class, flag="--slo-class", cast=str),
    )
    print(f"fleet up: {router.health()}", file=sys.stderr)
    return router


def _setup_obs(args, role: str):
    """Wire this process's distributed-observability surfaces: label
    the tracer, attach a span spool when ``--trace-spool`` (or the
    ``DVTPU_TRACE_SPOOL`` env a parent exported) names a directory,
    and install the always-on flight recorder with a dump-on-SIGTERM
    handler — so a drained/preempted replica leaves its black box next
    to its spool. Returns the spool (or None)."""
    import os
    import signal

    from deepvision_tpu.obs.distributed import (
        ENV_SPOOL,
        SpanSpool,
        enable_spool_from_env,
        flight_dump,
        install_flight_recorder,
    )
    from deepvision_tpu.obs.trace import get_tracer

    get_tracer().set_labels(role=role)
    if args.trace_spool:
        spool = SpanSpool(args.trace_spool, role=role)
    else:
        spool = enable_spool_from_env(role=role)
    obs_dir = args.trace_spool or os.environ.get(ENV_SPOOL)
    install_flight_recorder(obs_dir, meta={"role": role})

    def _on_sigterm(sig, frame):
        # black box first, then a GRACEFUL exit: SystemExit propagates
        # out of serve_forever/stdin so the finally blocks run — the
        # engine/router closes, child replicas are stopped (a fleet
        # parent dying abruptly would orphan them), spools flush
        flight_dump(f"signal-{sig}")
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, _on_sigterm)
    return spool


def _jsonable(obj):
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


# ---------------------------------------------------------- stdin-JSONL


def run_stdin(engine, args, stdin=None, stdout=None):
    """One JSON request per line; responses (in submission order) to
    stdout. Requests keep flowing while earlier ones execute, so the
    dispatcher sees real micro-batches even from a pipe.

    Control lines ride the same stream: ``{"control": "swap",
    "model": NAME, "perturb": F | "workdir": DIR}`` hot-swaps a
    tenant's weights on a background thread while data lines keep
    flowing — the swap-smoke drill's zero-drop evidence. Control
    lines produce stderr chatter only (stdout stays a pure
    data-response stream); the ``[tenancy]`` exit line carries the
    swap count."""
    import contextlib
    import threading
    import time

    stdin = stdin if stdin is not None else sys.stdin
    stdout = stdout if stdout is not None else sys.stdout

    pending: list[tuple[object, object, float]] = []  # (id, future, t0)
    control_threads: list[threading.Thread] = []

    def start_control(req: dict) -> None:
        if req.get("control") != "swap":
            print(f"[tenancy] unknown control {req.get('control')!r}",
                  file=sys.stderr, flush=True)
            return
        hot_swap = getattr(engine, "hot_swap", None)
        if hot_swap is None:
            print("[tenancy] swap control needs a single-engine host "
                  "(fleet routers don't own weights)",
                  file=sys.stderr, flush=True)
            return

        def _do_swap():
            kw = {k: req[k] for k in ("workdir", "perturb")
                  if k in req}
            try:
                # checkpoint-restore chatter must not pollute the
                # stdout data stream
                with contextlib.redirect_stdout(sys.stderr):
                    hot_swap(req["model"], **kw)
            except Exception as e:
                print(f"[tenancy] swap {req.get('model')!r} failed: "
                      f"{type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)

        t = threading.Thread(target=_do_swap, daemon=True,
                             name="tenancy-swap")
        t.start()
        control_threads.append(t)

    def emit(rid, fut, t0):
        try:
            result = fut.result(timeout=args.timeout_s + 1.0)
            line = {"id": rid, "result": _jsonable(result),
                    "ms": round((time.perf_counter() - t0) * 1e3, 2)}
        except ShedError as e:
            # async sheds (the router's circuit-open / all-replicas-
            # draining path) carry the same retry hint a synchronous
            # admission shed does
            line = {"id": rid, "error": str(e),
                    "retry_after": e.retry_after_s}
        except Exception as e:
            line = {"id": rid, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(line), file=stdout, flush=True)

    for raw in stdin:
        raw = raw.strip()
        if not raw:
            continue
        try:
            req = json.loads(raw)
            if not isinstance(req, dict):
                raise ValueError("request must be a JSON object")
            if "control" in req:
                start_control(req)
                continue
            x = np.asarray(req["input"], np.float32)
            # stateful streams: session (stream id) + seq (frame no.)
            seq = req.get("seq")
            seq = int(seq) if seq is not None else None
        except (ValueError, KeyError, TypeError) as e:
            print(json.dumps({"error": f"bad request: {e}"}),
                  file=stdout, flush=True)
            continue
        rid = req.get("id")
        t0 = time.perf_counter()
        try:
            # a pipeline is addressed like a model ({"pipeline": name}
            # is sugar for {"model": name}) — same queue, same engine
            fut = engine.submit(x, model=(req.get("model")
                                          or req.get("pipeline")),
                                timeout_s=args.timeout_s,
                                trace=req.get("trace"),
                                session=req.get("session"), seq=seq)
        except ShedError as e:
            print(json.dumps({"id": rid, "error": str(e),
                              "retry_after": e.retry_after_s}),
                  file=stdout, flush=True)
            continue
        except (ValueError, RuntimeError) as e:
            print(json.dumps({"id": rid, "error": str(e)}),
                  file=stdout, flush=True)
            continue
        pending.append((rid, fut, t0))
        # bounded in-flight window: keep ~2 ladders' worth queued so
        # batching happens, without unbounded memory on long streams
        while len(pending) > 2 * max(engine.buckets):
            emit(*pending.pop(0))
    for item in pending:
        emit(*item)
    for t in control_threads:
        # a swap started near EOF still completes (and is counted in
        # the [tenancy] exit line) before the engine closes
        t.join(timeout=args.timeout_s)


def run_generate(gen, args, stdin=None, stdout=None):
    """Token generation over stdin-JSONL: one ``{"id", "tokens": [...],
    "max_new_tokens": n}`` a line, responses ``{"id", "result":
    {"tokens": [...]}, "ms"}`` in submission order. Requests keep flowing
    while earlier ones generate, so the scheduler holds several at once."""
    import time

    stdin = stdin if stdin is not None else sys.stdin
    stdout = stdout if stdout is not None else sys.stdout
    pending: list = []      # (id, future, t0)

    def emit(rid, fut, t0):
        try:
            result = fut.result(timeout=args.timeout_s + 1.0)
            line = {"id": rid, "result": _jsonable(result),
                    "ms": round((time.perf_counter() - t0) * 1e3, 2)}
        except Exception as e:
            line = {"id": rid, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(line), file=stdout, flush=True)

    for raw in stdin:
        raw = raw.strip()
        if not raw:
            continue
        try:
            req = json.loads(raw)
            tokens = np.asarray(req["tokens"], np.int64)
            new = int(req.get("max_new_tokens", 16))
        except (ValueError, KeyError, TypeError) as e:
            print(json.dumps({"error": f"bad request: {e}"}), file=stdout,
                  flush=True)
            continue
        rid, t0 = req.get("id"), time.perf_counter()
        try:
            fut = gen.submit(tokens, new, timeout_s=args.timeout_s)
        except ShedError as e:
            print(json.dumps({"id": rid, "error": str(e),
                              "retry_after": e.retry_after_s}),
                  file=stdout, flush=True)
            continue
        except ValueError as e:
            print(json.dumps({"id": rid, "error": str(e)}), file=stdout,
                  flush=True)
            continue
        pending.append((rid, fut, t0))
        while len(pending) > 2 * gen.cache.slots:
            emit(*pending.pop(0))
    for item in pending:
        emit(*item)


def serve_generator(args) -> None:
    """``-m <token model>``: the one model of this process, served by the
    slot scheduler (``serve/generator.py``) over stdin-JSONL."""
    from deepvision_tpu.serve.generator import load_generator
    from deepvision_tpu.startup import mark_ready

    if len(args.model) > 1 or args.artifact or args.pipelines \
            or args.http is not None:
        sys.exit("a token model is served alone, over stdin-JSONL "
                 "(no --http, --artifact or --pipelines beside it)")
    name, workdir = _parse_spec(args.model[0])
    gen = load_generator(name, workdir, slots=args.slots,
                         max_len=args.max_len,
                         prefill_chunk=args.prefill_chunk,
                         max_queue=args.max_queue)
    mark_ready()
    try:
        run_generate(gen, args)
    finally:
        gen.close()
        s = gen.stats()
        # grep-stable exit line
        print(f"[generate] prefill_tokens={s['gen_prefill_tokens']} "
              f"decode_tokens={s['gen_decode_tokens']} "
              f"decode_steps={s['gen_decode_steps']} "
              f"decode_rows={s['gen_decode_rows']}",
              file=sys.stderr, flush=True)


# ----------------------------------------------------------------- HTTP


def make_handler(engine, args):
    """BaseHTTPRequestHandler subclass bound to ``engine`` — factored
    out of :func:`run_http` so tests can mount it on an ephemeral-port
    server."""
    import http.server

    from deepvision_tpu.serve import ShedError

    # static after build_engine: resolved once so the (load-balancer-
    # hammered) /healthz probe never pays a full stats() snapshot
    models = engine.stats()["models"]

    from deepvision_tpu.obs.distributed import TRACE_HEADER

    class Handler(http.server.BaseHTTPRequestHandler):
        # HTTP/1.1: keep-alive connections, so a router/load-balancer
        # client pays connection setup (and this server a handler
        # thread) once per CLIENT, not once per request — every
        # response path below sets Content-Length, which 1.1 requires
        protocol_version = "HTTP/1.1"

        # quiet per-request logging; telemetry is the observability
        def log_message(self, *a):
            pass

        def _send(self, code: int, payload: dict,
                  headers: dict | None = None):
            self._send_text(code, json.dumps(payload),
                            "application/json", headers)

        def _send_text(self, code: int, body: str, content_type: str,
                       headers: dict | None = None) -> None:
            data = body.encode()
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(data)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path == "/healthz":
                # degraded (503) while the dispatcher supervisor sits in
                # a post-crash backoff: load balancers should drain this
                # replica, not route fresh traffic into the restart.
                # The 503 carries Retry-After (rest of the backoff
                # window) so balancers re-probe on schedule — the same
                # hint contract the 429 shed path has always had.
                h = engine.health()
                h["models"] = models
                if h["status"] == "ok":
                    self._send(200, h)
                else:
                    import math

                    ra = max(1, math.ceil(h.get("retry_after_s", 1.0)))
                    self._send(503, h, {"Retry-After": str(ra)})
            elif self.path == "/stats":
                # /stats reads through the obs-backed telemetry
                # snapshot: every histogram's (count, total, samples)
                # triple is read under the metric's own lock, so a
                # scrape landing mid-record can never see a torn
                # count/total pair — the pre-obs snapshot only got that
                # guarantee via the engine lock the handler didn't hold
                self._send(200, engine.stats())
            elif self.path == "/metrics":
                # a fleet router renders the FEDERATED surface (its own
                # router_* families + every replica's serve_* families
                # with {replica=...} labels and exact counter sums); a
                # single engine renders the process registry as before
                render = getattr(engine, "render_metrics", None)
                self._send_text(200,
                                render() if render is not None
                                else _render_metrics(),
                                "text/plain; version=0.0.4; "
                                "charset=utf-8")
            elif self.path == "/metrics.json":
                # the typed registry dump (histogram reservoirs
                # included): what a fleet router scrapes from each
                # replica to federate exactly instead of re-parsing
                # lossy quantile text
                from deepvision_tpu.obs.metrics import default_registry

                self._send(200, default_registry().dump())
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            # POST /v1/pipeline/<name> addresses a served DAG by URL;
            # the engine serves pipelines through the same submit path
            # as models, so past this point the request is ordinary
            pipeline = None
            if self.path == "/v1/swap":
                self._do_swap()
                return
            if self.path.startswith("/v1/pipeline/"):
                pipeline = self.path[len("/v1/pipeline/"):]
                if not pipeline:
                    self._send(404, {"error": "not found"})
                    return
            elif self.path not in ("/v1/predict", "/predict"):
                self._send(404, {"error": "not found"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                if not isinstance(req, dict):
                    raise ValueError("request must be a JSON object")
                x = _decode_input(req)
                # stateful streams (the fleet router forwards these on
                # its replica hop): session = stream id, seq = frame
                session = req.get("session")
                seq = req.get("seq")
                seq = int(seq) if seq is not None else None
                # per-request deadline (the fleet router forwards its
                # remaining budget here); the CLI blanket is a CEILING
                timeout_s = args.timeout_s
                if "timeout_s" in req:
                    timeout_s = min(float(req["timeout_s"]),
                                    args.timeout_s)
                    if timeout_s <= 0:
                        raise ValueError(
                            f"timeout_s must be > 0, got {timeout_s}")
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"bad request: {e}"})
                return
            # distributed trace id: the router hop carries it as the
            # X-DVTPU-Trace header (the JSONL surface as a "trace"
            # field) — the engine stamps its queue/device/postprocess
            # spans with it so the merged fleet trace links this
            # request across processes
            trace = self.headers.get(TRACE_HEADER) or req.get("trace")
            try:
                fut = engine.submit(
                    x,
                    model=(pipeline or req.get("model")
                           or req.get("pipeline")),
                    timeout_s=timeout_s, trace=trace,
                    session=session, seq=seq)
                result = fut.result(timeout=timeout_s + 1.0)
            except ShedError as e:
                self._send(429, {"error": str(e),
                                 "retry_after": e.retry_after_s},
                           {"Retry-After": str(e.retry_after_s)})
                return
            # concurrent.futures.TimeoutError (the result-wait timeout)
            # only aliases builtin TimeoutError from Python 3.11; catch
            # both so a 3.10 wait-expiry is a 504, not a crashed handler
            except (TimeoutError, _FutureTimeout) as e:
                self._send(504, {"error": f"deadline expired: {e}"})
                return
            except ValueError as e:
                self._send(400, {"error": str(e)})
                return
            except RuntimeError as e:
                # server-side failure (dispatcher crash, engine closed,
                # exhausted fleet failover): 500, NOT 400 — a 400 tells
                # clients (and the fleet router, which maps it to a
                # non-retryable client error) never to retry, burying
                # exactly the fault class failover exists to absorb
                self._send(500, {"error": str(e)})
                return
            self._send(200, {"result": _jsonable(result)})

        def _do_swap(self):
            """POST /v1/swap {"model": NAME, "perturb": F |
            "workdir": DIR}: zero-drop weight hot-swap. Synchronous —
            the 200 means the new ladder is compiled, installed, and
            flipped; in-flight requests drained on the old weights.
            Other handler threads keep serving throughout (the flip
            happens between dispatcher batches, not here)."""
            hot_swap = getattr(engine, "hot_swap", None)
            if hot_swap is None:
                self._send(404, {"error": "swap needs a single-engine "
                                 "replica (fleet routers don't own "
                                 "weights)"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                if not isinstance(req, dict) or "model" not in req:
                    raise ValueError("need a JSON object with 'model'")
                kw = {k: req[k] for k in ("workdir", "perturb")
                      if k in req}
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"bad request: {e}"})
                return
            import contextlib

            try:
                with contextlib.redirect_stdout(sys.stderr):
                    result = hot_swap(req["model"], **kw)
            except ValueError as e:
                self._send(400, {"error": str(e)})
                return
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            self._send(200, {"result": result})

    return Handler


def _decode_input(req: dict) -> np.ndarray:
    """Request payload -> input array. Two wire formats:

    - ``"input"``: nested JSON float lists (human-typable, the
      original format);
    - ``"input_b64"`` + ``"shape"`` [+ ``"dtype"``, default float32]:
      base64 of the raw little-endian array bytes. ~20x cheaper to
      encode/decode than float lists on both ends — the format the
      fleet router uses, where per-request JSON cost is fleet-wide
      routing capacity.
    """
    if "input_b64" in req:
        import base64

        dtype = np.dtype(req.get("dtype", "float32"))
        raw = base64.b64decode(req["input_b64"], validate=True)
        x = np.frombuffer(raw, dtype=dtype).reshape(req["shape"])
        return np.ascontiguousarray(x, np.float32)
    return np.asarray(req["input"], np.float32)


def _render_metrics() -> str:
    """Prometheus text for GET /metrics: the process obs registry
    (serve_* counters + latency quantiles, plus whatever else this
    process registered), with the mem_* device gauges refreshed per
    scrape (one memory_stats() read per device; no-op on CPU)."""
    from deepvision_tpu.obs.metrics import default_registry
    from deepvision_tpu.obs.profiler import sample_memory_gauges

    sample_memory_gauges()
    return default_registry().render_prometheus()


def _make_server(addr, handler):
    """ThreadingHTTPServer tuned for fleet traffic: a deep accept
    backlog (the default 5 drops SYNs under a router's connection
    burst — each drop is a 1-3s TCP retransmit stall that reads as a
    'slow replica'), and daemon handler threads so shutdown never
    hangs on an idle keep-alive connection."""
    import http.server

    srv = http.server.ThreadingHTTPServer(addr, handler,
                                          bind_and_activate=False)
    srv.request_queue_size = 128
    srv.daemon_threads = True
    srv.server_bind()
    srv.server_activate()
    return srv


def run_http(engine, args):
    server = _make_server(("", args.http), make_handler(engine, args))
    port = server.server_address[1]
    if getattr(args, "port_file", None):
        # atomic write: a fleet router polls this file to find the
        # ephemeral port (--http 0), and must never read a torn value
        import os
        import tempfile

        fd, tmp = tempfile.mkstemp(
            dir=str(Path(args.port_file).parent) or ".")
        with os.fdopen(fd, "w") as f:
            f.write(str(port))
        os.replace(tmp, args.port_file)
    print(f"listening on :{port} "
          f"(POST /v1/predict, GET /stats, GET /metrics, GET /healthz)",
          file=sys.stderr)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-m", "--model", action="append",
                   help="NAME[=WORKDIR], repeatable (multi-model host)")
    p.add_argument("--artifact", action="append",
                   help="[NAME=]PATH to a StableHLO export, repeatable")
    p.add_argument("--pipelines", action="append", metavar="FILE",
                   help="JSON pipeline spec file (one spec, a list, or "
                        "{'pipelines': [...]}), repeatable; each DAG is "
                        "validated (acyclic, aval-compatible, ladder-"
                        "divisible) and warmed end-to-end at startup, "
                        "then served via {'pipeline': NAME} on the "
                        "JSONL surface or POST /v1/pipeline/NAME")
    p.add_argument("--http", type=int, default=None,
                   help="HTTP port (default: stdin-JSONL mode); 0 binds "
                        "an ephemeral port (see --port-file)")
    p.add_argument("--port-file", default=None,
                   help="write the actually-bound HTTP port here "
                        "(atomic); how a fleet router finds its "
                        "ephemeral-port replicas")
    p.add_argument("--fleet", type=int, default=None,
                   help="run a ROUTER over this many child-process "
                        "replicas instead of one in-process engine")
    p.add_argument("--fleet-max", type=int, default=None,
                   help="autoscaler ceiling (default: --fleet, i.e. "
                        "autoscaling off); the metric-driven autoscaler "
                        "adds/drains replicas between --fleet and this")
    p.add_argument("--slo", action="append",
                   help="NAME=SECONDS per-model p95 deadline budget, "
                        "repeatable; feeds SLO-aware admission and the "
                        "default request deadline (fleet mode)")
    p.add_argument("--hedge-after", type=float, default=None,
                   help="fleet mode: launch a duplicate attempt on a "
                        "second replica when the primary hasn't "
                        "answered within this many seconds (first "
                        "response wins, exactly once); off by default "
                        "— hedging trades duplicate work for tail "
                        "latency")
    p.add_argument("--buckets", default="1,4,16,64",
                   help="batch bucket ladder (comma-separated)")
    p.add_argument("--max-queue", type=int, default=256)
    p.add_argument("--slots", type=int, default=4,
                   help="token models: requests held at once, one cache "
                        "slot each")
    p.add_argument("--max-len", type=int, default=3072,
                   help="token models: positions a slot holds (prompt and "
                        "generated tokens)")
    p.add_argument("--prefill-chunk", type=int, default=512,
                   help="token models: prompt tokens a prefill step runs")
    p.add_argument("--per-model-limit", type=int, default=None)
    p.add_argument("--batch-window-ms", type=float, default=2.0,
                   help="wait this long for a bucket to fill before "
                        "running a padded partial batch")
    p.add_argument("--timeout-s", type=float, default=30.0,
                   help="per-request deadline")
    p.add_argument("--track", default=None, metavar="MODEL[:K]",
                   help="serve a stateful tracking-by-detection stream "
                        "named 'track' over detect-model MODEL "
                        "('synth' builds a weight-free synthetic "
                        "detector); the detector runs every K-th frame "
                        "(default 4), frames between run the compiled "
                        "advance program on the stream's device-"
                        "resident slate. Requests address it with "
                        "{'model': 'track', 'session': ID, 'seq': N}")
    p.add_argument("--session-dir", default=None, metavar="DIR",
                   help="crash-safe session snapshot directory "
                        "(default: auto tempdir; fleet mode shares one "
                        "dir across replicas so a migrated stream "
                        "restores on the survivor)")
    p.add_argument("--session-capacity", type=int, default=64,
                   help="max live sessions per engine; NEW sessions "
                        "are shed at submit when full — existing "
                        "pinned state is never dropped for a newcomer")
    p.add_argument("--session-ttl-s", type=float, default=300.0,
                   help="idle seconds before a session is evicted "
                        "(dirty state snapshots first)")
    p.add_argument("--snapshot-every", type=int, default=8,
                   help="incremental session snapshot cadence in "
                        "frames (bounds replay work after a crash)")
    p.add_argument("--session-replay-window", type=int, default=32,
                   help="fleet mode: frames the router buffers per "
                        "stream to replay the snapshot->present gap "
                        "after a failover; a gap wider than this "
                        "degrades to a DECLARED state_reset")
    p.add_argument("--store", default=None, metavar="DIR",
                   help="persistent AOT artifact store: warm "
                        "executables from this directory's verified "
                        "StableHLO blobs instead of re-tracing (cold "
                        "misses trace and populate it); fleet mode "
                        "shares the DIR across replicas so respawns "
                        "skip the compile storm")
    p.add_argument("--residency-mb", type=float, default=None,
                   help="HBM budget for resident tenant weights in "
                        "MiB: least-recently-served tenants beyond it "
                        "are evicted to host and re-materialized on "
                        "demand (default: everything stays resident)")
    p.add_argument("--tenant-quota", action="append", metavar="NAME=N",
                   help="per-tenant admission quota (max queued "
                        "requests), repeatable — a noisy tenant sheds "
                        "alone at its own cap")
    p.add_argument("--slo-class", action="append",
                   metavar="NAME=CLASS",
                   help="per-tenant SLO class (gold/standard/batch), "
                        "repeatable: under contention a tenant only "
                        "occupies its class's fraction of the queue "
                        "(1.0/0.8/0.5); alone it gets the whole host")
    p.add_argument("--num-classes", type=int, default=None)
    p.add_argument("--top", type=int, default=5)
    p.add_argument("--score", type=float, default=0.5)
    p.add_argument("--faults", default=None,
                   help="deterministic fault schedule for chaos drills "
                        "(resilience/faults.py grammar, e.g. "
                        "'crash@2' crashes the dispatcher on its 3rd "
                        "batch — the supervisor must recover)")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for probabilistic (~) fault specs")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the whole "
                        "serving session into this directory (started "
                        "before the model loads, so start-up and warmup "
                        "are in it; stopped at shutdown)")
    p.add_argument("--trace-spool", default=None, metavar="DIR",
                   help="distributed tracing: append every completed "
                        "span to a crash-safe per-process spool file "
                        "under DIR (fleet mode forwards it to every "
                        "replica); merge the fleet's spools into ONE "
                        "Perfetto trace with tools/trace_merge.py. "
                        "Flight-recorder dumps land in the same DIR")
    p.add_argument("--obs-role", default=None,
                   help="process label on spans/spools/dumps (fleet "
                        "mode sets each replica's slot id "
                        "automatically; default: router/replica by "
                        "mode)")
    args = p.parse_args(argv)

    if args.fleet is not None:
        # fleet mode: router over child processes, no jax in THIS
        # process (the replicas compile; the router only routes)
        spool = _setup_obs(args, args.obs_role or "router")
        router = build_fleet(args)
        try:
            if args.http is not None:
                run_http(router, args)
            else:
                run_stdin(router, args)
        finally:
            router.close()
            if spool is not None:
                spool.close()
            # grep-stable exit line: the router smoke gate asserts it
            print(router.summary_line(), file=sys.stderr, flush=True)
        return

    from deepvision_tpu.obs.profiler import profile_session
    from deepvision_tpu.startup import init_runtime, mark_ready

    init_runtime()
    from deepvision_tpu.serve.generator import is_generative

    if args.model and any(is_generative(_parse_spec(m)[0])
                          for m in args.model):
        serve_generator(args)
        return
    spool = _setup_obs(args, args.obs_role or "replica")
    # the profile starts before the model loads, so the start-up spans
    # (startup/load_model, startup/engine) land beside the warm-up's
    # device operations
    with profile_session(args.profile_dir):
        engine = build_engine(args)
        mark_ready()
        try:
            if args.http is not None:
                run_http(engine, args)
            else:
                run_stdin(engine, args)
        finally:
            engine.close()
            if spool is not None:
                spool.close()
            stats = engine.stats()
            # grep-stable exit line (chip_smoke.py reads it): executed rows
            # (rows + padded_rows) per batch say which buckets ran
            tel = stats["telemetry"]
            print(f"[serve] completed={tel['completed']} "
                  f"failed={tel['failed']} batches={tel['batches']} "
                  f"rows={tel['rows']} padded_rows={tel['padded_rows']}",
                  file=sys.stderr, flush=True)
            if stats.get("pipelines"):
                # grep-stable exit line: the pipeline smoke gate asserts
                # served counts and that the frozen cache saw zero
                # post-warm misses (no request paid a hidden compile)
                served = ",".join(f"{k}={v}" for k, v in
                                  sorted(stats["pipelines"].items()))
                cache = stats["cache"]
                print(f"[pipeline] served {served} "
                      f"frozen={cache['frozen']} misses={cache['misses']} "
                      f"hits={cache['hits']}", file=sys.stderr, flush=True)
            # grep-stable tenancy exit line: the swap smoke gate asserts
            # swaps=N on it (and zero dropped data responses upstream)
            print(engine.tenancy.summary_line(), file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
