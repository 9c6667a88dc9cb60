"""Serving telemetry: per-request latency histograms + counters.

The serving counterpart of ``data/prefetch.FeedTelemetry``: where the
feed telemetry attributes *training* feed wall time to pipeline stages,
:class:`ServeTelemetry` attributes *request* wall time to the serving
stages — queue wait (admitted → dispatched), pad overhead (the fraction
of each executed batch that was zero padding up to the bucket), device
time (the compiled forward), and end-to-end latency — and keeps the
admission/outcome counters (completed / timed out / shed) that say at a
glance whether the engine is keeping up with offered load.

Since the ``obs`` subsystem exists, the primitives live there: every
latency series is an :class:`obs.metrics.Histogram` (bounded reservoir,
exact lifetime count/total, p50/p95/p99 snapshots) and every counter an
:class:`obs.metrics.Counter`, all registered into the process registry
under ``serve_*`` names — so ``GET /metrics`` (Prometheus) and the one
merged ``obs`` snapshot render the same numbers ``/stats`` reports.
The ``/stats`` JSON shape is byte-compatible with the pre-obs
implementation, and torn reads are structurally impossible now: a
histogram's (count, total, samples) triple is read under its own lock
inside ``summary()``, so even a snapshot taken outside ``_lock`` (the
old ``/stats`` hazard) can never see a count/total pair mid-record.
"""

from __future__ import annotations

import threading

from deepvision_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
)

__all__ = ["LatencyStats", "ServeTelemetry", "RouterTelemetry"]


class LatencyStats:
    """Bounded-reservoir latency series with percentile snapshots —
    now a thin wrapper over :class:`obs.metrics.Histogram` (the summary
    dict is byte-compatible with the pre-obs shape).

    ``record`` takes seconds; ``summary`` reports milliseconds. The
    reservoir keeps the most recent ``maxlen`` samples (enough for
    stable p99 at serving rates) while ``count``/``total_s`` stay exact.
    """

    def __init__(self, maxlen: int = 8192,
                 hist: Histogram | None = None):
        self._hist = hist if hist is not None else Histogram(maxlen=maxlen)

    @property
    def hist(self) -> Histogram:
        return self._hist

    def record(self, seconds: float) -> None:
        self._hist.observe(seconds)

    @property
    def count(self) -> int:
        return self._hist.count

    @property
    def total_s(self) -> float:
        return self._hist.total

    def summary(self) -> dict:
        return self._hist.summary()


# exact counters, in the /stats JSON order (dict order is the contract)
_COUNTER_FIELDS = (
    "submitted",      # admitted into the queue
    "completed",      # futures resolved with a result
    "timed_out",      # deadline expired while queued
    "failed",         # postprocess/forward raised
    "shed",           # rejected at admission (backpressure)
    "batches",        # executed device batches
    "rows",           # real rows across executed batches
    "padded_rows",    # zero rows added to reach the bucket
    # dispatcher supervision (engine._supervise): a crash fails the
    # in-flight/queued futures and the loop restarts with backoff —
    # these counters are how /stats distinguishes a self-healed
    # engine from one that never faulted
    "dispatcher_crashes",
    "dispatcher_restarts",
)

# the engine's staging buffers (``InferenceEngine._stage``): buffers
# made and batches packed into a kept one. They come after the phase
# blocks in /stats, with the gauge ``stage_bytes`` (host bytes held)
_STAGE_FIELDS = ("stage_allocs", "stage_reuses")

# the dispatcher's cycle beside ``device_time``: one histogram a phase
# (``serve_<phase>_time``), fed by the engine's phase spans, so /stats
# and /metrics give the cycle's split with no profiler running
_PHASE_FIELDS = ("wait", "fill_window", "pack", "device_put", "resolve")


class ServeTelemetry:
    """Counters + per-stage histograms for one engine's lifetime.

    Registers everything into ``registry`` (default: the process
    registry) under ``serve_*`` names; a newer engine's telemetry
    replaces an older one's registrations (latest wins), so the
    Prometheus surface always reflects the live engine. ``_lock``
    still brackets multi-field records (e.g. ``record_batch`` touching
    batches+rows+padded_rows+device_time) so ``snapshot()`` reports
    coherent cross-counter derived values like ``pad_overhead_frac``.
    """

    def __init__(self, registry: Registry | None = None):
        reg = registry if registry is not None else default_registry()
        # kept public: a replica's metrics_dump() federates THIS
        # registry up to the fleet router (obs/distributed.py), so an
        # EngineReplica's private registry is scrapeable without HTTP
        self.registry = reg
        self._lock = threading.Lock()
        self._c = {f: reg.register(f"serve_{f}", Counter())
                   for f in _COUNTER_FIELDS + _STAGE_FIELDS}
        self.stage_bytes = reg.register("serve_stage_bytes", Gauge())
        self.queue_wait = LatencyStats(   # admitted -> batch dispatch
            hist=reg.register("serve_queue_wait", Histogram()))
        self.device_time = LatencyStats(  # compiled forward, per batch
            hist=reg.register("serve_device_time", Histogram()))
        self.e2e = LatencyStats(          # admitted -> future resolved
            hist=reg.register("serve_e2e_latency", Histogram()))
        self.phase_time = {               # per dispatcher phase
            f: LatencyStats(hist=reg.register(f"serve_{f}_time",
                                              Histogram()))
            for f in _PHASE_FIELDS}

    # -- recording (dispatcher + submit threads) -------------------------
    def record_submit(self) -> None:
        with self._lock:
            self._c["submitted"].inc()

    def record_shed(self) -> None:
        with self._lock:
            self._c["shed"].inc()

    def record_timeout(self) -> None:
        with self._lock:
            self._c["timed_out"].inc()

    def record_failure(self) -> None:
        with self._lock:
            self._c["failed"].inc()

    def record_dispatcher_crash(self) -> None:
        with self._lock:
            self._c["dispatcher_crashes"].inc()

    def record_dispatcher_restart(self) -> None:
        with self._lock:
            self._c["dispatcher_restarts"].inc()

    def record_batch(self, *, bucket: int, rows: int,
                     device_s: float) -> None:
        with self._lock:
            self._c["batches"].inc()
            self._c["rows"].inc(rows)
            self._c["padded_rows"].inc(bucket - rows)
            self.device_time.record(device_s)

    def record_stage(self, *, held_bytes: int | None = None) -> None:
        """One batch packed into a staging buffer: one just made
        (``held_bytes``: what the engine then holds) or a kept one."""
        with self._lock:
            if held_bytes is None:
                self._c["stage_reuses"].inc()
            else:
                self._c["stage_allocs"].inc()
                self.stage_bytes.set(held_bytes)

    def record_request(self, *, queue_wait_s: float, e2e_s: float) -> None:
        with self._lock:
            self._c["completed"].inc()
            self.queue_wait.record(queue_wait_s)
            self.e2e.record(e2e_s)

    # -- reporting -------------------------------------------------------
    def snapshot(self) -> dict:
        """One JSON-able dict: counters, pad overhead, and p50/p95/p99
        blocks per stage (the serving analog of
        ``FeedTelemetry.summary``) — every key of the pre-obs shape
        (the ``/stats`` contract), then one ``<phase>_time`` block per
        dispatcher phase, then the staging buffers' counters."""
        with self._lock:
            vals = {f: self._c[f].value for f in _COUNTER_FIELDS}
            executed = vals["rows"] + vals["padded_rows"]
            return {
                **vals,
                # fraction of executed device rows that were padding —
                # high values mean the ladder is too coarse (or traffic
                # too sparse) for the offered load
                "pad_overhead_frac": (
                    round(vals["padded_rows"] / executed, 4) if executed
                    else 0.0),
                "mean_batch_rows": (
                    round(vals["rows"] / vals["batches"], 2)
                    if vals["batches"] else 0.0),
                "queue_wait": self.queue_wait.summary(),
                "device_time": self.device_time.summary(),
                "e2e_latency": self.e2e.summary(),
                **{f"{f}_time": h.summary()
                   for f, h in self.phase_time.items()},
                **{f: self._c[f].value for f in _STAGE_FIELDS},
                "stage_bytes": int(self.stage_bytes.value),
            }


# attribute-style counter reads (eng.telemetry.batches, .timed_out, ...)
# are part of the public surface — generate one read-only property per
# counter field instead of ten hand-rolled copies
for _f in _COUNTER_FIELDS + _STAGE_FIELDS:
    setattr(ServeTelemetry, _f,
            property(lambda self, _f=_f: self._c[_f].value))
del _f


# router counters, in /stats JSON order. Sheds split by origin: an
# admission shed means the FLEET is saturated (autoscale signal), a
# circuit shed means a model's replicas keep FAILING (fast-fail), and a
# no-replica shed means every replica is draining/dead (availability
# gap the supervisor is already closing).
_ROUTER_COUNTER_FIELDS = (
    "requests",           # admitted into the router
    "completed",          # futures resolved with a result
    "failed",             # resolved with a non-shed error
    "failovers",          # attempts retried on another replica after a
                          # replica death/failure
    "hedges",             # duplicate attempts launched on a slow primary
    "hedge_wins",         # requests whose hedge resolved first
    "shed_admission",     # router admission (queue/SLO budget) rejects
    "shed_circuit",       # per-model circuit breaker open
    "shed_no_replica",    # no READY replica to route to
    "shed_replica",       # replica-side backpressure that survived the
                          # retry budget (capacity saturated, not absent)
    "replica_deaths",     # replicas observed dead (probe or attempt)
    "replica_restarts",   # replicas respawned by the supervisor
    "scale_ups",          # autoscaler added a replica
    "scale_downs",        # autoscaler drained a replica
    "sessions_migrated",  # stateful streams re-pinned to a survivor
                          # after their pinned replica died
    "session_resets",     # stream responses that DECLARED state loss
                          # (state_reset=true) — the honesty counter
                          # the chaos drill gates at zero
)


class RouterTelemetry:
    """Counters + latency histograms + autoscaler-signal gauges for one
    fleet router, registered under ``router_*`` names (default: the
    process registry, so ``GET /metrics`` and the bench ``obs`` block
    carry the fleet view). The gauges are the obs-registry signals the
    metric-driven autoscaler consumes: fleet queue-wait p95, fleet shed
    rate, and cumulative dispatcher crashes aggregated from the
    replicas' own ``/stats``.

    One router per process is the production shape and gets the default
    registry (so ``GET /metrics`` carries the fleet); a SECOND router
    in the same process must bring its own ``registry=`` — like the
    ``serve_*`` names, registration is latest-wins, and two fleets
    writing one ``router_*`` family would feed each other's autoscaler
    (``bench.py serve --sweep`` isolates its side-by-side fleets this
    way)."""

    def __init__(self, registry: Registry | None = None):
        reg = registry if registry is not None else default_registry()
        self.registry = reg  # the autoscaler reads its signals back here
        self._lock = threading.Lock()
        self._c = {f: reg.register(f"router_{f}", Counter())
                   for f in _ROUTER_COUNTER_FIELDS}
        self.e2e = LatencyStats(       # admitted -> future resolved
            hist=reg.register("router_e2e_latency", Histogram()))
        self.attempt = LatencyStats(   # one replica round-trip
            hist=reg.register("router_attempt_latency", Histogram()))
        # autoscaler signal gauges (written by the router's probe loop)
        self.replicas_ready = reg.gauge("router_replicas_ready")
        self.replicas_target = reg.gauge("router_replicas_target")
        self.queue_wait_p95_ms = reg.gauge("router_queue_wait_p95_ms")
        self.shed_rate_per_s = reg.gauge("router_shed_rate_per_s")
        self.dispatcher_crashes = reg.gauge("router_dispatcher_crashes")

    def inc(self, field: str, n: int = 1) -> None:
        self._c[field].inc(n)

    def record_attempt(self, seconds: float) -> None:
        self.attempt.record(seconds)

    def record_completed(self, e2e_s: float) -> None:
        with self._lock:
            self._c["completed"].inc()
            self.e2e.record(e2e_s)

    def snapshot(self) -> dict:
        vals = {f: c.value for f, c in self._c.items()}
        total_sheds = (vals["shed_admission"] + vals["shed_circuit"]
                       + vals["shed_no_replica"] + vals["shed_replica"])
        resolved = vals["completed"] + vals["failed"]
        return {
            **vals,
            "sheds_total": total_sheds,
            # the lived error budget: failed / resolved (sheds are the
            # DESIGNED overload response, not budget burn)
            "failed_frac": (round(vals["failed"] / resolved, 4)
                            if resolved else 0.0),
            "e2e_latency": self.e2e.summary(),
            "attempt_latency": self.attempt.summary(),
        }

    def summary_line(self) -> str:
        """Grep-stable one-liner for logs and the router smoke gate."""
        v = self.snapshot()
        return (f"[router] failovers={v['failovers']} "
                f"hedges={v['hedges']} deaths={v['replica_deaths']} "
                f"restarts={v['replica_restarts']} "
                f"sheds={v['sheds_total']} completed={v['completed']} "
                f"failed={v['failed']} "
                f"sessions_migrated={v['sessions_migrated']} "
                f"resets={v['session_resets']}")


for _f in _ROUTER_COUNTER_FIELDS:
    setattr(RouterTelemetry, _f,
            property(lambda self, _f=_f: self._c[_f].value))
del _f
