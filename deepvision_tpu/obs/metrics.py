"""Process-wide metric registry: counters, gauges, reservoir histograms.

Before this module, four telemetry objects each reinvented the same
primitives — ``serve/telemetry.LatencyStats`` (lock + deque + numpy
percentiles), ``data/prefetch.FeedTelemetry`` (bare float accumulators,
explicitly documented as racing their own ``reset``),
``resilience.RecoveryCounters`` (lock + dict of ints), and the
``train/loggers`` metric history — with four naming schemes and four
export paths, none of which could be read as ONE view of the process.

Here the primitives live once:

- :class:`Counter` / :class:`Gauge` — lock-guarded scalars;
- :class:`Histogram` — bounded-reservoir series (most recent ``maxlen``
  samples for p50/p95/p99) with EXACT lifetime ``count``/``total``.
  Every read of the (count, total, samples) triple happens under the
  histogram's own lock, so a reader can never see a torn count/total
  pair no matter which thread it runs on — the serve ``/stats`` path
  previously only got that guarantee when callers remembered to hold
  the outer telemetry lock;
- :class:`Registry` — a thread-safe name->metric table with a stable
  ``namespace_name`` naming scheme (``serve_e2e_latency``,
  ``input_h2d_wait``, ``recovery_rollbacks``, ``mem_bytes_in_use_dev0``),
  one merged JSON :meth:`~Registry.snapshot`, and a Prometheus text
  exposition renderer (:meth:`~Registry.render_prometheus`) for the
  ``serve.py GET /metrics`` surface.

The process-wide default registry (:func:`default_registry`) is what the
existing telemetry objects register into at construction; re-registering
a name replaces the previous owner (latest wins — telemetry objects are
long-lived per-process singletons in production, and tests that build
many engines sequentially must not accrete stale series).

Units: histograms record SECONDS. The JSON snapshot reports derived
milliseconds (``*_ms`` keys, matching the pre-existing ``/stats`` and
``input_*`` shapes); the Prometheus rendering reports base-unit seconds
(quantile samples + ``_sum``), per Prometheus convention.
"""

from __future__ import annotations

import re
import threading
from collections import deque

import numpy as np

__all__ = [
    "Counter",
    "GEN_COUNTERS",
    "GEN_TTFT",
    "Gauge",
    "Histogram",
    "Registry",
    "default_registry",
    "histogram_export",
    "histogram_summary",
    "record_token_step",
    "render_family",
    "start_exposition_server",
]

_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


class Counter:
    """Monotonic (in normal use) integer counter; ``inc`` from any
    thread, ``value`` reads are consistent."""

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    def reset(self) -> None:
        with self._lock:
            self._value = 0

    @property
    def value(self) -> int:
        with self._lock:
            return self._value

    def __repr__(self) -> str:
        return f"Counter({self.value})"


class Gauge:
    """Last-written float value (memory in use, queue depth, ...)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def __repr__(self) -> str:
        return f"Gauge({self.value})"


class Histogram:
    """Bounded-reservoir time series with percentile snapshots.

    ``observe`` takes seconds; :meth:`summary` reports milliseconds in
    the exact shape ``serve/telemetry.LatencyStats.summary`` has always
    produced (``/stats`` JSON contract). The reservoir keeps the most
    recent ``maxlen`` samples (enough for stable p99 at serving rates)
    while ``count``/``total`` stay exact over the metric's lifetime.

    All three of (samples, count, total) mutate and read under ONE
    internal lock: ``summary()`` computes ``mean_ms`` from a coherent
    (count, total) pair even while writers are mid-``observe``.
    """

    def __init__(self, maxlen: int = 8192):
        self._lock = threading.Lock()
        self._samples: deque[float] = deque(maxlen=maxlen)
        self._count = 0
        self._total = 0.0

    def observe(self, value: float) -> None:
        with self._lock:
            self._samples.append(value)
            self._count += 1
            self._total += value

    def reset(self) -> None:
        with self._lock:
            self._samples.clear()
            self._count = 0
            self._total = 0.0

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def total(self) -> float:
        with self._lock:
            return self._total

    def dump(self) -> dict:
        """Typed raw view INCLUDING the reservoir samples — the wire
        format of cross-process metric federation
        (``obs/distributed.py``): a parent merges children's reservoirs
        sample-for-sample instead of trying to average quantiles, so
        the federated percentiles are exactly what one process
        observing every sample would report."""
        with self._lock:
            return {"type": "histogram", "count": self._count,
                    "total": self._total,
                    "samples": [float(s) for s in self._samples]}

    def summary(self) -> dict:
        return histogram_summary(self.dump())

    def __repr__(self) -> str:
        return f"Histogram(count={self.count})"


_METRIC_TYPES = (Counter, Gauge, Histogram)


class Registry:
    """Thread-safe name -> metric table with one merged snapshot.

    Names follow ``namespace_name`` (``serve_completed``,
    ``input_h2d_wait``); :meth:`register` replaces an existing owner
    (latest wins), the get-or-create helpers (:meth:`counter`,
    :meth:`gauge`, :meth:`histogram`) return the existing metric — and
    refuse a type change, which is always a naming-collision bug.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, Counter | Gauge | Histogram] = {}

    # -- registration ----------------------------------------------------
    def register(self, name: str, metric):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r} (want "
                             "[a-zA-Z_][a-zA-Z0-9_]*)")
        if not isinstance(metric, _METRIC_TYPES):
            raise TypeError(f"not a metric: {metric!r}")
        with self._lock:
            self._metrics[name] = metric
        return metric

    def _get_or_create(self, name: str, cls, factory):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{type(m).__name__}, not {cls.__name__}")
                return m
        # create outside the lock, register() re-takes it (a racing
        # duplicate create is harmless: last registration wins)
        return self.register(name, factory())

    def counter(self, name: str) -> Counter:
        return self._get_or_create(name, Counter, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get_or_create(name, Gauge, Gauge)

    def histogram(self, name: str, maxlen: int = 8192) -> Histogram:
        return self._get_or_create(name, Histogram,
                                   lambda: Histogram(maxlen=maxlen))

    def get(self, name: str):
        with self._lock:
            return self._metrics.get(name)

    def value_of(self, name: str, default: float = 0.0) -> float:
        """Scalar read of a counter/gauge by name (``default`` when the
        metric is absent or a histogram) — the one-liner signal readers
        like the serving autoscaler use to consume registry gauges."""
        m = self.get(name)
        if isinstance(m, (Counter, Gauge)):
            return float(m.value)
        return default

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._metrics)

    # -- export ----------------------------------------------------------
    def collect(self, scalars_only: bool = False
                ) -> list[tuple[str, str, object]]:
        """One atomic collection pass: ``[(name, kind, payload), ...]``
        with every value read in a single tight sweep under the
        registry lock — no formatting, parsing, or I/O between family
        reads. Every renderer (``snapshot``, ``render_prometheus``,
        ``dump``) formats FROM a collect() result, so a scrape landing
        mid-update sees one point-in-time view instead of family A from
        before an event and family B from after it (the old
        render-while-reading hazard: a request completing mid-scrape
        could bump ``serve_completed`` into the text while the
        ``serve_e2e_latency`` family, rendered lines earlier, still
        predated it). ``scalars_only`` skips histograms (and their
        reservoir copies) — the flight recorder's delta notes run on
        hot cadences and only track counters/gauges."""
        out: list[tuple[str, str, object]] = []
        with self._lock:
            for name, m in sorted(self._metrics.items()):
                if isinstance(m, Counter):
                    out.append((name, "counter", m.value))
                elif isinstance(m, Gauge):
                    out.append((name, "gauge", m.value))
                elif not scalars_only:
                    out.append((name, "histogram", m.dump()))
        return out

    def snapshot(self) -> dict:
        """One merged JSON-able view: counters -> int, gauges -> float,
        histograms -> their ``summary()`` dict (ms). Rendered from one
        :meth:`collect` pass."""
        out: dict = {}
        for name, kind, payload in self.collect():
            if kind == "histogram":
                out[name] = histogram_summary(payload)
            else:
                out[name] = payload
        return out

    def dump(self) -> dict:
        """Typed raw registry view for cross-process federation
        (``obs/distributed.py``): counters/gauges with kind tags,
        histograms with their full reservoir (see
        :meth:`Histogram.dump`). One atomic :meth:`collect` pass."""
        out: dict = {}
        for name, kind, payload in self.collect():
            if kind == "histogram":
                out[name] = payload  # already typed by Histogram.dump
            else:
                out[name] = {"type": kind, "value": payload}
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition (format version 0.0.4): counters
        as ``<name>_total``, gauges verbatim, histograms as summaries
        (p50/p95/p99 quantile samples in seconds + ``_sum``/``_count``).
        Formats from one atomic :meth:`collect` pass, so families in
        one scrape never mix epochs."""
        lines: list[str] = []
        for name, payload in self.dump().items():
            lines.extend(render_family(name, payload))
        return "\n".join(lines) + "\n"


def histogram_export(dump: dict, qs=(0.5, 0.95, 0.99)) -> dict:
    """Seconds-unit (count, sum, quantiles) from a histogram dump —
    the pure half of :meth:`Histogram.export`, reusable on merged
    (federated) reservoirs."""
    samples = dump.get("samples") or []
    if samples:
        arr = np.asarray(samples, np.float64)
        vals = np.percentile(arr, [q * 100.0 for q in qs])
        quant = {q: float(v) for q, v in zip(qs, vals)}
    else:
        quant = {q: 0.0 for q in qs}
    return {"count": dump.get("count", 0),
            "sum": dump.get("total", 0.0), "quantiles": quant}


def histogram_summary(dump: dict) -> dict:
    """Milliseconds-unit summary (the ``/stats`` shape) from a
    histogram dump — the pure half of :meth:`Histogram.summary`."""
    samples = dump.get("samples") or []
    count = dump.get("count", 0)
    total = dump.get("total", 0.0)
    if not samples:
        return {"count": count, "mean_ms": 0.0, "p50_ms": 0.0,
                "p95_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
    arr = np.asarray(samples, np.float64) * 1e3
    p50, p95, p99 = np.percentile(arr, [50, 95, 99])
    return {
        "count": count,
        "mean_ms": round(total / max(1, count) * 1e3, 3),
        "p50_ms": round(float(p50), 3),
        "p95_ms": round(float(p95), 3),
        "p99_ms": round(float(p99), 3),
        "max_ms": round(float(arr.max()), 3),
    }


def _fmt(v: float) -> str:
    return f"{v:.9g}"


def render_family(name: str, payload: dict) -> list[str]:
    """Exposition lines for ONE unlabelled metric family from its
    typed :meth:`Registry.dump` payload — the single definition of the
    counter/gauge/histogram-summary text format, shared by
    :meth:`Registry.render_prometheus` and the federated renderer
    (``obs/distributed.render_federated``) so the two surfaces can
    never drift apart."""
    t = payload.get("type")
    if t == "counter":
        return [f"# TYPE {name}_total counter",
                f"{name}_total {int(payload['value'])}"]
    if t == "gauge":
        return [f"# TYPE {name} gauge", f"{name} {_fmt(payload['value'])}"]
    ex = histogram_export(payload)
    lines = [f"# TYPE {name} summary"]
    for q, v in ex["quantiles"].items():
        lines.append(f'{name}{{quantile="{q:g}"}} {_fmt(v)}')
    lines.append(f"{name}_sum {_fmt(ex['sum'])}")
    lines.append(f"{name}_count {ex['count']}")
    return lines


_DEFAULT = Registry()


def default_registry() -> Registry:
    """The process-wide registry every telemetry object registers into
    by default — the single source for ``GET /metrics`` and the bench
    JSON's ``obs`` block."""
    return _DEFAULT


def start_exposition_server(port: int, registry: Registry | None = None,
                            host: str = "0.0.0.0", render_fn=None):
    """Minimal standalone Prometheus scrape surface: a daemon-threaded
    stdlib HTTP server answering ``GET /metrics`` with
    :meth:`Registry.render_prometheus` (plus ``/healthz``, plus
    ``GET /metrics.json`` — the typed :meth:`Registry.dump` the
    federation layer scrapes). Exists for processes that are NOT
    already serving HTTP — the multi-host training supervisor
    (``train_dist.py --supervise --metrics-port``) most of all;
    ``serve.py`` keeps its own integrated endpoint.

    ``render_fn`` overrides the ``/metrics`` text (the cluster
    supervisor passes its federated renderer so one scrape describes
    the whole fleet); ``/metrics.json`` always dumps the local
    registry. Returns ``(server, actual_port)``; call
    ``server.shutdown()`` to stop. ``port=0`` binds an ephemeral port
    (tests)."""
    import http.server
    import json as _json
    import threading

    reg = registry if registry is not None else default_registry()
    render = render_fn if render_fn is not None else reg.render_prometheus

    class _Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (stdlib API name)
            if self.path.split("?")[0] == "/metrics":
                body = render().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif self.path.split("?")[0] == "/metrics.json":
                body = _json.dumps(reg.dump()).encode()
                ctype = "application/json"
            elif self.path.split("?")[0] == "/healthz":
                body, ctype = b"ok\n", "text/plain"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # scrapes are not log events
            pass

    server = http.server.ThreadingHTTPServer((host, int(port)), _Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name="metrics-exposition")
    thread.start()
    return server, server.server_address[1]


# Routing, selection and attention counts of a token-model train step
# (train/steps.vlm_train_step and lm_train_step put them in the step's
# metrics): totals as counters, the last step's load as gauges. A step
# carries those of its model: an indexer's selected pairs or a causal
# attention's pairs, a balancing bias or none, a multi-token prediction's
# loss and the hyper-connections' Sinkhorn error or neither.
TOKEN_STEP_COUNTERS = ("moe_local_assignments", "moe_dropped",
                       "dsa_selected_pairs", "attn_causal_pairs")
TOKEN_STEP_GAUGES = ("moe_expert_tokens_max", "moe_expert_tokens_mean",
                     "moe_bias_abs_mean", "mtp_loss", "mhc_sinkhorn_err")


def record_token_step(metrics: dict, registry: Registry | None = None
                      ) -> None:
    """Fold one fetched step's ``metrics`` (host floats) into the
    registry, whichever of the names above it carries; a step without
    any (every conv model's) is left alone."""
    reg = registry if registry is not None else default_registry()
    for name in TOKEN_STEP_COUNTERS:
        if name in metrics:
            reg.counter(name).inc(int(metrics[name]))
    for name in TOKEN_STEP_GAUGES:
        if name in metrics:
            reg.gauge(name).set(metrics[name])


# The token generator's (serve/generator.py) counters: prompt tokens
# prefilled, tokens handed to requests, decode steps, and the requests
# those steps served summed over steps; and its histogram of seconds from
# submit to a request's first token.
GEN_COUNTERS = ("gen_prefill_tokens", "gen_decode_tokens",
                "gen_decode_steps", "gen_decode_rows")
GEN_TTFT = "gen_ttft"


# Which lowering each call site of a traced token model took
# (models/transformer.batched_sparse_attention and chunk_scores,
# models/latent_moe._LatentAttention and models/hyper_latent's
# hyper-connections decide while the step is traced,
# so these count sites of traced programs, not steps): (through the
# Pallas kernels, through the XLA form).
ATTENTION_SITE_COUNTERS = ("dsa_kernel_sites", "dsa_xla_sites")
INDEXER_SITE_COUNTERS = ("indexer_kernel_sites", "indexer_xla_sites")
LATENT_SITE_COUNTERS = ("mla_kernel_sites", "mla_xla_sites")
MHC_SITE_COUNTERS = ("mhc_kernel_sites", "mhc_xla_sites")


def _record_site(counters: tuple, by_kernel: bool,
                 registry: Registry | None) -> None:
    reg = registry if registry is not None else default_registry()
    reg.counter(counters[0 if by_kernel else 1]).inc()


def record_attention_site(by_kernel: bool, registry: Registry | None = None
                          ) -> None:
    """One sparse-attention call site (``dsa_kernel_sites`` or
    ``dsa_xla_sites``)."""
    _record_site(ATTENTION_SITE_COUNTERS, by_kernel, registry)


def record_indexer_site(by_kernel: bool, registry: Registry | None = None
                        ) -> None:
    """One call site of a chunk's indexer scores
    (``indexer_kernel_sites`` or ``indexer_xla_sites``)."""
    _record_site(INDEXER_SITE_COUNTERS, by_kernel, registry)


def record_latent_site(by_kernel: bool, registry: Registry | None = None
                       ) -> None:
    """One latent-attention call site of a program that will run, the
    shape trace of ``init`` left out (``mla_kernel_sites`` or
    ``mla_xla_sites``)."""
    _record_site(LATENT_SITE_COUNTERS, by_kernel, registry)


def record_mhc_site(by_kernel: bool, registry: Registry | None = None
                    ) -> None:
    """One hyper-connected sublayer of a program that will run, the shape
    trace of ``init`` left out (``mhc_kernel_sites`` or
    ``mhc_xla_sites``)."""
    _record_site(MHC_SITE_COUNTERS, by_kernel, registry)
