"""Serving replicas: the units the fleet router supervises.

A replica is one engine's worth of serving capacity behind a uniform,
transport-agnostic surface the :class:`~deepvision_tpu.serve.router.
FleetRouter` can route to, probe, drain, and kill:

- :class:`EngineReplica` wraps an in-process
  :class:`~deepvision_tpu.serve.engine.InferenceEngine` — compiles in
  milliseconds on the toy test models, so the router's lifecycle tests
  (draining, failover, autoscaling, chaos) stay in the fast tier.
- :class:`ProcessReplica` spawns ``serve.py --http 0 --port-file ...``
  as a child process and talks HTTP — the production topology
  (process-per-replica: one crash, one SIGKILL, one OOM takes out ONE
  replica's capacity, never the fleet), and the only backend a chaos
  drill can *actually* SIGKILL (``bench.py serve --sweep``,
  ``make router-smoke``).

The contract every backend honors:

- ``request()`` either returns the result dict or raises: a
  :class:`ReplicaDeadError` (replica gone — the router fails over), a
  :class:`~deepvision_tpu.serve.admission.ShedError` (replica-side
  backpressure, carries ``retry_after_s``), a ``TimeoutError`` (the
  replica's own deadline machinery), or ``ValueError`` (client error —
  bad shape/model; NOT retryable on another replica).
- ``probe()`` returns the replica's health dict (``status`` of ``"ok"``
  or ``"recovering"``) or raises :class:`ReplicaDeadError`.
- ``kill()`` is abrupt (SIGKILL / fail-everything close); ``stop()``
  is the graceful twin. Both are idempotent. A killed replica is
  single-use: the router respawns a FRESH replica via its factory
  instead of resurrecting the corpse.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable

import numpy as np

from deepvision_tpu.serve.admission import ShedError

__all__ = ["ReplicaDeadError", "EngineReplica", "ProcessReplica",
           "process_replica_factory"]


class ReplicaDeadError(RuntimeError):
    """The replica is gone (process died / engine closed / connection
    refused): the router should mark it dead and fail the attempt over
    to a healthy replica."""


class EngineReplica:
    """In-process replica: one :class:`InferenceEngine` built from a
    ``models_factory`` at :meth:`start`. ``kill()`` models abrupt death
    (the engine closes, failing every in-flight future — exactly what
    the router's failover must absorb)."""

    def __init__(self, replica_id: str,
                 models_factory: Callable[[], list],
                 **engine_kw):
        self.replica_id = replica_id
        self._models_factory = models_factory
        self._engine_kw = dict(engine_kw)
        self._engine = None
        self._dead = False

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        from deepvision_tpu.serve.engine import InferenceEngine
        from deepvision_tpu.serve.telemetry import ServeTelemetry

        from deepvision_tpu.obs.metrics import Registry

        # private registry per replica: N in-process engines must not
        # fight over the process-default serve_* names (latest-wins
        # would hide every replica but one from the autoscaler signals)
        kw = dict(self._engine_kw)
        kw.setdefault("telemetry", ServeTelemetry(registry=Registry()))
        self._engine = InferenceEngine(self._models_factory(), **kw)

    def stop(self) -> None:
        if self._engine is not None:
            self._engine.close()

    def kill(self) -> None:
        """Abrupt death: in-flight futures fail with 'engine closed',
        subsequent requests/probes raise :class:`ReplicaDeadError`.
        Session state is ABANDONED, not flushed — an in-process kill
        must exercise the same cadence-snapshot recovery a SIGKILL
        would, or the chaos drill proves nothing."""
        self._dead = True
        if self._engine is not None:
            self._engine.close(abandon_sessions=True)

    # -- serving surface -------------------------------------------------
    def request(self, model: str | None, x, *,
                timeout_s: float | None = None,
                trace: str | None = None,
                session: str | None = None,
                seq: int | None = None) -> dict:
        if self._dead or self._engine is None:
            raise ReplicaDeadError(f"{self.replica_id}: replica is dead")
        try:
            fut = self._engine.submit(x, model=model, timeout_s=timeout_s,
                                      trace=trace, session=session,
                                      seq=seq)
            return fut.result(
                timeout=timeout_s + 1.0 if timeout_s is not None else None)
        except (ShedError, TimeoutError, ValueError):
            raise
        except RuntimeError as e:
            # "closed" = the engine is permanently gone: a death
            # verdict is right. A dispatcher CRASH is not — the PR 4
            # supervisor is already restarting it (probe reports
            # "recovering", the router drains); condemning here would
            # kill a self-healing engine and pay a full respawn.
            if "closed" in str(e):
                raise ReplicaDeadError(
                    f"{self.replica_id}: {e}") from e
            raise

    def probe(self) -> dict:
        if self._dead or self._engine is None:
            raise ReplicaDeadError(f"{self.replica_id}: replica is dead")
        return self._engine.health()

    def stats(self) -> dict:
        if self._dead or self._engine is None:
            raise ReplicaDeadError(f"{self.replica_id}: replica is dead")
        return self._engine.stats()

    def metrics_dump(self) -> dict:
        """This replica's typed registry dump (histogram reservoirs
        included) — the federation scrape, straight off the engine's
        private registry."""
        if self._dead or self._engine is None:
            raise ReplicaDeadError(f"{self.replica_id}: replica is dead")
        return self._engine.telemetry.registry.dump()


class ProcessReplica:
    """Child-process replica: spawns ``serve.py --http 0 --port-file``
    and talks plain HTTP (`POST /v1/predict`, `GET /healthz`,
    `GET /stats`). ``cpu_affinity`` (a set of core ids, Linux only) pins
    the child so a fleet bench measures replica scaling, not N processes
    thrashing one core."""

    def __init__(self, replica_id: str, argv: list[str], *,
                 startup_timeout_s: float = 240.0,
                 cpu_affinity: set[int] | None = None,
                 env: dict | None = None,
                 stop_event: threading.Event | None = None):
        self.replica_id = replica_id
        self._argv = list(argv)
        self._startup_timeout_s = startup_timeout_s
        self._affinity = cpu_affinity
        self._env = env
        self._stop_event = stop_event or threading.Event()
        self._proc: subprocess.Popen | None = None
        self._port: int | None = None
        self._dead = False
        self._log_path: Path | None = None
        # per-thread keep-alive connection to this replica (the server
        # speaks HTTP/1.1): a router attempt thread pays TCP setup once,
        # not once per request
        self._conns = threading.local()

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        tmp = Path(tempfile.mkdtemp(prefix=f"dvt-replica-{self.replica_id}-"))
        port_file = tmp / "port"
        self._log_path = tmp / "replica.log"
        argv = self._argv + ["--port-file", str(port_file)]
        env = dict(self._env if self._env is not None else os.environ)
        try:
            with open(self._log_path, "wb") as log:
                self._proc = subprocess.Popen(
                    argv, stdout=log, stderr=subprocess.STDOUT,
                    stdin=subprocess.DEVNULL, env=env)
        except OSError:
            self._dead = True  # never ran: nothing it held is held
            raise
        if self._affinity and hasattr(os, "sched_setaffinity"):
            try:
                os.sched_setaffinity(self._proc.pid, self._affinity)
            except OSError:
                pass  # affinity is an optimization, never a failure
        deadline = time.monotonic() + self._startup_timeout_s
        # stop-responsive poll: the port file appears once the server is
        # bound, /healthz 200 once warmup compiles finished
        while True:
            if self._proc.poll() is not None:
                raise ReplicaDeadError(
                    f"{self.replica_id}: exited rc={self._proc.returncode} "
                    f"during startup (log: {self._log_path})")
            if self._stop_event.is_set():
                self.kill()
                raise ReplicaDeadError(
                    f"{self.replica_id}: startup aborted by shutdown")
            if self._port is None and port_file.exists():
                try:
                    self._port = int(port_file.read_text().strip())
                except ValueError:
                    self._port = None  # partially written: retry
            if self._port is not None:
                try:
                    if self.probe().get("status") == "ok":
                        return
                except (ReplicaDeadError, OSError):
                    pass
            if time.monotonic() > deadline:
                self.kill()
                raise ReplicaDeadError(
                    f"{self.replica_id}: not ready within "
                    f"{self._startup_timeout_s:.0f}s (log: {self._log_path})")
            self._stop_event.wait(0.1)

    def stop(self, grace_s: float = 5.0) -> None:
        if self._proc is None:
            return
        self._dead = True
        if self._proc.poll() is None:
            self._proc.terminate()
            try:
                self._proc.wait(grace_s)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait(grace_s)

    def kill(self) -> None:
        """SIGKILL — the real thing, not a simulation."""
        self._dead = True
        if self._proc is not None and self._proc.poll() is None:
            self._proc.kill()
            try:
                self._proc.wait(5.0)
            except subprocess.TimeoutExpired:
                pass

    @property
    def pid(self) -> int | None:
        return self._proc.pid if self._proc is not None else None

    @property
    def exited(self) -> bool:
        """The child process has come and gone (or the replica was
        stopped before it ever spawned one): whatever it held — a TPU
        chip, see :func:`process_replica_factory` — is free again. A
        replica that is created but not yet spawned has NOT exited: its
        chip is spoken for."""
        if self._proc is None:
            return self._dead
        return self._proc.poll() is not None

    # -- HTTP plumbing ---------------------------------------------------
    def _http(self, method: str, path: str, body: str | None = None,
              timeout_s: float = 10.0, headers: dict | None = None):
        import http.client

        if self._dead or self._port is None:
            raise ReplicaDeadError(f"{self.replica_id}: replica is dead")
        if self._proc is not None and self._proc.poll() is not None:
            raise ReplicaDeadError(
                f"{self.replica_id}: process exited "
                f"rc={self._proc.returncode}")
        conn = getattr(self._conns, "conn", None)
        if conn is None:
            conn = http.client.HTTPConnection("127.0.0.1", self._port,
                                              timeout=timeout_s)
            self._conns.conn = conn
        else:
            conn.timeout = timeout_s
            if conn.sock is not None:
                conn.sock.settimeout(timeout_s)
        try:
            conn.request(method, path, body, headers=headers or {})
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        except TimeoutError as e:
            # a client-side read timeout means SLOW, not DEAD: the
            # router must treat it as a latency failure (breaker food,
            # retryable) — declaring a healthy-but-slow replica dead
            # would turn a latency event into a respawn cascade. The
            # half-read keep-alive socket is unusable either way.
            self._drop_conn(conn)
            raise TimeoutError(
                f"{self.replica_id}: no response within {timeout_s}s"
            ) from e
        except (ConnectionError, OSError,
                http.client.HTTPException) as e:
            # a broken keep-alive socket is not reusable; drop it so
            # the next call (possibly post-restart) reconnects fresh
            self._drop_conn(conn)
            if self._proc is not None and self._proc.poll() is None:
                # the process is still alive: one dropped connection
                # (a crashed handler thread, a reset keep-alive) is a
                # request failure — breaker food, retryable — not a
                # death verdict. Condemning here would SIGKILL a live
                # replica and pay a full respawn+recompile for what
                # may be a single poison request.
                raise RuntimeError(
                    f"{self.replica_id}: request failed "
                    f"({type(e).__name__}: {e}); process alive") from e
            raise ReplicaDeadError(
                f"{self.replica_id}: {type(e).__name__}: {e}") from e

    def _drop_conn(self, conn) -> None:
        self._conns.conn = None
        try:
            conn.close()
        except Exception:
            pass

    # -- serving surface -------------------------------------------------
    def request(self, model: str | None, x, *,
                timeout_s: float | None = None,
                trace: str | None = None,
                session: str | None = None,
                seq: int | None = None) -> dict:
        import base64

        # binary wire format (serve.py `input_b64`): base64 raw bytes
        # beat nested float lists ~20x on both encode and decode — at
        # fleet scale the router's per-request JSON cost IS capacity
        x = np.ascontiguousarray(np.asarray(x, np.float32))
        payload: dict = {
            "input_b64": base64.b64encode(x.tobytes()).decode("ascii"),
            "shape": list(x.shape),
            "dtype": "float32",
        }
        if model is not None:
            payload["model"] = model
        if session is not None:
            # stateful stream frame: the child's SessionStore threads
            # state by (session, seq)
            payload["session"] = session
            payload["seq"] = seq
        if timeout_s is not None:
            # carry the router's remaining deadline to the child, so
            # the replica stops working a request the router has
            # already timed out or hedged away — without this, every
            # losing attempt still burns a full replica slot under the
            # child's blanket --timeout-s
            payload["timeout_s"] = round(timeout_s, 3)
        req_headers = None
        if trace is not None:
            # the distributed-trace hop: the child stamps its
            # queue/device/postprocess spans with this id, so the
            # merged fleet trace links router attempt -> replica work
            from deepvision_tpu.obs.distributed import TRACE_HEADER

            req_headers = {TRACE_HEADER: trace}
        status, headers, body = self._http(
            "POST", "/v1/predict", json.dumps(payload),
            timeout_s=(timeout_s or 30.0) + 1.0, headers=req_headers)
        try:
            data = json.loads(body)
        except ValueError:
            data = {"error": body.decode(errors="replace")}
        if status == 200:
            return data["result"]
        if status == 429:
            raise ShedError(data.get("error", "shed"),
                            float(data.get("retry_after", 0.05)))
        if status == 504:
            raise TimeoutError(data.get("error", "deadline expired"))
        if status == 400:
            raise ValueError(data.get("error", "bad request"))
        # 5xx / unknown: the replica ANSWERED (it is alive) — a
        # request-level failure the router may retry elsewhere, never
        # a death verdict
        raise RuntimeError(
            f"{self.replica_id}: HTTP {status}: {data.get('error')}")

    def probe(self) -> dict:
        status, headers, body = self._http("GET", "/healthz",
                                           timeout_s=5.0)
        try:
            health = json.loads(body)
        except ValueError:
            health = {}
        if status == 200:
            health.setdefault("status", "ok")
        else:
            health.setdefault("status", "recovering")
        return health

    def stats(self) -> dict:
        status, _h, body = self._http("GET", "/stats", timeout_s=5.0)
        if status != 200:
            raise ReplicaDeadError(
                f"{self.replica_id}: /stats HTTP {status}")
        return json.loads(body)

    def metrics_dump(self) -> dict:
        """The child's typed registry dump over HTTP
        (``GET /metrics.json``) — what the router federates into its
        fleet-wide ``/metrics``."""
        status, _h, body = self._http("GET", "/metrics.json",
                                      timeout_s=5.0)
        if status != 200:
            raise RuntimeError(
                f"{self.replica_id}: /metrics.json HTTP {status}")
        return json.loads(body)


def process_replica_factory(make_argv: Callable[[str], list[str]], *,
                            replicas: int, devices: dict
                            ) -> Callable[[str], ProcessReplica]:
    """-> the ``replica_factory(sid)`` a :class:`FleetRouter` over
    ``serve.py`` children needs, given what a device probe found
    (``startup.probe_devices()``: the router process itself stays off
    JAX).

    A TPU chip belongs to one process at a time, so on a TPU host every
    live child is confined to a chip of its own (``startup.chip_env``);
    a respawn takes the lowest chip whose previous holder has exited.
    ``replicas`` — the most children that will ever live at once
    (``--fleet-max``) — greater than the chips present raises
    ValueError HERE, at start, instead of a child waiting out its
    startup timeout on a held chip. Off TPU the children share the
    host freely and inherit the environment unchanged."""
    if devices["platform"] != "tpu":
        return lambda sid: ProcessReplica(sid, make_argv(sid))
    from deepvision_tpu.startup import chip_env

    chips = devices["count"]
    chip_env(replicas - 1, chips)  # raises: fewer chips than replicas
    lock = threading.Lock()  # the router boots replicas on threads
    holders: dict[int, ProcessReplica] = {}

    def factory(sid: str) -> ProcessReplica:
        with lock:
            free = [c for c in range(chips)
                    if c not in holders or holders[c].exited]
            if not free:
                raise ReplicaDeadError(
                    f"{sid}: all {chips} chip(s) of this host are held "
                    "by live replicas")
            chip = free[0]
            replica = ProcessReplica(
                sid, make_argv(sid),
                env={**os.environ, **chip_env(chip, chips)})
            holders[chip] = replica
        print(f"[fleet] replica {sid} -> chip {chip} of {chips}",
              file=sys.stderr, flush=True)
        return replica

    return factory


def replica_argv(model_specs: list[str], *, buckets: str | None = None,
                 artifact_specs: list[str] | None = None,
                 store: str | None = None,
                 extra: list[str] | None = None) -> list[str]:
    """argv for a ``ProcessReplica`` child: this interpreter running the
    repo's ``serve.py`` in HTTP mode on an ephemeral port.

    ``store``: a shared AOT artifact-store directory (``--store``) —
    every child of the fleet warms its executables from the same disk
    cache, so a respawned replica skips the compile storm the first
    generation paid."""
    serve_py = Path(__file__).resolve().parent.parent.parent / "serve.py"
    argv = [sys.executable, str(serve_py), "--http", "0"]
    for spec in model_specs:
        argv += ["-m", spec]
    for spec in artifact_specs or []:
        argv += ["--artifact", spec]
    if buckets:
        argv += ["--buckets", buckets]
    if store:
        argv += ["--store", str(store)]
    argv += list(extra or [])
    return argv
