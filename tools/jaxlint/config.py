"""jaxlint configuration: ``jaxlint.toml`` loading (stdlib ``tomllib``,
the same reader the runtime sharding engine uses for the
``[[shardcheck.rule]]`` table) + the LintConfig model."""

from __future__ import annotations

import fnmatch
import re
import tomllib
from dataclasses import dataclass, field
from pathlib import Path


class TomlError(ValueError):
    """A ``jaxlint.toml`` entry that parses but breaks the config's own
    rules (missing ``reason``, unknown key, bad value)."""


# ------------------------------------------------------------- LintConfig


@dataclass
class BaselineEntry:
    """A recorded, justified exception: findings matching (path, code[,
    match-substring]) are suppressed. ``reason`` is mandatory by
    convention so the debt ledger stays reviewable."""

    path: str
    code: str
    reason: str = ""
    match: str = ""
    hits: int = 0  # filled by the engine; stale entries are warned about

    def matches(self, path: str, code: str, text: str) -> bool:
        return (
            self.path == path
            and fnmatch.fnmatch(code, self.code)
            and (not self.match or self.match in text)
        )


@dataclass
class LintConfig:
    """Knobs for the checkers; defaults encode this repo's layout and are
    overridable from ``jaxlint.toml`` (``[jaxlint]`` table)."""

    # Directories whose every function is traced-by-construction (the
    # README contract: models/ops/losses are pure jit-able code).
    traced_dirs: list[str] = field(default_factory=lambda: [
        "deepvision_tpu/models", "deepvision_tpu/ops",
        "deepvision_tpu/losses",
    ])
    # Host-side data pipelines: jnp compute is a hazard here (JX107).
    data_dirs: list[str] = field(default_factory=lambda: [
        "deepvision_tpu/data",
    ])
    # Sharding-sensitive layout code: reshape/transpose must be followed
    # by a sharding constraint (JX108).
    parallel_dirs: list[str] = field(default_factory=lambda: [
        "deepvision_tpu/parallel",
    ])
    # Function-name patterns treated as traced even outside traced_dirs
    # (the step-function naming contract of train/steps.py, train/gan.py).
    traced_name_patterns: list[str] = field(default_factory=lambda: [
        "*_train_step", "*_eval_step", "*_loss_fn", "loss_fn",
        "*_step_fn",
    ])
    # Callables that trace their function argument: a function passed to
    # (or decorated by) one of these is traced, and its same-module
    # callees transitively so.
    jit_wrappers: list[str] = field(default_factory=lambda: [
        "jit", "pjit", "eval_shape", "grad", "value_and_grad", "vmap",
        "pmap", "shard_map", "checkify", "scan", "cond", "while_loop",
        "fori_loop", "switch", "remat", "checkpoint", "custom_vjp",
        "custom_jvp", "compile_train_step", "compile_eval_step",
        "compile_checked_train_step",
    ])
    # jax/lax calls that return *static* Python values — safe in Python
    # control flow, never a taint source (JX101/JX102).
    static_return_calls: list[str] = field(default_factory=lambda: [
        "axis_size", "process_index", "process_count", "device_count",
        "local_device_count", "default_backend", "devices",
        "local_devices",
    ])
    # jax.random.* that mint fresh keys rather than consuming entropy.
    key_fresheners: list[str] = field(default_factory=lambda: [
        "split", "fold_in", "key", "PRNGKey", "key_data",
        "wrap_key_data", "clone",
    ])
    # Parameter-name patterns tracked as PRNG keys (JX103); names
    # assigned from split()/fold_in()/next(KeySeq) are tracked regardless.
    key_name_patterns: list[str] = field(default_factory=lambda: [
        "key", "rng", "*_key", "*_rng", "key_*", "rng_*", "seed_key",
    ])
    # Blessed sharding-constraint sinks for JX108.
    constraint_funcs: list[str] = field(default_factory=lambda: [
        "with_sharding_constraint", "guard_thin_h",
    ])
    # Iterator factories whose consuming loops are overlapped-H2D hot
    # loops (JX109): a blocking host sync inside one stalls the async
    # feed — the queued transfers drain while the host waits.
    prefetch_funcs: list[str] = field(default_factory=lambda: [
        "device_prefetch", "DevicePrefetcher", "prefetch_to_device",
    ])
    # Function-name patterns treated as request-handling loops (JX110):
    # a jax.jit/pjit call inside a loop there traces+compiles on the
    # request path instead of hitting a warmed executable cache.
    serve_funcs: list[str] = field(default_factory=lambda: [
        "*serve*", "*dispatch*", "*handle*", "*request_loop*",
    ])
    # Call-name patterns treated as compiled-step invocations (JX111):
    # a broad `except Exception`/bare `except` around one swallows the
    # checkify NaN/Inf tripwire (core/step.compile_checked_train_step)
    # along with real device failures — recovery code must catch
    # `core.step.checkify_error_cls()` narrowly instead.
    checked_step_funcs: list[str] = field(default_factory=lambda: [
        "*_train_step", "*_eval_step", "*_step_fn", "train_step",
        "eval_step",
    ])
    # Call-name patterns treated as compiled-step invocations for the
    # async-dispatch timing check (JX112): a time.time()/perf_counter()
    # delta spanning one of these without a block_until_ready between
    # call and stop times ENQUEUE, not compute — the classic 10-100x
    # throughput lie on an async backend.
    timed_funcs: list[str] = field(default_factory=lambda: [
        "*_train_step", "*_eval_step", "*_step_fn", "train_step",
        "eval_step",
    ])
    # Function-name patterns treated as supervised service loops
    # (JX113): a bare time.sleep inside a loop there ignores the stop
    # event, so shutdown blocks until the sleep expires — PR 4's
    # stop-responsive idiom is Event.wait(backoff), which sleeps the
    # same but wakes instantly on close().
    loop_sleep_funcs: list[str] = field(default_factory=lambda: [
        "*supervise*", "*dispatch*", "*router*", "*probe*",
        "*autoscale*", "*respawn*", "*_loop*", "*watchdog*",
    ])
    # Call names treated as host->device wire sinks (JX114): a host
    # f32 cast feeding one of these ships 4-byte pixels over the H2D
    # link — the input-wall hazard ISSUE 7 removed (uint8 wire +
    # on-device normalize, ops/normalize.py + data/device_aug.py).
    wire_funcs: list[str] = field(default_factory=lambda: [
        "device_put", "shard_batch", "shard_by_process",
        "DevicePrefetcher", "device_prefetch",
        "make_array_from_process_local_data",
    ])
    # Blocking cluster joins / cross-host barriers (JX115): calling one
    # without a timeout argument hangs the launcher/supervisor forever
    # on a missing peer — jax.distributed.initialize takes
    # initialization_timeout, the coordination-service barriers take
    # timeout_in_ms, and the repo's own save-barrier rendezvous takes
    # timeout_s. Matched against the dotted call name AND its last
    # attribute; any keyword matching ``*timeout*`` satisfies the check.
    cluster_funcs: list[str] = field(default_factory=lambda: [
        "*distributed.initialize", "*wait_at_barrier*",
        "*sync_global_devices*", "*await_all_arrived*",
        "*blocking_key_value_get*",
    ])
    # Function-name patterns treated as numerics-policy hot bodies
    # (JX123): a raw jnp.float32 cast / f32-literal array creation
    # inside one bypasses the mixed-precision policy
    # (core/precision.py) — the regression path the HBM diet erodes
    # by. Policy-derived dtypes (self.dtype, promote_types floors)
    # pass; deliberate f32 reduce floors get reasoned baselines.
    precision_funcs: list[str] = field(default_factory=lambda: [
        "__call__", "loss_fn", "*_loss_fn", "*_loss",
    ])
    # Function-name patterns treated as sentinel-consuming step loops
    # (JX116): a per-step float()/np.asarray()/device_get of the
    # in-graph sentinel outputs (the `sent_*` naming contract of
    # resilience/sentinel.py) re-introduces the JX109 host-sync stall
    # the pending/drain pattern exists to avoid — sentinel fetches
    # must ride the drain cadence (an `i % k` guarded block) instead.
    sentinel_funcs: list[str] = field(default_factory=lambda: [
        "*epoch*", "*fit*", "*train_loop*", "*step_loop*",
    ])
    # Call-name patterns treated as compiled-step invocations for the
    # span-timing check (JX117): a `with span(...)` wrapping one with
    # no device_sync/block_until_ready before the span end records the
    # JX112 async-dispatch lie into the trace — the span times enqueue,
    # not compute. Same default step-call naming as JX111/JX112.
    span_funcs: list[str] = field(default_factory=lambda: [
        "*_train_step", "*_eval_step", "*_step_fn", "train_step",
        "eval_step",
    ])
    # -- concurrency tier (JX118-JX122, tools/jaxlint/concurrency.py) --
    # Name patterns (matched case-insensitively against the FINAL
    # attribute/name segment) treated as mutex objects: `with self._lock:`
    # scopes, `.acquire()` receivers, and the instance lock JX118 expects
    # shared state to hide behind.
    lock_name_patterns: list[str] = field(default_factory=lambda: [
        "*lock*", "*mutex*", "*_mu",
    ])
    # Call-name patterns treated as host-BLOCKING while a lock is held
    # (JX119): HTTP round-trips, subprocess waits, file I/O, sleeps.
    # Structural rules ride along in the checker: zero-arg `.get()` /
    # `.join()` / `.wait()` are unbounded queue/thread/event blocks
    # (a timeout argument bounds them; `str.join(iterable)` has an
    # argument and is skipped), and resolved calls to helpers that
    # TRANSITIVELY block are flagged through the project call graph.
    lock_blocking_calls: list[str] = field(default_factory=lambda: [
        "urlopen", "*.urlopen", "requests.get", "requests.post",
        "requests.put", "requests.request", "subprocess.run",
        "subprocess.check_output", "subprocess.check_call",
        "subprocess.call", "*.communicate", "*.getresponse",
        "*.recv", "*.accept", "*.connect", "open", "*.read_text",
        "*.write_text", "*.read_bytes", "*.write_bytes", "*.flush",
        "time.sleep",
    ])
    # Cross-host collective/barrier calls (JX120's flock-across-
    # collective rule): holding ANY lock across one of these deadlocks
    # the fleet the moment a peer blocked at the barrier needs the same
    # lock — the PR 8 hazard (the Trainer's cluster save is lock-free
    # for exactly this reason).
    collective_calls: list[str] = field(default_factory=lambda: [
        "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
        "pswapaxes", "wait_at_barrier", "sync_global_devices",
        "await_all_arrived",
    ])
    # Import roots that make fork-based multiprocessing unsafe (JX121):
    # once jax/tf runtime threads + locks exist, a forked child
    # inherits locked mutexes with no owner thread and wedges on first
    # use — the PR 2 tier-1 deadlock. Modules reaching these imports
    # (directly or through the project import graph) must create
    # Pool/Process/Queue through an explicit spawn context.
    fork_unsafe_imports: list[str] = field(default_factory=lambda: [
        "jax", "tensorflow",
    ])
    # -- SPMD tier source checkers (JX124-JX126) --
    # Mesh axis names the repo declares (core/mesh.py AXIS_DATA /
    # AXIS_MODEL). JX124 flags these as string LITERALS in sharding
    # contexts (PartitionSpec/Mesh arguments, ``mesh.shape[...]``
    # lookups, ``axis_name=`` keywords, collective axis arguments,
    # ``*axis*`` parameter defaults) anywhere outside the axis-name
    # home — a renamed/reshaped mesh must be a one-file change, and the
    # shardcheck rules table keys on the canonical names.
    mesh_axis_names: list[str] = field(default_factory=lambda: [
        "data", "model",
    ])
    # Files allowed to SPELL the axis-name literals (fnmatch on the
    # lint-root relpath): the single definition site.
    mesh_axis_home: list[str] = field(default_factory=lambda: [
        "deepvision_tpu/core/mesh.py",
    ])
    # Directories whose code runs against multi-device meshes (JX125):
    # a bare single-argument ``jax.device_put(x)`` there silently
    # gathers/replicates onto the default device — every transfer on a
    # sharded path must say its sharding (or go through
    # core.mesh.shard_batch, which applies one).
    multidevice_dirs: list[str] = field(default_factory=lambda: [
        "deepvision_tpu/parallel", "deepvision_tpu/serve",
        "deepvision_tpu/train", "deepvision_tpu/core",
        "deepvision_tpu/resilience",
    ])
    # Directories where literal ``PartitionSpec``/``P`` construction is
    # banned (JX126): model and step code must get specs from the
    # ``[[shardcheck.rule]]`` table (via core/step helpers), not bake
    # them in — the rules table is what shardcheck audits for coverage
    # and what ROADMAP item 1's sharding engine consumes.
    partition_rule_dirs: list[str] = field(default_factory=lambda: [
        "deepvision_tpu/models", "deepvision_tpu/train",
    ])
    # Call names (matched against the FULL dotted name — a bare "dump"
    # would exempt json.dump/pickle.dump, exactly the non-atomic I/O
    # JX122 flags) VETTED for use inside signal handlers: the
    # flight-recorder dump path is written to be best-effort/atomic
    # and never raises (obs/distributed.FlightRecorder.dump /
    # flight_dump), so handlers may route through it; everything else
    # that locks/allocates/does I/O in a handler is flagged.
    signal_safe_calls: list[str] = field(default_factory=lambda: [
        "flight_dump", "self.dump",
    ])
    # Function-name patterns treated as pipeline execution paths
    # (JX127): the device-resident DAG runner and its per-stage
    # executors (serve/pipeline.py naming contract). A jax.device_get /
    # np.asarray / .block_until_ready() on an inter-stage value there
    # re-introduces the host round-trip the pipeline subsystem exists
    # to remove — stage outputs must stay device arrays until the
    # engine's single final fetch.
    pipeline_funcs: list[str] = field(default_factory=lambda: [
        "*pipeline*", "*_stage*", "run_dag*", "*_dag_*",
    ])
    # Function-name patterns treated as stream-handling loops (JX128):
    # stateful serving (serve/sessions.py) keeps each stream's session
    # state device-resident between frames, and the engine's stateful
    # batch path does exactly ONE device_get per executed batch — a
    # jax.device_get / np.asarray / .item() inside the per-frame loop
    # re-materializes the slate on the host every frame. The store's
    # own snapshot path (cadence-driven host I/O) is exempt by scoping:
    # it isn't a per-frame loop and these names don't match it.
    session_funcs: list[str] = field(default_factory=lambda: [
        "*frame_loop*", "*session_loop*", "handle_stream*",
        "*stream_loop*", "serve_stream*",
    ])
    # Function-name patterns treated as weight-residency managers
    # (JX129): the tenancy layer (serve/tenancy.py) owns the ONE
    # sanctioned path that stages weight pytrees onto the device —
    # adopt / ensure_resident / rematerialize, amortized across
    # requests behind the LRU budget. A ``jax.device_put`` of a
    # weights/params/variables pytree inside a dispatch or request
    # loop anywhere else re-uploads the full checkpoint per request
    # (HBM churn + PCIe stall on the hot path); results stay correct,
    # only the residency contract breaks.
    residency_funcs: list[str] = field(default_factory=lambda: [
        "*residency*", "*rematerialize*", "ensure_resident*",
        "*stage_weights*", "adopt*",
    ])
    disable: list[str] = field(default_factory=list)
    baseline: list[BaselineEntry] = field(default_factory=list)


def load_config(path: str | Path | None) -> LintConfig:
    """Build a LintConfig from ``jaxlint.toml`` (or defaults if absent)."""
    cfg = LintConfig()
    if path is None:
        return cfg
    path = Path(path)
    if not path.exists():
        return cfg
    data = tomllib.loads(path.read_text())
    table = data.get("jaxlint", {})
    for name in (
        "traced_dirs", "data_dirs", "parallel_dirs",
        "traced_name_patterns", "jit_wrappers", "static_return_calls",
        "key_fresheners", "key_name_patterns", "constraint_funcs",
        "prefetch_funcs", "serve_funcs", "checked_step_funcs",
        "timed_funcs", "loop_sleep_funcs", "wire_funcs",
        "cluster_funcs", "sentinel_funcs", "span_funcs",
        "precision_funcs", "pipeline_funcs", "session_funcs",
        "residency_funcs",
        "lock_name_patterns", "lock_blocking_calls", "collective_calls",
        "fork_unsafe_imports", "signal_safe_calls",
        "mesh_axis_names", "mesh_axis_home", "multidevice_dirs",
        "partition_rule_dirs", "disable",
    ):
        if name in table:
            setattr(cfg, name, list(table[name]))
    for entry in data.get("baseline", []):
        if "path" not in entry or "code" not in entry:
            raise TomlError(
                "baseline entries need at least 'path' and 'code': "
                f"{entry!r}")
        if not str(entry.get("reason", "")).strip():
            # the ledger is a reviewed debt list, not a mute button:
            # an exception nobody can justify is not an exception
            raise TomlError(
                "baseline entry for "
                f"{entry['path']!r} {entry['code']!r} has no 'reason' — "
                "every recorded exception must say why it is deliberate")
        cfg.baseline.append(BaselineEntry(
            path=entry["path"], code=entry["code"],
            reason=entry["reason"], match=entry.get("match", ""),
        ))
    return cfg


# ---------------------------------------------------------- ircheck config


@dataclass
class DonationWaiver:
    """A justified exception to the IR-level donation gate (JX104
    enforcement): ``model``'s compiled step is allowed an undonated
    state fraction up to ``max_undonated_fraction``. ``reason`` is
    mandatory — the ledger burns down, it does not accrete."""

    model: str
    reason: str
    max_undonated_fraction: float = 1.0
    hits: int = 0  # filled by ircheck; stale waivers are warned about


@dataclass
class HbmBaseline:
    """Recorded ``hbm_gb_per_step`` for one (model, platform, mesh,
    batch) lowering — the regression ledger the ±tolerance gate compares
    against, so the 76 GB class of numbers can only go down.

    ``wire_gb_per_step`` (optional, ISSUE 15) is the backend-neutral
    twin: logical traced-step bytes at the avals' own dtypes
    (ircheck.jaxpr_wire_bytes) — the number the bf16 diet provably
    moves even where a backend's float normalization blinds cost
    analysis to dtype (this box's cpu backend does exactly that)."""

    model: str
    platform: str  # jax backend the number was recorded on (cpu/tpu/...)
    batch: int
    hbm_gb_per_step: float
    mesh: str = "1x1"
    note: str = ""
    wire_gb_per_step: float | None = None


@dataclass
class DietTarget:
    """A declared mixed-precision diet floor: the case's bf16-policy
    trace must show at least ``min_reduction`` lower wire bytes than
    its f32 twin (``ircheck --diet``). The acceptance numbers of
    ISSUE 15 live here instead of in prose."""

    model: str
    min_reduction: float
    reason: str = ""


@dataclass
class DtypeWaiver:
    """A justified f32 pixel input on the H2D boundary of ``model``'s
    step (the IR twin of JX114) — e.g. feeds with no uint8 source.
    ``reason`` is mandatory."""

    model: str
    reason: str
    hits: int = 0


@dataclass
class IRCheckConfig:
    """Knobs + ledgers for the compiled-IR contract gate
    (``tools/jaxlint/ircheck.py``), loaded from the ``[ircheck]`` table
    and the ``[[ircheck.donation]]`` / ``[[ircheck.hbm]]`` /
    ``[[ircheck.dtype]]`` arrays of ``jaxlint.toml``."""

    # minimum donated fraction of state BYTES that must be aliased
    # input->output in the compiled executable (JX104 enforcement)
    donation_min_fraction: float = 0.99
    # HBM ledger gate: fail when measured > baseline * (1 + tolerance);
    # nudge to re-record when measured < baseline * (1 - tolerance)
    hbm_tolerance: float = 0.05
    # ircheck CASE names cheap enough for the tier-1/`make check`
    # subset (a case may cover several registry entries, e.g. "dcgan")
    fast_models: list[str] = field(default_factory=lambda: [
        "lenet5", "lenet5_tf", "dcgan",
    ])
    # registry-median floor for the --diet sweep (full runs only)
    diet_median_min: float = 0.25
    donation: list[DonationWaiver] = field(default_factory=list)
    hbm: list[HbmBaseline] = field(default_factory=list)
    dtype: list[DtypeWaiver] = field(default_factory=list)
    diet: list[DietTarget] = field(default_factory=list)

    def hbm_baseline(self, model: str, platform: str, mesh: str,
                     batch: int) -> HbmBaseline | None:
        for b in self.hbm:
            if (b.model, b.platform, b.mesh, b.batch) == \
                    (model, platform, mesh, batch):
                return b
        return None

    def donation_waiver(self, model: str) -> DonationWaiver | None:
        for w in self.donation:
            if w.model == model:
                return w
        return None

    def dtype_waiver(self, model: str) -> DtypeWaiver | None:
        for w in self.dtype:
            if w.model == model:
                return w
        return None

    def diet_target(self, model: str) -> DietTarget | None:
        for t in self.diet:
            if t.model == model:
                return t
        return None


def load_ircheck_config(path: str | Path | None) -> IRCheckConfig:
    """Build an IRCheckConfig from ``jaxlint.toml`` (defaults if
    absent). Donation/dtype waivers without a ``reason`` are rejected —
    same contract as the ``[[baseline]]`` ledger."""
    cfg = IRCheckConfig()
    if path is None:
        return cfg
    path = Path(path)
    if not path.exists():
        return cfg
    data = tomllib.loads(path.read_text())
    table = data.get("ircheck", {})
    for name in ("donation_min_fraction", "hbm_tolerance",
                 "diet_median_min"):
        if name in table:
            setattr(cfg, name, float(table[name]))
    if "fast_models" in table:
        cfg.fast_models = [str(x) for x in table["fast_models"]]
    for entry in table.get("donation", []):
        if "model" not in entry:
            raise TomlError(f"ircheck.donation entry needs 'model': {entry!r}")
        if not str(entry.get("reason", "")).strip():
            raise TomlError(
                f"ircheck.donation waiver for {entry['model']!r} has no "
                "'reason' — every donation exception must say why")
        cfg.donation.append(DonationWaiver(
            model=entry["model"], reason=entry["reason"],
            max_undonated_fraction=float(
                entry.get("max_undonated_fraction", 1.0)),
        ))
    for entry in table.get("hbm", []):
        for req in ("model", "platform", "batch", "hbm_gb_per_step"):
            if req not in entry:
                raise TomlError(
                    f"ircheck.hbm baseline needs {req!r}: {entry!r}")
        wire = entry.get("wire_gb_per_step")
        cfg.hbm.append(HbmBaseline(
            model=entry["model"], platform=entry["platform"],
            batch=int(entry["batch"]),
            hbm_gb_per_step=float(entry["hbm_gb_per_step"]),
            mesh=str(entry.get("mesh", "1x1")),
            note=str(entry.get("note", "")),
            wire_gb_per_step=float(wire) if wire is not None else None,
        ))
    for entry in table.get("diet", []):
        for req in ("model", "min_reduction"):
            if req not in entry:
                raise TomlError(
                    f"ircheck.diet entry needs {req!r}: {entry!r}")
        cfg.diet.append(DietTarget(
            model=entry["model"],
            min_reduction=float(entry["min_reduction"]),
            reason=str(entry.get("reason", "")),
        ))
    for entry in table.get("dtype", []):
        if "model" not in entry:
            raise TomlError(f"ircheck.dtype entry needs 'model': {entry!r}")
        if not str(entry.get("reason", "")).strip():
            raise TomlError(
                f"ircheck.dtype waiver for {entry['model']!r} has no "
                "'reason' — every f32-pixel exception must say why")
        cfg.dtype.append(DtypeWaiver(
            model=entry["model"], reason=entry["reason"],
        ))
    return cfg


# -------------------------------------------------------- shardcheck config


@dataclass
class PartitionRule:
    """One row of the declarative sharding rules table
    (``[[shardcheck.rule]]``): a regex over '/'-joined state-leaf paths
    (``params/Conv_0/kernel``, ``opt_state/0/mu/Dense_0/bias`` …) and
    the PartitionSpec it prescribes. ``spec`` is a tiny DSL whose ONE
    interpreter is the runtime sharding engine
    (``deepvision_tpu/core/sharding.py`` — trainer, checkpoint restore
    and shardcheck's ZeRO-1 compile all call it):

    - ``"replicated"`` — ``P()`` on every matched leaf
    - ``"data"`` / ``"data,*"`` … — per-dim axis entries (``*`` = None)
    - ``"largest(data)"`` — shard the LARGEST axis-divisible dim
      (``core.step.weight_update_sharding``'s ZeRO-1 rule)

    shardcheck's coverage audit asserts every leaf of every registry
    model matches a rule (first match wins, like the baseline ledger);
    ``largest(...)`` rules additionally mark the ZeRO-1 worklist the
    ``--zero1-ready`` residency table quantifies."""

    pattern: str
    spec: str
    reason: str = ""
    hits: int = 0  # filled by shardcheck; stale rules are warned about

    def matches(self, leaf_path: str) -> bool:
        return re.search(self.pattern, leaf_path) is not None


@dataclass
class CommsBaseline:
    """Recorded collective-traffic bytes for one (model, platform,
    mesh, batch) compile: ``coll_gb_per_step`` sums the output bytes of
    every collective instruction (all-reduce / all-gather /
    reduce-scatter / all-to-all / collective-permute) in the optimized
    SPMD module — per-participant bytes, the ratchet twin of the
    ``[[ircheck.hbm]]`` rows for the interconnect.

    ``zero1 = true`` rows key the ZeRO-1 compile (``shardcheck
    --zero1``): the weight-update sharding legitimately trades
    all-reduce for reduce-scatter/all-gather traffic, so replicated
    and ZeRO-1 programs ratchet against separate baselines."""

    model: str
    platform: str
    batch: int
    coll_gb_per_step: float
    mesh: str = "2x1"
    note: str = ""
    zero1: bool = False


@dataclass
class ReshardWaiver:
    """A justified implicit-resharding exception: ``model``'s compiled
    step at ``mesh`` is allowed collective opcode ``op`` (fnmatch)
    beyond the expected data-parallel set. ``reason`` is mandatory —
    a deliberate reshard (ZeRO-1's reduce-scatter + all-gather, spatial
    halo exchange) is declared here; an accidental one is a bug."""

    model: str
    op: str
    reason: str
    mesh: str = "*"
    hits: int = 0


@dataclass
class ShardCheckConfig:
    """Knobs + ledgers for the SPMD/collective-traffic gate
    (``tools/jaxlint/shardcheck.py``), loaded from the ``[shardcheck]``
    table and the ``[[shardcheck.rule]]`` / ``[[shardcheck.comms]]`` /
    ``[[shardcheck.reshard]]`` arrays of ``jaxlint.toml``."""

    # comms ledger gate: fail when measured > baseline * (1 + tol);
    # nudge to re-record when measured < baseline * (1 - tol)
    comms_tolerance: float = 0.05
    # case names cheap enough for the tier-1/`make lint-comms` subset
    fast_models: list[str] = field(default_factory=lambda: [
        "lenet5", "lenet5_tf", "dcgan",
    ])
    # mesh shapes ("NxM") every case is lowered at; >=2 shapes arm the
    # mesh-generalization gate (collective structure must not depend on
    # the grid extents). Chosen so the data axis divides every case's
    # batch (min registry batch is 2).
    mesh_shapes: list[str] = field(default_factory=lambda: [
        "2x1", "2x2",
    ])
    # collective opcodes a pure data-parallel replicated-params step is
    # EXPECTED to contain (fnmatch): gradient/metric all-reduce. Any
    # other collective in the compiled module is an implicit reshard
    # pjit inserted behind the program's back and needs a waiver.
    expected_collectives: list[str] = field(default_factory=lambda: [
        "all-reduce",
    ])
    rules: list[PartitionRule] = field(default_factory=list)
    comms: list[CommsBaseline] = field(default_factory=list)
    reshard: list[ReshardWaiver] = field(default_factory=list)

    def comms_baseline(self, model: str, platform: str, mesh: str,
                       batch: int, *,
                       zero1: bool = False) -> CommsBaseline | None:
        for b in self.comms:
            if (b.model, b.platform, b.mesh, b.batch, b.zero1) == \
                    (model, platform, mesh, batch, zero1):
                return b
        return None

    def reshard_waiver(self, model: str, mesh: str,
                       op: str) -> ReshardWaiver | None:
        for w in self.reshard:
            if w.model == model and fnmatch.fnmatch(op, w.op) \
                    and fnmatch.fnmatch(mesh, w.mesh):
                return w
        return None

    def match_rule(self, leaf_path: str) -> PartitionRule | None:
        for r in self.rules:
            if r.matches(leaf_path):
                return r
        return None


def load_shardcheck_config(path: str | Path | None) -> ShardCheckConfig:
    """Build a ShardCheckConfig from ``jaxlint.toml`` (defaults if
    absent). Reshard waivers without a ``reason`` and rules with an
    unparseable regex are rejected — same contract as every other
    ledger in this file."""
    cfg = ShardCheckConfig()
    if path is None:
        return cfg
    path = Path(path)
    if not path.exists():
        return cfg
    data = tomllib.loads(path.read_text())
    table = data.get("shardcheck", {})
    if "comms_tolerance" in table:
        cfg.comms_tolerance = float(table["comms_tolerance"])
    if "fast_models" in table:
        cfg.fast_models = [str(x) for x in table["fast_models"]]
    if "mesh_shapes" in table:
        cfg.mesh_shapes = [str(x) for x in table["mesh_shapes"]]
    if "expected_collectives" in table:
        cfg.expected_collectives = [
            str(x) for x in table["expected_collectives"]]
    for entry in table.get("rule", []):
        for req in ("pattern", "spec"):
            if req not in entry:
                raise TomlError(
                    f"shardcheck.rule entry needs {req!r}: {entry!r}")
        try:
            re.compile(str(entry["pattern"]))
        except re.error as e:
            raise TomlError(
                f"shardcheck.rule pattern {entry['pattern']!r} is not a "
                f"valid regex: {e}") from None
        cfg.rules.append(PartitionRule(
            pattern=str(entry["pattern"]), spec=str(entry["spec"]),
            reason=str(entry.get("reason", "")),
        ))
    for entry in table.get("comms", []):
        for req in ("model", "platform", "batch", "coll_gb_per_step"):
            if req not in entry:
                raise TomlError(
                    f"shardcheck.comms baseline needs {req!r}: {entry!r}")
        cfg.comms.append(CommsBaseline(
            model=entry["model"], platform=entry["platform"],
            batch=int(entry["batch"]),
            coll_gb_per_step=float(entry["coll_gb_per_step"]),
            mesh=str(entry.get("mesh", "2x1")),
            note=str(entry.get("note", "")),
            zero1=bool(entry.get("zero1", False)),
        ))
    for entry in table.get("reshard", []):
        for req in ("model", "op"):
            if req not in entry:
                raise TomlError(
                    f"shardcheck.reshard entry needs {req!r}: {entry!r}")
        if not str(entry.get("reason", "")).strip():
            raise TomlError(
                f"shardcheck.reshard waiver for {entry['model']!r} "
                f"{entry['op']!r} has no 'reason' — every deliberate "
                "reshard must say why it is intended")
        cfg.reshard.append(ReshardWaiver(
            model=entry["model"], op=entry["op"],
            reason=entry["reason"], mesh=str(entry.get("mesh", "*")),
        ))
    return cfg
