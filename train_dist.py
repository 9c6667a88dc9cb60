#!/usr/bin/env python
"""Multi-host distributed training launcher + cluster supervisor.

The reference's READMEs advertise a ``train_dist.py`` that was never
committed (ref: ResNet/pytorch/README.md:15 — SURVEY §0); this is that
file, TPU-native and preemption-tolerant. Two modes:

**Worker mode** (default) — run the SAME command on every host of a TPU
slice (or a CPU/GPU cluster with explicit coordinator flags):

    # TPU pod slice (all topology auto-detected from the TPU metadata):
    python train_dist.py -m resnet50 --data-dir gs://.../imagenet

    # explicit coordinator (CPU/GPU clusters, local testing):
    python train_dist.py --coordinator host0:1234 --num-processes 2 \
        --process-id 0 -m resnet50 ...

``jax.distributed.initialize`` is ALWAYS called with a bounded
``--init-timeout-s`` (a missing peer used to hang the launcher
forever); on timeout the worker fails with a per-host error naming the
coordinator it waited on and exits 69 (EX_UNAVAILABLE) so a supervisor
can relaunch.

**Supervisor mode** (``--supervise N``) — spawn N worker processes on
this machine and keep the JOB alive through preemption
(``resilience/cluster.py``): per-host heartbeat liveness + straggler
detection (obs gauges ``cluster_host_alive`` / ``cluster_step_lag``), a
SIGTERM preemption notice triggering the coordinated save barrier (all
hosts commit ONE mid-epoch step through the PR 4 manifest machinery),
and deterministic elastic resume — the job relaunches on the surviving
host set with ``--resume``, the loader re-partitions its file shards
over the new host count, and ``KeySeq.skip`` replays identical PRNG
draws. Chaos-testable end to end:

    python train_dist.py --supervise 2 --platform cpu \
        --faults host_preempt@8 -m lenet5 --epochs 3 ...

``--faults`` schedules split automatically: ``host_preempt`` /
``host_stall`` specs drive the supervisor (consulted once per observed
cluster step — drills replay bit-identically), everything else passes
through to the in-job injectors. Exit line:
``[cluster] preemptions=P resumes=R stragglers=S host_deaths=D``.

Mechanics (SURVEY §5.8's DCN mapping): ``jax.distributed.initialize``
joins the processes into one runtime; each process feeds only its own
file shard (``make_dataset(num_process=, process_index=)``) through its
own async device-feed thread; ``core.shard_batch`` assembles per-process
local arrays into global jax.Arrays. Multi-host runs default to
``--prefetch-depth 3`` and to ZeRO-1 cross-replica weight-update
sharding (``--zero1``; ``--no-zero1`` opts out — core/sharding.py).
Everything else — step functions, checkpointing
(Orbax is multi-process-aware), metrics — is identical to single-host
train.py, which worker mode delegates to after initialization.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--coordinator", default=None,
                   help="coordinator address host:port (omit on TPU pods "
                        "— auto-detected from the TPU metadata)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. 'cpu' for local "
                        "multi-process testing)")
    p.add_argument("--init-timeout-s", type=float, default=300.0,
                   help="bound on jax.distributed.initialize — a missing "
                        "peer fails the join with a clear per-host error "
                        "instead of hanging the launcher forever")
    p.add_argument("--supervise", type=int, default=None, metavar="N",
                   help="cluster-supervisor mode: spawn N local worker "
                        "processes, watch heartbeats, deliver/absorb "
                        "preemptions, and relaunch on the surviving "
                        "host set (resilience/cluster.py)")
    p.add_argument("--faults", default=None,
                   help="deterministic fault schedule (resilience/"
                        "faults.py grammar); host_preempt/host_stall "
                        "specs drive the supervisor, the rest pass "
                        "through to the workers' in-job injectors")
    p.add_argument("--fault-seed", type=int, default=0)
    p.add_argument("--heartbeat-timeout-s", type=float, default=120.0,
                   help="supervisor: a host silent this long is dead — "
                        "the generation is killed and relaunched from "
                        "the newest commonly-verified epoch")
    p.add_argument("--straggler-after-s", type=float, default=5.0,
                   help="supervisor: heartbeat age that flags a host as "
                        "a straggler (logged + counted, gauges updated)")
    p.add_argument("--barrier-lead", type=int, default=None,
                   help="coordinated-save stop-step lead (default 64; "
                        "must exceed 2x the trainer's fetch cadence)")
    p.add_argument("--barrier-timeout-s", type=float, default=30.0,
                   help="bound on the all-hosts save-barrier rendezvous; "
                        "on timeout the save is skipped and resume "
                        "falls back to the newest commonly-verified "
                        "epoch")
    p.add_argument("--max-relaunches", type=int, default=3,
                   help="supervisor: crash/dead-host relaunch budget "
                        "(graceful preemptions don't consume it)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="supervisor: serve the obs registry as a "
                        "Prometheus scrape surface on this port "
                        "(GET /metrics: cluster_host_alive / "
                        "cluster_step_lag liveness gauges + the "
                        "sentinel_* SDC counters; 0 = ephemeral)")
    return p


def run_supervisor(dist_args, train_argv) -> int:
    from deepvision_tpu.resilience.cluster import (
        BARRIER_LEAD,
        ClusterSupervisor,
        argv_value,
    )
    from deepvision_tpu.resilience.faults import (
        CLUSTER_SITES,
        FaultInjector,
        split_schedule,
    )

    if dist_args.supervise > 1 and dist_args.platform != "cpu":
        # the N workers are LOCAL processes and none is confined to a
        # chip: on a TPU host they would all open the same chips, and a
        # chip belongs to one process at a time. Ask a child (this
        # process stays off jax) and refuse before anything spawns.
        from deepvision_tpu.startup import probe_devices

        devices = probe_devices()
        if devices["platform"] == "tpu":
            raise SystemExit(
                f"--supervise {dist_args.supervise}: the "
                f"{dist_args.supervise} local workers would all open "
                f"this host's {devices['count']} TPU chip(s), and a "
                "chip belongs to one process at a time. Run one worker "
                "per host (--supervise 1, or worker mode on every "
                "host), or pass --platform cpu for a local drill.")
    injector = None
    if dist_args.faults:
        mine, rest = split_schedule(dist_args.faults, CLUSTER_SITES)
        if mine:
            injector = FaultInjector(mine, seed=dist_args.fault_seed)
            print(f"[cluster] supervisor fault injection armed: "
                  f"{mine!r}", flush=True)
        if rest:
            train_argv = [*train_argv, "--faults", rest,
                          "--fault-seed", str(dist_args.fault_seed)]
    workdir = argv_value(train_argv, "--workdir") or "runs"
    sup = ClusterSupervisor(
        train_argv, dist_args.supervise, workdir,
        launcher=__file__,
        platform=dist_args.platform,
        injector=injector,
        init_timeout_s=dist_args.init_timeout_s,
        heartbeat_timeout_s=dist_args.heartbeat_timeout_s,
        straggler_after_s=dist_args.straggler_after_s,
        barrier_lead=(dist_args.barrier_lead
                      if dist_args.barrier_lead is not None
                      else BARRIER_LEAD),
        barrier_timeout_s=dist_args.barrier_timeout_s,
        max_relaunches=dist_args.max_relaunches,
    )
    server = None
    if dist_args.metrics_port is not None:
        # the multi-host scrape surface, now FEDERATED
        # (obs/distributed.py): the supervisor's own registry (liveness
        # gauges + sentinel_* SDC counters) plus every live host's
        # registry dump — published on the heartbeat cadence into the
        # generation dir — re-exported with {host=<id>} labels and
        # exact counter sums, so one scrape describes the whole fleet
        from deepvision_tpu.obs.metrics import start_exposition_server

        server, port = start_exposition_server(
            dist_args.metrics_port,
            render_fn=sup.render_federated_metrics)
        print(f"[cluster] Prometheus metrics on :{port}/metrics "
              "(federated over the live hosts)", flush=True)
    try:
        return sup.run()
    finally:
        if server is not None:
            server.shutdown()


def run_worker(dist_args, train_argv) -> None:
    import os

    import jax

    if dist_args.platform:
        jax.config.update("jax_platforms", dist_args.platform)
    platform = (dist_args.platform
                or os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" in platform:
        # multiprocess CPU computations need an explicit collectives
        # backend (without it every cross-process psum — orbax's sync
        # barriers included — fails with "Multiprocess computations
        # aren't implemented on the CPU backend")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if dist_args.coordinator:
        kwargs = dict(
            coordinator_address=dist_args.coordinator,
            num_processes=dist_args.num_processes,
            process_id=dist_args.process_id,
        )
    who = (f"process {dist_args.process_id}/{dist_args.num_processes}"
           if dist_args.process_id is not None else "this process")
    # banner BEFORE the join: this jax hard-aborts (absl FATAL,
    # SIGABRT) on DEADLINE_EXCEEDED instead of raising, so the per-host
    # context must already be in the log when the process dies
    print(f"[cluster] {who}: joining coordinator "
          f"{dist_args.coordinator or '<auto-detected>'} "
          f"(--init-timeout-s {dist_args.init_timeout_s:.0f}s; a "
          "DEADLINE_EXCEEDED abort below means a peer never came up)",
          flush=True)
    try:
        # bounded join (jaxlint JX115): a blocking cluster join without
        # a timeout hangs forever on a missing peer
        jax.distributed.initialize(
            initialization_timeout=int(dist_args.init_timeout_s),
            **kwargs)
    except Exception as e:
        print(
            f"[cluster] {who}: jax.distributed.initialize failed after "
            f"--init-timeout-s={dist_args.init_timeout_s:.0f}s against "
            f"coordinator {dist_args.coordinator or '<auto-detected>'}: "
            f"{type(e).__name__}: {e} — are all "
            f"{dist_args.num_processes or '?'} peers up and reachable?",
            file=sys.stderr, flush=True)
        raise SystemExit(69)  # EX_UNAVAILABLE: supervisor may relaunch
    print(
        f"process {jax.process_index()}/{jax.process_count()}: "
        f"{jax.local_device_count()} local / "
        f"{jax.device_count()} global devices"
    )

    if dist_args.faults:
        train_argv = [*train_argv, "--faults", dist_args.faults,
                      "--fault-seed", str(dist_args.fault_seed)]
    if jax.process_count() > 1 and not any(
            a == "--prefetch-depth" or a.startswith("--prefetch-depth=")
            for a in train_argv):
        # deeper default on real multi-host runs: the per-batch
        # make_array_from_process_local_data assembly adds latency
        # jitter that a 2-deep queue lets through to the step
        train_argv += ["--prefetch-depth", "3"]
    if jax.process_count() > 1 and not any(
            a in ("--zero1", "--no-zero1", "--shard-weight-update")
            for a in train_argv):
        # ZeRO-1 default on multi-host: with >1 host the data axis is
        # where the memory is — cross-replica weight-update sharding
        # (arXiv:2004.13336) frees ~(1-1/N) of optimizer state per
        # chip for a reduce-scatter/all-gather swap that is free-to-
        # cheap on TPU ICI. --no-zero1 opts back into the replicated
        # update.
        train_argv += ["--zero1"]
        print("[cluster] multi-host: ZeRO-1 weight-update sharding on "
              "by default (--no-zero1 opts out)", flush=True)

    sys.argv = [sys.argv[0], *train_argv]
    import train

    train.main()


def main():
    # peel off the launcher-only flags, pass the rest through to train.py
    dist_args, train_argv = build_parser().parse_known_args()
    if dist_args.supervise is not None:
        raise SystemExit(run_supervisor(dist_args, train_argv))
    run_worker(dist_args, train_argv)


if __name__ == "__main__":
    main()
