"""Stub cluster worker: the member protocol without jax.

Launched by ``tests/test_cluster.py`` through a ClusterSupervisor with
an injected ``worker_cmd`` — it heartbeats, answers the preemption
notice with the real save-barrier file protocol (barrier marker ->
arrive -> commit), and exits with the launcher's contract codes
(0 done / 143 preempted), so supervision (liveness, stragglers,
chaos delivery, elastic relaunch, counters) is testable in
milliseconds-per-step instead of jax-import-seconds. Not a test
module itself.

argv: STEPS STEP_SECONDS [resume]
env:  the DVTPU_CLUSTER_* contract train_dist.py exports.
"""

import json
import os
import signal
import sys
import time
from pathlib import Path

from deepvision_tpu.resilience.cluster import ClusterMember


def main() -> int:
    steps = int(sys.argv[1])
    step_s = float(sys.argv[2])
    member = ClusterMember.from_env()
    assert member is not None, "stub needs the DVTPU_CLUSTER_* env"
    # late-start drill ("INDEX:SECONDS"): that host is still importing,
    # with no handler installed and no beat written, for that long
    late = os.environ.get("STUB_LATE_HOST", "")
    if late and int(late.split(":")[0]) == member.host:
        time.sleep(float(late.split(":")[1]))
    preempt = {"flag": False}
    signal.signal(signal.SIGTERM, lambda *_: preempt.update(flag=True))

    # crash drill: die ungracefully at step N on the FIRST incarnation
    crash_at = int(os.environ.get("STUB_CRASH_AT", "0"))
    # wedge drill: stop beating forever at step N (heartbeat-dead food)
    hang_at = int(os.environ.get("STUB_HANG_AT", "0"))
    state = Path(os.environ.get("STUB_STATE", "")) if \
        os.environ.get("STUB_STATE") else None
    start = 0
    if state is not None and state.exists():
        start = json.loads(state.read_text()).get("step", 0)

    stop = None
    for cur in range(start + 1, steps + 1):
        member.beat(cur, epoch=0, status="run", force=True)
        if crash_at and cur == crash_at and not (
                state is not None and state.exists()):
            if state is not None:
                state.write_text(json.dumps({"step": cur - 1}))
            os._exit(1)  # ungraceful: no barrier, no commit
        if hang_at and cur == hang_at:
            time.sleep(3600)  # wedged: no beats, no exit
        if preempt["flag"] and member.read_barrier() is None:
            member.write_barrier(0, cur + member.barrier_lead)
        mark = member.read_barrier()
        if mark is not None and stop is None:
            stop = mark.get("stop_step", cur)
        if stop is not None and cur >= stop:
            member.arrive(stop)
            if member.await_all_arrived(
                    timeout_s=member.barrier_timeout_s):
                if state is not None:
                    state.write_text(json.dumps({"step": stop}))
                member.mark_committed(0, stop)
            return 143
        time.sleep(step_s)
    member.beat(steps, epoch=0, status="done", force=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
