"""Traffic kind ``serve_open_loop``: an in-process ``InferenceEngine``
under open-loop arrivals at a fixed rate.

The system under test is the program's ``load_served`` +
``InferenceEngine`` (queue, batch formation, bucket ladder, padding,
H2D, compiled forward with its in-graph post-process, host
post-process); the window drives ``InferenceEngine.submit``. The HTTP
front of ``serve.py`` is bypassed. The benchmark brings the weights, the
images and the schedule (all from ``--seed``) and the clock.

The schedule: ``round(rate x seconds)`` requests whose gaps are the
quantiles of the exponential distribution at that rate, shuffled by the
seed. Every seed thus offers the same number of requests and the same
set of gaps in another order: Poisson-like bursts, but the work does not
depend on the seed. A request's latency runs from the moment it was due
to its completion; how late the generator sent it is reported apart.

A request the engine refuses (``ShedError``) is offered again, as the
error's ``retry_after_s`` asks of a client: the generator holds at it,
in order, until an answer has come back or that time has passed, and
the wait reads as latency. The generator runs in the engine's process,
so a stall of the whole process makes every request of those seconds
due at once; a client that took the refusals of that burst for failures
would report its own stall as the engine's. Only the answers that the
comparison may read are kept: a server hands an answer over and forgets
it, and a heap of five thousand kept answers is the client's own.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time

import numpy as np

from benchmark.harness import checks
from benchmark.harness.device import memory_peak_bytes, peaks
from benchmark.reference import plain

GRACE_S = 60.0      # wait this long past the close for a late answer


def schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's opening) of every request."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()            # the last one is due at the close
    np.random.default_rng([int(seed), 1]).shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def build_engine(cfg: dict, traffic: dict, weights):
    """The program's objects: -> (engine, served model). Tests plant
    their faults by wrapping what this returns."""
    from deepvision_tpu.serve.engine import InferenceEngine
    from deepvision_tpu.serve.models import load_served

    prog = cfg["program"]
    served = load_served(
        prog["model"], None, input_size=cfg["input_size"],
        num_classes=cfg["num_classes"],
        score_thresh=cfg["score_threshold"],
        iou_thresh=cfg["iou_threshold"])
    checks.require_same_tree(served.variables, weights, "variable")
    served = dataclasses.replace(served, variables=weights)
    engine = InferenceEngine(
        [served], buckets=tuple(traffic["buckets"]),
        batch_window_s=traffic["batch_window_ms"] / 1e3,
        max_queue=traffic["max_queue"], freeze_cache=True)
    return engine, served


class _Client:
    """Submits on schedule, offers a refused request again, stamps each
    completion and keeps the answers at the indices ``keep``."""

    def __init__(self, engine, images, picks, keep):
        from deepvision_tpu.serve.engine import ShedError

        self.shed_error = ShedError     # imported outside the window
        self.engine, self.images, self.picks = engine, images, picks
        self.keep = frozenset(int(i) for i in keep)
        n = len(picks)
        self.done_at = np.full(n, np.nan)
        self.sent_late = np.zeros(n)    # first offer - due, less the holds
        self.held = np.zeros(n)         # first offer to admission
        self.refusals = np.zeros(n, np.int64)
        self.results: dict = {}
        self.refused = 0                # never admitted by the end
        self._holds: list = []          # (start, end) of each hold, in order
        self._answers = 0               # futures resolved, either way
        self._cond = threading.Condition()

    def _finish(self, i, fut):
        t = time.perf_counter()
        exc = fut.exception()
        with self._cond:
            if exc is None:
                self.done_at[i] = t
                if i in self.keep:
                    self.results[i] = fut.result()
            self._answers += 1
            self._cond.notify_all()

    def _held_since(self, t: float) -> float:
        """Seconds after ``t`` that the generator spent holding refused
        requests: the engine's doing, not the generator's lateness."""
        total = 0.0
        for start, end in reversed(self._holds):
            if end <= t:
                break
            total += end - max(start, t)
        return total

    def _offer(self, i, give_up_at: float):
        """-> the request's future, or None where the engine still
        refuses it at ``give_up_at``."""
        while True:
            seen = self._answers    # an answer after this wakes the hold
            try:
                return self.engine.submit(self.images[self.picks[i]])
            except self.shed_error as e:
                self.refusals[i] += 1
                now = time.perf_counter()
                if now >= give_up_at:
                    return None
                with self._cond:
                    self._cond.wait_for(
                        lambda: self._answers != seen,
                        min(e.retry_after_s, give_up_at - now))

    def drive(self, due: np.ndarray, t0: float, give_up_at: float) -> None:
        for i, d in enumerate(due):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            first = time.perf_counter()
            self.sent_late[i] = first - (t0 + d) - self._held_since(t0 + d)
            fut = self._offer(i, give_up_at)
            if self.refusals[i]:
                now = time.perf_counter()
                self.held[i] = now - first
                self._holds.append((first, now))
            if fut is None:     # it and everything behind it: never sent
                self.refused = len(due) - i
                return
            fut.add_done_callback(lambda f, i=i: self._finish(i, f))

    def wait(self, until: float) -> None:
        with self._cond:
            self._cond.wait_for(
                lambda: self._answers + self.refused == len(self.picks),
                max(0.0, until - time.perf_counter()))


class _CollectorClock:
    """Times every garbage collection while it is installed (the
    window): (start, end, generation). It changes nothing of the
    collector: the engine's process keeps the one a server has."""

    def __init__(self):
        self.events: list = []
        self._start = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.events.append((self._start, time.perf_counter(),
                                info["generation"]))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def _telemetry(engine) -> dict:
    t = engine.telemetry
    return {"rows": t.rows, "batches": t.batches,
            "device_s": t.device_time.total_s,
            "queue_s": t.queue_wait.total_s,
            "queued": t.queue_wait.count}


def _warm(engine, images, buckets) -> None:
    """One batch through every bucket by the request path itself, so
    that no first execution falls into the window."""
    engine.pause()
    for b in sorted(buckets, reverse=True):
        futs = [engine.submit(images[i % len(images)]) for i in range(b)]
        engine.resume()
        for f in futs:
            f.result(timeout=600)
        engine.pause()
    engine.resume()


def bring_up(cfg, traffic, ref, seed: int):
    """Weights and images from the seed, the engine built over them and
    warm. -> (engine, served model, the weights on the host for the
    comparison after the engine is gone, the image pool)."""
    import jax

    weights = jax.jit(lambda k: ref.make_weights(cfg, k))(
        plain.seed_key(seed))
    host_weights = jax.tree.map(np.asarray, weights)
    images = ref.make_images(cfg, seed, traffic["image_pool"])
    engine, served = build_engine(cfg, traffic, weights)
    del weights
    _warm(engine, images, traffic["buckets"])
    return engine, served, host_weights, images


REF_CHUNK = 8       # images a reference call: one shape, one program


def reference_candidates(cfg, ref, variables, images, picks, nm) -> dict:
    """``{pool index: (boxes, scores, classes)}`` of the images behind
    ``picks``, the reference run in blocks of ``REF_CHUNK`` images (the
    last block filled up by repeating, so that every run of every seed
    uses the one compiled shape)."""
    import jax.numpy as jnp

    uniq = sorted(set(picks))
    cand = {}
    for k in range(0, len(uniq), REF_CHUNK):
        chunk = uniq[k:k + REF_CHUNK]
        padded = chunk + [chunk[-1]] * (REF_CHUNK - len(chunk))
        b, s, c = ref.candidates(cfg, variables, jnp.asarray(images[padded]),
                                 nm)
        for j, idx in enumerate(chunk):
            cand[idx] = (b[j], s[j], c[j])
    return cand


def reference_answers(cfg, ref, variables, images, picks, nm) -> list:
    """What the reference itself would serve for ``picks`` in numerics
    ``nm``: a control, or the stated precision, put in the program's
    place."""
    cand = reference_candidates(cfg, ref, variables, images, picks, nm)
    out = []
    for pick in picks:
        b, s, c = cand[pick]
        keep = ref.suppress(cfg, b, s)
        out.append({"boxes": b[keep], "scores": s[keep],
                    "classes": c[keep]})
    return out


def compare(cfg, ref, cand: dict, picks, results) -> dict:
    """The numbers compared: every sampled request's answer (``results``:
    what the engine served, or a control's answers put in its place)
    against ``cand``, the float32 reference's candidates of the same
    image, and the reference's own exact suppression.

    A served detection's gap is its distance to the nearest candidate:
    the larger of the score's gap and the widest corner's (corners are
    shares of the image). ``det_gap_p50``, ``det_gap_p90`` and
    ``det_gap_p99`` are quantiles over all served detections (the very
    widest swings too much from seed to seed to carry a limit),
    ``class_gap`` the share whose class is not that candidate's,
    ``set_gap`` the served detections the reference's suppression did
    not keep plus the kept ones not served, over the kept."""
    gaps = []
    off_set, kept_total, served_total, cls_off, over_cap = 0, 0, 0, 0, 0
    for pick, res in zip(picks, results):
        b, s, c = cand[pick]
        # beyond the cap the program's suppression is no longer the
        # exact greedy one: such an image is not traffic for this cell
        over_cap += int(np.sum(s >= cfg["score_threshold"])
                        > cfg["nms_candidate_cap"])
        kept = set(ref.suppress(cfg, b, s).tolist())
        kept_total += len(kept)
        sb = np.asarray(res["boxes"], np.float32).reshape(-1, 4)
        ss = np.asarray(res["scores"], np.float32)
        sc = np.asarray(res["classes"])
        served_total += len(ss)
        matched = set()
        if len(ss):
            scale = np.maximum(1.0, np.abs(b))
            gap = np.maximum(
                np.abs(ss[:, None] - s[None, :]),
                np.max(np.abs(sb[:, None, :] - b[None, :, :]) / scale[None],
                       axis=-1))
            j = np.argmin(gap, axis=1)
            gaps.append(gap[np.arange(len(ss)), j])
            cls_off += int(np.sum(c[j] != sc))
            matched = set(j.tolist())
        off_set += len(matched ^ kept)
    gaps = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"det_gap": float(gaps.max()),
            "det_gap_p50": float(np.median(gaps)),
            "det_gap_p90": float(np.percentile(gaps, 90)),
            "det_gap_p99": float(np.percentile(gaps, 99)),
            "set_gap": off_set / max(1, kept_total),
            "class_gap": cls_off / max(1, served_total),
            "served_detections": served_total, "ref_kept": kept_total,
            "over_cap_images": over_cap}


EXCESS_QUANTILES = ("det_gap_p50", "det_gap_p90", "det_gap_p99")


def precision_excess(got: dict, stated: dict) -> float:
    """How much more rounding noise the answers carry than the stated
    precision itself does, as a share of that noise's variance.

    ``got`` and ``stated`` are :func:`compare` of the answers and of the
    reference computed in the configuration's ``stated_numerics`` put in
    their place, both against the float32 reference. Two sound
    computations at one precision round independently and lie equally
    far from float32, so the ratio of their gaps is 1 and this reads 0
    (to the sampling noise of some thousands of detections); storage
    one step down adds as much noise again and reads about 1. The ratio
    is the geometric mean over three quantiles of the gap, squared
    because independent roundings add in variance."""
    ratio = 1.0
    for q in EXCESS_QUANTILES:
        ratio *= got[q] / max(stated[q], 1e-30)
    return ratio ** (2.0 / len(EXCESS_QUANTILES)) - 1.0


def judge(cfg, ref, variables, images, picks, results) -> dict:
    """Every number of the serving comparison for one set of answers:
    :func:`compare` against the float32 reference, and where the
    configuration names ``stated_numerics`` the ``precision_excess``
    over the reference in those numerics."""
    cand = reference_candidates(cfg, ref, variables, images, picks,
                                plain.NUMERICS[cfg["reference_numerics"]])
    got = compare(cfg, ref, cand, picks, results)
    if cfg.get("stated_numerics"):
        stated = reference_answers(
            cfg, ref, variables, images, picks,
            plain.NUMERICS[cfg["stated_numerics"]])
        got["precision_excess"] = precision_excess(
            got, compare(cfg, ref, cand, picks, stated))
    return got


def serve_checks(cfg, got: dict) -> list:
    """Each compared number beside its limit."""
    limits = cfg["limits"]["serve"]
    return [checks.Check(k, got[k], limits[k]) for k in limits] + [
        checks.Check("no_detection_served",
                     0.0 if got["served_detections"] else 1.0, 0.0),
        checks.Check("over_cap_images", float(got["over_cap_images"]), 0.0)]


def sample_order(n: int, seed: int) -> np.ndarray:
    """All request indices in the seed's order: the sample is drawn from
    its front, and the answers at its first ``KEPT_PER_CHECKED x
    checked_requests`` indices are the ones a window keeps."""
    return np.random.default_rng([int(seed), 2]).permutation(n)


KEPT_PER_CHECKED = 4


def window(engine, images, rate: float, seconds: float, seed: int,
           checked: int, trace_after: float | None = None,
           trace_dir: str = "", grace_s: float = GRACE_S) -> dict:
    """Offer ``rate`` for ``seconds`` and wait for the answers, at most
    ``grace_s`` past the close. ``sample`` names the ``checked``
    answered requests the comparison reads: the first of the seed's
    order that were answered, among the answers kept. With
    ``trace_after`` the profiler starts that many seconds into the
    window and stops once every answer is in (writing the profile out
    stalls the host for seconds, which inside the window would be read
    as the engine's queue); the engine's counters are then read up to
    the profiler's start, whose own stall they would otherwise hold."""
    import jax

    due = schedule(rate, seconds, seed)
    picks = np.random.default_rng([int(seed), 3]).integers(
        0, len(images), size=len(due))
    kept = sample_order(len(due), seed)[:KEPT_PER_CHECKED * checked]
    client = _Client(engine, images, picks, kept)
    counted = {}
    tracer = None
    if trace_after is not None:
        # no Python tracer: with it this host-bound engine ran three to
        # four times slower (PERF.md, Findings, PR 23)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0

        def start():
            counted.update(_telemetry(engine))
            jax.profiler.start_trace(trace_dir, profiler_options=options)

        tracer = threading.Timer(trace_after, start)
    before = _telemetry(engine)
    with _CollectorClock() as collections:
        t0 = time.perf_counter()
        if tracer is not None:
            tracer.start()
        client.drive(due, t0, t0 + seconds + grace_s)
        client.wait(t0 + seconds + grace_s)
        t_end = time.perf_counter()
    if tracer is not None:
        tracer.join()
        jax.profiler.stop_trace()
    after = counted or _telemetry(engine)
    answered = ~np.isnan(client.done_at)
    done = np.sort(client.done_at[answered])
    last_done = done[-1] if len(done) else t_end
    latency = np.where(answered, client.done_at - (t0 + due),
                       t_end - (t0 + due))
    # the two stalls a run names, as intervals: the generator's largest
    # lateness and the longest the engine went without completing
    # anything (a sound run's is one dispatcher cycle, a stalled run's
    # seconds)
    latest = int(np.argmax(client.sent_late))
    stalls = [(t0 + due[latest], t0 + due[latest] + client.sent_late[latest])]
    done_gap_s = 0.0
    if len(done) > 1:
        gap = int(np.argmax(np.diff(done)))
        stalls.append((done[gap], done[gap + 1]))
        done_gap_s = float(done[gap + 1] - done[gap])
    gen2 = [(a, b) for a, b, g in collections.events if g == 2]
    return {"due": due, "picks": picks, "answered": answered,
            "latency": latency, "late": client.sent_late,
            "held": client.held, "refusals": client.refusals,
            "refused": client.refused, "results": client.results,
            "sample": sorted([int(i) for i in kept if answered[i]][:checked]),
            "window_s": max(seconds, last_done - t0),
            "done_gap_s": done_gap_s,
            "gc_s_max": max((b - a for a, b, _ in collections.events),
                            default=0.0),
            "gc_gen2_count": len(gen2),
            "gc_gen2_s_max": max((b - a for a, b in gen2), default=0.0),
            "stall_in_gc": any(a < end and b > start
                               for start, end in stalls for a, b in gen2),
            "telemetry": {k: after[k] - before[k] for k in after}}


def client_notes(w: dict) -> dict:
    """What the client saw of refusals and of the collector in a
    window: requests refused at least once, refusals in all, the longest
    a request was held from its first offer to its admission, requests
    never admitted by the end (0 in a sound run); the longest
    collection of any generation, the generation-2 collections, the
    longest of them, and whether one overlaps the window's largest
    generator lateness or its longest gap between answers."""
    return {"refused": w["refused"],
            "offered_again": int(np.sum(w["refusals"] > 0)),
            "offers_refused": int(w["refusals"].sum()),
            "held_ms_max": float(w["held"].max() * 1e3),
            "gc_ms_max": w["gc_s_max"] * 1e3,
            "gc_gen2_count": w["gc_gen2_count"],
            "gc_gen2_ms_max": w["gc_gen2_s_max"] * 1e3,
            "stall_in_gc": w["stall_in_gc"]}


def run(run) -> dict:
    import jax

    cfg, traffic, ref = run.cell.config, run.cell.traffic, run.reference
    chips = run.cell.chips
    engine, served, host_weights, images = bring_up(cfg, traffic, ref,
                                                    run.seed)

    # ---- the window
    compiles_before = run.compiles.count
    compile_s = run.compiles.seconds
    setup_s = run.setup_seconds()
    w = window(engine, images, traffic["rate"], run.seconds, run.seed,
               traffic["checked_requests"],
               trace_after=max(0.0, run.seconds - traffic["trace_seconds"])
               if run.trace else None, trace_dir=run.trace_dir)
    compiles_in_window = run.compiles.count - compiles_before

    due, answered, latency, late = (w["due"], w["answered"], w["latency"],
                                    w["late"])
    limit_s = traffic["limit_ms"] / 1e3
    good = int(np.sum(answered & (latency <= limit_s)))
    client = client_notes(w)
    print(f"[generator] requests {len(due)} late_ms p50 "
          f"{np.median(late) * 1e3:.3f} p99 "
          f"{np.percentile(late, 99) * 1e3:.3f} max {late.max() * 1e3:.3f} "
          + " ".join(f"{k} {v:.1f}" if isinstance(v, float) else f"{k} {v}"
                     for k, v in client.items())
          + f" longest_gap_between_answers_ms {w['done_gap_s'] * 1e3:.1f}",
          file=sys.stderr, flush=True)

    memory = memory_peak_bytes(jax.devices()[:chips])
    chosen = w["sample"]
    results = [w["results"][i] for i in chosen]
    engine.close()
    del engine, served

    got = judge(cfg, ref, jax.device_put(host_weights), images,
                [int(w["picks"][i]) for i in chosen], results)
    failed = int(len(due) - answered.sum())     # due and never answered
    result_checks = serve_checks(cfg, got) + [
        checks.Check("unanswered", float(failed), 0.0)]

    d = w["telemetry"]
    window_s = w["window_s"]
    return {
        "end_to_end": {
            "serve_p95_ms": float(np.percentile(latency, 95) * 1e3),
            # every request due in the window counts once, good or not:
            # the offered rate times the share that met the limit
            "serve_goodput": good / run.seconds / chips,
            "setup_s": setup_s},
        "attempted": len(due),
        "failed": failed,
        "checks": result_checks,
        "memory_peak_bytes": memory,
        "compiles_in_window": compiles_in_window,
        "serve": {
            "rows": d["rows"], "device_s": d["device_s"], "chips": chips,
            "flops_per_image": ref.forward_flops_per_image(cfg),
            "peak_flops": peaks(run.device["kind"])["bf16_flops_per_s"]
            if run.device["platform"] == "tpu" else None,
            "queue_wait_ms": 1e3 * d["queue_s"] / d["queued"]
            if d["queued"] else None,
            "batch_rows_mean": d["rows"] / d["batches"]
            if d["batches"] else None},
        "compile_s": compile_s,
        "notes": {
            "requests": len(due), "good": good, **client,
            "window_s": window_s,
            "latency_ms_p50": float(np.median(latency) * 1e3),
            "latency_ms_p99": float(np.percentile(latency, 99) * 1e3),
            "latency_ms_max": float(latency.max() * 1e3),
            "generator_late_ms_p99": float(np.percentile(late, 99) * 1e3),
            "generator_late_ms_max": float(late.max() * 1e3),
            "answer_gap_ms_max": w["done_gap_s"] * 1e3,
            "batches": d["batches"], "rows": d["rows"],
            "device_s": d["device_s"], "det_gap_max": got["det_gap"],
            "det_gap_p50": got["det_gap_p50"],
            "det_gap_p99": got["det_gap_p99"],
            "served_detections": got["served_detections"],
            "ref_kept": got["ref_kept"], "checked": len(chosen)},
    }


def calibrate(cell, seeds, *, control: bool, seconds, sweep=(), **_):
    """Readings for the limits (``benchmark/calibrate.py``): per seed a
    short window at the cell's own load and its sampled answers judged
    as a run judges them; with ``control`` the reference in the
    configuration's control numerics put in the program's place and
    judged by the same checks, which it has to fail. ``sweep`` offers
    each of its rates for ``seconds`` on the first seed's engine: the
    knee the cell's rate is four fifths of. Above the knee the engine
    refuses and the client offers again, so nothing is lost and the
    window lengthens (``window_s``) until the last held request is
    answered: the knee is ``done_per_s``, completions over that longer
    window."""
    import jax

    from benchmark.harness import cells

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference_for(cfg, cell.config_name)
    for n, seed in enumerate(seeds):
        engine, served, host_weights, images = bring_up(cfg, traffic, ref,
                                                        seed)
        for rate in sweep if n == 0 else ():
            w = window(engine, images, rate, seconds, seed,
                       traffic["checked_requests"])
            d, lat = w["telemetry"], w["latency"] * 1e3
            yield {"reading": "sweep", "rate": rate,
                   "requests": len(w["due"]), **client_notes(w),
                   "window_s": float(w["window_s"]),
                   "p50_ms": float(np.median(lat)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "max_ms": float(lat.max()),
                   "done_per_s": float(w["answered"].sum() / w["window_s"]),
                   "rows_per_batch": d["rows"] / max(1, d["batches"]),
                   "device_ms_per_batch": 1e3 * d["device_s"]
                   / max(1, d["batches"])}
        w = window(engine, images, traffic["rate"], seconds, seed,
                   traffic["checked_requests"])
        picks = [int(w["picks"][i]) for i in w["sample"]]
        answers = {"program": [w["results"][i] for i in w["sample"]]}
        engine.close()
        del engine, served
        variables = jax.device_put(host_weights)
        if control:
            answers[f"control:{cfg['control']}"] = reference_answers(
                cfg, ref, variables, images, picks,
                plain.NUMERICS[cfg["control"]])
        for reading, results in answers.items():
            got = judge(cfg, ref, variables, images, picks, results)
            yield {"seed": seed, "reading": reading, **got,
                   "correct": checks.verdict(serve_checks(cfg, got))}
