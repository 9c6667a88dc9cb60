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
"""

from __future__ import annotations

import dataclasses
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
    """Submits on schedule and stamps each completion."""

    def __init__(self, engine, images, picks):
        from deepvision_tpu.serve.engine import ShedError

        self.shed_error = ShedError     # imported outside the window
        self.engine, self.images, self.picks = engine, images, picks
        n = len(picks)
        self.done_at = np.full(n, np.nan)
        self.sent_late = np.zeros(n)
        self.results: list = [None] * n
        self.refused = 0
        self._left = n
        self._all_done = threading.Event()
        self._lock = threading.Lock()

    def _finish(self, i, fut):
        t = time.perf_counter()
        exc = fut.exception()
        with self._lock:
            if exc is None:
                self.done_at[i] = t
                self.results[i] = fut.result()
            self._left -= 1
            if not self._left:
                self._all_done.set()

    def drive(self, due: np.ndarray, t0: float) -> None:
        for i, d in enumerate(due):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.sent_late[i] = time.perf_counter() - (t0 + d)
            try:
                fut = self.engine.submit(self.images[self.picks[i]])
            except self.shed_error:
                with self._lock:
                    self.refused += 1
                    self._left -= 1
                    if not self._left:
                        self._all_done.set()
                continue
            fut.add_done_callback(lambda f, i=i: self._finish(i, f))

    def wait(self, until: float) -> None:
        self._all_done.wait(max(0.0, until - time.perf_counter()))


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


def sample_requests(n_done: np.ndarray, count: int, seed: int) -> list:
    """Indices of ``count`` finished requests, drawn from the seed."""
    done = np.flatnonzero(n_done)
    if len(done) <= count:
        return done.tolist()
    rng = np.random.default_rng([int(seed), 2])
    return sorted(rng.choice(done, size=count, replace=False).tolist())


def window(engine, images, rate: float, seconds: float, seed: int,
           trace_after: float | None = None, trace_dir: str = "") -> dict:
    """Offer ``rate`` for ``seconds`` and wait for the answers. With
    ``trace_after`` the profiler starts that many seconds into the
    window and stops once every answer is in (writing the profile out
    stalls the host for seconds, which inside the window would be read
    as the engine's queue); the engine's counters are then read up to
    the profiler's start, whose own stall they would otherwise hold."""
    import jax

    due = schedule(rate, seconds, seed)
    picks = np.random.default_rng([int(seed), 3]).integers(
        0, len(images), size=len(due))
    client = _Client(engine, images, picks)
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
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start()
    client.drive(due, t0)
    client.wait(t0 + seconds + GRACE_S)
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
    return {"due": due, "picks": picks, "answered": answered,
            "latency": latency, "late": client.sent_late,
            "refused": client.refused, "results": client.results,
            "window_s": max(seconds, last_done - t0),
            # the longest the engine went without completing anything: a
            # sound run's is one dispatcher cycle, a stalled run's seconds
            "done_gap_s": float(np.diff(done).max()) if len(done) > 1
            else 0.0,
            "telemetry": {k: after[k] - before[k] for k in after}}


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
               trace_after=max(0.0, run.seconds - traffic["trace_seconds"])
               if run.trace else None, trace_dir=run.trace_dir)
    compiles_in_window = run.compiles.count - compiles_before

    due, answered, latency, late = (w["due"], w["answered"], w["latency"],
                                    w["late"])
    limit_s = traffic["limit_ms"] / 1e3
    good = int(np.sum(answered & (latency <= limit_s)))
    print(f"[generator] requests {len(due)} late_ms p50 "
          f"{np.median(late) * 1e3:.3f} p99 "
          f"{np.percentile(late, 99) * 1e3:.3f} max {late.max() * 1e3:.3f} "
          f"refused {w['refused']} longest_gap_between_answers_ms "
          f"{w['done_gap_s'] * 1e3:.1f}", file=sys.stderr, flush=True)

    memory = memory_peak_bytes(jax.devices()[:chips])
    chosen = sample_requests(answered, traffic["checked_requests"], run.seed)
    results = [w["results"][i] for i in chosen]
    engine.close()
    del engine, served

    got = judge(cfg, ref, jax.device_put(host_weights), images,
                [int(w["picks"][i]) for i in chosen], results)
    result_checks = serve_checks(cfg, got) + [checks.Check(
        "unanswered", float(len(due) - int(answered.sum()) - w["refused"]),
        0.0)]

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
        "failed": int(len(due) - answered.sum()),
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
            "requests": len(due), "good": good, "refused": w["refused"],
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
    knee the cell's rate is four fifths of."""
    import jax

    from benchmark.harness import cells

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference_for(cfg, cell.config_name)
    for n, seed in enumerate(seeds):
        engine, served, host_weights, images = bring_up(cfg, traffic, ref,
                                                        seed)
        for rate in sweep if n == 0 else ():
            w = window(engine, images, rate, seconds, seed)
            d, lat = w["telemetry"], w["latency"] * 1e3
            yield {"reading": "sweep", "rate": rate,
                   "requests": len(w["due"]), "refused": w["refused"],
                   "p50_ms": float(np.median(lat)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "max_ms": float(lat.max()),
                   "done_per_s": float(w["answered"].sum() / w["window_s"]),
                   "rows_per_batch": d["rows"] / max(1, d["batches"]),
                   "device_ms_per_batch": 1e3 * d["device_s"]
                   / max(1, d["batches"])}
        w = window(engine, images, traffic["rate"], seconds, seed)
        chosen = sample_requests(w["answered"], traffic["checked_requests"],
                                 seed)
        picks = [int(w["picks"][i]) for i in chosen]
        answers = {"program": [w["results"][i] for i in chosen]}
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
