"""Traffic kind ``serve_generate``: the program's token generator under
open-loop arrivals of long prompts at a fixed rate.

The system under test is ``serve/generator.GenerationEngine`` (bounded
queue, slot scheduler, the slot cache, the compiled prefill and decode
programs) over the model named by the configuration's ``program``; the
window drives ``GenerationEngine.submit``. ``serve.py``'s stdin front is
bypassed. The benchmark brings the weights, the prompts and the
schedule (all from ``--seed``) and the clock.

The schedule is ``serve_open_loop.schedule``'s: ``round(rate x
seconds)`` requests whose gaps are the exponential distribution's
quantiles at that rate, here in the order of the traffic's
``trace_seed`` and not of ``--seed``. Request ``i`` is prompt ``i``:
the prompts' lengths are the log-uniform distribution's quantiles
between the traffic's bounds, also in the trace's order, their ids
uniform over the vocabulary from ``--seed``. So every run replays one
trace of arrivals and lengths (the order of a few dozen long and short
prompts decides the tail), and the seed moves the weights, the ids and
the requests checked. Each request asks for ``max_new_tokens``
tokens. A request's latency runs from the moment it
was due to its last token; a refused request is offered again as that
driver offers it (``serve_open_loop._Client``). ``serve_goodput``
counts a request that met ``limit_ms`` as one.

``correct``: for ``checked_requests`` requests of the seed's order that
were answered in the window, the logits of the prompt's last position
and of every decode step, which the generator hands back for requests
it is told to keep them for, against the plain reference's full forward
over the prompt and the program's own tokens (teacher-forced) in
float32, and in the precision the configuration states. A row's gap is
the RMS of the difference over the RMS of the float32 logits.
``precision_excess`` compares the program's gaps with the stated
precision's own (a sound program reads about 0, a delta-rule state
kept in bfloat16 about 1 or more, a lost conv state hundreds); the
largest gaps themselves, ``prefill_gap`` and ``decode_gap``, are
notes.
"""

from __future__ import annotations

import sys
import threading
import time

import numpy as np

from benchmark.drivers.serve_open_loop import (
    GRACE_S,
    _Client,
    _CollectorClock,
    client_notes,
    sample_order,
    schedule,
)
from benchmark.harness import checks
from benchmark.harness.device import memory_peak_bytes, peaks
from benchmark.reference import plain

# requests whose logits the generator keeps, a checked request: a kept
# request's logits cross to the host every step it decodes
KEPT_PER_CHECKED = 2


def program():
    """The program's generator and model modules, imported before
    anything is built: a checkout without them ends the run here, at
    once and with a non-zero exit."""
    from deepvision_tpu.models import get_model, olmo_hybrid
    from deepvision_tpu.serve.generator import GenerationEngine

    return get_model, olmo_hybrid, GenerationEngine


def build_engine(cfg: dict, traffic: dict, weights):
    """The program's generator over ``weights`` (the reference's tree,
    as the program stores it). Tests plant their faults by wrapping what
    this returns."""
    import jax

    get_model, olmo_hybrid, GenerationEngine = program()
    model = get_model(cfg["program"]["model"])
    served = jax.eval_shape(lambda: olmo_hybrid.serving_params(
        model.init(jax.random.key(0), model.sample_input())["params"]))
    checks.require_same_tree(served, weights, "parameter")
    bad = [(a.dtype, b.dtype) for a, b in zip(jax.tree.leaves(served),
                                              jax.tree.leaves(weights))
           if a.dtype != b.dtype]
    if bad:
        raise SystemExit(f"parameter dtypes differ: {bad[:3]}")
    return GenerationEngine(
        model.config, weights, slots=traffic["slots"],
        max_len=traffic["max_len"], prefill_chunk=traffic["prefill_chunk"],
        max_queue=traffic["max_queue"])


class _Submitter:
    """What ``_Client`` offers request ``i`` to: the generator's
    ``submit`` of prompt ``i``, keeping the logits of the requests at
    ``keep``."""

    def __init__(self, engine, prompts, max_new: int, keep):
        self.engine, self.prompts, self.max_new = engine, prompts, max_new
        self.keep = frozenset(int(i) for i in keep)

    def submit(self, i):
        return self.engine.submit(self.prompts[i], self.max_new,
                                  keep_logits=i in self.keep)


def _counters(engine) -> dict:
    t = engine.telemetry
    return {**t.values(), "ttft_count": t.ttft.count}


def window(engine, prompts, traffic, rate: float, seconds: float, seed: int,
           checked: int, trace_after: float | None = None,
           trace_dir: str = "", grace_s: float = GRACE_S) -> dict:
    """Offer ``rate`` for ``seconds`` (one request a prompt, in order) and
    wait for the answers, at most ``grace_s`` past the close. ``sample``
    names the ``checked`` answered requests the comparison reads, the
    first of the seed's order among those whose logits were kept. With
    ``trace_after`` the profiler runs from that many seconds into the
    window until every answer is in; the counters are then read up to
    its start (``serve_open_loop.window`` says why), and ``traced``
    holds them from its start to the last answer."""
    import jax

    due = schedule(rate, seconds, traffic["trace_seed"])
    if len(prompts) != len(due):
        raise ValueError(f"{len(prompts)} prompts for {len(due)} requests")
    kept = sample_order(len(due), seed)[:KEPT_PER_CHECKED * checked]
    client = _Client(_Submitter(engine, prompts, traffic["max_new_tokens"],
                                kept),
                     list(range(len(due))), np.arange(len(due)), kept)
    counted = {}
    tracer = None
    if trace_after is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0

        def start():
            counted.update(_counters(engine))
            jax.profiler.start_trace(trace_dir, profiler_options=options)

        tracer = threading.Timer(trace_after, start)
    before = _counters(engine)
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
    end = _counters(engine)
    after = counted or end
    answered = ~np.isnan(client.done_at)
    done = np.sort(client.done_at[answered])
    last_done = done[-1] if len(done) else t_end
    latency = np.where(answered, client.done_at - (t0 + due),
                       t_end - (t0 + due))
    # every time to first token of the window (a host-side stamp the
    # profiler hardly moves)
    n_ttft = engine.telemetry.ttft.count - before["ttft_count"]
    samples = engine.telemetry.ttft.dump()["samples"]
    # the two stalls a run names, as serve_open_loop.window has them: the
    # generator's largest lateness and the longest gap between answers
    late = client.sent_late
    latest = int(np.argmax(late))
    stalls = [(t0 + due[latest], t0 + due[latest] + late[latest])]
    done_gap_s = 0.0
    if len(done) > 1:
        g = int(np.argmax(np.diff(done)))
        stalls.append((done[g], done[g + 1]))
        done_gap_s = float(done[g + 1] - done[g])
    gen2 = [(a, b) for a, b, g in collections.events if g == 2]
    return {"due": due, "answered": answered, "latency": latency,
            "late": client.sent_late, "held": client.held,
            "refusals": client.refusals, "refused": client.refused,
            "results": client.results,
            "sample": sorted([int(i) for i in kept if answered[i]][:checked]),
            "window_s": max(seconds, last_done - t0),
            "done_gap_s": done_gap_s,
            "ttft_s": np.asarray(samples[len(samples) - n_ttft:]
                                 if n_ttft else [], np.float64),
            "gc_s_max": max((b - a for a, b, _ in collections.events),
                            default=0.0),
            "gc_gen2_count": len(gen2),
            "gc_gen2_s_max": max((b - a for a, b in gen2), default=0.0),
            "stall_in_gc": any(a < end and b > start
                               for start, end in stalls for a, b in gen2),
            "counters": {k: after[k] - before[k] for k in after},
            # the work of the traced span, profiler start to last answer
            "traced": {k: end[k] - counted[k] for k in counted}
            if counted else None}


def _warm(engine, prompts, max_new: int) -> None:
    """One request through both programs and a second slot behind it,
    so that no first execution falls into the window."""
    futs = [engine.submit(np.resize(p, engine.prefill_chunk + 1), max_new)
            for p in prompts[:2]]
    for f in futs:
        f.result(timeout=1200)


def bring_up(cfg, traffic, ref, seed: int, n_requests: int):
    """Weights and prompts from the seed, the generator built over them
    and warm. -> (engine, weights, prompts)."""
    import jax

    program()
    weights = jax.jit(lambda k: ref.make_weights(cfg, k))(
        plain.seed_key(seed))
    prompts = ref.make_prompts(cfg, traffic, seed, n_requests)
    engine = build_engine(cfg, traffic, weights)
    _warm(engine, prompts, traffic["max_new_tokens"])
    return engine, weights, prompts


def gap(got, want) -> np.ndarray:
    """RMS of ``got - want`` over the RMS of ``want``, row by row."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return (np.sqrt(np.mean((got - want) ** 2, -1))
            / np.maximum(np.sqrt(np.mean(want ** 2, -1)), 1e-30))


def reference_logits(cfg, traffic, ref, weights, prompt, tokens,
                     numerics: str = "highest") -> np.ndarray:
    """The reference's logits at the prompt's last position and at each
    generated token but the last, teacher-forced on ``tokens`` (the
    program's own), the sequence padded to one length for every request
    (``max_len`` rounded up to the reference's query block)."""
    block = cfg["reference_q_block"]
    length = -(-traffic["max_len"] // block) * block
    seq = np.zeros(length, np.int32)
    n = len(prompt)
    seq[:n] = prompt
    seq[n:n + len(tokens) - 1] = tokens[:-1]
    positions = np.arange(n - 1, n - 1 + len(tokens))
    return np.asarray(ref.logits_at(cfg, weights, seq, positions, numerics,
                                    reset_at=n))


EXCESS_QUANTILES = (50, 90)


def precision_excess(got, stated) -> float:
    """How much more rounding noise the rows ``got`` carry than the
    stated precision's own rows ``stated`` do (both gaps to the float32
    reference, :func:`gap`), as a share of that noise's variance: the
    ratio of the two gaps' quantiles (:data:`EXCESS_QUANTILES`) in
    geometric mean, squared, less 1. Two sound computations at one
    precision round independently and lie equally far from float32, so
    this reads about 0; a state kept one step lower adds its own noise
    and reads about 1 or more (``serve_open_loop.precision_excess`` is
    the same measure of detections)."""
    ratio = np.prod([np.percentile(got, q) / max(np.percentile(stated, q),
                                                  1e-30)
                     for q in EXCESS_QUANTILES])
    return float(ratio ** (2.0 / len(EXCESS_QUANTILES)) - 1.0)


def reference_rows(cfg, traffic, ref, weights, prompts, chosen, results,
                   numerics: str) -> dict:
    return {i: reference_logits(cfg, traffic, ref, weights, prompts[i],
                                results[i]["tokens"], numerics)
            for i in chosen}


def compare(chosen, results, truth: dict, stated: dict) -> dict:
    """The numbers compared, over the ``chosen`` requests: ``results``
    (``{"tokens", "logits"}`` each: what the program answered, or a
    control's logits put in their place) against the reference's
    float32 logits ``truth``, and ``precision_excess`` over the
    reference's own logits in the stated precision ``stated``, each row
    a prefill's last position or a decode step. ``prefill_gap`` and
    ``decode_gap`` are the largest row gaps to the truth (the decode's
    over every step)."""
    pre, dec, rows, stated_rows, agree, steps = [], [], [], [], 0, 0
    for i in chosen:
        g = gap(results[i]["logits"], truth[i])
        pre.append(g[0])
        dec.append(g[1:].max() if len(g) > 1 else 0.0)
        rows.append(g)
        stated_rows.append(gap(stated[i], truth[i]))
        want = np.argmax(truth[i], -1)
        agree += int(np.sum(want == np.asarray(results[i]["tokens"])))
        steps += len(want)
    excess = (precision_excess(np.concatenate(rows),
                               np.concatenate(stated_rows))
              if rows else np.inf)
    return {"precision_excess": excess,
            "prefill_gap": float(max(pre, default=np.inf)),
            "decode_gap": float(max(dec, default=np.inf)),
            "token_agreement": agree / max(1, steps),
            "compared": len(chosen)}


def judge(cfg, traffic, ref, weights, prompts, chosen, results) -> dict:
    """:func:`compare` of the program's answers, the reference computed
    here in float32 and in the stated precision."""
    refs = {nm: reference_rows(cfg, traffic, ref, weights, prompts, chosen,
                               results, cfg[key])
            for key, nm in (("reference_numerics", "truth"),
                            ("stated_numerics", "stated"))}
    return compare(chosen, results, refs["truth"], refs["stated"])


def gen_checks(cfg, traffic, got: dict) -> list:
    limits = cfg["limits"]["serve"]
    return [checks.Check(k, got[k], limits[k]) for k in limits] + [
        checks.Check("compared_short",
                     float(traffic["checked_requests"] - got["compared"]),
                     0.0)]


def run(run) -> dict:
    import jax

    cfg, traffic, ref = run.cell.config, run.cell.traffic, run.reference
    chips = run.cell.chips
    rate = traffic["rate"]
    n = len(schedule(rate, run.seconds, traffic["trace_seed"]))
    engine, weights, prompts = bring_up(cfg, traffic, ref, run.seed, n)

    # ---- the window
    compiles_before = run.compiles.count
    compile_s = run.compiles.seconds
    setup_s = run.setup_seconds()
    w = window(engine, prompts, traffic, rate, run.seconds, run.seed,
               traffic["checked_requests"],
               trace_after=max(0.0, run.seconds - traffic["trace_seconds"])
               if run.trace else None, trace_dir=run.trace_dir)
    compiles_in_window = run.compiles.count - compiles_before

    due, answered, latency, late = (w["due"], w["answered"], w["latency"],
                                    w["late"])
    good = int(np.sum(answered & (latency <= traffic["limit_ms"] / 1e3)))
    client = client_notes(w)
    print(f"[generator] requests {len(due)} late_ms p99 "
          f"{np.percentile(late, 99) * 1e3:.3f} max {late.max() * 1e3:.3f} "
          + " ".join(f"{k} {v}" for k, v in client.items()),
          file=sys.stderr, flush=True)
    memory = memory_peak_bytes(jax.devices()[:chips])
    engine.close()
    del engine

    got = judge(cfg, traffic, ref, weights, prompts, w["sample"],
                w["results"])
    failed = int(len(due) - answered.sum())
    d = w["counters"]
    kind = run.device["kind"]
    return {
        "end_to_end": {
            "serve_p95_ms": float(np.percentile(latency, 95) * 1e3),
            "serve_goodput": good / run.seconds / chips,
            "setup_s": setup_s},
        "attempted": len(due),
        "failed": failed,
        "checks": gen_checks(cfg, traffic, got) + [
            checks.Check("unanswered", float(failed), 0.0)],
        "memory_peak_bytes": memory,
        "compiles_in_window": compiles_in_window,
        "serve": {
            "ttft_p95_ms": float(np.percentile(w["ttft_s"], 95) * 1e3)
            if len(w["ttft_s"]) else None,
            "decode_rows_mean": d["gen_decode_rows"] / d["gen_decode_steps"]
            if d["gen_decode_steps"] else None},
        "gen": {
            "prefill_tokens": w["traced"]["gen_prefill_tokens"],
            "decode_rows": w["traced"]["gen_decode_rows"],
            "peak_flops": peaks(kind)["bf16_flops_per_s"]
            if run.device["platform"] == "tpu" else None}
        if w["traced"] else None,
        "compile_s": compile_s,
        "notes": {
            "requests": len(due), "good": good, **client,
            "window_s": w["window_s"],
            "latency_ms_p50": float(np.median(latency) * 1e3),
            "latency_ms_max": float(latency.max() * 1e3),
            "generator_late_ms_max": float(late.max() * 1e3),
            "answer_gap_ms_max": w["done_gap_s"] * 1e3,
            **{k: int(v) for k, v in d.items()},
            "token_agreement": got["token_agreement"],
            "prefill_gap": got["prefill_gap"], "decode_gap": got["decode_gap"],
            "checked": got["compared"]},
    }


def calibrate(cell, seeds, *, control: bool, seconds, sweep=(), **_):
    """Readings for the limits and the rate (``benchmark/calibrate.py``).
    With ``sweep``, on the first seed's generator: one request of the
    longest prompt alone (``unloaded``: the latency ``limit_ms`` is
    three times), then each rate offered for ``seconds``; above the knee
    refused requests are offered again and the window lengthens, so the
    knee is ``done_per_s``. Then per seed a window at the cell's rate,
    its sampled requests judged as a run judges them, and with
    ``control`` each of the configuration's controls, the reference in
    that numerics put in the program's place, judged by the same checks,
    which it has to fail."""
    from benchmark.harness import cells

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference_for(cfg, cell.config_name)
    for k, seed in enumerate(seeds):
        prompts_at = lambda rate: ref.make_prompts(
            cfg, traffic, seed, len(schedule(rate, seconds,
                                             traffic["trace_seed"])))
        engine, weights, _ = bring_up(cfg, traffic, ref, seed, 2)
        if k == 0 and sweep:
            longest = np.resize(prompts_at(1.0)[0], traffic["prompt_max"])
            t = time.perf_counter()
            engine.submit(longest, traffic["max_new_tokens"]).result()
            yield {"reading": "unloaded", "prompt": len(longest),
                   "latency_ms": (time.perf_counter() - t) * 1e3}
            for rate in sweep:
                w = window(engine, prompts_at(rate), traffic, rate, seconds,
                           seed, traffic["checked_requests"])
                lat, d = w["latency"] * 1e3, w["counters"]
                yield {"reading": "sweep", "rate": rate,
                       "requests": len(w["due"]), **client_notes(w),
                       "window_s": float(w["window_s"]),
                       "p50_ms": float(np.median(lat)),
                       "p95_ms": float(np.percentile(lat, 95)),
                       "ttft_p95_ms": float(np.percentile(w["ttft_s"], 95)
                                            * 1e3),
                       "done_per_s": float(w["answered"].sum()
                                           / w["window_s"]),
                       "decode_rows_mean": d["gen_decode_rows"]
                       / max(1, d["gen_decode_steps"])}
        prompts = prompts_at(traffic["rate"])
        w = window(engine, prompts, traffic, traffic["rate"], seconds, seed,
                   traffic["checked_requests"])
        engine.close()
        del engine
        chosen, results = w["sample"], w["results"]
        rows = lambda nm: reference_rows(cfg, traffic, ref, weights, prompts,
                                         chosen, results, nm)
        truth, stated = rows(cfg["reference_numerics"]), rows(
            cfg["stated_numerics"])
        readings = {"program": results}
        for name in cfg["controls"] if control else ():
            logits = rows(name)
            readings[f"control:{name}"] = {
                i: {"tokens": results[i]["tokens"], "logits": logits[i]}
                for i in chosen}
        # the next seed's weights and cache need this seed's room
        del weights
        for reading, res in readings.items():
            got = compare(chosen, res, truth, stated)
            yield {"seed": seed, "reading": reading, **got,
                   "correct": checks.verdict(gen_checks(cfg, traffic, got))}
