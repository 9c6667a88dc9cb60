"""Serving engine (deepvision_tpu/serve/): bucket selection + pad
isolation, deadline expiry, admission-control shedding, clean dispatcher
shutdown, compile-cache warmup invariants, multi-model routing, the
StableHLO artifact path, both CLI surfaces (stdin-JSONL + HTTP), and a
lenet5 end-to-end smoke on CPU.

Fast-tier tests run on a toy linear model (compiles in milliseconds);
the real-model e2e/saturation/multi-head checks ride the slow tier
(tests/conftest.py registry).
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent))


# ------------------------------------------------------------- fixtures


def toy_model(name="toy", weight=2.0, dim=3, buckets=None):
    """Per-example linear forward: y_i = x_i * w + bias_row — compiles
    in milliseconds, so engine-lifecycle tests stay in the fast tier."""
    import jax.numpy as jnp

    from deepvision_tpu.serve import ServedModel

    def forward(variables, x):
        return {"y": x * variables["w"] + jnp.float32(0.5)}

    def post(host, i):
        return {"y": np.asarray(host["y"][i]).tolist()}

    return ServedModel(
        name=name, task="classify", forward=forward,
        variables={"w": np.float32(weight)}, input_shape=(dim,),
        postprocess=post, buckets=buckets,
    )


def make_engine(models=None, **kw):
    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.serve import InferenceEngine

    kw.setdefault("mesh", create_mesh(1, 1))
    kw.setdefault("buckets", (1, 4, 16))
    return InferenceEngine(models or [toy_model()], **kw)


def expected_toy(x, weight=2.0):
    return np.asarray(x, np.float32) * np.float32(weight) \
        + np.float32(0.5)


# ------------------------------------------- buckets + pad isolation


def test_bucket_selection_pads_to_ladder_and_chunks():
    with make_engine(max_queue=128) as eng:
        eng.pause()
        futs = [eng.submit(np.full(3, i, np.float32)) for i in range(3)]
        eng.resume()
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                f.result(timeout=30)["y"],
                expected_toy(np.full(3, i, np.float32)))
        tel = eng.telemetry
        # 3 requests -> ONE bucket-4 batch with exactly one padded row
        assert tel.batches == 1
        assert tel.rows == 3
        assert tel.padded_rows == 1

        # 19 pending > max bucket 16 -> chunked: a full 16, then the
        # 3 leftovers in a bucket-4 batch with one padded row
        eng.pause()
        futs = [eng.submit(np.full(3, i, np.float32))
                for i in range(19)]
        eng.resume()
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                f.result(timeout=30)["y"],
                expected_toy(np.full(3, i, np.float32)))
        assert tel.batches == 3
        assert tel.rows == 22
        assert tel.padded_rows == 2


def test_padded_rows_never_leak_into_results():
    """Each request's result depends only on its own input — the padded
    zero rows are sliced away before postprocess, and row order matches
    submission order."""
    with make_engine() as eng:
        eng.pause()
        xs = [np.random.default_rng(i).normal(size=3).astype(np.float32)
              for i in range(3)]
        futs = [eng.submit(x) for x in xs]
        eng.resume()
        for x, f in zip(xs, futs):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=30)["y"], np.float32),
                expected_toy(x))


def test_submit_rejects_wrong_shape_and_unknown_model():
    with make_engine() as eng:
        with pytest.raises(ValueError, match="input shape"):
            eng.submit(np.zeros(5, np.float32))
        with pytest.raises(ValueError, match="unknown model"):
            eng.submit(np.zeros(3, np.float32), model="nope")


def test_engine_rejects_unsorted_or_duplicate_ladder():
    """_bucket_for takes the first bucket >= n in ladder order, so an
    unsorted ladder would silently pad every request to the first
    (largest) bucket — reject it at construction."""
    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.serve import InferenceEngine

    mesh = create_mesh(1, 1)
    for bad in ((64, 16, 4, 1), (4, 4, 16), ()):
        with pytest.raises(ValueError, match="ladder"):
            InferenceEngine([toy_model()], mesh=mesh, buckets=bad,
                            warmup=False)


# ------------------------------------------------------------ deadlines


def test_deadline_expiry_returns_timeout_not_wrong_answer():
    with make_engine() as eng:
        eng.pause()
        doomed = eng.submit(np.zeros(3, np.float32), timeout_s=0.02)
        ok = eng.submit(np.ones(3, np.float32), timeout_s=60.0)
        time.sleep(0.08)  # let the doomed deadline lapse while queued
        eng.resume()
        with pytest.raises(TimeoutError):
            doomed.result(timeout=30)
        np.testing.assert_array_equal(
            ok.result(timeout=30)["y"],
            expected_toy(np.ones(3, np.float32)))
        assert eng.telemetry.timed_out == 1
        # the expired request released its queue slot
        assert eng.stats()["queue"]["depth"] == 0


# --------------------------------------------------------- backpressure


def test_backpressure_sheds_at_capacity_with_retry_after():
    from deepvision_tpu.serve import ShedError

    with make_engine(max_queue=4) as eng:
        eng.pause()
        futs = [eng.submit(np.zeros(3, np.float32)) for _ in range(4)]
        with pytest.raises(ShedError) as exc:
            eng.submit(np.zeros(3, np.float32))
        assert exc.value.retry_after_s > 0
        assert eng.telemetry.shed == 1
        eng.resume()
        for f in futs:  # admitted work still completes after the shed
            assert f.result(timeout=30)
        # capacity freed: new work admits again
        assert eng.submit(np.zeros(3, np.float32)).result(timeout=30)


def test_per_model_limit_sheds_only_the_hot_model():
    from deepvision_tpu.serve import ShedError

    models = [toy_model("a", 2.0), toy_model("b", 3.0)]
    with make_engine(models, max_queue=64, per_model_limit=2) as eng:
        eng.pause()
        for _ in range(2):
            eng.submit(np.zeros(3, np.float32), model="a")
        with pytest.raises(ShedError, match="concurrency limit"):
            eng.submit(np.zeros(3, np.float32), model="a")
        # model b is unaffected by a's limit
        f = eng.submit(np.ones(3, np.float32), model="b")
        eng.resume()
        np.testing.assert_array_equal(
            f.result(timeout=30)["y"],
            expected_toy(np.ones(3, np.float32), weight=3.0))


# ------------------------------------------------------------- shutdown


def test_dispatcher_joins_cleanly_and_fails_pending():
    before = {t.name for t in threading.enumerate()}
    eng = make_engine()
    assert any(t.name == "serve-dispatch"
               for t in threading.enumerate())
    eng.pause()
    orphan = eng.submit(np.zeros(3, np.float32))
    eng.close()
    eng.close()  # idempotent
    with pytest.raises(RuntimeError, match="engine closed"):
        orphan.result(timeout=30)
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(np.zeros(3, np.float32))
    # no leaked threads beyond what existed before the engine
    time.sleep(0.05)
    after = {t.name for t in threading.enumerate()}
    assert "serve-dispatch" not in after - before


# ------------------------------------------------- compile-cache warmup


def test_warmup_compiles_ladder_and_traffic_never_recompiles():
    with make_engine() as eng:
        cache = eng.stats()["cache"]
        assert cache["entries"] == 3          # one per ladder bucket
        assert cache["misses"] == 3
        misses_after_warmup = cache["misses"]
        # traffic at assorted sizes: every batch is a cache HIT
        for n in (1, 2, 3, 4, 5, 16, 1):
            eng.pause()
            futs = [eng.submit(np.zeros(3, np.float32))
                    for _ in range(n)]
            eng.resume()
            for f in futs:
                f.result(timeout=30)
        cache = eng.stats()["cache"]
        assert cache["misses"] == misses_after_warmup
        assert cache["hits"] >= 7


def test_compile_cache_lru_eviction_and_counters():
    from deepvision_tpu.serve import CompileCache

    cc = CompileCache(max_entries=2)
    built = []

    def builder(key):
        def build():
            built.append(key)
            return lambda x: (key, x)
        return build

    assert cc.get_or_build("a", builder("a"))(1) == ("a", 1)
    assert cc.get_or_build("b", builder("b"))(1) == ("b", 1)
    assert cc.get_or_build("a", builder("a"))(2) == ("a", 2)  # hit
    cc.get_or_build("c", builder("c"))  # evicts LRU "b"
    assert cc.contains("a") and cc.contains("c")
    assert not cc.contains("b")
    stats = cc.stats()
    assert stats == {"entries": 2, "hits": 1, "misses": 3,
                     "evictions": 1, "frozen": False}
    assert built == ["a", "b", "c"]


def test_telemetry_percentiles_and_pad_overhead():
    from deepvision_tpu.serve import LatencyStats, ServeTelemetry

    ls = LatencyStats()
    for ms in range(1, 101):
        ls.record(ms / 1e3)
    s = ls.summary()
    assert s["count"] == 100
    assert 49 <= s["p50_ms"] <= 52
    assert 94 <= s["p95_ms"] <= 96
    assert s["max_ms"] == 100.0

    tel = ServeTelemetry()
    tel.record_batch(bucket=4, rows=3, device_s=0.004)
    snap = tel.snapshot()
    assert snap["padded_rows"] == 1
    assert snap["pad_overhead_frac"] == 0.25


# ------------------------------------------------- multi-model routing


def test_multi_model_round_robin_routing():
    models = [toy_model("a", 2.0), toy_model("b", -1.0)]
    with make_engine(models, max_queue=128) as eng:
        eng.pause()
        futs = []
        for i in range(10):
            name = "a" if i % 2 == 0 else "b"
            futs.append((name, i,
                         eng.submit(np.full(3, i, np.float32),
                                    model=name)))
        eng.resume()
        for name, i, f in futs:
            w = 2.0 if name == "a" else -1.0
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=30)["y"], np.float32),
                expected_toy(np.full(3, i, np.float32), weight=w))
        # both models' ladders were warmed
        assert eng.stats()["cache"]["entries"] == 6


def test_sharded_engine_on_mesh8(mesh8):
    """Buckets divisible by the data axis serve sharded; indivisible
    ladders are rejected at construction (fail fast, not per batch)."""
    from deepvision_tpu.serve import InferenceEngine

    with InferenceEngine([toy_model()], mesh=mesh8,
                         buckets=(8, 16)) as eng:
        eng.pause()
        xs = [np.full(3, i, np.float32) for i in range(5)]
        futs = [eng.submit(x) for x in xs]
        eng.resume()
        for x, f in zip(xs, futs):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=60)["y"], np.float32),
                expected_toy(x))
        assert eng.telemetry.padded_rows == 3  # 5 real rows -> bucket 8

    with pytest.raises(ValueError, match="divisible"):
        InferenceEngine([toy_model()], mesh=mesh8, buckets=(1, 4),
                        warmup=False)


# ------------------------------------------------------ StableHLO path


def test_stablehlo_artifact_serves_with_zero_compiles(tmp_path):
    import optax

    from deepvision_tpu.export import (
        export_forward,
        load_exported,
        save_exported,
    )
    from deepvision_tpu.models import get_model
    from deepvision_tpu.serve import InferenceEngine, from_stablehlo
    from deepvision_tpu.train.state import create_train_state

    rng = np.random.default_rng(0)
    sample = rng.normal(size=(4, 32, 32, 1)).astype(np.float32)
    state = create_train_state(
        get_model("lenet5", num_classes=10), optax.sgd(0.1), sample)
    variables = {"params": state.params,
                 "batch_stats": state.batch_stats}
    path = save_exported(
        tmp_path / "lenet5.stablehlo",
        export_forward(state.apply_fn, variables, sample))

    # load_exported round-trip carries the input signature metadata
    fn = load_exported(path)
    assert fn.in_avals[0].shape == (4, 32, 32, 1)
    want = np.asarray(state.apply_fn(variables, sample, train=False))
    np.testing.assert_allclose(np.asarray(fn(sample)), want, atol=1e-5)

    served = from_stablehlo(path, name="lenet5_hlo", top_k=3)
    assert served.buckets == (4,)  # pinned to the exported batch
    with InferenceEngine([served], warmup=True) as eng:
        eng.pause()
        futs = [eng.submit(sample[i]) for i in range(3)]
        eng.resume()
        for i, f in enumerate(futs):
            res = f.result(timeout=60)
            assert res["classes"][0] == int(np.argmax(want[i]))
            assert len(res["probs"]) == 3
        # the deserialized executable IS the runner: one cache entry,
        # zero jit compiles
        assert eng.stats()["cache"]["entries"] == 1


# ------------------------------------------------------- CLI surfaces


def _cli_args(**over):
    import argparse

    base = dict(timeout_s=10.0)
    base.update(over)
    return argparse.Namespace(**base)


def test_stdin_jsonl_surface_end_to_end():
    import io

    import serve as serve_cli

    with make_engine() as eng:
        lines = [json.dumps({"id": i, "model": "toy",
                             "input": [float(i)] * 3})
                 for i in range(5)]
        lines.append('{"id": 9, "model": "nope", "input": [0,0,0]}')
        lines.append("not json")
        lines.append("[1, 2, 3]")  # valid JSON, not an object
        out = io.StringIO()
        serve_cli.run_stdin(eng, _cli_args(),
                            stdin=io.StringIO("\n".join(lines)),
                            stdout=out)
        got = [json.loads(line) for line in
               out.getvalue().strip().splitlines()]
        results = [g for g in got if "result" in g]
        errors = [g for g in got if "error" in g]
        assert len(results) == 5 and len(errors) == 3
        # responses come back in submission order with correct routing
        for i, g in enumerate(results):
            assert g["id"] == i
            np.testing.assert_array_equal(
                np.asarray(g["result"]["y"], np.float32),
                expected_toy(np.full(3, i, np.float32)))


def test_http_surface_predict_stats_and_shed():
    import http.client
    import http.server

    import serve as serve_cli

    with make_engine(max_queue=64) as eng:
        args = _cli_args(http=0)
        handler = serve_cli.make_handler(eng, args)
        server = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                                 handler)
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.server_address[1], timeout=30)
            body = json.dumps({"model": "toy", "input": [1.0, 2.0, 3.0]})
            conn.request("POST", "/v1/predict", body)
            resp = conn.getresponse()
            assert resp.status == 200
            res = json.loads(resp.read())["result"]
            np.testing.assert_array_equal(
                np.asarray(res["y"], np.float32),
                expected_toy(np.array([1, 2, 3], np.float32)))

            conn.request("GET", "/stats")
            stats = json.loads(conn.getresponse().read())
            assert stats["cache"]["misses"] == 3
            assert stats["telemetry"]["completed"] >= 1

            # the server speaks HTTP/1.1 keep-alive now: a client
            # reusing the connection must drain each body (read())
            # before the next request — which also pins that every
            # handler path sets Content-Length correctly
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()

            conn.request("POST", "/v1/predict",
                         json.dumps({"model": "toy", "input": "bad"}))
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()

            # valid JSON but not an object: 400, not a dead handler
            conn.request("POST", "/v1/predict", json.dumps([1, 2, 3]))
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()

            # binary wire format: base64 raw bytes + shape
            import base64

            x = np.array([1, 2, 3], np.float32)
            conn.request("POST", "/v1/predict", json.dumps({
                "model": "toy",
                "input_b64": base64.b64encode(x.tobytes()).decode(),
                "shape": [3]}))
            resp = conn.getresponse()
            assert resp.status == 200
            res = json.loads(resp.read())["result"]
            np.testing.assert_array_equal(
                np.asarray(res["y"], np.float32), expected_toy(x))

            # per-request deadline (the fleet router forwards its
            # remaining budget): honored when sane, 400 when not
            conn.request("POST", "/v1/predict", json.dumps(
                {"model": "toy", "input": [1.0, 2.0, 3.0],
                 "timeout_s": 10.0}))
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
            conn.request("POST", "/v1/predict", json.dumps(
                {"model": "toy", "input": [1.0, 2.0, 3.0],
                 "timeout_s": 0}))
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()
            # a server-side RuntimeError (dispatcher crash, engine
            # closed) is a 500 — retryable server fault — NOT a 400:
            # the fleet router maps 400 to a terminal client error, so
            # a 400 here would bury exactly the fault class failover
            # exists to absorb
            real_submit = eng.submit
            try:
                def boom(*a, **kw):
                    raise RuntimeError("dispatcher crashed: injected")
                eng.submit = boom
                conn.request("POST", "/v1/predict", json.dumps(
                    {"model": "toy", "input": [1.0, 2.0, 3.0]}))
                resp = conn.getresponse()
                assert resp.status == 500
                resp.read()
            finally:
                eng.submit = real_submit

            # ...and it must actually reach the engine: a paused
            # engine + a 0.3s request deadline is a 504 in ~0.3s, not
            # a hang until the blanket --timeout-s
            eng.pause()
            try:
                t0 = time.perf_counter()
                conn.request("POST", "/v1/predict", json.dumps(
                    {"model": "toy", "input": [1.0, 2.0, 3.0],
                     "timeout_s": 0.3}))
                resp = conn.getresponse()
                assert resp.status == 504
                resp.read()
                assert time.perf_counter() - t0 < 5.0
            finally:
                eng.resume()
        finally:
            server.shutdown()
            server.server_close()


def test_serving_mesh_adapts_ladder_to_device_count():
    """conftest pins 8 virtual devices: the default ladder must adapt
    (1/4 -> 8) so sharded serving stays active instead of degrading to
    a single-device mesh."""
    import jax

    import serve as serve_cli

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device virtual CPU env")
    mesh, ladder = serve_cli._serving_mesh((1, 4, 16, 64))
    n = len(jax.devices())
    assert mesh.shape["data"] == n
    assert ladder == tuple(sorted({((b + n - 1) // n) * n
                                   for b in (1, 4, 16, 64)}))
    assert all(b % n == 0 for b in ladder)


# ----------------------------------------------------- real-model e2e


def test_lenet5_e2e_smoke_padded_matches_single():
    """Full path on a real registry model: restore (fresh weights) ->
    engine -> padded bucket-4 batch. Padding must be numerically
    invisible: a request served in a 3-real-row padded batch is
    BIT-identical to the same request served alone (1 real + 3 pad
    rows) through the same bucket executable. Across *different*
    bucket executables XLA fuses differently (last-ulp, ~1e-8), so the
    engine-less batch-1 reference is pinned to 1e-6 with identical
    top-k classes. No post-warmup compiles either way."""
    from deepvision_tpu.serve import InferenceEngine
    from deepvision_tpu.serve.models import load_served

    rng = np.random.default_rng(0)
    served = load_served("lenet5", None, num_classes=10, top_k=5)
    xs = rng.normal(size=(3, 32, 32, 1)).astype(np.float32)
    with InferenceEngine([served], buckets=(4,)) as eng:
        misses = eng.stats()["cache"]["misses"]
        assert misses == 1
        # singles first: each request alone in a padded bucket-4 batch
        singles = [eng.submit(x).result(timeout=120) for x in xs]
        assert eng.telemetry.batches == 3
        # then all three together: one bucket-4 batch, one padded row
        eng.pause()
        futs = [eng.submit(x) for x in xs]
        eng.resume()
        batched = [f.result(timeout=120) for f in futs]
        assert eng.telemetry.batches == 4
        assert eng.stats()["cache"]["misses"] == misses
    for x, res, alone in zip(xs, batched, singles):
        # padding invisible: bit-identical within the same executable
        assert res == alone
        # decode-correct vs the engine-less batch-1 reference
        ref = served.run_one(x)
        assert res["classes"] == ref["classes"]
        np.testing.assert_allclose(
            np.asarray(res["probs"], np.float32),
            np.asarray(ref["probs"], np.float32), atol=1e-6)
        assert len(res["classes"]) == 5
        assert res["probs"] == sorted(res["probs"], reverse=True)


def test_gan_head_padded_matches_single():
    """DCGAN generator served from latents: a request in a padded
    2-real-row batch is bit-identical to the same request served alone
    through the same bucket executable (and 1e-6-close to the
    engine-less batch-1 forward)."""
    from deepvision_tpu.serve import InferenceEngine
    from deepvision_tpu.serve.models import load_served

    rng = np.random.default_rng(1)
    # explicit-epoch invariant holds on the GAN path too: no silent
    # random weights when the requested checkpoint is absent
    with pytest.raises(FileNotFoundError):
        load_served("dcgan", "/nonexistent-workdir", epoch=3)
    served = load_served("dcgan", None)
    assert served.input_shape == (100,)
    zs = rng.normal(size=(2, 100)).astype(np.float32)
    with InferenceEngine([served], buckets=(4,)) as eng:
        singles = [eng.submit(z).result(timeout=120) for z in zs]
        eng.pause()
        futs = [eng.submit(z) for z in zs]
        eng.resume()
        batched = [f.result(timeout=120) for f in futs]
    for z, res, alone in zip(zs, batched, singles):
        assert res == alone  # padding is numerically invisible
        np.testing.assert_allclose(
            np.asarray(res["image"], np.float32),
            np.asarray(served.run_one(z)["image"], np.float32),
            atol=1e-6)
        assert np.asarray(res["image"]).shape == (28, 28, 1)


def test_detect_and_pose_heads_padded_match_single():
    """The remaining task heads (YOLO decode+NMS, hourglass heatmap
    argmax) through the engine at reduced geometry: a request in a
    padded multi-row batch must be bit-identical to the same request
    served alone through the same bucket executable, and agree with
    the engine-less batch-1 reference to 1e-6 (identical classes /
    argmax joints)."""
    from deepvision_tpu.serve import InferenceEngine
    from deepvision_tpu.serve.models import load_served

    rng = np.random.default_rng(2)
    detect = load_served("yolov3", None, task="detect", input_size=64,
                         num_classes=5, score_thresh=0.0)
    pose = load_served("hourglass104", None, task="pose",
                       input_size=64, num_heatmaps=4)
    imgs = rng.normal(size=(2, 64, 64, 3)).astype(np.float32)
    with InferenceEngine([detect, pose], buckets=(4,)) as eng:
        dsingle = [eng.submit(x, model="yolov3").result(timeout=600)
                   for x in imgs]
        psingle = [eng.submit(x, model="hourglass104").result(
            timeout=600) for x in imgs]
        eng.pause()
        dfuts = [eng.submit(x, model="yolov3") for x in imgs]
        pfuts = [eng.submit(x, model="hourglass104") for x in imgs]
        eng.resume()
        dres = [f.result(timeout=600) for f in dfuts]
        pres = [f.result(timeout=600) for f in pfuts]
    for x, res, alone in zip(imgs, dres, dsingle):
        assert res == alone  # padding is numerically invisible
        ref = detect.run_one(x)
        assert res["classes"] == ref["classes"]
        # cross-executable: fresh-init YOLO's exp(wh) decode amplifies
        # the per-shape fusion ulps into relative noise on unbounded
        # box magnitudes, so boxes get rtol (scores are sigmoid-bounded)
        np.testing.assert_allclose(
            np.asarray(res["boxes"], np.float32),
            np.asarray(ref["boxes"], np.float32), rtol=5e-3, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(res["scores"], np.float32),
            np.asarray(ref["scores"], np.float32), atol=1e-5)
    for x, res, alone in zip(imgs, pres, psingle):
        assert res == alone
        ref = pose.run_one(x)
        joints = np.asarray(res["joints"], np.float32)
        ref_joints = np.asarray(ref["joints"], np.float32)
        # argmax cell fractions are exact across executables; only the
        # confidence value carries float noise (fresh-init hourglass
        # heatmaps are unbounded, so relative tolerance)
        np.testing.assert_array_equal(joints[:, :2], ref_joints[:, :2])
        np.testing.assert_allclose(joints[:, 2], ref_joints[:, 2],
                                   rtol=1e-4, atol=1e-6)
        assert joints.shape == (4, 3)


def test_serve_saturation_throughput_vs_sequential():
    """Saturation batching must beat the sequential batch-1 closed loop
    (the predict.py pattern). The acceptance bar (>=5x on the driver's
    run) is measured by `bench.py serve`; here a conservative 2x guards
    the mechanism without flaking on a loaded 2-core box."""
    from deepvision_tpu.serve import InferenceEngine
    from deepvision_tpu.serve.models import load_served

    rng = np.random.default_rng(3)
    served = load_served("lenet5", None, num_classes=10)
    xs = rng.normal(size=(256, 32, 32, 1)).astype(np.float32)
    with InferenceEngine([served], buckets=(1, 4, 16, 64),
                         max_queue=1024) as eng:
        for i in range(8):  # settle both paths
            eng.submit(xs[i]).result(timeout=120)

        def seq_once():
            t0 = time.perf_counter()
            for i in range(32):
                eng.submit(xs[i]).result(timeout=120)
            return 32 / (time.perf_counter() - t0)

        def sat_once():
            eng.pause()  # offer the whole load before the drain starts
            futs = [eng.submit(x) for x in xs]
            eng.resume()
            t0 = time.perf_counter()
            for f in futs:
                f.result(timeout=300)
            return len(xs) / (time.perf_counter() - t0)

        # best-of-2 per path: one scheduler stall on the loaded 2-core
        # box must not sink the comparison (measured ratio is ~6-8x,
        # bench.py serve reports the honest figure)
        seq_rate = max(seq_once(), seq_once())
        rows_before = eng.telemetry.rows
        batches_before = eng.telemetry.batches
        sat_rate = max(sat_once(), sat_once())
        burst_rows = eng.telemetry.rows - rows_before
        burst_batches = eng.telemetry.batches - batches_before
    assert sat_rate > 2.0 * seq_rate, (sat_rate, seq_rate)
    # saturation actually filled the big buckets (each backlogged
    # 256-request burst over a max-64 ladder -> 4 full batches)
    assert burst_rows / burst_batches > 32


# ------------------------------------------- the dispatcher's phases

_PHASES = ("wait", "fill_window", "pack", "device_put", "device",
           "resolve")
# what /stats held before the phases came (PR 25 adds keys, loses none)
_STATS_KEYS = ("models", "pipelines", "buckets", "warmup_s", "health",
               "queue", "cache", "tenancy", "warmed_from_store",
               "telemetry")
_TELEMETRY_KEYS = (
    "submitted", "completed", "timed_out", "failed", "shed", "batches",
    "rows", "padded_rows", "dispatcher_crashes", "dispatcher_restarts",
    "pad_overhead_frac", "mean_batch_rows", "queue_wait", "device_time",
    "e2e_latency")


def _lenet_phase_run():
    """A lenet engine under the ring's sink: six full bucket-16 batches
    back to back out of a backlog, an idle stretch, then three requests
    one at a time. -> (the dispatcher thread's phase spans in order,
    the engine's telemetry, its /stats)."""
    from deepvision_tpu.obs.metrics import Registry
    from deepvision_tpu.obs.trace import get_tracer
    from deepvision_tpu.serve import InferenceEngine, ServeTelemetry
    from deepvision_tpu.serve.models import load_served

    served = load_served("lenet5", None, num_classes=10, top_k=5)
    xs = np.random.default_rng(0).normal(
        size=(16, 32, 32, 1)).astype(np.float32)
    records = []
    tracer = get_tracer()
    tracer.add_sink(records.append)     # before the dispatcher starts
    try:
        with InferenceEngine(
                [served], buckets=(4, 16), batch_window_s=0.002,
                telemetry=ServeTelemetry(registry=Registry())) as eng:
            called = []
            eng.pause()
            futs = [eng.submit(xs[i % 16]) for i in range(96)]
            for f in futs:  # a client's callback runs inside `resolve`
                f.add_done_callback(lambda _f: called.append(1))
            eng.resume()
            for f in futs:
                f.result(timeout=120)
            time.sleep(0.3)             # nothing offered: `wait`
            for i in range(3):
                eng.submit(xs[i]).result(timeout=120)
            stats = eng.stats()
            tel = eng.telemetry
        assert len(called) == 96
    finally:
        tracer.remove_sink(records.append)
    spans = sorted((r for r in records if r["tname"] == "serve-dispatch"
                    and r["cat"] == "serve" and r["name"] in _PHASES),
                   key=lambda r: r["ts"])
    return spans, tel, stats


@pytest.fixture(scope="module")
def lenet_phases():
    return _lenet_phase_run()


def test_phases_are_flat_consecutive_and_in_order(lenet_phases):
    import re

    spans, tel, _ = lenet_phases
    for a, b in zip(spans, spans[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 1e-9, (a, b)  # none nested
    letters = "".join({"wait": "w", "fill_window": "f", "pack": "p",
                       "device_put": "u", "device": "d",
                       "resolve": "r"}[s["name"]] for s in spans)
    # every batch: the window held open, then pack, put, device,
    # resolve; `wait` only between batches
    assert re.fullmatch(r"w*(fpudrw*)+", letters), letters
    assert letters.count("p") == tel.batches == 9
    assert "r" + "fpudr" * 5 in letters     # the backlog: no wait between
    assert "rw" in letters and "wfpudr" in letters
    by = {s["name"]: s for s in spans}
    assert by["pack"]["args"] == {"model": "lenet5", "bucket": 16,
                                  "rows": 16} or by["pack"]["args"][
        "bucket"] == 4
    assert set(by["device_put"]["args"]) == {"bucket"}
    assert set(by["device"]["args"]) == {"model", "bucket", "rows"}
    assert set(by["resolve"]["args"]) == {"rows"}


def test_phases_cover_the_dispatchers_time_but_for_one_percent(
        lenet_phases):
    """From the first batch's `pack` to the last `resolve` the phases
    leave under 1% of the dispatcher thread's time between them. Up to
    three runs: what lies between two phases is tens of microseconds
    here, but one preemption of the thread there reads as the
    engine's."""
    shares = []
    for run in (lambda: lenet_phases, _lenet_phase_run, _lenet_phase_run):
        spans = run()[0]
        first = next(i for i, s in enumerate(spans) if s["name"] == "pack")
        last = max(i for i, s in enumerate(spans)
                   if s["name"] == "resolve")
        cut = spans[first:last + 1]
        extent = cut[-1]["ts"] + cut[-1]["dur"] - cut[0]["ts"]
        shares.append(1.0 - sum(s["dur"] for s in cut) / extent)
        if shares[-1] < 0.01:
            break
    assert min(shares) < 0.01, shares


def test_phase_histograms_are_the_spans_own_measurements(lenet_phases):
    spans, tel, _ = lenet_phases
    for name, hist in tel.phase_time.items():
        durs = [s["dur"] for s in spans if s["name"] == name]
        assert hist.count == len(durs) > 0, name
        assert hist.total_s == pytest.approx(sum(durs), rel=1e-12), name
    durs = [s["dur"] for s in spans if s["name"] == "device"]
    assert tel.device_time.count == len(durs) == tel.batches
    assert tel.device_time.total_s == pytest.approx(sum(durs), rel=1e-12)
    assert {f"serve_{p}_time" for p in _PHASES if p != "device"} \
        | {"serve_device_time"} <= set(tel.registry.names())


def test_stats_holds_every_key_it_held(lenet_phases):
    _, _, stats = lenet_phases
    assert list(stats)[:len(_STATS_KEYS)] == list(_STATS_KEYS)
    assert list(stats["telemetry"])[:len(_TELEMETRY_KEYS)] == list(
        _TELEMETRY_KEYS)
    for p in _PHASES:
        if p != "device":
            assert stats["telemetry"][f"{p}_time"]["count"] > 0


# ------------------------------------------- staging buffers (PR 26)


def whole_batch_model(name, forward_y, dim=3):
    """A model whose post-process hands the client a VIEW of the fetched
    output's row ``i`` (no ``tolist``): what a client holds is the
    engine's own host array."""
    from deepvision_tpu.serve import ServedModel

    return ServedModel(
        name=name, task="classify",
        forward=lambda variables, x: {"y": forward_y(x)},
        variables={"w": np.float32(1.0)}, input_shape=(dim,),
        postprocess=lambda host, i: {"y": np.asarray(host["y"][i])})


def batch_sum_model(name="bsum", dim=3):
    """Every row answers the sum over ALL rows of the executed batch: a
    stale row left in the padding changes every answer."""
    import jax.numpy as jnp

    return whole_batch_model(
        name, lambda x: jnp.broadcast_to(
            jnp.sum(x, axis=0, keepdims=True), x.shape), dim)


def identity_model(name="ident", dim=3):
    return whole_batch_model(name, lambda x: x, dim)


def _one_batch(eng, xs, **submit_kw):
    """``xs`` through the engine as ONE batch -> each request's ``y``."""
    eng.pause()
    futs = [eng.submit(x, **submit_kw) for x in xs]
    eng.resume()
    return [f.result(timeout=30)["y"] for f in futs]


def _rows(n, dim=3, seed=0):
    # whole numbers: a float32 sum of them is exact in any order
    return np.random.default_rng(seed).integers(
        1, 100, size=(n, dim)).astype(np.float32)


def _private_telemetry():
    from deepvision_tpu.obs.metrics import Registry
    from deepvision_tpu.serve import ServeTelemetry

    return ServeTelemetry(registry=Registry())


@pytest.mark.parametrize("first,then", [(4, 3), (16, 5), (3, 3), (4, 2)])
def test_padding_is_zero_again_after_a_larger_batch(first, then):
    """``then`` rows after ``first`` rows in the same staging buffer
    read exactly what ``then`` rows alone read: rows ``then .. first``
    were zeroed, and only those had to be."""
    with make_engine([batch_sum_model()], buckets=(4, 16),
                     telemetry=_private_telemetry()) as eng:
        _one_batch(eng, _rows(first, seed=1))
        xs = _rows(then, seed=2)
        got = _one_batch(eng, xs)
        for y in got:
            np.testing.assert_array_equal(y, xs.sum(axis=0))
        (slot,) = eng._staging.values()
        buf, last_n = slot
        assert last_n == then
        np.testing.assert_array_equal(buf[:then], xs)
        assert not buf[then:].any()
        assert (eng.telemetry.stage_allocs,
                eng.telemetry.stage_reuses) == (1, 1)


def _aligned_like(a, align=64):
    raw = np.zeros(a.nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


@pytest.mark.parametrize("aligned", [True, False])
def test_a_held_result_never_aliases_the_staging_buffer(aligned):
    """The CPU backend's ``device_put`` is zero-copy for a 64-byte
    aligned numpy array, so there the device input IS the staging
    buffer; with an identity model the fetched output must still be
    memory of its own, or a client's result would change when the next
    batch is packed."""
    import jax

    with make_engine([identity_model()], buckets=(4,),
                     telemetry=_private_telemetry()) as eng:
        _one_batch(eng, _rows(4, seed=1))
        (slot,) = eng._staging.values()
        if aligned:
            slot[0] = _aligned_like(slot[0])
            # the hazard is real on this backend: nothing is copied in
            assert np.shares_memory(
                np.asarray(jax.device_put(slot[0])), slot[0])
        xs = _rows(3, seed=2)
        held = _one_batch(eng, xs)
        _one_batch(eng, _rows(4, seed=3))
        _one_batch(eng, _rows(2, seed=4))
        for y, x in zip(held, xs):
            assert not np.shares_memory(y, slot[0])
            np.testing.assert_array_equal(y, x)
        assert eng.telemetry.stage_reuses == 3


def test_stage_counters_over_mixed_sizes_and_close_drops_the_buffers():
    """One buffer per distinct (bucket, input shape, dtype) — two
    models of one shape share it — and every other batch a reuse."""
    tel = _private_telemetry()
    eng = make_engine(
        [batch_sum_model("a"), batch_sum_model("b"),
         batch_sum_model("c", dim=5)], telemetry=tel)
    try:
        for n in (3, 4, 1, 16, 2, 5):       # buckets 4 4 1 16 4 16
            _one_batch(eng, _rows(n, seed=n), model="a")
        _one_batch(eng, _rows(2), model="b")            # a's bucket-4
        _one_batch(eng, _rows(2, dim=5), model="c")     # a new shape
        assert sorted((b, s) for b, s, _d in eng._staging) == [
            (1, (3,)), (4, (3,)), (4, (5,)), (16, (3,))]
        assert tel.batches == 8
        assert (tel.stage_allocs, tel.stage_reuses) == (4, 4)
        held = 4 * ((1 + 4 + 16) * 3 + 4 * 5)
        assert tel.stage_bytes.value == held
        snap = eng.stats()["telemetry"]
        assert (snap["stage_allocs"], snap["stage_reuses"],
                snap["stage_bytes"]) == (4, 4, held)
        reg = tel.registry
        assert reg.value_of("serve_stage_allocs") == 4
        assert reg.value_of("serve_stage_reuses") == 4
        assert reg.value_of("serve_stage_bytes") == held
    finally:
        eng.close()
    assert eng._staging == {}
    assert tel.stage_bytes.value == 0
    assert (tel.stage_allocs, tel.stage_reuses) == (4, 4)


def _pipeline_traffic():
    sys.path.insert(0, str(Path(__file__).parent))
    from test_pipeline import entry_image, make_pipe_engine

    eng, _pipe = make_pipe_engine(telemetry=_private_telemetry())
    return eng, [lambda i=i: eng.submit(entry_image(i), model="detpose")
                 for i in range(6)]


def _stateful_traffic(tmp_path):
    sys.path.insert(0, str(Path(__file__).parent))
    from test_sessions import frame, tracking_engine

    eng, _store = tracking_engine(tmp_path)
    rng = np.random.default_rng(0)
    return eng, [lambda s=s, q=q: eng.submit(
        frame(rng), model="track", session=f"s{s}", seq=q)
        for q in range(5) for s in range(3)]


@pytest.mark.parametrize("kind", ["pipeline", "stateful"])
def test_pipelines_and_stateful_models_pack_through_the_same_helper(
        kind, tmp_path):
    eng, sends = (_pipeline_traffic() if kind == "pipeline"
                  else _stateful_traffic(tmp_path))
    with eng:
        tel = eng.telemetry
        before = (tel.stage_allocs, tel.stage_reuses, tel.batches)
        for send in sends:
            send().result(timeout=60)
        allocs, reuses, batches = (
            a - b for a, b in zip(
                (tel.stage_allocs, tel.stage_reuses, tel.batches), before))
        assert allocs == len(eng._staging) >= 1
        assert reuses >= 1
        assert allocs + reuses == batches


def test_batch_after_a_dispatcher_crash_packs_over_the_dead_one():
    """Dispatch 1 crashes between a 4-row batch and a 2-row one: the
    restarted loop packs into the same buffer and its padding is
    zero."""
    from deepvision_tpu.resilience import FaultInjector

    with make_engine([batch_sum_model()], buckets=(4,),
                     fault_injector=FaultInjector("crash@1"),
                     restart_backoff_s=0.02,
                     telemetry=_private_telemetry()) as eng:
        _one_batch(eng, _rows(4, seed=1))
        eng.pause()
        doomed = [eng.submit(x) for x in _rows(3, seed=2)]
        eng.resume()
        for f in doomed:
            with pytest.raises(RuntimeError, match="dispatcher crashed"):
                f.result(timeout=30)
        deadline = time.monotonic() + 30
        while eng.telemetry.dispatcher_restarts < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        xs = _rows(2, seed=3)
        for y in _one_batch(eng, xs):
            np.testing.assert_array_equal(y, xs.sum(axis=0))
        assert (eng.telemetry.stage_allocs,
                eng.telemetry.stage_reuses) == (1, 1)
