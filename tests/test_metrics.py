"""paddle_tpu.observe.metrics tests — registry semantics, the
Prometheus exposition (golden-guarded: tests/golden/
metrics_exposition.txt), the exact-percentile histogram readout, and
the serving integration acceptance: ``GET /metrics`` on a live server
returns Prometheus-parseable text whose counters agree with ``/stats``
after a burst of ``POST /infer`` traffic, and the readiness probe is
false before bucket warmup completes.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.observe import metrics

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "metrics_exposition.txt")


# -- instruments -------------------------------------------------------------

def test_counter_monotonic():
    reg = metrics.MetricsRegistry()
    c = reg.counter("reqs_total", help="requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_gauge_set_inc_dec():
    reg = metrics.MetricsRegistry()
    g = reg.gauge("depth")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8.0


def test_histogram_buckets_and_exact_percentiles():
    reg = metrics.MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 2.0, 3.0, 7.0, 50.0):
        h.observe(v)
    count, total, cumulative = h.state()
    assert count == 5 and total == pytest.approx(62.5)
    assert cumulative == [1, 3, 4]  # le=1, le=5, le=10 (cumulative)
    # exact percentiles from the raw reservoir, NOT bucket interpolation
    assert h.percentile(50) == pytest.approx(3.0)
    p = h.percentiles()
    assert p["p50"] == pytest.approx(3.0)
    assert p["p99"] == pytest.approx(48.28, abs=0.01)
    assert reg.histogram("empty").percentiles() == {
        "p50": None, "p95": None, "p99": None}


def test_percentile_helper_matches_numpy():
    vals = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 25, 50, 90, 95, 99, 100):
        assert metrics.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert metrics.percentile([], 50) is None
    assert metrics.percentile([2.5], 99) == 2.5


def test_registry_get_or_create_is_process_wide():
    reg = metrics.MetricsRegistry()
    a = reg.counter("shared_total")
    b = reg.counter("shared_total")
    assert a is b  # two call sites share one series
    lab1 = reg.gauge("fill", labels={"bucket": "8"})
    lab2 = reg.gauge("fill", labels={"bucket": "32"})
    assert lab1 is not lab2
    assert reg.gauge("fill", labels={"bucket": "8"}) is lab1
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("shared_total")
    assert metrics.get_registry() is metrics.get_registry()


def _golden_registry(include_workers=True):
    """The deterministic registry the golden exposition pins.

    ``include_workers=False`` leaves out the ``{worker=}``-labeled
    series — the merged-exposition test re-creates those from per-
    worker ``dump_series`` snapshots instead (the WorkerSet ``/metrics``
    path) and must land on the same golden bytes."""
    reg = metrics.MetricsRegistry()
    c = reg.counter("paddle_tpu_serve_requests_total",
                    help="requests completed by the serving engine")
    c.inc(42)
    # labeled families (multi-model serving, serve/router.py): the same
    # family carries an unlabeled series AND {model=...} series, plus
    # the shed counter's {model, priority, reason} label set
    for model, n in (("mnist_mlp", 30), ("tagger", 12)):
        reg.counter("paddle_tpu_serve_requests_total",
                    help="requests completed by the serving engine",
                    labels={"model": model}).inc(n)
    if include_workers:
        # worker-process series (serve/workers.py): a WorkerSet's
        # router merges each worker's registry dump under an injected
        # {worker=} label — pinned here as locally-registered series
        for worker, n in (("0", 5), ("1", 4)):
            reg.counter("paddle_tpu_serve_requests_total",
                        help="requests completed by the serving engine",
                        labels={"model": "tagger",
                                "worker": worker}).inc(n)
    reg.counter("paddle_tpu_serve_shed_total",
                help="requests rejected by admission control",
                labels={"model": "tagger", "priority": "low",
                        "reason": "pressure"}).inc(7)
    g = reg.gauge("paddle_tpu_serve_queue_depth",
                  help="rows waiting for a batch flush")
    g.set(3)
    if include_workers:
        reg.gauge("paddle_tpu_serve_queue_depth",
                  help="rows waiting for a batch flush",
                  labels={"worker": "1"}).set(2)
    for bucket, fill in (("4", 0.75), ("8", 0.5)):
        reg.gauge("paddle_tpu_serve_batch_fill_ratio",
                  help="real rows / bucket slots (cumulative)",
                  labels={"bucket": bucket}).set(fill)
    h = reg.histogram("paddle_tpu_serve_request_latency_ms",
                      help="end-to-end request latency (enqueue to result)",
                      buckets=(1.0, 5.0, 25.0, 100.0))
    for v in (0.4, 3.0, 3.5, 17.0, 250.0):
        h.observe(v)
    # session-tier families (docs/serving.md "Session tier & paging"):
    # spill/restore counters, reason-labeled evictions, the
    # resident-vs-suspended gauges and the swap-latency histogram
    reg.counter("paddle_tpu_serve_session_spills_total",
                help="session carries paged out to the host store",
                labels={"model": "tagger"}).inc(9)
    reg.counter("paddle_tpu_serve_session_restores_total",
                help="session carries paged back into a decode slot",
                labels={"model": "tagger"}).inc(6)
    for reason, n in (("capacity", 2), ("ttl", 1)):
        reg.counter("paddle_tpu_serve_session_evictions_total",
                    help="sessions evicted from the host store",
                    labels={"model": "tagger", "reason": reason}).inc(n)
    reg.gauge("paddle_tpu_serve_session_resident",
              help="sessions whose carry is in a decode slot",
              labels={"model": "tagger"}).set(2)
    reg.gauge("paddle_tpu_serve_session_suspended",
              help="sessions paged out to the host store",
              labels={"model": "tagger"}).set(5)
    sw = reg.histogram("paddle_tpu_serve_session_swap_ms",
                       help="device<->host carry copy latency per swap",
                       labels={"model": "tagger"},
                       buckets=(0.5, 2.0, 10.0))
    for v in (0.2, 1.1, 6.0):
        sw.observe(v)
    # multi-host serving families (serve/cluster.py): per-host ring
    # membership plus the rehome counter — one excluded host mid-drill
    for host, live in (("hostA", 1), ("hostB", 0)):
        reg.gauge("paddle_tpu_serve_hosts",
                  help="serving-host membership (1 live in the ring, "
                       "0 excluded)",
                  labels={"host": host}).set(live)
    reg.counter("paddle_tpu_serve_host_rehomes_total",
                help="sessions re-homed onto this host after their "
                     "previous host left the ring",
                labels={"host": "hostA"}).inc(3)
    # the SLO verdict gauges (observe/health.py SloMonitor publishes
    # into these every evaluation) — fixed mid-burn values
    slo = metrics.slo_gauges(reg)
    slo["objective_p99_ms"].set(50)
    slo["current_p99_ms"].set(42.5)
    slo["burn_fast"].set(0.62)
    slo["burn_slow"].set(0.4)
    slo["budget_remaining"].set(0.6)
    slo["state"].set(0)
    # the build-info info-gauge (value is always 1, the payload is the
    # label set) — fixed label values here; live engines stamp the real
    # versions through observe.metrics.build_info()
    reg.gauge("paddle_tpu_build_info",
              help="build/version info (value is always 1)",
              labels={"version": "0.1.0", "jax_version": "0.9",
                      "schema": "1"}).set(1)
    return reg


def test_prometheus_exposition_matches_golden():
    """Golden-file check: the text exposition is a scrape contract
    (# HELP/# TYPE headers, label rendering, cumulative le buckets,
    _sum/_count) — it changes only together with the golden."""
    got = _golden_registry().to_prometheus()
    want = open(GOLDEN).read()
    assert got == want


def test_prometheus_exposition_parses_as_prometheus():
    """Structural re-parse of the exposition: every non-comment line is
    ``name{labels} value``, histogram bucket counts are cumulative and
    end in +Inf == _count."""
    text = _golden_registry().to_prometheus()
    # cumulativeness holds PER histogram series: key the bucket runs by
    # family+labels (the golden now carries two histogram families)
    buckets, counts = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)  # parseable sample value
        assert " " not in name
        if "_bucket" in name:
            family = name.split("_bucket", 1)[0]
            buckets.setdefault(family, []).append(int(value))
        if name.endswith("_count") or "_count{" in name:
            counts[name.split("_count", 1)[0]] = int(value)
    assert buckets  # the golden carries histogram families
    for family, runs in buckets.items():
        assert runs == sorted(runs), family  # cumulative
        assert runs[-1] == counts[family], family  # +Inf == _count
    assert counts["paddle_tpu_serve_request_latency_ms"] == 5


def test_merged_exposition_reconstructs_golden_from_worker_dumps():
    """The WorkerSet ``/metrics`` path: the router registry merged with
    per-worker ``dump_series`` snapshots under injected ``{worker=}``
    labels must render byte-identically to the same series registered
    locally — i.e. land on the same golden. With no extras the merged
    renderer is byte-identical to ``to_prometheus()``."""
    base = _golden_registry(include_workers=False)
    w0 = metrics.MetricsRegistry()
    w0.counter("paddle_tpu_serve_requests_total",
               help="requests completed by the serving engine",
               labels={"model": "tagger"}).inc(5)
    w1 = metrics.MetricsRegistry()
    w1.counter("paddle_tpu_serve_requests_total",
               help="requests completed by the serving engine",
               labels={"model": "tagger"}).inc(4)
    w1.gauge("paddle_tpu_serve_queue_depth",
             help="rows waiting for a batch flush").set(2)
    got = metrics.merged_exposition(
        base, [(w0.dump_series(), {"worker": "0"}),
               (w1.dump_series(), {"worker": "1"})])
    assert got == open(GOLDEN).read()
    full = _golden_registry()
    assert metrics.merged_exposition(full, []) == full.to_prometheus()
    # the dump itself is JSON-able (it crosses the control RPC)
    json.loads(json.dumps(full.dump_series()))


def test_label_escaping():
    reg = metrics.MetricsRegistry()
    reg.counter("c_total", labels={"k": 'a"b\\c\nd'}).inc()
    line = [l for l in reg.to_prometheus().splitlines()
            if not l.startswith("#")][0]
    assert line == 'c_total{k="a\\"b\\\\c\\nd"} 1'


def test_snapshot_json_roundtrip():
    snap = _golden_registry().snapshot()
    snap2 = json.loads(json.dumps(snap))  # JSON-able
    assert snap2["counters"]["paddle_tpu_serve_requests_total"] == 42
    assert snap2["gauges"]['paddle_tpu_serve_batch_fill_ratio'
                           '{bucket="4"}'] == 0.75
    hist = snap2["histograms"]["paddle_tpu_serve_request_latency_ms"]
    assert hist["count"] == 5
    assert hist["buckets"] == {"1": 1, "5": 3, "25": 4, "100": 4}
    assert hist["p50"] == pytest.approx(3.5)


def test_nonfinite_values_render_prometheus_style():
    reg = metrics.MetricsRegistry()
    reg.gauge("loss").set(float("nan"))
    reg.gauge("peak").set(float("inf"))
    lines = dict(l.rsplit(" ", 1) for l in reg.to_prometheus().splitlines()
                 if not l.startswith("#"))
    assert lines["loss"] == "NaN" and lines["peak"] == "+Inf"


def test_histogram_reservoir_is_bounded():
    reg = metrics.MetricsRegistry()
    h = reg.histogram("lat", buckets=(10.0,))
    for i in range(metrics.RESERVOIR_SIZE + 100):
        h.observe(float(i % 7))
    assert h.count == metrics.RESERVOIR_SIZE + 100  # counts stay exact
    assert len(h._recent) == metrics.RESERVOIR_SIZE  # window slides


def test_concurrent_observers_lose_nothing():
    reg = metrics.MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("v", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


# -- serving integration (the ISSUE acceptance check) ------------------------

@pytest.fixture(scope="module")
def mlp_bundle(tmp_path_factory):
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve import load_bundle
    from paddle_tpu.serve.export import export_bundle

    tmp = tmp_path_factory.mktemp("metrics_bundle")
    reset_name_counters()
    out = mlp(hidden=(16, 8))
    params = Parameters.create(out)
    export_bundle(out, params, str(tmp / "b"), batch_sizes=(1, 4),
                  name="mnist_mlp")
    return load_bundle(str(tmp / "b"))


def _get(base, path):
    return json.load(urllib.request.urlopen(base + path, timeout=30))


def test_metrics_endpoint_agrees_with_stats_after_burst(mlp_bundle):
    """Acceptance: /metrics is Prometheus-parseable and its request/
    batch counters agree with /stats after a burst of POST /infer."""
    from paddle_tpu.serve import InferenceEngine
    from paddle_tpu.serve.server import serve_in_thread

    reg = metrics.MetricsRegistry()
    with InferenceEngine(mlp_bundle, max_batch_size=4, max_latency_ms=4.0,
                         metrics_registry=reg) as eng:
        server, _ = serve_in_thread(mlp_bundle, eng)
        base = "http://%s:%d" % server.server_address
        try:
            health = _get(base, "/healthz")
            assert health == {"ok": True, "live": True, "ready": True,
                              "bundle": "mnist_mlp"}
            rng = np.random.RandomState(0)
            n_requests = 9
            for i in range(n_requests):
                x = rng.randn(1 + i % 2, 784).astype(np.float32)
                body = json.dumps({"inputs":
                                   {"pixel": x.tolist()}}).encode()
                req = urllib.request.Request(
                    base + "/infer", data=body,
                    headers={"Content-Type": "application/json"})
                json.load(urllib.request.urlopen(req, timeout=60))
            stats = _get(base, "/stats")
            assert stats["requests"] == n_requests
            assert stats["queue_depth"] == 0 and stats["in_flight"] == 0
            assert stats["latency_ms"]["p99"] >= stats["latency_ms"]["p50"]

            resp = urllib.request.urlopen(base + "/metrics", timeout=30)
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
            samples = {}
            for line in text.strip().splitlines():  # parseable exposition
                if line.startswith("#"):
                    continue
                name, value = line.rsplit(" ", 1)
                samples[name] = float(value)
            # the scrape and the JSON stats are the same counters
            assert samples["paddle_tpu_serve_requests_total"] \
                == stats["requests"]
            assert samples["paddle_tpu_serve_batches_total"] \
                == stats["batches"]
            assert samples["paddle_tpu_serve_rows_total"] == stats["rows"]
            assert samples["paddle_tpu_serve_pad_rows_total"] \
                == stats["pad_rows"]
            assert samples["paddle_tpu_serve_queue_depth"] == 0
            assert samples["paddle_tpu_serve_in_flight"] == 0
            assert samples[
                "paddle_tpu_serve_request_latency_ms_count"] == n_requests
            # per-bucket occupancy: fill + waste account for every slot
            for b in ("1", "4"):
                fill = samples.get(
                    'paddle_tpu_serve_batch_fill_ratio{bucket="%s"}' % b)
                waste = samples.get(
                    'paddle_tpu_serve_padding_waste_ratio{bucket="%s"}'
                    % b)
                if fill is not None:
                    assert fill + waste == pytest.approx(1.0)
        finally:
            server.shutdown()


def test_readiness_false_before_warmup_completes(mlp_bundle):
    """Acceptance: with async warmup the endpoints bind first and
    /healthz + /readyz report not-ready (503) until every bucket is
    warm; liveness is true the whole time."""
    from paddle_tpu.serve import InferenceEngine
    from paddle_tpu.serve.server import serve_in_thread

    gate = threading.Event()
    done = threading.Event()
    real_warmup = mlp_bundle.warmup

    def slow_warmup():
        gate.wait(timeout=30)
        try:
            return real_warmup()
        finally:
            done.set()

    mlp_bundle.warmup = slow_warmup
    try:
        eng = InferenceEngine(mlp_bundle, max_batch_size=4,
                              max_latency_ms=4.0, warmup="async",
                              metrics_registry=metrics.MetricsRegistry())
        server, _ = serve_in_thread(mlp_bundle, eng)
        base = "http://%s:%d" % server.server_address
        try:
            assert not eng.ready()
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(base + "/healthz", timeout=30)
            assert exc_info.value.code == 503
            payload = json.load(exc_info.value)
            assert payload["ready"] is False and payload["live"] is True
            assert payload["ok"] is False
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(base + "/readyz", timeout=30)
            assert exc_info.value.code == 503
            assert _get(base, "/livez") == {"live": True}

            gate.set()  # let the warmup finish
            assert done.wait(timeout=60)
            assert eng._ready.wait(timeout=30)
            health = _get(base, "/healthz")
            assert health["ok"] is True and health["ready"] is True
            assert _get(base, "/readyz") == {"ready": True}
        finally:
            server.shutdown()
            eng.stop()
    finally:
        mlp_bundle.warmup = real_warmup


def test_failed_async_warmup_stays_not_ready(mlp_bundle):
    """A warmup that raises (corrupt artifact, compile OOM) must leave
    the readiness probe NOT-ready — flipping ready would route traffic
    into the compiles readiness exists to fence."""
    import time

    from paddle_tpu.serve import InferenceEngine

    real_warmup = mlp_bundle.warmup
    failed = threading.Event()

    def broken_warmup():
        try:
            raise RuntimeError("corrupt artifact")
        finally:
            failed.set()

    mlp_bundle.warmup = broken_warmup
    try:
        eng = InferenceEngine(mlp_bundle, max_batch_size=4,
                              warmup="async",
                              metrics_registry=metrics.MetricsRegistry())
        assert failed.wait(timeout=30)
        time.sleep(0.05)  # let the warmup thread unwind
        assert not eng.ready()
        assert eng.stats()["ready"] is False
        eng.stop()
        # sync warmup propagates the failure to the constructor
        with pytest.raises(RuntimeError, match="corrupt artifact"):
            InferenceEngine(mlp_bundle, max_batch_size=4, warmup=True,
                            metrics_registry=metrics.MetricsRegistry())
    finally:
        mlp_bundle.warmup = real_warmup


@pytest.mark.slow
def test_cli_serve_process_exposes_metrics(mlp_bundle, tmp_path):
    """Subprocess variant of the acceptance check: a live ``cli serve``
    process answers GET /metrics with Prometheus text agreeing with
    /stats after POST /infer traffic (readiness polled first — the CLI
    warms asynchronously)."""
    import subprocess
    import sys
    import time

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env.setdefault("PADDLE_TPU_LOG_LEVEL", "WARNING")
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.cli", "serve",
         mlp_bundle.directory, "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        banner = proc.stdout.readline()  # "serving ... on http://..."
        base = banner.split("on ")[1].split(" ")[0].strip()
        deadline = time.time() + 120
        while time.time() < deadline:  # poll readiness (async warmup)
            try:
                if _get(base, "/readyz")["ready"]:
                    break
            except urllib.error.HTTPError:
                pass
            time.sleep(0.2)
        else:
            pytest.fail("serve process never became ready")
        x = np.random.RandomState(2).randn(3, 784).astype(np.float32)
        body = json.dumps({"inputs": {"pixel": x.tolist()}}).encode()
        req = urllib.request.Request(
            base + "/infer", data=body,
            headers={"Content-Type": "application/json"})
        json.load(urllib.request.urlopen(req, timeout=60))
        stats = _get(base, "/stats")
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=30).read().decode()
        samples = dict(l.rsplit(" ", 1) for l in text.splitlines()
                       if l and not l.startswith("#"))
        assert float(samples["paddle_tpu_serve_requests_total"]) \
            == stats["requests"] >= 1
        assert float(samples["paddle_tpu_serve_batches_total"]) \
            == stats["batches"]
        assert float(samples["paddle_tpu_serve_ready"]) == 1
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_trainer_updates_train_metrics():
    """trainer.SGD bumps the process-wide steps/examples counters and
    the loss / examples-per-sec gauges every finalized step."""
    import paddle_tpu as paddle
    from paddle_tpu import activation as A
    from paddle_tpu import data_type as dt
    from paddle_tpu import layer as L
    from paddle_tpu import minibatch
    from paddle_tpu import optimizer as opt
    from paddle_tpu.parameters import Parameters

    reg = metrics.get_registry()
    steps0 = reg.counter("paddle_tpu_train_steps_total").value
    examples0 = reg.counter("paddle_tpu_train_examples_total").value

    x = L.data(name="x", type=dt.dense_vector(4))
    lab = L.data(name="y", type=dt.integer_value(2))
    out = L.fc(input=L.fc(input=x, size=8, act=A.Tanh()), size=2)
    cost = L.classification_cost(input=out, label=lab)
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost, params, opt.Momentum(momentum=0.9, learning_rate=0.1))

    def reader():
        rng = np.random.RandomState(3)
        for _ in range(16):
            xv = rng.randn(4).astype(np.float32)
            yield xv, int(xv[0] > 0)

    trainer.train(minibatch.batch(reader, 8), num_passes=1)
    assert reg.counter("paddle_tpu_train_steps_total").value == steps0 + 2
    assert reg.counter(
        "paddle_tpu_train_examples_total").value == examples0 + 16
    assert np.isfinite(reg.gauge("paddle_tpu_train_loss").value)
    assert reg.gauge("paddle_tpu_train_examples_per_sec").value > 0


def test_build_info_gauge_registered_by_engine(mlp_bundle):
    """Every serving engine registers the build-info info-gauge: value
    1, the payload is the label set (version / jax_version / schema)."""
    import jax

    import paddle_tpu
    from paddle_tpu.serve import InferenceEngine

    reg = metrics.MetricsRegistry()
    with InferenceEngine(mlp_bundle, metrics_registry=reg,
                         warmup=False):
        pass
    line = [l for l in reg.to_prometheus().splitlines()
            if l.startswith("paddle_tpu_build_info")][0]
    assert line.endswith(" 1")
    assert 'version="%s"' % paddle_tpu.__version__ in line
    assert 'jax_version="%s"' % jax.__version__ in line
    assert 'schema="1"' in line


def test_concurrent_scrapes_during_fleet_burst(mlp_bundle):
    """The scrape contract under load: N scraper threads rendering the
    exposition while a 2-replica fleet serves a burst — no exceptions,
    no torn exposition (every line parses), and the requests counter is
    monotone across successive scrapes."""
    from paddle_tpu.serve import ReplicaSet

    reg = metrics.MetricsRegistry()
    errors, stop = [], threading.Event()

    def scraper():
        last = -1.0
        while not stop.is_set():
            try:
                text = reg.to_prometheus()
                seen = None
                for line in text.strip().splitlines():
                    if line.startswith("#"):
                        continue
                    name, value = line.rsplit(" ", 1)
                    float(value)  # parseable: no torn lines
                    assert " " not in name
                    if name.startswith(
                            "paddle_tpu_serve_requests_total"):
                        seen = (seen or 0.0) + float(value)
                if seen is not None:
                    if seen < last:
                        errors.append("requests_total went backwards: "
                                      "%s < %s" % (seen, last))
                    last = seen
            except Exception as exc:  # noqa: BLE001 — the assertion below reports
                errors.append(repr(exc))
                return

    with ReplicaSet(mlp_bundle, replicas=2,
                    metrics_registry=reg) as fleet:
        scrapers = [threading.Thread(target=scraper,
                                     name="metrics-scraper-%d" % i)
                    for i in range(3)]
        for t in scrapers:
            t.start()
        rng = np.random.RandomState(0)
        futures = [fleet.submit(
            {"pixel": rng.randn(1, 784).astype(np.float32)})
            for _ in range(40)]
        for f in futures:
            f.result(timeout=120)
        stop.set()
        for t in scrapers:
            t.join(timeout=30)
    assert errors == [], errors
    counters = reg.snapshot()["counters"]
    total = sum(v for k, v in counters.items()
                if k.startswith("paddle_tpu_serve_requests_total"))
    assert total == 40
