"""repro.obs: metrics registry semantics, Prometheus exposition, histogram
percentile accuracy, phase-span tracing (Chrome trace JSON), the JSONL
metrics sink, the no-op twins, and the serving HTTP exposition endpoints.

The one invariant everything here leans on: instrumentation must never
change what the system computes — the last test checks training draws are
bit-identical with and without the full observability bundle.
"""
import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.obs import (NULL_SINK, NULL_TRACER, JsonlSink, MetricsRegistry,
                       Observability, SpanTracer, WindowRate)
from repro.obs.metrics import NOOP_REGISTRY


class TestRegistry:
    def test_counter_inc_and_value(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests")
        c.inc()
        c.inc(4)
        assert c.value == 5

    def test_labelled_counter_families(self):
        reg = MetricsRegistry()
        c = reg.counter("errs_total", "errors", labelnames=("reason",))
        c.labels(reason="shutdown").inc()
        c.labels(reason="exception").inc(2)
        c.labels(reason="shutdown").inc()
        assert c.per_label() == {"shutdown": 2, "exception": 2}
        assert c.value == 4

    def test_create_or_get_same_object(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "x")
        b = reg.counter("x_total", "x")
        assert a is b
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total", "now a gauge?")

    def test_gauge_set_and_callback(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth", "queue depth")
        g.set(3.0)
        assert g.value == 3.0
        box = [7.0]
        live = reg.gauge("live", "callback gauge", fn=lambda: box[0])
        assert live.value == 7.0
        box[0] = 9.0
        # the callback is re-evaluated at every collection
        assert "live 9" in reg.render_prometheus()

    def test_registry_names_are_stable(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "a")
        reg.histogram("b_ms", "b")
        reg.gauge("c", "c")
        assert set(reg.names()) == {"a_total", "b_ms", "c"}


class TestHistogram:
    def test_percentiles_match_numpy_exactly(self):
        """The bounded exact-sample window means p50/p99 are np.percentile,
        not a bucket interpolation — the engine's p50_ms/p99_ms contract."""
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", "latency")
        rng = np.random.default_rng(0)
        xs = rng.lognormal(2.0, 1.0, size=1000)
        for x in xs:
            h.observe(float(x))
        for q in (50, 90, 99):
            assert h.percentile(q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12)
        assert h.count == 1000
        assert h.sum == pytest.approx(xs.sum(), rel=1e-9)
        assert h.mean == pytest.approx(xs.mean(), rel=1e-9)

    def test_bucket_estimate_is_close(self):
        """The Prometheus-side cumulative buckets carry the same story: the
        interpolated estimate lands within a bucket width of the truth."""
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", "latency",
                          buckets=(1, 2, 5, 10, 20, 50, 100))
        xs = np.linspace(0.5, 40.0, 500)
        for x in xs:
            h.observe(float(x))
        est = h.quantile_est(50)
        assert 10 <= est <= 50    # truth ~20.25, bucket (10, 20]

    def test_window_is_bounded(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", "latency")
        for i in range(10_000):
            h.observe(float(i))
        assert h.count == 10_000          # cumulative count keeps going
        # but percentiles slide over the bounded window (memory stays flat)
        assert h.percentile(0) >= 10_000 - 4096


class TestPrometheusExposition:
    _sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+-]+(inf)?$")

    def _registry(self):
        reg = MetricsRegistry()
        reg.counter("reqs_total", "total requests").inc(3)
        errs = reg.counter("errs_total", "errors", labelnames=("reason",))
        errs.labels(reason='sh"ut\ndown\\').inc()
        h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        h.observe(50.0)
        reg.gauge("depth", "queue depth").set(2)
        return reg

    def test_format(self):
        text = self._registry().render_prometheus()
        lines = text.strip().split("\n")
        for ln in lines:
            assert (ln.startswith("# HELP ") or ln.startswith("# TYPE ")
                    or self._sample.match(ln)), ln
        # every family is declared before its samples
        assert "# TYPE reqs_total counter" in text
        assert "# TYPE lat_ms histogram" in text
        assert "# TYPE depth gauge" in text
        assert "reqs_total 3" in text

    def test_label_escaping(self):
        text = self._registry().render_prometheus()
        # per the text format: backslash, double-quote and newline escaped
        assert r'errs_total{reason="sh\"ut\ndown\\"} 1' in text

    def test_histogram_cumulative_buckets(self):
        text = self._registry().render_prometheus()
        assert 'lat_ms_bucket{le="1"} 1' in text
        assert 'lat_ms_bucket{le="10"} 2' in text
        assert 'lat_ms_bucket{le="+Inf"} 3' in text
        assert "lat_ms_count 3" in text
        assert "lat_ms_sum 55.5" in text

    def test_snapshot_is_jsonable(self):
        snap = self._registry().snapshot()
        json.dumps(snap)
        assert snap["reqs_total"] == 3
        assert snap["lat_ms"]["count"] == 3
        assert snap["errs_total"] == {'sh"ut\ndown\\': 1}


class TestWindowRate:
    def test_idle_gap_does_not_drag_rate(self):
        r = WindowRate(window_s=10.0)
        # burst an hour ago, then a fresh burst: the rate reflects only the
        # in-window events (the lifetime-span rate would read ~0.003/s)
        for i in range(10):
            r.record(1, t=100.0 + i * 0.1)
        for i in range(10):
            r.record(1, t=3700.0 + i * 0.1)
        assert r.rate(now=3701.0) == pytest.approx(10 / 1.0, rel=0.2)

    def test_empty_is_zero(self):
        assert WindowRate().rate(now=5.0) == 0.0


class TestSpanTracer:
    def test_chrome_trace_schema(self, tmp_path):
        tr = SpanTracer(enabled=True, process_name="test")
        with tr.span("outer", k=1):
            with tr.span("inner"):
                pass
        tr.complete("manual", 1.0, 2.0, n=3)
        tr.instant("tick")
        p = tmp_path / "trace.json"
        tr.export(str(p))
        doc = json.loads(p.read_text())
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        spans = [e for e in evs if e["ph"] == "X"]
        assert any(e["name"] == "process_name" for e in meta)
        names = [e["name"] for e in spans]
        assert {"outer", "inner", "manual"} <= set(names)
        for e in spans:
            assert {"ph", "name", "ts", "dur", "pid", "tid"} <= e.keys()
            assert e["dur"] >= 0
        # Perfetto wants monotonically sane timestamps: sorted by ts
        ts = [e["ts"] for e in evs if e["ph"] == "X"]
        assert ts == sorted(ts)
        # the manually-timed phase is exactly 1s
        manual = next(e for e in spans if e["name"] == "manual")
        assert manual["dur"] == pytest.approx(1e6, rel=1e-6)
        assert manual["args"]["n"] == 3

    def test_disabled_tracer_records_nothing(self):
        tr = SpanTracer(enabled=False)
        with tr.span("x"):
            pass
        # metadata (process name) may remain; no span events recorded
        assert [e for e in tr.to_chrome()["traceEvents"]
                if e["ph"] != "M"] == []

    def test_spans_land_in_a_profile(self, tmp_path):
        """Every enabled span also enters a TraceAnnotation, so a profile
        recorded meanwhile holds it on the host plane, on the device
        trace's clock, where the benchmark's reduction finds it."""
        import importlib
        import pathlib
        import sys

        import jax

        root = str(pathlib.Path(__file__).resolve().parents[1])
        if root not in sys.path:
            sys.path.insert(0, root)
        bench_trace = importlib.import_module("bench.trace")
        tr = SpanTracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("obs.probe.span"):
                jax.numpy.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        data = next(tmp_path.rglob("*.xplane.pb")).read_bytes()
        summary = bench_trace.from_xspace(data, [])
        found = [s for s in summary.spans if s.name == "obs.probe.span"]
        assert len(found) == 1 and found[0].end > found[0].start

    def test_ring_buffer_bounds_memory(self):
        tr = SpanTracer(enabled=True, max_events=16)
        for i in range(100):
            tr.complete(f"s{i}", i, i + 0.5)
        assert len(tr.to_chrome()["traceEvents"]) <= 16 + 2  # + metadata

    def test_span_set_attaches_args(self):
        tr = SpanTracer(enabled=True)
        with tr.span("s") as sp:
            sp.set(bytes=128)
        ev = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"][0]
        assert ev["args"]["bytes"] == 128


class TestJsonlSink:
    def test_writes_one_json_object_per_line(self, tmp_path):
        p = tmp_path / "m.jsonl"
        with JsonlSink(str(p)) as sink:
            sink.write(dict(iteration=0, tps=np.float32(1.5),
                            tokens=np.int64(10)))
            sink.write(dict(iteration=1, ll=None))
            assert sink.rows_written == 2
        rows = [json.loads(ln) for ln in p.read_text().splitlines()]
        assert rows[0] == {"iteration": 0, "tps": 1.5, "tokens": 10}
        assert rows[1]["ll"] is None

    def test_null_sink_swallows(self):
        NULL_SINK.write(dict(a=1))
        NULL_SINK.close()
        assert NULL_SINK.rows_written == 0


class TestNoopTwins:
    def test_noop_mirrors_real_api(self):
        """Call sites stay unconditional: every operation used against the
        real bundle must be a no-op on the noop bundle, not an error."""
        obs = Observability.noop()
        assert not obs.enabled
        c = obs.registry.counter("x_total", "x", labelnames=("reason",))
        c.inc()
        c.labels(reason="r").inc(2)
        assert c.value == 0 and c.per_label() == {}
        h = obs.registry.histogram("h_ms", "h")
        h.observe(1.0)
        assert h.count == 0 and h.percentile(99) == 0.0 and h.mean == 0.0
        g = obs.registry.gauge("g", "g", fn=lambda: 1.0)
        g.set(2.0)
        assert g.value == 0.0
        assert obs.registry.render_prometheus() == ""
        assert obs.registry.snapshot() == {}
        r = obs.window_rate(5.0)
        r.record(3)
        assert r.rate() == 0.0
        with obs.tracer.span("s", k=1) as sp:
            if sp is not None and hasattr(sp, "set"):
                sp.set(x=1)
        obs.tracer.complete("c", 0.0, 1.0)
        assert [e for e in obs.tracer.to_chrome()["traceEvents"]
                if e["ph"] != "M"] == []
        assert NOOP_REGISTRY.counter("y_total", "y").value == 0
        with NULL_TRACER.span("z"):
            pass


def _serve_args(extra=()):
    from repro.launch.serve_lda import build_argparser

    return build_argparser().parse_args(
        ["--snapshot", "unused.npz", "--port", "0",
         "--burn-in", "2", "--samples", "2"] + list(extra))


@pytest.fixture(scope="module")
def http_endpoint():
    """The real stdlib HTTP server from serve_lda on an ephemeral port,
    backed by a tiny planted model."""
    import jax.numpy as jnp
    from repro.launch.serve_lda import make_engine, make_http_server
    from repro.serve import ModelSnapshot

    V, K = 64, 8
    phi = np.zeros((V, K), np.int32)
    for k in range(K):
        phi[k * 8:(k + 1) * 8, k] = 200
    snap = ModelSnapshot(phi_vk=jnp.asarray(phi),
                         phi_sum=jnp.asarray(phi.sum(0)),
                         alpha=0.1, beta=0.01, num_words_total=V)
    args = _serve_args()
    model, engine = make_engine(args, snap)
    httpd = make_http_server(args, model, engine)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        yield base, engine
    finally:
        httpd.shutdown()
        engine.stop()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


class TestHttpExposition:
    def test_healthz(self, http_endpoint):
        base, _ = http_endpoint
        status, _, body = _get(base + "/healthz")
        assert status == 200
        assert json.loads(body)["ok"] is True

    def test_metrics_prometheus(self, http_endpoint):
        base, _ = http_endpoint
        # serve one doc so the counters are warm
        status, out = _post(base + "/infer", {"tokens": list(range(8))})
        assert status == 200 and "theta" in out
        status, ctype, body = _get(base + "/metrics")
        assert status == 200
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        text = body.decode()
        assert "# TYPE repro_serve_requests_total counter" in text
        assert "# TYPE repro_serve_request_latency_ms histogram" in text
        assert 'repro_serve_request_latency_ms_bucket{le="+Inf"}' in text

    def test_stats_enriched(self, http_endpoint):
        base, _ = http_endpoint
        status, _, body = _get(base + "/stats")
        assert status == 200
        s = json.loads(body)
        for k in ("requests", "docs_per_sec", "docs_per_sec_window",
                  "errors_by_reason", "queue_depth", "jit_cache_size",
                  "model_version", "num_words", "num_topics",
                  "device_memory"):
            assert k in s, k
        assert s["num_words"] == 64 and s["num_topics"] == 8

    def test_trace_endpoint(self, http_endpoint):
        base, _ = http_endpoint
        _post(base + "/infer", {"tokens": list(range(8))})
        status, ctype, body = _get(base + "/trace")
        assert status == 200
        assert ctype.startswith("application/json")
        doc = json.loads(body)
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        names = {e["name"] for e in spans}
        # the serving hot path phases show up as spans
        assert {"pack", "sweep", "assemble", "callback"} <= names, names

    def test_unknown_route_404(self, http_endpoint):
        base, _ = http_endpoint
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/nope")
        assert ei.value.code == 404


def test_instrumentation_does_not_change_draws(tiny_corpus):
    """The load-bearing invariant: the full observability bundle (registry +
    tracer + named_scope phase annotations) must leave training draws
    bit-identical to the uninstrumented run."""
    from repro.core import trainer

    cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
    r_noop = trainer.train(tiny_corpus, cfg, 3, eval_every=3,
                           obs=Observability.noop())
    r_full = trainer.train(tiny_corpus, cfg, 3, eval_every=3,
                           obs=Observability.default(trace=True))
    np.testing.assert_array_equal(np.asarray(r_noop.state.z),
                                  np.asarray(r_full.state.z))
    np.testing.assert_array_equal(np.asarray(r_noop.state.phi_vk),
                                  np.asarray(r_full.state.phi_vk))


def _mesh_case(tiny_corpus):
    import jax

    from repro.core import trainer

    cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
    return cfg, jax.make_mesh((1,), ("data",))


def test_stats_scope_does_not_change_mesh_draws(tiny_corpus, monkeypatch):
    """The ``lda.stats`` scope around the mesh step's stats collectives
    reaches the compiled program and leaves its draws and stats
    bit-identical to the same step built with every scope off."""
    import contextlib

    import jax

    from repro.distributed.partition import DistributedLDA

    cfg, mesh = _mesh_case(tiny_corpus)

    def two_steps():
        dl = DistributedLDA(cfg, mesh, tiny_corpus, mode="1d")
        state = dl.init()
        for _ in range(2):
            state, stats = dl.step(state)
        return dl, state, stats

    dl, on, stats_on = two_steps()
    assert "lda.stats/" in dl.lower_step().compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    dl, off, stats_off = two_steps()
    assert "lda.stats/" not in dl.lower_step().compile().as_text()
    for f in ("z", "phi_vk", "phi_sum"):
        np.testing.assert_array_equal(np.asarray(getattr(on, f)),
                                      np.asarray(getattr(off, f)))
    for a, b in zip(stats_on, stats_off):
        assert float(a) == float(b)


def test_partition_counters_in_first_row_do_not_change_draws(tiny_corpus,
                                                             tmp_path):
    """``fit`` on a mesh writes the partition's counters into its first
    JSONL row only, and training draws the same with the sink as without."""
    from repro.train import fit

    cfg, mesh = _mesh_case(tiny_corpus)
    path = tmp_path / "m.jsonl"
    plain = fit(tiny_corpus, cfg, 3, mesh=mesh, eval_every=3)
    logged = fit(tiny_corpus, cfg, 3, mesh=mesh, eval_every=3,
                 metrics_out=str(path))
    np.testing.assert_array_equal(np.asarray(plain.state.z),
                                  np.asarray(logged.state.z))
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(rows) == 3
    assert all("partition" not in r for r in rows[1:])
    c = rows[0]["partition"]
    assert c["shard_tokens"] == [tiny_corpus.num_tokens]
    assert c["tiles_max"] == c["shard_tiles"][0] > 0
    slots = c["tiles_max"] * cfg.tile_tokens
    assert c["padding_share"] == pytest.approx(
        1 - tiny_corpus.num_tokens / slots)
    assert c["sync_bytes"] == tiny_corpus.num_words * cfg.num_topics * 4
