"""SLO objectives, burn-rate alerting, and the metrics-history plane
(ISSUE 15): bounded per-series rings with windowed delta math, the
declarative rule engine's fire/sustain/clear lifecycle, and the live
surfacing (/alerts, /query, /healthz, cluster aggregate).

Acceptance anchors: golden HAND-COMPUTED burn-rate values (fast/slow
window error fractions over histogram deltas), ring bounded-memory
under a multi-thread writer/scraper race, and the e2e tier-1 lifecycle
proof — an injected TTFT breach on a live ServeServer fires, sustains,
and clears an alert visible on /alerts and in the cluster aggregate.
All tier-1 fast.
"""

import json
import math
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from consensusml_tpu.obs import (
    AlertEngine,
    AlertRule,
    ClusterWriter,
    MetricsHistory,
    MetricsRegistry,
    SloSpec,
    aggregate,
    default_ruleset,
)
from consensusml_tpu.obs.metrics import DEFAULT_SLO_BUCKETS
from consensusml_tpu.obs.tracer import SpanTracer

pytestmark = pytest.mark.telemetry


def _engine(hist, rules, reg):
    return AlertEngine(
        hist, rules=rules, registry=reg, tracer=SpanTracer(), quiet=True
    )


# ---------------------------------------------------------------------------
# history rings: retention + windowed query math
# ---------------------------------------------------------------------------


def test_history_rate_and_increase_golden():
    reg = MetricsRegistry()
    c = reg.counter("t_total")
    hist = MetricsHistory(reg, keep=8)
    c.inc(100)
    hist.record(now=0.0)
    c.inc(60)
    hist.record(now=60.0)
    # delta over the window: exactly the 60 added between the samples
    assert hist.increase("t_total", 60.0, now=60.0) == pytest.approx(60.0)
    assert hist.rate("t_total", 60.0, now=60.0) == pytest.approx(1.0)
    # counter reset: a restart's negative delta is not a decrease
    reg2 = MetricsRegistry()
    g = reg2.gauge("t_reset")  # gauge lets us force the reset shape
    hist2 = MetricsHistory(reg2, keep=8)
    for now, v in ((0, 50.0), (10, 70.0), (20, 5.0), (30, 25.0)):
        g.set(v)
        hist2.record(now=float(now))
    # positive deltas only: (70-50) + (25-5) = 40
    assert hist2.increase("t_reset", 30.0, now=30.0) == pytest.approx(40.0)


def test_history_windowed_percentile_from_deltas():
    reg = MetricsRegistry()
    h = reg.histogram("t_lat_seconds", buckets=(0.1, 0.2, 0.4))
    hist = MetricsHistory(reg, keep=8)
    for _ in range(100):
        h.observe(0.05)  # old traffic, all fast
    hist.record(now=0.0)
    for _ in range(10):
        h.observe(0.3)  # recent traffic, all slow
    hist.record(now=60.0)
    # the window [0, 60] delta is ONLY the 10 slow observations: p99
    # interpolates inside the (0.2, 0.4] bucket, far above the lifetime
    # p99 (which the 100 fast obs dominate)
    p99 = hist.quantile("t_lat_seconds", 0.99, 60.0, now=60.0)
    assert 0.2 < p99 <= 0.4
    # exact interpolation: target 9.9 of 10 in the third bucket ->
    # 0.2 + (9.9/10) * (0.4 - 0.2)
    assert p99 == pytest.approx(0.2 + 0.99 * 0.2)
    stats = hist.window_stats("t_lat_seconds", 60.0, now=60.0)
    assert stats["count"] == 10
    assert stats["mean"] == pytest.approx(0.3)


def test_history_ring_is_bounded_and_capped():
    reg = MetricsRegistry()
    g = reg.gauge("t_g")
    hist = MetricsHistory(reg, keep=4)
    for i in range(20):
        g.set(i)
        hist.record(now=float(i))
    assert len(hist.last("t_g", 100)) == 4  # ring, not a log
    assert [v for _t, v in hist.last("t_g", 100)] == [16, 17, 18, 19]
    # series cap: refusals are counted, never silent
    reg2 = MetricsRegistry()
    for i in range(8):
        reg2.gauge("t_many", labels={"i": i}).set(i)
    hist2 = MetricsHistory(reg2, keep=4, max_series=3)
    hist2.record(now=0.0)
    assert len(hist2) == 3
    assert reg2.counter(
        "consensusml_history_series_dropped_total"
    ).value > 0


def test_history_bounded_memory_under_writer_scraper_race():
    """Observers, the recorder, and scrapers race; the rings stay
    bounded and every query returns without raising."""
    reg = MetricsRegistry()
    h = reg.histogram("t_race_seconds", buckets=DEFAULT_SLO_BUCKETS)
    c = reg.counter("t_race_total")
    hist = MetricsHistory(reg, keep=16)
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            h.observe(0.001 * (i % 13), exemplar=f"r{i}")
            c.inc()
            i += 1

    def recorder():
        while not stop.is_set():
            hist.record()

    def scraper():
        while not stop.is_set():
            try:
                hist.query("t_race_seconds", window_s=1.0)
                hist.rate("t_race_total", 1.0)
                hist.digest(points=8)
                hist.spark("t_race_seconds", points=8)
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(e)
                return

    threads = [
        threading.Thread(target=fn, daemon=True)
        for fn in (writer, writer, recorder, scraper, scraper)
    ]
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    assert not errors
    assert all(
        len(hist.last(k, 10_000)) <= 16 for k in hist.keys()
    )
    digest = hist.digest()
    assert digest["samples_total"] <= 16 * len(hist.keys())
    assert digest["memory_bytes_est"] > 0
    # the accounting gauges landed in the registry
    snap = reg.snapshot()["metrics"]
    assert snap["consensusml_history_series"] == len(hist.keys())


# ---------------------------------------------------------------------------
# burn-rate golden math + rule lifecycle
# ---------------------------------------------------------------------------


def test_burn_rate_golden_fast_slow_windows():
    """Hand-computed: 20 observations land in the fast window, 5 above
    the 0.1 s SLO threshold -> error fraction 0.25 against a 0.05
    budget = burn 5.0x in BOTH windows; factor 4 fires, and an empty
    fast window clears it."""
    reg = MetricsRegistry()
    h = reg.histogram("t_slo_seconds", buckets=DEFAULT_SLO_BUCKETS)
    hist = MetricsHistory(reg, keep=16)
    rule = AlertRule(
        "slo-burn", "t_slo_seconds", kind="burn_rate",
        slo=SloSpec("t_slo_seconds", threshold_s=0.1, objective=0.95),
        fast_window_s=60.0, slow_window_s=300.0, burn_factor=4.0,
    )
    eng = _engine(hist, [rule], reg)
    for _ in range(80):
        h.observe(0.05)  # pre-window baseline traffic, all good
    hist.record(now=0.0)
    assert eng.evaluate(now=0.0) == []  # single sample: no delta yet
    for _ in range(15):
        h.observe(0.05)
    for _ in range(5):
        h.observe(0.2)  # the breach: 5/20 over threshold
    hist.record(now=60.0)
    firing = eng.evaluate(now=60.0)
    assert len(firing) == 1
    a = firing[0]
    assert a["rule"] == "slo-burn" and a["state"] == "firing"
    # golden burn value: bad_fraction / budget = 0.25 / 0.05
    assert a["value"] == pytest.approx(5.0)
    # hand-check the window primitives the engine composed
    assert hist.bad_fraction(
        "t_slo_seconds", 0.1, 60.0, now=60.0
    ) == pytest.approx(0.25)
    assert hist.bad_fraction(
        "t_slo_seconds", 0.1, 300.0, now=60.0
    ) == pytest.approx(0.25)
    # sustains while the breach stays inside the fast window
    hist.record(now=90.0)
    assert len(eng.evaluate(now=90.0)) == 1
    # no new traffic: both windows' deltas empty out -> resolve
    hist.record(now=200.0)
    assert eng.evaluate(now=200.0) == []
    snap = eng.snapshot()
    assert snap["firing_total"] == 0
    assert [a["rule"] for a in snap["resolved_recent"]] == ["slo-burn"]
    # lifecycle metrics
    m = reg.snapshot()["metrics"]
    assert m["consensusml_alert_fired_total"] == 1.0
    assert m["consensusml_alert_resolved_total"] == 1.0
    assert m['consensusml_alert_firing{rule="slo-burn"}'] == 0.0


def test_burn_rate_needs_both_windows():
    """A breach entirely OUTSIDE the fast window must not fire even
    while the slow window still burns (the multiwindow point: old
    badness alone does not page)."""
    reg = MetricsRegistry()
    h = reg.histogram("t_slo_seconds", buckets=DEFAULT_SLO_BUCKETS)
    hist = MetricsHistory(reg, keep=16)
    rule = AlertRule(
        "slo-burn", "t_slo_seconds", kind="burn_rate",
        slo=SloSpec("t_slo_seconds", threshold_s=0.1, objective=0.95),
        fast_window_s=60.0, slow_window_s=600.0, burn_factor=4.0,
    )
    eng = _engine(hist, [rule], reg)
    hist.record(now=0.0)
    for _ in range(20):
        h.observe(1.0)  # all bad
    hist.record(now=10.0)
    assert len(eng.evaluate(now=10.0)) == 1  # both windows burning
    # 5 minutes later: good traffic resumed; the fast window is clean
    # but the slow window still contains the old breach
    for _ in range(50):
        h.observe(0.01)
    hist.record(now=300.0)
    assert hist.bad_fraction(
        "t_slo_seconds", 0.1, 600.0, now=300.0
    ) > 0.2  # slow window still burns...
    assert eng.evaluate(now=300.0) == []  # ...but the alert cleared


def test_threshold_rule_sustain_and_labels():
    reg = MetricsRegistry()
    for i in (0, 1):
        reg.gauge("t_depth", labels={"engine": i}).set(1.0)
    hist = MetricsHistory(reg, keep=16)
    rule = AlertRule(
        "backlog", "t_depth", op="above", threshold=10.0, for_s=20.0
    )
    eng = _engine(hist, [rule], reg)
    hist.record(now=0.0)
    assert eng.evaluate(now=0.0) == []
    # only engine 1 breaches; must sustain for_s before firing
    reg.gauge("t_depth", labels={"engine": 1}).set(50.0)
    hist.record(now=10.0)
    assert eng.evaluate(now=10.0) == []  # breach started, not sustained
    hist.record(now=35.0)
    firing = eng.evaluate(now=35.0)
    assert len(firing) == 1
    assert firing[0]["series"] == 't_depth{engine="1"}'
    # recovery clears it
    reg.gauge("t_depth", labels={"engine": 1}).set(0.0)
    hist.record(now=40.0)
    assert eng.evaluate(now=40.0) == []


def test_stale_rule_fires_on_old_heartbeat():
    reg = MetricsRegistry()
    hb = reg.gauge("t_heartbeat_seconds")
    hist = MetricsHistory(reg, keep=8)
    rule = AlertRule(
        "loop-stale", "t_heartbeat_seconds", kind="stale", max_age_s=30.0
    )
    eng = _engine(hist, [rule], reg)
    hb.set(1000.0)
    hist.record(now=1000.0)
    assert eng.evaluate(now=1010.0) == []
    firing = eng.evaluate(now=1045.0)  # 45 s stale
    assert len(firing) == 1 and firing[0]["rule"] == "loop-stale"
    assert firing[0]["value"] == pytest.approx(45.0)
    hb.set(1050.0)
    hist.record(now=1050.0)
    assert eng.evaluate(now=1051.0) == []


def test_default_ruleset_quiet_on_healthy_series():
    """The bundled posture fires nothing against a healthy serving
    shape (fast TTFTs, shallow queue, free blocks, fresh heartbeats)."""
    reg = MetricsRegistry()
    ttft = reg.histogram(
        "consensusml_serve_ttft_seconds", buckets=DEFAULT_SLO_BUCKETS
    )
    reg.gauge("consensusml_serve_queue_depth").set(3.0)
    reg.gauge("consensusml_pool_blocks_free").set(40.0)
    reg.gauge("consensusml_health_bound_violation").set(0.0)
    hb = reg.gauge("consensusml_serve_loop_heartbeat_seconds")
    hist = MetricsHistory(reg, keep=16)
    eng = _engine(hist, default_ruleset(), reg)
    t0 = 1000.0
    for tick in range(4):
        now = t0 + 15.0 * tick
        for _ in range(50):
            ttft.observe(0.05)
        hb.set(now)
        hist.record(now=now)
        assert eng.evaluate(now=now) == [], f"false firing at tick {tick}"


def test_notify_routes_health_episodes_into_snapshot(capsys):
    """ConsensusHealthMonitor with an alert engine attached routes its
    episode log through the plane's event stream."""
    from consensusml_tpu.obs import ConsensusHealthMonitor
    from consensusml_tpu.topology import RingTopology

    reg = MetricsRegistry()
    hist = MetricsHistory(reg, keep=8)
    eng = AlertEngine(
        hist, rules=default_ruleset(), registry=reg, tracer=SpanTracer()
    )
    mon = ConsensusHealthMonitor(
        RingTopology(4), registry=reg, tracer=SpanTracer(),
        sustain=2, alerts=eng,
    )
    d = 1.0
    for rnd in range(6):
        d *= 3.0  # sustained growth = divergence
        mon.observe(rnd, d)
    err = capsys.readouterr().err
    assert "alert-plane event" in err and "consensus-health" in err
    events = eng.snapshot()["events_recent"]
    assert any(e["source"] == "consensus-health" for e in events)
    # and the lifecycle gauge path: the violation gauge is now 1, so
    # the default consensus-health-violation rule fires on evaluation
    hist.record(now=0.0)
    firing = eng.evaluate(now=0.0)
    assert any(a["rule"] == "consensus-health-violation" for a in firing)


def test_flight_recorder_dump_carries_alert_state_and_history(tmp_path):
    """A crash dump answers "what was already wrong" (alert snapshot)
    and "cliff or slow burn" (the last-N history digest)."""
    from consensusml_tpu.obs import FlightRecorder

    reg = MetricsRegistry()
    g = reg.gauge("t_pressure")
    hist = MetricsHistory(reg, keep=8)
    rule = AlertRule("pressure", "t_pressure", op="above", threshold=5.0)
    eng = _engine(hist, [rule], reg)
    for now, v in ((0.0, 1.0), (10.0, 3.0), (20.0, 9.0)):
        g.set(v)
        hist.record(now=now)
        eng.evaluate(now=now)
    rec = FlightRecorder(
        str(tmp_path), tracer=SpanTracer(), registry=reg,
        history=hist, alerts=eng,
    )
    path = rec.dump("unit-test")
    with open(path) as f:
        doc = json.load(f)
    assert [a["rule"] for a in doc["alerts"]["firing"]] == ["pressure"]
    rows = {r["series"]: r for r in doc["history"]["series"]}
    assert [v for _t, v in rows["t_pressure"]["points"]] == [1.0, 3.0, 9.0]


# ---------------------------------------------------------------------------
# e2e: live ServeServer — injected breach fires, sustains, clears
# ---------------------------------------------------------------------------


def _tiny_engine(slots=4, max_new=8):
    from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM
    from consensusml_tpu.serve import Engine, ServeConfig

    model = GPT2LM(
        config=GPT2Config(
            vocab_size=64, hidden=32, layers=2, heads=2, max_len=32,
            dropout=0.0,
        )
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return Engine(
        model, params,
        ServeConfig(num_slots=slots, max_len=32, max_new_tokens=max_new),
    )


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        ctype = r.headers.get("Content-Type")
        return json.loads(r.read()), ctype


def _poll(fn, timeout_s=10.0, every_s=0.05):
    deadline = time.monotonic() + timeout_s
    while True:
        v = fn()
        if v:
            return v
        if time.monotonic() > deadline:
            return None
        time.sleep(every_s)


@pytest.mark.serving
def test_e2e_ttft_breach_fires_sustains_and_clears(tmp_path):
    """The acceptance anchor: a live ServeServer with the alert plane
    armed; real traffic is healthy, then an injected TTFT breach makes
    a burn-rate alert fire (visible on /alerts, in /healthz's firing
    count, and in the cluster aggregate), sustain under continued
    breach, and clear once the breach leaves both windows."""
    from consensusml_tpu.obs import get_registry
    from consensusml_tpu.serve.server import ServeServer

    engine = _tiny_engine()
    engine.warmup()
    # tight windows so fire AND clear happen in test time; the TTFT
    # threshold sits on a DEFAULT_SLO_BUCKETS edge
    rules = [
        AlertRule(
            "ttft-burn", "consensusml_serve_ttft_seconds",
            kind="burn_rate", severity="page",
            slo=SloSpec(
                "consensusml_serve_ttft_seconds",
                threshold_s=0.5, objective=0.9,
            ),
            fast_window_s=0.8, slow_window_s=2.0, burn_factor=3.0,
        )
    ]
    server = ServeServer(
        engine, metrics_port=0, obs_tick_s=0.1, alert_rules=rules
    )
    try:
        base = f"http://{server.metrics_address[0]}:{server.metrics_address[1]}"
        # consistent Content-Type on every JSON endpoint
        _doc, ctype = _get_json(base + "/alerts")
        assert ctype == "application/json; charset=utf-8"
        _doc, ctype = _get_json(base + "/requests")
        assert ctype == "application/json; charset=utf-8"

        # healthy traffic through the real engine: no alert
        for h in [engine.submit([1 + i] * 4) for i in range(6)]:
            h.result(timeout=300)
        time.sleep(0.3)  # a few ticks over the healthy distribution
        doc, _ = _get_json(base + "/alerts")
        assert doc["enabled"] and doc["firing"] == []
        hz, _ = _get_json(base + "/healthz")
        assert hz["ok"] and hz["firing_alerts"] == 0
        assert hz["last_tick_age_s"] is not None

        # /query surfaces the live TTFT series (the windowed count is
        # a DELTA between ticks — traffic that completed before the
        # first tick is baseline, so only structure is asserted here)
        q, _ = _get_json(
            base + "/query?series=consensusml_serve_ttft_seconds&window=5"
        )
        assert q["kind"] == "histogram"
        assert q["samples_retained"] >= 2 and q["window"] is not None

        # INJECT the breach: the server-side TTFT family takes a burst
        # of 2 s observations (what a wedged prefill would record)
        ttft = get_registry().histogram(
            "consensusml_serve_ttft_seconds", buckets=DEFAULT_SLO_BUCKETS
        )
        def breach():
            for _ in range(40):
                ttft.observe(2.0)
        breach()

        def firing():
            doc, _ = _get_json(base + "/alerts")
            return doc["firing"]
        fired = _poll(firing, timeout_s=10.0)
        assert fired, "injected TTFT breach never fired"
        assert fired[0]["rule"] == "ttft-burn"
        assert fired[0]["severity"] == "page"
        hz, _ = _get_json(base + "/healthz")
        assert hz["firing_alerts"] >= 1

        # SUSTAIN: keep breaching past several ticks — still firing
        breach()
        time.sleep(0.4)
        assert firing(), "alert did not sustain under continued breach"

        # the cluster aggregate shows the same breach fleet-wide (the
        # writer peeks the armed singletons; dedup by rule+series)
        ClusterWriter(str(tmp_path), rank=0).write(round=1)
        agg = aggregate(str(tmp_path))
        assert agg["alerts"] is not None
        assert [a["rule"] for a in agg["alerts"]["firing"]] == ["ttft-burn"]
        assert agg["history"] is not None and agg["history"]["series"]

        # RECOVER: stop injecting; once the breach ages out of both
        # windows the alert clears
        cleared = _poll(lambda: not firing(), timeout_s=15.0)
        assert cleared, "alert never cleared after recovery"
        doc, _ = _get_json(base + "/alerts")
        assert any(
            a["rule"] == "ttft-burn" for a in doc["resolved_recent"]
        )
        hz, _ = _get_json(base + "/healthz")
        assert hz["firing_alerts"] == 0
    finally:
        server.shutdown(drain=False)
