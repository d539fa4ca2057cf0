"""Cluster observability plane: labeled metrics, per-link probes,
consensus-health monitor, cross-rank aggregation, and the train.py
surface (docs/observability.md "Cluster view").

Acceptance anchors (ISSUE 6): a deliberately slowed link must rank
slowest in the report, a deliberately diverged replica must trip the
health anomaly, and a multi-rank directory must merge into one
deterministic cluster report — all asserted here, tier-1 fast.
"""

import importlib.util
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import pytest

from consensusml_tpu.comm import simulated
from consensusml_tpu.consensus import ConsensusEngine, GossipConfig
from consensusml_tpu.obs import (
    ClusterWriter,
    ConsensusHealthMonitor,
    LinkProber,
    MetricsRegistry,
    SpanTracer,
    aggregate,
    decay_bound,
    link_wire_bytes,
    parse_metric_key,
)
from consensusml_tpu.obs.links import edge_sends_per_round
from consensusml_tpu.topology import (
    OnePeerExponentialTopology,
    RingTopology,
    TorusTopology,
)

pytestmark = pytest.mark.telemetry


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name,
        os.path.join(os.path.dirname(__file__), "..", "tools", f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# labeled metrics
# ---------------------------------------------------------------------------


def test_labeled_metrics_exposition_and_snapshot_keys():
    r = MetricsRegistry()
    r.counter("t_edge_total", "bytes", labels={"src": 0, "dst": 1}).inc(5)
    r.counter("t_edge_total", labels={"src": 1, "dst": 0}).inc(7)
    h = r.histogram(
        "t_edge_seconds", buckets=(0.1, 1.0), labels={"src": 0, "dst": 1}
    )
    h.observe(0.5)
    text = r.to_prometheus()
    assert 't_edge_total{dst="1",src="0"} 5' in text
    assert 't_edge_total{dst="0",src="1"} 7' in text
    # one TYPE header per family, not per child
    assert text.count("# TYPE t_edge_total counter") == 1
    assert 't_edge_seconds_bucket{dst="1",le="0.1",src="0"} 0' in text
    snap = r.snapshot()["metrics"]
    assert snap['t_edge_total{dst="1",src="0"}'] == 5.0
    name, labels = parse_metric_key('t_edge_total{dst="1",src="0"}')
    assert name == "t_edge_total" and labels == {"dst": "1", "src": "0"}
    assert parse_metric_key("t_plain") == ("t_plain", {})
    # family kind is enforced across label children
    with pytest.raises(ValueError):
        r.gauge("t_edge_total", labels={"src": 9, "dst": 9})


# ---------------------------------------------------------------------------
# topology edge sets
# ---------------------------------------------------------------------------


def test_topology_edges_match_mixing_matrix():
    for topo in (RingTopology(5), TorusTopology(2, 3), RingTopology(2)):
        w = topo.mixing_matrix()
        edges = {(s, d): wt for s, d, wt in topo.edges()}
        for dst in range(topo.world_size):
            for src in range(topo.world_size):
                if src == dst:
                    continue
                if w[dst, src] > 0:
                    assert edges[(src, dst)] == pytest.approx(w[dst, src])
                else:
                    assert (src, dst) not in edges


def test_time_varying_edges_average_over_period():
    topo = OnePeerExponentialTopology(4)  # phases: offset 1, offset 2
    edges = {(s, d): wt for s, d, wt in topo.edges()}
    # each phase's single edge carries weight 0.5, active 1-in-2 rounds
    assert edges[(0, 1)] == pytest.approx(0.25)
    assert edges[(0, 2)] == pytest.approx(0.25)
    # a ring-of-2's +1/-1 shifts are SEPARATE sends on one edge
    assert edge_sends_per_round(RingTopology(2)) == {(0, 1): 2.0, (1, 0): 2.0}


# ---------------------------------------------------------------------------
# per-link probes
# ---------------------------------------------------------------------------


def test_slowed_link_is_ranked_slowest():
    topo = RingTopology(4)
    reg = MetricsRegistry()

    def transfer(src, dst):
        if (src, dst) == (2, 3):
            time.sleep(0.002)

    prober = LinkProber(topo, registry=reg, transfer=transfer)
    assert len(prober.edges) == 8 and prober.skipped_edges == 0
    for _ in range(3):
        prober.probe_round()
    top = prober.slowest(1)[0]
    assert (top["src"], top["dst"]) == (2, 3)
    assert top["probes"] == 3
    text = reg.to_prometheus()
    assert 'consensusml_link_latency_seconds_bucket{dst="3"' in text
    assert "consensusml_link_probe_rounds_total 3" in text
    assert 'consensusml_link_bandwidth_bytes_per_sec{dst="0",src="1"}' in text


def test_link_prober_max_edges_counted_not_silent():
    reg = MetricsRegistry()
    prober = LinkProber(RingTopology(6), registry=reg, max_edges=4,
                        transfer=lambda s, d: None)
    assert len(prober.edges) == 4 and prober.skipped_edges == 8
    assert reg.gauge("consensusml_link_edges_skipped").value == 8


def test_link_prober_default_transfer_times_device_copies():
    # real device_put probes over the virtual CPU mesh: values are
    # host-memcpy latencies, but every edge must land a measurement
    topo = RingTopology(4)
    reg = MetricsRegistry()
    prober = LinkProber(
        topo, registry=reg, devices=jax.devices()[:4], payload_bytes=1 << 12
    )
    lat = prober.probe_round()
    assert set(lat) == set(prober.edges)
    assert all(v > 0 for v in lat.values())


def test_link_wire_bytes_matches_engine_accounting():
    shapes = jax.eval_shape(
        lambda: {"w": jnp.zeros((256, 64), jnp.float32)}
    )
    for world in (2, 4):
        eng = ConsensusEngine(GossipConfig(topology=RingTopology(world)))
        per_edge = link_wire_bytes(eng, shapes)
        for rank in range(world):
            outgoing = sum(
                b for (s, _), b in per_edge.items() if s == rank
            )
            assert outgoing == pytest.approx(
                eng.wire_bytes_per_round(shapes)
            )


# ---------------------------------------------------------------------------
# consensus-health monitor
# ---------------------------------------------------------------------------


def test_health_strict_pure_gossip_stays_within_bound():
    topo = RingTopology(8)
    w = simulated.mixing_matrix(topo)
    x = jax.random.normal(jax.random.key(0), (8, 128))
    reg = MetricsRegistry()
    mon = ConsensusHealthMonitor(
        topo, registry=reg, tracer=SpanTracer(), strict=True
    )
    assert mon.bound == pytest.approx(1.0 - topo.spectral_gap())
    for rnd in range(12):
        d = float(simulated.consensus_error_stacked({"x": x}, 8))
        assert mon.observe(rnd, d) is None
        x = simulated.mix_stacked(x, w)
    # the spectral bound is worst-case: measured decay must respect it
    assert mon.measured_decay <= mon.bound + mon.tolerance
    assert reg.gauge("consensusml_health_bound_violation").value == 0.0
    assert reg.counter("consensusml_health_anomalies_total").value == 0


def test_deliberately_diverged_replica_trips_anomaly(capsys):
    topo = RingTopology(8)
    eng = ConsensusEngine(GossipConfig(topology=topo))
    w = simulated.mixing_matrix(topo)
    params = {"x": jax.random.normal(jax.random.key(1), (8, 64))}
    reg = MetricsRegistry()
    mon = ConsensusHealthMonitor(topo, registry=reg, tracer=SpanTracer())
    first = None
    for rnd in range(10):
        params, _ = eng.round_simulated(params, None, w)
        # replica 0 diverges harder every round (a poisoned update)
        params["x"] = params["x"].at[0].add(2.0 ** rnd)
        d = float(simulated.consensus_error_stacked(params, 8))
        rec = mon.observe(rnd, d)
        if rec and first is None:
            first = rec
    assert first is not None and first["kind"] == "divergence"
    assert first["streak"] == mon.sustain
    assert reg.gauge("consensusml_health_bound_violation").value == 1.0
    assert reg.counter("consensusml_health_anomalies_total").value == 1
    assert "consensus-health ANOMALY" in capsys.readouterr().err


def test_health_nonfinite_distance_is_divergence():
    mon = ConsensusHealthMonitor(
        RingTopology(4), registry=MetricsRegistry(), tracer=SpanTracer(),
        sustain=2,
    )
    assert mon.observe(0, 0.5) is None
    assert mon.observe(1, float("nan")) is None  # streak 1
    rec = mon.observe(2, float("nan"))  # streak 2 = sustain
    assert rec is not None and rec["kind"] == "divergence"


def test_decay_bound_time_varying_is_per_round_rate():
    topo = OnePeerExponentialTopology(8)
    per_period = 1.0 - topo.spectral_gap()
    assert decay_bound(topo) == pytest.approx(
        per_period ** (1.0 / topo.period)
    )


# ---------------------------------------------------------------------------
# cross-rank aggregation -> one cluster report
# ---------------------------------------------------------------------------


def _write_rank(tmp_path, rank, *, rounds, lat_s, heartbeat_ago=0.0,
                slow_edge=None, now=None):
    now = time.time() if now is None else now
    reg = MetricsRegistry()
    reg.counter("consensusml_rounds_total").inc(rounds)
    h = reg.histogram("consensusml_round_latency_seconds")
    for _ in range(rounds):
        h.observe(lat_s)
    reg.gauge("consensusml_consensus_distance").set(0.25)
    reg.gauge("consensusml_health_decay_measured").set(0.76)
    reg.gauge("consensusml_health_decay_bound").set(0.80)
    reg.gauge("consensusml_health_bound_violation").set(0.0)

    def transfer(src, dst):
        if slow_edge and (src, dst) == slow_edge:
            time.sleep(0.002)

    prober = LinkProber(RingTopology(4), registry=reg, transfer=transfer)
    prober.probe_round()
    writer = ClusterWriter(
        str(tmp_path), rank=rank, registry=reg, world_size=2
    )
    writer.write(round=rounds)
    if heartbeat_ago:
        doc = json.load(open(writer.path))
        doc["heartbeat_s"] = now - heartbeat_ago
        json.dump(doc, open(writer.path, "w"))
    return writer


def test_two_rank_directory_merges_into_one_report(tmp_path):
    now = time.time()
    _write_rank(tmp_path, 0, rounds=10, lat_s=0.1, slow_edge=(1, 2), now=now)
    _write_rank(
        tmp_path, 1, rounds=6, lat_s=0.3, heartbeat_ago=500.0, now=now
    )
    doc = aggregate(str(tmp_path), now=now)
    # per-rank skew
    assert doc["skew"]["ranks"] == 2
    assert doc["skew"]["round_lag"] == 4
    assert doc["skew"]["round_latency_skew"] == pytest.approx(3.0, rel=1e-6)
    # merged link histograms: both ranks probed each edge once, so every
    # edge shows 2 probes and the deliberately slowed one ranks first
    top = doc["links"][0]
    assert (top["src"], top["dst"]) == (1, 2)
    assert top["probes"] == 2
    # straggler: stale heartbeat AND 4 rounds behind
    (s,) = doc["stragglers"]
    assert s["rank"] == 1 and len(s["reasons"]) == 2
    # measured-vs-bound health made it through
    assert doc["health"]["decay_bound"] == 0.80
    assert doc["health"]["decay_measured_worst"] == 0.76
    assert doc["health"]["ranks_in_violation"] == 0
    # determinism: aggregating the same dir at the same instant is stable
    assert aggregate(str(tmp_path), now=now) == doc


def test_obs_report_tool_renders_text_and_json(tmp_path, capsys):
    now = time.time()
    _write_rank(tmp_path, 0, rounds=5, lat_s=0.1, slow_edge=(3, 0), now=now)
    mod = _tool("obs_report")
    rc = mod.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "links (slowest first" in out
    rows = [
        l for l in out.splitlines() if "->" in l and "src->dst" not in l
    ]
    assert rows[0].strip().startswith("3->0")  # slow edge ranks first
    rc = mod.main([str(tmp_path), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["links"][0]["src"] == 3
    # missing dir: clear error, rc 1
    assert mod.main([str(tmp_path / "nope")]) == 1
    assert "does not exist" in capsys.readouterr().err


def test_obs_report_tool_empty_dir_errors(tmp_path, capsys):
    mod = _tool("obs_report")
    assert mod.main([str(tmp_path)]) == 1
    assert "no obs-" in capsys.readouterr().err


def test_partial_snapshots_render_with_absent_blocks(tmp_path, capsys):
    """The degraded-cluster fixture (ISSUE 15): rank files missing every
    optional section — no metrics, no serving traces, no links, no
    swarm events, no alert plane, even a null metrics map — must render
    a full report with those blocks marked absent, never crash."""
    # bare-minimum identity-only snapshot (a writer that died right
    # after its first write)
    (tmp_path / "obs-rank-00000.json").write_text(
        json.dumps({"rank": 0, "role": "rank", "heartbeat_s": time.time()})
    )
    # a snapshot with round progress but a NULL metrics map and no
    # heartbeat at all
    (tmp_path / "obs-rank-00001.json").write_text(
        json.dumps({"rank": 1, "role": "rank", "round": 3, "metrics": None})
    )
    doc = aggregate(str(tmp_path))
    assert doc["skew"]["ranks"] == 2
    assert doc["alerts"] is None and doc["history"] is None
    mod = _tool("obs_report")
    rc = mod.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    for block in (
        "alerts: absent",
        "links: absent",
        "request traces: absent",
        "round timeline: absent",
        "membership: absent",
        "history: absent",
    ):
        assert block in out, f"missing absent marker: {block!r}\n{out}"
    # and a MIXED directory — one partial file next to one full rank —
    # still renders the full rank's sections
    _write_rank(tmp_path, 2, rounds=5, lat_s=0.1, slow_edge=(1, 0))
    rc = mod.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "links (slowest first" in out
    assert "alerts: absent" in out  # still no alert plane anywhere


def test_flight_recorder_dumps_are_indexed(tmp_path):
    from consensusml_tpu.obs import FlightRecorder

    _write_rank(tmp_path, 0, rounds=3, lat_s=0.1)
    rec = FlightRecorder(
        str(tmp_path), tracer=SpanTracer(), registry=MetricsRegistry()
    )
    rec.dump("unit-test")
    doc = aggregate(str(tmp_path))
    (fr,) = doc["flight_recorders"]
    assert fr["file"].startswith("flightrec-") and fr["bytes"] > 0


# ---------------------------------------------------------------------------
# loadgen client-side SLO snapshots merge into the same report
# ---------------------------------------------------------------------------


def test_loadgen_metrics_merge_with_rank_snapshots(tmp_path):
    lg = _tool("loadgen")
    from consensusml_tpu.obs import MetricsHistory, get_registry

    def submit(ids, max_new, ctx, sampling=None):
        time.sleep(0.02)  # give the history sampler ticks to land on
        return {"ttft_s": 0.01, "latency_s": 0.05, "tokens": [1] * max_new}

    reg = get_registry()
    history = MetricsHistory(reg, keep=64)
    report = lg.run_loadgen(
        submit, n_requests=4, rate_rps=200.0, prompt_lens=(4, 8),
        vocab=64, max_new_tokens=2,
        history=history, history_tick_s=0.01,
    )
    assert report["completed"] == 4
    assert reg.histogram("consensusml_loadgen_ttft_seconds").count >= 4
    # the sampler thread recorded the client rings DURING the run
    assert "consensusml_loadgen_ttft_seconds" in history.keys()
    assert len(history.last("consensusml_loadgen_ttft_seconds", 1000)) >= 2
    ClusterWriter(
        str(tmp_path), rank=0, role="loadgen", registry=reg,
        history=history,
    ).write(extra={"report": report})
    _write_rank(tmp_path, 0, rounds=3, lat_s=0.1)
    doc = aggregate(str(tmp_path))
    (client,) = doc["clients"]
    assert client["role"] == "loadgen"
    ttft = client["metrics"]["consensusml_loadgen_ttft_seconds"]
    assert ttft["count"] >= 4 and math.isfinite(ttft["p99"])
    # the rank rows are unaffected by the client snapshot
    assert len(doc["ranks"]) == 1
    # and the client-side history digest rides the merge: the TTFT
    # sparkline row the report joins against the server side
    assert doc["history"] is not None
    series = {r["series"] for r in doc["history"]["series"]}
    assert "consensusml_loadgen_ttft_seconds" in series


# ---------------------------------------------------------------------------
# round timeline + slowest-request table: two ranks + a loadgen client
# ---------------------------------------------------------------------------


def _write_rank_with_digest(
    tmp_path, rank, *, rounds, lat_s, feed_s, now
):
    """A rank snapshot whose span digest carries per-round phase rows
    (train.round + feed.wait/round.fence) and a compile-phase ratio
    (gossip.round vs train.inner_loop at 3:1)."""
    reg = MetricsRegistry()
    reg.counter("consensusml_rounds_total").inc(rounds)
    tracer = SpanTracer()
    tracer.complete("gossip.round", 0.03)
    tracer.complete("train.inner_loop", 0.01)
    for r in range(rounds):
        tracer.complete("feed.wait", feed_s, round=r)
        tracer.complete("round.fence", lat_s / 2, round=r)
        tracer.complete("train.round", lat_s, round=r)
    ClusterWriter(
        str(tmp_path), rank=rank, registry=reg, world_size=2, tracer=tracer
    ).write(round=rounds - 1)


def test_round_timeline_and_request_table_merge_deterministically(tmp_path):
    """The ISSUE-10 cluster fixture: two ranks with span digests (rank 1
    is the straggler, its extra time dominated by feed stall) plus a
    loadgen client snapshot carrying exemplar-bearing SLOs and the
    request-trace dump — one deterministic merged report with the
    cross-rank round timeline and the slowest-request table."""
    from consensusml_tpu.obs import RequestTraceRegistry, TraceContext
    from consensusml_tpu.obs.metrics import DEFAULT_SLO_BUCKETS

    now = time.time()
    _write_rank_with_digest(
        tmp_path, 0, rounds=3, lat_s=0.10, feed_s=0.001, now=now
    )
    _write_rank_with_digest(
        tmp_path, 1, rounds=3, lat_s=0.30, feed_s=0.180, now=now
    )

    # loadgen client: two traced requests, the slow one exemplared
    reg = MetricsRegistry()
    rt = RequestTraceRegistry()
    for rid, ttft in (("lgf-00000", 0.004), ("lgf-00001", 0.212)):
        ctx = TraceContext(rid)
        rt.start(ctx, 4)
        rt.event(ctx.request_id, "admission", slot=0, bucket=8)
        rt.event(ctx.request_id, "prefill", bucket=8)
        rt.decode_tick(ctx.request_id)
        rt.finish(ctx.request_id, "max_tokens", tokens=3)
        reg.histogram(
            "consensusml_loadgen_ttft_seconds", buckets=DEFAULT_SLO_BUCKETS
        ).observe(ttft, exemplar=ctx.request_id)
    ClusterWriter(
        str(tmp_path), rank=0, role="loadgen", registry=reg
    ).write(extra={"request_traces": rt.snapshot()})

    doc = aggregate(str(tmp_path), now=now)

    # ---- round timeline: 3 rounds, rank 1 the feed-bound straggler ------
    timeline = doc["round_timeline"]
    assert [row["round"] for row in timeline] == [0, 1, 2]
    for row in timeline:
        assert [r["rank"] for r in row["ranks"]] == [0, 1]
        st = row["straggler"]
        assert st["rank"] == 1
        assert st["extra_ms"] == pytest.approx(200.0, abs=1.0)
        assert st["phase"] == "feed"
        assert st["feed_ms"] == pytest.approx(179.0, abs=1.0)
        # the non-feed remainder splits 3:1 gossip:compute (the digest's
        # compile-round ratio), marked as an estimate
        assert st["gossip_ms_est"] == pytest.approx(
            0.75 * (st["extra_ms"] - st["feed_ms"]), rel=1e-6
        )

    # ---- slowest-request table: exemplar resolves to the trace ----------
    req = doc["requests"]
    assert req["traces_indexed"] == 2 and req["in_flight"] == 0
    (top, second) = req["slowest"]
    assert top["metric"] == "consensusml_loadgen_ttft_seconds"
    assert top["side"] == "client"
    assert top["request_id"] == "lgf-00001/0"
    assert top["resolved"] and top["trace_id"] == "lgf-00001"
    assert top["trace"]["decode_ticks"] == 1
    assert "prefill" in top["trace"]["events"]
    assert second["request_id"] == "lgf-00000/0"

    # ---- deterministic merge + rendered report --------------------------
    assert aggregate(str(tmp_path), now=now) == doc
    mod = _tool("obs_report")
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert mod.main([str(tmp_path)]) == 0
    out = buf.getvalue()
    assert "slowest requests (SLO exemplars -> traces):" in out
    assert "lgf-00001/0" in out
    assert "round timeline (cross-rank, straggler time by phase):" in out
    assert "-> feed" in out


# ---------------------------------------------------------------------------
# the 3-round simulated-comm smoke: train.py with the cluster plane on
# ---------------------------------------------------------------------------


def test_train_smoke_link_probes_and_cluster_report(tmp_path):
    import train as train_cli
    from consensusml_tpu.obs import get_tracer

    obs_dir = tmp_path / "obs"
    prom = tmp_path / "m.prom"
    tracer = get_tracer()
    was_enabled = tracer.enabled
    try:
        rc = train_cli.main(
            [
                "--config", "mnist_mlp",
                "--device", "cpu",
                "--backend", "simulated",
                "--rounds", "3",
                "--telemetry-every", "2",
                "--link-probes",
                "--obs-cluster-dir", str(obs_dir),
                "--metrics-prom", str(prom),
            ]
        )
    finally:
        tracer.enabled = was_enabled
        tracer.clear()  # the GLOBAL ring: later trace tests count spans
    assert rc == 0

    # prometheus carries the link + health families
    text = open(prom).read()
    assert "# TYPE consensusml_link_latency_seconds histogram" in text
    assert "consensusml_link_wire_bytes_per_round{" in text
    assert "# TYPE consensusml_health_decay_bound gauge" in text
    assert "consensusml_round_progress 2" in text

    # the rank snapshot aggregates into a cluster report
    doc = aggregate(str(obs_dir))
    assert [r["rank"] for r in doc["ranks"]] == [0]
    row = doc["ranks"][0]
    assert row["round"] == 2
    # >=: the process-wide registry accumulates across in-process runs
    assert row["round_latency"]["count"] >= 3
    assert row["health"]["decay_bound"] is not None
    probed = [l for l in doc["links"] if l["probes"] > 0]
    assert probed, "link probes produced no per-edge histograms"
    assert all(l["wire_bytes_per_round"] for l in probed)
    assert doc["stragglers"] == []


# ---------------------------------------------------------------------------
# fleet section (ISSUE 20): router snapshots merge + render
# ---------------------------------------------------------------------------


def test_fleet_snapshots_merge_and_render(tmp_path, capsys):
    """Two routers writing ``fleet`` snapshot extras (fleetctl
    --obs-snapshot) merge into one cluster-report section: stream
    counters SUM across routers, the replica table and canary state
    merge by name / last-writer, and obs_report renders the fleet rows
    (docs/fleet.md "Observability")."""
    def fleet_doc(accepted, replicas, canary=None, events=()):
        return {
            "router": {
                "policy": "score",
                "accepted": accepted,
                "completed": accepted - 1,
                "rejected": 1,
                "client_gone": 0,
                "lost_streams": 0,
                "redispatches": 2,
                "affinity_hits": 3,
            },
            "replicas": replicas,
            "canary": canary,
            "events": list(events),
        }

    rep0 = {"r0": {"ready": True, "queue_depth": 1, "generation": 2,
                   "hbm_free_bytes": 1 << 20, "firing": []}}
    rep1 = {"r1": {"ready": False, "queue_depth": None, "generation": None,
                   "hbm_free_bytes": None, "firing": ["serve-queue-full"]}}
    ClusterWriter(str(tmp_path), rank=0, role="router").write(
        extra={"fleet": fleet_doc(
            10, rep0,
            events=[{"time_s": 2.0, "kind": "canary-promote",
                     "replicas": ["r1"]}],
        )}
    )
    ClusterWriter(str(tmp_path), rank=1, role="router").write(
        extra={"fleet": fleet_doc(
            4, rep1,
            canary={"state": "promoted", "replica": "r0",
                    "target_generation": 2},
            events=[{"time_s": 1.0, "kind": "canary-start",
                     "replica": "r0"}],
        )}
    )
    # a third, fleet-less rank must not disturb the section
    ClusterWriter(str(tmp_path), rank=2, role="train").write(round=1)

    doc = aggregate(str(tmp_path))
    fl = doc["fleet"]
    assert fl["routers_reporting"] == 2
    assert fl["router"]["accepted"] == 14  # summed across routers
    assert fl["router"]["completed"] == 12
    assert fl["router"]["rejected"] == 2
    assert fl["router"]["policy"] == "score"  # non-numeric: first wins
    assert set(fl["replicas"]) == {"r0", "r1"}
    assert fl["canary"]["state"] == "promoted"
    assert [e["kind"] for e in fl["events"]] == [
        "canary-start", "canary-promote",  # time-sorted across ranks
    ]

    mod = _tool("obs_report")
    assert mod.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "fleet (2 router(s), policy=score)" in out
    assert "accepted=14" in out and "lost=0" in out
    assert "canary: state=promoted replica=r0 target_gen=2" in out
    assert "event: canary-start" in out

    # a directory with no fleet snapshots carries no fleet section
    bare = tmp_path / "bare"
    bare.mkdir()
    ClusterWriter(str(bare), rank=0).write(round=1)
    assert aggregate(str(bare)).get("fleet") is None
    assert mod.main([str(bare)]) == 0
    assert "fleet (" not in capsys.readouterr().out
