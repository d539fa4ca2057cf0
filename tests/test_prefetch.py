"""Overlapped host→device feed tests (ISSUE 3): prefetch determinism,
no-host-sync-between-rounds, zero-copy slot staging, ring planning, and
the feed-stall telemetry contract (docs/observability.md)."""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from consensusml_tpu import native
from consensusml_tpu.data.prefetch import (
    DevicePrefetcher,
    FeedItem,
    prefetch_to_device,
)
from consensusml_tpu.data.native_pipeline import plan_ring

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library not buildable here"
)


# ---------------------------------------------------------------------------
# DevicePrefetcher core (no native dependency)
# ---------------------------------------------------------------------------


def test_close_from_another_thread_unblocks_waiting_consumer():
    """A consumer blocked in __next__'s queue pop must wake to
    StopIteration when another thread (teardown, GC __del__) closes the
    prefetcher — even though close() drains the queue and the stopped
    producer never re-posts the end-of-stream sentinel."""

    def slow_source():
        yield {"x": np.zeros((2,), np.float32)}
        # block until closed: the consumer will be waiting on an empty
        # queue when close() arrives
        stop_evt.wait(timeout=20)

    stop_evt = threading.Event()
    pf = DevicePrefetcher(slow_source(), depth=1)
    assert next(pf) is not None  # drain the one staged batch

    result = {}

    def consume():
        try:
            next(pf)
            result["outcome"] = "item"
        except StopIteration:
            result["outcome"] = "stop"

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    import time as _time

    _time.sleep(0.2)  # let the consumer park in queue.get()
    pf.close()
    stop_evt.set()
    t.join(timeout=10)
    assert not t.is_alive(), "consumer never woke after close()"
    assert result["outcome"] == "stop"


def test_prefetcher_preserves_order_and_counts():
    src = [{"x": np.full((4,), i, np.float32)} for i in range(7)]
    pf = DevicePrefetcher(iter(src), depth=2)
    got = list(pf)
    assert len(got) == 7
    for i, b in enumerate(got):
        np.testing.assert_array_equal(np.asarray(b["x"]), src[i]["x"])
    assert pf.batches_out == 7
    assert pf.stall_seconds_total >= 0.0


def test_prefetcher_yields_device_arrays():
    import jax

    pf = DevicePrefetcher(iter([{"x": np.ones((2, 2), np.float32)}]), depth=1)
    (b,) = list(pf)
    assert isinstance(b["x"], jax.Array)


def test_prefetcher_depth_zero_is_passthrough():
    src = iter([1, 2, 3])
    assert prefetch_to_device(src, 0) is src


def test_prefetcher_on_done_fires_after_all_batches():
    done = []
    src = (
        FeedItem({"x": np.full((2,), i, np.float32)}, lambda i=i: done.append(i))
        for i in range(5)
    )
    got = list(DevicePrefetcher(src, depth=2))
    assert len(got) == 5
    # every completion hook fired (transfer done => host memory reusable)
    assert sorted(done) == [0, 1, 2, 3, 4]
    # releases are in acquisition order: the in-flight window is FIFO
    assert done == sorted(done)


def test_prefetcher_source_error_surfaces_to_consumer():
    def src():
        yield {"x": np.zeros((1,), np.float32)}
        raise RuntimeError("producer blew up")

    pf = DevicePrefetcher(src(), depth=2)
    it = iter(pf)
    next(it)
    with pytest.raises(RuntimeError, match="producer blew up"):
        next(it)


def test_prefetcher_feed_items_require_placement():
    src = (FeedItem({"x": np.zeros((1,), np.float32)}, lambda: None) for _ in range(2))
    pf = DevicePrefetcher(src, depth=1, place=False)
    with pytest.raises(RuntimeError, match="require.*place"):
        list(pf)


def test_prefetcher_close_is_idempotent_and_early():
    src = ({"x": np.full((2,), i, np.float32)} for i in range(100))
    pf = DevicePrefetcher(src, depth=2)
    next(iter(pf))
    pf.close()
    pf.close()
    assert not pf._thread.is_alive()
    # next() after close() raises instead of blocking on a dead queue
    with pytest.raises(StopIteration):
        next(iter(pf))


def test_prefetcher_stall_metrics_registered():
    from consensusml_tpu.obs import get_registry

    reg = get_registry()
    before = reg.counter("consensusml_feed_batches_total").value
    list(DevicePrefetcher(iter([{"x": np.zeros((1,), np.float32)}] * 3), depth=2))
    assert reg.counter("consensusml_feed_batches_total").value == before + 3
    # the gauge exists and carries the last round's wait
    assert reg.gauge("consensusml_feed_stall_seconds").value >= 0.0


def test_prefetcher_emits_feed_spans_on_both_threads(global_ring):
    """``feed.wait`` per batch on the consumer; ``feed.pull`` /
    ``feed.stage`` / ``feed.drain`` on the producer thread."""
    import threading

    src = [{"x": np.full((4,), i, np.float32)} for i in range(5)]
    assert len(list(DevicePrefetcher(iter(src), depth=2))) == 5
    spans = [e for e in global_ring.events() if e["name"].startswith("feed.")]
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    me = threading.get_ident()
    # one wait per batch and one for the end-of-stream sentinel
    assert len(by["feed.wait"]) == 6 and {e["tid"] for e in by["feed.wait"]} == {me}
    producer = {e["tid"] for n in ("feed.pull", "feed.stage", "feed.drain") for e in by[n]}
    assert len(producer) == 1 and me not in producer
    # one pull per batch and the one that found the source exhausted
    assert len(by["feed.pull"]) == 6
    assert len(by["feed.stage"]) == len(by["feed.drain"]) == 5


def test_plan_ring_shapes_depth_and_threads():
    # depth always leaves slack beyond the prefetch window (no deadlock:
    # prefetch in-flight slots + 2 free for the producers)
    for prefetch in (1, 2, 4):
        depth, _ = plan_ring(8, 4, prefetch=prefetch)
        assert depth == prefetch + 2
    # nthreads scales with slot bytes within [2, cpus-2]
    _, small = plan_ring(8, 16 * 16 * 3, cpu_count=16)
    assert small == 2
    _, big = plan_ring(128, 224 * 224 * 3 * 4, cpu_count=16)
    assert big == 10  # ~77 MB slot => one thread per 8 MB
    _, capped = plan_ring(512, 224 * 224 * 3 * 4, cpu_count=8)
    assert capped == 6  # cpus-2 cap


# ---------------------------------------------------------------------------
# native zero-copy staging + end-to-end feed
# ---------------------------------------------------------------------------


def _mk_loader(**kw):
    proto = np.arange(10 * 16, dtype=np.float32).reshape(10, 16) / 100.0
    args = dict(
        kind="classification", samples_per_slot=8, sample_floats=16,
        sample_ints=1, nclasses_or_vocab=10, noise=0.1, prototypes=proto,
        depth=3, nthreads=2, seed=0,
    )
    args.update(kw)
    return native.NativeLoader(**args)


@needs_native
def test_acquire_view_matches_next_stream():
    """Zero-copy views carry the identical deterministic byte stream the
    copying consume path yields, and released slots recycle."""
    with _mk_loader(seed=21) as a, _mk_loader(seed=21) as b:
        for _ in range(7):  # > depth: slots must recycle through release
            idx, data, ints = a.acquire_view()
            assert not data.flags.writeable and not ints.flags.writeable
            ref_d, ref_i = b.next()
            np.testing.assert_array_equal(data, ref_d)
            np.testing.assert_array_equal(ints, ref_i)
            a.release_slot(idx)


@needs_native
def test_release_slot_after_close_is_noop():
    ld = _mk_loader()
    idx, _, _ = ld.acquire_view()
    ld.close()
    ld.release_slot(idx)  # must not crash


@needs_native
def test_native_cls_feed_deterministic_across_knobs():
    """Same seed ⇒ byte-identical batch sequence regardless of prefetch
    depth, ring threads, or overlap on/off (the ISSUE 3 determinism
    contract)."""
    from consensusml_tpu.data import SyntheticClassification, native_cls_feed

    ds = SyntheticClassification(n=64, image_shape=(6, 6, 1), classes=10)

    def collect(**kw):
        out = []
        for b in native_cls_feed(ds, 2, 2, 4, 5, seed=13, wire="u8", **kw):
            out.append(
                {k: np.array(v, copy=True) for k, v in b.items()}
            )
        return out

    base = collect(prefetch=0)  # overlap off
    assert base[0]["image"].shape == (2, 2, 4, 6, 6, 1)
    assert base[0]["image"].dtype == np.uint8
    for kw in (
        dict(prefetch=2),
        dict(prefetch=4, depth=8, nthreads=5),
        dict(prefetch=1, depth=3, nthreads=1),
    ):
        got = collect(**kw)
        assert len(got) == len(base)
        for x, y in zip(base, got):
            np.testing.assert_array_equal(x["image"], y["image"])
            np.testing.assert_array_equal(x["label"], y["label"])


@needs_native
def test_native_cls_feed_finalizes_loader_threads():
    """Exhausting (or closing) the feed tears the C++ producer ring
    down: the release closures are the last loader references, so after
    the prefetcher drains, refcounting destroys it — no thread leak, and
    crucially no destroy-before-drain (slots stay alive until every
    in-flight transfer completed)."""
    import gc
    import time

    from consensusml_tpu.data import SyntheticClassification, native_cls_feed

    ds = SyntheticClassification(n=32, image_shape=(4, 4, 1))
    gc.collect()
    before = threading.active_count()
    list(native_cls_feed(ds, 2, 1, 2, 4, seed=1, prefetch=2, nthreads=3))
    # consumed to exhaustion => prefetcher closed itself; loader refs
    # all dropped => producer threads joined by the destructor
    gc.collect()
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before

    # early abandonment via close(): same teardown
    pf = native_cls_feed(ds, 2, 1, 2, 50, seed=1, prefetch=2, nthreads=3)
    next(iter(pf))
    pf.close()
    gc.collect()
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before


@needs_native
def test_native_cls_feed_f32_wire_matches_plain_iterator():
    from consensusml_tpu.data import (
        SyntheticClassification,
        native_cls_feed,
        native_round_batches,
    )

    ds = SyntheticClassification(n=32, image_shape=(4, 4, 1))
    plain = list(native_round_batches(ds, 2, 1, 3, rounds=4, seed=5))
    feed = list(native_cls_feed(ds, 2, 1, 3, 4, seed=5, wire="f32"))
    for x, y in zip(plain, feed):
        np.testing.assert_array_equal(np.asarray(x["image"]), np.asarray(y["image"]))
        np.testing.assert_array_equal(np.asarray(x["label"]), np.asarray(y["label"]))


@needs_native
def test_overlapped_feed_issues_no_host_sync_between_rounds():
    """The consumer's critical path is a queue pop: no block_until_ready
    (or any host sync) from the consuming thread between rounds — waits
    happen on the prefetcher's background thread only."""
    import jax

    from consensusml_tpu.data import SyntheticClassification, native_cls_feed

    ds = SyntheticClassification(n=64, image_shape=(6, 6, 1), classes=10)
    calls = []
    real = jax.block_until_ready

    def spy(x):
        calls.append(threading.get_ident())
        return real(x)

    consumer = threading.get_ident()
    jax.block_until_ready = spy
    try:
        got = list(native_cls_feed(ds, 2, 1, 4, 6, seed=3, prefetch=2))
    finally:
        jax.block_until_ready = real
    assert len(got) == 6
    # the background thread syncs (slot-release bookkeeping); the
    # consumer thread must never
    assert consumer not in calls
    assert calls, "expected the producer thread to fence slot transfers"


@needs_native
def test_train_cli_auto_u8_wire_and_prefetch(tmp_path):
    """--native-loader defaults to the u8 wire on image configs and runs
    through the overlapped feed; --native-wire f32 still overrides."""
    env = {**os.environ, "JAX_PLATFORMS": ""}
    r = subprocess.run(
        [sys.executable, "train.py", "--config", "mnist_mlp", "--device",
         "cpu", "--backend", "simulated", "--rounds", "3",
         "--native-loader"],
        cwd=REPO, capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, r.stderr[-1500:]
    assert "native wire: u8 (auto" in r.stdout
    assert "rounds prefetched" in r.stdout
    r = subprocess.run(
        [sys.executable, "train.py", "--config", "mnist_mlp", "--device",
         "cpu", "--backend", "simulated", "--rounds", "2",
         "--native-loader", "--native-wire", "f32", "--prefetch-depth", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, r.stderr[-1500:]
    assert "native wire: f32 (explicit)" in r.stdout
    assert "rounds prefetched" not in r.stdout  # overlap off
