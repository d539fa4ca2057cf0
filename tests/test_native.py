"""Native C++ runtime tests: kernel parity with the jnp reference codecs,
pipeline determinism, and end-to-end training via the native loader."""

import numpy as np
import pytest

from consensusml_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library not buildable here"
)


# ---------------------------------------------------------------------------
# kernel parity vs the jnp reference semantics
# ---------------------------------------------------------------------------


def test_quant_int8_matches_reference():
    import jax.numpy as jnp

    from consensusml_tpu.compress.reference import Int8Compressor

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048,)).astype(np.float32) * 3.0
    chunk = 256
    comp = Int8Compressor(chunk=chunk)
    ref = comp.compress(jnp.asarray(x))
    q, scales = native.quantize_int8_chunks(x.reshape(-1, chunk))
    np.testing.assert_array_equal(q.reshape(-1), np.asarray(ref.data))
    np.testing.assert_allclose(scales, np.asarray(ref.scales), rtol=0, atol=0)


def test_quant_int8_zero_chunk_roundtrip():
    x = np.zeros((2, 128), np.float32)
    x[1] = np.linspace(-1, 1, 128)
    q, scales = native.quantize_int8_chunks(x)
    assert scales[0] == 0.0
    out = native.dequantize_int8_chunks(q, scales)
    np.testing.assert_array_equal(out[0], 0.0)
    np.testing.assert_allclose(out[1], x[1], atol=1.0 / 127.0)


def test_topk_matches_lax_topk():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    x = rng.normal(size=(513,)).astype(np.float32)
    k = 37
    vals, idx = native.topk(x, k)
    _, ref_idx = jax.lax.top_k(jnp.abs(jnp.asarray(x)), k)
    np.testing.assert_array_equal(idx, np.asarray(ref_idx))
    np.testing.assert_array_equal(vals, x[idx])


def test_topk_tie_breaking_prefers_lower_index():
    x = np.array([1.0, -1.0, 0.5, 1.0], np.float32)
    _, idx = native.topk(x, 3)
    np.testing.assert_array_equal(idx, [0, 1, 3])


def test_topk_chunks_local_indices():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(4, 256)).astype(np.float32)
    vals, idx = native.topk_chunks(x, 16)
    assert vals.shape == (4, 16) and idx.shape == (4, 16)
    for c in range(4):
        v, i = native.topk(x[c], 16)
        np.testing.assert_array_equal(idx[c], i)
        np.testing.assert_array_equal(vals[c], v)


# ---------------------------------------------------------------------------
# prefetch pipeline
# ---------------------------------------------------------------------------


def _mk_loader(seed=0, depth=3, nthreads=2):
    proto = np.arange(10 * 16, dtype=np.float32).reshape(10, 16) / 100.0
    return native.NativeLoader(
        kind="classification",
        samples_per_slot=8,
        sample_floats=16,
        sample_ints=1,
        nclasses_or_vocab=10,
        noise=0.1,
        prototypes=proto,
        depth=depth,
        nthreads=nthreads,
        seed=seed,
    )


def test_loader_deterministic_across_thread_counts():
    slots_a, slots_b = [], []
    with _mk_loader(seed=7, depth=2, nthreads=1) as a:
        for _ in range(5):
            slots_a.append(a.next())
    with _mk_loader(seed=7, depth=5, nthreads=4) as b:
        for _ in range(5):
            slots_b.append(b.next())
    for (fa, ia), (fb, ib) in zip(slots_a, slots_b):
        np.testing.assert_array_equal(fa, fb)
        np.testing.assert_array_equal(ia, ib)


def test_loader_next_out_validated():
    """next(out=...) rejects mismatched reuse buffers LOUDLY — a silent
    fresh-copy fallback would defeat the staging reuse out= exists for."""
    with _mk_loader() as ld:
        good = (np.empty((8, 16), np.float32), np.empty((8, 1), np.int32))
        data, ints = ld.next(out=good)
        assert data is good[0] and ints is good[1]
        with pytest.raises(ValueError, match=r"\(data, ints\) pair"):
            ld.next(out=np.empty((8, 16), np.float32))
        with pytest.raises(ValueError, match="ndarray"):
            ld.next(out=([[0.0] * 16] * 8, good[1]))
        with pytest.raises(ValueError, match="data buffer mismatch"):
            ld.next(out=(np.empty((8, 15), np.float32), good[1]))
        with pytest.raises(ValueError, match="data buffer mismatch"):
            ld.next(out=(np.empty((8, 16), np.float64), good[1]))
        with pytest.raises(ValueError, match="ints buffer mismatch"):
            ld.next(out=(good[0], np.empty((8, 1), np.int64)))
        # u8-wire loader expects uint8 data buffers
        proto = np.arange(10 * 16, dtype=np.float32).reshape(10, 16) / 100.0
        with native.NativeLoader(
            kind="classification", samples_per_slot=8, sample_floats=16,
            sample_ints=1, nclasses_or_vocab=10, prototypes=proto, wire="u8",
        ) as u8:
            with pytest.raises(ValueError, match="data buffer mismatch"):
                u8.next(out=(np.empty((8, 16), np.float32), good[1]))
            data, _ = u8.next(out=(np.empty((8, 16), np.uint8), good[1]))
            assert data.dtype == np.uint8


def test_loader_u8_wire_requires_classification_kind():
    """cml_loader_create mirrors the create_file guard: the u8 wire
    quantizes the float payload, which only kind 0 has."""
    succ = np.zeros((10, 4), np.int32)
    with pytest.raises(RuntimeError, match="cml_loader_create failed"):
        native.NativeLoader(
            kind="lm", samples_per_slot=4, sample_floats=0, sample_ints=16,
            nclasses_or_vocab=10, successors=succ, wire="u8",
        )


def test_loader_seeds_differ():
    with _mk_loader(seed=1) as a, _mk_loader(seed=2) as b:
        fa, _ = a.next()
        fb, _ = b.next()
    assert not np.array_equal(fa, fb)


def test_loader_samples_cluster_around_prototypes():
    with _mk_loader(seed=3) as loader:
        floats, ints = loader.next()
    proto = np.arange(10 * 16, dtype=np.float32).reshape(10, 16) / 100.0
    for s in range(8):
        lab = ints[s, 0]
        assert 0 <= lab < 10
        # noise is N(0, 0.1): distance to own prototype is small
        assert np.abs(floats[s] - proto[lab]).max() < 0.6


def test_loader_prefetches_ahead():
    import time

    with _mk_loader(depth=4, nthreads=2) as loader:
        time.sleep(0.2)
        # producers should have filled the ring without any consumer pull
        assert loader.produced() >= 4


def test_native_round_batches_shapes_and_determinism():
    from consensusml_tpu.data import SyntheticClassification, native_round_batches

    ds = SyntheticClassification(n=64, image_shape=(8, 8, 1), classes=10)
    a = list(native_round_batches(ds, world_size=2, h=2, batch=4, rounds=3, seed=5))
    b = list(
        native_round_batches(
            ds, world_size=2, h=2, batch=4, rounds=3, seed=5, depth=7, nthreads=3
        )
    )
    assert a[0]["image"].shape == (2, 2, 4, 8, 8, 1)
    assert a[0]["label"].shape == (2, 2, 4)
    for ba, bb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(ba["image"]), np.asarray(bb["image"]))
        np.testing.assert_array_equal(np.asarray(ba["label"]), np.asarray(bb["label"]))


def test_native_lm_batches_in_vocab_and_mlm():
    from consensusml_tpu.data import SyntheticLM, native_lm_round_batches

    ds = SyntheticLM(vocab_size=32, seq_len=16)
    (plain,) = list(native_lm_round_batches(ds, 2, 1, 4, rounds=1, seed=0))
    ids = np.asarray(plain["input_ids"])
    assert ids.shape == (2, 1, 4, 16)
    # chain never emits the reserved mask token
    assert ids.max() < ds.mask_token and ids.min() >= 0
    (mlm,) = list(
        native_lm_round_batches(ds, 2, 1, 4, rounds=1, seed=0, mlm_rate=0.3)
    )
    mask = np.asarray(mlm["mlm_mask"]).astype(bool)
    np.testing.assert_array_equal(
        np.asarray(mlm["input_ids"])[mask], ds.mask_token
    )
    np.testing.assert_array_equal(
        np.asarray(mlm["input_ids"])[~mask], np.asarray(mlm["labels"])[~mask]
    )


def test_training_step_on_native_pipeline():
    """End-to-end: one local-SGD round fed by the C++ pipeline, loss drops."""
    import jax
    import jax.numpy as jnp
    import optax

    from consensusml_tpu.consensus import GossipConfig
    from consensusml_tpu.data import SyntheticClassification, native_round_batches
    from consensusml_tpu.models import MLP, mlp_loss_fn
    from consensusml_tpu.topology import topology_from_name
    from consensusml_tpu.train import (
        LocalSGDConfig,
        init_stacked_state,
        make_simulated_train_step,
    )

    world = 4
    ds = SyntheticClassification(n=256, image_shape=(8, 8, 1))
    model = MLP(hidden=32)
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topology_from_name("dense", world)),
        optimizer=optax.adam(1e-2),
        h=2,
    )
    step = make_simulated_train_step(cfg, mlp_loss_fn(model))
    state = init_stacked_state(
        cfg, lambda r: model.init(r, jnp.zeros((1, 8, 8, 1)))["params"],
        jax.random.key(0), world,
    )
    losses = []
    for batch in native_round_batches(ds, world, h=2, batch=8, rounds=20, seed=0):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses


def _tiny_file_cls(n=64, hw=6):
    rng = np.random.default_rng(5)
    from consensusml_tpu.data.files import FileClassification

    images = rng.normal(size=(n, hw, hw, 1)).astype(np.float32)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    return FileClassification(
        images=images, labels=labels,
        holdout_images=images[:4], holdout_labels=labels[:4],
    )


def test_native_file_round_batches_gathers_from_shards():
    from consensusml_tpu.data import native_file_round_batches

    data = _tiny_file_cls()
    world, h, batch = 4, 2, 3
    got = list(native_file_round_batches(data, world, h, batch, rounds=2, seed=1))
    assert got[0]["image"].shape == (world, h, batch, 6, 6, 1)
    # every emitted sample must be an exact row of the worker's OWN shard
    for w in range(world):
        xs, ys = data.worker_shard(w, world)
        imgs = np.asarray(got[0]["image"][w]).reshape(-1, 36)
        labs = np.asarray(got[0]["label"][w]).reshape(-1)
        table = xs.reshape(len(xs), 36)
        for img, lab in zip(imgs, labs):
            hits = np.where((table == img).all(axis=1))[0]
            assert hits.size >= 1
            assert ys[hits[0]] == lab


def test_native_file_round_batches_deterministic():
    from consensusml_tpu.data import native_file_round_batches

    data = _tiny_file_cls()
    a = list(native_file_round_batches(data, 2, 1, 4, rounds=3, seed=7, nthreads=1))
    b = list(native_file_round_batches(data, 2, 1, 4, rounds=3, seed=7, nthreads=4))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x["image"]), np.asarray(y["image"]))
        np.testing.assert_array_equal(np.asarray(x["label"]), np.asarray(y["label"]))


def test_native_file_token_batches_windows():
    from consensusml_tpu.data.files import TokenFileDataset
    from consensusml_tpu.data import native_file_token_batches

    toks = (np.arange(2048, dtype=np.int32) * 3) % 251
    data = TokenFileDataset(tokens=toks, seq_len=8, vocab_size=256,
                            val_tokens=toks[:64])
    world = 4
    got = list(native_file_token_batches(data, world, 1, 4, rounds=2, seed=3))
    ids = np.asarray(got[0]["input_ids"])
    assert ids.shape == (world, 1, 4, 8)
    # every window is a contiguous run from the worker's own region
    for w in range(world):
        lo, hi = data.worker_region(w, world)
        for row in ids[w].reshape(-1, 8):
            starts = np.where(toks[lo:hi] == row[0])[0]
            assert any(
                np.array_equal(row, toks[lo + s : lo + s + 8]) for s in starts
            ), (w, row)


def test_native_file_token_batches_mlm_and_determinism():
    from consensusml_tpu.data.files import TokenFileDataset
    from consensusml_tpu.data import native_file_token_batches

    toks = np.full(1024, 3, np.int32)
    data = TokenFileDataset(tokens=toks, seq_len=8, vocab_size=16,
                            val_tokens=toks[:16])
    a = list(native_file_token_batches(data, 2, 1, 2, rounds=2, seed=9,
                                       mlm_rate=0.5, nthreads=1))
    b = list(native_file_token_batches(data, 2, 1, 2, rounds=2, seed=9,
                                       mlm_rate=0.5, nthreads=3))
    for x, y in zip(a, b):
        for key in ("input_ids", "labels", "mlm_mask"):
            np.testing.assert_array_equal(np.asarray(x[key]), np.asarray(y[key]))
    masked = np.asarray(a[0]["mlm_mask"]) > 0
    assert (np.asarray(a[0]["input_ids"])[masked] == data.mask_token).all()


def test_native_loader_rejects_too_small_token_table():
    from consensusml_tpu.native import NativeLoader

    with pytest.raises(RuntimeError, match="create_file failed"):
        NativeLoader(
            kind="file_lm", samples_per_slot=4, sample_floats=0,
            sample_ints=16, world=4, tokens=np.zeros(64, np.int32),
        )


def test_native_file_token_batches_uint16_memmap(tmp_path):
    """uint16 token files flow through uncopied; ids match the int32 path."""
    from consensusml_tpu.data.files import TokenFileDataset
    from consensusml_tpu.data import native_file_token_batches

    raw = ((np.arange(1024) * 5) % 60000).astype(np.uint16)
    p = tmp_path / "t.bin"
    raw.tofile(p)
    mm = np.memmap(p, dtype=np.uint16, mode="r")
    d16 = TokenFileDataset(tokens=mm, seq_len=8, vocab_size=1 << 16,
                           val_tokens=mm[:16])
    d32 = TokenFileDataset(tokens=raw.astype(np.int32), seq_len=8,
                           vocab_size=1 << 16, val_tokens=raw[:16].astype(np.int32))
    a = list(native_file_token_batches(d16, 2, 1, 3, rounds=2, seed=11))
    b = list(native_file_token_batches(d32, 2, 1, 3, rounds=2, seed=11))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(
            np.asarray(x["input_ids"]), np.asarray(y["input_ids"])
        )
    assert np.asarray(a[0]["input_ids"]).dtype == np.int32


def test_native_start_seq_resumes_stream_exactly():
    """start=N reproduces the same batches a fresh run yields at round N
    — in O(1), not by discarding N slots."""
    from consensusml_tpu.data import native_round_batches
    from consensusml_tpu.data.synthetic import SyntheticClassification

    data = SyntheticClassification(n=64, image_shape=(4, 4, 1))
    full = list(native_round_batches(data, 2, 1, 4, rounds=5, seed=3))
    tail = list(native_round_batches(data, 2, 1, 4, rounds=2, seed=3, start=3))
    for a, b in zip(full[3:], tail):
        np.testing.assert_array_equal(np.asarray(a["image"]), np.asarray(b["image"]))
        np.testing.assert_array_equal(np.asarray(a["label"]), np.asarray(b["label"]))


def test_loader_u8_wire_quantizes_f32_stream():
    """u8 wire = clip((x + qoff) * qscale) of the SAME deterministic f32
    stream (labels identical, values within half a quant step), shipped
    as uint8 — the 1/4-wire mode."""
    proto = np.arange(10 * 16, dtype=np.float32).reshape(10, 16) / 100.0
    kw = dict(
        kind="classification", samples_per_slot=8, sample_floats=16,
        sample_ints=1, nclasses_or_vocab=10, noise=0.1, prototypes=proto,
        seed=11,
    )
    with native.NativeLoader(**kw) as a, native.NativeLoader(
        **kw, wire="u8", qscale=32.0, qoff=4.0
    ) as b:
        f, fi = a.next()
        u, ui = b.next()
    assert u.dtype == np.uint8
    np.testing.assert_array_equal(fi, ui)
    want = np.clip((f + 4.0) * 32.0, 0, 255)
    np.testing.assert_allclose(u.astype(np.float32), want, atol=0.5)
    # device-side dequant recovers the f32 values to half a quant step
    np.testing.assert_allclose(
        u.astype(np.float32) / 32.0 - 4.0, f, atol=0.5 / 32.0 + 1e-6
    )


def test_loader_u8_wire_file_kind():
    from consensusml_tpu.data.native_pipeline import native_file_round_batches

    class _DS:
        n = 8
        image_shape = (4, 4, 1)
        images = (np.arange(8 * 16, dtype=np.float32).reshape(8, 16) % 7) / 7.0
        labels = np.arange(8, dtype=np.int32)

    f32 = list(native_file_round_batches(_DS(), 2, 1, 2, rounds=3, seed=5))
    u8 = list(
        native_file_round_batches(
            _DS(), 2, 1, 2, rounds=3, seed=5, wire="u8", qscale=255.0, qoff=0.0
        )
    )
    for a, b in zip(f32, u8):
        assert np.asarray(b["image"]).dtype == np.uint8
        np.testing.assert_array_equal(
            np.asarray(a["label"]), np.asarray(b["label"])
        )
        # the table values are k/7 with k<7, so /255 quantization is
        # lossless to half a step
        np.testing.assert_allclose(
            np.asarray(b["image"]).astype(np.float32) / 255.0,
            np.asarray(a["image"]),
            atol=0.5 / 255.0 + 1e-6,
        )


def test_loader_next_out_reuse_matches_fresh_copies():
    """next(out=...) fills caller buffers with the identical stream (the
    rotating-buffer fast path the pipeline iterators use)."""
    with _mk_loader(seed=9) as a, _mk_loader(seed=9) as b:
        outs = (np.empty((8, 16), np.float32), np.empty((8, 1), np.int32))
        for _ in range(4):
            ff, fi = a.next()
            rf, ri = b.next(out=outs)
            assert rf is outs[0] and ri is outs[1]
            np.testing.assert_array_equal(ff, rf)
            np.testing.assert_array_equal(fi, ri)
