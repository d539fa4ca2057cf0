"""ctypes bindings for the native C++ runtime (native/).

Reference parity: the reference's native (CUDA/C++) runtime layer —
data-loading/prefetch and compression kernels (SURVEY.md L0; BASELINE.json
north_star names the CUDA compression kernels; mount empty so the design
is original). The TPU compute path stays JAX/Pallas; this layer is the
HOST runtime around it: threaded batch prefetch that overlaps with device
compute, and CPU kernels used as an independent parity check on the
jnp/Pallas codecs and for host-side payload work.

The library is built with ``make -C native`` on first use in every
process (g++ is part of the toolchain; make is a no-op when the build
is fresh). If the build fails, ``available()`` returns False and every
native call raises with the compiler's words; callers that were ASKED
for the native layer (``train.py --native-loader``) exit on that —
nothing falls back in silence.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from consensusml_tpu.analysis import guarded_by
from consensusml_tpu.obs import get_registry

# host-runtime telemetry (docs/observability.md): how far ahead the C++
# producer ring runs, and whether consumers exploit buffer reuse
_BATCHES = get_registry().counter(
    "consensusml_native_batches_total",
    "round batches handed out by the native prefetch ring",
)
_REUSE_HITS = get_registry().counter(
    "consensusml_native_reuse_hits_total",
    "staging-buffer reuses: next(out=...) caller-buffer fills plus "
    "zero-copy slot releases (release_slot)",
)
_QUEUE_DEPTH = get_registry().gauge(
    "consensusml_native_queue_depth",
    "slots the producer ring is ahead of the consumer (sampled at next())",
)

__all__ = [
    "available",
    "quantize_int8_chunks",
    "dequantize_int8_chunks",
    "topk",
    "topk_chunks",
    "NativeLoader",
]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libcml_native.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed: str | None = None

_f32p = ctypes.POINTER(ctypes.c_float)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i8p = ctypes.POINTER(ctypes.c_int8)
_u8p = ctypes.POINTER(ctypes.c_uint8)


def _build() -> None:
    subprocess.run(
        ["make", "-C", _NATIVE_DIR],
        check=True,
        capture_output=True,
        text=True,
        timeout=300,
    )


def _load() -> ctypes.CDLL | None:
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed is not None:
            return _lib
        try:
            # always through make: a no-op when the .so is newer than
            # native/src, a rebuild when it is not — never whatever
            # binary happens to lie in the git-ignored build dir
            _build()
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            # keep the compiler's stderr — without it a failed `make` is
            # undebuggable from the raised message alone; AttributeError =
            # a symbol the sources no longer export
            detail = getattr(e, "stderr", None)
            _load_failed = f"{type(e).__name__}: {e}" + (
                f"\n--- build stderr ---\n{detail}" if detail else ""
            )
            return None
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    """Bind argtypes; raises AttributeError if any symbol is missing."""
    lib.cml_quant_int8.argtypes = [_f32p, ctypes.c_int64, ctypes.c_int64, _i8p, _f32p]
    lib.cml_dequant_int8.argtypes = [_i8p, _f32p, ctypes.c_int64, ctypes.c_int64, _f32p]
    lib.cml_topk.argtypes = [_f32p, ctypes.c_int64, ctypes.c_int64, _f32p, _i32p]
    lib.cml_topk_chunks.argtypes = [
        _f32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, _f32p, _i32p,
    ]
    lib.cml_loader_create.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_float, _f32p, _i32p, ctypes.c_uint64,
        ctypes.c_int32, ctypes.c_float, ctypes.c_float,
    ]
    lib.cml_loader_create.restype = ctypes.c_void_p
    lib.cml_loader_create_file.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        _f32p, _i32p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_uint64, ctypes.c_int32, ctypes.c_float, ctypes.c_float,
    ]
    lib.cml_loader_create_file.restype = ctypes.c_void_p
    lib.cml_loader_acquire.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(_f32p), ctypes.POINTER(_i32p),
    ]
    lib.cml_loader_acquire.restype = ctypes.c_int
    lib.cml_loader_acquire_u8.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(_u8p), ctypes.POINTER(_i32p),
    ]
    lib.cml_loader_acquire_u8.restype = ctypes.c_int
    lib.cml_loader_float_bytes.argtypes = [ctypes.c_void_p]
    lib.cml_loader_float_bytes.restype = ctypes.c_int32
    lib.cml_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.cml_loader_produced.argtypes = [ctypes.c_void_p]
    lib.cml_loader_produced.restype = ctypes.c_uint64
    lib.cml_loader_destroy.argtypes = [ctypes.c_void_p]


def available() -> bool:
    """True if the native library is loadable (builds it if needed)."""
    return _load() is not None


def _as_f32(x) -> np.ndarray:
    return np.ascontiguousarray(x, dtype=np.float32)


def quantize_int8_chunks(chunks) -> tuple[np.ndarray, np.ndarray]:
    """Quantize ``(nchunks, chunk)`` f32 rows -> (int8 rows, f32 scales).

    Same semantics as compress.reference.Int8Compressor per-chunk math.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    chunks = _as_f32(chunks)
    nchunks, chunk = chunks.shape
    q = np.empty((nchunks, chunk), np.int8)
    scales = np.empty((nchunks,), np.float32)
    lib.cml_quant_int8(
        chunks.ctypes.data_as(_f32p), nchunks, chunk,
        q.ctypes.data_as(_i8p), scales.ctypes.data_as(_f32p),
    )
    return q, scales


def dequantize_int8_chunks(q, scales) -> np.ndarray:
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    q = np.ascontiguousarray(q, dtype=np.int8)
    scales = _as_f32(scales)
    nchunks, chunk = q.shape
    out = np.empty((nchunks, chunk), np.float32)
    lib.cml_dequant_int8(
        q.ctypes.data_as(_i8p), scales.ctypes.data_as(_f32p), nchunks, chunk,
        out.ctypes.data_as(_f32p),
    )
    return out


def topk(x, k: int) -> tuple[np.ndarray, np.ndarray]:
    """k largest by magnitude: (values, indices), jax.lax.top_k ordering."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    x = _as_f32(x).reshape(-1)
    k = min(k, x.size)
    vals = np.empty((k,), np.float32)
    idx = np.empty((k,), np.int32)
    lib.cml_topk(x.ctypes.data_as(_f32p), x.size, k,
                 vals.ctypes.data_as(_f32p), idx.ctypes.data_as(_i32p))
    return vals, idx


def topk_chunks(chunks, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-row top-k over ``(nchunks, chunk)``: (values, local indices)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    chunks = _as_f32(chunks)
    nchunks, chunk = chunks.shape
    k = min(k, chunk)
    vals = np.empty((nchunks, k), np.float32)
    idx = np.empty((nchunks, k), np.int32)
    lib.cml_topk_chunks(
        chunks.ctypes.data_as(_f32p), nchunks, chunk, k,
        vals.ctypes.data_as(_f32p), idx.ctypes.data_as(_i32p),
    )
    return vals, idx


@guarded_by("_lock", "_h", "_consumed")
class NativeLoader:
    """Threaded prefetching batch pipeline over the native ring buffer.

    One acquired slot = one "round batch" of ``samples_per_slot`` samples;
    the caller reshapes (see data.native_pipeline). Deterministic: slot
    ``i`` of a loader with seed ``s`` has identical bytes regardless of
    ``nthreads``/``depth``/timing.

    Two consume paths: :meth:`next` copies the slot out (simple, always
    safe), :meth:`acquire_view`/:meth:`release_slot` exposes the slot's
    own memory zero-copy — the device-prefetch hot path (the slot IS the
    H2D staging buffer; see data.prefetch).

    Thread safety: the zero-copy path hands ``release_slot`` to the
    device prefetcher's BACKGROUND thread (``FeedItem.on_done``) while
    the consumer thread acquires and teardown closes — so the handle
    ``_h`` and the ``_consumed`` counter only move under ``_lock``
    (cml-check lock-discipline pass). The blocking C++ ``acquire`` runs
    OUTSIDE the lock (holding it there would let a blocked consumer
    starve the producer's ``release``); acquire-vs-destroy stays the
    C++ side's contract, as before — ``close()`` wakes blocked
    consumers with "loader stopped". The lock closes the Python-side
    use-after-free: a deferred ``release_slot`` can no longer observe a
    non-None handle that ``close()`` frees mid-call.
    """

    def __init__(
        self,
        *,
        kind: str,  # "classification" | "lm" | "file_classification" | "file_lm"
        samples_per_slot: int,
        sample_floats: int,
        sample_ints: int,
        nclasses_or_vocab: int = 1,
        noise: float = 0.0,
        prototypes: np.ndarray | None = None,
        successors: np.ndarray | None = None,
        # file-backed kinds: loader gathers from these caller-owned tables
        # (retained on self so the borrowed C++ pointers stay valid)
        world: int = 1,
        images: np.ndarray | None = None,  # (n, sample_floats) f32
        labels: np.ndarray | None = None,  # (n,) i32
        tokens: np.ndarray | None = None,  # (n,) i32
        depth: int = 4,
        nthreads: int = 2,
        seed: int = 0,
        start_seq: int = 0,
        # "f32" (default) or "u8": u8 ships quantized bytes — producer
        # threads run clip((x + qoff) * qscale) and the consumer dequants
        # ON DEVICE (x^ = u8/qscale - qoff) — quartering host->device wire
        wire: str = "f32",
        qscale: float = 32.0,
        qoff: float = 4.0,
    ):
        # first: __del__ -> close() must find the lock even when the
        # rest of __init__ raises
        self._lock = threading.Lock()
        self._consumed = 0
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_load_failed}")
        if wire not in ("f32", "u8"):
            raise ValueError(f"unknown wire {wire!r}")
        self._lib = lib
        self._wire = wire
        self.qscale, self.qoff = float(qscale), float(qoff)
        self._shape_f = (samples_per_slot, sample_floats)
        self._shape_i = (samples_per_slot, sample_ints)
        fb = 1 if wire == "u8" else 4
        kinds = {"classification": 0, "lm": 1, "file_classification": 2, "file_lm": 3}
        if kind not in kinds:
            raise ValueError(f"unknown kind {kind!r}")
        if kind in ("file_classification", "file_lm"):
            data_p = label_p = tok_p = None
            n_items = 0
            token_bytes = 4
            if kind == "file_classification":
                if images is None or labels is None:
                    raise ValueError(f"{kind} requires images= and labels=")
                self._images = _as_f32(images).reshape(len(labels), sample_floats)
                self._labels = np.ascontiguousarray(labels, np.int32)
                data_p = self._images.ctypes.data_as(_f32p)
                label_p = self._labels.ctypes.data_as(_i32p)
                n_items = len(self._labels)
            else:
                if tokens is None:
                    raise ValueError(f"{kind} requires tokens=")
                tok = np.asarray(tokens).reshape(-1)
                if tok.dtype == np.uint16:
                    # pass the raw memmap through — the C++ side widens
                    # per window, so a multi-GB corpus is never copied
                    self._tokens = np.ascontiguousarray(tok)
                    token_bytes = 2
                else:
                    self._tokens = np.ascontiguousarray(tok, np.int32)
                tok_p = self._tokens.ctypes.data_as(ctypes.c_void_p)
                n_items = len(self._tokens)
            self._h = lib.cml_loader_create_file(
                depth, nthreads, seed, kinds[kind],
                samples_per_slot, sample_floats, sample_ints, world,
                data_p, label_p, tok_p, n_items, token_bytes, start_seq,
                fb, self.qscale, self.qoff,
            )
            if not self._h:
                raise RuntimeError(
                    "cml_loader_create_file failed (check world divides "
                    "samples_per_slot, and the table is large enough for "
                    f"{world} workers: n_items={n_items})"
                )
            self._check_wire(self._h, fb)
            return
        proto_p = None
        succ_p = None
        if prototypes is not None:
            self._proto = _as_f32(prototypes).reshape(nclasses_or_vocab, sample_floats)
            proto_p = self._proto.ctypes.data_as(_f32p)
        if successors is not None:
            self._succ = np.ascontiguousarray(successors, np.int32).reshape(
                nclasses_or_vocab, 4
            )
            succ_p = self._succ.ctypes.data_as(_i32p)
        if kind == "lm" and succ_p is None:
            raise ValueError("lm kind requires a successors table")
        self._h = lib.cml_loader_create(
            depth, nthreads, seed, kinds[kind],
            samples_per_slot, sample_floats, sample_ints,
            nclasses_or_vocab, noise, proto_p, succ_p, start_seq,
            fb, self.qscale, self.qoff,
        )
        if not self._h:
            raise RuntimeError("cml_loader_create failed (bad arguments)")
        self._check_wire(self._h, fb)

    def _check_wire(self, h, fb: int) -> None:
        """Attach-time invariant: the library's wire mode for this handle
        matches what this wrapper will read (guards a stale .so whose
        create ignored the float_bytes argument)."""
        got = int(self._lib.cml_loader_float_bytes(h))
        if got != fb:
            raise RuntimeError(
                f"native loader wire mismatch: library reports "
                f"float_bytes={got}, wrapper expected {fb} — rebuild "
                "native/ (make -C native)"
            )

    def _handle(self):
        """The live C++ handle, read under the lock; raises after
        close() (or on a loader whose __init__ never finished). Blocking
        C calls take the returned value so they run lock-free (see the
        class docstring)."""
        with self._lock:
            h = getattr(self, "_h", None)
        if not h:
            raise RuntimeError("loader closed")
        return h

    def _count_consumed(self) -> int:
        with self._lock:
            self._consumed += 1
            return self._consumed

    def next(self, out=None) -> tuple[np.ndarray, np.ndarray]:
        """Blocking: the next slot's (floats-or-u8, ints) arrays.

        ``out``: optional (data, ints) numpy pair to copy INTO (rotating
        reusable buffers let the backend's transfer path reuse staging
        state instead of seeing a fresh allocation every round). It must
        match this loader's slot layout exactly — a silent fallback to a
        fresh copy here would hide the exact bug reusable buffers exist
        to avoid (the transfer path re-staging every round)."""
        wire_dtype = np.uint8 if self._wire == "u8" else np.float32
        if out is not None:
            if not isinstance(out, (tuple, list)) or len(out) != 2:
                raise ValueError(
                    "next(out=...) takes a (data, ints) pair of ndarrays, "
                    f"got {type(out).__name__} of length "
                    f"{len(out) if isinstance(out, (tuple, list)) else 'n/a'}"
                )
            for name, arr, shape, dtype in (
                ("data", out[0], self._shape_f, wire_dtype),
                ("ints", out[1], self._shape_i, np.int32),
            ):
                if not isinstance(arr, np.ndarray):
                    raise ValueError(
                        f"next(out=...) {name} buffer must be a numpy "
                        f"ndarray, got {type(arr).__name__}"
                    )
                if tuple(arr.shape) != shape or arr.dtype != np.dtype(dtype):
                    raise ValueError(
                        f"next(out=...) {name} buffer mismatch: expected "
                        f"shape {shape} dtype {np.dtype(dtype).name}, got "
                        f"shape {tuple(arr.shape)} dtype {arr.dtype.name}"
                    )
        h = self._handle()
        data_p = _u8p() if self._wire == "u8" else _f32p()
        iptr = _i32p()
        acquire = (
            self._lib.cml_loader_acquire_u8
            if self._wire == "u8"
            else self._lib.cml_loader_acquire
        )
        idx = acquire(h, ctypes.byref(data_p), ctypes.byref(iptr))
        if idx < 0:
            raise RuntimeError("loader stopped")
        dtype = wire_dtype

        def _copy(ptr, shape, dt, dst):
            if 0 in shape:  # empty buffer: C++ data() may be NULL
                return np.empty(shape, dt)
            src = np.ctypeslib.as_array(ptr, shape=shape)
            if dst is not None:
                np.copyto(dst, src)
                return dst
            return src.copy()

        try:
            data = _copy(data_p, self._shape_f, dtype, out and out[0])
            ints = _copy(iptr, self._shape_i, np.int32, out and out[1])
        finally:
            self._lib.cml_loader_release(h, idx)
        consumed = self._count_consumed()
        _BATCHES.inc()
        if out is not None:
            _REUSE_HITS.inc()
        # produced() counts finished slots; the difference to what this
        # consumer has taken is the ring's current run-ahead
        _QUEUE_DEPTH.set(max(0, self.produced() - consumed))
        return data, ints

    def acquire_view(self) -> tuple[int, np.ndarray, np.ndarray]:
        """Zero-copy consume: ``(slot_idx, data_view, ints_view)``.

        The arrays are VIEWS of the ring slot's own memory — the hot
        path the device prefetcher uses: the slot doubles as the H2D
        staging buffer, ``jax.device_put`` reads straight out of it, and
        the per-batch allocation+copy that :meth:`next` pays disappears.

        Contract: the views are valid only until :meth:`release_slot`
        is called with the returned index, and the caller MUST release
        every acquired slot or the ring deadlocks once all ``depth``
        slots are held (the producer threads have nowhere to write).
        ``DevicePrefetcher`` releases automatically once the transfer
        out of the slot has completed; consume through it (see
        data.native_pipeline.native_cls_feed) unless you manage slot
        lifetimes yourself.
        """
        wire_dtype = np.uint8 if self._wire == "u8" else np.float32
        h = self._handle()
        data_p = _u8p() if self._wire == "u8" else _f32p()
        iptr = _i32p()
        acquire = (
            self._lib.cml_loader_acquire_u8
            if self._wire == "u8"
            else self._lib.cml_loader_acquire
        )
        idx = acquire(h, ctypes.byref(data_p), ctypes.byref(iptr))
        if idx < 0:
            raise RuntimeError("loader stopped")

        def _view(ptr, shape, dt):
            if 0 in shape:  # empty buffer: C++ data() may be NULL
                return np.empty(shape, dt)
            arr = np.ctypeslib.as_array(ptr, shape=shape)
            arr.flags.writeable = False  # views are read-only by contract
            return arr

        data = _view(data_p, self._shape_f, wire_dtype)
        ints = _view(iptr, self._shape_i, np.int32)
        consumed = self._count_consumed()
        _BATCHES.inc()
        _QUEUE_DEPTH.set(max(0, self.produced() - consumed))
        return idx, data, ints

    def release_slot(self, idx: int) -> None:
        """Hand slot ``idx`` (from :meth:`acquire_view`) back to the
        producer ring. Safe after :meth:`close` (no-op) so deferred
        release hooks can fire during teardown — the release runs under
        the handle lock, so it can never race ``close()`` freeing the
        ring out from under it (the prefetcher's background thread fires
        these)."""
        with self._lock:
            if self._h:
                self._lib.cml_loader_release(self._h, idx)
            else:
                return
        _REUSE_HITS.inc()  # the slot itself is the reused staging buffer

    def produced(self) -> int:
        return int(self._lib.cml_loader_produced(self._handle()))

    def close(self) -> None:
        with self._lock:
            h = self._h if hasattr(self, "_h") else None
            self._h = None
        if h:
            # destroy outside the lock: it joins producer threads and
            # wakes blocked consumers, either of which may grab the lock
            self._lib.cml_loader_destroy(h)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
