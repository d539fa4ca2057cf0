"""Gradient compression for gossip exchange.

Reference parity: ConsensusML's CUDA gradient-compression kernels —
top-k sparsification and 8-bit quantization (BASELINE.json north_star +
configs[4]; SURVEY.md L0 — file:line unavailable, mount empty). Here the
compressed representations are fixed-shape pytrees, so they travel through
``jax.lax.ppermute`` unchanged: workers exchange the SMALL payload over ICI
and decompress after receipt, which is where the bandwidth saving lives.

:mod:`consensusml_tpu.compress.reference` holds the pure-jnp definition of
the math — it runs everywhere and is the parity oracle for the Pallas TPU
kernels (per-chunk int8 quantize/dequantize, chunked top-k) that implement
the hot path.

Exact reference quantization semantics (rounding mode, chunking) are
unknowable without the mount; we implement symmetric per-chunk affine int8
(round-to-nearest-even, range [-127, 127]) and magnitude top-k with a
static per-tensor k — flagged in SURVEY.md §7 as a risk to re-check.
"""

from consensusml_tpu.compress.base import (  # noqa: F401
    ComposedCompressor,
    Compressor,
    Fp8Payload,
    IdentityCompressor,
    Int4Payload,
    Int8Payload,
    LocalTopKPayload,
    TopKPayload,
)
from consensusml_tpu.compress.kernels import (  # noqa: F401
    ChunkedTopKCompressor,
    FusedBucketCodec,
    PallasFp8Compressor,
    PallasInt4Compressor,
    PallasInt8Compressor,
    chunk_scatter,
    describe_codec,
    fused_bucket_codec,
    resolve_codec_impl,
)
from consensusml_tpu.compress.extra import (  # noqa: F401
    LowRankPayload,
    PowerSGDCompressor,
    QSGD4Compressor,
    QSGDCompressor,
    RandomKCompressor,
    SignCompressor,
    SignPayload,
)
from consensusml_tpu.compress.reference import (  # noqa: F401
    Fp8Compressor,
    Int4Compressor,
    Int8Compressor,
    TopKCompressor,
    topk_int4_compressor,
    topk_int8_compressor,
)
