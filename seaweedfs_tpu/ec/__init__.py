"""Erasure coding: Reed-Solomon over GF(2^8) — RS(10,4) by default, the
geometry a volume was sealed at otherwise (`constants.Geometry`: RS(k, m), or
a local reconstruction code, LRC(12,2,2)) — TPU-native.

The reference erasure-codes sealed volumes with klauspost/reedsolomon
(`weed/storage/erasure_coding/ec_encoder.go`). Here the same code — identical
generator matrix, identical shard bytes — is computed as GF(2) bit-matrix
matmuls on TPU (`codec_tpu`), with a C++ CPU kernel (`codec_cpu`) as the
host-side oracle/fallback.
"""

from .constants import (
    DATA_SHARDS,
    PARITY_SHARDS,
    TOTAL_SHARDS,
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
)
