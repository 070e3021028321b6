"""Consensus-payload compression (top-k + int8 with error feedback)."""

from repro.compression.compressors import (
    Compressor,
    NoneCompressor,
    QInt8Compressor,
    QInt8Payload,
    RawPayload,
    TopKCompressor,
    TopKPayload,
    compressor_names,
    ef_compress_leaf,
    from_config,
    get_compressor,
    register_compressor,
)

__all__ = [
    "Compressor",
    "NoneCompressor",
    "QInt8Compressor",
    "QInt8Payload",
    "RawPayload",
    "TopKCompressor",
    "TopKPayload",
    "compressor_names",
    "ef_compress_leaf",
    "from_config",
    "get_compressor",
    "register_compressor",
]
