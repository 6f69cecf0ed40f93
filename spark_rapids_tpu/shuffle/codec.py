"""Shuffle/spill buffer compression codecs.

Reference: `TableCompressionCodec.scala:41-98` (codec SPI),
`NvcompLZ4CompressionCodec.scala` (nvcomp device LZ4), `CopyCompressionCodec.scala`.
On TPU there is no device-side codec library; compression runs on the host between
D2H and the block store / wire (the multithreaded shuffle pipelines it across
writer threads, so it overlaps with device compute like nvcomp overlaps with
kernels). `lz4xla` is served by the native C++ runtime when built (native/), and
reports unavailable otherwise."""

from __future__ import annotations

import threading
from typing import Dict, Optional

# ---------------------------------------------------------------------------
# CRC32C (Castagnoli) — shuffle frame integrity (the reference transports
# get this from UCX/netty; the host wire here checks its own frames).
# google-crc32c (C) when present; table-driven software fallback otherwise.
# ---------------------------------------------------------------------------

_CRC32C_TABLE: Optional[list] = None


def _crc32c_soft(data: bytes, crc: int = 0) -> int:
    global _CRC32C_TABLE
    if _CRC32C_TABLE is None:
        poly = 0x82F63B78  # reversed Castagnoli
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ poly if c & 1 else c >> 1
            table.append(c)
        _CRC32C_TABLE = table
    crc ^= 0xFFFFFFFF
    for b in data:
        crc = _CRC32C_TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


try:
    import google_crc32c as _gcrc

    def crc32c(data: bytes) -> int:
        """CRC32C of data as an unsigned 32-bit int."""
        return int(_gcrc.value(bytes(data)))
except ImportError:  # pragma: no cover - environment-dependent
    crc32c = _crc32c_soft


def checksum_supported() -> bool:
    """True when a C-speed CRC32C is available. The pure-Python fallback
    runs at a few MiB/s — far too slow for the default-on shuffle checksum
    hot path — so callers gate the checksum DEFAULT on this (frames then
    carry checksum=0 = unchecked, which every reader accepts; integrity
    checking degrades gracefully instead of throttling the shuffle)."""
    return crc32c is not _crc32c_soft


class Codec:
    name = "none"

    def compress(self, data: bytes) -> bytes:
        raise NotImplementedError

    def decompress(self, data: bytes, uncompressed_len: int) -> bytes:
        raise NotImplementedError


class CopyCodec(Codec):
    name = "none"

    def compress(self, data: bytes) -> bytes:
        return data

    def decompress(self, data: bytes, uncompressed_len: int) -> bytes:
        return data


class ZstdCodec(Codec):
    name = "zstd"

    def __init__(self, level: int = 1):
        import zstandard  # noqa: F401 — absent wheel fails HERE, not per call
        self._level = level
        # zstandard contexts are not thread-safe and the shuffle writer's
        # pool shares one codec: a context per thread ("Src size is
        # incorrect" under concurrent compress otherwise)
        self._tls = threading.local()

    def _contexts(self):
        ctx = getattr(self._tls, "ctx", None)
        if ctx is None:
            import zstandard
            ctx = self._tls.ctx = (
                zstandard.ZstdCompressor(level=self._level),
                zstandard.ZstdDecompressor())
        return ctx

    def compress(self, data: bytes) -> bytes:
        return self._contexts()[0].compress(data)

    def decompress(self, data: bytes, uncompressed_len: int) -> bytes:
        return self._contexts()[1].decompress(
            data, max_output_size=uncompressed_len)


class ZlibCodec(Codec):
    """Stdlib fallback when the zstandard wheel is absent (missing deps are
    gated, not fatal). Frames stamp the ACTUAL codec name — never the
    requested one — so a cross-host peer that does have zstd still reads a
    zlib frame correctly instead of feeding zlib bytes to zstd."""

    name = "zlib"

    def __init__(self, level: int = 1):
        import zlib
        self._zlib = zlib
        self._level = level

    def compress(self, data: bytes) -> bytes:
        return self._zlib.compress(data, self._level)

    def decompress(self, data: bytes, uncompressed_len: int) -> bytes:
        d = self._zlib.decompressobj()
        out = d.decompress(data, uncompressed_len)
        if d.unconsumed_tail:
            raise ValueError("zlib payload exceeds declared length")
        return out


class NativeLz4Codec(Codec):
    """LZ4 block codec from the native runtime (native/libsrtpu.so)."""

    name = "lz4xla"

    def __init__(self):
        from ..native import runtime
        if not runtime.available():
            raise RuntimeError(
                "lz4xla codec needs the native runtime; build native/ first "
                "or use spark.rapids.shuffle.compression.codec=zstd")
        self._rt = runtime

    def compress(self, data: bytes) -> bytes:
        return self._rt.lz4_compress(data)

    def decompress(self, data: bytes, uncompressed_len: int) -> bytes:
        return self._rt.lz4_decompress(data, uncompressed_len)


_CACHE: Dict[str, Codec] = {}


def get_codec(name: str) -> Codec:
    if name not in _CACHE:
        if name == "none":
            _CACHE[name] = CopyCodec()
        elif name == "zstd":
            try:
                _CACHE[name] = ZstdCodec()
            except ImportError:  # no zstandard wheel: honest stdlib fallback
                _CACHE[name] = ZlibCodec()
        elif name == "zlib":
            _CACHE[name] = ZlibCodec()
        elif name == "lz4xla":
            _CACHE[name] = NativeLz4Codec()
        else:
            raise ValueError(f"unknown shuffle codec {name!r}")
    return _CACHE[name]
