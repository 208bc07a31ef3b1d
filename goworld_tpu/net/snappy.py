"""Snappy codec — from-scratch C++ block format + the public framing
format, via ctypes.

Closes the reference's client-edge compression parity gap: the
reference compresses gate<->client streams with snappy
(``ClientProxy.go:38-53`` via netconnutil's ``NewSnappyStream``); until
round 5 this environment had no snappy implementation (python-snappy is
not installed) and zlib-1 filled the role as a documented deviation.
The C++ core (``native/snappy_core.cpp``) implements the public BLOCK
format from google/snappy's format_description.txt; this module adds
the STREAM framing from framing_format.txt:

  stream identifier chunk: 0xff + 3B LE length(6) + "sNaPpY"
  compressed data chunk:   0x00 + 3B LE length + 4B masked CRC32C (of
                           the UNCOMPRESSED data) + snappy block
  uncompressed data chunk: 0x01 + 3B LE length + 4B masked CRC32C + raw
  (chunk payload <= 65536 bytes of uncompressed data; encoders emit the
  uncompressed form when compression would inflate)

so a framed stream produced here is readable by any conforming snappy
framing decoder and vice versa.
"""

from __future__ import annotations

import ctypes
import os
import threading

from goworld_tpu.net.nativebuild import ensure_built
from goworld_tpu.utils import log

logger = log.get("snappy")

_build_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_tried = False

_MAX_CHUNK = 65536                 # framing: max uncompressed per chunk
_MASK_DELTA = 0xA282EAD8
_STREAM_ID = b"\xff\x06\x00\x00sNaPpY"

_CHUNK_COMPRESSED = 0x00
_CHUNK_UNCOMPRESSED = 0x01
_CHUNK_STREAM_ID = 0xFF
# 0x02..0x7f are unskippable reserved; 0x80..0xfd skippable padding


def _load() -> ctypes.CDLL | None:
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    with _build_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        so = ensure_built("_snappy_core.so", "snappy_core.cpp", logger)
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            logger.warning("snappy native load failed (%s)", e)
            try:
                os.unlink(so)  # let the next process rebuild
            except OSError:
                pass
            return None
        # c_char_p srcs: ctypes passes Python bytes by pointer with no
        # copy — this sits on the per-packet hot path
        lib.gw_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.gw_crc32c.restype = ctypes.c_uint32
        lib.gw_snappy_max_compressed_length.argtypes = [ctypes.c_int64]
        lib.gw_snappy_max_compressed_length.restype = ctypes.c_int64
        lib.gw_snappy_compress.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p]
        lib.gw_snappy_compress.restype = ctypes.c_int64
        lib.gw_snappy_uncompress.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int64]
        lib.gw_snappy_uncompress.restype = ctypes.c_int64
        _lib = lib
        return _lib


class _Scratch(threading.local):
    """Reusable per-thread output buffer: the per-packet hot path must
    not pay a fresh zeroed allocation per chunk (the buffer grows
    geometrically and is zeroed only when (re)created)."""

    def __init__(self):
        self.buf = ctypes.create_string_buffer(80 * 1024)

    def at_least(self, n: int):
        if len(self.buf) < n:
            self.buf = ctypes.create_string_buffer(
                max(n, 2 * len(self.buf)))
        return self.buf


_scratch = _Scratch()


def available() -> bool:
    return _load() is not None


# ------------------------------------------------------------ block API --

def compress(data: bytes) -> bytes:
    """Snappy BLOCK compress."""
    lib = _load()
    if lib is None:
        raise RuntimeError("snappy native core unavailable")
    cap = lib.gw_snappy_max_compressed_length(len(data))
    out = _scratch.at_least(cap)
    n = lib.gw_snappy_compress(data, len(data), out)
    return ctypes.string_at(out, n)


def uncompress(data: bytes, max_len: int = 1 << 27) -> bytes:
    """Snappy BLOCK decompress (validates; raises on malformed input).
    ``max_len`` bounds the scratch buffer — framing callers pass the
    64KB chunk cap, so the steady state allocates nothing."""
    lib = _load()
    if lib is None:
        raise RuntimeError("snappy native core unavailable")
    out = _scratch.at_least(max_len)
    n = lib.gw_snappy_uncompress(data, len(data), out, max_len)
    if n < 0:
        raise ValueError("malformed snappy block")
    return ctypes.string_at(out, n)


def crc32c(data: bytes) -> int:
    lib = _load()
    if lib is None:
        raise RuntimeError("snappy native core unavailable")
    return int(lib.gw_crc32c(data, len(data)))


def _masked_crc(data: bytes) -> int:
    c = crc32c(data)
    return (((c >> 15) | (c << 17)) + _MASK_DELTA) & 0xFFFFFFFF


# ---------------------------------------------------------- framing API --

class StreamCompressor:
    """Incremental encoder for the snappy framing format: each
    ``compress(data)`` call returns framed chunk bytes (the stream
    identifier is prepended to the first output), mirroring the
    zlib-compressobj shape PacketConnection uses."""

    def __init__(self):
        self._started = False

    def compress(self, data: bytes) -> bytes:
        out = bytearray()
        if not self._started:
            out += _STREAM_ID
            self._started = True
        for off in range(0, len(data), _MAX_CHUNK):
            piece = data[off:off + _MAX_CHUNK]
            crc = _masked_crc(piece)
            comp = compress(piece)
            if len(comp) < len(piece):
                body_len = 4 + len(comp)
                out += bytes((_CHUNK_COMPRESSED,
                              body_len & 0xFF, (body_len >> 8) & 0xFF,
                              (body_len >> 16) & 0xFF))
                out += crc.to_bytes(4, "little")
                out += comp
            else:  # compression would inflate — emit raw (spec behavior)
                body_len = 4 + len(piece)
                out += bytes((_CHUNK_UNCOMPRESSED,
                              body_len & 0xFF, (body_len >> 8) & 0xFF,
                              (body_len >> 16) & 0xFF))
                out += crc.to_bytes(4, "little")
                out += piece
        return bytes(out)


class StreamDecompressor:
    """Incremental decoder: feed framed bytes, get uncompressed bytes.
    Buffers partial chunks across calls; raises ValueError on a corrupt
    stream (bad CRC, malformed block, reserved unskippable chunk)."""

    def __init__(self):
        self._buf = bytearray()

    def decompress(self, data: bytes, max_out: int | None = None) -> bytes:
        """``max_out`` bounds the decoded size DURING decode (a
        high-ratio stream of max-expansion chunks is the snappy shape
        of a decompression bomb — the check must not wait for the full
        allocation)."""
        self._buf += data
        out = bytearray()
        while True:
            if max_out is not None and len(out) > max_out:
                raise ValueError("snappy stream exceeds size bound")
            if len(self._buf) < 4:
                break
            ctype = self._buf[0]
            body_len = (self._buf[1] | (self._buf[2] << 8)
                        | (self._buf[3] << 16))
            if len(self._buf) < 4 + body_len:
                break
            body = bytes(self._buf[4:4 + body_len])
            del self._buf[:4 + body_len]
            if ctype == _CHUNK_STREAM_ID:
                if body != _STREAM_ID[4:]:
                    raise ValueError("bad snappy stream identifier")
                continue
            if ctype in (_CHUNK_COMPRESSED, _CHUNK_UNCOMPRESSED):
                if body_len < 4:
                    raise ValueError("short snappy chunk")
                want_crc = int.from_bytes(body[:4], "little")
                piece = (uncompress(body[4:], _MAX_CHUNK + 1)
                         if ctype == _CHUNK_COMPRESSED else body[4:])
                if len(piece) > _MAX_CHUNK:
                    raise ValueError("oversized snappy chunk")
                if _masked_crc(piece) != want_crc:
                    raise ValueError("snappy chunk CRC mismatch")
                out += piece
                continue
            if 0x80 <= ctype <= 0xFE:
                continue  # skippable (0x80-0xfd reserved, 0xfe padding)
            raise ValueError(f"unskippable snappy chunk 0x{ctype:02x}")
        return bytes(out)
