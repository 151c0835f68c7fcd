"""Micro-block: the unit of columnar storage and decode.

Reference surface: OceanBase micro blocks (~16KB units inside 2MB macro
blocks, storage/blocksstable/ob_imicro_block_reader.h) whose readers decode
per-column streams directly into expression vectors (get_rows,
ob_imicro_block_reader.h:506-552). Here a micro block is a self-contained
byte string: header + per-column descriptors + encoded streams + crc32
trailer; the reader decodes whole columns into numpy arrays (the host half
of the device marshalling boundary — see core/table.py).

Layout (little-endian):
  u32 magic 0x0B5EB10C | u16 version | u16 ncols | u32 nrows | u32 reserved
  ncols * ColumnDesc {
     u8 enc | u8 dtype_code | u8 flags(bit0 has_nulls, bit1 row width)
     u8 for_width
     i64 for_min (with bit1: the values a row holds)
     u32 data_off | u32 data_len | u32 null_off | u32 null_len
  }
A column of fixed-width rows (a VECTOR(d) column: `(nrows, d)` float32)
is stored RAW, row after row, with bit1 set and `d` in the `for_min` slot;
it has no zone map (its zone reads -inf..inf, so no range prunes it). A
column of one value a row never sets bit1, so its bytes are as before.
  payload streams...
  u32 crc32 (over everything before the trailer)
"""

from __future__ import annotations

import struct
from dataclasses import dataclass

import numpy as np

from . import encoding as enc

MAGIC = 0x0B5EB10C
# compressed-wrapper frame: u32 magic | u32 raw_len | deflate payload —
# the reference wraps ENCODED micro blocks in a general-purpose block
# compressor (lz4/zstd/snappy, deps/oblib/src/lib/compress); this image
# ships zlib, and the wrapper composes with (never replaces) the
# lightweight per-column encodings, exactly like the reference
MAGIC_COMPRESSED = 0x0B5EB10D
VERSION = 1
_HEADER = struct.Struct("<IHHII")
_CHEADER = struct.Struct("<II")
_COLDESC = struct.Struct("<BBBBqIIII")

# dtype codes on the wire
_DTYPE_CODES: dict[np.dtype, int] = {
    np.dtype(np.bool_): 0,
    np.dtype(np.int8): 1,
    np.dtype(np.int16): 2,
    np.dtype(np.int32): 3,
    np.dtype(np.int64): 4,
    np.dtype(np.float32): 5,
    np.dtype(np.float64): 6,
}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}

DEFAULT_BLOCK_ROWS = 16384
_ROW_WIDTH = 2  # ColumnDesc flag: `for_min` holds the values of one row


@dataclass(frozen=True)
class ColumnZone:
    """Zone map entry: min/max over the block (ints: value; floats: raw)."""

    vmin: float
    vmax: float


def write_block(
    columns: list[np.ndarray], valids: list[np.ndarray | None],
    compress: bool = True, hints: list | None = None,
) -> tuple[bytes, list[ColumnZone]]:
    """Encode one micro block; returns (bytes, per-column zone maps).
    `hints` (aligned to `columns`) carries per-column advisor encoding
    preferences — honored when lossless for the block, else the cost
    model decides as usual."""
    nrows = len(columns[0]) if columns else 0
    descs = []
    streams: list[bytes] = []
    zones: list[ColumnZone] = []
    pos = 0
    for i, (a, valid) in enumerate(zip(columns, valids)):
        a = np.ascontiguousarray(a)
        hint = hints[i] if hints is not None else None
        if a.ndim == 2:
            e, params = enc.ENC_RAW, {"min": a.shape[1]}
            data = a.tobytes()
            zones.append(ColumnZone(-np.inf, np.inf))
        elif a.dtype == np.bool_:
            a8 = a.astype(np.int8)
            stats = enc.analyze_ints(a8)
            picked = enc.hinted_encoding(a8, stats, hint) if hint else None
            e, params = picked or enc.choose_encoding(a8, stats)
            data = enc.encode_column(a8, e, params)
            zones.append(ColumnZone(stats.vmin, stats.vmax))
        elif np.issubdtype(a.dtype, np.integer):
            stats = enc.analyze_ints(a)
            picked = enc.hinted_encoding(a, stats, hint) if hint else None
            e, params = picked or enc.choose_encoding(a, stats)
            data = enc.encode_column(a, e, params)
            zones.append(ColumnZone(stats.vmin, stats.vmax))
        else:
            e, params = enc.choose_encoding(a, enc.ColumnStats(0, 0, 0))
            data = enc.encode_column(a, e, params)
            if nrows:
                zones.append(ColumnZone(float(a.min()), float(a.max())))
            else:
                zones.append(ColumnZone(0.0, 0.0))
        has_nulls = valid is not None and not bool(valid.all())
        nulls = enc.pack_validity(valid) if has_nulls else b""
        descs.append(
            (
                e,
                _DTYPE_CODES[a.dtype if a.dtype != np.bool_ else np.dtype(np.int8)],
                (1 if has_nulls else 0) | (_ROW_WIDTH if a.ndim == 2 else 0),
                params.get("width", 0),
                params.get("min", 0),
                pos,
                len(data),
                pos + len(data) if has_nulls else 0,
                len(nulls),
            )
        )
        streams.append(data)
        if has_nulls:
            streams.append(nulls)
            pos += len(data) + len(nulls)
        else:
            pos += len(data)
    out = bytearray()
    out += _HEADER.pack(MAGIC, VERSION, len(columns), nrows, 0)
    for d in descs:
        out += _COLDESC.pack(*d)
    for s in streams:
        out += s
    out += struct.pack("<I", enc.crc32(bytes(out)))
    raw = bytes(out)
    if compress:
        import zlib

        packed = zlib.compress(raw, 1)
        # only keep the wrapper when it actually saves space (already-
        # tight encodings often don't deflate further)
        if len(packed) + _CHEADER.size < int(len(raw) * 0.9):
            return (
                _CHEADER.pack(MAGIC_COMPRESSED, len(raw)) + packed,
                zones,
            )
    return raw, zones


@dataclass
class BlockReader:
    """Parsed block header; decodes columns lazily by index."""

    buf: memoryview
    nrows: int
    ncols: int
    _descs: list[tuple]
    _payload_off: int

    @staticmethod
    def open(buf: bytes | memoryview, verify: bool = True) -> "BlockReader":
        mv = memoryview(buf)
        magic2, raw_len = _CHEADER.unpack_from(mv, 0)
        if magic2 == MAGIC_COMPRESSED:
            import zlib

            try:
                raw = zlib.decompress(bytes(mv[_CHEADER.size:]))
            except zlib.error as e:  # corruption surfaces uniformly
                raise ValueError(f"micro-block decompress failed: {e}")
            if len(raw) != raw_len:
                raise ValueError("micro-block decompressed length mismatch")
            mv = memoryview(raw)
        magic, version, ncols, nrows, _ = _HEADER.unpack_from(mv, 0)
        if magic != MAGIC:
            raise ValueError(f"bad micro-block magic 0x{magic:08X}")
        if version != VERSION:
            raise ValueError(f"unsupported micro-block version {version}")
        if verify:
            (crc,) = struct.unpack_from("<I", mv, len(mv) - 4)
            if enc.crc32(bytes(mv[:-4])) != crc:
                raise ValueError("micro-block crc mismatch")
        descs = []
        off = _HEADER.size
        for _ in range(ncols):
            descs.append(_COLDESC.unpack_from(mv, off))
            off += _COLDESC.size
        return BlockReader(mv, nrows, ncols, descs, off)

    def column(self, i: int, as_bool: bool = False) -> tuple[np.ndarray, np.ndarray | None]:
        """Decode column i -> (values, validity-or-None); a column of
        fixed-width rows comes back `(nrows, width)`."""
        (e, dcode, flags, width, vmin, doff, dlen, noff, nlen) = self._descs[i]
        dtype = _CODE_DTYPES[dcode]
        start = self._payload_off + doff
        data = self.buf[start : start + dlen]
        if flags & _ROW_WIDTH:
            vals = enc.decode_column(data, e, {}, dtype, self.nrows * vmin)
            vals = vals.reshape(self.nrows, vmin)
        else:
            params = {"min": vmin, "width": width} if e == enc.ENC_FOR else {}
            vals = enc.decode_column(data, e, params, dtype, self.nrows)
        if as_bool:
            vals = vals.astype(np.bool_)
        valid = None
        if flags & 1:
            nstart = self._payload_off + noff
            valid = enc.unpack_validity(self.buf[nstart : nstart + nlen], self.nrows)
        return vals, valid
