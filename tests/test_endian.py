"""Byte-order guards: golden LITTLE-ENDIAN byte vectors for every wire
format (the s390x CI analog,
/root/reference/scripts/travis/travis_script.sh:62-66).

These assert EMITTED bytes, not round-trips (a round-trip passes on any
host whatever the byte order) — mirroring the reference's endian golden
bytes (/root/reference/test/unittest/unittest_serializer.cc:86-110). On a
big-endian host a native-endian '@' slipping into a pack format, or a raw
``tobytes()`` of a native-order array, fails these exact-byte asserts.
The native core is guarded separately: api.h #errors at COMPILE time on a
big-endian target (its frame loads are memcpy-native by design), so wire
corruption there is impossible rather than detected.
"""

import io
import struct

import numpy as np

from dmlc_tpu.io.recordio import RECORDIO_MAGIC, RecordIOWriter
from dmlc_tpu.utils import serializer


def _emit(fn, *args) -> bytes:
    buf = io.BytesIO()
    fn(buf, *args)
    return buf.getvalue()


class TestSerializerGoldenBytes:
    def test_scalar_wire_bytes(self):
        # one golden vector per fixed-width kind (serializer.h:83-104
        # arithmetic handler, explicit LE on the wire)
        golden = [
            ("int8", -2, b"\xfe"),
            ("uint8", 0xAB, b"\xab"),
            ("int32", 0x01020304, b"\x04\x03\x02\x01"),
            ("uint32", 0xDEADBEEF, b"\xef\xbe\xad\xde"),
            ("int64", 0x0102030405060708, b"\x08\x07\x06\x05\x04\x03\x02\x01"),
            ("uint64", 1, b"\x01\x00\x00\x00\x00\x00\x00\x00"),
            # IEEE-754: 1.0f = 0x3f800000, 1.0 = 0x3ff0000000000000
            ("float32", 1.0, b"\x00\x00\x80\x3f"),
            ("float64", 1.0, b"\x00\x00\x00\x00\x00\x00\xf0\x3f"),
            ("bool", True, b"\x01"),
        ]
        for kind, value, want in golden:
            got = _emit(serializer.write_scalar, value, kind)
            assert got == want, (kind, got.hex(), want.hex())
            # and the reader decodes the golden bytes (not just its own)
            assert serializer.read_scalar(io.BytesIO(want), kind) == value

    def test_length_prefixed_bytes_and_str(self):
        # [u64 LE length][payload] (serializer.h string handler)
        assert _emit(serializer.write_bytes, b"hi") == (
            b"\x02\x00\x00\x00\x00\x00\x00\x00hi")
        assert _emit(serializer.write_str, "A") == (
            b"\x01\x00\x00\x00\x00\x00\x00\x00A")

    def test_ndarray_wire_bytes(self):
        # [dtype str]['<i4'][ndim u32][shape u64...][LE payload]
        arr = np.array([[1, 2]], dtype=np.int32)
        got = _emit(serializer.write_ndarray, arr)
        want = (
            b"\x03\x00\x00\x00\x00\x00\x00\x00<i4"  # dtype tag (u64-len str)
            + b"\x02\x00\x00\x00"                  # ndim = 2 (u32)
            + b"\x01\x00\x00\x00\x00\x00\x00\x00"  # shape[0] = 1
            + b"\x02\x00\x00\x00\x00\x00\x00\x00"  # shape[1] = 2
            + b"\x01\x00\x00\x00\x02\x00\x00\x00"  # data LE
        )
        assert got == want, got.hex()
        back = serializer.read_ndarray(io.BytesIO(want))
        np.testing.assert_array_equal(back, arr)

    def test_obj_tagged_wire_bytes(self):
        # tag u8 + payload; int rides int64 LE
        got = _emit(serializer.write_obj, 3)
        assert got[1:] == b"\x03\x00\x00\x00\x00\x00\x00\x00"
        got = _emit(serializer.write_obj, True)
        assert got[1:] == b"\x01"

    def test_big_endian_input_arrays_normalize(self):
        # a BE-ordered array must serialize to the same LE wire bytes
        arr_be = np.array([1, 2], dtype=">i4")
        arr_le = np.array([1, 2], dtype="<i4")
        assert _emit(serializer.write_ndarray, arr_be)[-8:] == \
            _emit(serializer.write_ndarray, arr_le)[-8:] == \
            b"\x01\x00\x00\x00\x02\x00\x00\x00"


class TestRecordIOGoldenBytes:
    def test_frame_exact_bytes(self):
        # [magic u32 LE][lrec u32 LE][data][pad] — the full 16-byte vector,
        # magic 0xced7230a on the wire as 0a 23 d7 ce (recordio.h:17-45)
        buf = io.BytesIO()
        RecordIOWriter(buf).write_record(b"abcde")
        assert buf.getvalue() == (
            b"\x0a\x23\xd7\xce"      # magic LE
            b"\x05\x00\x00\x00"      # lrec: cflag=0, len=5
            b"abcde"
            b"\x00\x00\x00"          # pad to 4
        )

    def test_escaped_frame_exact_bytes(self):
        # payload == magic: escaped as a 2-part record, the aligned magic
        # cell dropped (cflag 1 = start then 3 = end, both zero-length
        # parts; recordio.h:17-45 cflag semantics)
        buf = io.BytesIO()
        RecordIOWriter(buf).write_record(struct.pack("<I", RECORDIO_MAGIC))
        assert buf.getvalue() == (
            b"\x0a\x23\xd7\xce" + struct.pack("<I", (1 << 29) | 0)
            + b"\x0a\x23\xd7\xce" + struct.pack("<I", (3 << 29) | 0)
        )

    def test_native_extract_reads_le_wire(self):
        # the native reader must interpret the SAME golden bytes (its
        # compile-time guard makes BE hosts unbuildable, so a passing build
        # implies these loads are LE-correct)
        from dmlc_tpu import native

        if not native.available():
            import pytest

            pytest.skip("native core unavailable")
        wire = b"\x0a\x23\xd7\xce\x05\x00\x00\x00abcde\x00\x00\x00"
        payload, offsets = native.recordio_extract(wire)
        assert bytes(payload[offsets[0]:offsets[1]]) == b"abcde"
