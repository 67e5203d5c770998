"""LZO1X decompressor tests (native/src/lzo.cc — the last nvcomp-analog
codec row, SURVEY §2.8).

No LZO compressor exists in this image (pyarrow has no LZO codec), so
streams are built by hand from the published LZO1X format: a tiny
literal/match assembler here plays the role the reference's nvcomp
round-trips play. Each case pins exact output bytes.
"""

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
from spark_rapids_jni_tpu import runtime

pytestmark = pytest.mark.usefixtures("native")

EOF_MARKER = bytes([0x11, 0x00, 0x00])


def first_literals(payload: bytes) -> bytes:
    """Leading literal run via the first-byte shortcut (len 4..238)."""
    assert 4 <= len(payload) <= 238
    return bytes([len(payload) + 17]) + payload


def m2(dist: int, length: int, trail: bytes = b"") -> bytes:
    """M2 match: len 3..8, dist 1..2048, 0..3 trailing literals."""
    assert 3 <= length <= 8 and 1 <= dist <= 2048 and len(trail) <= 3
    d = dist - 1
    t = ((length - 1) << 5) | ((d & 7) << 2) | len(trail)
    return bytes([t, d >> 3]) + trail


def m3(dist: int, length: int, trail: bytes = b"") -> bytes:
    """M3 match: len 3..33 (inline), dist 1..16384."""
    assert 3 <= length <= 33 and 1 <= dist <= 16384 and len(trail) <= 3
    d = dist - 1
    t = 0x20 | (length - 2)
    b0 = ((d & 0x3F) << 2) | len(trail)
    b1 = d >> 6
    return bytes([t, b0, b1]) + trail


def decompress(stream: bytes, bound: int = 1 << 20) -> bytes:
    return runtime.lzo1x_decompress(stream, bound)


def test_pure_literaccording_run():
    payload = b"hello lzo world!"
    stream = first_literals(payload) + EOF_MARKER
    assert decompress(stream) == payload


def test_empty_stream_is_just_eof():
    assert decompress(EOF_MARKER) == b""


def test_m2_overlapping_match_rle():
    # "abcd" then an overlapping dist-4 len-8 match = "abcd" * 3
    stream = first_literals(b"abcd") + m2(4, 8) + EOF_MARKER
    assert decompress(stream) == b"abcd" * 3


def test_m2_with_trailing_literals():
    stream = first_literals(b"wxyz") + m2(4, 4, b"!?") + EOF_MARKER
    assert decompress(stream) == b"wxyz" + b"wxyz" + b"!?"


def test_m3_long_distance():
    payload = bytes(np.random.default_rng(7).integers(0, 256, 100, dtype=np.uint8))
    stream = first_literals(payload) + m3(100, 10) + EOF_MARKER
    assert decompress(stream) == payload + payload[:10]


def test_long_literal_run_mid_stream():
    # after a match with no trailing literals, T<16 starts a literal
    # run: T=0 extends (18 + next byte)
    head = bytes(range(32, 36))
    run = bytes(np.random.default_rng(3).integers(0, 256, 18 + 30, dtype=np.uint8))
    stream = first_literals(head) + m2(4, 3) + bytes([0, 30]) + run + EOF_MARKER
    assert decompress(stream) == head + head[:3] + run


def test_short_literal_run_mid_stream():
    # non-extended literal run: T=1..15 -> T+3 literals
    run = b"0123456789"[:8]  # T=5 -> 8 literals
    head = b"qrst"
    stream = first_literals(head) + m2(4, 3) + bytes([5]) + run + EOF_MARKER
    assert decompress(stream) == head + head[:3] + run


def test_m1_after_literal_run_distance_2049():
    # T<16 right after a literal run is a 3-byte match at dist 2049+
    payload = bytes(np.random.default_rng(11).integers(0, 256, 238, dtype=np.uint8))
    chunks = [first_literals(payload)]
    expected = bytearray(payload)
    for _ in range(9):  # build up past 2049 bytes of history; literal
        # runs are only legal from the post-match state, so alternate
        chunks.append(m2(4, 3))
        expected.extend(expected[-4:][:3])
        chunks.append(bytes([0, 238 - 18]) + payload)
        expected.extend(payload)
    # now dist 2049 reaches history; M1-after-literal-run: len 3
    d = 0  # dist = 2049 exactly
    chunks.append(bytes([(d & 3) << 2, d >> 2]))
    idx = len(expected) - 2049
    expected.extend(expected[idx : idx + 3])
    stream = b"".join(chunks) + EOF_MARKER
    assert decompress(stream) == bytes(expected)


def test_truncated_stream_raises():
    with pytest.raises(RuntimeError):
        decompress(first_literals(b"abcd"))  # no EOF marker


def test_bad_distance_raises():
    with pytest.raises(RuntimeError):
        decompress(first_literals(b"abcd") + m2(2048, 3) + EOF_MARKER)


def test_output_bound_enforced():
    stream = first_literals(b"abcdefgh") + EOF_MARKER
    with pytest.raises(RuntimeError):
        runtime.lzo1x_decompress(stream, 4)


def test_parquet_lzo_codec_mapped():
    # codec 3 must not silently fall through to "uncompressed"
    from spark_rapids_jni_tpu.io.parquet_reader import _CODECS

    assert _CODECS[3] == "lzo"


def test_parquet_hadoop_lzo_page():
    import struct

    from spark_rapids_jni_tpu.io.parquet_reader import _decompress

    payload = b"spark" * 20
    block = first_literals(payload[:100]) + EOF_MARKER
    framed = struct.pack(">II", 100, len(block)) + block
    assert _decompress(framed, "lzo", 100) == payload[:100]


def test_orc_lzo_chunk():
    from spark_rapids_jni_tpu.io.orc_reader import _K_LZO, _deframe

    payload = b"orc lzo payload."
    blob = first_literals(payload) + EOF_MARKER
    hdr = len(blob) << 1  # compressed chunk
    framed = bytes([hdr & 0xFF, (hdr >> 8) & 0xFF, (hdr >> 16) & 0xFF]) + blob
    assert _deframe(framed, _K_LZO, 1 << 18) == payload
