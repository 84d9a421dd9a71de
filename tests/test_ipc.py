"""Wire protocol tests (`serve.ipc`) — the process fleet's only
cross-boundary surface, pinned at its edges:

* ROUNDTRIP — every frame type carries its payload (or None) intact
  over a real socketpair, flags included;
* RESUME — a deadline that expires mid-frame raises `WireDeadline`
  (transient) WITHOUT desyncing: the `FrameStream` keeps the partial
  bytes and a later call hands over exactly the frames sent, even when
  the peer dribbles bytes one at a time;
* STRUCTURED REJECTION — truncated / oversized / garbage / corrupt /
  version-mismatched frames raise their named `WireError` subclass
  immediately (never hang, never return garbage), each on a fresh
  connection because fatal framing errors cannot resync by design;
* RETRY CLASSES — `WireDeadline` is a `TimeoutError` and
  `TruncatedFrame` a `ConnectionError` (both transient under the PR-4
  ladder); the four fatal errors are deterministic and NOT transient.

All in-process and fast: no worker processes are spawned here (the
full SIGKILL drill lives in test_bench_smoke.py).
"""

import socket
import struct
import threading
import time

import pytest

from swiftly_tpu.resilience.retry import is_transient
from swiftly_tpu.serve import ipc
from swiftly_tpu.serve.ipc import (
    FRAME_CONTROL,
    FRAME_DRAIN,
    FRAME_ERROR,
    FRAME_HEARTBEAT,
    FRAME_HELLO,
    FRAME_REQUEST,
    FRAME_RESULT,
    HEADER_BYTES,
    MAX_FRAME_BYTES,
    WIRE_VERSION,
    BadChecksum,
    BadMagic,
    FrameStream,
    FrameTooLarge,
    TruncatedFrame,
    VersionMismatch,
    WireDeadline,
    WireError,
    encode_frame,
    recv_frame,
    send_frame,
)


@pytest.fixture
def pair():
    a, b = socket.socketpair()
    yield a, b
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# roundtrip
# ---------------------------------------------------------------------------


def test_roundtrip_all_frame_types(pair):
    a, b = pair
    stream = FrameStream(b)
    payloads = {
        FRAME_HELLO: {"rid": 3, "pid": 1234},
        FRAME_REQUEST: {"req_id": 7, "config": (0, 1, 2)},
        FRAME_RESULT: {"req_id": 7, "rows": [b"\x00" * 64]},
        FRAME_HEARTBEAT: {"beat": 12, "depth": 0},
        FRAME_DRAIN: None,
        FRAME_ERROR: {"req_id": 7, "error": "boom"},
        FRAME_CONTROL: {"dwell_l2_s": 0.5},
    }
    for ftype, payload in payloads.items():
        send_frame(a, ftype, payload, deadline_s=5.0)
    for ftype, payload in payloads.items():
        got_type, got_flags, got = stream.recv_frame(deadline_s=5.0)
        assert got_type == ftype
        assert got_flags == 0
        assert got == payload


def test_roundtrip_flags_and_empty_payload(pair):
    a, b = pair
    send_frame(a, FRAME_DRAIN, None, deadline_s=5.0, flags=0x5A)
    ftype, flags, payload = recv_frame(b, deadline_s=5.0)
    assert (ftype, flags, payload) == (FRAME_DRAIN, 0x5A, None)


def test_header_is_sixteen_bytes():
    # the documented fixed-size header: magic(4) version(2) type(1)
    # flags(1) length(4) crc(4)
    assert HEADER_BYTES == 16
    frame = encode_frame(FRAME_DRAIN)
    assert len(frame) == HEADER_BYTES


# ---------------------------------------------------------------------------
# deadline expiry resumes without desync
# ---------------------------------------------------------------------------


def test_partial_frame_survives_deadline_expiry(pair):
    a, b = pair
    stream = FrameStream(b)
    frame = encode_frame(FRAME_REQUEST, {"req_id": 1, "blob": b"x" * 500})

    # deliver only a prefix: the read must expire transiently, not hang
    a.sendall(frame[:10])
    with pytest.raises(WireDeadline):
        stream.recv_frame(deadline_s=0.05)

    # a little more (past the header, into the payload): still expires
    a.sendall(frame[10:100])
    with pytest.raises(WireDeadline):
        stream.recv_frame(deadline_s=0.05)

    # the rest arrives: the SAME stream decodes the frame from its kept
    # prefix, and a second frame sent whole proves the stream is in sync
    a.sendall(frame[100:])
    ftype, _, payload = stream.recv_frame(deadline_s=5.0)
    assert ftype == FRAME_REQUEST
    assert payload == {"req_id": 1, "blob": b"x" * 500}

    send_frame(a, FRAME_HEARTBEAT, {"beat": 1}, deadline_s=5.0)
    ftype, _, payload = stream.recv_frame(deadline_s=5.0)
    assert (ftype, payload) == (FRAME_HEARTBEAT, {"beat": 1})


def test_dribbled_bytes_decode_across_expiries(pair):
    # worst case: the peer delivers one byte per deadline window; every
    # intermediate call expires, the final call returns the exact frame
    a, b = pair
    stream = FrameStream(b)
    frame = encode_frame(FRAME_HELLO, {"rid": 9})
    for i, byte in enumerate(frame):
        a.sendall(bytes([byte]))
        if i < len(frame) - 1:
            with pytest.raises(WireDeadline):
                stream.recv_frame(deadline_s=0.01)
    ftype, _, payload = stream.recv_frame(deadline_s=5.0)
    assert (ftype, payload) == (FRAME_HELLO, {"rid": 9})


def test_deadline_expiry_never_hangs(pair):
    # an idle peer: recv_frame must return (by raising) near the
    # deadline, not block forever
    _, b = pair
    t0 = time.monotonic()
    with pytest.raises(WireDeadline):
        FrameStream(b).recv_frame(deadline_s=0.1)
    assert time.monotonic() - t0 < 2.0


# ---------------------------------------------------------------------------
# structured rejection (fresh socketpair per case: fatal errors desync)
# ---------------------------------------------------------------------------


def _fresh_pair_with(data):
    a, b = socket.socketpair()
    a.sendall(data)
    a.close()  # peer gone: any missing bytes surface as truncation
    return b


def test_truncated_frame_peer_closed_mid_frame():
    frame = encode_frame(FRAME_RESULT, {"req_id": 1, "rows": [b"y" * 256]})
    b = _fresh_pair_with(frame[: HEADER_BYTES + 5])
    with pytest.raises(TruncatedFrame) as exc_info:
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()
    assert isinstance(exc_info.value, ConnectionError)


def test_truncated_header():
    b = _fresh_pair_with(b"SWFT\x00")
    with pytest.raises(TruncatedFrame):
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()


def test_garbage_bytes_bad_magic():
    b = _fresh_pair_with(b"\xde\xad\xbe\xef" * 8)
    with pytest.raises(BadMagic):
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()


def test_unknown_frame_type_rejected():
    header = ipc._HEADER.pack(b"SWFT", WIRE_VERSION, 250, 0, 0, 0)
    b = _fresh_pair_with(header)
    with pytest.raises(BadMagic):
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()


def test_oversized_declared_length_rejected_before_payload():
    # a corrupt length field must be rejected from the header alone —
    # no payload bytes were even sent
    header = ipc._HEADER.pack(
        b"SWFT", WIRE_VERSION, FRAME_REQUEST, 0, MAX_FRAME_BYTES + 1, 0)
    b = _fresh_pair_with(header)
    t0 = time.monotonic()
    with pytest.raises(FrameTooLarge):
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()
    assert time.monotonic() - t0 < 2.0


def test_version_mismatch_rejected():
    frame = encode_frame(FRAME_HELLO, {"rid": 0}, version=WIRE_VERSION + 1)
    b = _fresh_pair_with(frame)
    with pytest.raises(VersionMismatch):
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()


def test_corrupt_payload_bad_checksum():
    frame = bytearray(encode_frame(FRAME_REQUEST, {"req_id": 42}))
    frame[-1] ^= 0xFF  # flip a payload bit; header CRC now disagrees
    b = _fresh_pair_with(bytes(frame))
    with pytest.raises(BadChecksum):
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()


def test_encode_oversized_payload_rejected(monkeypatch):
    monkeypatch.setattr(ipc, "MAX_FRAME_BYTES", 256)
    with pytest.raises(FrameTooLarge):
        encode_frame(FRAME_RESULT, {"blob": b"z" * 1024})


# ---------------------------------------------------------------------------
# retry classes
# ---------------------------------------------------------------------------


def test_error_classes_and_transience():
    # transient: the retry ladder may re-try these
    assert issubclass(WireDeadline, TimeoutError)
    assert issubclass(TruncatedFrame, ConnectionError)
    assert is_transient(WireDeadline("deadline"))
    assert is_transient(TruncatedFrame("closed"))
    # fatal: deterministic frame rejections are NOT retried
    for exc in (BadMagic("m"), BadChecksum("c"),
                FrameTooLarge("f"), VersionMismatch("v")):
        assert isinstance(exc, WireError)
        assert not is_transient(exc)


def test_bad_frames_counted(monkeypatch):
    counted = []
    monkeypatch.setattr(
        ipc._metrics, "count", lambda name, n=1: counted.append(name))
    b = _fresh_pair_with(b"\x00" * HEADER_BYTES)
    with pytest.raises(BadMagic):
        FrameStream(b).recv_frame(deadline_s=5.0)
    b.close()
    assert "ipc.bad_frames" in counted
    assert "ipc.bad_frames.magic" in counted


def test_send_frame_counts_bytes(pair, monkeypatch):
    a, b = pair
    counted = {}
    monkeypatch.setattr(
        ipc._metrics, "count",
        lambda name, n=1: counted.__setitem__(
            name, counted.get(name, 0) + n))
    n = send_frame(a, FRAME_HEARTBEAT, {"beat": 0}, deadline_s=5.0)
    ftype, _, _ = FrameStream(b).recv_frame(deadline_s=5.0)
    assert ftype == FRAME_HEARTBEAT
    assert counted["ipc.frames_sent"] == 1
    assert counted["ipc.bytes_sent"] == n
    assert counted["ipc.frames_received"] == 1
    assert counted["ipc.bytes_received"] == n


def test_concurrent_sender_interleaves_cleanly(pair):
    # a writer thread streams many frames while the reader drains them
    # through one FrameStream: order and content survive
    a, b = pair
    n_frames = 200

    def write():
        for i in range(n_frames):
            send_frame(a, FRAME_RESULT, {"req_id": i}, deadline_s=10.0)

    t = threading.Thread(target=write)
    t.start()
    stream = FrameStream(b)
    for i in range(n_frames):
        ftype, _, payload = stream.recv_frame(deadline_s=10.0)
        assert ftype == FRAME_RESULT
        assert payload == {"req_id": i}
    t.join(10.0)
    assert not t.is_alive()
