"""Disaggregated ingest service (petastorm_tpu.service): the v2 binary
wire (control codec, batch frames, robustness against corrupt/legacy
frames), dispatcher assignment/requeue/buffer-relay, client executor,
multi-client e2e with the shared warm tier, and chaos on the service plane
(worker SIGKILL, client connection drop, dispatcher loss)."""

import os
import pickle
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import uuid

import numpy as np
import pytest

from petastorm_tpu.batch import ColumnBatch
from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.pool import VentilatedItem, WorkerError
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.retry import RetryPolicy
from petastorm_tpu.schema import Field, Schema
from petastorm_tpu.service import wire
from petastorm_tpu.service.client import (ServiceConnectionError,
                                          ServiceExecutor)
from petastorm_tpu.service.dispatcher import Dispatcher
from petastorm_tpu.service.protocol import (FrameClosedError, FrameSocket,
                                            LegacyPickleFrameError,
                                            PayloadDecoder, WireItem,
                                            connect_frames, encode_result,
                                            parse_address,
                                            shm_transport_available)
from petastorm_tpu.service.wire import WireFormatError
from petastorm_tpu.service.worker import ServiceWorker
from petastorm_tpu.telemetry import Telemetry

FAST_RECONNECT = RetryPolicy(max_attempts=3, initial_backoff_s=0.05,
                             backoff_multiplier=1.5, max_backoff_s=0.3)


class EchoFactory:
    """Module-level (ServiceExecutor pickles factories to ship them)."""

    def __call__(self):
        return lambda item: ("echo", item.item,
                             getattr(item, "ordinal", None))


class PlainEchoFactory:
    def __call__(self):
        return lambda item: item.item


class SleepForeverFactory:
    def __call__(self):
        def fn(item):  # noqa: ARG001 - pretends to work forever
            time.sleep(3600)

        return fn


class HangFirstAttemptFactory:
    """Wedges attempt 0 of every item; requeued attempts complete - the
    shape the assignment-deadline liveness backstop recovers from."""

    def __call__(self):
        def fn(item):
            if getattr(item, "attempt", 0) == 0:
                time.sleep(3600)
            return ("recovered", item.ordinal)

        return fn


class UnpicklableResultFactory:
    """Returns a result pickle cannot serialize (a thread lock) - the
    worker must answer with a failure frame, not die silently."""

    def __call__(self):
        return lambda item: threading.Lock()


# -- fixtures -----------------------------------------------------------------

@pytest.fixture
def int_dataset(tmp_path):
    """200 int rows in 20 rowgroups."""
    url = str(tmp_path / "ds")
    schema = Schema("SvcInts", [Field("x", np.int64)])
    write_dataset(url, schema, [{"x": i} for i in range(200)],
                  row_group_size_rows=10)
    return url


@pytest.fixture
def fleet(int_dataset):
    """A dispatcher + two in-process workers, stopped at teardown."""
    disp = Dispatcher(telemetry=Telemetry(), heartbeat_timeout_s=5.0).start()
    addr = f"127.0.0.1:{disp.port}"
    workers = [ServiceWorker(addr, capacity=2, name=f"w{i}")
               for i in range(2)]
    threads = [threading.Thread(target=w.run, daemon=True) for w in workers]
    for t in threads:
        t.start()
    _wait_for(lambda: len(disp.stats()["workers"]) == 2)
    try:
        yield disp, addr, workers
    finally:
        for w in workers:
            w.stop()
        disp.stop()
        disp.join()


def _wait_for(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _read_all(url, addr, **kwargs):
    tele = kwargs.pop("telemetry", None) or Telemetry()
    with make_batch_reader(url, service_address=addr,
                           shuffle_row_groups=False, telemetry=tele,
                           **kwargs) as reader:
        rows = sorted(x for b in reader.iter_batches()
                      for x in b.columns["x"])
        diag = reader.diagnostics
    return rows, diag, tele


# -- protocol -----------------------------------------------------------------

def test_frame_roundtrip_and_eof():
    a, b = socket.socketpair()
    fa, fb = FrameSocket(a), FrameSocket(b)
    msgs = [{"t": "x", "n": 1}, {"t": "y", "blob": os.urandom(1 << 16)},
            {"t": "item", "item": WireItem.encode(
                VentilatedItem(3, "work", attempt=1))}]
    for m in msgs:
        fa.send(m)
    got = [fb.recv(timeout=2.0) for _ in msgs]
    assert got[0] == msgs[0]
    assert got[1]["blob"] == msgs[1]["blob"]
    item = WireItem.from_wire(got[2]["item"])
    assert item.ordinal == 3 and item.attempt == 1
    assert pickle.loads(item.blob) == "work"  # opaque blob: worker-side only
    assert fb.bytes_received == fa.bytes_sent
    # timeout (no data) -> None, partial state preserved
    assert fb.recv(timeout=0.05) is None
    # EOF -> FrameClosedError
    fa.close()
    with pytest.raises(FrameClosedError):
        fb.recv(timeout=2.0)
    fb.close()


def _ctrl_frame(msg) -> bytes:
    """A raw v2 CTRL frame as it appears on the socket."""
    payload = bytes([wire.KIND_CTRL]) + wire.dumps(msg)
    return struct.pack("!I", len(payload)) + payload


def test_frame_partial_delivery_survives_timeouts():
    a, b = socket.socketpair()
    fb = FrameSocket(b)
    payload = FrameSocket(a)
    framed = _ctrl_frame({"t": "big", "blob": b"z" * 100_000})
    # dribble the frame in two halves with a gap: the first recv times out
    # mid-frame, the second completes it from the kept buffer
    a.sendall(framed[:50])
    assert fb.recv(timeout=0.05) is None
    a.sendall(framed[50:])
    msg = fb.recv(timeout=2.0)
    assert msg["t"] == "big" and len(msg["blob"]) == 100_000
    payload.close()
    fb.close()


def test_send_timeout_declares_peer_dead():
    """A peer that stops draining its buffer must fail the send within the
    bound (and close the socket - a partial frame cannot be resumed), not
    block the sending thread forever."""
    a, b = socket.socketpair()
    fa = FrameSocket(a, send_timeout_s=0.2)
    blob = os.urandom(1 << 20)
    t0 = time.monotonic()
    with pytest.raises(OSError, match="drain|closed"):
        for _ in range(256):  # peer never reads: the buffer eventually fills
            fa.send({"t": "big", "blob": blob})
    assert time.monotonic() - t0 < 5.0
    # the timed-out socket is dead for good (stream would be desynced)
    with pytest.raises(OSError):
        fa.send({"t": "ping"})
    # ...and a read loop polling it must see the FrameClosedError it
    # already handles (reconnect path), not a ValueError from select on
    # the closed fd (which would crash a service worker's main loop)
    with pytest.raises(FrameClosedError):
        fa.recv(timeout=0.1)
    b.close()


def test_send_timeout_rearms_on_progress():
    """The send timeout bounds a drain STALL: a peer draining slowly but
    steadily must never be declared dead mid-frame."""
    a, b = socket.socketpair()
    fa = FrameSocket(a, send_timeout_s=0.3)
    stop = threading.Event()

    def slow_drain():
        while not stop.is_set():
            time.sleep(0.1)  # stalls shorter than the timeout, repeatedly
            try:
                if not b.recv(1 << 16):
                    return
            except OSError:
                return

    t = threading.Thread(target=slow_drain, daemon=True)
    t.start()
    try:
        # several times the socketpair buffer: completes only if progress
        # re-arms the deadline (total transfer time >> send_timeout_s)
        fa.send({"t": "big", "blob": os.urandom(1 << 20)})
    finally:
        stop.set()
        fa.close()
        b.close()
        t.join(timeout=5.0)


def test_recv_timeout_is_total_not_per_fill():
    """One recv deadline covers header AND body: a frame stuck mid-body
    must not double the caller's wait."""
    a, b = socket.socketpair()
    fb = FrameSocket(b)
    framed = _ctrl_frame({"t": "x"})
    a.sendall(framed[:5])  # length prefix + 1 body byte
    t0 = time.monotonic()
    assert fb.recv(timeout=0.3) is None
    assert time.monotonic() - t0 < 0.55
    a.close()
    fb.close()


def test_auth_token_gates_every_hello():
    """A dispatcher with a handshake secret refuses untokened/wrong-token
    workers, clients, and stats probes - and serves matching ones."""
    disp = Dispatcher(telemetry=Telemetry(), auth_token="s3cret").start()
    addr = f"127.0.0.1:{disp.port}"
    try:
        # untokened worker: registration refused (exit code 1, no state)
        assert ServiceWorker(addr, capacity=1, auth_token=None).run() == 1
        assert disp.stats()["workers"] == {}
        # wrong-token client: hello refused
        ex = ServiceExecutor(addr, telemetry=Telemetry(), auth_token="nope")
        with pytest.raises(OSError, match="refused"):
            ex.start(EchoFactory())
        # untokened stats probe: error frame, no snapshot
        probe = connect_frames(parse_address(addr))
        probe.send({"t": "stats?"})
        assert probe.recv(timeout=5.0)["t"] == "error"
        probe.close()
        assert disp.stats()["counters"].get(
            "service.auth_rejected", 0) >= 3
        # matching tokens: full roundtrip works
        worker = ServiceWorker(addr, capacity=2, auth_token="s3cret")
        wt = threading.Thread(target=worker.run, daemon=True)
        wt.start()
        _wait_for(lambda: len(disp.stats()["workers"]) == 1)
        ex = ServiceExecutor(addr, telemetry=Telemetry(), window=4,
                             auth_token="s3cret")
        ex.start(EchoFactory())
        ex.put(VentilatedItem(0, "payload"))
        assert ex.get(timeout=10.0) == ("echo", "payload", 0)
        ex.stop()
        ex.join()
        worker.stop()
    finally:
        disp.stop()
        disp.join()


def test_auth_token_env_var(monkeypatch):
    """$PETASTORM_TPU_SERVICE_TOKEN is the zero-plumbing path: every party
    resolves it by default."""
    from petastorm_tpu.service.protocol import resolve_auth_token

    monkeypatch.delenv("PETASTORM_TPU_SERVICE_TOKEN", raising=False)
    assert resolve_auth_token(None) is None
    assert resolve_auth_token("x") == "x"
    monkeypatch.setenv("PETASTORM_TPU_SERVICE_TOKEN", "tok")
    assert resolve_auth_token(None) == "tok"
    assert resolve_auth_token("explicit") == "explicit"
    disp = Dispatcher(telemetry=Telemetry()).start()
    addr = f"127.0.0.1:{disp.port}"
    try:
        worker = ServiceWorker(addr, capacity=1)  # token from env
        wt = threading.Thread(target=worker.run, daemon=True)
        wt.start()
        _wait_for(lambda: len(disp.stats()["workers"]) == 1)
        worker.stop()
        # a party that missed the env var is refused
        monkeypatch.delenv("PETASTORM_TPU_SERVICE_TOKEN")
        assert ServiceWorker(addr, capacity=1).run() == 1
    finally:
        disp.stop()
        disp.join()


def test_pick_worker_affinity_is_deterministic():
    """Rowgroup affinity must survive hash randomization and load churn:
    the same rowgroup maps to the same worker independent of the momentary
    free list, falling back only when the affine worker is saturated."""
    import zlib

    import types

    rg = types.SimpleNamespace(path="/data/part-0.parquet", row_group=7)
    work = types.SimpleNamespace(row_group=rg)
    disp = Dispatcher(telemetry=Telemetry())  # never started: pure routing
    a, b = socket.socketpair()
    conn = FrameSocket(a)
    from petastorm_tpu.service.dispatcher import _WorkerState
    workers = {n: _WorkerState(n, conn, 2, "h") for n in ("w1", "w2", "w3")}
    disp._workers = workers
    item = VentilatedItem(0, work)
    key = zlib.crc32(b"/data/part-0.parquet:7")
    expected = workers[sorted(workers)[key % 3]]
    free = list(workers.values())
    for _ in range(5):  # stable across repeated picks and free-list orders
        assert disp._pick_worker(item, free) is expected
        free = free[1:] + free[:1]
    # the wire plane's structural affinity key routes IDENTICALLY to the
    # in-process object path (the dispatcher never opens the item blob)
    wire_item = WireItem(0, 0, b"opaque", ["/data/part-0.parquet", 7])
    assert disp._pick_worker(wire_item, list(workers.values())) is expected
    # saturated affine worker -> least-loaded fallback, not a re-route of
    # the whole mapping
    others = [w for w in workers.values() if w is not expected]
    others[0].inflight.add(("c", 1))
    assert disp._pick_worker(
        item, others) is others[1]
    conn.close()
    b.close()


def test_parse_address():
    assert parse_address("host:123") == ("host", 123)
    assert parse_address(("h", 9)) == ("h", 9)
    assert parse_address(":123") == ("127.0.0.1", 123)
    with pytest.raises(PetastormTpuError):
        parse_address("no-port")


def _result_msg(header, parts):
    """Round one encoded result through a socketpair, as the client's
    receiver would see it (BATCH frame -> header dict + '_body')."""
    a, b = socket.socketpair()
    fa, fb = FrameSocket(a), FrameSocket(b)
    try:
        fa.send_batch(dict(header, t="result"), parts)
        return fb.recv(timeout=2.0)
    finally:
        fa.close()
        fb.close()


def test_payload_binary_roundtrip():
    """ColumnBatch results travel as schema'd binary frames - zero pickle -
    and decode to WRITABLE numpy views over the received buffer."""
    batch = ColumnBatch({"x": np.arange(5), "img": np.arange(30, dtype=np.uint8)
                         .reshape(5, 3, 2)}, 5, ordinal=7)
    header, parts = encode_result(batch, arena=None)
    assert header["pk"] == "bin"
    msg = _result_msg(header, parts)
    out = PayloadDecoder().decode(msg)
    np.testing.assert_array_equal(out.columns["x"], np.arange(5))
    np.testing.assert_array_equal(out.columns["img"], batch.columns["img"])
    assert out.ordinal == 7
    # consumers mutate batches in place (torch normalize etc.): the
    # zero-copy views must be writable or every batch pays a copy downstream
    assert out.columns["x"].flags.writeable
    out.columns["x"][0] = 99


def test_payload_object_columns_ride_inline_binary():
    """Object-dtype columns (strings/bytes/ragged arrays) stay on the
    binary plane via the control codec's inline path."""
    strs = np.empty(3, dtype=object)
    strs[:] = ["a", "bb", "ccc"]
    ragged = np.empty(3, dtype=object)
    ragged[:] = [np.arange(i + 1) for i in range(3)]
    batch = ColumnBatch({"s": strs, "r": ragged, "x": np.arange(3)}, 3)
    header, parts = encode_result(batch)
    assert header["pk"] == "bin"
    out = PayloadDecoder().decode(_result_msg(header, parts))
    assert list(out.columns["s"]) == ["a", "bb", "ccc"]
    np.testing.assert_array_equal(out.columns["r"][2], np.arange(3))


def test_payload_pickle_fallback_is_counted_and_gated():
    """Results outside the wire domain fall back to pickle (pk='pickle');
    a client refusing pickle gets a classified WireFormatError, never an
    unpickle."""
    header, parts = encode_result(("echo", "payload", 3))
    assert header["pk"] == "pickle"
    msg = _result_msg(header, parts)
    assert PayloadDecoder().decode(msg) == ("echo", "payload", 3)
    with pytest.raises(WireFormatError, match="refuses"):
        PayloadDecoder(allow_pickle=False).decode(msg)


def test_payload_compression_roundtrip():
    """A zlib-coded batch body decodes identically (end-to-end: the
    dispatcher never touches it)."""
    batch = ColumnBatch({"x": np.zeros((64, 128), dtype=np.uint8)}, 64)
    header, parts = encode_result(batch, codec="zlib")
    assert header["pk"] == "bin" and header["codec"] == "zlib"
    assert sum(len(p) for p in parts) < batch.columns["x"].nbytes  # it DID
    out = PayloadDecoder().decode(_result_msg(header, parts))
    np.testing.assert_array_equal(out.columns["x"], batch.columns["x"])
    # a corrupted compressed body is a classified failure, not a zlib crash
    msg = _result_msg(header, [b"\x00garbage"])
    with pytest.raises(WireFormatError, match="corrupt|bytes"):
        PayloadDecoder().decode(msg)


# -- wire robustness: corrupt/hostile frames ----------------------------------

def test_wire_control_codec_roundtrip():
    values = [None, True, False, 0, -(2 ** 62), 3.5, "héllo", b"\x00\xff",
              [1, [2, [3]]], {"a": {"b": [None, "x"]}},
              np.arange(6, dtype=np.float32).reshape(2, 3)]
    for v in values:
        out = wire.loads(wire.dumps(v))
        if isinstance(v, np.ndarray):
            np.testing.assert_array_equal(out, v)
        else:
            assert out == v, (v, out)
    with pytest.raises(WireFormatError, match="not wire-encodable"):
        wire.dumps(object())
    with pytest.raises(WireFormatError, match="64-bit"):
        wire.dumps(2 ** 70)


def test_wire_rejects_truncated_and_trailing():
    blob = wire.dumps({"k": [1, 2, 3]})
    with pytest.raises(WireFormatError, match="truncated"):
        wire.loads(blob[:-2])
    with pytest.raises(WireFormatError, match="trailing"):
        wire.loads(blob + b"\x00")
    # a list claiming 2^20+ items on 4 bytes of input: bounds, not OOM
    bomb = struct.pack("!BI", 0x07, (1 << 20) + 1)
    with pytest.raises(WireFormatError, match="claims"):
        wire.loads(bomb)
    # an object array claiming 2^29 elements in a 6-byte frame must be
    # bounded BEFORE allocation (np.empty of the pointer array alone
    # would be 4GB - the allocation-bomb shape of the same attack)
    obj_bomb = struct.pack("!BBI", 0x0A, 1, 1 << 29)
    with pytest.raises(WireFormatError, match="claims"):
        wire.loads(obj_bomb)
    # deep nesting is cut off, not a RecursionError
    deep = b"\x07\x00\x00\x00\x01" * 64 + wire.dumps(None)
    with pytest.raises(WireFormatError, match="nests deeper"):
        wire.loads(deep)


def test_frame_socket_rejects_unknown_and_legacy_kinds():
    """Unknown frame kinds and v1 pickled frames are refused as classified
    errors - the pickled frame is DETECTED (first byte), never loaded."""
    a, b = socket.socketpair()
    fb = FrameSocket(b)
    # unknown kind byte
    a.sendall(struct.pack("!I", 1) + b"\x7f")
    with pytest.raises(WireFormatError, match="unknown frame kind"):
        fb.recv(timeout=2.0)
    # a legacy pickled frame: the payload would RCE if anyone loaded it;
    # detection must classify it without executing anything
    evil = pickle.dumps({"t": "client_hello"})
    assert evil[0] == wire.PICKLE_PROTO_BYTE
    a.sendall(struct.pack("!I", len(evil)) + evil)
    with pytest.raises(LegacyPickleFrameError, match="v1 pickled"):
        fb.recv(timeout=2.0)
    # the stream itself stays synced: a good frame after the bad ones parses
    a.sendall(_ctrl_frame({"t": "ok"}))
    assert fb.recv(timeout=2.0) == {"t": "ok"}
    a.close()
    fb.close()


def test_batch_frame_spec_validation():
    """Every header/buffer disagreement is a classified WireFormatError:
    wrong lengths, out-of-bounds offsets, object dtypes, oversize column
    tables - never a numpy crash or an unpickle."""
    body = bytearray(np.arange(10, dtype=np.int64).tobytes())

    def decode(cols, rows=10, blen=None):
        header = {"pk": "bin", "rows": rows, "cols": cols,
                  "blen": len(body) if blen is None else blen, "codec": ""}
        return wire.decode_batch_body(header, memoryview(body))

    ok = decode({"x": ["raw", "<i8", [10], 0, 80]})
    np.testing.assert_array_equal(ok.columns["x"], np.arange(10))
    with pytest.raises(WireFormatError, match="needs"):
        decode({"x": ["raw", "<i8", [10], 0, 64]})  # nbytes vs dtype*shape
    with pytest.raises(WireFormatError, match="outside"):
        decode({"x": ["raw", "<i8", [10], 64, 80]})  # overruns the body
    with pytest.raises(WireFormatError, match="object dtypes"):
        decode({"x": ["raw", "|O", [10], 0, 80]})  # unpickle in disguise
    with pytest.raises(WireFormatError, match="bad wire dtype"):
        decode({"x": ["raw", "not-a-dtype", [10], 0, 80]})
    with pytest.raises(WireFormatError, match="rows"):
        decode({"x": ["raw", "<i8", [10], 0, 80]}, rows=7)  # len disagreement
    with pytest.raises(WireFormatError, match="body is"):
        decode({"x": ["raw", "<i8", [10], 0, 80]}, blen=79)
    with pytest.raises(WireFormatError, match="implausibly large"):
        decode({"x": ["raw", "<i8", [1 << 30, 1 << 30], 0, 80]})
    with pytest.raises(WireFormatError, match="oversize"):
        decode({f"c{i}": ["inline", None] for i in range(5000)})
    with pytest.raises(WireFormatError, match="unknown spec kind"):
        decode({"x": ["mystery", 1]})


def test_legacy_v1_client_is_refused_loudly():
    """A v1 (pickled-wire) client hello gets a v1-READABLE error frame and
    a closed connection - a loud version mismatch, not a hang or desync."""
    disp = Dispatcher(telemetry=Telemetry()).start()
    try:
        sock = socket.create_connection(("127.0.0.1", disp.port), timeout=5)
        evil = pickle.dumps({"t": "client_hello", "protocol": 1})
        sock.sendall(struct.pack("!I", len(evil)) + evil)
        # the reply is a pickled error dict (the one format v1 peers read)
        (length,) = struct.unpack("!I", _recv_exact(sock, 4))
        reply = pickle.loads(_recv_exact(sock, length))
        assert reply["t"] == "error"
        assert "protocol version mismatch" in reply["error"]
        assert _recv_exact(sock, 1) == b""  # then EOF: connection closed
        sock.close()
    finally:
        disp.stop()
        disp.join()


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return buf
        buf += chunk
    return buf


def test_corrupt_result_is_classified_failure_not_desync(fleet):
    """A result the client cannot decode (here: the pickle fallback with
    pickle refused) surfaces as a classified WorkerError per ordinal while
    the stream keeps flowing - no desync, no unpickle attempt - and is
    still ACKED (a refused outcome must not pin the dispatcher's
    redelivery buffer / replay forever)."""
    disp, addr, _workers = fleet
    ex = ServiceExecutor(addr, telemetry=Telemetry(), window=4,
                         stop_on_failure=False, allow_pickle_results=False)
    ex.start(EchoFactory())
    for i in range(3):
        ex.put(VentilatedItem(i, f"p{i}"))
    failures = 0
    for _ in range(3):
        with pytest.raises(WorkerError, match="refuses"):
            ex.get(timeout=15.0)
        failures += 1
    assert failures == 3  # every ordinal individually classified
    _wait_for(lambda: all(c["unacked"] == 0
                          for c in disp.stats()["clients"].values()),
              what="refused results acked (redelivery buffer freed)")
    ex.stop()
    ex.join()


# -- wire negotiation / encoding mix ------------------------------------------

def test_negotiate_codec_policy():
    codecs = ("zlib",)
    # auto: compress only cross-host hops both ends support
    assert wire.negotiate_codec("auto", True, codecs, codecs) == ""
    assert wire.negotiate_codec("auto", False, codecs, codecs) == "zlib"
    assert wire.negotiate_codec("auto", False, (), codecs) == ""
    assert wire.negotiate_codec("auto", False, codecs, ()) == ""
    # off: never; forced: wherever both ends support it
    assert wire.negotiate_codec("off", False, codecs, codecs) == ""
    assert wire.negotiate_codec("zlib", True, codecs, codecs) == "zlib"
    assert wire.negotiate_codec("zlib", True, (), codecs) == ""


def test_binary_wire_counters_and_shm_diagnostics(int_dataset, fleet):
    """The e2e result path is pickle-free for real reads: every batch is a
    binary frame (client AND dispatcher meter the mix), the per-direction
    decode stage records, and the reader surfaces which shm transport path
    this runtime can negotiate - and why not."""
    disp, addr, _workers = fleet
    rows, diag, tele = _read_all(int_dataset, addr)
    assert rows == list(range(200))
    c = tele.snapshot()["counters"]
    assert c["service.frames_binary"] == 20
    assert c.get("service.frames_pickle_fallback", 0) == 0
    assert "stage.service.decode.busy_s" in c
    dc = disp.stats()["counters"]
    assert dc["service.frames_binary"] >= 20
    assert dc.get("service.frames_pickle_fallback", 0) == 0
    # the zero-copy transport is live on this installation (python 3.12,
    # the native library builds from git)
    assert diag["native"]["shm_transport"] == {"available": True,
                                               "reason": "ok"}


def test_pickle_fallback_is_metered(fleet):
    """Non-ColumnBatch worker results (the echo factory's tuples) take the
    counted pickle fallback - visible, never silent."""
    _disp, addr, _workers = fleet
    tele = Telemetry()
    ex = ServiceExecutor(addr, telemetry=tele, window=4)
    ex.start(EchoFactory())
    for i in range(4):
        ex.put(VentilatedItem(i, f"p{i}"))
    got = sorted(ex.get(timeout=10.0) for _ in range(4))
    assert got == [("echo", f"p{i}", i) for i in range(4)]
    c = tele.snapshot()["counters"]
    assert c["service.frames_pickle_fallback"] == 4
    assert c.get("service.frames_binary", 0) == 0
    ex.stop()
    ex.join()


def test_forced_compression_end_to_end(int_dataset):
    """wire_codec='zlib' forces BATCH-body compression even on one host;
    the stream stays exact and the client meters compressed frames."""
    disp = Dispatcher(telemetry=Telemetry(), wire_codec="zlib").start()
    addr = f"127.0.0.1:{disp.port}"
    workers = [ServiceWorker(addr, capacity=2, name=f"wz{i}")
               for i in range(2)]
    for w in workers:
        threading.Thread(target=w.run, daemon=True).start()
    try:
        _wait_for(lambda: len(disp.stats()["workers"]) == 2,
                  what="worker registration")
        rows, _diag, tele = _read_all(int_dataset, addr)
        assert rows == list(range(200))
        c = tele.snapshot()["counters"]
        assert c["service.frames_binary"] == 20
        assert c["service.frames_compressed"] == 20
    finally:
        for w in workers:
            w.stop()
        disp.stop()
        disp.join()


def test_wire_codec_knob_validation():
    with pytest.raises(PetastormTpuError, match="wire_codec"):
        Dispatcher(telemetry=Telemetry(), wire_codec="snappy")


def test_client_hello_logs_negotiated_wire(fleet, caplog):
    """Satellite: the hello log states which data plane was negotiated and
    WHY the shm fast path is (un)available on this runtime."""
    import logging

    _disp, addr, _workers = fleet
    with caplog.at_level(logging.INFO, logger="petastorm_tpu.service.client"):
        ex = ServiceExecutor(addr, telemetry=Telemetry(), window=2)
        ex.start(EchoFactory())
    lines = [r.getMessage() for r in caplog.records
             if "service wire negotiated" in r.getMessage()]
    assert lines, caplog.records
    assert "binary v2 frames" in lines[0]
    if shm_transport_available():
        assert "shm fast path available" in lines[0]
    else:
        assert "unavailable (python" in lines[0] \
            or "unavailable (native" in lines[0]
    ex.stop()
    ex.join()


_SHM_DARK = not shm_transport_available()


@pytest.mark.skipif(
    _SHM_DARK and not os.environ.get("PETASTORM_TPU_REQUIRE_ARENA"),
    reason="shm transport plane unavailable (python >= 3.12 + native lib)")
def test_shm_fast_path_end_to_end(int_dataset):
    """Co-located client+worker negotiate the shm arena: batches cross the
    socket as descriptors only (pk='shm'), counted on both ends.

    Under PETASTORM_TPU_REQUIRE_ARENA=1 (the py3.12 CI jobs) this test
    RUNS unconditionally, so a silently-broken arena plane fails loudly
    instead of skipping - the fast path can never go dark unnoticed again.
    """
    disp = Dispatcher(telemetry=Telemetry()).start()
    addr = f"127.0.0.1:{disp.port}"
    workers = [ServiceWorker(addr, capacity=2, name=f"ws{i}",
                             shm_size_bytes=64 * 2 ** 20) for i in range(2)]
    for w in workers:
        threading.Thread(target=w.run, daemon=True).start()
    try:
        _wait_for(lambda: len(disp.stats()["workers"]) == 2,
                  what="worker registration")
        rows, diag, tele = _read_all(int_dataset, addr)
        assert rows == list(range(200))
        c = tele.snapshot()["counters"]
        assert c["service.frames_shm"] == 20, c
        assert c.get("service.frames_pickle_fallback", 0) == 0
        assert disp.stats()["counters"]["service.frames_shm"] >= 20
        assert diag["native"]["shm_transport"]["available"] is True
    finally:
        for w in workers:
            w.stop()
        disp.stop()
        disp.join()


# -- client executor unit behavior -------------------------------------------

def test_client_executor_requires_picklable_factory(fleet):
    _disp, addr, _workers = fleet
    ex = ServiceExecutor(addr, telemetry=Telemetry())
    with pytest.raises(PetastormTpuError, match="picklable"):
        ex.start(lambda: (lambda item: item))  # lambdas don't pickle
    ex.stop()
    ex.join()


def test_service_executor_roundtrip_plain(fleet):
    """The raw ExecutorBase protocol over the wire: put N, get N."""
    _disp, addr, _workers = fleet
    # window >= items: put and get run on one thread here (a real reader
    # ventilates from a separate thread, so the window can backpressure)
    ex = ServiceExecutor(addr, telemetry=Telemetry(), window=8)
    ex.start(EchoFactory())
    for i in range(8):
        ex.put(VentilatedItem(i, f"payload-{i}"))
    got = sorted(ex.get(timeout=10.0) for _ in range(8))
    assert got == [("echo", f"payload-{i}", i) for i in range(8)]
    ex.stop()
    ex.join()


# -- multi-client e2e ---------------------------------------------------------

def test_two_clients_exact_multisets(int_dataset, fleet):
    """Acceptance core: two make_reader(service_address=...) clients on one
    dataset each receive their exact expected row multiset."""
    _disp, addr, _workers = fleet
    out = {}

    def read(tag, epochs):
        out[tag] = _read_all(int_dataset, addr, num_epochs=epochs)[0]

    threads = [threading.Thread(target=read, args=("a", 1)),
               threading.Thread(target=read, args=("b", 2))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert out["a"] == list(range(200))
    assert out["b"] == sorted(list(range(200)) * 2)


def test_fleet_decodes_each_rowgroup_once_shared_tier(int_dataset, fleet,
                                                      tmp_path):
    """Decode-once sharing: with the host-wide warm tier, the second client
    is served entirely from cache - each rowgroup decoded exactly once
    fleet-wide (sequential clients make the accounting exact; concurrent
    clients can race a handful of duplicate decodes, by design)."""
    disp, addr, _workers = fleet
    loc = str(tmp_path / f"tier_{uuid.uuid4().hex[:8]}")
    rows_a, diag_a, _ = _read_all(int_dataset, addr, cache_type="shared",
                                  cache_location=loc)
    rows_b, diag_b, _ = _read_all(int_dataset, addr, cache_type="shared",
                                  cache_location=loc)
    assert rows_a == list(range(200))
    assert rows_b == list(range(200))
    stats = diag_b["cache"]
    # 20 rowgroups: every one decoded exactly once (client A's epoch),
    # client B fully tier-served (L1 hits, or L2 after an L1 eviction)
    assert stats["misses"] == 20, stats
    assert stats["hits"] + stats["l2_hits"] >= 20, stats
    # the fleet-side proof rides the dispatcher registry via worker
    # heartbeats: both clients' items were processed by the fleet
    _wait_for(lambda: disp.stats()["counters"].get(
        "service.fleet.worker.rowgroups_decoded", 0) >= 40,
        timeout=10.0, what="fleet heartbeat counters")


def test_shuffled_epochs_and_resume_cursor(int_dataset, fleet):
    """The deterministic plan plane is untouched by the service hop:
    shuffled epochs deliver exact multisets and the resume cursor restarts
    mid-stream exactly like a local pool's."""
    _disp, addr, _workers = fleet
    tele = Telemetry()
    with make_batch_reader(int_dataset, service_address=addr,
                           shuffle_row_groups=True, shuffle_seed=7,
                           telemetry=tele) as reader:
        it = reader.iter_batches()
        consumed = []
        for _ in range(6):
            consumed.extend(next(it).columns["x"])
        reader.quiesce()
        consumed.extend(x for b in it for x in b.columns["x"])
        state = reader.state_dict()
    assert state["ordinal_exact"]
    with make_batch_reader(int_dataset, service_address=addr,
                           shuffle_row_groups=True, shuffle_seed=7,
                           resume_from=state) as reader:
        rest = [x for b in reader.iter_batches() for x in b.columns["x"]]
    assert sorted(consumed + rest) == list(range(200))


def test_on_error_skip_quarantines_data_failures(int_dataset, fleet):
    """A poisoned rowgroup surfaces as a classified data failure across the
    wire and the reader's skip policy quarantines it - service and local
    pools share the on_error contract."""
    _disp, addr, _workers = fleet
    from petastorm_tpu.test_util.chaos import ChaosSpec

    rows, diag, tele = _read_all(int_dataset, addr, on_error="skip",
                                 chaos=ChaosSpec(decode_fail_ordinals=(3,)))
    assert rows == sorted(set(range(200)) - set(range(30, 40)))
    assert diag["skipped_rowgroups"] == 1
    assert diag["quarantined_rowgroups"][0]["ordinal"] == 3
    assert tele.snapshot()["counters"]["errors.skipped_rowgroups"] == 1


# -- chaos on the service plane ----------------------------------------------

def _spawn_worker_proc(addr, name, capacity=2):
    return subprocess.Popen(
        [sys.executable, "-m", "petastorm_tpu.service.cli", "worker",
         "--address", addr, "--capacity", str(capacity), "--name", name],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


@pytest.mark.slow
def test_chaos_worker_sigkill_mid_epoch(int_dataset):
    """Acceptance chaos: SIGKILL one remote worker holding in-flight items;
    both clients still see their exact row multiset and
    service.requeued_items accounts for the kill."""
    disp = Dispatcher(telemetry=Telemetry(), heartbeat_timeout_s=5.0).start()
    addr = f"127.0.0.1:{disp.port}"
    procs = [_spawn_worker_proc(addr, f"w{i}") for i in range(2)]
    try:
        _wait_for(lambda: len(disp.stats()["workers"]) == 2, timeout=30.0,
                  what="worker registration")
        out = {}

        def read(tag):
            out[tag] = _read_all(int_dataset, addr)[0:2]

        threads = [threading.Thread(target=read, args=(t,))
                   for t in ("a", "b")]
        for t in threads:
            t.start()
        _wait_for(lambda: disp.stats()["workers"].get(
            "w0", {}).get("inflight", 0) > 0, timeout=30.0,
            what="w0 holding in-flight work")
        os.kill(procs[0].pid, signal.SIGKILL)
        for t in threads:
            t.join(timeout=120)
        assert out["a"][0] == list(range(200))
        assert out["b"][0] == list(range(200))
        counters = disp.stats()["counters"]
        assert counters.get("service.requeued_items", 0) >= 1
        # the kill is visible client-side too (requeued notices)
        assert (out["a"][1]["requeued_items"]
                + out["b"][1]["requeued_items"]) >= 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        disp.stop()
        disp.join()


def test_chaos_client_connection_drop(int_dataset, fleet):
    """Yank the client's TCP connection mid-epoch: the executor reconnects
    with backoff, the dispatcher replays unacked results, the ledger dedups,
    and the epoch completes with the exact multiset."""
    _disp, addr, _workers = fleet
    tele = Telemetry()
    reader = make_batch_reader(int_dataset, service_address=addr,
                               shuffle_row_groups=False, telemetry=tele)
    reader._executor._reconnect_policy = FAST_RECONNECT
    rows = []
    for i, b in enumerate(reader.iter_batches()):
        rows.extend(b.columns["x"])
        if i == 4:
            reader._executor._conn._sock.shutdown(socket.SHUT_RDWR)
    diag = reader.diagnostics
    reader.stop()
    reader.join()
    assert sorted(rows) == list(range(200))
    assert diag["reconnects"] >= 1
    assert tele.snapshot()["counters"]["service.reconnects"] >= 1


def test_chaos_simulated_worker_kill_via_chaos_spec(int_dataset, fleet):
    """The chaos harness's kill injection rides the pickled factory to the
    fleet: in-process test workers treat it like a real death only when they
    are processes, so here we assert the dispatcher requeue path triggers
    via a dropped worker instead (worker.stop mid-epoch)."""
    disp, addr, workers = fleet
    tele = Telemetry()
    reader = make_batch_reader(int_dataset, service_address=addr,
                               shuffle_row_groups=False, telemetry=tele)
    rows = []
    stopped = False
    for b in reader.iter_batches():
        rows.extend(b.columns["x"])
        if not stopped and len(rows) >= 30:
            stopped = True
            workers[0].stop()  # drops its connection; in-flight requeues
    diag = reader.diagnostics
    reader.stop()
    reader.join()
    assert sorted(rows) == list(range(200))
    assert disp.stats()["counters"].get("service.requeued_items", 0) >= 0
    assert diag["consumed"] == 20


def test_assignment_deadline_drops_hung_worker(int_dataset):
    """Liveness backstop: a worker wedged inside user code (still
    heartbeating) is dropped once its assignment exceeds the deadline, and
    the requeued attempt completes on a fresh worker."""
    disp = Dispatcher(telemetry=Telemetry(), assignment_deadline_s=1.0).start()
    addr = f"127.0.0.1:{disp.port}"
    workers = [ServiceWorker(addr, capacity=1, name=f"w{i}")
               for i in range(2)]
    for w in workers:
        threading.Thread(target=w.run, daemon=True).start()
    _wait_for(lambda: len(disp.stats()["workers"]) == 2,
              what="worker registration")
    ex = ServiceExecutor(addr, telemetry=Telemetry(), window=2)
    ex.start(HangFirstAttemptFactory())
    try:
        ex.put(VentilatedItem(0, "wedge-me"))
        assert ex.get(timeout=30.0) == ("recovered", 0)
        counters = disp.stats()["counters"]
        assert counters.get("service.hung_workers_dropped", 0) >= 1, counters
        assert counters.get("service.requeued_items", 0) >= 1, counters
    finally:
        ex.stop()
        ex.join()
        for w in workers:
            w.stop()
        disp.stop()
        disp.join()


def test_unpicklable_result_surfaces_as_failure_not_hang(fleet):
    """A transform output pickle cannot serialize must come back as a
    classified data failure, not a silently-dead processor thread."""
    _disp, addr, _workers = fleet
    ex = ServiceExecutor(addr, telemetry=Telemetry(), window=2)
    ex.start(UnpicklableResultFactory())
    ex.put(VentilatedItem(0, "unpicklable"))
    with pytest.raises(WorkerError, match="TypeError|pickle|cannot"):
        ex.get(timeout=30.0)
    ex.join()


def test_dispatcher_loss_raises_classified_error(int_dataset):
    """Graceful client degrade: a lost dispatcher (reconnect window
    exhausted) raises a classified infrastructure WorkerError carrying
    .diagnostics instead of hanging the epoch."""
    disp = Dispatcher(telemetry=Telemetry()).start()
    addr = f"127.0.0.1:{disp.port}"
    worker = ServiceWorker(addr, capacity=2)
    threading.Thread(target=worker.run, daemon=True).start()
    reader = make_batch_reader(int_dataset, service_address=addr,
                               shuffle_row_groups=False)
    reader._executor._reconnect_policy = FAST_RECONNECT
    with pytest.raises(ServiceConnectionError) as info:
        for i, _b in enumerate(reader.iter_batches()):
            if i == 2:
                disp.stop()
    assert info.value.kind == "infra"
    assert info.value.diagnostics["service_address"] == addr
    assert info.value.diagnostics["connected"] is False
    reader.stop()
    reader.join()
    worker.stop()
    disp.join()


def test_requeue_budget_exhaustion_surfaces_worker_error(fleet, int_dataset):
    """An item whose every attempt lands on a dying worker exhausts the
    budget and surfaces the pool-shaped infra WorkerError."""
    disp, addr, _workers = fleet
    ex = ServiceExecutor(addr, telemetry=Telemetry(), window=2,
                         max_requeue_attempts=0)
    ex.start(SleepForeverFactory())
    ex.put(VentilatedItem(0, "doomed"))
    # kill whichever fleet worker holds it (in-process workers: stop both)
    _wait_for(lambda: sum(w["inflight"]
                          for w in disp.stats()["workers"].values()) > 0,
              what="item assigned")
    for w in _workers:
        w.stop()
    with pytest.raises(WorkerError, match="requeue budget exhausted"):
        ex.get(timeout=30.0)
    assert ex._stopped  # stop_on_failure honored
    ex.join()


# -- observability / scaling --------------------------------------------------

def test_service_stage_prerendered_and_watch_line(int_dataset, fleet):
    """Satellite: a just-started service pipeline renders 'service' as
    "(no samples yet)" in pipeline_report and the watch frame, then as live
    rates once results flow."""
    from petastorm_tpu.telemetry.report import render_pipeline_report
    from petastorm_tpu.tools.diagnose import render_watch_frame

    _disp, addr, _workers = fleet
    tele = Telemetry()
    reader = make_batch_reader(int_dataset, service_address=addr,
                               shuffle_row_groups=False, telemetry=tele,
                               sample_interval_s=0.05)
    try:
        report = render_pipeline_report(tele.snapshot())
        assert "service" in report  # registered before any result
        empty_frame = render_watch_frame(
            {"dt_s": 0.1, "rates": {}, "stages": {}, "gauges":
             {"service.connected": 1.0}, "counters": {}})
        assert "service: (no samples yet)" in empty_frame
        rows = [x for b in reader.iter_batches() for x in b.columns["x"]]
        assert sorted(rows) == list(range(200))
        reader.sampler.sample_now()
        point = reader.sampler.latest()
        frame = render_watch_frame(point, reader.diagnostics)
        service_line = frame.split("service:")[1].splitlines()[0]
        assert "(no samples yet)" not in service_line
        # the wire-encoding mix rides the line: all-binary here, zero
        # pickle fallback (the satellite observable of the v2 wire)
        assert "wire bin=20" in service_line, service_line
        assert "/pkl=0" in service_line, service_line
        report = render_pipeline_report(tele.snapshot())
        assert "service" in report
    finally:
        reader.stop()
        reader.join()


def test_dispatcher_scaling_signal(int_dataset):
    """The fleet-pressure signal: starved clients + queued work with no
    capacity -> grow; an idle fleet -> shrink eligibility; busy -> ok."""
    disp = Dispatcher(telemetry=Telemetry()).start()
    addr = f"127.0.0.1:{disp.port}"
    try:
        # no workers at all, a client with pending work and starvation
        ex = ServiceExecutor(addr, telemetry=Telemetry(), window=4)
        ex.start(PlainEchoFactory())
        ex.put(VentilatedItem(0, "queued"))
        # simulate the reader's starved-consumer report
        ex._starved_s = 5.0
        ex._stats_sent_at = 0.0
        ex._maybe_send_stats()
        _wait_for(lambda: disp.scaling_signal()["pressure"] > 0,
                  what="starved report folded")
        sig = disp.scaling_signal()
        assert sig["recommendation"] == "grow", sig
        assert sig["pressure"] > sig["starved_threshold"]
        # a worker joins and drains: pressure decays toward ok/shrink
        worker = ServiceWorker(addr, capacity=2)
        threading.Thread(target=worker.run, daemon=True).start()
        assert ex.get(timeout=15.0) == "queued"
        _wait_for(lambda: disp.scaling_signal()["recommendation"]
                  in ("ok", "shrink"), timeout=15.0,
                  what="pressure decay")
        ex.stop()
        ex.join()
        worker.stop()
    finally:
        disp.stop()
        disp.join()


def test_dispatcher_stats_and_cli_stats_roundtrip(fleet, int_dataset):
    """Dispatcher stats carry fleet membership + per-client progress, and
    the stats? frame (the CLI's probe) returns the same snapshot."""
    disp, addr, _workers = fleet
    rows, _diag, _tele = _read_all(int_dataset, addr)
    assert rows == list(range(200))
    stats = disp.stats()
    assert len(stats["workers"]) == 2
    assert stats["counters"]["service.completed_items"] >= 20
    assert stats["counters"]["service.client_rows"] >= 200
    conn = connect_frames(parse_address(addr))
    try:
        conn.send({"t": "stats?"})
        reply = conn.recv(timeout=10.0)
    finally:
        conn.close()
    assert reply["t"] == "stats"
    assert reply["stats"]["workers"].keys() == stats["workers"].keys()


def test_service_reader_validation(int_dataset, fleet):
    """service_address refuses process-local caches and quietly disables
    client-side liveness/autotune knobs."""
    _disp, addr, _workers = fleet
    with pytest.raises(PetastormTpuError, match="process-local"):
        make_batch_reader(int_dataset, service_address=addr,
                          cache_type="memory")
    # liveness knobs are dropped with a warning, not fatal
    rows, diag, _ = _read_all(int_dataset, addr, item_deadline_s=5.0,
                              hedge_after_s=2.0)
    assert rows == list(range(200))
    assert diag["connected"] is True  # diagnostics captured mid-read
