"""Fault-tolerant socket transport (deepspeed_tpu/serving/transport.py):
frame fuzzing (torn / oversized / junk frames, checksum + version
mismatches — all typed, never unhandled), interleaved responses matched by
request id, exactly-once retries through the server reply cache, bounded
backoff + deadlines, heartbeat-lease expiry against a frozen worker, the
KV-handoff wire codec, and a full router-over-sockets round trip with a
DISCOVERED worker death — all host-only (stub engines, zero jax device
work), so the whole wire layer runs in the tier-1 fast lane."""
import os
import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

from deepspeed_tpu.analysis.schedviz import _stub_scheduler
from deepspeed_tpu.comm import qcomm
from deepspeed_tpu.config.config import ConfigError, RouterConfig
from deepspeed_tpu.inference.faults import FaultInjector
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.serving import transport
from deepspeed_tpu.serving.handoff import KVHandoff
from deepspeed_tpu.serving.remote import RemoteWorker
from deepspeed_tpu.serving.router import Router
from deepspeed_tpu.serving.transport import (
    FT_BLOB,
    FT_ERROR,
    FT_HELLO,
    FT_HELLO_ACK,
    FT_REQUEST,
    FT_RESPONSE,
    MAGIC,
    PROTO_VERSION,
    ChaosLink,
    ConnectionLost,
    FrameStream,
    HeartbeatMonitor,
    ProtocolError,
    RpcClient,
    RpcTimeout,
    WorkerDead,
    WorkerServer,
    decode_handoff,
    dial,
    encode_handoff,
    pack_frame,
)
from deepspeed_tpu.telemetry import Telemetry


def _pair():
    a, b = socket.socketpair()
    return FrameStream(a), FrameStream(b)


# ---------------------------------------------------------------------------
# framing: round trips and every corruption class, typed
# ---------------------------------------------------------------------------
def test_frame_roundtrip():
    a, b = _pair()
    a.send_frame(FT_REQUEST, 42, b'{"op":"x"}')
    f = b.recv_frame(timeout=2.0)
    assert (f.ftype, f.rid, f.payload) == (FT_REQUEST, 42, b'{"op":"x"}')
    assert f.json() == {"op": "x"}
    a.send_frame(FT_BLOB, 43, b"\x00\x01\x02" * 100)
    f2 = b.recv_frame(timeout=2.0)
    assert f2.ftype == FT_BLOB and len(f2.payload) == 300
    a.close(), b.close()


def test_torn_frame_is_typed_connection_lost():
    a, b = _pair()
    raw = pack_frame(FT_REQUEST, 7, b"x" * 64)
    a._sock.sendall(raw[: len(raw) // 2])  # half a frame, then death
    a.close()
    with pytest.raises(ConnectionLost) as ei:
        b.recv_frame(timeout=2.0)
    assert ei.value.torn and ei.value.transient
    b.close()


def test_clean_eof_is_not_torn():
    a, b = _pair()
    a.close()
    with pytest.raises(ConnectionLost) as ei:
        b.recv_frame(timeout=2.0)
    assert not ei.value.torn
    b.close()


@pytest.mark.parametrize("corruption", ["magic", "version", "crc", "ftype"])
def test_corrupt_frames_are_typed_protocol_errors(corruption):
    a, b = _pair()
    payload = b'{"op":"x"}'
    head = {
        "magic": struct.pack("!4sBBHQII", b"JUNK", PROTO_VERSION, FT_REQUEST,
                             0, 1, len(payload), zlib.crc32(payload)),
        "version": struct.pack("!4sBBHQII", MAGIC, 99, FT_REQUEST, 0, 1,
                               len(payload), zlib.crc32(payload)),
        "crc": struct.pack("!4sBBHQII", MAGIC, PROTO_VERSION, FT_REQUEST, 0,
                           1, len(payload), 0xDEAD),
        "ftype": struct.pack("!4sBBHQII", MAGIC, PROTO_VERSION, 200, 0, 1,
                             len(payload), zlib.crc32(payload)),
    }[corruption]
    a._sock.sendall(head + payload)
    with pytest.raises(ProtocolError):
        b.recv_frame(timeout=2.0)
    a.close(), b.close()


def test_oversized_frame_refused_both_sides():
    a, b = _pair()
    b.max_frame_bytes = 128
    with pytest.raises(ProtocolError):
        FrameStream(a._sock, max_frame_bytes=64).send_frame(
            FT_REQUEST, 1, b"x" * 65)
    # an oversized frame ON the wire is rejected from the HEADER, before
    # the receiver ever buffers the payload
    a._sock.sendall(pack_frame(FT_REQUEST, 1, b"y" * 256))
    with pytest.raises(ProtocolError) as ei:
        b.recv_frame(timeout=2.0)
    assert "oversized" in str(ei.value)
    a.close(), b.close()


def test_junk_json_payload_typed():
    a, b = _pair()
    a.send_frame(FT_REQUEST, 1, b"\xff\xfenot json")
    f = b.recv_frame(timeout=2.0)
    with pytest.raises(ProtocolError):
        f.json()
    a.close(), b.close()


def test_recv_timeout_is_typed():
    a, b = _pair()
    with pytest.raises(RpcTimeout):
        b.recv_frame(timeout=0.1)
    a.close(), b.close()


def test_mid_frame_timeout_resumes_without_desync():
    """A recv that times out MID-frame must keep the partial bytes: the
    next recv resumes the same frame instead of reading garbage from the
    middle of it (the desync would surface as a bogus ProtocolError and a
    spuriously-condemned worker)."""
    a, b = _pair()
    raw = pack_frame(FT_REQUEST, 9, b"x" * 4096)
    a._sock.sendall(raw[:100])
    with pytest.raises(RpcTimeout):
        b.recv_frame(timeout=0.15)
    a._sock.sendall(raw[100:])
    f = b.recv_frame(timeout=2.0)
    assert (f.ftype, f.rid, f.payload) == (FT_REQUEST, 9, b"x" * 4096)
    # and the stream stays frame-aligned for the NEXT message
    a.send_frame(FT_REQUEST, 10, b"y")
    assert b.recv_frame(timeout=2.0).rid == 10
    a.close(), b.close()


# ---------------------------------------------------------------------------
# handshake
# ---------------------------------------------------------------------------
def test_handshake_version_mismatch_typed():
    a, b = _pair()

    def server():
        try:
            transport.server_handshake(b, {"pid": 1}, timeout=2.0)
        except ProtocolError:
            pass

    t = threading.Thread(target=server, daemon=True)
    t.start()
    # client speaking a FUTURE protocol version gets the typed refusal
    a.send_json(FT_HELLO, 0, {"version": 99, "channel": "rpc"})
    f = a.recv_frame(timeout=2.0)
    assert f.ftype == FT_ERROR and f.json()["kind"] == "version_mismatch"
    t.join(timeout=2.0)
    a.close(), b.close()


def test_handshake_identity_round_trip():
    a, b = _pair()
    out = {}

    def server():
        out["meta"] = transport.server_handshake(
            b, {"pid": 123, "nonce": 9}, timeout=2.0)

    t = threading.Thread(target=server, daemon=True)
    t.start()
    ident = transport.client_handshake(a, "heartbeat", timeout=2.0,
                                       extra={"client_nonce": "abc"})
    t.join(timeout=2.0)
    assert ident["pid"] == 123
    assert out["meta"]["channel"] == "heartbeat"
    assert out["meta"]["client_nonce"] == "abc"
    a.close(), b.close()


# ---------------------------------------------------------------------------
# KV-handoff wire codec
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ["none", "int8"])
def test_handoff_codec_roundtrip(fmt):
    rng = np.random.default_rng(0)
    leaves = [rng.standard_normal((3, 8, 2, 4)).astype(np.float32)
              for _ in range(4)]
    payloads, wire = [], 0
    for leaf in leaves:
        q, s = qcomm.quantize_payload(leaf, fmt)
        payloads.append((q, s, leaf.shape, leaf.dtype))
        wire += qcomm.payload_wire_bytes(leaf.size, fmt,
                                         none_bytes_per_el=leaf.dtype.itemsize)
    ho = KVHandoff(uid=5, tokens=[1, 2, 3], n_ctx=2, n_pages=1, fmt=fmt,
                   payloads=payloads, wire_bytes=wire)
    meta, blobs = encode_handoff(ho)
    # the accounting that crosses the wire is EXACTLY the qcomm payload
    # arithmetic the in-proc handoff counter uses
    assert meta["wire_bytes"] == wire
    back = decode_handoff(meta, blobs)
    assert back.uid == 5 and back.tokens == [1, 2, 3] and back.fmt == fmt
    assert back.wire_bytes == wire
    for (q0, s0, sh0, dt0), (q1, s1, sh1, dt1) in zip(payloads, back.payloads):
        np.testing.assert_array_equal(q0, q1)
        assert (s0 is None) == (s1 is None)
        if s0 is not None:
            np.testing.assert_array_equal(s0, s1)
        assert tuple(sh0) == tuple(sh1) and np.dtype(dt0) == np.dtype(dt1)
        out = qcomm.dequantize_payload(q1, s1, sh1, dt1, fmt)
        ref = qcomm.dequantize_payload(q0, s0, sh0, dt0, fmt)
        np.testing.assert_array_equal(out, ref)


def test_handoff_codec_malformed_typed():
    leaf = np.ones((4, 4), np.float32)
    q, s = qcomm.quantize_payload(leaf, "int8")
    ho = KVHandoff(uid=1, tokens=[1], n_ctx=1, n_pages=1, fmt="int8",
                   payloads=[(q, s, leaf.shape, leaf.dtype)], wire_bytes=10)
    meta, blobs = encode_handoff(ho)
    with pytest.raises(ProtocolError):
        decode_handoff(meta, blobs[:-1])  # missing scales blob
    with pytest.raises(ProtocolError):
        decode_handoff(meta, blobs + [b"extra"])  # trailing blob


# ---------------------------------------------------------------------------
# a stub-engine worker server (host-only; real ServeScheduler, zero jax)
# ---------------------------------------------------------------------------
@pytest.fixture
def stub_server():
    servers = []

    def make(**serve):
        eng, _ss = _stub_scheduler(serve=serve or None)
        srv = WorkerServer(eng, identity={"worker": len(servers)})
        srv.bind()
        t = threading.Thread(target=srv.serve_socket, daemon=True)
        t.start()
        servers.append((srv, t))
        return srv

    yield make
    for srv, t in servers:
        srv.shutdown()
        t.join(timeout=5.0)


def _client(srv, **kw):
    return RpcClient(lambda: dial("127.0.0.1", srv.port, "rpc"), **kw)


def test_worker_server_submit_tick_pop(stub_server):
    srv = stub_server()
    c = _client(srv)
    reply, _ = c.call({"op": "submit", "uid": 1, "tokens": [1, 2, 3],
                       "sampling": {"max_new_tokens": 3}})
    assert reply["ok"] and reply["result"]["reason"] == "queued"
    for _ in range(8):
        reply, _ = c.call({"op": "tick"})
        if reply["requests"].get("1", {}).get("state") == "finished":
            break
    assert reply["requests"]["1"]["state"] == "finished"
    assert reply["load"]["queue_depth"] == 0
    reply, _ = c.call({"op": "pop", "uid": 1})
    assert reply["result"]["state"] == "finished"
    assert len(reply["result"]["tokens"]) == 3
    # load signals ride every reply
    assert "headroom_blocks" in reply["load"]
    c.close()


def test_unknown_op_is_typed_not_fatal(stub_server):
    srv = stub_server()
    c = _client(srv)
    reply, _ = c.call({"op": "frobnicate"})
    assert not reply["ok"] and reply["error"]["kind"] == "bad_request"
    # the worker survived and still serves
    reply, _ = c.call({"op": "stats"})
    assert not reply["ok"] or "sched" in reply  # stub engine has no .stats
    c.close()


def test_interleaved_responses_match_by_rid(stub_server):
    srv = stub_server()
    c = _client(srv)
    rids = [c.post({"op": "submit", "uid": 10 + i, "tokens": [1, 2],
                    "sampling": {"max_new_tokens": 1}}) for i in range(4)]
    # collect DELIBERATELY out of posting order: responses demux by rid
    for rid in reversed(rids):
        reply, _ = c.wait(rid)
        assert reply["ok"] and reply["result"]["reason"] == "queued"
    uids = sorted(int(u) for u in c.call({"op": "tick"})[0]["requests"])
    assert uids == [10, 11, 12, 13]
    c.close()


def test_exactly_once_retry_after_lost_response(stub_server):
    srv = stub_server()
    c = _client(srv)
    rid = c.post({"op": "submit", "uid": 77, "tokens": [1, 2, 3],
                  "sampling": {"max_new_tokens": 1}})
    # let the worker execute, then lose the connection BEFORE reading the
    # response — the retry re-sends the SAME rid and must hit the server's
    # exactly-once reply cache, not re-execute the submit
    deadline = time.monotonic() + 5.0
    while rid not in srv._replies:
        assert time.monotonic() < deadline, "server never executed the op"
        time.sleep(0.01)
    c._drop_stream()
    reply, _ = c.wait(rid)
    assert reply["ok"] and reply["result"]["reason"] == "queued"
    # exactly once: one submitted request, no duplicate_uid rejection
    assert srv.scheduler.stats["submitted"] == 1
    assert len(srv.scheduler.requests) == 1
    c.close()


def test_new_client_nonce_gets_fresh_reply_cache(stub_server):
    """Request ids are only unique PER CLIENT: a restarted client whose rid
    counter starts over must never be answered from the previous client's
    exactly-once cache."""
    srv = stub_server()
    c1 = _client(srv)
    reply, _ = c1.call({"op": "submit", "uid": 1, "tokens": [1, 2],
                        "sampling": {"max_new_tokens": 1}})  # rid 1
    assert reply["result"]["reason"] == "queued"
    c1.close()
    # a NEW client (fresh nonce, rid counter restarts at 1) sends a
    # DIFFERENT op under the same rid — it must execute, not replay
    c2 = _client(srv)
    assert c2.nonce != c1.nonce
    reply2, _ = c2.call({"op": "tick"})  # rid 1 again
    assert "requests" in reply2 and "result" not in reply2
    c2.close()


def test_conn_drop_chaos_retries_and_succeeds(stub_server):
    srv = stub_server()
    inj = FaultInjector(seed=0).arm("conn_drop", uids=[0], times=2)
    chaos = ChaosLink(inj, endpoint=0)
    c = RpcClient(lambda: dial("127.0.0.1", srv.port, "rpc", chaos=chaos),
                  backoff_ms=1.0, backoff_max_ms=5.0)
    reply, _ = c.call({"op": "submit", "uid": 5, "tokens": [1],
                       "sampling": {"max_new_tokens": 1}})
    assert reply["ok"] and inj.fired("conn_drop") == 2
    assert srv.scheduler.stats["submitted"] == 1
    c.close()


def test_partition_black_hole_then_recovery(stub_server):
    srv = stub_server()
    inj = FaultInjector(seed=0).arm("partition", uids=[0], times=1,
                                    delay_s=0.3)
    chaos = ChaosLink(inj, endpoint=0)
    c = RpcClient(lambda: dial("127.0.0.1", srv.port, "rpc", chaos=chaos),
                  backoff_ms=1.0, backoff_max_ms=5.0)
    t0 = time.monotonic()
    reply, _ = c.call({"op": "tick"}, deadline_ms=10_000)
    dt = time.monotonic() - t0
    assert reply["ok"]
    assert dt >= 0.25, f"partition window not honored ({dt:.3f}s)"
    c.close()


def test_retry_budget_exhaustion_is_worker_dead():
    def dead_dial():
        raise ConnectionLost("nobody home")

    c = RpcClient(dead_dial, max_attempts=3, backoff_ms=1.0,
                  backoff_max_ms=2.0)
    t0 = time.monotonic()
    with pytest.raises(WorkerDead):
        c.call({"op": "tick"}, deadline_ms=5_000)
    assert time.monotonic() - t0 < 2.0  # bounded backoff, not the deadline


def test_deadline_exceeded_is_worker_dead(stub_server):
    srv = stub_server()
    c = _client(srv)
    with pytest.raises(WorkerDead):
        c.wait(999_999, deadline_ms=150)  # rid that will never be answered
    c.close()


def test_abort_hook_short_circuits_wait(stub_server):
    srv = stub_server()
    c = _client(srv)
    t0 = time.monotonic()
    with pytest.raises(WorkerDead) as ei:
        c.wait(999_999, deadline_ms=60_000, abort=lambda: "lease expired")
    assert "lease expired" in str(ei.value)
    assert time.monotonic() - t0 < 1.0
    c.close()


def test_fuzz_junk_bytes_never_kill_the_worker(stub_server):
    srv = stub_server()
    rng = np.random.default_rng(0)
    for trial in range(8):
        sock = socket.create_connection(("127.0.0.1", srv.port), timeout=5.0)
        stream = FrameStream(sock)
        try:
            transport.client_handshake(stream, "rpc", timeout=5.0)
            junk = rng.integers(0, 256, rng.integers(8, 200),
                                dtype=np.uint8).tobytes()
            sock.sendall(junk)
            # the worker answers with a typed ERROR frame or just drops the
            # corrupt connection — never an unhandled exception
            try:
                f = stream.recv_frame(timeout=2.0)
                assert f.ftype == FT_ERROR, f.name
            except (ConnectionLost, RpcTimeout, ProtocolError):
                pass
        finally:
            stream.close()
    # after all that abuse a FRESH connection still serves
    c = _client(srv)
    reply, _ = c.call({"op": "tick"})
    assert reply["ok"]
    c.close()


# ---------------------------------------------------------------------------
# heartbeats: lease expiry against frozen/lossy workers
# ---------------------------------------------------------------------------
def test_heartbeat_ack_and_lease_expiry_on_freeze(stub_server):
    srv = stub_server()
    mon = HeartbeatMonitor(interval_ms=20.0, lease_ms=200.0)
    hb, _ = dial("127.0.0.1", srv.port, "heartbeat")
    mon.watch(0, hb)
    mon.start()
    try:
        deadline = time.monotonic() + 5.0
        while mon.snapshot()[0]["age_s"] > 0.5 or not mon.snapshot():
            assert time.monotonic() < deadline, "no heartbeat ack"
            time.sleep(0.02)
        assert not mon.lease_expired(0)
        # freeze the worker: acceptor + hb threads die, acks stop
        srv.shutdown()
        deadline = time.monotonic() + 5.0
        while not mon.lease_expired(0):
            assert time.monotonic() < deadline, "lease never expired"
            time.sleep(0.02)
        assert mon.lease_expired(0)  # latched
    finally:
        mon.stop()


def test_heartbeat_loss_injection_expires_live_worker(stub_server):
    srv = stub_server()
    inj = FaultInjector(seed=0).arm("heartbeat_loss", uids=[3])
    chaos = ChaosLink(inj, endpoint=3)
    mon = HeartbeatMonitor(interval_ms=20.0, lease_ms=150.0)
    hb, _ = dial("127.0.0.1", srv.port, "heartbeat", chaos=chaos)
    mon.watch(3, hb)
    mon.start()
    try:
        deadline = time.monotonic() + 5.0
        while not mon.lease_expired(3):
            assert time.monotonic() < deadline, \
                "heartbeat_loss never expired the lease"
            time.sleep(0.02)
        assert inj.fired("heartbeat_loss") > 0
    finally:
        mon.stop()


# ---------------------------------------------------------------------------
# the full loop: router over socket workers, death DISCOVERED via the lease
# ---------------------------------------------------------------------------
class _RemoteTestPool:
    """Pool shim over directly-constructed RemoteWorkers (the subprocess
    spawn path is test_multiprocess_bootstrap's, ``slow``)."""

    def __init__(self, workers, telemetry, monitor):
        self.workers = workers
        self.telemetry = telemetry
        self.monitor = monitor

    @property
    def alive(self):
        return [w for w in self.workers if w.alive]

    @property
    def decode_workers(self):
        return [w for w in self.alive if w.role == "mixed"]

    @property
    def prefill_workers(self):
        return [w for w in self.alive if w.role == "prefill"]

    def prefix_hit_rate(self):
        return 0.0

    def close(self):
        audits = [w.close() if w.alive else w.close_audit
                  for w in self.workers]
        self.monitor.stop()
        return audits


def test_router_over_sockets_discovers_death_and_replays(stub_server):
    srv0, srv1 = stub_server(), stub_server()
    cfg = RouterConfig(n_workers=2, heartbeat_interval_ms=20.0, lease_ms=200.0,
                       rpc_backoff_ms=1.0, rpc_backoff_max_ms=5.0,
                       rpc_max_attempts=3)
    mon = HeartbeatMonitor(interval_ms=cfg.heartbeat_interval_ms,
                           lease_ms=cfg.lease_ms)
    tel = Telemetry(True)
    workers = [
        RemoteWorker(i, "127.0.0.1", srv.port, mon, config=cfg)
        for i, srv in enumerate((srv0, srv1))
    ]
    mon.start()
    router = Router(_RemoteTestPool(workers, tel, mon), cfg)
    # long enough generations that the freeze below lands MID-FLIGHT
    samp = SamplingParams(temperature=0.0, max_new_tokens=24)
    prompts = {u: [u, u + 1, u + 2] for u in range(1, 7)}

    # the reference: the same stub-engine arithmetic run directly
    ref_eng, ref_ss = _stub_scheduler()
    for u, p in prompts.items():
        assert ref_ss.try_submit(u, p, samp).accepted
    ref_ss.run()
    want = {u: ref_ss.pop_result(u) for u in prompts}
    ref_eng.close()

    for u, p in prompts.items():
        assert router.try_submit(u, p, samp).accepted
    for _ in range(3):
        router.tick()
    # FREEZE worker 1 mid-flight: no injected flag anywhere — the router
    # must DISCOVER the death through the heartbeat lease and replay
    srv1.shutdown()
    out = router.run(max_ticks=4096)
    stats = dict(router.stats)
    assert stats["worker_deaths"] == 1
    assert stats["discovered_deaths"] == 1
    assert not workers[1].alive
    assert all(out[u] == ("finished", want[u]) for u in prompts), (
        "replayed results diverged from the reference")
    # zero live workers after closing: typed refusal, never a hang
    audits = router.close()
    live_audits = [a for a in audits if a is not None]
    assert live_audits and all(a["blocks_in_use"] == 0 for a in live_audits)
    res = router.try_submit(99, [1, 2], samp)
    assert res.reason == "retry_later" and "no live workers" in res.detail


def test_step_burst_op_fuses_ticks_exactly_once(stub_server):
    """The megastep wire op: one ``step_burst`` RPC runs up to n owner
    ticks (early exit on idle), and a replayed request frame after a lost
    response hits the reply cache instead of running the ticks again."""
    srv = stub_server(decode_megastep=4)
    c = _client(srv)
    reply, _ = c.call({"op": "submit", "uid": 1, "tokens": [1, 2, 3],
                       "sampling": {"max_new_tokens": 6}})
    assert reply["ok"]
    reply, _ = c.call({"op": "step_burst", "n": 4})
    assert 1 <= reply["ticks"] <= 4
    assert reply["tick_no"] == srv.scheduler.tick_no
    # lose the connection BEFORE reading the next burst's response — the
    # same-rid retry must be served from the exactly-once cache, not
    # re-tick the scheduler
    rid = c.post({"op": "step_burst", "n": 4})
    deadline = time.monotonic() + 5.0
    while rid not in srv._replies:
        assert time.monotonic() < deadline, "server never executed the op"
        time.sleep(0.01)
    tick_no = srv.scheduler.tick_no
    c._drop_stream()
    reply, _ = c.wait(rid)
    assert reply["tick_no"] == tick_no
    assert srv.scheduler.tick_no == tick_no, "burst re-executed on replay"
    # drain and pop: views carried cumulative progress the whole way
    while srv.scheduler.requests[1].state not in ("finished",):
        reply, _ = c.call({"op": "step_burst", "n": 4})
    reply, _ = c.call({"op": "pop", "uid": 1})
    assert len(reply["result"]["tokens"]) == 6
    c.close()


def test_router_megastep_death_mid_burst_replays(stub_server):
    """Router at ``decode_megastep=4`` posts ONE pipelined step_burst RPC
    per worker per megastep; a worker dying mid-burst is discovered via
    the heartbeat lease and its requests replay TOKEN-IDENTICALLY on the
    survivor (replay-from-prompt: cumulative demux never double-counts a
    half-run burst)."""
    srv0, srv1 = (stub_server(decode_megastep=4),
                  stub_server(decode_megastep=4))
    cfg = RouterConfig(n_workers=2, decode_megastep=4,
                       heartbeat_interval_ms=20.0, lease_ms=200.0,
                       rpc_backoff_ms=1.0, rpc_backoff_max_ms=5.0,
                       rpc_max_attempts=3)
    mon = HeartbeatMonitor(interval_ms=cfg.heartbeat_interval_ms,
                           lease_ms=cfg.lease_ms)
    tel = Telemetry(True)
    workers = [
        RemoteWorker(i, "127.0.0.1", srv.port, mon, config=cfg)
        for i, srv in enumerate((srv0, srv1))
    ]
    mon.start()
    router = Router(_RemoteTestPool(workers, tel, mon), cfg)
    # long generations so the freeze below lands with bursts still
    # in flight (megastep moves ~16x more tokens per router tick)
    samp = SamplingParams(temperature=0.0, max_new_tokens=96)
    prompts = {u: [u, u + 1, u + 2] for u in range(1, 7)}

    # the reference: the same stub-engine arithmetic, per-tick — megastep
    # plus replay must not change a single token
    ref_eng, ref_ss = _stub_scheduler()
    for u, p in prompts.items():
        assert ref_ss.try_submit(u, p, samp).accepted
    ref_ss.run()
    want = {u: ref_ss.pop_result(u) for u in prompts}
    ref_eng.close()

    for u, p in prompts.items():
        assert router.try_submit(u, p, samp).accepted
    fused = 0
    for _ in range(2):
        router.tick()
        fused = max([fused] + [w.last_burst_ticks for w in workers
                               if w.alive])
    # the wire really fused: some worker ran a multi-tick burst in ONE RPC
    assert fused > 1, "no step_burst RPC ever covered more than one tick"
    # FREEZE worker 1 mid-flight (mid-burst from the router's view: its
    # step_burst RPC never completes) — death is DISCOVERED via the lease
    srv1.shutdown()
    out = router.run(max_ticks=4096)
    stats = dict(router.stats)
    assert stats["worker_deaths"] == 1
    assert stats["discovered_deaths"] == 1
    assert not workers[1].alive
    assert all(out[u] == ("finished", want[u]) for u in prompts), (
        "megastep replay diverged from the per-tick reference")
    audits = router.close()
    live_audits = [a for a in audits if a is not None]
    assert live_audits and all(a["blocks_in_use"] == 0 for a in live_audits)


def test_zero_workers_fails_tracked_requests_loudly(stub_server):
    srv = stub_server()
    cfg = RouterConfig(n_workers=1, heartbeat_interval_ms=10.0, lease_ms=100.0,
                       rpc_backoff_ms=1.0, rpc_backoff_max_ms=5.0,
                       rpc_max_attempts=2, max_replays=2)
    mon = HeartbeatMonitor(interval_ms=10.0, lease_ms=100.0)
    tel = Telemetry(True)
    w = RemoteWorker(0, "127.0.0.1", srv.port, mon, config=cfg)
    mon.start()
    router = Router(_RemoteTestPool([w], tel, mon), cfg)
    samp = SamplingParams(temperature=0.0, max_new_tokens=64)
    assert router.try_submit(1, [1, 2, 3], samp).accepted
    router.tick()
    srv.shutdown()  # the only worker dies with the request in flight
    out = router.run(wait_for=[1], max_ticks=4096)
    state, toks = out[1]
    assert state == "failed" and toks == []
    assert dict(router.stats)["no_worker_refusals"] >= 0
    res = router.try_submit(2, [4, 5], samp)
    assert res.reason == "retry_later" and res.retry_after_ms is not None
    router.close()


# ---------------------------------------------------------------------------
# stdio worker hardening (the serve_worker_main contract, host-only half)
# ---------------------------------------------------------------------------
class _Duplex:
    """In-memory rfile/wfile pair for the stdio server."""

    def __init__(self, inbound: bytes):
        import io

        self._in = io.BytesIO(inbound)
        self.out = bytearray()

    def read(self, n):
        return self._in.read(n)

    def write(self, data):
        self.out.extend(data)
        return len(data)

    def flush(self):
        pass


def _stdio_frames(out: bytes):
    """Parse every frame in an output byte string."""
    frames = []
    off = 0
    while off + transport.HEADER_BYTES <= len(out):
        head = out[off:off + transport.HEADER_BYTES]
        _m, _v, ftype, _f, rid, length, _crc = struct.unpack("!4sBBHQII", head)
        payload = out[off + transport.HEADER_BYTES:
                      off + transport.HEADER_BYTES + length]
        frames.append(transport.Frame(ftype, rid, bytes(payload)))
        off += transport.HEADER_BYTES + length
    return frames


def _hello_bytes():
    return pack_frame(FT_HELLO, 0, b'{"version": %d, "channel": "rpc"}'
                      % PROTO_VERSION)


def test_stdio_junk_frame_typed_error_and_clean_shutdown():
    eng, _ss = _stub_scheduler()
    srv = WorkerServer(eng)
    stream_bytes = _hello_bytes() + b"GARBAGE-NOT-A-FRAME-AT-ALL-########"
    duplex = _Duplex(stream_bytes)
    srv.serve_stream(FrameStream(rfile=duplex, wfile=duplex))
    frames = _stdio_frames(bytes(duplex.out))
    assert frames[0].ftype == FT_HELLO_ACK
    assert frames[-1].ftype == FT_ERROR
    assert frames[-1].json()["kind"] == "protocol_error"
    # clean audited shutdown: the engine closed with zero leaked blocks
    assert srv.close_audit is not None
    assert srv.close_audit["blocks_in_use"] == 0


def test_stdio_torn_frame_typed_error_and_clean_shutdown():
    eng, _ss = _stub_scheduler()
    srv = WorkerServer(eng)
    torn = pack_frame(FT_REQUEST, 1, b'{"op":"tick"}')[:10]
    duplex = _Duplex(_hello_bytes() + torn)
    srv.serve_stream(FrameStream(rfile=duplex, wfile=duplex))
    frames = _stdio_frames(bytes(duplex.out))
    assert frames[-1].ftype == FT_ERROR
    assert frames[-1].json()["kind"] == "connection_lost"
    assert srv.close_audit is not None


def test_stdio_full_request_cycle_then_clean_eof():
    eng, _ss = _stub_scheduler()
    srv = WorkerServer(eng)
    req = {"op": "submit", "uid": 1, "tokens": [1, 2],
           "sampling": {"max_new_tokens": 2}}
    import json as _json

    inbound = _hello_bytes()
    inbound += pack_frame(FT_REQUEST, 1, _json.dumps(req).encode())
    for i in range(4):
        inbound += pack_frame(FT_REQUEST, 2 + i, b'{"op": "tick"}')
    inbound += pack_frame(FT_REQUEST, 9, b'{"op": "pop", "uid": 1}')
    inbound += pack_frame(FT_REQUEST, 10, b'{"op": "close"}')
    duplex = _Duplex(inbound)
    srv.serve_stream(FrameStream(rfile=duplex, wfile=duplex))
    frames = _stdio_frames(bytes(duplex.out))
    replies = {f.rid: f.json() for f in frames if f.ftype == FT_RESPONSE}
    assert replies[1]["result"]["reason"] == "queued"
    assert replies[9]["result"]["state"] == "finished"
    assert len(replies[9]["result"]["tokens"]) == 2
    assert replies[10]["audit"]["blocks_in_use"] == 0


def test_stdio_version_mismatch_refused_typed():
    eng, _ss = _stub_scheduler()
    srv = WorkerServer(eng)
    duplex = _Duplex(pack_frame(FT_HELLO, 0, b'{"version": 42}'))
    srv.serve_stream(FrameStream(rfile=duplex, wfile=duplex))
    frames = _stdio_frames(bytes(duplex.out))
    assert frames[0].ftype == FT_ERROR
    assert frames[0].json()["kind"] == "version_mismatch"
    assert srv.close_audit is not None


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------
def test_router_transport_config_validation():
    with pytest.raises(ConfigError):
        RouterConfig(lease_ms=10.0, heartbeat_interval_ms=20.0)
    with pytest.raises(ConfigError):
        RouterConfig(rpc_max_attempts=0)
    with pytest.raises(ConfigError):
        RouterConfig(rpc_backoff_ms=50.0, rpc_backoff_max_ms=10.0)
    with pytest.raises(ConfigError):
        RouterConfig(max_frame_bytes=16)
    RouterConfig(heartbeat_interval_ms=25.0, lease_ms=250.0)


def test_worker_launch_cmd_composes_with_multinode_runner():
    """The launcher's multinode runners are the real multi-host spawn
    path: the worker argv slots straight into get_cmd()."""
    from deepspeed_tpu.launcher.multinode_runner import get_runner
    from deepspeed_tpu.serving.remote import worker_launch_cmd

    spec = {"preset": "tiny", "seed": 0, "sec": {"max_seqs": 2}}
    argv = worker_launch_cmd(spec, python="python3")
    assert argv[:3] == ["python3", "-m", "deepspeed_tpu.serving.remote"]
    runner = get_runner("slurm", {"host-a": 1, "host-b": 1})
    cmd = runner.get_cmd(argv)
    assert cmd[0] == "srun" and "deepspeed_tpu.serving.remote" in cmd
    assert any("DSTPU_COORDINATOR" in c for c in cmd)
