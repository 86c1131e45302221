"""REAL multi-process rendezvous + cross-process collective.

The reference tests distributed logic by spawning local processes over a
file-store rendezvous (``tests/unit/common.py:129 DistributedExec``); every
other test here uses the cheaper single-process virtual mesh.  This one is
the genuine article: two OS processes bootstrap through
``deepspeed_tpu.comm.init_distributed`` (the ``DSTPU_*`` env protocol the
launcher/runners emit), form one 4-device global CPU world, and run a
cross-process reduction.
"""
import os
import subprocess
import sys

import jax
import pytest

# Cross-process collectives on the CPU backend need a CPU collectives
# implementation (gloo) wired into the client.  jaxlib may ship the gloo
# bindings, but jax only plumbs them through where the
# ``jax_cpu_collectives_implementation`` config exists (jax >= 0.5); on
# older jax the two-process CPU world forms (bootstrap, device view,
# process-local sharding) and then any cross-process computation raises
# XlaRuntimeError "Multiprocess computations aren't implemented on the CPU
# backend".  TPU backends run multiprocess regardless, and these tests run
# there unchanged.  Same treatment as test_offload's ``needs_pinned_host``:
# probe the exact capability seam, skip with the measured reason.
_CPU_COLLECTIVES = hasattr(jax.config, "jax_cpu_collectives_implementation")
needs_cpu_multiprocess = pytest.mark.skipif(
    not _CPU_COLLECTIVES,
    reason=(
        "this jax exposes no jax_cpu_collectives_implementation config "
        "(jax " + jax.__version__ + "): the CPU client is built without "
        "gloo collectives, so cross-process CPU computations raise "
        "'Multiprocess computations aren't implemented on the CPU backend'"
    ),
)

_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
# newer jax wires gloo into the CPU client through this config; the gate
# in the test module skips the two-process collective where it is absent
if hasattr(jax.config, "jax_cpu_collectives_implementation"):
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
import numpy as np
import jax.numpy as jnp

from deepspeed_tpu.comm.comm import init_distributed

init_distributed()  # DSTPU_COORDINATOR / _NUM_PROCESSES / _PROCESS_ID env
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, len(jax.devices())

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

pid = jax.process_index()
mesh = Mesh(np.asarray(jax.devices()), ("d",))
sharding = NamedSharding(mesh, P("d"))
# each process contributes its own local shard values: proc p writes p+1
local = np.full((2,), float(pid + 1), np.float32)
arr = jax.make_array_from_process_local_data(sharding, local, (4,))
total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr)
# 1+1+2+2 = 6 on BOTH processes -> the reduction crossed the process boundary
assert float(total) == 6.0, float(total)
print(f"OK proc={pid}")
"""


_ROUTER_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")

# the DSTPU_* bootstrap must precede ANY jax computation (init_params
# below); serve_worker_main's own init_distributed call is then a no-op
from deepspeed_tpu.comm.comm import init_distributed
init_distributed()

import jax.numpy as jnp

from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import init_params
from deepspeed_tpu.serving import serve_worker_main

cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
serve_worker_main(
    params=params, cfg=cfg,
    sec=dict(max_seqs=2, num_blocks=32, block_size=8,
             prefill_buckets=[16, 32]),
)
"""


def _reference_tokens(prompt, max_new):
    """Greedy tokens from an in-proc reference engine (same seed 0 fp32
    init on the same platform -> bit-identical params)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine_v2 import build_serve_engine
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.models import get_preset
    from deepspeed_tpu.models.transformer import init_params

    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    ref = build_serve_engine(params, cfg, dict(
        max_seqs=2, num_blocks=32, block_size=8, prefill_buckets=[16, 32]))
    want = ref.generate(prompt, SamplingParams(temperature=0.0,
                                               max_new_tokens=max_new))
    ref.close()
    return want


# slow: 13 s: spawns a serving worker process and drives it over its pipes
@pytest.mark.slow
def test_two_process_router_worker_round_trip():
    """Router process + worker process over the ``DSTPU_*`` env protocol:
    the worker bootstraps through ``comm.init_distributed`` (the same env
    seam the launcher/runners emit — a real ``jax.distributed.initialize``
    with a live coordinator), serves the FRAMED stdio protocol
    (``serving/transport.py``: length prefix + version handshake + payload
    checksum), and one request round-trips token-identically to an in-proc
    reference engine.  This test's own process plays the router side of
    the pipe with a real ``FrameStream``."""
    from deepspeed_tpu.serving.transport import (
        FT_RESPONSE, FrameStream, client_handshake)

    port = 9231 + (os.getpid() % 500)
    env = dict(os.environ)
    env.update({
        "DSTPU_COORDINATOR": f"127.0.0.1:{port}",
        "DSTPU_NUM_PROCESSES": "1",
        "DSTPU_PROCESS_ID": "0",
        "JAX_PLATFORMS": "",
    })
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    proc = subprocess.Popen(
        [sys.executable, "-c", _ROUTER_WORKER], env=env,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,  # binary pipes: every byte is a frame
    )
    try:
        stream = FrameStream(rfile=proc.stdout, wfile=proc.stdin)
        identity = client_handshake(stream, "rpc", timeout=180.0)
        assert identity["block_size"] == 8, identity

        def call(rid, op):
            stream.send_json(3, rid, op)  # FT_REQUEST
            f = stream.recv_frame(timeout=180.0)
            assert f.ftype == FT_RESPONSE and f.rid == rid, (f.name, f.rid)
            return f.json()

        reply = call(1, {"op": "submit", "uid": 1, "tokens": prompt,
                         "sampling": {"temperature": 0.0,
                                      "max_new_tokens": 6}})
        assert reply["ok"] and reply["result"]["reason"] == "queued", reply
        rid = 2
        for _ in range(64):
            reply = call(rid, {"op": "tick"})
            rid += 1
            if reply["requests"].get("1", {}).get("state") == "finished":
                break
        assert reply["requests"]["1"]["state"] == "finished", reply
        popped = call(rid, {"op": "pop", "uid": 1})
        closed = call(rid + 1, {"op": "close"})
        proc.stdin.close()
        proc.wait(timeout=60)
    except Exception:
        proc.kill()
        proc.wait()
        raise
    finally:
        err = proc.stderr.read().decode(errors="replace") if proc.stderr else ""
        for s in (proc.stdout, proc.stderr):
            if s is not None:
                s.close()
    assert proc.returncode == 0, f"worker failed:\n{err[-2000:]}"
    # zero-leak audit from the worker's engine.close()
    assert closed["audit"]["blocks_in_use"] == 0, closed
    want = _reference_tokens(prompt, 6)
    assert popped["result"]["tokens"] == want, (popped, want)


# slow: 15 s: spawns two socket workers, binds ports and waits for their reaping
@pytest.mark.slow
def test_two_process_socket_round_trip_and_reap():
    """The full out-of-process spawn path: ``spawn_worker`` launches real
    worker subprocesses serving the SOCKET protocol, a ``RemoteWorker``
    (RPC client + heartbeat lease) drives one request to completion
    token-identically to the in-proc reference, teardown audits zero-leak
    — and every child is REAPED (no zombies), idempotently, including a
    worker hard-killed between health checks."""
    from deepspeed_tpu.config.config import RouterConfig
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.serving.remote import RemoteWorker, spawn_worker
    from deepspeed_tpu.serving.transport import HeartbeatMonitor

    spec = {"preset": "tiny", "seed": 0, "dtype": "float32",
            "max_seq_len": 128, "platform": "cpu",
            "sec": dict(max_seqs=2, num_blocks=32, block_size=8,
                        prefill_buckets=[16, 32])}
    env = {"JAX_PLATFORMS": "cpu"}
    handles = [spawn_worker({**spec, "worker": i}, env=env, wait_ready=False)
               for i in range(2)]
    cfg = RouterConfig(heartbeat_interval_ms=50.0, lease_ms=2000.0,
                       rpc_backoff_ms=5.0, rpc_backoff_max_ms=100.0)
    mon = HeartbeatMonitor(interval_ms=50.0, lease_ms=2000.0)
    workers = []
    try:
        for i, h in enumerate(handles):
            h.wait_ready(240.0)
            workers.append(RemoteWorker(i, h.host, h.port, mon, handle=h,
                                        config=cfg))
        mon.start()
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        w0, w1 = workers
        res = w0.try_submit(1, prompt, SamplingParams(temperature=0.0,
                                                      max_new_tokens=6))
        assert res.accepted, res
        for _ in range(64):
            w0.tick()
            view = w0.request_view(1)
            if view is not None and view.state == "finished":
                break
        assert w0.request_view(1).state == "finished"
        state, error, tokens = w0.pop_state(1)
        assert state == "finished" and error is None
        assert tokens == _reference_tokens(prompt, 6), tokens
        # graceful close: audited zero-leak teardown in the worker process
        audit = w0.close()
        assert audit is not None and audit["blocks_in_use"] == 0, audit
        assert handles[0].proc.poll() is not None  # reaped, no zombie
        # hard-kill the second worker (death between health checks), then
        # tear down through BOTH paths — idempotent, still no zombie
        handles[1].kill_process()
        w1.kill()
        w1.kill()
        assert w1.close() is None  # audit died with the process
        assert handles[1].proc.poll() is not None
    finally:
        mon.stop()
        for h in handles:
            h.reap()


# slow: 5 s: spawns two jax processes that rendezvous over a localhost port; stays out of the
# six-worker lane with the file's other two
@pytest.mark.slow
@needs_cpu_multiprocess
def test_two_process_bootstrap_and_collective(tmp_path):
    port = 9731 + (os.getpid() % 500)
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "DSTPU_COORDINATOR": f"127.0.0.1:{port}",
            "DSTPU_NUM_PROCESSES": "2",
            "DSTPU_PROCESS_ID": str(pid),
            "JAX_PLATFORMS": "",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-2000:]}"
        assert "OK proc=" in out
