#!/usr/bin/env python3
"""Record the small trace the reduction's tests read (run on the chip):

    chiprun -- python3 benchmark/tools/record_small_trace.py chiprun_out/small_tpu_v5e.xplane.pb

Four "ticks" of one jitted program (two matmuls, a tanh and the program's
flash kernel at a small shape) under ``bench.capture`` / ``bench.tick``
annotations, with a host sleep between ticks so the device has idle gaps.
"""
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    out = Path(argv[1])
    tmp = ROOT / ".bench_out" / "trace_small"
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)

    @jax.jit
    def tick(x, w, q):
        y = jnp.tanh(x @ w) @ w
        o = flash_attention(q, q[:, :, :2], q[:, :, :2], causal=True)
        return y.sum() + o.astype(jnp.float32).sum()

    x = jnp.ones((512, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    q = jnp.ones((1, 512, 8, 128), jnp.bfloat16)
    tick(x, w, q).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.capture"):
        for i in range(4):
            with jax.profiler.TraceAnnotation("bench.tick", tick=i):
                tick(x, w, q).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(tmp.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    print(f"wrote {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
