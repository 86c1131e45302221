#!/usr/bin/env python3
"""Record the small trace ``xprograms``' tests read (run on the chip):

    chiprun -- python3 benchmark/tools/record_programs_trace.py chiprun_out/small_programs_tpu_v5e

writes ``<out>.xplane.pb`` and ``<out>.json``.  Three "ticks" under
``bench.capture``; each is a span tree of the program's own ``Telemetry``
(``jax_profiler`` on, so every span is mirrored into the trace with its
``span_id``): ``tick`` > ``build`` (host work), ``decode_tick`` (program
``jit_serve_step``: a matmul under scope ``mlp`` and a Pallas call named
``toy_double`` under ``attn``; fetched inside the span) and ``train_tick``
(program ``jit_train_step``: value_and_grad of an ``mlp`` layer and a
``loss``, then a clipped ``optimizer`` update).  The programs are kept tiny:
the trace file carries their whole HLO.  The JSON holds the
recorder's spans as the serving driver keeps them and
``telemetry.program_scopes()`` of the two tracked programs.
"""
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu import telemetry
    from jax.experimental import pallas as pl

    out = Path(argv[1])
    tmp = ROOT / ".bench_out" / "trace_small_programs"
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    tel = telemetry.Telemetry(enabled=True, jax_profiler=True)

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def serve_step(x, w):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("attn"):
            y = pl.pallas_call(double, out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
                               name="toy_double", interpret=jax.default_backend() != "tpu")(y)
        return y.astype(jnp.float32).sum()

    def train_step(w, x):
        def loss_fn(w):
            with jax.named_scope("mlp"):
                h = jnp.tanh(x @ w)
            with jax.named_scope("loss"):
                return jnp.mean(jnp.square(h.astype(jnp.float32)))

        with jax.named_scope("grad"):
            loss, g = jax.value_and_grad(loss_fn)(w)
        with jax.named_scope("optimizer"):
            # the clip needs the whole gradient first, so XLA cannot fold the
            # update into the backward matmul: the optimizer is its own op
            g = g.astype(jnp.float32)
            scale = jnp.minimum(1.0, jax.lax.rsqrt(jnp.sum(jnp.square(g)) + 1e-6))
            w = (w - 1e-3 * scale * g).astype(w.dtype)
        return w, loss

    serve, train = jax.jit(serve_step), jax.jit(train_step)
    tracked = [telemetry.track_program(serve), telemetry.track_program(train)]
    x = jnp.ones((512, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    serve(x, w).block_until_ready()
    tracked[0].note((x, w))
    jax.block_until_ready(train(w, x))
    tracked[1].note((w, x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # spans and annotations; the runtime's own events are in the first small trace
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.capture"):
        for i in range(3):
            with tel.span("tick", tick=i):
                with tel.span("build"):
                    time.sleep(0.0005)
                with tel.span("decode_tick", batch=1) as sp:
                    r = serve(x, w)
                    sp.dispatched()
                    np.asarray(r)
                with tel.span("train_tick"):
                    np.asarray(train(w, x)[1])
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(tmp.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out.with_suffix(".xplane.pb"))
    spans = [[ev["name"], ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6, ev["args"]]
             for ev in tel.recorder.chrome_events() if ev.get("ph") == "X"]
    out.with_suffix(".json").write_text(json.dumps(
        {"spans": spans, "scopes": telemetry.program_scopes()}, indent=0))
    for p in (out.with_suffix(".xplane.pb"), out.with_suffix(".json")):
        print(f"wrote {p} ({p.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
