"""Stand-alone curves behind ``ops/pallas/latent_prefill.py``'s tiles (a
builder's tool, run on the chip: ``chiprun -- python tools/latent_prefill_curves.py``):
ONE layer's attention of a document's chunk at ``deepseek_v2_doc_qa_sessions_closed``'s
shapes (a run of ``--queries`` queries of one sequence in a pack of 2048, 128
heads, rows of 512 + 64 on 640 lanes, pages of 128, bf16) whose last query sits
at each of ``--contexts``: 2048 is a document's chunk, 256 a question behind a
hit, 640 a document's last odd pages.

For each context it prints the ms of one call (host clock around
``block_until_ready``, median of ``--reps``; a call is 10-80 ms, a dispatch
~0.2) of the ABSORBED walk as the pack program runs it (``selected_attn``, the
causal positions its mask, the mask's build included) and of the DECOMPRESSED
kernel at each ``TQ,KP,HB,TAIL`` of ``--tiles``, alone and with the layout work the
seam does around it (``ms_seam``: the queries padded and laid head-major, ``[W_uk
| W_uv]`` laid a head at a time, the values laid back), with the MXU's share of
peak each reaches on its OWN FLOPs and the largest difference of the two
forms' values (bf16 both: rounding, no limit).  ``--rehearse`` is the CPU
pre-flight at a toy shape, interpreted.  Writes ``chiprun_out/latent_prefill_curves.json``."""
import argparse
import contextlib
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deepspeed_tpu.ops.pallas import latent_prefill as lp  # noqa: E402
from deepspeed_tpu.ops.pallas import selected_attention as sa  # noqa: E402

PEAK = 197e12  # bf16 FLOP/s of a v5e (benchmark/peaks.py)


def timed(f, args, reps):
    jax.block_until_ready(f(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        out.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--contexts", type=int, nargs="+", default=[2048, 8192, 16384, 32768, 49152])
    ap.add_argument("--queries", type=int, nargs="+", default=[2048],
                    help="queries of the run (whole pages; the rest of the pack is dead)")
    ap.add_argument("--first-page", type=int, default=0, help="the pack's page the run starts on")
    ap.add_argument("--tiles", nargs="+", default=["1024,8,2,256"])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        h, r, nope, rope, v, bs, t, nb, dt = 4, 64, 16, 8, 16, 8, 64, 64, jnp.float32
        args.contexts, args.tiles, args.reps = [64, 160], ["16,2,2,16", "32,4,4,8"], 1
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from a CPU is no measurement (--rehearse for the pre-flight)")
    else:
        h, r, nope, rope, v, bs, t, nb, dt = 128, 512, 128, 64, 128, 128, 2048, 512, jnp.bfloat16
    lanes = -(-(r + rope) // 128) * 128
    scale = (nope + rope) ** -0.5
    g = t // bs
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    q = jax.random.normal(ks[0], (t, h, nope + rope)).astype(dt)
    w_uk = (jax.random.normal(ks[1], (r, h, nope)) * r ** -0.5).astype(dt)
    w_uv = (jax.random.normal(ks[2], (r, h, v)) * r ** -0.5).astype(dt)
    pages = jnp.pad(jax.random.normal(ks[3], (nb, bs, r + rope)),
                    ((0, 0), (0, 0), (0, lanes - r - rope))).astype(dt)
    rows = []

    def laid(q, w_uk, w_uv):  # what the seam hands the kernel
        return (jnp.pad(q, ((0, 0), (0, 0), (0, lanes - r - rope))).transpose(1, 0, 2),
                jnp.concatenate([w_uk, w_uv], -1).transpose(1, 0, 2))

    with lp.interpreted() if args.rehearse else contextlib.nullcontext():
        for end, nq in ((e, n) for e in args.contexts for n in args.queries if n <= e):
            p0, p, g0 = end - nq, end // bs, args.first_page
            table = jax.random.permutation(ks[4], nb)[:p].astype(jnp.int32)
            pairs = nq * p0 + nq * (nq + 1) / 2
            at = slice(g0 * bs, g0 * bs + nq)  # the run's rows of the pack

            @jax.jit
            def absorbed(q, w_uk):
                q_abs = jnp.concatenate(
                    [jnp.einsum("thn,rhn->thr", q[..., :nope], w_uk), q[..., nope:]], -1)
                return jnp.pad(q_abs, ((0, 0), (0, 0), (0, lanes - r - rope))).reshape(g, bs, h, lanes)

            @jax.jit
            def walked(q_abs, pages):
                q_pos = jnp.maximum(p0 + jnp.arange(t) - g0 * bs, 0).reshape(g, bs)
                live = (jnp.arange(g) >= g0) & (jnp.arange(g) < g0 + nq // bs)
                mask = (jnp.arange(p * bs)[None, None, :] <= q_pos[:, :, None]).astype(jnp.int8)
                return sa.selected_attention(
                    q_abs, mask, pages, jnp.tile(table, (g, 1)),
                    jnp.where(live, jnp.max(q_pos, axis=1) // bs + 1, 0), r, scale)

            q_abs = absorbed(q, w_uk)
            ms = timed(walked, (q_abs, pages), args.reps)
            want = jnp.einsum("gchr,rhv->gchv", walked(q_abs, pages), w_uv).reshape(t, h, v)
            rows.append({"keys": end, "queries": nq, "form": "absorbed walk", "ms": ms,
                         "mxu_pct": 100 * 2 * h * (r + lanes) * pairs / PEAK / (ms / 1e3)})
            print(json.dumps(rows[-1]), flush=True)
            runs = jnp.asarray([[g0, nq // bs, p0]] + [[0, 0, 0]] * (g // 2 - 1), jnp.int32)
            tables = jnp.tile(table, (g // 2, 1))
            for tile in args.tiles:
                lp.TQ, lp.KP, lp.HB, lp.TAIL = (int(x) for x in tile.split(","))  # read when traced
                kernel = jax.jit(lambda qh, w, pages: lp.latent_prefill(
                    qh, w, pages, tables, runs, r, scale))
                seam = jax.jit(lambda q, w_uk, w_uv, pages: lp.latent_prefill(
                    *laid(q, w_uk, w_uv), pages, tables, runs, r, scale).transpose(1, 0, 2))
                try:
                    ms = timed(kernel, (*jax.jit(laid)(q, w_uk, w_uv), pages), args.reps)
                    ms_seam = timed(seam, (q, w_uk, w_uv, pages), args.reps)
                    got = seam(q, w_uk, w_uv, pages)
                except Exception as e:  # a tile the compiler declines: say so, go on
                    rows.append({"keys": end, "queries": nq, "form": f"decompressed {tile}",
                                 "error": str(e)[:300]})
                    print(json.dumps(rows[-1]), flush=True)
                    continue
                flops = 2 * h * (nope + lanes - r + v) * pairs + 2 * h * r * (nope + v) * end
                rows.append({"keys": end, "queries": nq, "form": f"decompressed {tile}", "ms": ms,
                             "ms_seam": ms_seam, "mxu_pct": 100 * flops / PEAK / (ms / 1e3),
                             "max_diff": float(jnp.abs(got[at].astype(jnp.float32)
                                                       - want[at].astype(jnp.float32)).max())})
                print(json.dumps(rows[-1]), flush=True)
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "latent_prefill_curves.json").write_text(json.dumps(
        {"device": jax.devices()[0].device_kind, "rows": rows}, indent=1))


if __name__ == "__main__":
    main()
