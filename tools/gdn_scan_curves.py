"""Stand-alone curves behind ``ops/gdn.py:SOLVE_BLOCK`` (a builder's tool, run
on the chip: ``chiprun -- python tools/gdn_scan_curves.py``): ``gdn_scan`` alone
at the pack shape of ``qwen3_next_longctx_qa_closed`` (4 chunks of 128 tokens,
16 key / 32 value heads x 128, float32 states), unit keys sharing ``--alike`` of
one direction.

For each block size of ``--blocks`` it prints the ms of ONE call (a jitted loop
of ``--chain`` calls, each reading what the one before it wrote so that none is
shared or hoisted; host clock around ``block_until_ready``, median of
``--reps``: a dispatch costs ~0.2 ms, more than a solve), the same for the solve
alone (``[W | U]`` from ``A`` and the right-hand side) and the largest
difference of ``[W | U]``, relative to its largest entry, from
``jax.lax.linalg.triangular_solve``, which is timed beside them as ``xla`` (the
call before PR 46: on the chip an explicit inverse by a 128-step row loop).  ``--rehearse`` is the CPU pre-flight at a toy shape.
Writes ``chiprun_out/gdn_scan_curves.json``."""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deepspeed_tpu.ops import gdn  # noqa: E402


def timed(f, args, chain, reps):
    """ms of one ``f(*args) -> args`` of a jitted loop of ``chain``."""
    loop = jax.jit(lambda *t: jax.lax.fori_loop(0, chain, lambda _, c: f(*c), t))
    jax.block_until_ready(loop(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(*args))
        out.append(1e3 * (time.perf_counter() - t0) / chain)
    return statistics.median(out)


def scan_again(q, k, v, g, beta, loaded, cont):
    """One ``gdn_scan`` whose next call reads its outputs (keys and states)."""
    o, states = gdn.gdn_scan(q, k, v, g, beta, loaded, cont)
    return q, k + 1e-6 * o[:, :, :k.shape[2]], v, g, beta, states, cont


def solve_again(solve):
    def again(a, rhs):
        wu = solve(a, rhs)
        strict = jnp.arange(a.shape[-1])[:, None] > jnp.arange(a.shape[-1])[None, :]
        return jnp.where(strict, a + 1e-6 * wu[..., :a.shape[-1]], 0.0), rhs
    return again


def inputs(seed, g, l, hk, hv, d, alike):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    common = jax.random.normal(ks[5], (g, 1, hk, d))
    q = unit(jax.random.normal(ks[0], (g, l, hk, d))) * d ** -0.5
    k = unit((1 - alike) * jax.random.normal(ks[1], (g, l, hk, d)) + alike * 4 * common)
    v = jax.random.normal(ks[2], (g, l, hv, d))
    gate = -jnp.exp(jax.random.uniform(ks[3], (g, l, hv), minval=-6.0, maxval=1.0))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (g, l, hv)))
    loaded = jax.random.normal(ks[6], (g, hv, d, d))
    return q, k, v, gate, beta, loaded, jnp.asarray([False] + [True] * (g - 1))


def system(k, v, beta):
    """A chunk's ``A`` (at decay 1, where it is largest) and right-hand side, [G, Hv, L, ...]."""
    heads = lambda t: jnp.moveaxis(t, 1, 2)
    k = jnp.repeat(heads(k), v.shape[2] // k.shape[2], axis=1)
    beta = heads(beta)[..., None]
    a = jnp.tril(beta * jnp.einsum("ghid,ghjd->ghij", k, k, precision=gdn._HI), -1)
    return a, beta * jnp.concatenate([k, heads(v)], axis=-1)


def xla_solve(a, rhs):
    return jax.lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True, unit_diagonal=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--alike", type=float, default=0.9)
    ap.add_argument("--chain", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    shape = (2, 32, 2, 4, 16) if args.rehearse else (4, 128, 16, 32, 128)
    if args.rehearse:
        args.chain, args.reps = 2, 1
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from a CPU is no measurement (--rehearse for the pre-flight)")
    case = inputs(args.seed, *shape, args.alike)
    a, rhs = jax.jit(system)(case[1], case[2], case[4])
    want = jax.jit(xla_solve)(a, rhs)
    rows = [{"solve": "xla", "solve_ms": timed(solve_again(xla_solve), (a, rhs), args.chain, args.reps)}]
    for b in args.blocks:
        gdn.SOLVE_BLOCK = b  # read when traced: every jit below is a fresh one
        got = jax.jit(lambda *t: gdn._solve_unit_lower(*t))(a, rhs)
        rows.append({"solve": f"b{b}",
                     "scan_ms": timed(scan_again, case, args.chain, args.reps),
                     "solve_ms": timed(solve_again(gdn._solve_unit_lower), (a, rhs), args.chain, args.reps),
                     "solve_err": float(jnp.abs(got - want).max() / jnp.abs(want).max())})
    report = {"device": jax.devices()[0].device_kind, "shape": shape, "alike": args.alike, "rows": rows}
    for row in rows:
        print(json.dumps(row))
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "gdn_scan_curves.json").write_text(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
