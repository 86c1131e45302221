"""Stand-alone curves behind ``latent_attention.DENSE_KEYS_MAX`` (a builder's
tool, run on the chip: ``chiprun -- python tools/selected_attn_curves.py``):
the Pallas kernel ``selected_attn`` against the gathered XLA body, one layer's
attention for one pack's 16 groups of 128 queries at dots3-note-prev's widths
(128 heads, rows of 640 lanes, pages of 128, top 2048), every group ending at
context ``L``.  Prints ms a GROUP for both and writes
``chiprun_out/selected_attn_curves.json``.  ``--pack`` times instead the WHOLE
prefill-pack program of the benchmark's dots3-note-prev configuration (seeded
weights, random pages) for one 2048-token pack ending at ``L``, once with every
group on the gathered body and once with every group through the kernel: inside
the program the gathered body is slower than alone, so THIS pair of curves sets
the constant.  Times are host-clock medians around ``block_until_ready`` of
calls that last 10-400 ms."""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deepspeed_tpu.ops import latent_attention as la  # noqa: E402
from deepspeed_tpu.ops.pallas import selected_attention as sa  # noqa: E402

G, C, H, W, R, BS, P, NB, K = 16, 128, 128, 640, 512, 128, 272, 2176, 2048
SCALE = 0.07


def timed(f, *args, reps=5):
    jax.block_until_ready(f(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        out.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(out)


def pack_curves(lengths):
    """ms of one pack program ending at L: (all gathered, all through the kernel)."""
    from benchmark import harness
    from deepspeed_tpu.inference import latent_runner as lr
    from deepspeed_tpu.models.transformer import init_params

    root = Path(__file__).resolve().parents[1]
    model = harness.rehearsed(harness.load_json(
        root / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), False)
    e = model["engine"]
    cfg = harness.module("models", model["model_type"]).transformer_config(
        model, max_seq_len=e["max_seq_len"])
    params = jax.jit(lambda key: init_params(key, cfg, dtype=cfg.dtype))(jax.random.PRNGKey(32))
    t, bs, n = e["prefill_chunk"], e["block_size"], e["max_seqs"]
    pages = -(-e["max_seq_len"] // bs)
    cache = lr.init_cache(cfg, e["num_blocks"], bs, n, t)
    ks = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    for kind in ("lat", "idx"):  # random rows: zero pages would tie every score
        cache[kind] = tuple(jax.random.normal(next(ks), a.shape, a.dtype) for a in cache[kind])
    tables = jnp.full((n, pages), -1, jnp.int32).at[0].set(jnp.arange(pages))
    tokens = jax.random.randint(next(ks), (t,), 0, cfg.vocab_size)
    seg, last_idx = jnp.ones(t, jnp.int32), jnp.zeros(n, jnp.int32).at[0].set(t - 1)
    rows = []
    for L in lengths:
        pos = jnp.arange(L - t, L, dtype=jnp.int32)
        row = {"L": L}
        for name, gate in (("gathered_pack_ms", 0), ("kernel_pack_ms", 1 << 30)):
            la.DENSE_KEYS_MAX = gate  # read when the program is traced
            f = jax.jit(lambda p, c, pos: lr.prefill_pack(
                p, cfg, tokens, seg, pos, pos[::bs] // bs, last_idx, tables, c)[0])
            row[name] = timed(f, params, cache, pos)
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pack", action="store_true")
    ap.add_argument("--shared-picks", type=float, default=0.0, metavar="NOISE",
                    help="a group's queries score their keys alike (one score a key + NOISE x "
                         "a query's own): neighbouring queries then pick nearly the same rows")
    ap.add_argument("--lengths", default="2048,6144,12288,18432,24576,32768")
    ap.add_argument("--tiles", default="16x4,32x4,16x8,32x8,16x2",
                    help="(queries a tile) x (pages a step) to try at --tile-length")
    ap.add_argument("--tile-length", type=int, default=12288)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("no TPU: a curve from another device is not a curve")
    out = Path(__file__).resolve().parents[1] / "chiprun_out"
    out.mkdir(exist_ok=True)
    if args.pack:
        rows = pack_curves([int(x) for x in args.lengths.split(",")])
        (out / "selected_attn_pack_curves.json").write_text(json.dumps(rows, indent=1))
        return
    ks = jax.random.split(jax.random.PRNGKey(32), 4)
    q = jax.random.normal(ks[0], (G, C, H, W), jnp.bfloat16)
    lat = jax.random.normal(ks[1], (NB, BS, W), jnp.bfloat16)
    table = jax.random.permutation(ks[2], NB)[:P].astype(jnp.int32)
    tables = jnp.broadcast_to(table, (G, P))  # a pack's groups are one sequence's

    @jax.jit
    def picks(key, last):
        q_pos = last - (C - 1) + jnp.arange(C)
        sc = jax.random.normal(key, (G, C, P * BS))
        if args.shared_picks:
            sc = jax.random.normal(ks[0], (G, 1, P * BS)) + args.shared_picks * sc
        sc = jnp.where(jnp.arange(P * BS)[None, None, :] <= q_pos[None, :, None], sc, -jnp.inf)
        vals, ix = jax.lax.map(lambda s: la.select_topk(s, K, last + 1), sc)
        return vals, ix, la.selected_mask(sc, vals, ix)

    @jax.jit
    def gathered(q, ix, vals, tables, lat):
        def group(xs):
            q, ix, vals, table = xs
            own = jax.lax.optimization_barrier(lat[table].reshape(P * BS, W))
            return la.sparse_attention(q, ix, vals > -jnp.inf, lambda r: own[r], R, SCALE)
        return jax.lax.map(group, (q, ix, vals, tables))

    def kernel_at(tq, kp):
        sa.TQ, sa.KP = tq, kp
        return jax.jit(lambda q, m, lat, t, live: sa.selected_attention(q, m, lat, t, live, R, SCALE))

    lengths = [int(x) for x in args.lengths.split(",")]
    data = {L: picks(ks[3], L - 1) for L in sorted(set(lengths + [args.tile_length]))}
    live = lambda L: jnp.full((G,), -(-L // BS), jnp.int32)
    report = {"device": dev.device_kind, "shapes": dict(G=G, C=C, H=H, W=W, R=R, BS=BS, K=K),
              "tiles": {}, "curves": []}
    # needed FLOPs of the masked walk: every live page, all heads
    walk_flops = lambda L: 2.0 * G * C * H * (W + R) * (-(-L // BS) * BS)
    vals, ix, mask = data[args.tile_length]
    best = None
    for tile in args.tiles.split(","):
        tq, kp = map(int, tile.split("x"))
        try:
            ms = timed(kernel_at(tq, kp), q, mask, lat, tables, live(args.tile_length))
        except Exception as e:  # a tile the compiler refuses is a finding, not a crash
            report["tiles"][tile] = f"refused: {str(e)[:200]}"
            continue
        share = walk_flops(args.tile_length) / (ms * 1e-3) / 197e12
        report["tiles"][tile] = {"ms_a_group": ms / G, "mxu_share": share}
        print(f"tile {tile} at L={args.tile_length}: {ms / G:.3f} ms a group, "
              f"{100 * share:.1f}% of the MXU peak", flush=True)
        if best is None or ms < best[0]:
            best = (ms, tq, kp)
    _, tq, kp = best
    kern = kernel_at(tq, kp)
    report["tile"] = f"{tq}x{kp}"
    for L in lengths:
        vals, ix, mask = data[L]
        k_ms = timed(kern, q, mask, lat, tables, live(L))
        g_ms = timed(gathered, q, ix, vals, tables, lat)
        a = np.asarray(kern(q, mask, lat, tables, live(L)).astype(jnp.float32))
        b = np.asarray(gathered(q, ix, vals, tables, lat).astype(jnp.float32))
        row = {"L": L, "kernel_ms_a_group": k_ms / G, "gathered_ms_a_group": g_ms / G,
               "kernel_mxu_share": walk_flops(L) / (k_ms * 1e-3) / 197e12,
               "mask_density": float(jnp.mean(mask[:, :, :L].astype(jnp.float32))),
               "max_abs_diff": float(np.abs(a - b).max()), "mean_abs_diff": float(np.abs(a - b).mean())}
        report["curves"].append(row)
        print(json.dumps(row), flush=True)
    (out / "selected_attn_curves.json").write_text(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
