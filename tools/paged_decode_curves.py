"""Stand-alone curves behind ``paged_attention._TILE_BYTES`` (a builder's tool,
run on the chip: ``chiprun -- python tools/paged_decode_curves.py``): the Pallas
kernel ``paged_decode`` alone at the serving cells' shapes, one layer's decode
attention over random bf16 pools, live slots scattered among the dead, table ids
interleaved and non-contiguous.

- ``chat``: 64 slots x 32 / 8 heads x 128, block 32, 128 table pages, 10 live
  rows of ~1.8k keys (``mistral7b_chat_rate``);
- ``docs``: the same pool, 8 live rows of 1-4k keys (``mistral7b_docs_closed``);
- ``nemo``: 128 slots x 32 / 2 heads x 128, block 128, 48 table pages, 128 live
  rows of ~1k keys (``nemotron3_super_reasoning_closed``).

For each shape and each keys-per-tile of ``--keys`` it prints the ms of ONE
call (a jitted chain of ``--chain`` dependent calls, host clock around
``block_until_ready``, median of ``--reps``), the share of the roofline (the
live K and V rows, q and out once at ``benchmark/peaks.py``'s HBM rate) and the largest difference from
the dense gather body; with ``--parent DIR`` (a ``git archive`` of the parent
commit) the parent's kernel beside it, handed its own ``len 1`` for a dead slot.
Writes ``chiprun_out/paged_decode_curves.json``."""
import argparse
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deepspeed_tpu.inference.paged import _paged_attention_decode_dense  # noqa: E402
from deepspeed_tpu.ops.pallas import paged_attention as pk  # noqa: E402

from benchmark.peaks import peaks_for  # noqa: E402

HQ, HD = 32, 128

SHAPES = {
    # name: slots, kv heads, block, table pages, pool blocks, live lengths
    "chat": (64, 8, 32, 128, 2304, lambda r: r.integers(1500, 2100, 10)),
    "docs": (64, 8, 32, 128, 2304, lambda r: r.integers(1024, 4000, 8)),
    "nemo": (128, 2, 128, 48, 6272, lambda r: r.integers(700, 1400, 128)),
    "idle": (64, 8, 32, 128, 2304, lambda r: r.integers(1, 2, 0)),
}


def timed(f, *args, reps):
    jax.block_until_ready(f(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        out.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(out)


def case(name, seed):
    slots, hkv, bs, pages, nb, draw = SHAPES[name]
    rng = np.random.default_rng(seed)
    live_lens = draw(rng)
    lens = np.zeros(slots, np.int32)
    lens[rng.permutation(slots)[:len(live_lens)]] = live_lens
    table = np.full((slots, pages), -1, np.int32)
    ids = iter(rng.permutation(nb))
    for i in range(pages):  # page i of every live slot in turn: interleaved ids
        for b in range(slots):
            if i * bs < lens[b]:
                table[b, i] = next(ids)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (slots, HQ, HD), jnp.bfloat16)
    ck = jax.random.normal(k2, (nb, bs, hkv, HD), jnp.bfloat16)
    cv = jax.random.normal(k3, (nb, bs, hkv, HD), jnp.bfloat16)
    need = 2 * (2 * hkv * HD * int(lens.sum()) + 2 * HQ * HD * int((lens > 0).sum()))
    return q, ck, cv, jnp.asarray(table), jnp.asarray(lens), need


def chained(kernel, n):
    """n dependent calls of one kernel under one jit: ms a call = total / n."""
    def run(q, ck, cv, table, lens):
        def step(x, _):
            out = kernel(x, ck, cv, table, lens)
            return (x + out * jnp.asarray(1e-3, x.dtype)).astype(x.dtype), None
        return jax.lax.scan(step, q, None, length=n)[0]
    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="chat,docs,nemo,idle")
    ap.add_argument("--keys", default="128,256,512,1024")
    ap.add_argument("--chain", type=int, default=64)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=34)
    ap.add_argument("--parent", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: interpret mode, one call; the times mean nothing")
    args = ap.parse_args()
    if args.rehearse:
        pk.set_interpret(True)
        args.chain = args.reps = 1
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind}), flush=True)
    hbm = peaks_for(dev.device_kind if dev.platform == "tpu" else "TPU v5 lite")[
        "hbm_bytes_per_s"]

    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_paged_attention",
            Path(args.parent) / "deepspeed_tpu/ops/pallas/paged_attention.py")
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        parent.set_interpret(args.rehearse)

    rows = []
    for name in args.shapes.split(","):
        q, ck, cv, table, lens, need = case(name, args.seed)
        bs, hkv = ck.shape[1:3]
        floor_ms = 1e3 * need / hbm
        ref = jax.jit(_paged_attention_decode_dense)(q, ck, cv, table, lens)
        live = np.asarray(lens) > 0
        base = {"shape": name, "live": int(live.sum()), "keys": int(np.asarray(lens).sum()),
                "roofline_ms": floor_ms}

        def record(row, out, ms):
            o, r = np.asarray(out, np.float32), np.asarray(ref, np.float32)
            row.update(
                ms=ms, roofline_pct=100 * floor_ms / ms if need else None,
                max_err_live=float(np.abs(o[live] - r[live]).max()) if live.any() else 0.0,
                finite=bool(np.isfinite(o).all()))
            print(json.dumps(row), flush=True)
            rows.append(row)

        if parent is not None:
            old_lens = jnp.maximum(lens, 1)
            record({**base, "kernel": "parent"},
                   jax.jit(parent.paged_attention_decode_kernel)(q, ck, cv, table, old_lens),
                   timed(chained(parent.paged_attention_decode_kernel, args.chain),
                         q, ck, cv, table, old_lens, reps=args.reps) / args.chain)
        for keys in (int(k) for k in args.keys.split(",")):
            if keys % bs:
                continue
            pk._TILE_BYTES = keys * hkv * HD * 2  # read when the call is traced
            row = {**base, "kernel": "paged_decode", "tile_keys": keys,
                   "tile_pages": pk._tile_pages(bs, hkv, HD, 2, table.shape[1])}
            out = jax.jit(pk.paged_attention_decode_kernel)(q, ck, cv, table, lens)
            row["dead_rows_max"] = float(np.abs(np.asarray(out, np.float32)[~live]).max()) \
                if (~live).any() else 0.0
            record(row, out, timed(chained(pk.paged_attention_decode_kernel, args.chain),
                                   q, ck, cv, table, lens, reps=args.reps) / args.chain)

    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "paged_decode_curves.json").write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
