"""Stand-alone curves behind ``moe/layer.py``'s tile rules (``held_row_tile``,
``_gmm_tiling``; a builder's tool, run on the chip: ``chiprun -- python
tools/grouped_matmul_curves.py``): the held experts' grouped products ALONE at the
programs the benchmark's expert cells run (their packs and ticks, and cell 10's
training step), widths and spec from the benchmark's configuration files, groups
sized by one draw of uniform routing from ``--seed`` and laid out as
``moe_block_held`` lays them out (each group padded to whole row tiles).

For each program and product (``up``: k = the experts' input width, n = their
width; ``down``: the other way) it prints, for the rule's tiling, the parent's
(a 128-row tile, ``tk`` of 1024 / 512 / 256 / 128) and the ``--try``'d ones
(``tm,tk,tn``; at most six a shape): the rows laid out, the ms of ONE call and the
GB/s of what the call NEEDS (the live rows in and out, each touched expert's
matrix once).  ``--backward`` times the transposed product and ``tgmm`` of a
training step's shapes too.  ``--layer`` times ``moe_block_held`` itself (under
``jax.grad`` for the training step) at the rule's row tile and at a fixed one of
128.  A call is timed inside a jitted loop of ``--chain`` calls, each reading one
element the call before it wrote so that none is hoisted; host clock around
``block_until_ready``, median of ``--reps`` (a dispatch costs ~0.2 ms, as much as
a short product).  ``--rehearse`` is the CPU pre-flight at toy sizes (kernels
interpreted).  Writes ``chiprun_out/grouped_matmul_curves.json``."""
import argparse
import contextlib
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402
from deepspeed_tpu.moe import layer  # noqa: E402

# (configuration file, tokens of the program, what the program is)
PROGRAMS = [
    ("dots3_note_l5_e32_serve_1chip", 2048, "pack"), ("dots3_note_l5_e32_serve_1chip", 16, "tick"),
    ("nemotron3_super_l11_e128_serve_1chip", 512, "pack"),
    ("nemotron3_super_l11_e128_serve_1chip", 128, "tick"),
    ("qwen3_next_l8_e128_serve_1chip", 512, "pack"), ("qwen3_next_l8_e128_serve_1chip", 16, "tick"),
    ("laguna_xs2_l5_serve_1chip", 512, "pack"), ("laguna_xs2_l5_serve_1chip", 32, "tick"),
    ("deepseek_v2_l5_e40_serve_1chip", 2048, "pack"), ("deepseek_v2_l5_e40_serve_1chip", 24, "tick"),
    ("mellum2_l4_e16_train_1chip", 16384, "step"),
]


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


def parent_tiling(k, n):
    """What ``_gmm_tiling`` gave before PR 51."""
    tk = next(t for t in (1024, 512, 256, 128) if k % t == 0)
    tn = max(t for t in range(128, n + 1, 128) if n % t == 0 and tk * t <= 1600 * 1024)
    return 128, tk, tn


def spec_of(config, rehearse):
    m = harness.load_json(harness.HERE / "configs" / f"{config}.json")
    cfg = harness.module("models", m["model_type"]).transformer_config(harness.rehearsed(m, rehearse))
    s = cfg.latent
    if rehearse:  # whole 128-lane tiles, one of them irregular
        s = dataclasses.replace(s, moe_width=384, moe_latent=256 if s.moe_latent else 0)
        return s, 512 if s.moe_latent else 256, 256, 384
    return s, cfg.hidden_size, (s.moe_latent or cfg.hidden_size), s.moe_width


def group_sizes(rng, t, spec):
    """The held groups' rows under ONE draw of uniform routing."""
    picks = np.argsort(rng.random((t, spec.n_routed)), axis=1)[:, :spec.experts_per_tok]
    local = picks - spec.held_offset
    return np.bincount(local[(local >= 0) & (local < spec.n_held)], minlength=spec.n_held)


@contextlib.contextmanager
def row_tile(tile):
    """``moe/layer.py`` with a fixed row tile in place of its rule (None: the rule's)."""
    rule = layer.held_row_tile
    if tile:
        layer.held_row_tile = lambda t, spec: tile
    try:
        yield
    finally:
        layer.held_row_tile = rule


def product(kind, tiling, rows, sizes, k, n, interpret):
    """(f, args) of one kernel call at ``tiling`` over ``rows`` laid-out rows."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    tm = tiling[0]
    padded = jnp.asarray(-(-sizes // tm) * tm, jnp.int32)
    rows = -(-rows // tm) * tm
    g = sizes.shape[0]
    key = jax.random.PRNGKey(0)
    xs = jax.random.normal(key, (rows, k), jnp.bfloat16)
    w = jax.random.normal(key, (g, k, n), jnp.bfloat16) * 0.02
    ct = jax.random.normal(key, (rows, n), jnp.bfloat16)
    if kind == "forward":
        def f(xs, w):
            out = gmm(xs, w, padded, preferred_element_type=xs.dtype, tiling=tiling, interpret=interpret)
            return xs, w.at[0, 0, 0].add(out[0, 0] * 0)
        return f, (xs, w)
    if kind == "transposed":  # d_xs [rows, k] = ct [rows, n] @ w[g]^T: contraction n, output k
        def f(ct, w):
            out = gmm(ct, w, padded, preferred_element_type=ct.dtype, tiling=tiling,
                      transpose_rhs=True, interpret=interpret)
            return ct, w.at[0, 0, 0].add(out[0, 0] * 0)
        return f, (ct, w)

    def f(xs, ct):  # tgmm: d_w [g, k, n]
        out = tgmm(xs.swapaxes(0, 1), ct, padded, preferred_element_type=xs.dtype, tiling=tiling,
                   num_actual_groups=g, interpret=interpret)
        return xs.at[0, 0].add(out[0, 0, 0] * 0), ct
    return f, (xs, ct)


def layer_call(spec, t, d_in, d_model, grad):
    """(f, args) of ``moe_block_held``'s routed experts, forward or forward + backward."""
    key = jax.random.PRNGKey(1)
    n = lambda *s: (jax.random.normal(key, s, jnp.float32) / np.sqrt(s[-2])).astype(jnp.bfloat16)
    g, f_ = spec.n_held, spec.moe_width
    lw = {"router": n(d_model, spec.n_routed), "w_up": n(g, d_in, f_), "w_down": n(g, f_, d_in)}
    if spec.expert_form == "swiglu":
        lw["w_gate"] = n(g, d_in, f_)
    if spec.routing == "sigmoid":
        lw["bias"] = jnp.zeros((spec.n_routed,), jnp.float32)
    if spec.moe_latent:
        lw["w_lat_down"], lw["w_lat_up"] = n(d_model, d_in), n(d_in, d_model)
    x = jax.random.normal(key, (t, d_model), jnp.float32).astype(jnp.bfloat16)

    def run(lw, x):
        if not grad:
            return layer.moe_block_held(lw, x, spec)[0]
        loss = lambda lw, x: jnp.sum(layer.moe_block_held(lw, x, spec)[0].astype(jnp.float32) ** 2)
        d_lw, d_x = jax.grad(loss, argnums=(0, 1))(lw, x)
        return d_x + sum(jnp.sum(v[..., :1, :1]).astype(d_x.dtype) for v in jax.tree.leaves(d_lw))

    return (lambda lw, x: (lw, x + (run(lw, x) * 0).astype(x.dtype))), (lw, x)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chain", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", default="", help="a substring of the configurations to run")
    ap.add_argument("--try", dest="tried", action="append", default=[],
                    help="a tiling to try beside the rule's and the parent's: tm,tk,tn")
    ap.add_argument("--backward", action="store_true")
    ap.add_argument("--layer", action="store_true")
    ap.add_argument("--tiles", default="0,128", help="--layer's row tiles (0: the rule's)")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if not a.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("grouped_matmul_curves: no TPU (--rehearse is the CPU pre-flight)")
    if a.rehearse:
        a.chain, a.reps = 2, 1
    tried = [tuple(int(v) for v in t.split(",")) for t in a.tried]
    rng, results = np.random.default_rng(a.seed), []
    from deepspeed_tpu.ops.pallas import selected_attention

    for config, t, what in PROGRAMS:
        if a.only not in config:
            continue
        spec, d_model, d_in, width = spec_of(config, a.rehearse)
        t = min(t, 64) if a.rehearse else t
        sizes = group_sizes(rng, t, spec)
        rule_tile = layer.held_row_tile(t, spec)
        live, touched = int(sizes.sum()), int((sizes > 0).sum())
        print(f"== {config} {what} t={t}: {live} live pairs on {touched} of {spec.n_held} held experts, "
              f"largest group {sizes.max()}, rule's row tile {rule_tile}", flush=True)
        if a.layer:
            for tile in (int(v) or None for v in a.tiles.split(",")):
                interpreted = selected_attention.interpreted() if a.rehearse else contextlib.nullcontext()
                with interpreted, row_tile(tile):  # the loop is traced in here
                    ms = timed(*layer_call(spec, t, d_in, d_model, what == "step"), a.chain, a.reps)
                row = dict(config=config, t=t, what="layer" + ("+grad" if what == "step" else ""),
                           tile=tile or rule_tile, ms=ms)
                results.append(row)
                print(f"   layer{'+grad' if what == 'step' else ''} tile {tile or rule_tile:4d}: {ms:8.3f} ms", flush=True)
            continue
        kinds = ("forward", "transposed", "tgmm") if a.backward and what == "step" else ("forward",)
        for name, k, n in (("up", d_in, width), ("down", width, d_in)):
            for kind in kinds:
                kk, nn = (n, k) if kind == "transposed" else (k, n)  # the tiling's own k and n
                cands = [layer._gmm_tiling(rule_tile, kk, nn), parent_tiling(kk, nn)]
                cands += [c for c in tried if kk % c[1] == 0 and nn % c[2] == 0]
                for tiling in list(dict.fromkeys(cands))[:6]:
                    with row_tile(tiling[0]):
                        rows = layer.held_rows_a_pass(t, spec)
                    f, args = product(kind, tiling, rows, sizes, k, n, a.rehearse)
                    ms = timed(f, args, a.chain, a.reps)
                    need = 2 * (live * (k + n) + touched * k * n)
                    if kind == "tgmm":
                        need = 2 * (live * (k + n) + spec.n_held * k * n)
                    row = dict(config=config, t=t, product=name, kind=kind, k=k, n=n, tiling=tiling,
                               rows=rows, ms=ms, need_gb_s=need / ms / 1e6)
                    results.append(row)
                    print(f"   {name:4s} {kind:10s} k {k:5d} n {n:5d} tiling {str(tiling):18s} rows {rows:6d}: "
                          f"{ms:8.3f} ms  {row['need_gb_s']:7.1f} GB/s needed", flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "grouped_matmul_curves.json").write_text(json.dumps(
        {"device": str(jax.devices()[0]), "seed": a.seed, "results": results}, indent=1))


if __name__ == "__main__":
    main()
