"""Audit drivers: run the checker passes over a live engine's REAL jits.

``serve_jit_specs`` builds example arguments for every hot jit of an
:class:`~deepspeed_tpu.inference.engine_v2.InferenceEngineV2` (decode,
megastep decode burst, packed prefill, ctx-pack prefill, speculative
verify) mirroring the
engine's own dispatch sites, lowers the engine's actual compiled callables
(donation flags, out-shardings and all), and ``audit_serve_engine`` runs
the donation / collective-budget / dtype / sharding passes over each.
``audit_train_step`` does the training half (the fused train-step jit).
``tests/test_analysis.py`` consumes the returned JSON-able report.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..comm.budget import serving_tick_plan
from . import checks
from .hlo import parse_scheduled_hlo


def _triple(sampling=None):
    if sampling is None:
        return (0.0, 0, 1.0)
    return (sampling.temperature, sampling.top_k, sampling.top_p)


def donation_param_numbers(compiled, args: Sequence,
                           positions: Dict[str, int],
                           static_argnums: Sequence[int] = (),
                           ) -> Dict[str, List[int]]:
    """Map argument positions onto the compiled module's XLA parameter
    numbers.  Two wrinkles the naive flat-leaf count misses:

    - static arguments are compile-time constants, never parameters;
    - jit PRUNES unused array arguments from the executable
      (``keep_unused=False`` default) — e.g. the verify jit's per-slot
      sampling rows vanish entirely under ``all_greedy=True`` — shifting
      every later parameter number.  The executable's kept-variable set
      records the surviving flat indices.
    """
    import jax

    flat_ranges = {}
    start = 0
    dyn = 0
    arg_to_dyn = {}
    for i, a in enumerate(args):
        if i in static_argnums:
            continue
        n = len(jax.tree_util.tree_leaves(a))
        flat_ranges[dyn] = (start, n)
        arg_to_dyn[i] = dyn
        start += n
        dyn += 1
    kept = None
    ex = getattr(compiled, "_executable", None)
    if ex is not None:
        kept = getattr(ex, "_kept_var_idx", None)
    if kept is None:
        kept = set(range(start))
    order = sorted(kept)
    rank = {flat: i for i, flat in enumerate(order)}
    out: Dict[str, List[int]] = {}
    for label, pos in positions.items():
        lo, n = flat_ranges[arg_to_dyn[pos]]
        out[label] = [rank[i] for i in range(lo, lo + n) if i in rank]
    return out


def serve_jit_specs(eng, sampling=None) -> Dict[str, dict]:
    """{name: spec} for each auditable hot jit of a serve engine.  Each
    spec carries the jit, example args shaped exactly like the engine's
    dispatch site builds them, the donated-argument table for the donation
    check, and the token/sample-row counts the byte budget needs."""
    cfg = eng.cfg
    B = eng.mgr.max_seqs
    bs = eng.block_size
    key = jax.random.PRNGKey(0)
    tr = _triple(sampling)
    t_pad = eng.prefill_buckets[0]
    specs: Dict[str, dict] = {}

    toks = jnp.zeros(B, jnp.int32)
    lens = jnp.ones(B, jnp.int32)
    bt = jnp.zeros((B, eng.max_pages), jnp.int32)
    act = jnp.ones(B, bool)
    # a tick's ONE upload: tokens, KV positions, 0 / 1 for a live slot,
    # 0 / 1 for an input token read from the chain (the newest token a slot
    # sampled, carried from program to program like the key)
    rows = jnp.stack([toks, lens, act.astype(jnp.int32), jnp.zeros(B, jnp.int32)])
    chain = jnp.zeros(B, jnp.int32)
    specs["decode"] = dict(
        jit=eng._decode_jit,
        args=(eng.params, rows, bt, eng.kv, key, chain, tr),
        donated={"kv": 3}, static=(6,),
        n_tokens=B, sample_rows=B,
    )

    # megastep burst (PR 16): decode + on-device accumulation/termination.
    # Same per-dispatch collective plan as plain decode; the burst carries
    # (active, burst buffer, tick, emitted) as donated state while the
    # per-slot stop/cap rows are deliberately NOT donated (they feed every
    # fused tick) — the donation check proves both halves.
    n_burst = 4
    specs["decode_burst"] = dict(
        jit=eng._decode_burst_jit,
        args=(eng.params, toks, lens, bt, act, eng.kv, key,
              jnp.full((n_burst + 1, B), -2, jnp.int32),
              jnp.zeros((), jnp.int32), jnp.zeros(B, jnp.int32),
              jnp.full(B, -1, jnp.int32), jnp.full(B, n_burst, jnp.int32),
              tr),
        donated={"seq_lens": 2, "active": 4, "kv": 5, "burst": 7,
                 "tick": 8, "emitted": 9},
        static=(12,),
        n_tokens=B, sample_rows=B,
    )

    # a pack's ONE buffer, as the dispatch site lays it out (with the step's
    # rows behind it where the engine's packs carry the tick's step: a dense
    # engine's and, PR 56, the one pack program of a ``cfg.latent`` engine
    # whose runner says ``packs_carry_step`` alike)
    from ..inference.engine_v2 import new_pack

    step = eng.packs_carry_step
    specs["prefill_packed"] = dict(
        jit=eng._packed_prefill_jit,
        args=(eng.params, jnp.asarray(new_pack(t_pad, bs, B, eng.max_pages, False, step)[0]),
              eng.kv, key, chain, tr),
        donated={"kv": 2}, static=(5,),
        n_tokens=t_pad, sample_rows=B,
        # cold pack: dense attention only, never reads the paged pool — no
        # seq-shard ring in this dispatch
        ring=False,
    )

    specs["prefill_packed_ctx"] = dict(
        jit=eng._packed_prefill_ctx_jit,
        args=(eng.params, jnp.asarray(new_pack(t_pad, bs, B, eng.max_pages, True, step)[0]),
              eng.kv, key, chain, tr),
        donated={"kv": 2}, static=(5,),
        n_tokens=t_pad, sample_rows=B,
    )

    if eng.enable_speculation:
        K = eng.spec_max_draft
        K1 = K + 1
        t = B * K1
        specs["verify"] = dict(
            jit=eng._spec_jit,
            args=(eng.params, jnp.zeros(t, jnp.int32),
                  jnp.zeros(t, jnp.int32), jnp.zeros(t, jnp.int32),
                  jnp.full(t, -1, jnp.int32), jnp.zeros(t, jnp.int32),
                  jnp.full((B, eng.max_pages), -1, jnp.int32),
                  jnp.zeros(B, jnp.int32), jnp.zeros((B, K), jnp.int32),
                  jnp.zeros(B, jnp.int32), jnp.zeros((B, 2), jnp.float32),
                  eng.kv, key, 0, True),
            donated={"kv": 11}, static=(13, 14),
            n_tokens=t, sample_rows=t,
        )
    return specs


def audit_serve_engine(
    eng,
    which: Optional[Sequence[str]] = None,
    *,
    sampling=None,
    tol: float = 0.05,
    total_tol: float = 0.3,
) -> Dict[str, object]:
    """Full compiled-program audit of one serve engine.  Per hot jit:
    donation, collective budget (vs the ``comm/budget`` plan at this
    engine's transport format), and payload dtype audit; engine-level:
    the TP parameter-sharding lint.  Returns a JSON-able report with an
    overall ``passed`` flag."""
    tp = eng.serving_ctx.size
    fmt = eng.serving_ctx.comm_fmt
    specs = serve_jit_specs(eng, sampling=sampling)
    if which is not None:
        specs = {k: v for k, v in specs.items() if k in which}
    report: Dict[str, object] = {
        "engine": {
            "tp": tp, "serve_replicas": eng.serve_replicas,
            "seq_shards": getattr(eng, "seq_shards", 1),
            "quant_comm": fmt, "comm_tiles": eng.serving_ctx.comm_tiles,
            "quantize_weights": eng.quantize_weights,
            "max_seqs": eng.mgr.max_seqs, "num_layers": eng.cfg.num_layers,
            "hidden_size": eng.cfg.hidden_size,
            "vocab_size": eng.cfg.vocab_size,
        },
        "jits": {},
    }
    ok = True
    for name, spec in specs.items():
        jit = spec["jit"]
        if not hasattr(jit, "lower"):
            report["jits"][name] = {"skipped": "not a plain jit "
                                    "(offload-wrapped?)"}
            continue
        compiled = jit.lower(*spec["args"]).compile()
        facts = parse_scheduled_hlo(compiled.as_text())
        plan = serving_tick_plan(
            eng.cfg, spec["n_tokens"], tp, fmt,
            tiles=max(eng.serving_ctx.comm_tiles, 1),
            sample_rows=spec["sample_rows"],
            seq_shards=(getattr(eng, "seq_shards", 1)
                        if spec.get("ring", True) else 1),
            replicas=eng.serve_replicas,
        )
        required = donation_param_numbers(
            compiled, spec["args"], spec["donated"], spec.get("static", ()))
        results = [
            checks.check_donation(facts, required),
            checks.check_collective_budget(
                facts, plan, tol=tol, total_tol=total_tol),
            checks.check_payload_dtypes(facts, fmt),
        ]
        passed = all(r.passed for r in results)
        ok = ok and passed
        report["jits"][name] = {
            "passed": passed,
            "collectives": len([c for c in facts.collectives
                                if c.phase != "done"]),
            "async_pairs": len(facts.async_pairs),
            "donated_params": len(facts.donations),
            "checks": [r.to_json() for r in results],
        }
    if tp > 1 and getattr(eng, "_param_shardings", None) is not None:
        sh = checks.check_tp_param_sharding(
            eng.params, eng._param_shardings, eng.cfg, tp)
        ok = ok and sh.passed
        report["sharding"] = sh.to_json()
    report["passed"] = ok
    return report


def audit_train_step(engine, batch, rng=None,
                     quantized_comm: bool = False) -> Dict[str, object]:
    """Audit the fused train-step jit: the optimizer/param state must be
    donated (a lost donation doubles peak memory of the biggest program in
    the repo), and with ZeRO++ quantized collectives on, the gather/reduce
    wires must carry narrow payloads.  Byte budgets are NOT asserted here:
    the step scans over layers, and a collective inside a scan body
    executes per-iteration while the module text lists it once (see
    ``ProgramFacts.wire_bytes_total``)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    step = engine._get_train_step(batch)
    args = (engine.state, batch, rng)
    compiled = step.lower(*args).compile()
    facts = parse_scheduled_hlo(compiled.as_text())
    results = [
        checks.check_donation(
            facts, donation_param_numbers(compiled, args, {"state": 0})),
        checks.check_payload_dtypes(
            facts, "int8" if quantized_comm else "none",
            sources=("qcomm.py", "zeropp.py")),
    ]
    by_kind: Dict[str, int] = {}
    for c in facts.collectives:
        if c.phase != "done":
            by_kind[c.kind] = by_kind.get(c.kind, 0) + 1
    return {
        "passed": all(r.passed for r in results),
        "collectives_by_kind": by_kind,
        "donated_params": len(facts.donations),
        "checks": [r.to_json() for r in results],
    }
