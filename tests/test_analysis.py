"""Graft Auditor (deepspeed_tpu/analysis/): parser, checkers, source lint.

Three layers of coverage, all in the tier-1 fast lane (this file IS the
CI gate — a lint violation or a failed audit over the repo's real hot
jits fails here):

1. parser unit tests — real CPU-compiled scheduled HLO plus synthetic
   fixtures reproducing the TPU printer quirks the old regex tests broke
   on (async custom-call fusions, ``collective-permute-done`` tuple-typed
   operands, scan back-edges, iota replica groups);
2. seeded-regression tests: every checker proven to CATCH its planted
   bug (donation dropped, fp32 payload on a path claiming int8, sub-head
   TP sharding, hot-path host sync, steady-state recompile);
3. green runs: the full audit over every real serving hot jit (decode,
   packed prefill, ctx prefill, speculative verify) on a TP engine, the
   fused train-step jit, and the AST lint over all of deepspeed_tpu/.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.analysis import astlint, checks
from deepspeed_tpu.analysis import hlo as ahlo
from deepspeed_tpu.analysis.audit import (
    audit_serve_engine,
    audit_train_step,
    donation_param_numbers,
    serve_jit_specs,
)
from deepspeed_tpu.comm import budget, qcomm
from deepspeed_tpu.parallel.sharding import shard_map_compat

from conftest import make_grid


# ---------------------------------------------------------------------------
# parser: real CPU-compiled programs
# ---------------------------------------------------------------------------
def test_parser_real_psum_program_typed_records():
    mesh = make_grid(model=2).mesh

    def body(x, w):
        return jax.lax.psum(x @ w, "model")

    f = jax.jit(shard_map_compat(
        body, mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P(None, None),
    ))
    facts = ahlo.program_facts(
        f, jnp.zeros((4, 64)), jnp.zeros((64, 8)))
    ars = facts.find(kind="all-reduce")
    assert len(ars) == 1
    c = ars[0]
    assert c.dtype == "f32" and c.shape == (4, 8) and c.group_size == 2
    assert c.source_file.endswith(".py")  # source metadata captured
    # ring convention matches the qcomm accounting exactly
    assert c.bytes_on_wire == qcomm.wire_bytes("all_reduce", 32, "none", 2)
    assert facts.wire_bytes_total() == c.bytes_on_wire


# the two-device psum below as the printer before jax 0.9 wrote it: source
# inline in the metadata, each operand's type before its name
_INLINE_SOURCE_PSUM_HLO = """\
HloModule jit_body, is_scheduled=true, entry_computation_layout={(f32[1,8]{1,0})->f32[1,8]{1,0}}, num_partitions=2

%region_0.4 (Arg_0.5: f32[], Arg_1.6: f32[]) -> f32[] {
  %Arg_0.5 = f32[] parameter(0)
  %Arg_1.6 = f32[] parameter(1)
  ROOT %add.7 = f32[] add(f32[] %Arg_0.5, f32[] %Arg_1.6), metadata={op_name="jit(body)/jit(main)/shard_map/psum" source_file="/root/repo/tests/test_analysis.py" source_line=99}
}

ENTRY %main.10_spmd (param: f32[1,8]) -> f32[1,8] {
  %param = f32[1,8]{1,0} parameter(0), sharding={devices=[2,1]<=[2]}
  ROOT %all-reduce = f32[1,8]{1,0} all-reduce(f32[1,8]{1,0} %param), channel_id=1, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%region_0.4, metadata={op_name="jit(body)/jit(main)/shard_map/psum" source_file="/root/repo/tests/test_analysis.py" source_line=99}
}
"""


def test_parser_reads_both_printed_forms_to_equal_records():
    """The same collective from the text with ``source_file=`` inline and
    typed operands (a literal) and from the installed JAX's text (compiled
    here: ``stack_frame_id`` against the header's tables, operands by bare
    name) parses to records equal in kind, source and operand types."""
    mesh = make_grid(model=2).mesh

    def body(x):
        return jax.lax.psum(x, "model")

    f = jax.jit(shard_map_compat(
        body, mesh, in_specs=(P("model", None),), out_specs=P(None, None)))
    live_facts = ahlo.program_facts(f, jnp.zeros((2, 8)))
    inline_facts = ahlo.parse_scheduled_hlo(_INLINE_SOURCE_PSUM_HLO)
    (live,), (inline,) = live_facts.collectives, inline_facts.collectives
    for name in ("kind", "phase", "source_file", "operand_types",
                 "result_types", "group_size", "bytes_on_wire"):
        assert getattr(live, name) == getattr(inline, name), name
    assert live.source_file == "test_analysis.py"
    assert live.operand_types == (("f32", (1, 8)),)
    assert live.source_line == body.__code__.co_firstlineno + 1
    assert live_facts.wire_bytes_total() == inline_facts.wire_bytes_total()


_SHARED_CHANNEL_HLO = """\
HloModule jit_two, is_scheduled=true

%async_computation.1 (param_0: f32[4,8]) -> f32[4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  ROOT %all-to-all.9 = f32[4,8]{1,0} all-to-all(%param_0), channel_id=1, replica_groups={{0,1}}, dimensions={0}
}

ENTRY %main.3 (x: f32[4,8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0)
  %all-reduce.1 = f32[4,8]{1,0} all-reduce(%x), channel_id=1, replica_groups={{0,1}}, to_apply=%add
  %all-reduce.2 = f32[4,8]{1,0} all-reduce(%all-reduce.1), channel_id=1, replica_groups={{0,1}}, to_apply=%add
  %all-to-all-start.1 = (f32[4,8]{1,0}, f32[4,8]{1,0}) all-to-all-start(%all-reduce.2), channel_id=1, replica_groups={{0,1}}, dimensions={0}, calls=%async_computation.1
  ROOT %all-to-all-done.1 = f32[4,8]{1,0} all-to-all-done(%all-to-all-start.1)
}
"""


def test_wire_bytes_count_each_transfer_once_without_channel_ids():
    """jax 0.9 prints ``channel_id=1`` on every ``shard_map`` collective:
    two all-reduces of one computation are two transfers, while an async
    start and the collective inside the computation it calls are one."""
    facts = ahlo.parse_scheduled_hlo(_SHARED_CHANNEL_HLO)
    ar, a2a = 2 * 128 * 1 // 2, 128 * 1 // 2  # ring convention, W=2
    assert facts.wire_bytes_total(kinds=("all-reduce",)) == 2 * ar
    assert facts.wire_bytes_total() == 2 * ar + a2a
    done = facts.find(kind="all-to-all", phase="done")[0]
    assert done.operand_types == (("f32", (4, 8)), ("f32", (4, 8)))


def test_parser_real_donation_header():
    def g(kv, x):
        ck, cv = kv
        ck = tuple(c.at[0].set(x) for c in ck)
        return (ck, cv), x + 1.0

    kv = (tuple(jnp.zeros((3, 4)) for _ in range(2)),
          tuple(jnp.zeros((3, 4)) for _ in range(2)))
    donated = ahlo.program_facts(
        jax.jit(g, donate_argnums=(0,)), kv, jnp.zeros(4))
    assert len(donated.donations) == 4  # all four pool leaves alias
    plain = ahlo.program_facts(jax.jit(g), kv, jnp.zeros(4))
    assert plain.donations == []


# ---------------------------------------------------------------------------
# parser: synthetic TPU-printer fixtures (the PR 9 breakage class)
# ---------------------------------------------------------------------------
_ASYNC_FUSION_HLO = """\
HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (1, {}, may-alias), {1,0}: (3, {1}, must-alias) }, entry_computation_layout={(bf16[32,128]{1,0})->bf16[8,128]{1,0}}

%fused_computation.1 (param_0.1: bf16[32,128]) -> (bf16[256,128], u32[]) {
  %param_0.1 = bf16[32,128]{1,0} parameter(0)
  %all-gather.1 = s8[256,128]{1,0} all-gather(s8[32,128]{1,0} %param_0.1), channel_id=5, replica_groups=[1,8]<=[8], dimensions={0}, use_global_device_ids=true
  ROOT %custom-call.1 = (s8[256,128]{1,0}, u32[]) custom-call(s8[256,128]{1,0} %all-gather.1), custom_call_target="AsyncCollectiveStart"
}

%fused_computation.2 (param_0.2: (s8[256,128], u32[])) -> s8[256,128] {
  %param_0.2 = (s8[256,128]{1,0}, u32[]) parameter(0)
  ROOT %custom-call.2 = s8[256,128]{1,0} custom-call((s8[256,128]{1,0}, u32[]) %param_0.2), custom_call_target="AsyncCollectiveDone", channel_id=5
}

ENTRY %main.10 (p0: bf16[32,128]) -> bf16[8,128] {
  %p0 = bf16[32,128]{1,0} parameter(0)
  %ag-start = (s8[256,128]{1,0}, u32[]) fusion(bf16[32,128]{1,0} %p0), kind=kLoop, calls=%fused_computation.1
  %dot.5 = bf16[8,128]{1,0} dot(bf16[8,128]{1,0} %p0, bf16[128,128]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag-done = s8[256,128]{1,0} fusion((s8[256,128]{1,0}, u32[]) %ag-start), kind=kLoop, calls=%fused_computation.2
  ROOT %dot.6 = bf16[8,128]{1,0} dot(bf16[8,128]{1,0} %dot.5, bf16[128,128]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_synthetic_async_fusion_pairing_and_iota_groups():
    facts = ahlo.parse_scheduled_hlo(_ASYNC_FUSION_HLO)
    # donation header with nested/multi-element indices
    assert ahlo.Donation((0,), 1, (), "may-alias") in facts.donations
    assert ahlo.Donation((1, 0), 3, (1,), "must-alias") in facts.donations
    # the wrapped collective parses with the iota replica-group world size
    ag = facts.find(kind="all-gather")[0]
    assert ag.group_size == 8 and ag.dtype == "s8" and ag.async_wrapped
    # start/done fusions pair by channel with the dot scheduled between
    assert facts.async_starts == 1 and facts.async_dones == 1
    pairs = facts.overlapped(min_compute=1)
    assert len(pairs) == 1 and pairs[0].dtype == "s8"
    assert pairs[0].compute_between == 1


_PERMUTE_HLO = """\
HloModule jit_ring, is_scheduled=true

%fused_computation.9 (param_0: bf16[2,512]) -> bf16[2,512] {
  %param_0 = bf16[2,512]{1,0} parameter(0)
  ROOT %dot.9 = bf16[2,512]{1,0} dot(bf16[2,512]{1,0} %param_0, bf16[512,512]{1,0} %param_0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%while_body.3 (arg: bf16[2,512]) -> bf16[2,512] {
  %arg = bf16[2,512]{1,0} parameter(0)
  %collective-permute-done.2 = bf16[2,512]{1,0:T(8,128)(2,1)S(1)} collective-permute-done((bf16[2,512]{1,0:T(8,128)(2,1)}, bf16[2,512]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) %collective-permute-start.2)
  %fusion.7 = bf16[2,512]{1,0} fusion(bf16[2,512]{1,0} %arg), kind=kOutput, calls=%fused_computation.9
  ROOT %collective-permute-start.2 = (bf16[2,512]{1,0:T(8,128)(2,1)}, bf16[2,512]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(bf16[2,512]{1,0:T(8,128)(2,1)} %fusion.7), channel_id=3, source_target_pairs={{0,1},{1,0}}
}

ENTRY %main.20 (x: bf16[2,512]) -> bf16[2,512] {
  %x = bf16[2,512]{1,0} parameter(0)
  %collective-permute-start.1 = (bf16[2,512]{1,0:T(8,128)(2,1)}, bf16[2,512]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(bf16[2,512]{1,0:T(8,128)(2,1)} %x), channel_id=2, source_target_pairs={{0,1},{1,0}}
  %fusion.2 = bf16[2,512]{1,0} fusion(bf16[2,512]{1,0} %x), kind=kOutput, calls=%fused_computation.9
  ROOT %collective-permute-done.1 = bf16[2,512]{1,0:T(8,128)(2,1)S(1)} collective-permute-done((bf16[2,512]{1,0:T(8,128)(2,1)}, bf16[2,512]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) %collective-permute-start.1)
}
"""


def test_synthetic_permute_tuple_operand_and_backedge():
    """The printer quirks that broke the old regexes (fixture types copied
    from real v5e scheduled HLO): the done op prints its operand with the
    full 4-tuple type (SSA name is not at a fixed position), tuple types
    nest PARENS inside tiled-layout annotations
    (``{1,0:T(8,128)(2,1)S(1)}`` — the first ``)`` is not the tuple
    close), and a scan body may schedule done BEFORE start (the pair spans
    the loop back-edge)."""
    facts = ahlo.parse_scheduled_hlo(_PERMUTE_HLO)
    pairs = facts.overlapped(kinds=("collective-permute",), min_compute=1,
                             loose=True)
    # ENTRY: start -> fusion(dot) -> done, paired through the tuple type
    assert any(p.computation == "%main.20" and p.compute_between >= 1
               for p in pairs)
    # while body: done scheduled before start -> back-edge pair
    assert any(p.computation == "%while_body.3" and p.spans_backedge
               for p in pairs)
    # a raw -start op's tuple result aliases in-flight buffers: the wire
    # payload is ONE transferred buffer, not the tuple sum
    start = facts.find(kind="collective-permute", phase="start")[0]
    assert start.bytes_on_wire == 2 * 512 * 2  # one bf16[2,512]


def test_stablehlo_collective_scan():
    mesh = make_grid(fsdp=2).mesh

    def body(x):
        return jax.lax.all_gather(x, "fsdp")

    lowered = jax.jit(shard_map_compat(
        body, mesh, in_specs=(P("fsdp", None),), out_specs=P(None, None),
    )).lower(jnp.zeros((4, 8), jnp.int8))
    colls = ahlo.stablehlo_collectives(lowered.as_text())
    assert any(c.kind == "all_gather" and c.dtype == "i8" for c in colls)


# ---------------------------------------------------------------------------
# engine fixtures (shared across checker + audit tests)
# ---------------------------------------------------------------------------
def _tiny_cfg():
    from deepspeed_tpu.models import get_preset

    return get_preset("tiny", max_seq_len=128, dtype=jnp.float32).replace(
        hidden_size=256, intermediate_size=256, num_heads=4, num_kv_heads=2,
    )


@pytest.fixture(scope="module")
def tp_engine():
    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import CausalLM

    cfg = _tiny_cfg()
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    grid = make_grid(model=2)
    return InferenceEngineV2(
        params, cfg, grid=grid, quantize_weights="int8", quant_comm="int8",
        comm_tiles=2, enable_speculation=True, spec_max_draft=2,
        max_seqs=2, num_blocks=64, block_size=8, prefill_buckets=(16,),
    )


@pytest.fixture(scope="module")
def solo_engine():
    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models import CausalLM

    cfg = _tiny_cfg()
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(1))
    return InferenceEngineV2(
        params, cfg, max_seqs=2, num_blocks=32, block_size=8,
        prefill_buckets=(16,),
    )


# ---------------------------------------------------------------------------
# green runs: the audit over every real hot jit (the CI gate)
# ---------------------------------------------------------------------------
def test_audit_green_on_tp_engine_all_hot_jits(tp_engine):
    """ACCEPTANCE: decode, the megastep decode burst, packed prefill,
    ctx-pack prefill and the speculative verify jit all pass donation +
    collective-budget + dtype audits on clean HEAD, and the TP param
    shardings pass the lint — with the int8 transport, where the budget
    also proves the analytic ``comm/bytes_on_wire`` accounting matches
    the compiled program."""
    report = audit_serve_engine(tp_engine)
    assert set(report["jits"]) == {
        "decode", "decode_burst", "prefill_packed", "prefill_packed_ctx",
        "verify"}
    for name, j in report["jits"].items():
        assert j["passed"], (name, j["checks"])
        assert j["collectives"] > 0  # a TP jit with no collectives is wrong
    assert report["sharding"]["passed"], report["sharding"]["violations"]
    assert report["passed"]
    # the transport budget is byte-EXACT, not merely within tolerance
    for name, j in report["jits"].items():
        b = next(c["facts"] for c in j["checks"]
                 if c["check"] == "collective_budget")
        assert b["emitted_transport_bytes"] == b["expected_transport_bytes"], name


def test_audit_green_on_single_chip_engine(solo_engine):
    """Single-chip jits must audit clean too: donation intact and ZERO
    collectives (tp=1 has nothing to put on a wire)."""
    report = audit_serve_engine(solo_engine)
    assert report["passed"], report
    for name, j in report["jits"].items():
        assert j["collectives"] == 0, (name, j)
        assert j["donated_params"] > 0, name


def test_audit_green_on_fused_train_step(grid8):
    """The fused ZeRO-3 + ZeRO++ train-step jit: optimizer/param state
    donated, int8 payloads on the qwZ/qgZ wires."""
    import deepspeed_tpu as ds
    from simple_model import init_mlp, mlp_loss, random_batches

    engine = ds.initialize(
        loss_fn=mlp_loss,
        params=init_mlp(jax.random.PRNGKey(0)),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {
                "stage": 3, "param_persistence_threshold": 0,
                "zero_quantized_weights": True,
                "zero_quantized_gradients": True,
            },
            "steps_per_print": 10**6,
        },
        mesh=grid8,
    )[0]
    batch = random_batches(1, 1, 16)[0]
    rep = audit_train_step(engine, batch, quantized_comm=True)
    assert rep["passed"], rep
    assert rep["donated_params"] > 0
    assert rep["collectives_by_kind"]  # the sharded step really communicates


def test_astlint_repo_clean():
    """The tier-1 source gate: zero violations over deepspeed_tpu/ —
    host syncs in hot paths, new global state, and raw lax collectives
    outside comm/ all fail HERE before they fail in production."""
    violations = astlint.lint_package()
    assert violations == [], "\n".join(str(v) for v in violations)


# ---------------------------------------------------------------------------
# seeded regressions: every checker catches its planted bug
# ---------------------------------------------------------------------------
def test_donation_checker_catches_dropped_donate_argnums():
    def g(kv, x):
        ck, cv = kv
        ck = tuple(c.at[0].set(x) for c in ck)
        return (ck, cv), x + 1.0

    kv = (tuple(jnp.zeros((3, 4)) for _ in range(2)),
          tuple(jnp.zeros((3, 4)) for _ in range(2)))
    args = (kv, jnp.zeros(4))

    def run(jitted):
        compiled = jitted.lower(*args).compile()
        facts = ahlo.parse_scheduled_hlo(compiled.as_text())
        req = donation_param_numbers(compiled, args, {"kv": 0})
        return checks.check_donation(facts, req)

    assert run(jax.jit(g, donate_argnums=(0,))).passed
    bad = run(jax.jit(g))  # the planted bug: donation dropped
    assert not bad.passed
    assert "no input-output alias" in str(bad.violations[0])


def _qcomm_facts(fmt, shape=(8, 512)):
    mesh = make_grid(model=2).mesh

    def body(y):
        return qcomm.q_psum_tiled(y, "model", fmt, tiles=1, world=2,
                                  out_dtype=jnp.float32)

    f = jax.jit(shard_map_compat(
        body, mesh, in_specs=(P(None, None),), out_specs=P(None, None),
    ))
    return ahlo.program_facts(f, jnp.zeros(shape, jnp.float32))


def test_dtype_checker_catches_fp32_payload_on_int8_path():
    """Planted bug: a transport that claims int8 but ships the full fp32
    partial (fmt silently reset to 'none') — the exact failure mode the
    dtype audit exists for."""
    good = checks.check_payload_dtypes(_qcomm_facts("int8"), "int8")
    assert good.passed, [str(v) for v in good.violations]
    bad = checks.check_payload_dtypes(_qcomm_facts("none"), "int8")
    assert not bad.passed
    assert "no narrow-dtype" in str(bad.violations[0])


def test_budget_checker_catches_unaccounted_transport(tp_engine):
    """Planted bug: the analytic plan loses half its row psums (the
    accounting-drift class the checker reconciles) — the same facts that
    pass against the true plan must fail against the broken one."""
    spec = serve_jit_specs(tp_engine)["decode"]
    facts = ahlo.program_facts(spec["jit"], *spec["args"])
    cfg = tp_engine.cfg
    true_plan = budget.serving_tick_plan(
        cfg, spec["n_tokens"], 2, "int8", tiles=2,
        sample_rows=spec["sample_rows"])
    assert checks.check_collective_budget(facts, true_plan).passed
    broken = [p if p.label != "row_psum" else
              budget.PlannedCollective(
                  op=p.op, n_elements=p.n_elements, fmt=p.fmt,
                  world=p.world, count=p.count // 2,
                  none_bytes_per_el=p.none_bytes_per_el, label=p.label)
              for p in true_plan]
    res = checks.check_collective_budget(facts, broken)
    assert not res.passed
    assert "drift" in str(res.violations[0])


def test_sharding_checker_catches_planted_sub_head_rule():
    """Planted bug: wq out-features sharded though num_heads does not
    divide tp (the historical tp=4 GQA parity failure class), plus a
    row-parallel kernel with sharded scales."""
    mesh = make_grid(model=2).mesh
    cfg = _tiny_cfg().replace(num_heads=3, num_kv_heads=3, hidden_size=384,
                              head_dim=128)
    d = 384
    params = {"layers": {"attn": {
        "wq": {"q": jnp.zeros((d, d), jnp.int8), "s": jnp.zeros(d)},
        "wo": {"q": jnp.zeros((d, d), jnp.int8), "s": jnp.zeros(d)},
    }}}
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    planted = {"layers": {"attn": {
        "wq": {"q": sh(None, "model"), "s": sh("model")},  # sub-head!
        "wo": {"q": sh("model", None), "s": sh("model")},  # sharded scale!
    }}}
    res = checks.check_tp_param_sharding(params, planted, cfg, tp=2)
    msgs = "\n".join(str(v) for v in res.violations)
    assert "SUB-HEAD" in msgs
    assert "row-parallel kernel's scales sharded" in msgs
    # the correct placement passes
    good = {"layers": {"attn": {
        "wq": {"q": sh(None, None), "s": sh(None)},  # replicated: 3 % 2
        "wo": {"q": sh("model", None), "s": sh(None)},
    }}}
    assert checks.check_tp_param_sharding(params, good, cfg, tp=2).passed


def test_recompile_sentinel_on_live_engine(solo_engine):
    """Steady-state serving must not recompile; a drifting static arg
    (new sampling temperature) must be counted."""
    from deepspeed_tpu.inference import SamplingParams

    eng = solo_engine
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    eng.put([901], [[3, 1, 4, 1]], samp)
    eng.step(samp)
    with checks.RecompileSentinel.for_engine(eng) as sentinel:
        eng.step(samp)
        eng.step(samp)
    assert sentinel.total_misses() == 0, sentinel.misses()
    assert sentinel.to_result().passed
    sentinel.snapshot()
    eng.step(SamplingParams(temperature=0.7, top_k=3))  # planted drift
    assert sentinel.misses().get("decode_jit", 0) >= 1
    assert not sentinel.to_result().passed
    eng.flush([901])


# ---------------------------------------------------------------------------
# astlint: planted sources per rule
# ---------------------------------------------------------------------------
def test_astlint_catches_hot_path_host_sync():
    src = (
        "import jax\n"
        "class E:\n"
        "    def step(self, x):\n"
        "        jax.block_until_ready(x)\n"
        "        y = float(x.sum())\n"
        "        z = x.item()\n"
        "        return y, z\n"
        "    def cold(self, x):\n"
        "        return float(x.sum())\n"
    )
    out = astlint.lint_source(src, "inference/engine_v2.py")
    rules = [(v.rule, v.line) for v in out]
    assert ("host-sync", 4) in rules  # block_until_ready
    assert ("host-sync", 5) in rules  # float(<computed>)
    assert ("host-sync", 6) in rules  # .item()
    assert not any(line == 9 for _, line in rules)  # cold() is not hot


def test_astlint_catches_new_global_state():
    src = "def set_mode(v):\n    global _MODE\n    _MODE = v\n"
    out = astlint.lint_source(src, "ops/quantizer.py")
    assert [v.rule for v in out] == ["global-state"]
    # grandfathered global stays legal
    ok = astlint.lint_source(
        "def set_current_mesh(m):\n    global _CURRENT_MESH\n"
        "    _CURRENT_MESH = m\n",
        "parallel/sharding.py",
    )
    assert ok == []


def test_astlint_catches_raw_lax_collective_outside_comm():
    src = "import jax\ndef f(x):\n    return jax.lax.psum(x, 'model')\n"
    out = astlint.lint_source(src, "inference/new_feature.py")
    assert [v.rule for v in out] == ["lax-collective"]
    assert astlint.lint_source(src, "comm/qcomm.py") == []
    assert astlint.lint_source(src, "runtime/zeropp.py") == []  # baseline
    # the escape hatch: a documented, explicitly-allowed line
    allowed = src.replace(
        "jax.lax.psum(x, 'model')",
        "jax.lax.psum(x, 'model')  # lint: allow(lax-collective)")
    assert astlint.lint_source(allowed, "inference/new_feature.py") == []


# ---------------------------------------------------------------------------
# budget plan unit identities (the shared-enumeration satellite)
# ---------------------------------------------------------------------------
def test_serving_tick_plan_matches_engine_accounting_formula():
    """The plan's row_psum group must equal the pre-refactor engine
    arithmetic (2 transports/layer of [n_tokens, hidden] at the engine's
    format) — the counter semantics test_qcomm pins did not move."""
    cfg = _tiny_cfg()
    for fmt in ("none", "int8"):
        plan = budget.serving_tick_plan(cfg, 8, 4, fmt, sample_rows=8)
        row = [p for p in plan if p.label == "row_psum"]
        assert len(row) == 1 and row[0].count == 2 * cfg.num_layers
        legacy = 2 * cfg.num_layers * qcomm.wire_bytes(
            "all_reduce", 8 * cfg.hidden_size, fmt, 4,
            none_bytes_per_el=jnp.dtype(cfg.dtype).itemsize)
        assert budget.plan_bytes(plan, overhead=False) == legacy
        # overhead is strictly additive and format-independent
        assert budget.plan_bytes(plan, overhead=True) == budget.plan_bytes(
            budget.serving_tick_plan(cfg, 8, 4, "none", sample_rows=8),
            overhead=True)
    assert budget.serving_tick_plan(cfg, 8, 1, "int8") == []
    # the reconciliation the auditor surfaced: small quantized tiles pad
    # to a tp*chunk multiple on the wire — the tiled plan must report
    # MORE bytes than the naive n_tokens*hidden arithmetic, not fewer
    cfg2 = cfg  # hidden 256: 2-token tiles of 128 pad 4x at tp=2
    tiled = budget.serving_tick_plan(cfg2, 2, 2, "int8", tiles=2)
    naive = 2 * cfg2.num_layers * qcomm.wire_bytes(
        "all_reduce", 2 * cfg2.hidden_size, "int8", 2)
    assert budget.plan_bytes(tiled, overhead=False) > naive


def test_zero3_step_plan_matches_flagship_arithmetic():
    n = 1_000_000
    plan = budget.zero3_step_plan(n, 8, "int8", micro_batches=2)
    assert budget.plan_bytes(plan) == 2 * (
        qcomm.wire_bytes("all_gather", n, "int8", 8)
        + qcomm.wire_bytes("reduce_scatter", n, "int8", 8))
