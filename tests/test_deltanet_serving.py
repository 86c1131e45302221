"""What only a model of two-norm blocks has (``models/latent.py:HYBRID``: a
Gated DeltaNet matrix state beside gated GQA with partial rotary, a held share
of softmax-routed experts in every block), at the rehearsal size of the
benchmark's configuration of it (float32, CPU, seeded weights): the runner's
two bodies against the reference's LOGITS, and the share tied to the model.
Its way through ``InferenceEngineV2`` and the scheduler (chunked prefill in
shared packs, slot re-use, preemption and resume, idle slots, the refusals) is
``tests/test_hybrid_serving.py``'s, run for both families."""
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.models.transformer import init_params  # noqa: E402

CONFIG = "benchmark/configs/qwen3_next_l8_e128_serve_1chip.json"
PAGE, CHUNK = 8, 32  # the engine's page (= the scan's chunk) and pack here


@pytest.fixture(scope="module")
def model():
    m = harness.rehearsed(harness.load_json(ROOT / CONFIG), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    assert s.hybrid and s.stateful and not s.single
    assert s.layer_kinds == ("gdn", "gdn", "gdn", "gattn") * 2 and s.expert_layers == tuple(range(8))
    params = init_params(jax.random.PRNGKey(7), cfg)
    ref = jax.jit(lambda p, t: arch.logits(p, t, m))
    return m, arch, cfg, params, ref


def test_the_logits_of_the_runners_bodies_match_the_reference(model):
    """Prefill in chunks, then decode, straight through ``latent_runner``'s two
    bodies on pages that are not contiguous: the LOGITS at the prompt's last
    position and of every decode step against the reference's full forward."""
    from deepspeed_tpu.inference import latent_runner

    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(3)
    n, steps, slots, slot = 53, 6, 3, 2
    seq = rng.integers(0, cfg.vocab_size, n + steps).astype(np.int32)
    want = np.asarray(ref(params, seq[None]))[0]
    pages = -(-(n + steps) // PAGE)
    table = np.full((slots, pages), -1, np.int32)
    table[slot] = np.arange(pages)[::-1] + 3
    cache = latent_runner.init_cache(cfg, pages + 4, PAGE, slots, CHUNK)
    pack = jax.jit(lambda *a: latent_runner.prefill_pack(params, cfg, *a))
    for start in range(0, n, CHUNK):
        end = min(start + CHUNK, n)
        tok, seg, pos = (np.zeros(CHUNK, np.int32) for _ in range(3))
        tok[:end - start], seg[:end - start] = seq[start:end], slot + 1
        pos[:end - start] = np.arange(start, end)
        pp = np.full(CHUNK // PAGE, -1, np.int32)
        used = -(-(end - start) // PAGE)
        pp[:used] = table[slot, start // PAGE: start // PAGE + used]
        last = np.full(slots, -1, np.int32)
        last[slot] = end - start - 1
        lg, cache = pack(tok, seg, pos, pp, last, table, cache)
    assert np.abs(np.asarray(lg)[slot] - want[n - 1]).max() <= 1e-4
    dec = jax.jit(lambda *a: latent_runner.decode_step(params, cfg, *a))
    active = np.arange(slots) == slot
    for j in range(steps):
        t1, lens = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        t1[slot], lens[slot] = seq[n + j], n + j
        lg, cache = dec(t1, lens, table, active, cache)
        assert np.abs(np.asarray(lg)[slot] - want[n + j]).max() <= 1e-4, j


def test_the_four_members_shares_add_up_to_the_uncut_reference_layer(model):
    """The share tied to the model: one Qwen3-Next expert layer with ALL its
    routed experts, cut four ways (offsets 0, 1/4, 1/2, 3/4 of them); each
    member routes every token over all of them and computes its own through
    ``moe_block_held``; the members' partial sums, the gated shared expert
    counted once, are the uncut REFERENCE layer's output."""
    from deepspeed_tpu.moe.layer import moe_block_held

    m, arch, cfg, params, ref = model
    total, held = m["deployment"]["num_experts_total"], m["num_experts"]
    assert total == 4 * held
    whole_cfg = arch.transformer_config(dict(m, num_experts=total))
    lw = init_params(jax.random.PRNGKey(11), whole_cfg)["layers"]["moe"][0]
    assert lw["w_up"].shape[0] == total and "bias" not in lw and lw["w_sg"].shape == (64, 1)
    x = jax.random.normal(jax.random.PRNGKey(12), (40, cfg.hidden_size))
    want = arch.uncut_expert_layer(lw, x[None], m)[0]
    shared = jax.nn.sigmoid(x @ lw["w_sg"]) * (
        (jax.nn.silu(x @ lw["s_gate"]) * (x @ lw["s_up"])) @ lw["s_down"])
    got, pairs = jnp.zeros_like(x), 0
    for off in range(0, total, held):
        mine = dict(lw, **{k: lw[k][off:off + held] for k in ("w_gate", "w_up", "w_down")})
        y, (stats, _, _) = moe_block_held(mine, x, replace(cfg.latent, held_offset=off))
        got += y - shared
        pairs += int(stats[1])
    assert pairs == 40 * m["num_experts_per_tok"]  # every pick fell on exactly one member
    assert float(jnp.abs(got + shared - want).max()) <= 1e-5
