"""The serving runner's seam (PR 31): ``model_runner`` holds ONE dense layer
body and ONE loop over it, the four entries differ in how rows are written,
read and handed to the head; ``InferenceEngineV2`` chooses its runner once and
asks it, not ``cfg.latent``, for the cache, the entries and the kind's host
accounting.  CPU, tiny sizes: what is called and what is registered."""
import inspect
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from test_weight_layout import BODIES, _body

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import engine_v2, latent_runner, model_runner, paged  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

LATENT_COUNTERS = (
    "index_keys_scored", "index_keys_selected", "window_rows_discarded",
    "selected_groups", "selected_groups_dense", "expert_pairs_routed", "expert_pairs_held", "expert_rows_laid_out",
    "expert_group_rows_max", "expert_group_rows_min")


@pytest.mark.parametrize("name", BODIES)
def test_every_entry_runs_the_one_layer_body_once_a_layer(name, monkeypatch):
    """Tracing an entry calls ``_layer`` exactly ``num_layers`` times with a
    ``write`` and a ``read`` of its own, and the entry's own source names
    neither the layers nor a piece of the block."""
    calls = []
    layer = model_runner._layer

    def counted(cfg, lw, x, positions, kv_l, write, read, ctx):
        calls.append((write, read))
        return layer(cfg, lw, x, positions, kv_l, write, read, ctx)

    monkeypatch.setattr(model_runner, "_layer", counted)
    cfg = get_preset("tiny", max_seq_len=64, dtype=jnp.float32, num_layers=3)
    fn, specs = _body(name, cfg, slots=4, pages=8, pack=16, bs=8, blocks=16)
    jax.eval_shape(fn, *specs)
    assert len(calls) == cfg.num_layers == 3
    assert len({id(w) for w, _ in calls}) == 1 and len({id(r) for _, r in calls}) == 1
    source = inspect.getsource(getattr(model_runner, name))
    for piece in ("num_layers", "_qkv(", "_ffn(", "_attn_out(", "rope(", "for l in"):
        assert piece not in source, (name, piece)


def test_the_module_holds_one_layer_loop_and_no_unreachable_prefill():
    """A sixth copy of the block does not come back by a revert: the one
    padded prompt's ``prefill`` and its ``write_prefill_kv`` had no caller."""
    source = inspect.getsource(model_runner)
    assert source.count("range(cfg.num_layers)") == 1
    assert source.count("= _qkv(") == 1  # the block is written once
    assert not hasattr(model_runner, "prefill")
    assert not hasattr(paged, "write_prefill_kv")
    # ctx prefill and verify share their read and differ in their write
    for name in ("prefill_packed_ctx", "verify_packed_ctx"):
        assert "_read_ctx(" in inspect.getsource(getattr(model_runner, name))
    # ... and both prefill packs their page-granular write
    for name in ("prefill_packed", "prefill_packed_ctx"):
        assert "_write_pages(" in inspect.getsource(getattr(model_runner, name))


def _latent_cfg():
    """The benchmark's ``cfg.latent`` configuration at its rehearsal size."""
    m = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
    return harness.module("models", m["model_type"]).transformer_config(
        m, max_seq_len=m["engine"]["max_seq_len"])


def _build(kind):
    if kind == "dense":
        cfg = get_preset("tiny", max_seq_len=64, dtype=jnp.float32)
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)
        kw = dict(prefill_buckets=(16, 32))
    else:
        cfg = _latent_cfg()
        params = init_params(jax.random.PRNGKey(7), cfg)
        kw = dict(prefill_buckets=(32,), prefill_chunk=32, max_seq_len=256)
    return InferenceEngineV2(params, cfg, max_seqs=4, num_blocks=64, block_size=8, **kw)


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_the_engine_holds_the_runner_of_its_model(kind):
    """The matching runner, its counters and no others, and its share of the
    ``close()`` audit; the rings' host mirror lives with the runner."""
    eng = _build(kind)
    latent = kind == "latent"
    want = latent_runner.LatentRunner if latent else model_runner.DenseRunner
    assert type(eng.runner) is want
    assert eng.runner.packs_are_one_program is latent
    assert eng.runner.counters == (LATENT_COUNTERS if latent else ())
    assert [k for k in LATENT_COUNTERS if k in eng.stats] == (
        list(LATENT_COUNTERS) if latent else [])
    assert set(eng._tracked) == (
        {"_packed_prefill_ctx_jit", "_decode_jit"} if latent else set())
    assert isinstance(eng.kv, dict if latent else tuple)
    audit = eng.close()
    assert set(audit) == {"blocks_in_use", "cached_blocks"} | (
        {"window_rows"} if latent else set())
    assert not any(audit.values())


def test_the_engine_asks_the_runner_not_the_config():
    """``cfg.latent`` is read where the runner is chosen and nowhere else in
    the engine; the latent kind's accounting is not the engine's."""
    source = inspect.getsource(engine_v2)
    assert source.count("cfg.latent is") == 1
    for name in ("LATENT_COUNTERS", "_count_latent", "_ring_rows", "_release_ring"):
        assert not hasattr(engine_v2, name) and name not in source, name
    assert inspect.getsource(model_runner).count("cfg.latent is") == 1  # the guard


@pytest.mark.parametrize("name", BODIES)
def test_a_dense_entry_refuses_a_latent_model(name):
    """Handed ``cfg.latent`` a dense entry refuses before it touches an
    argument; ``LatentRunner`` refuses what its kind has no program for."""
    cfg = _latent_cfg()
    entry = getattr(model_runner, name)
    required = [p for p in inspect.signature(entry).parameters.values()
                if p.default is p.empty]
    with pytest.raises(NotImplementedError, match="TransformerConfig.latent"):
        entry(None, cfg, *[None] * (len(required) - 2))
    runner = latent_runner.LatentRunner(cfg)
    for refused, word in (("verify_packed_ctx", "enable_speculation"),
                          ("prefill_packed", "prefill_packed_ctx")):
        with pytest.raises(NotImplementedError, match=word):
            getattr(runner, refused)()
