"""Model-family tests: forward shapes, KV-cache decode parity, GQA, presets,
training convergence on the tiny preset (the reference's pattern of tiny
synthetic models, tests/unit/simple_model.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import (
    CausalLM,
    TransformerConfig,
    forward,
    get_preset,
    init_kv_cache,
    init_params,
    list_presets,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_forward_shapes(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits, cache, aux = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert cache is None
    assert float(aux) == 0.0


def test_gpt2_architecture():
    cfg = get_preset("tiny_gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params  # tied embeddings
    assert "pos_embed" in params
    assert "bias" in params["final_norm"]
    logits, _, _ = forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    assert logits.shape == (1, 8, cfg.vocab_size)


def test_kv_cache_decode_matches_full(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)))
    full_logits, _, _ = forward(params, tokens, cfg)

    cache = init_kv_cache(cfg, batch=1, max_len=16, dtype=jnp.float32)
    # prefill 8, then decode 4 one at a time
    logits, cache, _ = forward(params, tokens[:, :8], cfg, cache=cache, cache_index=0)
    outs = [logits]
    for i in range(8, 12):
        logits, cache, _ = forward(
            params, tokens[:, i : i + 1], cfg, cache=cache, cache_index=i
        )
        outs.append(logits)
    inc = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full_logits), atol=2e-2, rtol=2e-2)


def test_gqa_matches_mha_when_repeated():
    """GQA with kv heads replicated up front must equal MHA."""
    from deepspeed_tpu.ops.attention import dot_product_attention, repeat_kv

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 16, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 16, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 16, 2, 16)), jnp.float32)
    out_gqa = dot_product_attention(q, k, v)
    out_mha = dot_product_attention(q, repeat_kv(k, 4), repeat_kv(v, 4))
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha), atol=1e-6)


def test_presets_registered():
    names = list_presets()
    for expected in ("llama3_8b", "llama3_70b", "mixtral_8x7b", "gpt2_small",
                     "mistral_7b", "qwen2_7b", "llama3_proxy_410m"):
        assert expected in names
    cfg = get_preset("llama3_8b")
    assert abs(cfg.param_count - 8.03e9) / 8.03e9 < 0.01


def test_tiny_model_trains():
    cfg = get_preset("tiny")
    model = CausalLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "steps_per_print": 100,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    # a memorizable batch (fixed tokens)
    batch = {"input_ids": rng.integers(0, 64, (1, 8 * 4, 33), dtype=np.int64)}
    first = float(engine.train_batch(batch))
    for _ in range(20):
        loss = float(engine.train_batch(batch))
    assert loss < first * 0.7, f"no learning: first={first} last={loss}"


def test_remat_matches_no_remat(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((1, 16), jnp.int32)
    base, _, _ = forward(params, tokens, cfg)
    rem, _, _ = forward(params, tokens, cfg.replace(remat="full"))
    np.testing.assert_allclose(np.asarray(base), np.asarray(rem), atol=1e-5)


def test_graft_entry_compiles():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out)).all()


def test_remat_offload_policy_trains():
    """remat='offload': activation save points ride pinned host memory
    (FPDT host-offload analogue, reference sequence/fpdt_layer.py:510)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=32).replace(remat="offload")
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=CausalLM(cfg),
            config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            },
            mesh=deepspeed_tpu.initialize_mesh(data=8),
        )
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 33)).astype(np.int32)}
        losses = [float(engine.train_batch(batch)) for _ in range(3)]
    except Exception as e:  # host memory spaces may be unsupported off-TPU
        if any(k in str(e).lower() for k in ("memory", "offload", "pinned", "placement", "side-effect")):
            pytest.skip(f"backend rejects host offload: {type(e).__name__}")
        raise
    assert np.isfinite(losses).all() and losses[-1] < losses[0]

    # numerics match the selective policy (same save points, different home)
    cfg2 = cfg.replace(remat="selective")
    e2, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(cfg2),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 33)).astype(np.int32)}
    ref = [float(e2.train_batch(batch)) for _ in range(3)]
    np.testing.assert_allclose(losses, ref, rtol=1e-4, atol=1e-4)


# slow: 13 s: two TP training engines (domino_chunks 1 and 2) compiled and stepped
@pytest.mark.slow
def test_domino_chunks_numerical_parity():
    """domino_chunks=2 splits layer compute into independent chunks; the
    math must be identical to the single-chunk body (values and grads)."""
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg1 = get_preset("tiny", num_layers=2)
    cfg2 = cfg1.replace(domino_chunks=2)
    m1, m2 = CausalLM(cfg1), CausalLM(cfg2)
    params = m1.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 64, (4, 17)))}
    l1 = float(m1.loss_fn(params, batch))
    l2 = float(m2.loss_fn(params, batch))
    assert abs(l1 - l2) < 2e-3, (l1, l2)
    g1 = jax.grad(lambda p: m1.loss_fn(p, batch))(params)
    g2 = jax.grad(lambda p: m2.loss_fn(p, batch))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def test_domino_chunks_config_wiring():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import CausalLM, get_preset

    model = CausalLM(get_preset("tiny", num_layers=2))
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "tensor_parallel": {"domino_chunks": 2},
        "steps_per_print": 1000,
    })
    assert model.cfg.domino_chunks == 2
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, (8, 17)).astype(np.int32)
    losses = [float(engine.train_batch({"input_ids": ids})) for _ in range(3)]
    assert losses[-1] < losses[0]
