"""Flops profiler tests (reference: tests/unit/profiling/ on tiny models)."""
import re

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, get_preset
from deepspeed_tpu.profiling import (
    FlopsProfiler,
    analyze_train_step,
    get_model_profile,
    model_tree,
)


def test_model_tree_params_match_real_param_tree():
    """Tree param counts are exact vs the actual initialized pytree."""
    for name in ("tiny", "tiny_gpt2", "tiny_moe"):
        cfg = get_preset(name)
        model = CausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        real = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
        tree = model_tree(cfg, batch=2, seq_len=64)
        assert tree.total_params() == real, name


def test_model_tree_macs_sanity():
    cfg = get_preset("tiny")
    b, s = 2, 64
    tree = model_tree(cfg, b, s)
    tok = b * s
    d, f, L, v = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers, cfg.vocab_size

    def find(node, name):
        if node.name == name:
            return node
        for c in node.children:
            r = find(c, name)
            if r is not None:
                return r
        return None

    # exact node-level expectations
    assert find(tree, "lm_head").macs == tok * d * v
    layer = find(tree, "decoder_layer")
    assert find(layer, "wq").macs == tok * d * cfg.num_heads * cfg.hd
    assert find(layer, "qk_scores").macs == b * cfg.num_heads * (s * s // 2) * cfg.hd
    assert find(layer, "mlp").macs == tok * 3 * d * f
    # total = L * per-layer + head
    assert tree.total_macs() == L * layer.total_macs() + tok * d * v


def test_get_model_profile_strings():
    model = CausalLM(get_preset("tiny"))
    flops, macs, params = get_model_profile(
        model, batch=1, seq_len=32, as_string=True, print_profile=False
    )
    assert flops.endswith("FLOPS") and macs.endswith("MACs")


def test_profiler_report_and_engine_hook(tmp_path):
    cfg = get_preset("tiny", max_seq_len=32)
    model = CausalLM(cfg)
    report_file = str(tmp_path / "flops.txt")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "flops_profiler": {
                "enabled": True,
                "profile_step": 2,
                "output_file": report_file,
            },
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 33)).astype(np.int32)}
    for _ in range(3):
        engine.train_batch(batch)
    with open(report_file) as fh:
        out = fh.read()
    assert "Flops Profiler" in out
    assert "per-module breakdown" in out
    assert "decoder_layer" in out
    assert "XLA scheduled FLOPs" in out or "params:" in out


def test_analyze_train_step_reports_xla_flops():
    cfg = get_preset("tiny", max_seq_len=32)
    model = CausalLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 33)).astype(np.int32)}
    info = analyze_train_step(engine, batch)
    # CPU cost analysis counts scan bodies once (undercount); assert presence
    # and positivity here, exactness is a TPU-only property.
    assert info.get("flops", 0) > 0
    assert info.get("bytes_accessed", 0) > 0
    assert info.get("argument_size_in_bytes", 0) > 0
