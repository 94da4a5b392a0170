"""convert_llama: numerical parity with HuggingFace's Llama.

The strongest possible check for config 4's real-world story: build a tiny
``transformers`` LlamaForCausalLM, save it as HF safetensors, convert with
our tool, lazy-load through the engine, and compare logits token-for-token
with the HF forward pass.  Passing means naming, layout (transposes), RoPE
convention, GQA, rms_norm, and the SiLU MLP all line up — not just shapes.
"""

import json
import os

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from nvme_strom_tpu.tools import convert_llama


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("hf_llama")
    cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False)
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval()
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def _load_converted(out_dir, dtype=None):
    """strom_config.json + lazy params from a converted dir (single
    device) — the boilerplate every parity test needs."""
    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import TransformerConfig
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    with open(os.path.join(out_dir, "strom_config.json")) as f:
        cfg = TransformerConfig(dtype=dtype or jnp.float32,
                                **json.load(f))
    params = LazyCheckpoint(out_dir).load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]))
    return cfg, params


def test_map_name_covers_llama_tensors():
    assert convert_llama.map_name("model.embed_tokens.weight") == (
        "tok_embed", False)
    assert convert_llama.map_name(
        "model.layers.3.self_attn.q_proj.weight") == ("layers.3.wq", True)
    assert convert_llama.map_name(
        "model.layers.0.post_attention_layernorm.weight") == (
        "layers.0.mlp_norm", False)
    assert convert_llama.map_name("lm_head.weight") == ("lm_head", True)
    # unknown buffers are skipped, not mis-mapped
    assert convert_llama.map_name(
        "model.layers.0.self_attn.rotary_emb.inv_freq") is None


def test_convert_and_logit_parity(hf_checkpoint, tmp_path):
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import forward

    hf_dir, model = hf_checkpoint
    out_dir = str(tmp_path / "strom")
    summary = convert_llama.convert(hf_dir, out_dir, shard_bytes=64 << 10)
    assert summary["shards"] >= 2          # shard budget actually splits

    cfg, params = _load_converted(out_dir)
    assert cfg.n_kv_heads == 2 and cfg.n_layers == 2

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(2, 16), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.float().numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg))
    # f32 end-to-end on both sides: tight tolerance
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_convert_rejects_unsupported_arch(tmp_path):
    """Bias terms / exotic rope scaling must be a hard error, not a
    silently wrong conversion."""
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=32, attention_bias=True)
    model = transformers.LlamaForCausalLM(cfg).eval()
    d = str(tmp_path / "hf_bias")
    model.save_pretrained(d, safe_serialization=True)
    with pytest.raises(ValueError, match="attention_bias"):
        convert_llama.convert(d, str(tmp_path / "out"))
    with pytest.raises(ValueError, match="hidden_act"):
        convert_llama.config_from_hf({
            "vocab_size": 64, "hidden_size": 16, "num_hidden_layers": 1,
            "num_attention_heads": 2, "intermediate_size": 32,
            "hidden_act": "gelu"})
    with pytest.raises(ValueError, match="rope_scaling"):
        convert_llama.config_from_hf({
            "vocab_size": 64, "hidden_size": 16, "num_hidden_layers": 1,
            "num_attention_heads": 2, "intermediate_size": 32,
            "rope_scaling": {"rope_type": "yarn", "factor": 4}})


def test_convert_llama3_rope_scaling_parity(tmp_path):
    """Llama-3.1-style rope_scaling converts AND matches HF logits —
    the frequency remap in models.transformer._llama3_scale_freqs is
    checked against transformers' implementation, not just accepted."""
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import forward

    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 16})
    torch.manual_seed(2)
    model = transformers.LlamaForCausalLM(cfg).eval()
    d = str(tmp_path / "hf31")
    model.save_pretrained(d, safe_serialization=True)
    out = str(tmp_path / "strom31")
    convert_llama.convert(d, out)
    scfg, params = _load_converted(out)
    assert scfg.rope_scaling is not None
    rng = np.random.default_rng(1)
    # positions beyond original_max_position_embeddings exercise the
    # scaled long-wavelength branch
    tokens = rng.integers(0, 128, size=(1, 48), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.float().numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32),
                              scfg))
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_convert_tied_embeddings(tmp_path):
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True)
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(cfg).eval()
    d = str(tmp_path / "hf")
    model.save_pretrained(d, safe_serialization=True)
    out = str(tmp_path / "strom")
    summary = convert_llama.convert(d, out)
    # lm_head materialized from the tied embedding
    from nvme_strom_tpu.formats.safetensors import SafetensorsFile
    names = set()
    for s in os.listdir(out):
        if s.endswith(".safetensors"):
            names |= set(SafetensorsFile(os.path.join(out, s)).keys())
    assert "lm_head" in names and "tok_embed" in names
    assert summary["tensors"] == 1 + 1 + 1 + 9  # embed, norm, head, layer


def test_greedy_generation_parity(hf_checkpoint, tmp_path):
    """GENERATION parity (not just one forward): greedy decode through
    our KV-cache scan must emit the same token ids as transformers'
    .generate on the converted checkpoint — validates prefill/cache/
    step rotation end to end."""
    import functools

    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models.decode import generate

    hf_dir, model = hf_checkpoint
    out_dir = str(tmp_path / "strom_gen")
    convert_llama.convert(hf_dir, out_dir)
    cfg, params = _load_converted(out_dir)

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, size=(1, 12), dtype=np.int64)
    new = 16
    with torch.no_grad():
        ref = model.generate(
            torch.from_numpy(prompt), max_new_tokens=new,
            do_sample=False, use_cache=True,
            eos_token_id=None,   # random weights may emit the default
            pad_token_id=0).numpy()[0, prompt.shape[1]:]
    gen = jax.jit(functools.partial(generate, cfg=cfg,
                                    max_new_tokens=new))
    ours = np.asarray(gen(params, jnp.asarray(prompt, jnp.int32))[0])
    np.testing.assert_array_equal(ours, ref)


def test_generate_example_cli(hf_checkpoint, tmp_path):
    """examples/generate.py end to end from an HF checkpoint dir."""
    import subprocess
    import sys as _sys
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    hf_dir, _ = hf_checkpoint
    r = subprocess.run(
        [_sys.executable, str(repo / "examples" / "generate.py"),
         "--from-hf", hf_dir, "--out-dir", str(tmp_path / "conv"),
         "--prompt", "5,6,7", "--new", "8"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(repo))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "output ids:" in r.stdout
    ids = (r.stdout.split("output ids:")[1].strip().splitlines()[0]
           .split(","))
    assert len(ids) == 8 and all(i.strip().isdigit() for i in ids)

    # same checkpoint through the serving example: each request's ids
    # match the solo run's prefix of the same length
    rs = subprocess.run(
        [_sys.executable, str(repo / "examples" / "serve.py"),
         "--weights", str(tmp_path / "conv"), "--slots", "2",
         "--request", "5,6,7:8", "--request", "9,1:5"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(repo))
    assert rs.returncode == 0, rs.stderr[-2000:]
    line = [ln for ln in rs.stdout.splitlines()
            if ln.startswith("r0:")][0]
    assert line.split(":", 1)[1].strip().split(",") == ids
    assert "aggregate" in rs.stdout

    # same checkpoint through the SSD-backed cache: identical greedy ids
    r2 = subprocess.run(
        [_sys.executable, str(repo / "examples" / "generate.py"),
         "--weights", str(tmp_path / "conv"),
         "--prompt", "5,6,7", "--new", "8",
         "--offload", str(tmp_path / "kv.bin"), "--offload-window", "4"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(repo))
    assert r2.returncode == 0, r2.stderr[-2000:]
    ids2 = (r2.stdout.split("output ids:")[1].strip().splitlines()[0]
            .split(","))
    assert ids2 == ids


# -- granitemoehybrid: Mamba-2 layers beside attention ----------------------

@pytest.fixture(scope="module")
def hf_hybrid(tmp_path_factory):
    if not hasattr(transformers, "GraniteMoeHybridForCausalLM"):
        pytest.skip("this transformers has no GraniteMoeHybrid")
    d = tmp_path_factory.mktemp("hf_hybrid")
    cfg = transformers.GraniteMoeHybridConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        shared_intermediate_size=128, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
        mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=8,
        mamba_conv_bias=True, mamba_proj_bias=False,
        position_embedding_type="nope", embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0,
        attention_multiplier=1 / 16, tie_word_embeddings=True,
        num_local_experts=0, num_experts_per_tok=0, attention_bias=False)
    torch.manual_seed(0)
    model = transformers.GraniteMoeHybridForCausalLM(cfg).eval()
    with torch.no_grad():       # D, the norms and the conv bias off their init
        for name, p in model.named_parameters():
            if name.endswith(("mamba.D", "norm.weight", "layernorm.weight",
                              "conv1d.bias")):
                p.add_(0.1 * torch.randn_like(p))
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_map_name_covers_hybrid_tensors():
    m = convert_llama.map_name
    assert m("model.layers.1.mamba.in_proj.weight") == ("layers.1.ssm_in",
                                                        True)
    assert m("model.layers.1.mamba.conv1d.weight") == ("layers.1.ssm_conv_w",
                                                       True)
    assert m("model.layers.1.mamba.A_log") == ("layers.1.ssm_A_log", False)
    assert m("model.layers.0.shared_mlp.input_linear.weight") == (
        "layers.0.w_gate_up", True)


def test_hybrid_logits_match_hf(hf_hybrid, tmp_path):
    """Converted granitemoehybrid weights through ``forward`` (the Pallas
    scan in interpret mode) against transformers' own forward: names,
    the conv's layout, the fused MLP's split, gate-before-norm, no rotary,
    the four multipliers and the tied head all line up."""
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import forward
    hf_dir, model = hf_hybrid
    out = str(tmp_path / "converted")
    summary = convert_llama.convert(hf_dir, out)
    assert summary["skipped"] == []
    cfg, params = _load_converted(out)
    assert cfg.layer_kinds == ("mamba", "mamba", "attention", "mamba")
    assert cfg.tie_embed and "lm_head" not in params
    toks = np.random.default_rng(0).integers(0, 256, (2, 21))
    with torch.no_grad():
        want = model(torch.tensor(toks)).logits.numpy()
    got = np.asarray(forward({k: jnp.asarray(v, jnp.float32)
                              for k, v in params.items()},
                             jnp.asarray(toks), cfg))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


# -- lfm2 / lfm2_moe: gated short convs beside q/k-normed attention ---------

LFM2_MOE = dict(
    model_type="lfm2_moe", vocab_size=256, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
    norm_eps=1e-5, conv_L_cache=3, conv_bias=False, num_dense_layers=1,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    layer_types=["conv", "conv", "full_attention", "conv"])


@pytest.fixture(scope="module")
def hf_lfm2(tmp_path_factory):
    if not hasattr(transformers, "Lfm2ForCausalLM"):
        pytest.skip("this transformers has no Lfm2")
    d = tmp_path_factory.mktemp("hf_lfm2")
    cfg = transformers.Lfm2Config(
        vocab_size=256, hidden_size=64, intermediate_size=192,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, norm_eps=1e-5, rope_theta=1000000.0,
        conv_bias=False, conv_L_cache=3, block_multiple_of=32,
        block_ffn_dim_multiplier=1.0, block_auto_adjust_ff_dim=True,
        layer_types=["conv", "conv", "full_attention", "conv"],
        tie_word_embeddings=True)
    torch.manual_seed(0)
    model = transformers.Lfm2ForCausalLM(cfg).eval()
    with torch.no_grad():       # the norms off their init of ones
        for name, p in model.named_parameters():
            if name.endswith(("norm.weight", "layernorm.weight")):
                p.add_(0.1 * torch.randn_like(p))
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_map_name_covers_lfm2_tensors():
    m = convert_llama.map_name
    assert m("model.layers.0.conv.in_proj.weight") == ("layers.0.conv_in",
                                                       True)
    assert m("model.layers.0.conv.conv.weight") == ("layers.0.conv_w", True)
    assert m("model.layers.2.self_attn.out_proj.weight") == ("layers.2.wo",
                                                             True)
    assert m("model.layers.2.self_attn.q_layernorm.weight") == (
        "layers.2.q_norm", False)
    assert m("model.layers.1.feed_forward.w3.weight") == ("layers.1.w_up",
                                                          True)
    assert m("model.layers.1.operator_norm.weight") == ("layers.1.attn_norm",
                                                        False)
    assert m("model.embedding_norm.weight") == ("final_norm", False)
    # the MoE block's names (assumed: benchmark/configs/lfm2-24b-a2b.json)
    assert m("model.layers.3.feed_forward.gate.weight") == ("layers.3.router",
                                                            True)
    assert m("model.layers.3.feed_forward.expert_bias") == (
        "layers.3.router_bias", False)
    assert m("model.layers.3.feed_forward.experts.5.w2.weight") == (
        "layers.3.moe_w_down.5", True)


def test_lfm2_logits_match_hf(hf_lfm2, tmp_path):
    """Converted lfm2 weights through ``forward`` against transformers' own
    ``Lfm2ForCausalLM``: the conv operator (B | C | x split, the depthwise
    taps' layout, no activation), per-head q/k norms before rotary, w1/w3/w2,
    the MLP's adjusted width, the family's norm names and the tied head."""
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import forward
    hf_dir, model = hf_lfm2
    out = str(tmp_path / "converted")
    summary = convert_llama.convert(hf_dir, out)
    assert summary["skipped"] == []
    cfg, params = _load_converted(out)
    assert cfg.layer_kinds == ("conv", "conv", "attention", "conv")
    assert cfg.qk_norm and cfg.tie_embed and "lm_head" not in params
    assert cfg.d_ff == 128 == params["layers.0.w_gate"].shape[1]  # 2/3 of 192
    assert params["layers.0.conv_w"].shape == (3, 64)
    toks = np.random.default_rng(0).integers(0, 256, (2, 21))
    with torch.no_grad():
        want = model(torch.tensor(toks)).logits.numpy()
    got = np.asarray(forward({k: jnp.asarray(v, jnp.float32)
                              for k, v in params.items()},
                             jnp.asarray(toks), cfg))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


def test_lfm2_moe_config_round_trips_through_strom_config():
    from nvme_strom_tpu.models.transformer import TransformerConfig
    cfg = convert_llama.config_from_hf(LFM2_MOE)
    assert cfg.mlp_kinds == ("dense", "experts", "experts", "experts")
    assert (cfg.n_experts, cfg.expert_top_k, cfg.d_expert) == (8, 2, 32)
    assert cfg.router_kind == "sigmoid" and cfg.router_bias
    assert cfg.router_norm_topk and cfg.router_scale == 1.0
    assert cfg.rope_theta == 1e6 and cfg.conv_taps == 3 and cfg.qk_norm
    d = convert_llama.strom_config_dict(cfg)
    assert TransformerConfig(**json.loads(json.dumps(d))) == cfg
    # a dense config's file is what it was: none of the new keys
    plain = convert_llama.strom_config_dict(convert_llama.config_from_hf(
        dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=4, intermediate_size=64)))
    assert not {"mlp_kinds", "layer_kinds", "router_kind"} & set(plain)


@pytest.mark.parametrize("key,value,msg", [
    ("conv_bias", True, "conv_bias"),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}, "rope_type"),
    ("num_experts_per_tok", 9, "num_experts_per_tok"),
    ("layer_types", ["conv", "mamba", "full_attention", "conv"],
     "layer_types"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_lfm2_moe_config_raises_on_what_is_not_implemented(key, value, msg):
    with pytest.raises(ValueError, match=msg):
        convert_llama.config_from_hf(dict(LFM2_MOE, **{key: value}))


def test_convert_stacks_the_experts_of_a_layer(tmp_path):
    """An lfm2_moe checkpoint under the assumed names: every expert's three
    matrices land stacked (experts, in, out), and a missing expert is an
    error, not a short stack."""
    from nvme_strom_tpu.formats.safetensors import write_safetensors
    hf = dict(LFM2_MOE, num_hidden_layers=1, layer_types=["conv"],
              num_dense_layers=0, num_experts=2, num_experts_per_tok=1)
    rng = np.random.default_rng(1)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)   # noqa: E731
    L = "model.layers.0."
    tensors = {
        "model.embed_tokens.weight": f32(256, 64),
        "model.embedding_norm.weight": f32(64),
        L + "operator_norm.weight": f32(64), L + "ffn_norm.weight": f32(64),
        L + "conv.in_proj.weight": f32(192, 64),
        L + "conv.conv.weight": f32(64, 1, 3),
        L + "conv.out_proj.weight": f32(64, 64),
        L + "feed_forward.gate.weight": f32(2, 64),
        L + "feed_forward.expert_bias": f32(2)}
    for e in range(2):
        E = f"{L}feed_forward.experts.{e}."
        tensors.update({E + "w1.weight": f32(32, 64),
                        E + "w3.weight": f32(32, 64),
                        E + "w2.weight": f32(64, 32)})
    src = tmp_path / "hf"
    src.mkdir()

    def write(ts):
        write_safetensors(str(src / "model.safetensors"), ts)
        (src / "config.json").write_text(json.dumps(hf))
    write(tensors)
    out = str(tmp_path / "out")
    assert convert_llama.convert(str(src), out)["skipped"] == []
    cfg, params = _load_converted(out)
    assert cfg.mlp_kinds == ("experts",)
    assert params["layers.0.moe_w_gate"].shape == (2, 64, 32)
    assert params["layers.0.moe_w_down"].shape == (2, 32, 64)
    np.testing.assert_array_equal(
        params["layers.0.moe_w_up"][1],
        tensors[L + "feed_forward.experts.1.w3.weight"].T)
    np.testing.assert_array_equal(params["layers.0.router"],
                                  tensors[L + "feed_forward.gate.weight"].T)
    del tensors[L + "feed_forward.experts.1.w2.weight"]
    write(tensors)
    with pytest.raises(ValueError, match="expert matrices missing"):
        convert_llama.convert(str(src), str(tmp_path / "out2"))


# -- deepseek_v3 / kimi_k2: latent attention, routed + shared experts -------

@pytest.fixture(scope="module")
def hf_deepseek(tmp_path_factory):
    if not hasattr(transformers, "DeepseekV3ForCausalLM"):
        pytest.skip("this transformers has no DeepseekV3")
    d = tmp_path_factory.mktemp("hf_deepseek")
    cfg = transformers.DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, n_shared_experts=1,
        n_routed_experts=8, routed_scaling_factor=2.827, kv_lora_rank=16,
        q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=8,
        qk_nope_head_dim=8, n_group=1, topk_group=1, num_experts_per_tok=3,
        first_k_dense_replace=1, norm_topk_prob=True,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=50000.0,
        rope_scaling={"type": "yarn", "rope_type": "yarn", "factor": 4.0,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                      "mscale_all_dim": 1.0,
                      "original_max_position_embeddings": 16},
        attention_bias=False, tie_word_embeddings=False)
    torch.manual_seed(0)
    model = transformers.DeepseekV3ForCausalLM(cfg).eval()
    with torch.no_grad():       # norms and the selection bias off their init
        for name, p in list(model.named_parameters()) + list(
                model.named_buffers()):
            if name.endswith(("norm.weight", "layernorm.weight")):
                p.add_(0.1 * torch.randn_like(p))
            elif name.endswith("e_score_correction_bias"):
                p.add_(0.2 * torch.randn_like(p))
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_deepseek_logits_match_hf(hf_deepseek, tmp_path):
    """Converted deepseek_v3 weights (kimi_k2's architecture) through
    ``forward`` — latent attention in its expanded form through the blocked
    kernel in interpret mode — against transformers' own forward: the tensor
    names, the rotary features de-interleaved into the weights, YaRN's
    frequencies (factor 4 over 16 positions, so the blend is in play at 21)
    and the m^2 in the softmax scale, top-3 of 8 sigmoid scores by score +
    bias, the weights' normalisation and 2.827, the shared expert, the dense
    first layer and the untied head all line up."""
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import forward
    hf_dir, model = hf_deepseek
    out = str(tmp_path / "converted")
    summary = convert_llama.convert(hf_dir, out)
    assert summary["skipped"] == []
    cfg, params = _load_converted(out)
    assert cfg.latent and cfg.mlp_kinds == ("dense", "experts", "experts")
    assert cfg.d_shared == 32 and cfg.experts_held == 0
    assert params["layers.1.moe_w_gate"].shape == (8, 64, 32)
    toks = np.random.default_rng(0).integers(0, 256, (2, 21))
    with torch.no_grad():
        ref = model(torch.from_numpy(toks)).logits.float().numpy()
    ours = np.asarray(forward(params, jnp.asarray(toks, jnp.int32), cfg))
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)


# -- mimo_v2: window and full attention mixed, a fused qkv projection --------

def test_mimo_v2_converts_the_fused_projection_by_each_layers_heads(tmp_path):
    """A mimo_v2 checkpoint under the assumed names: ``qkv_proj`` (rows [q |
    k | v]) splits into wq, wk, wv by the LAYER's head counts and widths —
    a full layer's 1 KV head, a window layer's 2, keys 24 and values 16
    wide —, a window layer's sinks land as ``sink``, the experts stack, the
    config round-trips through ``strom_config.json``."""
    import test_swa
    from nvme_strom_tpu.formats.safetensors import write_safetensors
    from nvme_strom_tpu.models.transformer import TransformerConfig
    hf = dict(test_swa.HF, num_hidden_layers=2, hybrid_layer_pattern=[0, 1],
              moe_layer_freq=[0, 1], expert_share=None)
    cfg = convert_llama.config_from_hf(hf)
    d = convert_llama.strom_config_dict(cfg)
    assert TransformerConfig(**json.loads(json.dumps(d))) == cfg
    assert (d["qk_head_dim"], d["v_head_dim"], d["rotary_dim"], d["window"],
            d["window_kv_heads"]) == (24, 16, 8, 16, 2)
    rng = np.random.default_rng(2)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)   # noqa: E731
    nq, tensors = 4 * 24, {
        "model.embed_tokens.weight": f32(96, 64),
        "model.norm.weight": f32(64), "lm_head.weight": f32(96, 64)}
    for i, nkv in enumerate((1, 2)):
        L = f"model.layers.{i}."
        tensors.update({
            L + "input_layernorm.weight": f32(64),
            L + "post_attention_layernorm.weight": f32(64),
            L + "self_attn.qkv_proj.weight": f32(nq + nkv * (24 + 16), 64),
            L + "self_attn.o_proj.weight": f32(64, 4 * 16)})
    tensors.update({
        "model.layers.0.mlp.gate_proj.weight": f32(128, 64),
        "model.layers.0.mlp.up_proj.weight": f32(128, 64),
        "model.layers.0.mlp.down_proj.weight": f32(64, 128),
        "model.layers.1.self_attn.attention_sink_bias": f32(4),
        "model.layers.1.mlp.gate.weight": f32(4, 64),
        "model.layers.1.mlp.gate.e_score_correction_bias": f32(4)})
    for e in range(4):
        E = f"model.layers.1.mlp.experts.{e}."
        tensors.update({E + "gate_proj.weight": f32(32, 64),
                        E + "up_proj.weight": f32(32, 64),
                        E + "down_proj.weight": f32(64, 32)})
    src = tmp_path / "hf"
    src.mkdir()
    write_safetensors(str(src / "model.safetensors"), tensors)
    (src / "config.json").write_text(json.dumps(hf))
    out = str(tmp_path / "out")
    assert convert_llama.convert(str(src), out)["skipped"] == []
    got, params = _load_converted(out, dtype=cfg.dtype)
    assert got == cfg
    for i, nkv in enumerate((1, 2)):
        fused = tensors[f"model.layers.{i}.self_attn.qkv_proj.weight"].T
        L = f"layers.{i}."
        assert params[L + "wk"].shape == (64, nkv * 24)
        assert params[L + "wv"].shape == (64, nkv * 16)
        np.testing.assert_array_equal(params[L + "wq"], fused[:, :nq])
        np.testing.assert_array_equal(params[L + "wk"],
                                      fused[:, nq:nq + nkv * 24])
        np.testing.assert_array_equal(params[L + "wv"],
                                      fused[:, nq + nkv * 24:])
    np.testing.assert_array_equal(
        params["layers.1.sink"],
        tensors["model.layers.1.self_attn.attention_sink_bias"])
    assert params["layers.1.moe_w_gate"].shape == (4, 64, 32)
    assert "layers.0.sink" not in params


@pytest.mark.parametrize("key,value,msg", [
    ("add_full_attention_sink_bias", True, "only window layers"),
    ("swa_v_head_dim", 32, "swa_v_head_dim"),
    ("scoring_func", "softmax", "only sigmoid"),
    ("moe_layer_freq", [0, 1, 2, 1, 1], "a 0 or a 1"),
])
def test_mimo_v2_config_raises_on_what_is_not_implemented(key, value, msg):
    import test_swa
    with pytest.raises(ValueError, match=msg):
        convert_llama.config_from_hf(dict(test_swa.HF, **{key: value}))


# -- qwen3_next: gated-delta-rule layers, gated attention, a gated shared ----
# -- expert, zero-centred norms ---------------------------------------------

#: the catalog row's ``config`` (model-configs/architectures.jsonl,
#: Qwen3-Next-80B-A3B-Instruct), key for key
QWEN3_NEXT_ROW = dict(
    decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
    hidden_act="silu", hidden_size=2048, intermediate_size=5120,
    linear_conv_kernel_dim=4, linear_key_head_dim=128,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_value_head_dim=128, max_position_embeddings=262144,
    mlp_only_layers=[], model_type="qwen3_next", moe_intermediate_size=512,
    norm_topk_prob=True, num_attention_heads=16, num_experts=512,
    num_experts_per_tok=10, num_hidden_layers=48, num_key_value_heads=2,
    partial_rotary_factor=0.25, rms_norm_eps=1e-06, rope_scaling=None,
    rope_theta=10000000, shared_expert_intermediate_size=512,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936)


def test_qwen3_next_config_from_the_catalog_rows_keys():
    """The published keys give the published model: 36 delta-rule layers and
    12 full ones 3 : 1, 16 / 32 heads of 128 and 4 taps, 16 / 2 heads of 256
    with rotary on 64, gate, q/k norms, 512 softmax-routed experts of 512
    top-10 beside a gated shared expert of 512; a file that states a share
    holds its experts and keeps the router whole; the config round-trips
    through ``strom_config.json``."""
    from nvme_strom_tpu.models.transformer import TransformerConfig
    cfg = convert_llama.config_from_hf(QWEN3_NEXT_ROW)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attention") * 12
    assert cfg.mlp_kinds == ("experts",) * 48
    assert (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim,
            cfg.gdn_conv, cfg.gdn_conv_dim) == (16, 32, 128, 128, 4, 8192)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.v_dim,
            cfg.rotary_dim) == (16, 2, 256, 256, 64)
    assert cfg.attn_gate and cfg.qk_norm and cfg.stated_kv
    assert cfg.rope_theta == 1e7 and cfg.norm_eps == 1e-6
    assert (cfg.n_experts, cfg.expert_top_k, cfg.d_expert, cfg.d_shared,
            cfg.experts_held) == (512, 10, 512, 512, 0)
    assert cfg.router_kind == "softmax" and cfg.router_norm_topk \
        and not cfg.router_bias and cfg.shared_gate and not cfg.tie_embed
    assert len(cfg.recurrent_layers) == len(cfg.state_layers) == 36
    assert len(cfg.attn_layers) == 12
    d = convert_llama.strom_config_dict(cfg)
    assert TransformerConfig(**json.loads(json.dumps(d))) == cfg
    share = convert_llama.config_from_hf(dict(
        QWEN3_NEXT_ROW, num_hidden_layers=16, num_experts=32,
        expert_share={"routed": 512, "offset": 64, "chips": 16}))
    assert (share.n_experts, share.experts_held, share.expert_offset,
            share.experts_local) == (512, 32, 64, 32)
    assert share.layer_kinds == ("gdn", "gdn", "gdn", "attention") * 4


@pytest.mark.parametrize("key,value,msg", [
    ("decoder_sparse_step", 2, "every layer holds experts"),
    ("mlp_only_layers", [0], "every layer holds experts"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("linear_num_value_heads", 24, "no multiple"),
    ("layer_types", ["sliding_attention"] * 48, "layer_types"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_qwen3_next_config_raises_on_what_is_not_implemented(key, value, msg):
    with pytest.raises(ValueError, match=msg):
        convert_llama.config_from_hf(dict(QWEN3_NEXT_ROW, **{key: value}))


def test_qwen3_next_deinterleave_on_a_hand_built_tensor():
    """``in_proj_qkvz`` lies [q 2 | k 2 | v 3x2 | z 3x2] a key head (2 key
    heads of 2, 6 value heads of 2: three a key head) and ``in_proj_ba`` [b
    3 | a 3] a key head; the converter leaves all q, then all k, all v, all
    z — each column told by a number that says (part, key head, place)."""
    from nvme_strom_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(n_layers=1, layer_kinds=("gdn",), gdn_k_heads=2,
                            gdn_v_heads=6, gdn_k_dim=2, gdn_v_dim=2)
    parts = (("q", 2), ("k", 2), ("v", 6), ("z", 6))
    code = {"q": 1000, "k": 2000, "v": 3000, "z": 4000, "b": 5000, "a": 6000}
    cols = [code[p] + 100 * head + j for head in range(2)
            for p, width in parts for j in range(width)]
    w = np.stack([np.asarray(cols), -np.asarray(cols)]).astype(np.float32)
    out = convert_llama._deinterleave_gdn(w, cfg, (2, 2, 6, 6))
    want = [code[p] + 100 * head + j for p, width in parts
            for head in range(2) for j in range(width)]
    np.testing.assert_array_equal(out[0], want)
    np.testing.assert_array_equal(out[1], -np.asarray(want))
    # value head 4 = key head 1's second: columns 2..3 of its v part
    np.testing.assert_array_equal(out[0, 8 + 4 * 2:8 + 5 * 2], [3102, 3103])
    ba = np.asarray([[code[p] + 100 * head + j for head in range(2)
                      for p in "ba" for j in range(3)]], np.float32)
    np.testing.assert_array_equal(
        convert_llama._deinterleave_gdn(ba, cfg, (3, 3))[0],
        [5000, 5001, 5002, 5100, 5101, 5102,
         6000, 6001, 6002, 6100, 6101, 6102])


@pytest.fixture(scope="module")
def hf_qwen3_next(tmp_path_factory):
    if not hasattr(transformers, "Qwen3NextForCausalLM"):
        pytest.skip("this transformers has no Qwen3Next")
    d = tmp_path_factory.mktemp("hf_qwen3_next")
    cfg = transformers.Qwen3NextConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, partial_rotary_factor=0.25, rope_theta=50000.0,
        max_position_embeddings=128, rms_norm_eps=1e-6,
        full_attention_interval=4, linear_conv_kernel_dim=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        decoder_sparse_step=1, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts_per_tok=3,
        num_experts=8, norm_topk_prob=True, mlp_only_layers=[],
        attention_bias=False, tie_word_embeddings=False)
    torch.manual_seed(0)
    model = transformers.Qwen3NextForCausalLM(cfg).eval()
    with torch.no_grad():       # every norm, decay and gate off its init
        for name, p in model.named_parameters():
            if name.endswith(("norm.weight", "layernorm.weight", "dt_bias")):
                p.add_(0.3 * torch.randn_like(p))
            elif name.endswith("A_log"):
                p.copy_(torch.log(torch.rand_like(p) * 4 + 0.05))
            elif name.endswith(("shared_expert_gate.weight",
                                "in_proj_ba.weight")):
                p.mul_(20.0)
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_qwen3_next_logits_match_hf(hf_qwen3_next, tmp_path):
    """Converted qwen3_next weights through ``decode.block_step`` — the
    delta rule through the chunked scan kernel in interpret mode over a
    prompt of 150 rows (three chunks, the last ragged), gated attention
    through the blocked kernel — against transformers' own forward: the
    tensor names, ``in_proj_qkvz`` / ``in_proj_ba`` de-interleaved by key
    head, the conv's channel order, the L2 norms and q's scale, β and the
    decay, the gated per-head norm, the (q | gate) halves of ``q_proj``,
    rotary on the first 8 of 32, every zero-centred norm stored as 1 + w,
    top-3 of 8 softmax scores renormalised, the gated shared expert and the
    untied head all line up."""
    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models import decode
    hf_dir, model = hf_qwen3_next
    out = str(tmp_path / "converted")
    summary = convert_llama.convert(hf_dir, out)
    assert summary["skipped"] == []
    cfg, params = _load_converted(out)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attention")
    assert cfg.attn_gate and cfg.shared_gate and cfg.rotary_dim == 8
    assert params["layers.0.gdn_in"].shape == (64, 2 * 32 + 2 * 64)
    assert params["layers.3.wq"].shape == (64, 4 * 2 * 32)
    assert params["layers.1.shared_gate"].shape == (64, 1)
    toks = np.random.default_rng(0).integers(0, 256, (2, 150))
    with torch.no_grad():
        ref = model(torch.from_numpy(toks)).logits.float().numpy()
    with jax.default_matmul_precision("highest"):
        ours, _ = decode.block_step(params, jnp.asarray(toks, jnp.int32),
                                    cfg, decode.init_cache(cfg, 2, 160))
    np.testing.assert_allclose(np.asarray(ours), ref, atol=3e-4, rtol=3e-4)
