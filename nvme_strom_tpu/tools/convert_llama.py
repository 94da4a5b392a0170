"""convert_llama — HuggingFace Llama checkpoints → nvme_strom_tpu layout.

BASELINE config 4's story is "Llama-3 8B safetensors weight shards on NVMe
→ lazy HBM param load"; real shards come from the HF hub with HF naming
(``model.layers.N.self_attn.q_proj.weight``, (out, in) layout) while
:mod:`nvme_strom_tpu.models.transformer` names them ``layers.N.wq`` with
(in, out) layout.  This tool converts once, offline, on host (copies here
are deliberate and off the hot path); after conversion
``parallel.weights.LazyCheckpoint`` serves the shards with per-device
ranged O_DIRECT reads like any native checkpoint.

Semantic parity notes (verified by tests/test_convert_llama.py against
``transformers``' reference implementation):

- RoPE: both implementations rotate half-split features with
  ``theta^(-i/half)`` frequencies — identical convention, so NO head-dim
  permutation is needed (unlike Meta→HF conversions).
- rms_norm epsilon-inside-rsqrt, SiLU-gated MLP, GQA via head repeat,
  1/sqrt(head_dim) attention scale: all match.
- Projection weights transpose (HF nn.Linear stores (out, in)); the token
  embedding is (vocab, d) on both sides and copies as-is; tied embeddings
  (``tie_word_embeddings``) materialize an explicit transposed ``lm_head``.

Usage:
    python -m nvme_strom_tpu.tools.convert_llama HF_DIR OUT_DIR \
        [--shard-bytes BYTES]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

_LAYER_RULES: Tuple[Tuple[str, str, bool], ...] = (
    # (HF suffix, our suffix, transpose)
    ("self_attn.q_proj.weight", "wq", True),
    ("self_attn.k_proj.weight", "wk", True),
    ("self_attn.v_proj.weight", "wv", True),
    ("self_attn.o_proj.weight", "wo", True),
    ("mlp.gate_proj.weight", "w_gate", True),
    ("mlp.up_proj.weight", "w_up", True),
    ("mlp.down_proj.weight", "w_down", True),
    ("input_layernorm.weight", "attn_norm", False),
    ("post_attention_layernorm.weight", "mlp_norm", False),
    # granitemoehybrid: the Mamba-2 mixer (models/ssm.py) and the shared MLP.
    # conv1d.weight is (conv_dim, 1, K): squeezed to (conv_dim, K), then
    # transposed like a Linear to our (K, conv_dim).  input_linear stacks
    # [gate; up] along its output rows: convert() splits "w_gate_up".
    ("mamba.in_proj.weight", "ssm_in", True),
    ("mamba.out_proj.weight", "ssm_out", True),
    ("mamba.conv1d.weight", "ssm_conv_w", True),
    ("mamba.conv1d.bias", "ssm_conv_b", False),
    ("mamba.dt_bias", "ssm_dt_bias", False),
    ("mamba.A_log", "ssm_A_log", False),
    ("mamba.D", "ssm_D", False),
    ("mamba.norm.weight", "ssm_norm", False),
    ("shared_mlp.input_linear.weight", "w_gate_up", True),
    ("shared_mlp.output_linear.weight", "w_down", True),
    # lfm2 / lfm2_moe: the gated short conv (models/ssm.py conv_block; its
    # conv.weight is (d, 1, K) like Mamba's), q/k norms, w1/w3/w2 for
    # gate/up/down, the family's own norm names.  The MoE block's names
    # (feed_forward.gate, .expert_bias, .experts.E.w1/w3/w2) are ASSUMED from
    # the dense half of the family: the installed transformers has lfm2 only.
    ("conv.in_proj.weight", "conv_in", True),
    ("conv.out_proj.weight", "conv_out", True),
    ("conv.conv.weight", "conv_w", True),
    ("self_attn.out_proj.weight", "wo", True),
    ("self_attn.q_layernorm.weight", "q_norm", False),
    ("self_attn.k_layernorm.weight", "k_norm", False),
    ("feed_forward.w1.weight", "w_gate", True),
    ("feed_forward.w3.weight", "w_up", True),
    ("feed_forward.w2.weight", "w_down", True),
    ("operator_norm.weight", "attn_norm", False),
    ("ffn_norm.weight", "mlp_norm", False),
    ("feed_forward.gate.weight", "router", True),
    ("feed_forward.expert_bias", "router_bias", False),
    # deepseek_v3 / kimi_k2: latent attention (models/mla.py), the router
    # with its selection bias, the shared expert(s) as one gated MLP.  The
    # names are transformers' deepseek_v3's; kimi_k2 ships its own modelling
    # file, ASSUMED to keep them.
    ("self_attn.q_a_proj.weight", "wq_a", True),
    ("self_attn.q_a_layernorm.weight", "q_a_norm", False),
    ("self_attn.q_b_proj.weight", "wq_b", True),
    ("self_attn.kv_a_proj_with_mqa.weight", "wkv_a", True),
    ("self_attn.kv_a_layernorm.weight", "kv_a_norm", False),
    ("self_attn.kv_b_proj.weight", "wkv_b", True),
    # (``mlp.gate.weight`` and ``mlp.experts.E.*_proj.weight`` are Qwen3-MoE's
    # too, which sdar_moe is ASSUMED to keep: it ships its own modelling file)
    ("mlp.gate.weight", "router", True),
    ("mlp.gate.e_score_correction_bias", "router_bias", False),
    ("mlp.shared_experts.gate_proj.weight", "shared_w_gate", True),
    ("mlp.shared_experts.up_proj.weight", "shared_w_up", True),
    ("mlp.shared_experts.down_proj.weight", "shared_w_down", True),
    # mimo_v2: q, k and v as ONE projection (``attention_projection_layout``
    # fused_qkv; convert() splits "wqkv" by the layer's head counts and
    # widths) and a window layer's learned sink, one scalar a head.  Both
    # names and the fused rows' order [q | k | v] are ASSUMED: the model
    # ships its own modelling file, which could not be read here.
    ("self_attn.qkv_proj.weight", "wqkv", True),
    ("self_attn.attention_sink_bias", "sink", False),
    # qwen3_next: the gated-delta-rule mixer (models/ssm.py gdn_block).
    # in_proj_qkvz and in_proj_ba interleave their output rows by KEY head
    # ([q | k | v | z] and [b | a] a head): convert() de-interleaves
    # "gdn_in" / "gdn_ba" (``_deinterleave_gdn``).  conv1d.weight is
    # (channels, 1, K) over q | k | v in that order, no bias.  q_proj holds
    # the output gate beside each head's query, (q | g) a head, as
    # ``qkvg_project`` reads it.  Every norm but linear_attn.norm is
    # zero-centred: convert() stores 1 + w (``_ZERO_CENTRED``).  The names
    # are transformers' qwen3_next's as remembered: ASSUMED, no checkpoint
    # was there to check.
    ("linear_attn.in_proj_qkvz.weight", "gdn_in", True),
    ("linear_attn.in_proj_ba.weight", "gdn_ba", True),
    ("linear_attn.conv1d.weight", "gdn_conv_w", True),
    ("linear_attn.dt_bias", "gdn_dt_bias", False),
    ("linear_attn.A_log", "gdn_A_log", False),
    ("linear_attn.norm.weight", "gdn_norm", False),
    ("linear_attn.out_proj.weight", "gdn_out", True),
    ("self_attn.q_norm.weight", "q_norm", False),
    ("self_attn.k_norm.weight", "k_norm", False),
    ("mlp.shared_expert.gate_proj.weight", "shared_w_gate", True),
    ("mlp.shared_expert.up_proj.weight", "shared_w_up", True),
    ("mlp.shared_expert.down_proj.weight", "shared_w_down", True),
    ("mlp.shared_expert_gate.weight", "shared_gate", True),
    # olmo_hybrid: Olmo 2/3's block (its two norms stand AFTER the
    # sub-layers: ``_OLMO_NORMS`` renames them for that model type) and FLA's
    # GatedDeltaNet, which keeps q, k, v, the gate, a and b as six
    # projections and three depthwise convs: convert() lays them side by
    # side into "gdn_in" [q | k | v | g], "gdn_ba" [b | a] and "gdn_conv_w"
    # (q | k | v) (``_GDN_PARTS``).  The names are FLA's and Olmo3's:
    # ASSUMED, no checkpoint was there to check.
    ("linear_attn.q_proj.weight", "gdn_in.0", True),
    ("linear_attn.k_proj.weight", "gdn_in.1", True),
    ("linear_attn.v_proj.weight", "gdn_in.2", True),
    ("linear_attn.g_proj.weight", "gdn_in.3", True),
    ("linear_attn.b_proj.weight", "gdn_ba.0", True),
    ("linear_attn.a_proj.weight", "gdn_ba.1", True),
    ("linear_attn.q_conv1d.weight", "gdn_conv_w.0", True),
    ("linear_attn.k_conv1d.weight", "gdn_conv_w.1", True),
    ("linear_attn.v_conv1d.weight", "gdn_conv_w.2", True),
    ("linear_attn.o_norm.weight", "gdn_norm", False),
    ("linear_attn.o_proj.weight", "gdn_out", True),
    ("post_feedforward_layernorm.weight", "mlp_norm", False),
)

#: leaves convert() assembles from parts "leaf.j", side by side along the
#: output columns, once all of them are in: leaf -> number of parts
_GDN_PARTS = {"gdn_in": 4, "gdn_ba": 2, "gdn_conv_w": 3}
#: olmo_hybrid (post-norm): the norm after attention is the MIXER's, where
#: the Llama family's tensor of that name is the MLP's (HF suffix -> leaf)
_OLMO_NORMS = {"post_attention_layernorm.weight": "attn_norm"}

#: the norms a qwen3_next checkpoint stores zero-centred (the model computes
#: x̂ ⊙ (1 + w)); this model multiplies by the stored weight, so convert()
#: stores 1 + w, in float32
_ZERO_CENTRED = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "k_norm")

#: one expert's matrices: convert() stacks them into moe_w_* (E, in, out)
_EXPERT_RE = re.compile(r"^(?:feed_forward|mlp)\.experts\.(\d+)\."
                        r"(w[123]|gate_proj|up_proj|down_proj)\.weight$")
_EXPERT_LEAF = {"w1": "moe_w_gate", "w3": "moe_w_up", "w2": "moe_w_down",
                "gate_proj": "moe_w_gate", "up_proj": "moe_w_up",
                "down_proj": "moe_w_down"}

_TOP_RULES: Dict[str, Tuple[str, bool]] = {
    "model.embed_tokens.weight": ("tok_embed", False),
    "model.norm.weight": ("final_norm", False),
    "model.embedding_norm.weight": ("final_norm", False),       # lfm2
    "lm_head.weight": ("lm_head", True),
}

_LAYER_RE = re.compile(r"^model\.layers\.(\d+)\.(.+)$")

#: non-weight buffers some exports carry — safe to drop silently.  Any
#: OTHER unmapped tensor is a hard error: a bias or adapter weight we
#: drop would convert into a complete-looking but numerically wrong model.
_SKIP_OK_RE = re.compile(r"rotary_emb\.inv_freq$")


def map_name(hf_name: str) -> Optional[Tuple[str, bool]]:
    """HF tensor name → (our name, needs_transpose); None = not mapped
    (convert() decides whether that's a benign buffer or an error)."""
    if hf_name in _TOP_RULES:
        return _TOP_RULES[hf_name]
    m = _LAYER_RE.match(hf_name)
    if m:
        idx, rest = m.group(1), m.group(2)
        for hf_suffix, ours, tr in _LAYER_RULES:
            if rest == hf_suffix:
                return f"layers.{idx}.{ours}", tr
        e = _EXPERT_RE.match(rest)
        if e:       # "…moe_w_gate.7": expert 7's slice, stacked by convert()
            return f"layers.{idx}.{_EXPERT_LEAF[e.group(2)]}.{e.group(1)}", \
                True
    return None


def _hybrid_fields(hf_cfg: dict) -> dict:
    """The TransformerConfig fields of a ``granitemoehybrid`` config
    (Mamba-2 layers beside attention, no routed experts), beyond the dense
    family's.  Raises on what the model does not implement."""
    def refuse(what):
        raise ValueError(f"unsupported granitemoehybrid config: {what}")
    if hf_cfg.get("num_local_experts", 0) or hf_cfg.get("num_experts_per_tok",
                                                         0):
        refuse(f"num_local_experts={hf_cfg.get('num_local_experts')} "
               "(routed experts; only the shared MLP is implemented)")
    if hf_cfg.get("mamba_n_groups", 1) != 1:
        refuse(f"mamba_n_groups={hf_cfg['mamba_n_groups']} (one B/C group)")
    if hf_cfg.get("mamba_proj_bias"):
        refuse("mamba_proj_bias=True (projections have no bias)")
    if not hf_cfg.get("mamba_conv_bias", True):
        refuse("mamba_conv_bias=False (the conv carries a bias)")
    if hf_cfg.get("position_embedding_type", "rope") != "nope":
        refuse(f"position_embedding_type="
               f"{hf_cfg.get('position_embedding_type')!r} (only 'nope': "
               "hybrid attention layers take no rotary)")
    if hf_cfg.get("normalization_function", "rmsnorm") != "rmsnorm":
        refuse(f"normalization_function="
               f"{hf_cfg['normalization_function']!r}")
    ff = hf_cfg.get("shared_intermediate_size", hf_cfg["intermediate_size"])
    if ff != hf_cfg["intermediate_size"]:
        refuse(f"shared_intermediate_size {ff} != intermediate_size "
               f"{hf_cfg['intermediate_size']}")
    heads, p = hf_cfg["mamba_n_heads"], hf_cfg["mamba_d_head"]
    if heads * p != hf_cfg.get("mamba_expand", 2) * hf_cfg["hidden_size"]:
        refuse(f"mamba_n_heads x mamba_d_head = {heads * p} is not "
               "mamba_expand x hidden_size")
    return dict(
        layer_kinds=tuple(hf_cfg["layer_types"]),   # checked by the config
        ssm_heads=heads, ssm_head_dim=p,
        ssm_state=hf_cfg["mamba_d_state"], ssm_conv=hf_cfg["mamba_d_conv"],
        ssm_chunk=hf_cfg.get("mamba_chunk_size", 256),
        embed_mult=float(hf_cfg.get("embedding_multiplier", 1.0)),
        residual_mult=float(hf_cfg.get("residual_multiplier", 1.0)),
        logits_div=float(hf_cfg.get("logits_scaling", 1.0)),
        attn_scale=float(hf_cfg["attention_multiplier"]),
        rope=False, tie_embed=bool(hf_cfg.get("tie_word_embeddings", False)))


def _lfm2_fields(hf_cfg: dict) -> dict:
    """The TransformerConfig fields of an ``lfm2`` / ``lfm2_moe`` config
    (gated short convs beside q/k-normed GQA; in ``lfm2_moe`` sigmoid-routed
    experts after ``num_dense_layers`` dense MLPs), beyond the dense
    family's.  Raises on what the model does not implement."""
    def refuse(what):
        raise ValueError(f"unsupported {hf_cfg['model_type']} config: {what}")
    if hf_cfg.get("conv_bias"):
        refuse("conv_bias=True (the short conv and its projections have "
               "no bias)")
    rope = hf_cfg.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        refuse(f"rope_type {rope.get('rope_type')!r} (only 'default')")
    kinds = tuple("attention" if k == "full_attention" else k
                  for k in hf_cfg["layer_types"])
    if set(kinds) - {"attention", "conv"}:
        refuse(f"layer_types {sorted(set(hf_cfg['layer_types']))}")
    out = dict(layer_kinds=kinds, conv_taps=int(hf_cfg.get("conv_L_cache", 3)),
               qk_norm=True,
               tie_embed=bool(hf_cfg.get("tie_word_embeddings",
                                         hf_cfg.get("tie_embedding", True))))
    if "rope_theta" in rope:
        out["rope_theta"] = float(rope["rope_theta"])
    if hf_cfg["model_type"] == "lfm2":
        ff = hf_cfg.get("block_ff_dim", hf_cfg["intermediate_size"])
        if hf_cfg.get("block_auto_adjust_ff_dim", True):
            # Lfm2MLP's own rule: 2/3 of the stated width, times the
            # multiplier, rounded up to block_multiple_of
            ff = int(2 * ff / 3)
            mult = hf_cfg.get("block_ffn_dim_multiplier", 1.0)
            if mult is not None:
                mo = hf_cfg.get("block_multiple_of", 256)
                ff = mo * ((int(mult * ff) + mo - 1) // mo)
        out["d_ff"] = ff
        return out
    n_exp, k = hf_cfg["num_experts"], hf_cfg["num_experts_per_tok"]
    if not 0 < k <= n_exp:
        refuse(f"num_experts_per_tok={k} of num_experts={n_exp}")
    dense = hf_cfg.get("num_dense_layers", 0)
    n = len(kinds)
    out.update(
        mlp_kinds=tuple("dense" if i < dense else "experts"
                        for i in range(n)),
        n_experts=n_exp, expert_top_k=k,
        d_expert=hf_cfg["moe_intermediate_size"], router_kind="sigmoid",
        router_bias=bool(hf_cfg.get("use_expert_bias", False)),
        router_norm_topk=bool(hf_cfg.get("norm_topk_prob", True)),
        router_scale=float(hf_cfg.get("routed_scaling_factor", 1.0)))
    return out


def _mla_fields(hf_cfg: dict) -> dict:
    """The TransformerConfig fields of a ``deepseek_v3`` / ``kimi_k2``
    config (latent attention in every layer, ``first_k_dense_replace`` dense
    MLPs then sigmoid-routed experts beside shared ones, YaRN), beyond the
    dense family's.  A file that states one device's share of a deployment
    carries ``expert_share`` ({"routed": the router's published width,
    "offset": the first expert held}) beside ``n_routed_experts``, which
    then counts the experts held.  Raises on what the model does not
    implement."""
    import math

    def refuse(what):
        raise ValueError(f"unsupported {hf_cfg['model_type']} config: {what}")
    for knob in ("n_group", "topk_group"):
        if hf_cfg.get(knob, 1) > 1:
            refuse(f"{knob}={hf_cfg[knob]}: the router picks its top-k among "
                   "all the experts; choosing groups of experts first is not "
                   "implemented")
    if hf_cfg.get("scoring_func", "sigmoid") != "sigmoid":
        refuse(f"scoring_func {hf_cfg['scoring_func']!r} (only 'sigmoid')")
    if hf_cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        refuse(f"topk_method {hf_cfg['topk_method']!r} (only 'noaux_tc': "
               "top-k of score + e_score_correction_bias)")
    if hf_cfg.get("moe_layer_freq", 1) != 1:
        refuse(f"moe_layer_freq={hf_cfg['moe_layer_freq']} (every layer "
               "past the dense ones holds experts)")
    if not hf_cfg.get("rope_interleave", True):
        refuse("rope_interleave=False (the converter folds HF's "
               "de-interleave of the rotary features into the weights)")
    if hf_cfg.get("num_nextn_predict_layers", 0):
        refuse("num_nextn_predict_layers > 0 (no multi-token prediction "
               "head)")
    if not hf_cfg.get("q_lora_rank"):
        refuse("q_lora_rank is not set (queries go through the low-rank "
               "projection)")
    dn, dr = hf_cfg["qk_nope_head_dim"], hf_cfg["qk_rope_head_dim"]
    scale = float(dn + dr) ** -0.5
    scaling = hf_cfg.get("rope_scaling")
    if scaling is not None:
        if scaling.get("rope_type", scaling.get("type")) != "yarn":
            refuse(f"rope_scaling {scaling} (only 'yarn')")
        scaling = {k: v for k, v in scaling.items() if k in (
            "factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
            "original_max_position_embeddings")}
        scaling["rope_type"] = "yarn"
        if scaling.get("mscale_all_dim"):
            # HF DeepseekV3Attention: the softmax scale takes YaRN's
            # temperature twice
            scale *= (0.1 * scaling["mscale_all_dim"]
                      * math.log(scaling["factor"]) + 1.0) ** 2
    share = hf_cfg.get("expert_share") or {}
    held = hf_cfg["n_routed_experts"]
    routed = share.get("routed", held)
    n = hf_cfg["num_hidden_layers"]
    dense = hf_cfg.get("first_k_dense_replace", 0)
    return dict(
        q_lora_rank=hf_cfg["q_lora_rank"], kv_lora_rank=hf_cfg["kv_lora_rank"],
        qk_nope_dim=dn, qk_rope_dim=dr, v_head_dim=hf_cfg["v_head_dim"],
        attn_scale=scale, rope_scaling=scaling,
        mlp_kinds=tuple("dense" if i < dense else "experts"
                        for i in range(n)),
        n_experts=routed, expert_top_k=hf_cfg["num_experts_per_tok"],
        experts_held=held if held != routed else 0,
        expert_offset=share.get("offset", 0),
        d_expert=hf_cfg["moe_intermediate_size"],
        d_shared=(hf_cfg["moe_intermediate_size"]
                  * hf_cfg.get("n_shared_experts", 0)),
        router_kind="sigmoid", router_bias=True,
        router_norm_topk=bool(hf_cfg.get("norm_topk_prob", True)),
        router_scale=float(hf_cfg.get("routed_scaling_factor", 1.0)),
        tie_embed=bool(hf_cfg.get("tie_word_embeddings", False)))


def _mimo_fields(hf_cfg: dict) -> dict:
    """The TransformerConfig fields of a ``mimo_v2`` config (MiMo-V2-Flash /
    V2.5: window and full GQA layers mixed by ``hybrid_layer_pattern``, each
    kind with its own KV-head count and rotary base, stated head widths with
    keys wider than values, rotary on the leading ``partial_rotary_factor``
    of a head, a value scale, a learned sink in the window layers; dense or
    sigmoid-routed expert MLPs by ``moe_layer_freq``), beyond the dense
    family's.  A file that states one device's share of a deployment carries
    ``expert_share`` as ``_mla_fields`` reads it.  Raises on what the model
    does not implement."""
    def refuse(what):
        raise ValueError(f"unsupported mimo_v2 config: {what}")
    n = hf_cfg["num_hidden_layers"]
    pattern, freq = hf_cfg["hybrid_layer_pattern"], hf_cfg["moe_layer_freq"]
    if len(pattern) != n or len(freq) != n or (
            set(pattern) | set(freq)) - {0, 1}:
        refuse(f"hybrid_layer_pattern and moe_layer_freq must hold a 0 or a "
               f"1 for each of the {n} layers")
    hd, vd = hf_cfg["head_dim"], hf_cfg["v_head_dim"]
    for key, want in (("swa_num_attention_heads",
                       hf_cfg["num_attention_heads"]),
                      ("swa_head_dim", hd), ("swa_v_head_dim", vd)):
        if hf_cfg.get(key, want) != want:
            refuse(f"{key}={hf_cfg[key]} (window layers share the full "
                   f"layers' {want})")
    if hf_cfg.get("add_full_attention_sink_bias"):
        refuse("add_full_attention_sink_bias=True (only window layers take "
               "a sink)")
    window = hf_cfg["sliding_window"]
    for key in ("sliding_window_size", "attention_chunk_size"):
        if hf_cfg.get(key) not in (None, window):
            refuse(f"{key}={hf_cfg[key]} beside sliding_window={window} "
                   "(read as one window, no chunked mechanism of its own)")
    scaling = hf_cfg.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        refuse(f"rope_scaling {scaling} (only 'default')")
    for knob in ("n_group", "topk_group"):
        if (hf_cfg.get(knob) or 1) > 1:
            refuse(f"{knob}={hf_cfg[knob]} (no group-limited routing)")
    if hf_cfg.get("scoring_func", "sigmoid") != "sigmoid" or hf_cfg.get(
            "topk_method", "noaux_tc") != "noaux_tc":
        refuse("only sigmoid scores with noaux_tc selection (top-k of score "
               "+ e_score_correction_bias)")
    if hf_cfg.get("n_shared_experts"):
        refuse(f"n_shared_experts={hf_cfg['n_shared_experts']}")
    rotary = int(hd * hf_cfg.get("partial_rotary_factor", 1.0))
    if rotary % 2:
        refuse(f"int(head_dim x partial_rotary_factor) = {rotary} is odd")
    share = hf_cfg.get("expert_share") or {}
    held = hf_cfg["n_routed_experts"]
    routed = share.get("routed", held)
    return dict(
        layer_kinds=tuple("window" if w else "attention" for w in pattern),
        mlp_kinds=tuple("experts" if e else "dense" for e in freq),
        qk_head_dim=hd, v_head_dim=vd,
        rotary_dim=rotary if rotary != hd else 0,
        value_scale=float(hf_cfg.get("attention_value_scale") or 1.0),
        window=window,
        window_kv_heads=hf_cfg.get("swa_num_key_value_heads", 0),
        window_rope_theta=float(hf_cfg.get("swa_rope_theta", 0.0)),
        window_sink=bool(hf_cfg.get("add_swa_attention_sink_bias", False)),
        rope_scaling=None,
        norm_eps=float(hf_cfg["layernorm_epsilon"]),
        n_experts=routed, expert_top_k=hf_cfg["num_experts_per_tok"],
        experts_held=held if held != routed else 0,
        expert_offset=share.get("offset", 0),
        d_expert=hf_cfg["moe_intermediate_size"],
        router_kind="sigmoid", router_bias=True,
        router_norm_topk=bool(hf_cfg.get("norm_topk_prob", True)),
        router_scale=float(hf_cfg.get("routed_scaling_factor") or 1.0),
        tie_embed=bool(hf_cfg.get("tie_word_embeddings", False)))


def _qwen3_next_fields(hf_cfg: dict) -> dict:
    """The TransformerConfig fields of a ``qwen3_next`` config (Qwen3-Next:
    gated-delta-rule layers with every ``full_attention_interval``-th layer
    full GQA attention under an output gate, a stated head width with
    rotary on its leading ``partial_rotary_factor``, q/k head norms,
    zero-centred norms — stored as 1 + w by ``convert`` —, and in every
    layer softmax-routed experts beside one shared expert under a sigmoid
    gate), beyond the dense family's.  A file that states one device's
    share of a deployment carries ``expert_share`` as ``_mla_fields`` reads
    it, beside ``num_experts``, which then counts the experts held.  Raises
    on what the model does not implement."""
    def refuse(what):
        raise ValueError(f"unsupported qwen3_next config: {what}")
    n = hf_cfg["num_hidden_layers"]
    every = hf_cfg["full_attention_interval"]
    types = hf_cfg.get("layer_types") or [
        "linear_attention" if (i + 1) % every else "full_attention"
        for i in range(n)]
    if len(types) != n or set(types) - {"linear_attention",
                                        "full_attention"}:
        refuse(f"layer_types {sorted(set(types))} for {n} layers")
    if hf_cfg.get("decoder_sparse_step", 1) != 1 or hf_cfg.get(
            "mlp_only_layers"):
        refuse("decoder_sparse_step != 1 or mlp_only_layers (every layer "
               "holds experts)")
    if hf_cfg.get("use_sliding_window"):
        refuse("use_sliding_window=True")
    if hf_cfg.get("rope_scaling"):
        refuse(f"rope_scaling {hf_cfg['rope_scaling']}")
    hd = hf_cfg["head_dim"]
    rotary = int(hd * hf_cfg.get("partial_rotary_factor", 1.0))
    if rotary % 2:
        refuse(f"int(head_dim x partial_rotary_factor) = {rotary} is odd")
    hk, hv = hf_cfg["linear_num_key_heads"], hf_cfg["linear_num_value_heads"]
    if hv % hk:
        refuse(f"linear_num_value_heads {hv} is no multiple of "
               f"linear_num_key_heads {hk}")
    share = hf_cfg.get("expert_share") or {}
    held = hf_cfg["num_experts"]
    routed = share.get("routed", held)
    return dict(
        layer_kinds=tuple("gdn" if t == "linear_attention" else "attention"
                          for t in types),
        mlp_kinds=("experts",) * n,
        gdn_k_heads=hk, gdn_v_heads=hv,
        gdn_k_dim=hf_cfg["linear_key_head_dim"],
        gdn_v_dim=hf_cfg["linear_value_head_dim"],
        gdn_conv=hf_cfg["linear_conv_kernel_dim"],
        qk_head_dim=hd, rotary_dim=rotary if rotary != hd else 0,
        qk_norm=True, attn_gate=True, rope_scaling=None,
        n_experts=routed, expert_top_k=hf_cfg["num_experts_per_tok"],
        experts_held=held if held != routed else 0,
        expert_offset=share.get("offset", 0),
        d_expert=hf_cfg["moe_intermediate_size"],
        d_shared=hf_cfg.get("shared_expert_intermediate_size", 0),
        shared_gate=bool(hf_cfg.get("shared_expert_intermediate_size", 0)),
        router_kind="softmax", router_bias=False,
        router_norm_topk=bool(hf_cfg.get("norm_topk_prob", True)),
        tie_embed=bool(hf_cfg.get("tie_word_embeddings", False)))


def _olmo_hybrid_fields(hf_cfg: dict) -> dict:
    """The TransformerConfig fields of an ``olmo_hybrid`` config (Olmo 2/3's
    post-norm block around, by ``layer_types`` read as given, FLA's gated
    delta rule or full attention with q/k norms over the whole projection; a
    dense MLP), beyond the dense family's.  ``rope_parameters.rope_theta``
    null is read as NO rotary (the conv and the recurrence carry position:
    ASSUMED).  Raises on what the model does not implement."""
    def refuse(what):
        raise ValueError(f"unsupported olmo_hybrid config: {what}")
    types = hf_cfg.get("layer_types") or ()
    n = hf_cfg["num_hidden_layers"]
    if len(types) != n or set(types) - {"linear_attention",
                                        "full_attention"}:
        refuse(f"layer_types {sorted(set(types))} over {len(types)} entries "
               f"for {n} layers")
    rope = hf_cfg.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        refuse(f"rope_type {rope.get('rope_type')!r} (only 'default')")
    if hf_cfg.get("sliding_window"):
        refuse(f"sliding_window={hf_cfg['sliding_window']}")
    hk, hv = hf_cfg["linear_num_key_heads"], hf_cfg["linear_num_value_heads"]
    if hv % hk:
        refuse(f"linear_num_value_heads {hv} is no multiple of "
               f"linear_num_key_heads {hk}")
    theta = rope.get("rope_theta")
    out = dict(
        layer_kinds=tuple("gdn" if t == "linear_attention" else "attention"
                          for t in types),
        post_norm=True, qk_norm=True, qk_norm_whole=True,
        gdn_k_heads=hk, gdn_v_heads=hv,
        gdn_k_dim=hf_cfg["linear_key_head_dim"],
        gdn_v_dim=hf_cfg["linear_value_head_dim"],
        gdn_conv=hf_cfg["linear_conv_kernel_dim"],
        gdn_neg_eigval=bool(hf_cfg.get("linear_allow_neg_eigval", False)),
        rope=theta is not None, rope_scaling=None,
        tie_embed=bool(hf_cfg.get("tie_word_embeddings", False)))
    if theta is not None:
        out["rope_theta"] = float(theta)
    return out


#: what an ``sdar_moe`` file may leave unsaid (``_sdar_fields``)
SDAR_BLOCK_LENGTH, SDAR_MASK_TOKEN_ID = 4, 151669


def _sdar_fields(hf_cfg: dict) -> dict:
    """The TransformerConfig fields of an ``sdar_moe`` config (SDAR-MoE: a
    Qwen3-MoE decoder — a stated head width, q/k norms a head at a time,
    softmax-routed experts in every layer with the chosen weights
    renormalised, no shared expert — that generates by diffusion over
    blocks under a block-causal mask), beyond the dense family's.  The
    published config states neither the block length nor the mask token:
    a file gives them, and a server's denoising steps and commit rule,
    under ``serving.diffusion`` (``block_length``, ``mask_token_id``,
    ``denoising_steps``, ``remasking``, ``threshold``); absent, the block
    is ``SDAR_BLOCK_LENGTH`` long, the released default of the family's
    ``-Chat`` checkpoints, and the mask ``SDAR_MASK_TOKEN_ID`` (both
    ASSUMED: no checkpoint was there to check).  Raises on what the model
    does not implement."""
    def refuse(what):
        raise ValueError(f"unsupported sdar_moe config: {what}")
    if hf_cfg.get("decoder_sparse_step", 1) != 1 or hf_cfg.get(
            "mlp_only_layers"):
        refuse("decoder_sparse_step != 1 or mlp_only_layers (every layer "
               "holds experts)")
    if hf_cfg.get("use_sliding_window") or hf_cfg.get("sliding_window"):
        refuse("a sliding window")
    if hf_cfg.get("rope_scaling"):
        refuse(f"rope_scaling {hf_cfg['rope_scaling']}")
    bd = (hf_cfg.get("serving") or {}).get("diffusion") or {}
    rule = bd.get("remasking", "low_confidence_static")
    if rule not in ("low_confidence_static", "low_confidence_dynamic"):
        refuse(f"remasking {rule!r} (low_confidence_static | "
               "low_confidence_dynamic)")
    dynamic = rule == "low_confidence_dynamic"
    if dynamic and not 0.0 < bd.get("threshold", 0.0) < 1.0:
        refuse("low_confidence_dynamic needs a threshold in (0, 1)")
    return dict(
        mlp_kinds=("experts",) * hf_cfg["num_hidden_layers"],
        qk_head_dim=hf_cfg["head_dim"], qk_norm=True, rope_scaling=None,
        n_experts=hf_cfg["num_experts"],
        expert_top_k=hf_cfg["num_experts_per_tok"],
        d_expert=hf_cfg["moe_intermediate_size"],
        router_kind="softmax", router_bias=False,
        router_norm_topk=bool(hf_cfg.get("norm_topk_prob", True)),
        tie_embed=bool(hf_cfg.get("tie_word_embeddings", False)),
        diffusion_block=int(bd.get("block_length", SDAR_BLOCK_LENGTH)),
        mask_token_id=int(bd.get("mask_token_id", SDAR_MASK_TOKEN_ID)),
        diffusion_steps=0 if dynamic else int(bd.get("denoising_steps", 0)),
        diffusion_threshold=float(bd["threshold"]) if dynamic else 0.0)


def config_from_hf(hf_cfg: dict):
    """HF ``config.json`` → TransformerConfig: the dense Llama family,
    ``model_type`` granitemoehybrid (``_hybrid_fields``), ``lfm2`` /
    ``lfm2_moe`` (``_lfm2_fields``), ``deepseek_v3`` / ``kimi_k2``
    (``_mla_fields``), ``mimo_v2`` (``_mimo_fields``), ``qwen3_next``
    (``_qwen3_next_fields``), ``olmo_hybrid`` (``_olmo_hybrid_fields``) and
    ``sdar_moe`` (``_sdar_fields``).

    Raises on architecture knobs the model does not implement — silently
    ignoring them (e.g. a non-SiLU activation) would convert into a model
    with wrong logits."""
    from nvme_strom_tpu.models.transformer import TransformerConfig
    act = hf_cfg.get("hidden_act", "silu")
    if act != "silu":
        raise ValueError(f"unsupported hidden_act {act!r} (model is "
                         "SiLU-gated)")
    for knob in ("attention_bias", "mlp_bias"):
        if hf_cfg.get(knob):
            raise ValueError(f"unsupported {knob}=True (model has no "
                             "bias terms)")
    model_type = hf_cfg.get("model_type")
    family = (_hybrid_fields(hf_cfg) if model_type == "granitemoehybrid"
              else _lfm2_fields(hf_cfg) if model_type in ("lfm2", "lfm2_moe")
              else _mla_fields(hf_cfg)
              if model_type in ("deepseek_v3", "kimi_k2")
              else _mimo_fields(hf_cfg) if model_type == "mimo_v2"
              else _qwen3_next_fields(hf_cfg) if model_type == "qwen3_next"
              else _olmo_hybrid_fields(hf_cfg)
              if model_type == "olmo_hybrid"
              else _sdar_fields(hf_cfg) if model_type == "sdar_moe"
              else {})
    derived_hd = hf_cfg["hidden_size"] // hf_cfg["num_attention_heads"]
    if hf_cfg["hidden_size"] % hf_cfg["num_attention_heads"]:
        raise ValueError("hidden_size not divisible by num_attention_heads")
    # (latent attention states its own head widths: where HF writes a
    # head_dim there, it is qk_rope_head_dim; mimo_v2 and qwen3_next state
    # their head_dim and the model takes it, ``qk_head_dim``)
    if "kv_lora_rank" not in family and "qk_head_dim" not in family \
            and hf_cfg.get(
            "head_dim", derived_hd) != derived_hd:
        # recent HF configs may carry an explicit head_dim decoupled from
        # hidden_size/n_heads; TransformerConfig derives it, so a
        # mismatch would only explode later inside qkv_project
        raise ValueError(
            f"unsupported explicit head_dim={hf_cfg['head_dim']} "
            f"(model derives {derived_hd} = hidden_size/num_heads)")
    scaling = None if "rope_scaling" in family else hf_cfg.get(
        "rope_scaling")
    if scaling is not None:
        rt = scaling.get("rope_type", scaling.get("type"))
        if rt != "llama3":
            raise ValueError(f"unsupported rope_scaling type {rt!r} "
                             "(only llama3 frequency scaling)")
        scaling = {k: v for k, v in scaling.items()
                   if k in ("rope_type", "type", "factor",
                            "low_freq_factor", "high_freq_factor",
                            "original_max_position_embeddings")}
    fields = dict(
        vocab=hf_cfg["vocab_size"],
        d_model=hf_cfg["hidden_size"],
        n_layers=hf_cfg["num_hidden_layers"],
        n_heads=hf_cfg["num_attention_heads"],
        n_kv_heads=hf_cfg.get("num_key_value_heads",
                              hf_cfg["num_attention_heads"]),
        d_ff=hf_cfg["intermediate_size"],
        max_seq=hf_cfg.get("max_position_embeddings", 2048),
        rope_theta=float(hf_cfg.get("rope_theta", 10000.0)),
        rope_scaling=scaling,
        norm_eps=float(hf_cfg.get("rms_norm_eps",
                                  hf_cfg.get("norm_eps", 1e-5))))
    fields.update(family)         # a family may state a key its own way
    return TransformerConfig(**fields)


def _deinterleave_rope(w: np.ndarray, cfg, per_head: bool) -> np.ndarray:
    """HF's DeepSeek-V3 attention de-interleaves the rotary features of q
    and k ((rope/2, 2) -> (2, rope/2)) before its half-split rotation
    (``rope_interleave``); ``models/mla.py`` rotates half-split as the rest
    of this model does, so the same permutation goes into the output columns
    that produce those features, once, here: the last ``qk_rope_dim``
    columns of every head of W_qb (in, heads x (nope + rope)), of W_kva (in,
    kv_lora_rank + rope)."""
    dr = cfg.qk_rope_dim
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    out = w.copy()
    if per_head:
        h = out.reshape(w.shape[0], cfg.n_heads, cfg.qk_nope_dim + dr)
        h[..., cfg.qk_nope_dim:] = h[..., cfg.qk_nope_dim:][..., perm]
    else:
        out[:, cfg.kv_lora_rank:] = w[:, cfg.kv_lora_rank:][:, perm]
    return out


def _deinterleave_gdn(w: np.ndarray, cfg, parts: tuple) -> np.ndarray:
    """A qwen3_next delta-rule projection (in, out) whose output columns lie
    interleaved by KEY head — each head's ``parts`` (widths) side by side,
    [q | k | v | z] for in_proj_qkvz, [b | a] for in_proj_ba — into the order
    ``models/ssm.py`` splits: all heads' first part, then all heads' second,
    ...  (HF's ``fix_query_key_value_ordering`` does the same to the
    activations at every call.)"""
    per = w.reshape(w.shape[0], cfg.gdn_k_heads, sum(parts))
    out, at = [], 0
    for width in parts:
        out.append(per[:, :, at:at + width].reshape(w.shape[0], -1))
        at += width
    return np.ascontiguousarray(np.concatenate(out, axis=1))


def strom_config_dict(cfg) -> dict:
    """``strom_config.json`` of a converted checkpoint: the
    TransformerConfig keys the serving/training entry points rebuild the
    model from."""
    out = {k: getattr(cfg, k) for k in (
        "vocab", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
        "max_seq", "rope_theta", "norm_eps")}
    if cfg.rope_scaling:
        out["rope_scaling"] = dict(cfg.rope_scaling)
    if cfg.layer_kinds:     # a hybrid: what the family's fields read off HF
        out.update({k: getattr(cfg, k) for k in (
            "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_conv", "ssm_chunk",
            "embed_mult", "residual_mult", "logits_div", "attn_scale", "rope",
            "tie_embed", "conv_taps", "qk_norm", "gdn_k_heads",
            "gdn_v_heads", "gdn_k_dim", "gdn_v_dim", "gdn_conv",
            "gdn_chunk", "gdn_neg_eigval", "post_norm", "qk_norm_whole")},
            layer_kinds=list(cfg.layer_kinds))
    if cfg.mlp_kinds:       # the per-layer MLPs and the exact layer's router
        out.update({k: getattr(cfg, k) for k in (
            "n_experts", "expert_top_k", "d_expert", "router_kind",
            "router_bias", "router_norm_topk", "router_scale",
            "experts_held", "expert_offset", "d_shared", "shared_gate")},
            mlp_kinds=list(cfg.mlp_kinds))
    if cfg.latent:
        out.update({k: getattr(cfg, k) for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
            "v_head_dim", "attn_scale", "tie_embed")})
    if cfg.stated_kv:
        out.update({k: getattr(cfg, k) for k in (
            "qk_head_dim", "v_head_dim", "rotary_dim", "value_scale",
            "window", "window_kv_heads", "window_rope_theta", "window_sink",
            "tie_embed", "attn_gate", "qk_norm")})
    if cfg.diffusion_block:
        out.update({k: getattr(cfg, k) for k in (
            "diffusion_block", "mask_token_id", "diffusion_steps",
            "diffusion_threshold")})
    return out


def _iter_hf_tensors(hf_dir: str) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (hf_name, np array) across every safetensors shard of the
    checkpoint.  Shard discovery (dir / index.json / single file) is
    LazyCheckpoint's — one implementation, shared."""
    from nvme_strom_tpu.formats.safetensors import _np_dtype
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    idx_path = os.path.join(hf_dir, "model.safetensors.index.json")
    ckpt = LazyCheckpoint(idx_path if os.path.exists(idx_path) else hf_dir)
    for sf in ckpt.files:
        with open(sf.path, "rb") as f:
            for name in sf.keys():
                t = sf.tensors[name]
                f.seek(t["offset"])
                raw = f.read(t["nbytes"])
                arr = np.frombuffer(raw, dtype=_np_dtype(t["dtype"]))
                yield name, arr.reshape(t["shape"])


def convert(hf_dir: str, out_dir: str, shard_bytes: int = 1 << 30,
            ignore_unmapped: bool = False) -> dict:
    """Convert an HF Llama checkpoint dir → our sharded safetensors +
    ``strom_config.json``.  Returns a summary dict.

    Unmapped WEIGHT tensors are a hard error (the converted model would
    be silently wrong); known non-weight buffers (rotary inv_freq) are
    dropped.  ``ignore_unmapped=True`` downgrades the error to the
    summary's ``skipped`` list — for callers who know what they're
    dropping."""
    from nvme_strom_tpu.formats.safetensors import write_safetensors
    os.makedirs(out_dir, exist_ok=True)
    # A rerun with different sharding would leave stale trailing shards
    # beside the fresh ones — LazyCheckpoint would then see duplicate
    # tensors and refuse the whole directory. Clear our own output
    # pattern first (only strom-*: never touch anything else).
    for stale in os.listdir(out_dir):
        if re.fullmatch(r"strom-\d{5}\.safetensors", stale):
            os.unlink(os.path.join(out_dir, stale))
    with open(os.path.join(hf_dir, "config.json")) as f:
        hf_cfg = json.load(f)
    cfg = config_from_hf(hf_cfg)
    zero_centred = hf_cfg.get("model_type") == "qwen3_next"
    olmo = hf_cfg.get("model_type") == "olmo_hybrid"

    pending: Dict[str, np.ndarray] = {}
    pending_bytes = 0
    shards = []
    seen = set()
    embed: Optional[np.ndarray] = None

    def flush():
        nonlocal pending, pending_bytes
        if not pending:
            return
        p = os.path.join(out_dir, f"strom-{len(shards):05d}.safetensors")
        write_safetensors(p, pending)
        shards.append(p)
        pending, pending_bytes = {}, 0

    def emit(name: str, arr: np.ndarray):
        nonlocal pending_bytes
        pending[name] = arr
        pending_bytes += arr.nbytes
        if pending_bytes >= shard_bytes:
            flush()

    skipped = []
    experts: Dict[str, Dict[int, np.ndarray]] = {}
    parts: Dict[str, Dict[int, np.ndarray]] = {}
    for hf_name, arr in _iter_hf_tensors(hf_dir):
        mapped = map_name(hf_name)
        if mapped is None:
            if not (_SKIP_OK_RE.search(hf_name) or ignore_unmapped):
                raise ValueError(
                    f"unmapped weight tensor {hf_name!r} — converting "
                    "without it would produce a numerically wrong model "
                    "(pass ignore_unmapped=True / --ignore-unmapped to "
                    "drop it anyway)")
            skipped.append(hf_name)
            continue
        ours, transpose = mapped
        renamed = olmo and _OLMO_NORMS.get(hf_name.split(".", 3)[-1])
        if renamed:
            ours = ours.rsplit(".", 1)[0] + "." + renamed
        if arr.ndim == 3:       # depthwise conv (channels, 1, taps)
            arr = arr.reshape(arr.shape[0], arr.shape[2])
        # bf16 fields load as uint16 views via numpy; keep raw dtype
        out = np.ascontiguousarray(arr.T) if transpose else arr
        if ours == "tok_embed":
            embed = arr
        if cfg.latent and ours.endswith((".wq_b", ".wkv_a")):
            out = _deinterleave_rope(out, cfg, ours.endswith(".wq_b"))
        e = re.fullmatch(r"(.*\.(gdn_in|gdn_ba|gdn_conv_w))\.(\d)", ours)
        if e:                               # one part of a leaf laid side
            got = parts.setdefault(e.group(1), {})          # by side
            got[int(e.group(3))] = out
            if len(got) == _GDN_PARTS[e.group(2)]:
                seen.add(e.group(1))
                emit(e.group(1), np.ascontiguousarray(np.concatenate(
                    [got[j] for j in range(len(got))], axis=1)))
                del parts[e.group(1)]
            continue
        if ours.endswith((".gdn_in", ".gdn_ba")):
            rep = cfg.gdn_v_heads // cfg.gdn_k_heads
            out = _deinterleave_gdn(
                out, cfg, (rep, rep) if ours.endswith(".gdn_ba") else
                (cfg.gdn_k_dim, cfg.gdn_k_dim, rep * cfg.gdn_v_dim,
                 rep * cfg.gdn_v_dim))
        if zero_centred and ours.rsplit(".", 1)[-1] in _ZERO_CENTRED:
            out = 1.0 + out.astype(np.float32)
        e = re.fullmatch(r"(.*\.moe_w_(?:gate|up|down))\.(\d+)", ours)
        if e:                               # one expert's slice: held until
            experts.setdefault(e.group(1), {})[int(e.group(2))] = out
            if len(experts[e.group(1)]) == cfg.experts_local:   # the layer's
                stack = experts.pop(e.group(1))
                seen.add(e.group(1))
                emit(e.group(1), np.stack([stack[j] for j in range(
                    cfg.experts_local)]))
            continue
        if ours.endswith(".wqkv"):          # (d, q | k | v) → wq, wk, wv
            i = int(ours.split(".")[1])
            nq = cfg.n_heads * cfg.head_dim
            nk = cfg.kv_heads(i) * cfg.head_dim
            for leaf, part in (("wq", out[:, :nq]), ("wk", out[:, nq:nq + nk]),
                               ("wv", out[:, nq + nk:])):
                name = ours[:-len("wqkv")] + leaf
                seen.add(name)
                emit(name, np.ascontiguousarray(part))
            continue
        if ours.endswith("w_gate_up"):      # (d, 2 ff) → gate | up
            ff = out.shape[1] // 2
            for leaf, half in (("w_gate", out[:, :ff]), ("w_up", out[:, ff:])):
                name = ours[:-len("w_gate_up")] + leaf
                seen.add(name)
                emit(name, np.ascontiguousarray(half))
            continue
        seen.add(ours)
        emit(ours, out)

    if experts:
        raise ValueError(f"expert matrices missing: {sorted(experts)} hold "
                         f"fewer than {cfg.experts_local} experts")
    if parts:
        raise ValueError(f"delta-rule projections missing: "
                         f"{sorted(parts)} lack parts")
    if cfg.tie_embed:
        seen.add("lm_head")     # the head IS tok_embed: nothing to write
    if "lm_head" not in seen:
        if not hf_cfg.get("tie_word_embeddings", False) or embed is None:
            raise ValueError("checkpoint has no lm_head.weight and "
                             "tie_word_embeddings is not set")
        emit("lm_head", np.ascontiguousarray(embed.T))
        seen.add("lm_head")
    flush()

    cfg_out = strom_config_dict(cfg)
    # Provenance marker: lets reuse logic (examples/train_lm.py --from-hf)
    # detect that an existing conversion came from a DIFFERENT source
    # checkpoint instead of silently serving stale weights.
    import hashlib
    with open(os.path.join(hf_dir, "config.json"), "rb") as f:
        cfg_sha = hashlib.sha256(f.read()).hexdigest()
    with open(os.path.join(out_dir, "source.json"), "w") as f:
        json.dump({"hf_dir": os.path.realpath(hf_dir),
                   "config_sha256": cfg_sha}, f, indent=1)
    with open(os.path.join(out_dir, "strom_config.json"), "w") as f:
        json.dump(cfg_out, f, indent=1)
    return {"tensors": len(seen), "shards": len(shards),
            "skipped": skipped, "config": cfg_out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="convert_llama",
        description="HF Llama checkpoint → nvme_strom_tpu safetensors")
    ap.add_argument("hf_dir")
    ap.add_argument("out_dir")
    ap.add_argument("--shard-bytes", type=int, default=1 << 30)
    ap.add_argument("--ignore-unmapped", action="store_true",
                    help="drop unmapped weight tensors instead of erroring")
    args = ap.parse_args(argv)
    summary = convert(args.hf_dir, args.out_dir, args.shard_bytes,
                      ignore_unmapped=args.ignore_unmapped)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
