"""Flagship model: a Llama-style decoder transformer, pure-JAX functional.

The reference is a storage engine, not a trainer (SURVEY.md §1) — this model
exists to exercise the framework end-to-end the way PG-Strom exercises the
reference (SURVEY.md §3.5): its weights are lazily loaded from NVMe
safetensors shards (parallel/weights.py), its input batches stream from
WebDataset/TFRecord shards (data/loader.py), and its training step runs
SPMD over a dp×tp Mesh.  TPU-first choices: bfloat16 activations, einsum
formulations that XLA tiles onto the MXU, static shapes, no Python control
flow under jit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from nvme_strom_tpu.models import moe as _moe


#: what ``layer_kinds`` may name, and those of them that carry a state per
#: sequence other than K/V (models/ssm.py)
MIXER_KINDS = ("attention", "window", "mamba", "conv", "gdn")
RECURRENT_KINDS = ("mamba", "conv", "gdn")


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8         # grouped-query attention when < n_heads
    d_ff: int = 1408
    max_seq: int = 2048
    rope_theta: float = 10000.0
    # Llama-3.1-style rope scaling: None, or a dict with rope_type
    # "llama3" and keys factor / low_freq_factor / high_freq_factor /
    # original_max_position_embeddings (HF config.json "rope_scaling").
    # Stored canonically as a sorted (key, value) tuple so the frozen
    # config stays hashable (cfg is a static jit argument for callers).
    rope_scaling: object = None
    norm_eps: float = 1e-5
    dtype: object = jnp.bfloat16  # activation/compute dtype (MXU-friendly)
    # Mixture-of-experts (models/moe.py): 0 experts == dense model.
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_every: int = 2            # layer i is MoE iff i % moe_every == rem
    # Routing-group size (tokens per GShard group; 0 = one batch row).
    # Dispatch memory is O(T·k·group·factor) — linear in total tokens.
    moe_group_size: int = 0
    # Rematerialize each layer in backward (jax.checkpoint): trades one
    # extra forward's FLOPs for O(1)-layers activation memory — the HBM
    # lever for deep configs.
    remat: bool = False
    # Selective remat (round-2 verdict #3: all-or-nothing remat cost ~6
    # MFU points): "none" keeps every activation, "full" recomputes the
    # whole layer (== remat=True), "dots" saves matmul outputs and
    # recomputes only the cheap elementwise/norm ops — most of full
    # remat's memory win at a fraction of its recompute FLOPs
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable).
    # Takes precedence over ``remat`` when set.
    remat_policy: str = ""
    # Cross-entropy in N sequence slices so (b, s, vocab) logits never
    # materialize (chunked_xent) — essential at Llama-vocab sizes.
    # 0/1 = the plain full-logits path.
    xent_chunks: int = 0
    # The per-layer description, read off the model's own config
    # (tools/convert_llama.config_from_hf): the mixer of every layer
    # ("attention", "window" — below —, "mamba", "conv" or "gdn":
    # models/ssm.py) and its MLP
    # ("dense" at d_ff, or "experts" at d_expert: the exact expert layer
    # of models/moe.py).  Empty layer_kinds == attention in every layer;
    # empty mlp_kinds == dense everywhere, or, with n_experts > 0, the
    # capacity-dropping GShard layer wherever ``moe_every`` puts one (the
    # training path's MoE, which a config has to name this way).  The
    # ssm_* sizes are Mamba-2's heads, head width, state width,
    # causal-conv taps and scan chunk (one B/C group); conv_taps the short
    # conv mixer's.
    layer_kinds: tuple = ()
    mlp_kinds: tuple = ()
    d_expert: int = 0               # an expert's width (0: d_ff)
    # the exact layer's router: scores "sigmoid" or "softmax" of the
    # logits; a per-expert bias ("router_bias" leaf) added for the top-k
    # SELECTION only; the chosen scores renormalised to sum 1; a scale
    router_kind: str = "softmax"
    router_bias: bool = False
    router_norm_topk: bool = True
    router_scale: float = 1.0
    conv_taps: int = 3
    qk_norm: bool = False           # per-head RMS norm of q and k pre-rotary
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # Scalars some families put on the residual path (all 1 == absent):
    # x0 = embed_mult·E[tok]; x += residual_mult·f(x); logits /=
    # logits_div; attention scores × attn_scale (None: 1/√head_dim).
    embed_mult: float = 1.0
    residual_mult: float = 1.0
    logits_div: float = 1.0
    attn_scale: object = None
    rope: bool = True               # False: no positional encoding
    tie_embed: bool = False         # logits through tok_embedᵀ, no lm_head
    # Latent attention (MLA, models/mla.py; kv_lora_rank 0 == the K/V kind):
    # q through a rank-``q_lora_rank`` bottleneck to n_heads x (qk_nope_dim |
    # qk_rope_dim); ONE latent row of kv_lora_rank + qk_rope_dim values a
    # token, which is what a cache holds, expanded to n_heads x (qk_nope_dim
    # | v_head_dim) keys and values.  Such a config states ``attn_scale``
    # and a "yarn" ``rope_scaling`` itself (config_from_hf).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # The exact expert layer's share of a deployment: the router scores
    # ``n_experts``, this device holds ``experts_held`` of them (0: all)
    # from ``expert_offset`` on and computes the pairs that fall on those;
    # ``d_shared`` is the width of a shared expert every row goes through
    # beside the routed ones (0: none).
    experts_held: int = 0
    expert_offset: int = 0
    d_shared: int = 0
    # K/V attention whose geometry the config STATES (all 0 / 1 == absent):
    # ``qk_head_dim`` the width of a query and key head (0: d_model //
    # n_heads), ``v_head_dim`` above that of a value head (0: as wide as a
    # key's); rotary on the first ``rotary_dim`` features of a head only (0:
    # all of them); values multiplied by ``value_scale`` as they are
    # projected.  A "window" entry of ``layer_kinds`` is an attention layer
    # whose row i sees the ``window`` keys i - window < j <= i: it has
    # ``window_kv_heads`` KV heads (0: n_kv_heads) and rotates at
    # ``window_rope_theta`` (0: rope_theta); with ``window_sink`` a learned
    # scalar per query head (leaf "sink") joins its softmax as one more
    # column that carries no value.  Such a layer keeps a ring of its last
    # rows per sequence, not pages (models/serving.py).
    qk_head_dim: int = 0
    rotary_dim: int = 0
    value_scale: float = 1.0
    window: int = 0
    window_kv_heads: int = 0
    window_rope_theta: float = 0.0
    window_sink: bool = False
    # A "gdn" entry of ``layer_kinds`` is a gated-delta-rule layer
    # (models/ssm.py): ``gdn_k_heads`` key heads of ``gdn_k_dim`` and
    # ``gdn_v_heads`` value heads of ``gdn_v_dim`` (value head j reads key
    # head j // (gdn_v_heads // gdn_k_heads)), a causal conv of ``gdn_conv``
    # taps over q | k | v, the prefill's scan in chunks of ``gdn_chunk`` rows.
    gdn_k_heads: int = 0
    gdn_v_heads: int = 0
    gdn_k_dim: int = 0
    gdn_v_dim: int = 0
    gdn_conv: int = 4
    gdn_chunk: int = 64
    # ``attn_gate``: an attention layer's query projection is twice as wide,
    # a head at a time (q | g), and sigmoid(g) multiplies the heads' output
    # before W_o.  ``shared_gate``: the shared expert's output is multiplied
    # by sigmoid(x . w), one scalar a row (leaf "shared_gate", (d, 1)).
    attn_gate: bool = False
    shared_gate: bool = False
    # ``post_norm``: a block normalises what a sub-layer GIVES and nothing
    # it takes (the Olmo 2/3 order: x + N(f(x)), ``norm_in`` / ``norm_out``
    # below); the leaves keep their names, "attn_norm" the mixer's norm and
    # "mlp_norm" the MLP's.  ``qk_norm_whole``: the q/k norm (``qk_norm``)
    # runs over ALL of a projection's features before the split into heads,
    # its weights n_heads·head_dim and n_kv_heads·head_dim wide.
    # ``gdn_neg_eigval``: a delta-rule layer's β is 2·sigmoid(b), in (0, 2),
    # so that I − β k kᵀ may have an eigenvalue in (−1, 0).
    post_norm: bool = False
    qk_norm_whole: bool = False
    gdn_neg_eigval: bool = False
    # Generation by diffusion over blocks (``diffusion_block`` Bl; 0: none,
    # tokens are made one a step, left to right).  The attention mask is
    # BLOCK-causal: position i sees position j iff j // Bl <= i // Bl,
    # blocks counted from position 0 — its whole block, the later rows of
    # it too, and every earlier block (``decode.block_step``).  A serving
    # step forwards the Bl rows of a slot's current block, the still-masked
    # ones as ``mask_token_id``, and commits the most confident
    # (``serving._paged_step``).  ``diffusion_steps`` T and
    # ``diffusion_threshold`` tau are a server's defaults, not the model's:
    # a block's masked positions are committed over T denoising forwards
    # (0: one a position), the most confident first; with tau > 0 every
    # position whose confidence passes it, and at least one a forward.
    diffusion_block: int = 0
    mask_token_id: int = 0
    diffusion_steps: int = 0
    diffusion_threshold: float = 0.0

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(
                self, "rope_scaling",
                tuple(sorted(self.rope_scaling.items())))
        kinds = tuple(self.layer_kinds)
        object.__setattr__(self, "layer_kinds", kinds)
        if kinds:
            if len(kinds) != self.n_layers or set(kinds) - set(MIXER_KINDS):
                raise ValueError(
                    f"layer_kinds must name one of {MIXER_KINDS} for each of "
                    f"the {self.n_layers} layers, got {kinds}")
            if "window" in kinds and (self.window < 1 or self.latent):
                raise ValueError("window layers need window >= 1 and K/V "
                                 "attention (no kv_lora_rank)")
            if "mamba" in kinds and not (self.ssm_heads and self.ssm_head_dim
                                         and self.ssm_state):
                raise ValueError("mamba layers need ssm_heads, ssm_head_dim "
                                 "and ssm_state")
            if "gdn" in kinds and not (
                    self.gdn_k_heads and self.gdn_k_dim and self.gdn_v_dim
                    and self.gdn_v_heads
                    and self.gdn_v_heads % self.gdn_k_heads == 0):
                raise ValueError("gdn layers need gdn_k_heads, gdn_k_dim, "
                                 "gdn_v_dim and gdn_v_heads, a multiple of "
                                 "gdn_k_heads")
        mlps = tuple(self.mlp_kinds)
        object.__setattr__(self, "mlp_kinds", mlps)
        if mlps:
            if len(mlps) != self.n_layers or set(mlps) - {"dense",
                                                          "experts"}:
                raise ValueError(
                    f"mlp_kinds must name 'dense' or 'experts' for each of "
                    f"the {self.n_layers} layers, got {mlps}")
            if "experts" in mlps and not (
                    0 < self.expert_top_k <= self.n_experts):
                raise ValueError(
                    f"expert layers need 0 < expert_top_k <= n_experts, got "
                    f"{self.expert_top_k} of {self.n_experts}")
        if self.router_kind not in ("softmax", "sigmoid"):
            raise ValueError(f"router_kind {self.router_kind!r}: expected "
                             "'softmax' or 'sigmoid'")
        if self.experts_held and not (
                0 <= self.expert_offset
                and self.expert_offset + self.experts_held <= self.n_experts):
            raise ValueError(
                f"{self.experts_held} experts from {self.expert_offset} on "
                f"held of {self.n_experts} routed")
        if self.diffusion_block and (
                self.diffusion_block < 1 or kinds or self.latent
                or not 0 <= self.mask_token_id < self.vocab
                or self.diffusion_steps < 0
                or not 0.0 <= self.diffusion_threshold < 1.0):
            raise ValueError(
                "generation by diffusion needs diffusion_block >= 1, "
                "K/V attention in every layer (no layer_kinds, no "
                "kv_lora_rank), a mask_token_id inside the vocabulary, "
                "diffusion_steps >= 0 and diffusion_threshold in [0, 1)")
        if self.latent and (kinds or not (
                self.q_lora_rank and self.qk_nope_dim and self.qk_rope_dim
                and self.v_head_dim and self.attn_scale)):
            raise ValueError(
                "latent attention needs q_lora_rank, qk_nope_dim, "
                "qk_rope_dim, v_head_dim and attn_scale, in every layer "
                "(no layer_kinds)")

    @property
    def rope_scaling_dict(self):
        """rope_scaling as the dict _rope consumes (None if unset)."""
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def head_dim(self) -> int:
        return self.qk_head_dim or self.d_model // self.n_heads

    @property
    def v_dim(self) -> int:
        """Width of a value head of K/V attention."""
        return (0 if self.latent else self.v_head_dim) or self.head_dim

    def mixer(self, i: int) -> str:
        """Layer ``i``'s mixer, one of ``MIXER_KINDS``."""
        return self.layer_kinds[i] if self.layer_kinds else "attention"

    def kv_heads(self, i: int) -> int:
        """KV heads of attention layer ``i``."""
        return (self.window_kv_heads if self.mixer(i) == "window"
                else 0) or self.n_kv_heads

    def theta(self, i: int) -> float:
        """Rotary base of attention layer ``i``."""
        return (self.window_rope_theta if self.mixer(i) == "window"
                else 0.0) or self.rope_theta

    @property
    def window_layers(self) -> tuple:
        """Indices of the window layers, in layer order: what a server's
        rings hold a layer of each (``window_layers.index(i)``)."""
        return tuple(i for i, k in enumerate(self.layer_kinds)
                     if k == "window")

    @property
    def stated_kv(self) -> bool:
        """Whether K/V attention's geometry is the config's own — stated
        head widths, partial rotary, a value scale, an output gate or window
        layers: such a
        config attends through the blocked kernels (ops/kv_prefill.py) and
        is served by ``DecodeServer`` alone."""
        return bool(self.qk_head_dim or self.rotary_dim or self.window_layers
                    or self.value_scale != 1.0 or self.attn_gate
                    or (self.v_head_dim and not self.latent))

    def mlp_kind(self, i: int) -> str:
        """Layer ``i``'s MLP: "dense", "experts" (the exact expert layer)
        or "gshard" (capacity-dropping, placed by ``moe_every``)."""
        if self.mlp_kinds:
            return self.mlp_kinds[i]
        if self.n_experts > 0 and i % self.moe_every == self.moe_every - 1:
            return "gshard"
        return "dense"

    def is_moe_layer(self, i: int) -> bool:
        """Whether layer ``i`` holds expert weights, of either kind."""
        return self.mlp_kind(i) != "dense"

    def is_mamba_layer(self, i: int) -> bool:
        return self.mixer(i) == "mamba"

    @property
    def mamba_layers(self) -> tuple:
        """Indices of the Mamba-2 layers (empty for a plain decoder)."""
        return tuple(i for i, k in enumerate(self.layer_kinds)
                     if k == "mamba")

    @property
    def recurrent_layers(self) -> tuple:
        """Indices of the layers that carry something per sequence other
        than K/V — a Mamba-2 or delta-rule state and conv tail, a short
        conv's tail — in layer order: what ``models/ssm.init_state`` holds a
        row of."""
        return tuple(i for i, k in enumerate(self.layer_kinds)
                     if k in RECURRENT_KINDS)

    @property
    def state_layers(self) -> tuple:
        """Indices of the recurrent layers that keep a state matrix beside
        their conv tail (``init_state``'s ``"s"``), in layer order."""
        return tuple(i for i, k in enumerate(self.layer_kinds)
                     if k in ("mamba", "gdn"))

    @property
    def expert_layers(self) -> tuple:
        """Indices of the layers whose MLP is the exact expert layer."""
        return tuple(i for i in range(self.n_layers)
                     if self.mlp_kind(i) == "experts")

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def attn_layers(self) -> tuple:
        """Indices of the layers that keep K/V: all of them for a plain
        decoder.  A cache holds ``len(attn_layers)`` layers of K/V; layer
        ``i``'s are at ``attn_layers.index(i)``."""
        return tuple(i for i in range(self.n_layers)
                     if self.mixer(i) == "attention")

    @property
    def latent(self) -> bool:
        """Whether attention is the latent kind (MLA): a cache then holds
        one row of ``latent_width`` values a token a layer, not K and V."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def experts_local(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.experts_held or self.n_experts

    def require_kv_pages(self, what: str) -> None:
        """The one message of everything that reads a cache as K and V
        pages at KV-head width."""
        if self.latent:
            raise NotImplementedError(
                f"{what} holds K and V pages; a latent-attention config "
                f"(kv_lora_rank {self.kv_lora_rank}) caches one "
                f"{self.latent_width}-wide latent row a token, which has no "
                f"store format yet: serve this config from DecodeServer on "
                f"one device, without a kv_store, a mesh or session "
                f"hand-off")
        if self.stated_kv:
            raise NotImplementedError(
                f"{what} holds K and V pages of one head width for every "
                f"layer; this config states its own attention geometry "
                f"(head widths {self.head_dim}/{self.v_dim}, "
                f"{len(self.window_layers)} window layers that keep a ring "
                f"of their last {self.window} rows, not pages): serve it "
                f"from DecodeServer on one device, without a kv_store, a "
                f"mesh, session hand-off or a training step")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Width of what the causal conv sees: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def gdn_conv_dim(self) -> int:
        """Width of what a delta-rule layer's causal conv sees: q | k | v."""
        return (2 * self.gdn_k_heads * self.gdn_k_dim
                + self.gdn_v_heads * self.gdn_v_dim)

    def require_pre_norm(self, what: str) -> None:
        """The one message of the layer loops that keep their own copy of
        the pre-norm block, or split a projection's features over devices."""
        if self.post_norm or self.qk_norm_whole:
            raise NotImplementedError(
                f"{what} normalises what a sub-layer takes and q and k a "
                f"head at a time; this config has post-norm blocks "
                f"(post_norm {self.post_norm}) and a q/k norm over the "
                f"whole projection (qk_norm_whole {self.qk_norm_whole}): "
                f"serve it from DecodeServer on one device, or run "
                f"transformer.forward")

    def require_causal(self, what: str) -> None:
        """The one message of everything that makes tokens one a step, left
        to right, under the causal mask."""
        if self.diffusion_block:
            raise NotImplementedError(
                f"{what} is causal and makes one token a step; this config "
                f"generates by diffusion over blocks of "
                f"{self.diffusion_block} under a block-causal mask "
                f"(diffusion_block): serve it from DecodeServer on one "
                f"device, without a kv_store, a mesh, session hand-off, "
                f"speculative decoding or a training step")

    def require_no_recurrent(self, what: str) -> None:
        """The one message of everything that holds K/V pages only."""
        if self.recurrent_layers:
            raise NotImplementedError(
                f"{what} does not carry the recurrent state of "
                f"{' or '.join(RECURRENT_KINDS)} layers (layer_kinds has "
                f"{len(self.recurrent_layers)}); serve "
                f"this config from DecodeServer on one device, "
                f"without a kv_store, a mesh or session hand-off")


def flagship_config() -> TransformerConfig:
    return TransformerConfig()


def tiny_config() -> TransformerConfig:
    return TransformerConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                             n_kv_heads=2, d_ff=128, max_seq=64)


def tiny_moe_config() -> TransformerConfig:
    return TransformerConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                             n_kv_heads=2, d_ff=128, max_seq=64,
                             n_experts=4, expert_top_k=2)


# ----------------------------- params -----------------------------

def dense_init(key, fan_in, shape):
    """Scaled-normal init (normal/√fan_in, f32) — the single init scheme
    for every weight, dense and MoE alike."""
    return (jax.random.normal(key, shape, jnp.float32)
            / np.sqrt(fan_in)).astype(jnp.float32)


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Dict:
    """Parameters as a flat {name: array} dict — the same namespace the
    safetensors lazy loader uses, so checkpoints round-trip by name."""
    keys = iter(jax.random.split(
        rng, 4 + (16 if cfg.latent or cfg.d_shared or cfg.window_layers
                  else 13) * cfg.n_layers))
    hd, vd, nh = cfg.head_dim, cfg.v_dim, cfg.n_heads
    dense = dense_init

    p = {
        "tok_embed": dense(next(keys), 1.0, (cfg.vocab, cfg.d_model)),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    lm_key = next(keys)
    if not cfg.tie_embed:
        p["lm_head"] = dense(lm_key, cfg.d_model, (cfg.d_model, cfg.vocab))
    for i in range(cfg.n_layers):
        L = f"layers.{i}."
        p[L + "attn_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        if cfg.is_mamba_layer(i):
            from nvme_strom_tpu.models.ssm import init_mamba_params
            p.update(init_mamba_params(keys, cfg, L, dense))
        elif cfg.mixer(i) == "conv":
            from nvme_strom_tpu.models.ssm import init_conv_params
            p.update(init_conv_params(keys, cfg, L, dense))
        elif cfg.mixer(i) == "gdn":
            from nvme_strom_tpu.models.ssm import init_gdn_params
            p.update(init_gdn_params(keys, cfg, L, dense))
        elif cfg.latent:
            from nvme_strom_tpu.models.mla import init_mla_params
            p.update(init_mla_params(keys, cfg, L, dense))
        else:
            nkv = cfg.kv_heads(i)
            if cfg.qk_norm:
                whole = cfg.qk_norm_whole
                p[L + "q_norm"] = jnp.ones((nh * hd if whole else hd,),
                                           jnp.float32)
                p[L + "k_norm"] = jnp.ones((nkv * hd if whole else hd,),
                                           jnp.float32)
            # with an output gate a head's columns are (q | g)
            p[L + "wq"] = dense(next(keys), cfg.d_model,
                                (cfg.d_model, nh * hd * (1 + cfg.attn_gate)))
            p[L + "wk"] = dense(next(keys), cfg.d_model,
                                (cfg.d_model, nkv * hd))
            p[L + "wv"] = dense(next(keys), cfg.d_model,
                                (cfg.d_model, nkv * vd))
            p[L + "wo"] = dense(next(keys), nh * vd, (nh * vd, cfg.d_model))
            if cfg.mixer(i) == "window" and cfg.window_sink:
                p[L + "sink"] = dense(next(keys), 1.0, (nh,))
        p[L + "mlp_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        if cfg.is_moe_layer(i):
            p.update(_moe.init_moe_params(keys, cfg, L, dense))
        else:
            p[L + "w_gate"] = dense(next(keys), cfg.d_model,
                                    (cfg.d_model, cfg.d_ff))
            p[L + "w_up"] = dense(next(keys), cfg.d_model,
                                  (cfg.d_model, cfg.d_ff))
            p[L + "w_down"] = dense(next(keys), cfg.d_ff,
                                    (cfg.d_ff, cfg.d_model))
    return p


# ----------------------------- layers -----------------------------

def rms_norm(x, weight, eps):
    # All norm math in f32, ONE downcast at the end.  The previous
    # form multiplied the already-downcast activation by the f32
    # weight, so jnp promotion returned an f32 tensor from every norm
    # — and since every attention/mlp input is post-norm, EVERY matmul
    # in the network lowered as f32×f32 (window-9 evidence: the
    # StableHLO dots were all f32 despite cfg.dtype=bf16, and the big
    # ff fusions capped at ~92 TFLOP/s while truly-dense ones hit 187).
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight).astype(x.dtype)


# --- the block's order -----------------------------------------------------
#
# The ONE statement of where a block's two norms stand, read by every layer
# loop (forward_hidden here, models/decode.py, models/serving.py):
#
#     x = x + norm_out(f(norm_in(x, w)), w)
#
# Pre-norm (the default) normalises what a sub-layer takes; ``post_norm``
# what it gives.  Each is the identity where the other acts, so a loop traces
# exactly one norm a sub-layer.

def norm_in(x, w, cfg: "TransformerConfig"):
    """What a sub-layer takes: N(x), or x itself under ``post_norm``."""
    return x if cfg.post_norm else rms_norm(x, w, cfg.norm_eps)


def norm_out(f, w, cfg: "TransformerConfig"):
    """What a sub-layer adds to the stream: f, or N(f) under ``post_norm``."""
    return rms_norm(f, w, cfg.norm_eps) if cfg.post_norm else f


def _llama3_scale_freqs(freqs, scaling: dict):
    """Llama-3.1 frequency remap (HF ROPE_INIT_FUNCTIONS["llama3"]):
    long-wavelength components are divided by ``factor``, short ones kept,
    with a smooth ramp between — extends context without retraining."""
    factor = float(scaling["factor"])
    low = float(scaling.get("low_freq_factor", 1.0))
    high = float(scaling.get("high_freq_factor", 4.0))
    orig = float(scaling["original_max_position_embeddings"])
    wavelen = 2.0 * np.pi / freqs
    smooth = (orig / wavelen - low) / (high - low)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    return jnp.where(wavelen > orig / low, freqs / factor,
                     jnp.where(wavelen < orig / high, freqs,
                               (1 - smooth) * freqs / factor
                               + smooth * freqs))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature (HF ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def yarn_freqs(half: int, theta: float, scaling: dict) -> np.ndarray:
    """YaRN's rotary frequencies (HF DeepSeek-V3 ``YarnRotaryEmbedding``):
    theta^(-j/half) kept where a pair turns more than ``beta_fast`` times
    over the original context, divided by ``factor`` where it turns fewer
    than ``beta_slow`` times, blended linearly between.  float32 (half,)."""
    dim, factor = 2 * half, float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * np.log(orig / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(correction_dim(float(scaling.get("beta_fast", 32)))),
              0)
    high = min(np.ceil(correction_dim(float(scaling.get("beta_slow", 1)))),
               dim - 1)
    if low == high:
        high += 0.001                       # HF: prevent singularity
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0, 1)
    extra = float(theta) ** (-np.arange(half, dtype=np.float32) / half)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def _rope_cos_sin(half: int, theta, positions, scaling, seq: int):
    """cos/sin tables for RoPE: (..., seq, half) in f32."""
    if positions is None:
        positions = jnp.arange(seq, dtype=jnp.float32)
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    mult = 1.0
    if scaling is not None:
        rt = scaling.get("rope_type", scaling.get("type"))
        if rt == "yarn":
            freqs = jnp.asarray(yarn_freqs(half, theta, scaling))
            # HF ``_compute_yarn_parameters``: the ratio where both are
            # stated, else the plain temperature
            ms, ma = scaling.get("mscale"), scaling.get("mscale_all_dim")
            mult = (yarn_mscale(scaling["factor"], ms)
                    / yarn_mscale(scaling["factor"], ma) if ms and ma
                    else yarn_mscale(scaling["factor"]))
        elif rt == "llama3":
            freqs = _llama3_scale_freqs(freqs, scaling)
        else:
            raise NotImplementedError(f"rope_scaling type {rt!r}")
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if mult != 1.0:
        return jnp.cos(ang) * mult, jnp.sin(ang) * mult
    return jnp.cos(ang), jnp.sin(ang)


def _rope(q, k, theta, positions=None, scaling=None):
    """Rotary position embeddings, half-split convention (x split into
    two halves rotated against each other — the same convention as HF
    Llama's rotate_half, so converted checkpoints need no permutation).

    ``positions``: absolute token positions, shape (seq,) — or (b, seq)
    when rows sit at DIFFERENT positions (continuous batching,
    models/serving.py); defaults to arange(seq).  The decode path
    passes the cache write position so an incrementally-generated token
    gets the same rotation it would in a full forward pass
    (models/decode.py).  ``scaling``: optional Llama-3.1 rope_scaling
    dict (see TransformerConfig)."""
    seq = q.shape[-2]
    half = q.shape[-1] // 2
    cos, sin = _rope_cos_sin(half, theta, positions, scaling, seq)
    if cos.ndim == 3:              # per-row positions: (b, s, half)
        cos, sin = cos[:, None], sin[:, None]   # broadcast over heads
    return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)


def _apply_rope(t, cos, sin):
    """Half-split rotation (the single copy of the RoPE math — both
    the (b,h,s,d) and (b,s,h,d) paths feed pre-broadcast cos/sin)."""
    t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1
    ).astype(t.dtype)


def wmat(p: Dict, name: str, dtype):
    """Matmul weight by name, transparently dequantizing quantized
    weight-only leaves.

    Two leaf kinds (models/quant.py): int8 ``{"q8": int8 (..., d_in,
    d_out), "scale": f32 (..., 1, d_out)}`` and packed int4 ``{"q4":
    uint8 (..., d_in/2, d_out), "scale4": f32 (..., n_groups, 1,
    d_out)}`` (two values per byte along d_in, group-wise scales).
    Dequant is elementwise on the weight and XLA fuses it into the
    consuming matmul, so the HBM read is the quantized bytes: half
    (int8) or a quarter (int4) of bf16 — the lever for
    weight-streaming-bound decode.  Plain array leaves pass through, so
    every model path serves quantized and full-precision params with
    the same code.  Consumers that need the logical weight shape
    use ``quant.logical_shape`` (never re-derive the packing)."""
    w = p[name]
    if isinstance(w, dict):
        if "q8" in w:
            return w["q8"].astype(dtype) * w["scale"].astype(dtype)
        # int4: two values per byte along d_in; nibble unpack is two
        # shifts + a mask on the VPU, then the group-wise scale multiply
        # — all fused into the consuming matmul's operand read
        pk = w["q4"]
        sc = w["scale4"]
        lead = pk.shape[:-2]
        dhalf, dout = pk.shape[-2], pk.shape[-1]
        lo = (pk & jnp.uint8(0xF)).astype(jnp.int8) - 8
        hi = (pk >> jnp.uint8(4)).astype(jnp.int8) - 8
        q = jnp.stack([lo, hi], axis=-2).reshape(*lead, 2 * dhalf, dout)
        ngroup = sc.shape[-3]
        g = (2 * dhalf) // ngroup
        wf = (q.astype(dtype).reshape(*lead, ngroup, g, dout)
              * sc.astype(dtype))
        return wf.reshape(*lead, 2 * dhalf, dout)
    return w.astype(dtype)


# --- the residual path's scalars (TransformerConfig: all 1 == absent) ------
#
# One copy each, used by every layer loop (forward_hidden here,
# models/decode.py, models/serving.py), so that a loop cannot drop one.  At
# the defaults each returns exactly the expression the loops had inline.

def embed_tokens(params: Dict, cfg: "TransformerConfig", tokens):
    x = params["tok_embed"].astype(cfg.dtype)[tokens]
    if cfg.embed_mult != 1.0:
        x = x * jnp.asarray(cfg.embed_mult, cfg.dtype)
    return x


def valid_rows(n_valid, b: int, m: int):
    """(b, m) bool: row t of sequence i is real while t < n_valid[i], the
    rest right padding.  ``n_valid`` is one count for every sequence, (),
    or one each, (b,)."""
    ends = jnp.reshape(jnp.asarray(n_valid, jnp.int32), (-1, 1))
    return jnp.broadcast_to(jnp.arange(m) < ends, (b, m))


def add_residual(x, f, cfg: "TransformerConfig"):
    """x + residual_mult · f."""
    if cfg.residual_mult != 1.0:
        f = f * jnp.asarray(cfg.residual_mult, f.dtype)
    return x + f


def lm_logits(params: Dict, cfg: "TransformerConfig", x):
    """Final-norm hidden (..., d) → float32 logits (..., vocab)."""
    if cfg.tie_embed:
        logits = jnp.einsum("...d,vd->...v", x,
                            wmat(params, "tok_embed", x.dtype),
                            preferred_element_type=jnp.float32)
    else:
        logits = (x @ wmat(params, "lm_head", x.dtype)).astype(jnp.float32)
    if cfg.logits_div != 1.0:
        logits = logits / jnp.float32(cfg.logits_div)
    return logits


# --- attention precision gates -------------------------------------------
#
# The two attention einsums with explicit VJPs that downcast the
# incoming cotangent to the operand dtype before the backward matmuls.
# Autodiff's rule keeps the f32 cotangent (the preferred_element_type
# output) and lets jnp promotion widen the bf16 operand, so every
# attention-backward dot lowered f32×f32 — half the MXU rate (the dot
# census found 4-8 such dots in every attention-bearing train step).
# Softmax/mask/scale stay ordinary f32 autodiff; at f32 activations the
# downcasts are no-ops and gradients equal autodiff to rounding (pinned
# by the ring/ulysses parity tests).  Composable: callers mix the gates
# with plain jnp ops and autodiff handles the rest.

@jax.custom_vjp
def qk_scores(q, k):
    """einsum("bhqd,bhkd->bhqk") with f32 accumulation; backward dots
    take activation-dtype operands."""
    return jnp.einsum("bhqd,bhkd->bhqk", q, k,
                      preferred_element_type=jnp.float32)


def _qk_scores_fwd(q, k):
    return qk_scores(q, k), (q, k)


def _qk_scores_bwd(res, g):
    q, k = res
    g16 = g.astype(q.dtype)
    dq = jnp.einsum("bhqk,bhkd->bhqd", g16, k,
                    preferred_element_type=jnp.float32).astype(q.dtype)
    dk = jnp.einsum("bhqk,bhqd->bhkd", g16, q,
                    preferred_element_type=jnp.float32).astype(k.dtype)
    return dq, dk


qk_scores.defvjp(_qk_scores_fwd, _qk_scores_bwd)


@jax.custom_vjp
def pv_apply(p32, v):
    """einsum("bhqk,bhkd->bhqd") of f32 probabilities against V.

    The probs downcast to V's dtype happens INSIDE the gate (so the
    forward matmul runs bf16 on the MXU), and the backward downcasts
    the output cotangent before the dp/dv matmuls — but the dp
    COTANGENT returned upstream stays f32: the softmax VJP it feeds
    relies on f32 cancellation, and quantizing a matmul OUTPUT buys no
    MXU rate (only operand dtypes decide that)."""
    return jnp.einsum("bhqk,bhkd->bhqd", p32.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _pv_apply_fwd(p32, v):
    return pv_apply(p32, v), (p32, v)


def _pv_apply_bwd(res, g):
    p32, v = res
    g16 = g.astype(v.dtype)
    dp32 = jnp.einsum("bhqd,bhkd->bhqk", g16, v,
                      preferred_element_type=jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p32.astype(v.dtype), g16,
                    preferred_element_type=jnp.float32).astype(v.dtype)
    return dp32, dv


pv_apply.defvjp(_pv_apply_fwd, _pv_apply_bwd)


def dense_causal_attention(q, k, v, scale=None):
    """softmax(QKᵀ/√d)V with a causal mask; q/k/v (b, h, s, d), same head
    count (GQA already expanded).  The single-chip default ``attn_fn``.
    Built on the precision gates so the backward matmuls stay in the
    activation dtype (bf16 on TPU) — used directly and as the Ulysses
    inner.  ``scale`` replaces 1/√d (``TransformerConfig.attn_scale``)."""
    s, hd = q.shape[-2], q.shape[-1]
    scores = qk_scores(q, k)
    scores = scores / np.sqrt(hd) if scale is None else scores * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -1e30)
    probs32 = jax.nn.softmax(scores, axis=-1)
    return pv_apply(probs32, v).astype(q.dtype)


@jax.custom_vjp
def dense_causal_attention_grouped(q, k, v):
    """The same computation with q/k/v in PROJECTION layout (b, s, h, d)
    and k/v at KV-HEAD width — the default single-chip train path.

    Two copy killers vs transpose + expand + dense_causal_attention
    (AOT HLO probe on the d2048/b8 train step, 2026-07-31 — the jax
    profiler showed 69% of device time in copy ops at 35% MFU):

    - no ``jnp.repeat``: the einsums carry (b, nkv) as batch dims and
      read each K/V head once instead of ``g`` materialized replicas;
    - no (b,s,h,d)→(b,h,s,d) transposes: the matmul's dot_general
      absorbs the layout (non-contracting dims are free to permute),
      where the explicit transposes materialized q/k/v copies.

    Custom VJP (round-5): autodiff's backward kept the f32 scores
    cotangent from ``preferred_element_type`` and promoted k/q, so the
    dq/dk dots lowered f32×f32 — the last non-bf16 matmuls in the
    train step (StableHLO dot census: 4 of 57).  The explicit backward
    runs the softmax VJP in f32 and downcasts dS to the activation
    dtype before the dq/dk matmuls — exactly what flash-attention
    backward kernels do — so EVERY dot in the step is now
    bf16×bf16→f32.  At f32 activations the downcast is a no-op and
    gradients match autodiff to rounding (pinned by
    tests/test_model.py).

    Numerically identical to the expanded path (pinned by
    tests/test_model.py)."""
    out, _ = _grouped_attn_fwd(q, k, v)
    return out


def _grouped_attn_probs(q, k):
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    scores = jnp.einsum("bsngd,btnd->bngst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -1e30)
    return jax.nn.softmax(scores, axis=-1)       # f32 (b,n,g,s,t)


def _grouped_attn_fwd(q, k, v):
    b, s, nh, hd = q.shape
    probs32 = _grouped_attn_probs(q, k)
    probs = probs32.astype(q.dtype)
    out = jnp.einsum("bngst,btnd->bsngd", probs, v)
    return out.reshape(b, s, nh * hd), (q, k, v, probs32)


def _grouped_attn_bwd(res, g_out):
    q, k, v, probs32 = res
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    gr = nh // nkv
    go = g_out.reshape(b, s, nkv, gr, hd)
    probs = probs32.astype(q.dtype)
    dv = jnp.einsum("bngst,bsngd->btnd", probs, go,
                    preferred_element_type=jnp.float32).astype(v.dtype)
    dprobs = jnp.einsum("bsngd,btnd->bngst", go, v,
                        preferred_element_type=jnp.float32)
    # softmax VJP in f32; masked entries have probs32 == 0 exactly, so
    # no gradient leaks through the causal mask
    ds32 = probs32 * (dprobs
                      - jnp.sum(dprobs * probs32, -1, keepdims=True))
    ds = (ds32 / np.sqrt(hd)).astype(q.dtype)    # the precision gate
    qg = q.reshape(b, s, nkv, gr, hd)
    dqg = jnp.einsum("bngst,btnd->bsngd", ds, k,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    dk = jnp.einsum("bngst,bsngd->btnd", ds, qg,
                    preferred_element_type=jnp.float32).astype(k.dtype)
    return dqg.reshape(b, s, nh, hd), dk, dv


dense_causal_attention_grouped.defvjp(_grouped_attn_fwd,
                                      _grouped_attn_bwd)


def _qk_norm(q, k, p, prefix, cfg: TransformerConfig):
    """RMS norm of q and k (..., heads, head_dim) before rotary, for the
    families that have one (``cfg.qk_norm``): a head at a time, or, with
    ``qk_norm_whole``, over all the heads' features as one row."""
    if not cfg.qk_norm:
        return q, k
    if cfg.qk_norm_whole:
        def whole(t, w):
            flat = t.reshape(*t.shape[:-2], t.shape[-2] * t.shape[-1])
            return rms_norm(flat, w, cfg.norm_eps).reshape(t.shape)
        return whole(q, p[prefix + "q_norm"]), whole(k, p[prefix + "k_norm"])
    return (rms_norm(q, p[prefix + "q_norm"], cfg.norm_eps),
            rms_norm(k, p[prefix + "k_norm"], cfg.norm_eps))


def qkv_project(x, p, prefix, cfg: TransformerConfig, positions=None):
    """Shared QKV projection + RoPE.  Returns q (b, nh, s, hd) and k/v at
    kv-head width (b, n_kv_heads, s, hd) — pre-GQA-expansion, which is the
    shape the decode KV cache stores (models/decode.py)."""
    return qkvg_project(x, p, prefix, cfg, positions)[:3]


def qkvg_project(x, p, prefix, cfg: TransformerConfig, positions=None):
    """``qkv_project`` and, for a config with ``attn_gate``, the output
    gate's logits g (b, s, nh * hd) that the query projection brings beside
    each head's query (None without): (q, k, v, g)."""
    b, s, _ = x.shape
    layer = int(prefix.split(".")[1])
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.kv_heads(layer)
    # the three products stand before the head split as plain 2-D results:
    # without the barrier the TPU compiler folds the split and the
    # transpose below into each product and carries the head-major layout
    # back into the WEIGHT, which it then transposes (and stages) again on
    # every call — a decode step of 17.1 ms for 16.4 at hidden 4096 on a
    # v5e (tests/test_chip_compile_*.py::test_projection_weights_read_in_place)
    q, k, v = jax.lax.optimization_barrier(tuple(
        x @ wmat(p, prefix + name, x.dtype) for name in ("wq", "wk", "wv")))
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, v.dtype)
    g = None
    if cfg.attn_gate:
        q = q.reshape(b, s, nh, 2 * hd)
        q, g = q[..., :hd], q[..., hd:].reshape(b, s, nh * hd)
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, cfg.v_dim)
    q, k = _qk_norm(q, k, p, prefix, cfg)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # b h s d
    if cfg.rope and cfg.rotary_dim:
        # the first rotary_dim features of a head turn, the rest pass
        rd = cfg.rotary_dim
        qr, kr = _rope(q[..., :rd], k[..., :rd], cfg.theta(layer),
                       positions=positions, scaling=cfg.rope_scaling_dict)
        q = jnp.concatenate([qr, q[..., rd:]], axis=-1)
        k = jnp.concatenate([kr, k[..., rd:]], axis=-1)
    elif cfg.rope:
        q, k = _rope(q, k, cfg.theta(layer), positions=positions,
                     scaling=cfg.rope_scaling_dict)
    return q, k, v, g


def gate_heads(a, g):
    """The heads' output a (..., nh * vd) under its output gate: a ⊙
    sigmoid(g), in float32; ``g`` None (no ``attn_gate``): a as it is."""
    if g is None:
        return a
    return (a.astype(jnp.float32)
            * jax.nn.sigmoid(g.astype(jnp.float32))).astype(a.dtype)


def qkv_project_bshd(x, p, prefix, cfg: TransformerConfig,
                     positions=None):
    """QKV projection + RoPE in PROJECTION layout (b, s, h, d) — no
    head/seq transpose; the grouped attention einsums absorb the layout
    (see dense_causal_attention_grouped)."""
    b, s, _ = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ wmat(p, prefix + "wq", x.dtype)).reshape(b, s, nh, hd)
    k = (x @ wmat(p, prefix + "wk", x.dtype)).reshape(b, s, nkv, hd)
    v = (x @ wmat(p, prefix + "wv", x.dtype)).reshape(b, s, nkv, hd)
    q, k = _qk_norm(q, k, p, prefix, cfg)
    cos, sin = _rope_cos_sin(hd // 2, cfg.rope_theta, positions,
                             cfg.rope_scaling_dict, s)
    # (s, half) → (s, 1, half) broadcasts over (b, s, H, half);
    # per-row positions (b, s, half) → (b, s, 1, half)
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin), v


def expand_gqa(t, cfg: TransformerConfig):
    """kv-head width → full head width (no-op when nkv == nh)."""
    if cfg.n_kv_heads != cfg.n_heads:
        t = jnp.repeat(t, cfg.n_heads // cfg.n_kv_heads, axis=1)
    return t


def attention(x, p, prefix, cfg: TransformerConfig, attn_fn=None,
              positions=None, return_kv=False):
    """``attn_fn`` swaps the attention inner block: dense (default), the
    ring sequence-parallel kernel (parallel/ring_attention.make_ring_attn),
    or the Pallas flash kernel — all take/return (b, h, s, d).
    ``return_kv=True`` additionally returns the post-RoPE kv-width k/v for
    cache prefill."""
    b, s, _ = x.shape
    if cfg.latent:
        if attn_fn is not None or return_kv:
            raise NotImplementedError(
                "latent attention has its own inner block (models/mla.py)")
        from nvme_strom_tpu.models import mla
        return mla.self_attention(x, p, prefix, cfg, positions)
    cfg.require_causal("the training path (transformer.attention)")
    cfg.require_kv_pages("the training path (transformer.attention)")
    # no rotary, or a config's own score scale: only qkv_project and
    # dense_causal_attention know them
    llama_like = cfg.rope and cfg.attn_scale is None
    if attn_fn is not None and not llama_like:
        raise NotImplementedError(
            "attn_fn kernels assume rotary and 1/sqrt(head_dim)")
    if attn_fn is None and not return_kv and llama_like:
        # default dense path: projection layout end-to-end + grouped
        # einsums — no transposes, no materialized GQA repeat (the
        # d2048 step's 69%-copy profile, see the grouped fn)
        q, k, v = qkv_project_bshd(x, p, prefix, cfg,
                                   positions=positions)
        out = dense_causal_attention_grouped(q, k, v)
        return out @ wmat(p, prefix + "wo", x.dtype)
    # explicit attn_fns (flash/ring/ulysses) and the cache-prefill path
    # take (b, h, s, d) with equal head counts
    q, k, v = qkv_project(x, p, prefix, cfg, positions=positions)
    if attn_fn is None:
        out = dense_causal_attention(q, expand_gqa(k, cfg),
                                     expand_gqa(v, cfg), cfg.attn_scale)
    else:
        out = attn_fn(q, expand_gqa(k, cfg), expand_gqa(v, cfg))
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = out @ wmat(p, prefix + "wo", x.dtype)
    return (out, k, v) if return_kv else out


def mlp(x, p, prefix):
    gate = jax.nn.silu(x @ wmat(p, prefix + "w_gate", x.dtype))
    up = x @ wmat(p, prefix + "w_up", x.dtype)
    return (gate * up) @ wmat(p, prefix + "w_down", x.dtype)


def forward_hidden(params: Dict, tokens: jax.Array,
                   cfg: TransformerConfig, attn_fn=None, act_store=None
                   ) -> tuple[jax.Array, jax.Array]:
    """tokens (b, s) int32 → (final-norm hidden (b, s, d) in cfg.dtype,
    aux_loss scalar) — everything up to but excluding the lm_head, so
    the chunked cross-entropy can project vocab slices itself.

    aux_loss is the summed MoE load-balancing loss (0 for dense models).

    ``remat_policy="nvme"`` + ``act_store`` (an
    ``act_offload.ActivationStore``): layer-boundary activations live
    on NVMe between forward and backward and the backward recomputes
    each layer from its streamed-back input — O(1)-layers HBM
    activations, below remat="full"'s O(n_layers) (the engine's
    larger-than-device-memory identity applied to the activation
    axis)."""
    x = embed_tokens(params, cfg, tokens)
    aux = jnp.zeros((), jnp.float32)

    def layer_body(p, x, i):
        L = f"layers.{i}."
        h = norm_in(x, p[L + "attn_norm"], cfg)
        if cfg.is_mamba_layer(i):
            from nvme_strom_tpu.models.ssm import mamba_block
            h = mamba_block(h, p, L, cfg)[0]
        elif cfg.mixer(i) == "conv":
            from nvme_strom_tpu.models.ssm import conv_block
            h = conv_block(h, p, L, cfg)[0]
        elif cfg.mixer(i) == "gdn":
            from nvme_strom_tpu.models.ssm import gdn_block
            h = gdn_block(h, p, L, cfg)[0]
        else:
            h = attention(h, p, L, cfg, attn_fn)
        x = add_residual(x, norm_out(h, p[L + "attn_norm"], cfg), cfg)
        h = norm_in(x, p[L + "mlp_norm"], cfg)
        a = jnp.zeros((), jnp.float32)
        kind = cfg.mlp_kind(i)
        if kind == "gshard":
            h, a = _moe.moe_mlp(h, p, L, cfg)
        elif kind == "experts":
            h = _moe.expert_mlp(h, p, L, cfg)[0]
        else:
            h = mlp(h, p, L)
        return add_residual(x, norm_out(h, p[L + "mlp_norm"], cfg), cfg), a

    def one_layer(x, i):
        return layer_body(params, x, i)

    policy = cfg.remat_policy or ("full" if cfg.remat else "none")
    if policy == "full":
        one_layer = jax.checkpoint(one_layer, static_argnums=(1,))
    elif policy == "dots":
        one_layer = jax.checkpoint(
            one_layer, static_argnums=(1,),
            policy=jax.checkpoint_policies
            .dots_with_no_batch_dims_saveable)
    elif policy == "nvme":
        if act_store is None:
            raise ValueError(
                "remat_policy='nvme' needs an act_store= "
                "(parallel/act_offload.ActivationStore)")
        # The store's ordered io_callbacks cannot lower inside a
        # multi-device computation (they would either fail to lower or
        # force implicit gathers far from the cause) — reject HERE, in
        # the library, not just in examples/train_lm.py's arg parsing.
        # Inputs that merely COULD be sharded are fine: under the
        # test/dev hosts jax exposes many CPU devices, so the predicate
        # is "this computation actually spans devices", i.e. a
        # multi-process runtime or a committed input sharded across >1
        # device (tracers inside jit expose no sharding — callers going
        # through examples/train_lm.py are guarded there).
        if jax.process_count() > 1:
            raise ValueError(
                "remat_policy='nvme' is single-host: the activation "
                "store's ordered io_callbacks cannot lower in a "
                "multi-process computation — use remat full/dots")
        try:
            n_dev = len(tokens.sharding.device_set)
        except Exception:       # tracer / non-jax input: no verdict
            n_dev = 1
        if n_dev > 1:
            raise ValueError(
                "remat_policy='nvme' is single-device: tokens are "
                f"sharded across {n_dev} devices and the activation "
                "store's ordered io_callbacks cannot lower inside a "
                "multi-device computation — use remat full/dots")
        from nvme_strom_tpu.parallel.act_offload import offload_layer
        off = offload_layer(layer_body, act_store, x.shape, x.dtype)
        for i in range(cfg.n_layers):
            L = f"layers.{i}."
            lp = {k: params[k] for k in params if k.startswith(L)}
            x, a = off(lp, x, i)
            aux = aux + a
        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux
    elif policy != "none":
        raise ValueError(
            f"remat_policy {policy!r}: expected none|full|dots|nvme")
    for i in range(cfg.n_layers):
        x, a = one_layer(x, i)
        aux = aux + a
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def forward_with_aux(params: Dict, tokens: jax.Array,
                     cfg: TransformerConfig, attn_fn=None,
                     act_store=None) -> tuple[jax.Array, jax.Array]:
    """tokens (b, s) int32 → (logits (b, s, vocab) f32, aux_loss scalar)."""
    x, aux = forward_hidden(params, tokens, cfg, attn_fn,
                            act_store=act_store)
    return lm_logits(params, cfg, x), aux


def forward(params: Dict, tokens: jax.Array,
            cfg: TransformerConfig, attn_fn=None) -> jax.Array:
    """tokens (b, s) int32 → logits (b, s, vocab) float32."""
    return forward_with_aux(params, tokens, cfg, attn_fn)[0]


def chunked_xent(params, hidden, tokens, cfg) -> jax.Array:
    """Mean next-token NLL without ever materializing (b, s, vocab).

    The full-logits path peaks at b·s·vocab f32 — ~4 GiB for the Llama-3
    flagship (vocab 128k, b8 s1024) against a 16 GiB chip.  Here the
    sequence is scanned in ``cfg.xent_chunks`` slices: each step
    projects one (b, s/n, d) slice through the lm_head, reduces it to
    its logsumexp and target logit, and ``jax.checkpoint`` drops the
    slice's logits so the backward pass recomputes them — peak logits
    memory is one slice, forward and backward.

    Chunks split the FULL ``s`` positions (so power-of-two chunk counts
    divide power-of-two sequence lengths); the final position — which
    has no next token — carries weight 0 instead of being sliced off,
    which would leave the awkward odd length s-1.  Numerically
    identical to log_softmax + gather (pinned by tests/test_model.py)."""
    b, s, d = hidden.shape
    n = cfg.xent_chunks
    if s % n:
        raise ValueError(
            f"xent_chunks={n} must divide the sequence length {s}")
    c = s // n
    # target for position i is token i+1; the last position is padding
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    weights = jnp.concatenate(
        [jnp.ones((b, s - 1), jnp.float32),
         jnp.zeros((b, 1), jnp.float32)], axis=1)
    hs = hidden.reshape(b, n, c, d).transpose(1, 0, 2, 3)   # (n, b, c, d)
    ts = targets.reshape(b, n, c).transpose(1, 0, 2)
    ws = weights.reshape(b, n, c).transpose(1, 0, 2)
    w = wmat(params, "lm_head", hidden.dtype)

    def chunk_nll(h, t, wt):
        logits = (h @ w).astype(jnp.float32)        # (b, c, vocab)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return ((lse - tl) * wt).sum()

    def body(acc, htw):
        return acc + jax.checkpoint(chunk_nll)(*htw), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (hs, ts, ws))
    return total / (b * (s - 1))


def loss_fn(params, tokens, cfg, attn_fn=None, act_store=None
            ) -> jax.Array:
    """Next-token cross-entropy (tokens supply both input and target).

    The full sequence is forwarded and the last logit dropped — identical
    to forwarding tokens[:, :-1] for a causal model, but keeps the seq dim
    a multiple of the ``sp`` shard count for ring attention.

    ``cfg.xent_chunks > 1`` switches to the chunked lm_head+softmax
    (:func:`chunked_xent`) — the big-vocab activation-memory lever.
    ``act_store`` serves ``remat_policy="nvme"`` (see forward_hidden)."""
    if cfg.xent_chunks > 1:
        hidden, aux = forward_hidden(params, tokens, cfg, attn_fn,
                                     act_store=act_store)
        loss = chunked_xent(params, hidden, tokens, cfg)
        return loss + cfg.router_aux_coef * aux
    logits, aux = forward_with_aux(params, tokens, cfg, attn_fn,
                                   act_store=act_store)
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll) + cfg.router_aux_coef * aux


# ----------------------------- training -----------------------------

def make_train_step(cfg: TransformerConfig, optimizer, attn_fn=None,
                    accum_steps: int = 1, act_store=None):
    """Returns step(params, opt_state, tokens) -> (params, opt_state, loss).
    Pure function — jit/shard it at the call site.  ``attn_fn`` selects the
    attention inner block (dense / ring / flash).

    ``accum_steps > 1``: gradient accumulation — tokens (b, s) split
    into ``accum_steps`` microbatches along b and their gradients
    averaged in one ``lax.scan`` before the single optimizer update, so
    the activation footprint is that of b/accum_steps while the update
    matches the full-batch step exactly (same mean-over-tokens loss).

    ``act_store``: NVMe-offloaded saved activations for
    ``remat_policy="nvme"`` (parallel/act_offload).
    """

    import optax

    def step(params, opt_state, tokens):
        loss, grads = accumulate_grads(
            lambda mb: jax.value_and_grad(
                lambda p: loss_fn(p, mb, cfg, attn_fn,
                                  act_store=act_store))(params),
            params, tokens, accum_steps)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def accumulate_grads(grad_fn, like, tokens, accum_steps: int):
    """Microbatched gradient driver shared by the full and LoRA steps.

    ``grad_fn(microbatch) -> (loss, grads)`` with grads shaped
    ``like``; tokens (b, s) split into ``accum_steps`` row groups, one
    ``lax.scan`` accumulates in f32, and the mean matches the
    full-batch value exactly (equal micro sizes)."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if accum_steps == 1:
        return grad_fn(tokens)
    b = tokens.shape[0]
    if b % accum_steps:
        raise ValueError(f"batch {b} not divisible by "
                         f"accum_steps {accum_steps}")
    micro = tokens.reshape(accum_steps, b // accum_steps, -1)

    def one(carry, mb):
        loss_sum, grads = carry
        l, g = grad_fn(mb)
        return (loss_sum + l,
                jax.tree_util.tree_map(jnp.add, grads, g)), None

    zero = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), like)
    (loss_sum, grads), _ = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), zero), micro)
    inv = jnp.float32(1.0 / accum_steps)
    return loss_sum * inv, jax.tree_util.tree_map(
        lambda g: g * inv, grads)
