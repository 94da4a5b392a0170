"""Parameter/batch sharding rules for the flagship transformer.

Megatron-style tensor parallelism expressed as NamedShardings: the SPMD
partitioner inserts the all-reduces (psum over "tp" after the second matmul
of attention and MLP) — XLA collectives over ICI, never hand-written
NCCL-style calls (the TPU-idiomatic answer to the reference's lack of any
distributed layer, SURVEY.md §2/§5).
"""

from __future__ import annotations

from typing import Dict

from jax.sharding import NamedSharding, PartitionSpec as P

from nvme_strom_tpu.models.moe import moe_param_specs
from nvme_strom_tpu.models.transformer import TransformerConfig


def param_specs(cfg: TransformerConfig) -> Dict[str, P]:
    cfg.require_causal("a mesh (parallel/shardings.param_specs)")
    cfg.require_no_recurrent("a mesh (parallel/shardings.param_specs)")
    cfg.require_kv_pages("a mesh (parallel/shardings.param_specs)")
    cfg.require_pre_norm("a mesh (parallel/shardings.param_specs)")
    if cfg.expert_layers:
        # the exact expert layer holds every expert on one device: under
        # an ``ep`` axis its rows would need an exchange it does not have
        raise NotImplementedError(
            "a mesh (parallel/shardings.param_specs) does not shard the "
            "exact expert layer (mlp_kinds 'experts'): serve this config "
            "on one device")
    specs = {
        "tok_embed": P(None, "tp"),     # d_model sharded
        "final_norm": P(),
        "lm_head": P(None, "tp"),       # vocab logits sharded
    }
    for i in range(cfg.n_layers):
        L = f"layers.{i}."
        specs[L + "attn_norm"] = P()
        specs[L + "wq"] = P(None, "tp")   # heads split across tp
        specs[L + "wk"] = P(None, "tp")
        specs[L + "wv"] = P(None, "tp")
        specs[L + "wo"] = P("tp", None)   # row-parallel: psum after
        specs[L + "mlp_norm"] = P()
        if cfg.is_moe_layer(i):
            specs.update(moe_param_specs(cfg, L))
        else:
            specs[L + "w_gate"] = P(None, "tp")
            specs[L + "w_up"] = P(None, "tp")
            specs[L + "w_down"] = P("tp", None)
    return specs


#: The framework's canonical mesh axes.  A spec axis absent from the mesh
#: means "this parallelism feature is off → replicate" (the pjit idiom);
#: any OTHER name in a spec is a bug and must fail fast.
CANONICAL_AXES = frozenset({"dp", "tp", "sp", "pp", "ep"})


def prune_spec(spec: P, mesh) -> P:
    """Drop canonical axis names the mesh doesn't have, so one set of specs
    serves every mesh shape (dp×tp, dp×tp×sp, dp×ep, …).  Non-canonical
    names raise — a mesh with axes ('data', 'model') must not silently
    replicate everything."""
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if keep(a) is not None)
            return kept if kept else None
        if entry in mesh.shape:
            return entry
        if entry not in CANONICAL_AXES:
            raise ValueError(
                f"spec axis {entry!r} is neither in the mesh "
                f"{dict(mesh.shape)} nor a canonical axis "
                f"{sorted(CANONICAL_AXES)}")
        return None
    return P(*(keep(e) for e in spec))


def param_shardings(cfg: TransformerConfig, mesh) -> Dict[str, NamedSharding]:
    return {k: NamedSharding(mesh, prune_spec(spec, mesh))
            for k, spec in param_specs(cfg).items()}


def shard_params(params: Dict, cfg: TransformerConfig, mesh) -> Dict:
    """device_put every param leaf under its name's sharding — incl.
    int8-quantized leaves (models/quant.py): ``q8`` takes the weight's
    own spec and the broadcast-shaped ``scale`` takes the spec's
    OUTPUT-axis slice (its (..., 1, d_out) shape shards along d_out the
    same way the weight does), so tp-sharded quantized inference just
    works."""
    import jax

    sh = param_shardings(cfg, mesh)
    out = {}
    for name, w in params.items():
        if name not in sh:
            # fail fast like the manual {k: device_put(v, p_sh[k])}
            # pattern — an unplaced leaf would otherwise surface later
            # as jit's 'incompatible devices', far from the typo
            raise KeyError(f"no sharding spec for param {name!r}")
        s = sh[name]
        if isinstance(w, dict) and "q8" in w:
            spec = tuple(s.spec)
            # pad the spec to the q8 rank, then scale's rank matches
            spec = spec + (None,) * (w["q8"].ndim - len(spec))
            q_sh = NamedSharding(mesh, P(*spec))
            out[name] = {
                "q8": jax.device_put(w["q8"], q_sh),
                "scale": jax.device_put(
                    w["scale"],
                    NamedSharding(mesh, P(*spec[:-2], None, spec[-1]))),
            }
        elif isinstance(w, dict):
            # int4: q4 is (..., d_in/2, d_out) — the weight's own spec
            # applies (the packed axis halves the dim, the axis name
            # still shards it); scale4 has an extra group dim that
            # shards like d_in, with the within-group axis unsharded
            spec = tuple(s.spec)
            spec = spec + (None,) * (w["q4"].ndim - len(spec))
            out[name] = {
                "q4": jax.device_put(w["q4"],
                                     NamedSharding(mesh, P(*spec))),
                # group dim replicated: n_groups is typically far
                # smaller than the mesh axis (tiny tensor anyway);
                # only the d_out axis shards with the weight
                "scale4": jax.device_put(
                    w["scale4"],
                    NamedSharding(mesh, P(*[None] * (w["scale4"].ndim
                                                     - 1), spec[-1]))),
            }
        else:
            out[name] = jax.device_put(w, s)
    return out


def batch_spec(seq_sharded: bool = False) -> P:
    """(batch, seq) tokens: batch over dp; seq over sp when ring attention
    is in play (parallel/ring_attention.py)."""
    return P("dp", "sp") if seq_sharded else P("dp", None)


def batch_shardings(mesh, seq_sharded: bool = False) -> NamedSharding:
    if seq_sharded and "sp" not in mesh.shape:
        raise ValueError("mesh has no 'sp' axis for sequence sharding")
    return NamedSharding(mesh, batch_spec(seq_sharded))


def replicated_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def replicate_scalars(state, mesh):
    """device_put every 0-d array leaf of ``state`` as mesh-replicated.

    optax states mirror the params' shardings for mu/nu (zeros_like of
    sharded arrays) but create bare scalars (count) on the default device;
    a checkpoint restored under its recorded shardings then mixes
    single-device scalars with mesh-wide params and jit rejects the
    device sets.  Replicating scalars at init makes fresh and restored
    states placement-identical."""
    import jax
    rep = replicated_sharding(mesh)
    return jax.tree.map(
        lambda l: jax.device_put(l, rep)
        if getattr(l, "ndim", None) == 0 else l, state)
