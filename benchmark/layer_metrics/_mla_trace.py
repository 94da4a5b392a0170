"""What is the latent attention's own: the absorbed-form kernel's fixed name
(``ops/mla_attention.py``) and the configuration test.  The walks are
``_kernel_trace``'s."""

from __future__ import annotations

KERNEL = "strom_mla_attn"


def is_latent(config: dict) -> bool:
    return bool(config.get("kv_lora_rank"))
