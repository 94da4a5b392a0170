"""The lowered text of the two serving programs, ``_paged_step`` and
``_paged_prefill``, of every accepted serving configuration at its test size
(the ``TINY`` overrides of ``benchmark/tests``), on the CPU: what a PR that
adds a layer kind, a flag or a kernel runs on ITS tree and on the parent's to
show that the other configurations trace the programs they traced before.

    python3 benchmark/tools/lowered_text.py <checkout> <out dir>

writes ``<out dir>/<family>.<program>.txt`` and prints each text's sha256;
two checkouts' files are then compared byte for byte (``cmp``).  PR 43: all
ten texts of the parent and of the change are the same bytes."""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import sys

#: family -> (its test module under benchmark/tests, a configuration file
#: where the module's overrides sit on the dense one)
FAMILIES = {"mistral": ("test_run_cpu", "mistral-7b-v0.3"),
            "granite": ("test_granite_hybrid", None),
            "lfm2": ("test_lfm2_moe", None),
            "kimi": ("test_kimi_mla", None),
            "mimo": ("test_mimo_swa", None)}


def main() -> int:
    root, out_dir = os.path.abspath(sys.argv[1]), sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    os.makedirs(out_dir, exist_ok=True)
    spec = jax.ShapeDtypeStruct
    shas = {}
    for family, (module, file) in FAMILIES.items():
        m = importlib.import_module(f"benchmark.tests.{module}")
        base = m.HF if file is None else harness.load_json(
            "benchmark", "configs", file + ".json")
        hf = {**base, **m.TINY}
        cfg = config_from_hf(hf)
        sv = hf["serving"]
        B, bk, blocks = sv["slots"], sv["block_len"], sv["total_blocks"]
        params = {k: spec(v.shape, jnp.bfloat16) for k, v in jax.eval_shape(
            lambda: init_params(jax.random.key(0), cfg)).items()}
        layers = len(cfg.attn_layers)
        if cfg.latent:
            k_pool = spec((layers, blocks + 1, cfg.latent_width, bk),
                          cfg.dtype)
            v_pool = None
        else:
            k_pool = spec((layers, blocks + 1, cfg.n_kv_heads, bk,
                           cfg.head_dim), cfg.dtype)
            v_pool = spec(k_pool.shape[:-1] + (cfg.v_dim,), cfg.dtype)
        state = jax.eval_shape(lambda: serving.init_carried(cfg, B + 1, bk))

        def vec(n, dtype=jnp.int32):
            return spec((n,), dtype)

        def recur(n):
            return () if state is None else (state, vec(n))

        texts = {
            "step": serving._paged_step.lower(
                params, cfg, vec(B), k_pool, v_pool, vec(B), vec(B),
                spec((B, sv["max_len"] // bk), jnp.int32), vec(B),
                vec(B, jnp.float32), vec(B, jnp.float32),
                vec(B, jnp.uint32), *recur(B)).as_text(),
            "prefill": serving._paged_prefill.lower(
                params, cfg, k_pool, v_pool, spec((2, 4 * bk), jnp.int32),
                spec((2, 4), jnp.int32), vec(2), *recur(2)).as_text()}
        for program, text in texts.items():
            name = f"{family}.{program}"
            with open(os.path.join(out_dir, name + ".txt"), "w") as f:
                f.write(text)
            shas[name] = hashlib.sha256(text.encode()).hexdigest()
    print(json.dumps(shas, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
