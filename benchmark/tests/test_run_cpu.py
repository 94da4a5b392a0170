"""``run.py`` end to end on the CPU at a tiny preset, through the test-only
override of ``run.execute`` (no option of the program, none of the command
line): every kind of cell, a traced run, the control, and broken timed paths
that must come out as not correct."""

import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run
from benchmark.runners import serve

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = dict(hidden_size=64, vocab_size=128, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128,
            num_hidden_layers=2, max_position_embeddings=256,
            serving=dict(slots=4, max_len=256, block_len=16, total_blocks=64),
            correct=dict(served_mean_gap_limit=0.0008,
                         served_max_gap_limit=0.05))
TRAFFIC = {
    "m7b.restore": {}, "m7b-tp4.restore4": {},
    "m7b.flood": dict(requests=40, prompts=[16, 32, 48], budgets=[8, 12],
                      lookahead=4),
    "m7b.chat": dict(rate=3.0, lead_in_s=1, drain_limit_s=5,
                     pairs=[[16, 8], [32, 12], [48, 8]])}


def _run(workload, seed=2**31 + 77, trace=0, seconds=3, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC[workload],
                **test)
    return run.execute(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)],
                       test=test)


def test_no_cpu_fallback():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "m7b.restore", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and "no TPU" in p.stderr


@pytest.mark.parametrize("workload,metrics", [
    ("m7b.restore", {"data_gib_s", "setup_s"}),
    ("m7b-tp4.restore4", {"data_gib_s", "setup_s"}),     # 4 virtual devices
    ("m7b.flood", {"tok_s", "setup_s"}),
    ("m7b.chat", {"ttft_p50_ms", "setup_s"})])
def test_cell_end_to_end(workload, metrics):
    out, ctx = _run(workload)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert ctx.facts["compiles_in_window"] == 0


def test_traced_run_reports_per_layer_metrics():
    out, _ = _run("m7b.chat", trace=1)
    assert out["correct"] is True
    assert {"admit_share.chat", "gen_late_p90_ms.chat", "ttft_p90_ms.chat",
            "tpot_mean_ms.chat",
            "compiles_in_window.chat"} <= set(out["metrics"])
    assert "ttft_p50_ms" not in out["metrics"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_altered_token_is_not_correct():
    """The timed path broken where tokens are produced: slot 0's token is
    replaced in every decode step."""
    def break_step(srv):
        inner = srv._run_step

        def bad():
            nxt = inner()
            return nxt.at[0].set((nxt[0] + 1) % TINY["vocab_size"])
        srv._run_step = bad

    out, _ = _run("m7b.flood", server_built=break_step)
    assert out["correct"] is False


def test_flipped_bit_in_a_restored_tensor_is_not_correct():
    def flip(ctx, params, checks):
        import jax

        from benchmark.runners.restore import compare_with_generator
        a = np.asarray(params["layers.0.wk"]).copy()
        a.view(np.uint16)[0, 0] ^= 0x8000
        params["layers.0.wk"] = jax.device_put(a)
        one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        return compare_with_generator(
            params, ctx.config, ctx.seed, {n: one for n in params}, 1)

    out, _ = _run("m7b.restore", after_window=flip)
    assert out["correct"] is False


def test_int8_control_is_not_correct():
    """The control at test size: the reference computed in int8 in the
    program's place puts tokens first that the float32 reference holds far
    below its best; the sound program stays under the limit."""
    got = {}

    def hook(ctx, sample):
        ref = ctx.config["reference"]
        got["control"] = serve.control_gaps(ctx.config, ctx.seed, sample,
                                            ref)["mean_gap"]
        got["program"] = serve.served_gaps(ctx.config, ctx.seed, sample,
                                           ref)["mean_gap"]

    out, _ = _run("m7b.flood", seed=31, after_window=hook)
    limit = TINY["correct"]["served_mean_gap_limit"]
    assert out["correct"] is True and got["program"] <= limit
    assert got["control"] > limit
