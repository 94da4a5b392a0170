"""The seeded generator: the device's bits are numpy's; a checkpoint written
from it restores bit for bit through the program's loader."""

import numpy as np
import pytest

from benchmark import weights as W

TINY = dict(hidden_size=64, vocab_size=128, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128,
            num_hidden_layers=2, max_position_embeddings=256,
            rope_theta=1e6, rms_norm_eps=1e-5)


@pytest.mark.parametrize("seed", (0, 2**31 + 12345))
def test_device_bits_equal_the_definition(seed):
    params = W.make_params(TINY, seed)
    bs = W.bases(TINY, seed)
    for i, (name, shape) in enumerate(W.tensor_specs(TINY)):
        want = W.make_tensor_np(seed, i, name, shape).view(np.uint16)
        assert (np.asarray(params[name]).view(np.uint16) == want).all(), name
        again = np.asarray(W.one_tensor(bs[i], name, shape))
        assert (again.view(np.uint16) == want).all(), name


def test_scales():
    p = W.make_params(TINY, 3)
    f = {k: np.asarray(v).astype(np.float32) for k, v in p.items()}
    assert abs(f["tok_embed"].std() - 1.0) < 0.05
    assert abs(f["layers.0.w_down"].std() * 128 ** 0.5 - 1.0) < 0.05
    assert abs(f["final_norm"].mean() - 1.0) < 0.05
    assert not (f["layers.0.wq"] == f["layers.1.wq"]).all()
    other = np.asarray(W.make_params(TINY, 4)["layers.0.wq"])
    assert not (other == np.asarray(p["layers.0.wq"])).all()


def test_checkpoint_restores_bit_for_bit(tmp_path):
    import jax

    from benchmark.runners.restore import compare_with_generator
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    ck = W.ensure_checkpoint(str(tmp_path), TINY, 9, "tiny")
    assert ck["written_s"] > 0
    assert W.ensure_checkpoint(str(tmp_path), TINY, 9, "tiny")[
        "written_s"] == 0.0                      # the seed was there
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    sh = {n: one for n, _ in W.tensor_specs(TINY)}
    params = LazyCheckpoint(ck["dir"]).load_sharded(sh)
    checks = compare_with_generator(params, TINY, 9, sh, 1)
    assert all(v == 0 for _, v, _ in checks), checks
    # the exact comparison catches one flipped bit, and another seed
    bad = dict(params)
    a = np.asarray(bad["layers.1.w_up"]).copy()
    a.view(np.uint16)[3, 5] ^= 1
    bad["layers.1.w_up"] = jax.device_put(a)
    assert compare_with_generator(bad, TINY, 9, sh, 1)[0][1] == 1
    assert compare_with_generator(params, TINY, 10, sh, 1)[0][1] > 1000
    # a new seed replaces the old files: the directory does not grow
    W.ensure_checkpoint(str(tmp_path), TINY, 10, "tiny")
    assert compare_with_generator(
        LazyCheckpoint(ck["dir"]).load_sharded(sh), TINY, 10, sh, 1)[0][1] == 0
