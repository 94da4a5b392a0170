"""chip_smoke.py off the chip: it refuses to run, and its pieces hold at
tiny widths on the CPU (the chip run itself is `python chip_smoke.py`)."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from nvme_strom_tpu.io import StromEngine
from nvme_strom_tpu.parallel.weights import LazyCheckpoint
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the Llama-3.1-8B keys with every width cut — tests only
TINY = dict(chip_smoke.LLAMA31_8B, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
            max_position_embeddings=256)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    out = tmp_path_factory.mktemp("smoke_ckpt")
    got = chip_smoke.write_checkpoint(str(out), chip_smoke.hf_config(2, TINY),
                                      seed=3, shard_bytes=1 << 16)
    got["dir"] = str(out)
    return got


@pytest.fixture()
def engine():
    cfg = EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                       buffer_pool_bytes=16 << 20)
    with StromEngine(cfg, stats=StromStats()) as e:
        yield e


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_without_a_tpu_exits_nonzero_and_prints_no_ok(argv):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "platform=cpu" in r.stdout.splitlines()[0]
    assert "needs a TPU" in r.stdout
    assert not os.path.exists(chip_smoke.DATA_DIR) or \
        not os.listdir(chip_smoke.DATA_DIR)    # nothing was run


def test_writer_round_trips_through_lazy_checkpoint(ckpt):
    """Names and shapes are ``init_params``' own, the layout is the one
    ``examples/serve.py --weights`` takes, every tensor reads back as
    the bytes the generator draws again from the seed."""
    from examples.serve import read_config
    from nvme_strom_tpu.models.transformer import init_params
    cfg = read_config(ckpt["dir"])
    assert cfg == ckpt["cfg"]
    assert (cfg.d_model, cfg.n_layers, cfg.rope_theta) == (64, 2, 500000.0)
    assert cfg.rope_scaling_dict["rope_type"] == "llama3"
    want = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    assert len(ckpt["shards"]) > 1
    lazy = LazyCheckpoint(ckpt["dir"])
    assert set(lazy.keys()) == set(want)
    params = lazy.load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]))
    for i, (name, shape) in enumerate(ckpt["specs"]):
        assert shape == want[name].shape == params[name].shape
        assert lazy.dtype(name) == "bfloat16"
        again = chip_smoke.make_tensor(3, i, name, shape)
        assert np.array_equal(np.asarray(params[name]).view(np.uint16),
                              again.view(np.uint16)), name
    other_seed = chip_smoke.make_tensor(4, 0, *ckpt["specs"][0])
    assert not np.array_equal(
        other_seed.view(np.uint16),
        np.asarray(params[ckpt["specs"][0][0]]).view(np.uint16))


def test_four_chip_phase_on_virtual_devices(ckpt, engine, capsys):
    """The --chips 4 path end to end on 4 virtual CPU devices: sharded
    restore with the per-device share check, forward comparison,
    exchange comparison."""
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip(f"needs 4 devices, have {len(devs)}")
    chip_smoke.phase_four_chips(engine, ckpt, 3, devs[:4],
                                weights_dir=ckpt["dir"])
    out = capsys.readouterr().out
    assert "1/4 on each of 4 distinct devices" in out
    assert "backend=lax_all_gather" in out and "byte for byte equal" in out


def test_share_check_rejects_everything_on_one_device(ckpt, engine,
                                                      monkeypatch):
    """The check the four-chip phase exists for: a restore that leaves
    every tensor whole on device 0 must fail it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from nvme_strom_tpu.parallel import shardings
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip(f"needs 4 devices, have {len(devs)}")
    real = shardings.param_shardings

    def on_device_zero(cfg, mesh):
        one = jax.sharding.Mesh(np.array(devs[:1]), ("tp",))
        return {k: NamedSharding(one, P()) for k in real(cfg, mesh)}

    monkeypatch.setattr(shardings, "param_shardings", on_device_zero)
    with pytest.raises(AssertionError):
        chip_smoke.phase_four_chips(engine, ckpt, 3, devs[:4],
                                    weights_dir=ckpt["dir"])
