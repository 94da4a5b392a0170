"""End-to-end and per-layer arithmetic on a hand-made log."""

import types

import pytest

from benchmark import costs, harness
from benchmark.end_to_end import data_gib_s, tok_s, ttft_p50_ms
from benchmark.layer_metrics import (admit_share, admit_wait_p50_ms,
                                     direct_share, gen_late_p90_ms,
                                     restore_s_p50, tpot_mean_ms,
                                     tpot_p50_ms, ttft_p90_ms)


def _req(due, submit, admit, first, last, n):
    return {"due": due, "t_submit": submit, "t_admit": admit,
            "t_first": first, "t_last": last, "n": n}


LOG = [_req(0.0, 0.10, 0.20, 1.00, 3.00, 11),     # ttft 1000, 10 gaps in 2 s
       _req(1.0, 1.00, 1.10, 1.50, 5.50, 41),     # ttft  500, 40 gaps in 4 s
       _req(2.0, 2.50, 2.60, 4.00, 4.90, 10)]     # ttft 2000,  9 gaps in .9 s


def _ctx(**facts):
    return types.SimpleNamespace(facts=facts, window_s=10.0,
                                 traffic={"drain_limit_s": 30})


def test_ttft_is_timed_from_due_not_submit():
    ctx = _ctx(requests=LOG, open_loop=True)
    assert ttft_p50_ms.read(ctx) == pytest.approx(1000.0)
    assert sorted(ttft_p50_ms.ttfts_ms(ctx)) == pytest.approx(
        [500.0, 1000.0, 2000.0])
    assert ttft_p90_ms.read(ctx) > 1000.0


def test_failed_request_counts_as_the_drain_limit():
    log = LOG + [_req(3.0, 3.0, None, None, None, 0)]
    assert max(ttft_p50_ms.ttfts_ms(_ctx(requests=log))) == 40000.0


def test_tpot_mean_is_a_ratio_of_sums():
    ctx = _ctx(requests=LOG)
    assert tpot_mean_ms.read(ctx) == pytest.approx(1000 * 6.9 / 59)
    # the median of per-request ratios is another number
    assert tpot_p50_ms.read(ctx) == pytest.approx(100.0)


def test_generator_lateness_and_admission_wait():
    ctx = _ctx(requests=LOG)
    assert admit_wait_p50_ms.read(ctx) == pytest.approx(200.0)
    assert gen_late_p90_ms.read(ctx) > 100.0


def test_rates_and_shares():
    ctx = _ctx(open_loop=False, tokens_in_window=1500,
               timings={"admit_s": 4.0})
    assert tok_s.read(ctx) == pytest.approx(150.0)
    assert admit_share.read(ctx) == pytest.approx(40.0)
    ctx = _ctx(restores=[(0.0, 2.0, 2**31), (2.5, 4.5, 2**31)],
               engine={"bytes_direct": 1, "bytes_fallback": 3})
    assert data_gib_s.read(ctx) == pytest.approx(4.0 / 4.5)
    assert restore_s_p50.read(ctx) == pytest.approx(2.0)
    assert direct_share.read(ctx) == pytest.approx(25.0)


def test_readers_with_nothing_to_read_return_nothing():
    ctx = _ctx()
    for mod in (data_gib_s, tok_s, tpot_mean_ms, ttft_p50_ms, admit_share,
                direct_share, restore_s_p50, tpot_p50_ms):
        assert mod.read(ctx) is None


def test_decode_step_bytes_against_a_hand_count():
    hf = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    layer = (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
             + 3 * 4096 * 14336 + 2 * 4096)
    assert costs.param_count(hf)["layer"] == layer == 218_112_000
    assert costs.kv_bytes_per_token(hf) == 2 * 24 * 8 * 128 * 2 == 98304
    want = 2 * (24 * layer + 4096 * 32768 + 4096 + 16 * 4096) \
        + 7000 * 98304
    assert costs.decode_step_bytes(hf, 16, 7000) == want
    total = costs.param_count(hf)["total"]
    assert round(total * 2 / 2**30, 2) == 10.25     # the checkpoint, GiB
    # memory bound at 16 rows: bytes/819e9 is far above flops/197e12
    assert want / 819e9 > costs.decode_step_flops(hf, 16, 7000) / 197e12
