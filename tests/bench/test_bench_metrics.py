"""Whole-window rates and percentiles (time to first token from the due
time), and the per-layer readers on a hand-built record."""

from __future__ import annotations

import pytest

from bench import flops, harness, loadgen, peaks
from bench import trace as tr
from bench.drivers import serve

from conftest import TINY_MODEL


def test_percentile_matches_linear_interpolation():
    assert harness.percentile([1, 2, 3, 4], 50) == 2.5
    assert harness.percentile([10, 20, 30, 40, 50], 95) == pytest.approx(48)
    assert harness.percentile([5], 95) == 5
    assert harness.percentile([1, float("inf")], 0) == 1


def _rec():
    rec = serve.Record(TINY_MODEL, 4, t0=100.0, t_end=110.0)

    def req(rid, due, times):
        it = loadgen.Item(rid, [1, 2, 3], 4, due - 100.0)
        rec.reqs[rid] = serve.ReqRec(it, due, due + 0.1, times=times)

    req(0, 100.0, [101.0, 102.0, 103.0])  # ttft 1 s
    req(1, 104.0, [107.0, 111.0])  # ttft 3 s; second gap after the close
    req(2, 109.0, [])  # unserved: censored at close + drain
    req(3, 111.0, [112.0])  # due after the close: not counted
    return rec


def test_end_to_end_counts_from_due_time_over_the_window():
    e = serve.end_to_end(_rec(), drain_s=5.0)
    # tokens emitted inside [100, 110]: 101, 102, 103, 107
    assert e["out_tok_s"] == pytest.approx(0.4)
    # gaps with both ends in the window: 1 s, 1 s
    assert e["itl_p95_ms"] == pytest.approx(1000.0)
    # ttft: 1000, 3000, censored 6000 (110 + 5 - 109)
    assert e["ttft_samples"] == 3 and e["unserved"] == 1
    assert e["ttft_p50_ms"] == pytest.approx(3000.0)


def test_host_clock_readers():
    rec = _rec()
    rec.ticks = [serve.Tick(100.0, 100.5, True, 5, 19.0, 2),
                 serve.Tick(100.5, 100.6, False, 2, 15.0, 2),
                 serve.Tick(109.9, 110.4, True, 4, 10.0, 4)]
    rd = harness.load_reader
    assert rd("step_ms.prefill").read(rec) == pytest.approx(500.0)
    assert rd("step_ms.decode").read(rec) == pytest.approx(100.0)
    assert rd("batch_occupancy").read(rec) == pytest.approx(50.0)
    lags = rd("gen_lag_p95_ms").read(rec)
    assert lags == pytest.approx(100.0)
    rec.hit0, rec.hit1 = (0.5, 100), (0.6, 300)  # 50 + 130 cached of 200
    assert rd("prefix_hit_share").read(rec) == pytest.approx(65.0)
    rec.device_kind = "TPU v5 lite"
    # the third step ends after the close: only the first two count
    total = flops.step_flops(TINY_MODEL, 7, 34.0)
    assert total == 2 * flops.matmul_params(TINY_MODEL) * 7 + \
        2 * 4 * 4 * 16 * 34
    assert rd("serve_mfu").read(rec) == pytest.approx(
        100 * total / (10.0 * 197e12))


def test_live_length_kernel_work():
    L, H, KV, D = 2, 4, 2, 16
    f, b = flops.paged_attention_work(TINY_MODEL, 2, 8)
    assert f == L * 4 * H * D * 8
    assert b == L * (2 * KV * D * 2 * 8 + 2 * H * D * 2 * 2)


def test_roofline_reader_takes_live_work_over_kernel_time():
    rec = _rec()
    rec.device_kind = "TPU v5 lite"
    rec.ticks = [serve.Tick(100.0, 100.5, True, 3, 14.0, 2)]
    rec.traced = (0, 1)
    events = [("/host:CPU", "python", "bench.window", 0.0, 1e9),
              ("/device:TPU:0", tr.OPS_LINE, "paged_decode_kernel", 0.0,
               1e3)]
    rec.trace = tr.reduce(events)
    f, b = flops.paged_attention_work(TINY_MODEL, 3, 14.0)
    least = max(f / 197e12, b / 819e9)
    got = harness.load_reader("paged_attn_roofline").read(rec)
    assert got == pytest.approx(100 * least / 1e-6)
    assert harness.load_reader("idle_share.serve").read(rec) == \
        pytest.approx(100 * (1 - 1e-6))
    rec.trace = None
    assert harness.load_reader("paged_attn_roofline").read(rec) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks("TPU v99")
