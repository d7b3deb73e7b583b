"""The trace reduction, checked on a small profiler trace recorded on the
CPU, and the roofline byte counts."""

from __future__ import annotations

import time

import numpy as np
import pytest

import chip_bench_util  # noqa: F401  (puts the harness on the path)
import roofline
import tracesum

HOST_SLEEP_S = 0.05
GAP_S = 0.1


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp

    @jax.jit
    def busy_program(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((512, 512), jnp.float32) * 1e-3
    busy_program(x).block_until_ready()
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    jp.start_trace(str(log_dir), profiler_options=opts)
    for _ in range(3):
        with jp.TraceAnnotation(tracesum.BATCH_ANNOTATION):
            time.sleep(HOST_SLEEP_S)
            busy_program(x).block_until_ready()
        time.sleep(GAP_S)
    jp.stop_trace()
    return tracesum.read_xspace(tracesum.find_xspace(log_dir))


def test_batches_and_devices_are_found(summary):
    assert len(summary.batches) == 3
    assert summary.devices
    lo, hi = summary.window
    assert hi - lo >= 3 * HOST_SLEEP_S + 2 * GAP_S


def test_busy_and_idle_share(summary):
    lo, hi = summary.window
    busy = tracesum.busy_ns(summary)
    assert all(0 < b < hi - lo for b in busy.values())
    idle = tracesum.idle_pct(summary)
    # the host sleeps at least 0.35 s of the window, so the device idles
    assert 100 * (3 * HOST_SLEEP_S + 2 * GAP_S) / ((hi - lo) * 1e-9) <= idle + 1
    assert idle < 100


def test_program_time_is_found_by_name(summary):
    per_dev = tracesum.program_ns(summary, "busy_program")
    total = sum(per_dev.values())
    lo, hi = summary.window
    assert 0 < total <= hi - lo
    assert sum(tracesum.program_ns(summary, "no_such_program").values()) == 0


def test_host_time_per_batch(summary):
    host = tracesum.host_ns_per_batch(summary)
    assert len(host) == 3
    for (a, b), h in zip(summary.batches, host):
        assert HOST_SLEEP_S * 1e9 * 0.95 <= h < b - a


def test_breakdown(summary):
    bd = tracesum.breakdown(summary)
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    lo, hi = summary.window
    idle_s = sum(v for _, v in bd["idle_gaps"])
    busy_s = max(tracesum.busy_ns(summary).values()) * 1e-9
    # the ten largest shares of the idle time: nearly all of it here
    assert 0.95 * ((hi - lo) * 1e-9 - busy_s) <= idle_s
    assert idle_s <= (hi - lo) * 1e-9 - busy_s + 1e-9


def test_idle_time_goes_to_the_innermost_host_event():
    s = tracesum.TraceSummary(
        ops={"d": [("op", 10, 20), ("op", 50, 60)]}, batches=[(0, 100)],
        host=[("copy", 15, 30), ("wait", 12, 35), ("args", 40, 45)])
    gaps = dict(tracesum.breakdown(s)["idle_gaps"])
    assert gaps == pytest.approx({"copy": 10e-9, "wait": 5e-9,
                                  "args": 5e-9,
                                  "host code in knn_batch": 60e-9})


def test_union_of_intervals():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 29, 29)]
    s, t = tracesum.merged(evs, 0, 100)
    assert list(s) == [0, 20] and list(t) == [15, 30]
    s, t = tracesum.merged(evs, 8, 25)
    assert list(s) == [8, 20] and list(t) == [15, 25]
    assert tracesum.covered(s, t, 10, 22) == 5 + 2
    assert tracesum.merged([], 0, 1)[0].size == 0


def test_scan_least_bytes():
    # 2^20 codes of 64 bits read once, 64 x 100 results of 8 bytes
    assert roofline.scan_least_bytes(1 << 20, 64, 64, 100) == \
        (1 << 23) + 64 * 100 * 8
    # K is clamped to the corpus
    assert roofline.scan_least_bytes(10, 32, 2, 100) == 40 + 2 * 10 * 8
    assert roofline.scan_least_bytes(1 << 20, 128, 3, 1) == (1 << 24) + 24


def test_roofline_share():
    bw = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert bw == 819e9
    # the least bytes in exactly the least time is 100%
    assert roofline.roofline_pct(819e6, 1e-3, bw) == pytest.approx(100.0)
    assert roofline.roofline_pct(819e6, 4e-3, bw) == pytest.approx(25.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_peaks_table_has_its_source():
    import json

    table = json.loads(roofline.PEAKS_FILE.read_text())
    assert "TPU v5e" in table["source"]
    assert np.isclose(table["devices"]["TPU v5 lite"]["bf16_flops_per_s"],
                      197e12)
