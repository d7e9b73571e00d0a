"""The trace reduction on a recorded trace, against numbers worked out by
hand from its event list.

``data/flash3.xplane.pb`` (PR 24, one v5e chip): three calls of a jitted
step that runs the Pallas flash forward (``jvp__``) and its two backward
kernels (``transpose_jvp___``) once each on ``[1, 8192, 32, 64]`` bf16,
20 device operations a call, none overlapping. In nanoseconds from the
first operation's start:

    call  first op starts  last op ends  sum of its ops  its 3 Mosaic calls
    1                   0      21919373        21919351            20631016
    2            34330409      56251234        21920802            20632044
    3            68162104      90080526        21918401            20630506

So the device is busy 65,758,554 ns of a window of 90,080,526 ns, the
kernels take 61,893,566 ns of it, and the idle time is the two stretches
between the calls (12,411,036 and 11,910,870 ns) plus 66 ns of seams
inside the calls. The host's ``bench.tick`` spans end 2.2 ms into each
stretch, so neither is covered by half: both are ``host_idle``.
"""

import os

import pytest

from lib import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "flash3.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(xplane.load(TRACE))


def test_busy_and_window(reduced):
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(65758554e-9, rel=1e-9)
    assert reduced["window_s"] == pytest.approx(90080526e-9, rel=1e-9)
    idle_share = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle_share == pytest.approx(24321972 / 90080526, rel=1e-9)


def test_mosaic_kernel_time_and_calls(reduced):
    assert xplane.seconds_matching(reduced, r":tpu_custom_call$") == \
        pytest.approx(61893566e-9, rel=1e-9)
    assert xplane.calls_matching(reduced, r"^jvp.*:tpu_custom_call$") == 3
    assert xplane.calls_matching(
        reduced, r"^transpose.*:tpu_custom_call$") == 6
    # XLA's own fusions are not kernels
    assert "fusion" in reduced["op_seconds"]
    assert not any(k.startswith("fusion") and k.endswith("tpu_custom_call")
                   for k in reduced["op_seconds"])


def test_named_gaps(reduced):
    gaps = reduced["idle_gaps"]
    assert sum(gaps.values()) == pytest.approx(24321972e-9, rel=1e-9)
    assert gaps["host_idle"] == pytest.approx((12411036 + 11910870) * 1e-9,
                                              rel=1e-4)
    top = xplane.breakdown(reduced)
    assert top["idle_gaps"][0][0] == "host_idle"
    assert top["device_ops"][0][0] == "transpose_jvp___:tpu_custom_call"
    assert len(top["device_ops"]) <= 10


def test_window_from_a_host_span_and_gap_names():
    """A hand-made trace: two chips, a window given by a host span, one
    operation cut by the window's edge, a gap under a scheduler span."""
    op = '%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop'
    kernel = ('%attn.1 = bf16[8]{0} custom-call(bf16[8]{0} %q), '
              'custom_call_target="tpu_custom_call"')
    trace = {
        "devices": {
            "/device:TPU:0": {"ops": [(op, 50.0, 100.0),     # cut: 50 in
                                      (kernel, 200.0, 300.0),
                                      (op, 900.0, 100.0)], "modules": []},
            "/device:TPU:1": {"ops": [(kernel, 100.0, 400.0)],
                              "modules": []}},
        "host": {"main": [("bench.traced_window", 100.0, 900.0),
                          ("scheduler.tick", 480.0, 450.0),
                          ("$python frame", 0.0, 2000.0)]}}
    window = xplane.find_span(trace, "bench.traced_window")
    assert window == (100.0, 1000.0)
    r = xplane.reduce(trace, window, ignore=("bench.traced_window",))
    assert r["window_s"] == pytest.approx(900e-9)
    # chip 0: 50 + 300 + 100 = 450 busy; chip 1: 400; mean 425
    assert r["busy_s"] == pytest.approx(425e-9)
    # the cut fusion is in busy, not in the table; means over 2 chips
    assert r["op_seconds"]["fusion"] == pytest.approx(100e-9 / 2)
    assert r["op_seconds"]["attn:tpu_custom_call"] == \
        pytest.approx((300 + 400) * 1e-9 / 2)
    # chip 0's gaps: [150, 200] nobody's, [500, 900] under scheduler.tick
    assert r["idle_gaps"] == {"host_idle": pytest.approx(50e-9),
                              "scheduler.tick": pytest.approx(400e-9)}


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {}, "host": {}})
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {"/device:TPU:0": {"ops": [],
                                                     "modules": []}},
                       "host": {}})
