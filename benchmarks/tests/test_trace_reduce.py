"""The trace reduction on a small recorded trace, against numbers worked out
by hand from the listing below (nanoseconds, ``start dur`` on the device's
``XLA Ops`` line of ``trace_fixture.txt``):

    1000     87       broadcast.74
    1089     1        copy-start.302
    1091     944475   jvp_flash_fwd_.12            <- flash_fwd
    945568   134208   reduce.63
    1079778  3        copy-done.302
    1079781  2292     reshape.955
    1087073  7113     copy-done.995                (5000 ns cut before it)
    1094187  654078   transpose_jvp_flash_bwd_dq__.12   <- flash_bwd_dq
    1748267  1        slice-start.1224
    1753268  717436   transpose_jvp_flash_bwd_dkv__.12  <- flash_bwd_dkv
    2470706  112956   copy.653
    2588662  872088   while.24                     spans the next four:
    2588668  13         fusion.896
    2588683  17         constant_dynamic-slice_fusion.48
    2588701  315        slice.748
    2589017  82         slice.749
    3465750  1901806  flash_decode.12              <- flash_decode
    10355314 1902553  flash_decode.13              <- flash_decode

Window: 12257867 - 1000 = 12256867. Busy union: the fourteen outer durations,
7249097 (the four inside ``while.24`` add 427 to a plain sum and nothing to
the union). Idle: 5007770, of which 4987758 after ``flash_decode.12``.
"""

import os

import pytest

from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.readers import trace_idle_share

FIXTURE = os.path.join(os.path.dirname(__file__), "trace_fixture.txt")
# as run.py gathers them from the cells' per-layer metric files
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_decode")


@pytest.fixture(scope="module")
def events():
    return tr.load_events(FIXTURE)


@pytest.fixture(scope="module")
def summary(events):
    return tr.reduce(events, 1, KERNELS)


def test_only_the_ops_line_of_device_planes_is_read(events):
    assert len(events) == 18
    assert {e.plane for e in events} == {"/device:TPU:0"}
    assert {e.line for e in events} == {"XLA Ops"}


def test_window_and_busy_union(summary):
    assert summary["window_s"] == pytest.approx(12256867e-9, rel=1e-12)
    assert summary["busy_s"] == pytest.approx(7249097e-9, rel=1e-12)
    assert summary["chips_traced"] == 1


def test_overlapping_events_are_not_counted_twice(events, summary):
    plain_sum = sum(e.dur_ns for e in events)
    assert plain_sum == 7249097 + 427
    assert summary["busy_s"] * 1e9 == pytest.approx(7249097)
    assert tr.busy_union_ns([(0, 10), (2, 5), (8, 12), (20, 21)]) == 13


def test_idle_share(summary):
    got = trace_idle_share.read({"trace": summary})
    assert got == pytest.approx(100.0 * 5007770 / 12256867, rel=1e-9)
    assert trace_idle_share.read({"trace": None}) is None


def test_per_kernel_sums(summary):
    k = summary["kernels"]
    assert k["flash_fwd"] == [pytest.approx(944475e-9), 1]
    assert k["flash_bwd_dq"] == [pytest.approx(654078e-9), 1]
    assert k["flash_bwd_dkv"] == [pytest.approx(717436e-9), 1]
    assert k["flash_decode"] == [pytest.approx(3804359e-9), 2]
    assert set(k) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                      "flash_decode"}


def test_top_operations_and_longest_gap(summary):
    top = summary["top_ops"]
    # a kernel's calls are taken together under the kernel's name
    assert [n for n, _ in top[:6]] == [
        "flash_decode", "flash_fwd", "while.24 s32[]", "flash_bwd_dkv",
        "flash_bwd_dq", "reduce.63 f32[384,512]"]
    assert top[0][1] == pytest.approx(3804359e-9)
    assert len(top) == 10
    name, seconds = summary["idle_gaps"][0]
    assert name == "after flash_decode.12 bf16[1536,1,64]"
    assert seconds == pytest.approx(4987758e-9)
    assert sum(s for _, s in summary["idle_gaps"]) <= 5007770e-9 + 1e-12


@pytest.mark.parametrize("name, kernel", [
    ("%jvp_flash_fwd_.23 = (bf16[384,512,64]{2,1,0}) custom-call()", "flash_fwd"),
    ("%transpose_jvp_flash_bwd_dq__.12 = bf16[3]{0} custom-call()", "flash_bwd_dq"),
    ("%transpose_jvp_flash_bwd_dkv__.12 = bf16[3]{0} custom-call()", "flash_bwd_dkv"),
    ("%flash_decode.20 = bf16[1536,1,64]{2,1,0} custom-call()", "flash_decode"),
    ("%reduce.196 = f32[2]{0} reduce(f32[4]{0} %flash_decode.20)", None),
    ("%flash_bwd_dq2.1 = f32[1]{0} custom-call()", None),
    ("%fusion.308 = f32[768,30522]{0,1} fusion()", None),
])
def test_kernel_names(name, kernel):
    assert tr.kernel_of(name, KERNELS) == kernel
    assert tr.kernel_of(name, ()) is None


def test_no_device_operation_gives_nothing():
    assert tr.reduce([], 1, KERNELS) is None
    host_only = [tr.Event("/host:CPU", "python", "fit_batch", 0.0, 9.0)]
    assert tr.reduce(host_only, 1, KERNELS) is None
