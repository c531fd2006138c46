"""The trace reduction, on a trace recorded on one TPU v5e and on a
hand-built one, and the required work of each configuration."""

import json
import os

import pytest

from bench import trace_reduce as tr
from bench import work

DATA = os.path.join(os.path.dirname(__file__), "data")
CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")
TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "traffic")


def _load(kind, name):
    with open(os.path.join(CONFIGS if kind == "c" else TRAFFIC,
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,opcode,kind", [
    ('%_stencil_call.1 = f32[512,512,512]{2,1,0:T(8,128)} custom-call('
     's32[3]{0} %broadcast.1, f32[516,520,640]{2,1,0} %pad.0), '
     'custom_call_target="tpu_custom_call"', "custom-call", "kernel"),
    ('%pad.0 = f32[516,520,640]{2,1,0:T(8,128)} pad(f32[512,512,512]{2,1,0} '
     '%us_0_.1, f32[] %constant), padding=2_2x2_6x2_126', "pad", "xla"),
    ('%collective-permute-start.1 = (f32[1,4,4]{2,1,0}, f32[1,4,4]{2,1,0}, '
     'u32[], u32[]) collective-permute-start(f32[1,4,4]{2,1,0} %slice.2), '
     'source_target_pairs={{0,1},{1,2}}', "collective-permute-start",
     "collective"),
    ('%fusion.3 = f32[8]{0} fusion(f32[8]{0} %collective-permute-done.1), '
     'kind=kLoop', "fusion", "xla"),
    ('%all-reduce = f32[] all-reduce(f32[] %x), to_apply=%add', "all-reduce",
     "collective"),
    ("fusion.7", "fusion.7", "xla"),
])
def test_op_kind_by_opcode(name, opcode, kind):
    assert tr.hlo_opcode(name) == opcode
    assert tr.op_kind(name) == kind


def test_union_length():
    assert tr.union_length([]) == 0
    assert tr.union_length([(0, 10), (5, 12), (20, 25), (24, 26), (1, 2)]) \
        == 12 + 6


KERNEL = '%k.1 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"'
PAD = "%pad.1 = f32[8]{0} pad(f32[4]{0} %x, f32[] %c), padding=2_2"
PERMUTE = "%collective-permute.1 = f32[2]{0} collective-permute(f32[2]{0} %s)"


def _hand_built():
    """Two devices, two calls; times in ns.  Device 0 runs pad, kernel,
    and a permute that overlaps the kernel; device 1 only the kernel.
    The device clock runs 1000 ns behind the host's."""
    dev0 = [
        (PAD, 0, 100), (KERNEL, 100, 300),
        (PERMUTE, 350, 100),              # busy 0..450
        (PAD, 1000, 100), (KERNEL, 1100, 300),
        (PERMUTE, 1350, 100),             # busy 1000..1450
    ]
    dev1 = [(KERNEL, 100, 300), (KERNEL, 1100, 300)]
    host = [
        ("bench.dispatch", 900, 200, "python"),
        ("PJRT_LoadedExecutable_Execute", 1000, 50, "python"),
        ("bench.block", 1100, 700, "python"),
        ("bench.dispatch", 1800, 300, "python"),
        ("PJRT_LoadedExecutable_Execute", 2000, 50, "python"),
        ("bench.block", 2100, 600, "python"),        # window 900..2700
        ("tpu::System::Execute", 1500, 10, "other"),
    ]
    return {0: dev0, 1: dev1}, host


def test_reduce_hand_built():
    devices, host = _hand_built()
    r = tr.reduce(devices, host, use=[0, 1])
    d0, d1 = r["per_device"][0], r["per_device"][1]
    assert d0["busy_s"] == pytest.approx(900e-9)
    assert d0["kernel_s"] == pytest.approx(600e-9)
    assert d0["xla_s"] == pytest.approx(200e-9)
    assert d0["collective_s"] == pytest.approx(200e-9)
    assert d1["busy_s"] == pytest.approx(600e-9)
    assert d1["collective_s"] == 0
    assert r["busiest"] == 0
    assert r["busy_s"] == pytest.approx(750e-9)
    assert r["window_s"] == pytest.approx(1800e-9)
    # Device 0's gaps on the host clock (shift +1000): 1450..2000 (block
    # then dispatch: the middle, 1725, is in block) and 2450..2700.
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.block"] == pytest.approx((550 + 250) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(1800e-9 - 900e-9)
    assert gaps["bench.dispatch"] == pytest.approx(100e-9)
    assert r["top_ops"][0] == ["%k.1 kernel", pytest.approx(600e-9)]


def test_reduce_recorded_tpu_trace():
    """54 calls of star13_512.step1 traced on one TPU v5e: per call one
    pad, one broadcast and one Mosaic kernel; the sums were taken by hand
    from the trace's events."""
    r = tr.reduce_file(os.path.join(DATA, "star13_512.step1.xplane.pb"),
                       devices=[0])
    d = r["per_device"][0]
    assert d["ops"] == 162
    assert d["kernel_s"] == pytest.approx(123_518_421e-9)
    assert d["xla_s"] == pytest.approx(104_061_307e-9)
    assert d["collective_s"] == 0
    assert d["busy_s"] == pytest.approx(227_579_728e-9)  # no op overlaps
    assert r["window_s"] == pytest.approx((348_719_128 - 46_245_449) * 1e-9)
    idle = 1 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.24760, abs=1e-5)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6
    )
    names = [k for k, _ in r["top_ops"]]
    assert names[:2] == ["%_stencil_call.1 kernel", "%pad.0 pad"]


@pytest.mark.parametrize("config,traffic,expect", [
    # 512^3 f32: 2 x 512 MiB per call; 13 taps, 2 flops each, per point.
    ("star13_512", "step1", (2 * 512**3 * 4, 2 * 13 * 512**3)),
    ("star13_512", "smooth4", (2 * 512**3 * 4, 4 * 2 * 13 * 512**3)),
    # 21 non-zero taps of operator A (the six faces weigh 0).
    ("mg27_512", "apply_fixed", (2 * 512**3 * 4, 2 * 21 * 512**3)),
    ("mg27_1024", "apply_fixed", (2 * 1024**3 * 4, 2 * 21 * 1024**3)),
    ("jacobi2d_2800", "program_step1", (2 * 2800**2 * 4, 2 * 5 * 2800**2)),
])
def test_required_work(config, traffic, expect):
    w = work.call_work(_load("c", config), _load("t", traffic))
    assert (w["bytes"], w["flops"]) == expect


def test_reduce_recorded_four_chip_trace():
    """34 calls of mg27_512.apply_4chip traced on a 2x2 v5e host: per
    chip the pad and wrap fill, two halo permutes, the Mosaic kernel.
    Device 0's ``Async XLA Ops`` line (each permute's time in flight) is
    not busy time and is left out; the sums were taken by hand from the
    ``XLA Ops`` lines."""
    r = tr.reduce_file(os.path.join(DATA, "mg27_512.apply_4chip.xplane.pb"),
                       devices=[0, 1, 2, 3])
    d0 = r["per_device"][0]
    assert d0["ops"] == 578
    assert d0["kernel_s"] == pytest.approx(28_428_717e-9)
    assert d0["collective_s"] == pytest.approx(1_105_207e-9)
    assert d0["xla_s"] == pytest.approx(57_705_521e-9)
    assert d0["busy_s"] == pytest.approx(87_239_445e-9)  # no op overlaps
    assert r["per_device"][1]["collective_s"] == pytest.approx(1_079_522e-9)
    assert r["busiest"] == 0
    assert r["busy_s"] == pytest.approx(
        (87_239_445 + 87_212_182 + 87_188_205 + 87_186_081) / 4 * 1e-9
    )
