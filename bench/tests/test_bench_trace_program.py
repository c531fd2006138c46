"""The reading of the program's own layers from a profiler trace: host
spans, device scopes and the idle split, on traces recorded on TPU v5e
chips and on hand-built events."""

import os

import pytest

from bench import trace_program as tp
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = {
    # cell: (modules on the busiest device, its figures from the runtime's
    # run_id links, read by hand: median µs of the device gap, its host
    # part and the rest; the clock offset's bounds in ms)
    "star13_512.step1": (54, 1371, 749, 626, (1.27, 1.71)),
    "mg27_512.apply_4chip": (34, 1851, 1263, 587, (1.51, 1.89)),
}


def _path(cell, new=False):
    return os.path.join(DATA, f"{cell}{'.scoped' if new else ''}.xplane.pb")


@pytest.mark.parametrize("cell", sorted(OLD))
def test_idle_split_on_recorded_traces(cell):
    """The split reproduces the figures read by hand (which are medians;
    the reading is a mean, within 2% of them), and its two parts add up
    to the mean gap between modules."""
    modules, gap, host, rest, (lo, hi) = OLD[cell]
    r = tp.read_file(_path(cell))
    assert r["busiest"] == 0 and r["modules"] == modules
    split = r["idle_split"]
    assert split["pairs"] == modules - 1
    assert split["module_gap_us"] == pytest.approx(gap, rel=0.02)
    assert split["host_gap_us"] == pytest.approx(host, rel=0.02)
    assert split["roundtrip_us"] == pytest.approx(rest, rel=0.02)
    assert split["host_gap_us"] + split["roundtrip_us"] == pytest.approx(
        split["module_gap_us"], rel=1e-12
    )
    assert split["offset_ms"] == pytest.approx([lo, hi], abs=0.005)
    m = tp.metrics(r)
    assert m["host.gap_us"] == split["host_gap_us"]
    assert m["device.roundtrip_us"] == split["roundtrip_us"]


@pytest.mark.parametrize("cell,new,op,tf_op", [
    ("star13_512.step1", False, "%pad.0 ",
     "jit(_stencil_call)/jit(_pad)/pad"),
    ("star13_512.step1", False, "%_stencil_call.1 ",
     "jit(_stencil_call)/pallas_call"),
    ("mg27_512.apply_4chip", False, "%collective-permute-start.1 ",
     "jit(run)/shard_map/ppermute"),
    ("mg27_512.apply_4chip", False, "%pad_maximum_fusion ",
     "jit(run)/shard_map/concatenate"),  # a fusion: its root's scope
    ("star13_512.step1", True, "%pad.0 ",
     "jit(_stencil_call)/stencil_embed/jit(_pad)/pad"),
    ("star13_512.step1", True, "%stencil_sweep.1 ",
     "jit(_stencil_call)/stencil_sweep/pallas_call"),
    ("mg27_512.apply_4chip", True, "%pad_maximum_fusion ",
     "jit(run)/shard_map/stencil_halo/concatenate"),
    ("mg27_512.apply_4chip", True, "%dynamic-update-slice.17 ",
     "jit(run)/stencil_wrap/scatter"),
])
def test_tf_op_reader(cell, new, op, tf_op):
    ops = tp.op_scopes(_path(cell, new))["/device:TPU:0"]
    found = {v for k, v in ops.items() if k.startswith(op)}
    assert found == {tf_op}


@pytest.mark.parametrize("cell", sorted(OLD))
def test_tf_op_reader_names_every_device_op(cell):
    """Every op on the device's ops line is named in the metadata the
    wire reader returns, under the name ``ProfileData`` gives it."""
    from jax.profiler import ProfileData

    devices, _ = tr.device_events(ProfileData.from_file(_path(cell)))
    scopes = tp.op_scopes(_path(cell))
    for dev, ops in devices.items():
        named = scopes[f"/device:TPU:{dev}"]
        missing = {name for name, _, _ in ops if name not in named}
        # Only ops the compiler made up have no scope (a constant's
        # broadcast); none of the launch's copies or kernels.
        assert all("broadcast" in name for name in missing), missing


@pytest.mark.parametrize("cell", sorted(OLD))
def test_span_and_scope_readers_find_nothing_on_old_traces(cell):
    r = tp.read_file(_path(cell))
    assert r["spans"] is None and r["scopes"] is None
    m = tp.metrics(r)
    for name in ("entry.lower_us", "plan.resolve_us", "launch.enqueue_us",
                 "launch.embed_ms", "launch.wrap_ms", "shard.halo_ms"):
        assert m[name] is None


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(_stencil_call)/stencil_embed/jit(_pad)/pad", "stencil_embed"),
    ("jit(run)/shard_map/stencil_halo/ppermute", "stencil_halo"),
    ("jit(f)/stencil_sweep/pallas_call", "stencil_sweep"),
    ("jit(run)/stencil_wrap/scatter", "stencil_wrap"),
    ("jit(_stencil_call)/jit(_pad)/pad", None),
    ("jit(f)/my_stencil_embed/add", None),
    (None, None),
])
def test_scope_of(tf_op, scope):
    assert tp.scope_of(tf_op) == scope


def test_span_means_hand_built():
    """Two calls on one thread; a plan outside any call and a launch on
    another thread are not counted.  Times in ns."""
    host = [
        ("stencil_call", 0, 1000, "py", {"call": 7}),
        ("ir_lower", 10, 200, "py", {}),
        ("plan", 220, 300, "py", {"cache": "hit"}),
        ("kernel_launch", 600, 100, "py", {}),
        ("kernel_launch", 750, 150, "py", {}),     # self 1000-750 = 250
        ("stencil_call", 2000, 800, "py", {"call": 8}),
        ("ir_lower", 2010, 190, "py", {}),
        ("plan", 2200, 300, "py", {"cache": "miss"}),
        ("kernel_launch", 2550, 200, "py", {}),    # self 800-690 = 110
        ("plan", 5000, 999, "py", {}),             # outside the calls
        ("kernel_launch", 100, 50, "other", {}),   # another thread
        ("PjitFunction(run)", 2560, 100, "py", {}),
    ]
    r = tp.span_means(host)
    assert r["calls"] == 2
    assert r["call_numbers"] == [7, 8]
    assert r["us"]["stencil_call"] == pytest.approx(0.9)
    assert r["us"]["ir_lower"] == pytest.approx(0.195)
    assert r["us"]["plan"] == pytest.approx(0.3)
    assert r["us"]["kernel_launch"] == pytest.approx(0.225)
    assert r["self_us"] == pytest.approx(0.18)
    assert r["plan_cache"] == {"hit": 1, "miss": 1}
    assert tp.span_means([("plan", 0, 5, "py", {})]) is None


def test_idle_split_hand_built():
    """Three modules; the host completes module n 30 ns after it ends
    (on the host clock, offset +1000) and enqueues n+1 after its own
    work.  A module whose links are missing leaves its pair out."""
    modules = [(0, 100, 1), (300, 400, 2), (700, 800, 3), (900, 950, 4)]
    links = {
        ("CompleteCallbacks", 1): (1130, 1150),
        ("DoEnqueueProgram", 2): (1200, 1260),   # module 2 at 1300
        ("CompleteCallbacks", 2): (1430, 1450),
        ("DoEnqueueProgram", 3): (1600, 1650),   # module 3 at 1700
        ("CompleteCallbacks", 3): (1830, 1840),  # no enqueue of 4
    }
    r = tp.idle_split(modules, links)
    assert r["pairs"] == 2
    assert r["module_gap_us"] == pytest.approx((200 + 300) / 2 / 1e3)
    assert r["host_gap_us"] == pytest.approx((50 + 150) / 2 / 1e3)
    assert r["roundtrip_us"] == pytest.approx((150 + 150) / 2 / 1e3)
    # offset >= enqueue end - module start; <= completion start - end
    assert r["offset_ms"] == pytest.approx([max(960, 950) / 1e6,
                                            min(1030, 1030) / 1e6])
    assert tp.idle_split(modules[:1], links) is None


def test_scope_times_and_metrics_hand_built():
    kernel = ('%stencil_sweep.1 = f32[8]{0} custom-call(f32[8]{0} %p), '
              'custom_call_target="tpu_custom_call"')
    pad = "%pad.1 = f32[8]{0} pad(f32[4]{0} %x, f32[] %c)"
    dus = "%dynamic-update-slice.2 = f32[8]{0} dynamic-update-slice(...)"
    cat = "%concatenate.3 = f32[8]{0} concatenate(...)"
    perm = "%collective-permute.4 = f32[2]{0} collective-permute(...)"
    bcast = "%broadcast.5 = s32[3]{0} broadcast(...)"
    tf_ops = {
        kernel: "jit(run)/shard_map/stencil_sweep/pallas_call",
        pad: "jit(run)/stencil_embed/pad",
        dus: "jit(run)/stencil_wrap/scatter",
        cat: "jit(run)/shard_map/stencil_halo/concatenate",
        perm: "jit(run)/shard_map/stencil_halo/ppermute",
    }
    ops = [(kernel, 0, 4000), (pad, 0, 2000), (dus, 0, 1000),
           (cat, 0, 600), (perm, 0, 300), (bcast, 0, 10)] * 2
    times = tp.scope_times(ops, tf_ops)
    assert times["stencil_sweep"] == {"kernel": pytest.approx(8e-6)}
    assert times["stencil_halo"] == {"xla": pytest.approx(1.2e-6),
                                     "collective": pytest.approx(0.6e-6)}
    assert times[None] == {"xla": pytest.approx(20e-9)}
    reading = {"spans": None, "scopes": times, "idle_split": None}
    m = tp.metrics(reading, calls=2)
    assert m["launch.embed_ms"] == pytest.approx(2e-3)
    assert m["launch.wrap_ms"] == pytest.approx(1e-3)
    assert m["shard.halo_ms"] == pytest.approx(0.6e-3)
    assert m["entry.lower_us"] is None
    assert tp.scope_times([(bcast, 0, 1)], tf_ops) is None


# Traces recorded with this engine's spans and scopes (`trace_program.py
# --record`, one chip per device plane): per cell the metrics that read
# something there.
ONE_CHIP = ("entry.lower_us", "plan.resolve_us", "launch.enqueue_us",
            "host.gap_us", "device.roundtrip_us", "launch.embed_ms")
SCOPED = {
    "star13_512.step1": ONE_CHIP,
    "mg27_512.apply_4chip": ONE_CHIP + ("launch.wrap_ms", "shard.halo_ms"),
}


@pytest.mark.parametrize("cell", sorted(SCOPED))
def test_span_reader_on_scoped_traces(cell):
    """One numbered ``stencil_call`` per call, each holding its children;
    every plan a cache hit; the root's self time under a fifth of it."""
    r = tp.read_file(_path(cell, new=True))
    spans = r["spans"]
    assert spans["calls"] == r["modules"] >= 20
    numbers = spans["call_numbers"]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    assert spans["plan_cache"] == {"hit": spans["calls"]}
    us = spans["us"]
    assert us["stencil_call"] == pytest.approx(
        us["ir_lower"] + us["plan"] + us["kernel_launch"] + spans["self_us"]
    )
    assert 0 < spans["self_us"] < 0.2 * us["stencil_call"]


@pytest.mark.parametrize("cell", sorted(SCOPED))
def test_scope_reader_on_scoped_traces(cell):
    """The kernel is all of ``stencil_sweep``; the launch scopes name all
    but a few ns of the launch's XLA ops, which ``trace_reduce`` sums as
    ``launch.xla_ms``; collectives only under ``stencil_halo``."""
    path = _path(cell, new=True)
    r = tp.read_file(path)
    red = tr.reduce_file(path, devices=None)
    dev = red["per_device"][red["busiest"]]
    scopes = r["scopes"]
    assert scopes["stencil_sweep"] == {
        "kernel": pytest.approx(dev["kernel_s"])
    }
    named = sum(scopes.get(s, {}).get("xla", 0.0) for s in tp.LAUNCH_SCOPES)
    assert named >= 0.999 * dev["xla_s"]
    collective = sum(k.get("collective", 0.0) for k in scopes.values())
    assert collective == pytest.approx(dev["collective_s"])
    assert collective == pytest.approx(
        scopes.get("stencil_halo", {}).get("collective", 0.0)
    )
    assert red["top_ops"][0][0].startswith("%stencil_sweep")


@pytest.mark.parametrize("cell", sorted(SCOPED))
def test_metrics_on_scoped_traces(cell):
    """Every metric the cell reports reads a value; the rest none."""
    r = tp.read_file(_path(cell, new=True))
    m = tp.metrics(r)
    assert {k for k, v in m.items() if v is not None} == set(SCOPED[cell])
    assert all(m[k] > 0 for k in SCOPED[cell])
    split = r["idle_split"]
    assert split["pairs"] == r["modules"] - 1
    lo, hi = split["offset_ms"]
    assert 0 < lo < hi
