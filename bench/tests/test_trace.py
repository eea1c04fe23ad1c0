"""The trace reduction on a synthetic profile with known answers, and on
a small trace recorded on a TPU v5e (a short traced replay window)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from harness import trace

RECORDED = Path(__file__).resolve().parent / "data" / "small_replay.xplane.pb.gz"


def _ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def _profile():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 1000),
        _ev("bench.pass", 10, 900),
        _ev("bench.pack", 20, 100),
        _ev("bench.run_window", 130, 700),
        _ev("not.ours", 0, 1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("%kernel = f32[8,128]{1,0} "
                                       "custom-call(u32[8]{0} %keys)", 200, 300),
                                   _ev("fusion", 400, 200),   # overlaps
                                   _ev("copy", 950, 100)]),   # leaves window
        NS(name="XLA Modules", events=[_ev("jit_step(1)", 200, 400)]),
        NS(name="Steps", events=[_ev("0", 0, 2000)])])
    return NS(planes=[host, dev, NS(name="/device:CPU:0", lines=[])])


def test_reduce_synthetic():
    red = trace.reduce(_profile())
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(1e-3)
    # busy: [200, 600] and [950, 1000] -> 450 us
    assert red.busy_s == pytest.approx(450e-6)
    assert red.op_s == pytest.approx({"kernel": 300e-6, "fusion": 200e-6,
                                      "copy": 50e-6})
    assert red.module_time("jit_step") == pytest.approx(400e-6)
    assert red.span_s["bench.pack"] == pytest.approx(100e-6)
    # idle: [0, 200) mid 100 -> pack; [600, 950) mid 775 -> run_window
    assert red.idle_by_span == pytest.approx({"bench.pack": 200e-6,
                                              "bench.run_window": 350e-6})
    b = red.breakdown()
    assert b["device_ops"][0] == ["kernel", pytest.approx(300e-6)]
    assert b["idle_gaps"][0][0].startswith("bench.run_window")


def test_reduce_without_window_is_empty():
    p = _profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[1:]
    red = trace.reduce(p)
    assert not red.has_device and red.window_s == 0


def _run(ops, modules):
    import numpy as np

    red = trace.Reduced(window_s=1.0, busy_s=0.5, n_devices=1, op_s=ops,
                        module_s=modules)
    b = NS(trace=NS(observations=1_000_000), widths=np.array([1000, 3000]))
    mode = NS(windows=4, passes=1, b=b)
    ref = NS(n_used=[np.array([1, 2])] * 8)
    return NS(trace=red, mode=mode, ref=ref,
              peaks=lambda: {"hbm_bytes_s": 819e9})


@pytest.mark.parametrize("ops,modules,kernel_s", [
    # the Pallas custom call, named after its jitted wrapper
    ({"_fleet_update_ragged_jit.1": 0.02, "copy.3": 0.5}, {}, 0.02),
    ({"fleet_ragged_kernel": 0.02}, {}, 0.02),
    # no operation by name: the wrapper's program
    ({"fusion.1": 0.5}, {"jit__fleet_update_ragged_jit(7)": 0.03}, 0.03),
])
def test_update_kernel_readers(ops, modules, kernel_s):
    from harness import cell

    run = _run(ops, modules)
    ms = cell.reader("replay.update_kernel_ms_per_window")(run)
    assert ms == pytest.approx(1e3 * kernel_s / 4)
    # 12 B x 1e6 observations + 4 B x 8 epochs x (1000 + 6000) counters
    least = (12e6 + 4 * 8 * 7000) / 819e9
    roof = cell.reader("replay.update_kernel_roofline")(run)
    assert roof == pytest.approx(100 * least / kernel_s)


def test_reduce_recorded_chip_trace():
    """One pass of ``ft14-cms.replay`` traced on a TPU v5e (seed
    2147483999, ``--seconds 0.5``): its planes, lines and names as the
    chip writes them, reduced to the figures read off it once by hand."""
    from harness import find

    red = trace.reduce(trace.load(str(RECORDED)))
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(1.173257969)
    assert red.busy_s == pytest.approx(0.042393006)
    assert red.span_n == {"bench.pack": 32, "bench.pass": 1,
                          "bench.pass_boundary": 1, "bench.run_window": 4}
    # an operation is named without its HLO text
    assert all(" = " not in n and not n.startswith("%") for n in red.op_s)
    kernel = find.module("metrics", "replay.update_kernel_ms_per_window")
    assert kernel.kernel_s(red) == pytest.approx(0.011723134)
    assert red.module_time("_fleet_update_ragged_jit") > kernel.kernel_s(red)
    gaps = red.breakdown()["idle_gaps"]
    assert gaps[0][0] == "bench.run_window (119 gaps)"
    assert gaps[0][1] == pytest.approx(1.130864963)


def test_readers_find_nothing_without_the_kernel():
    from harness import cell

    run = _run({"fusion.1": 0.5}, {"jit_other(1)": 0.1})
    assert cell.reader("replay.update_kernel_ms_per_window")(run) is None
    assert cell.reader("replay.update_kernel_roofline")(run) is None
