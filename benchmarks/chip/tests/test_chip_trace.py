"""Trace reduction on a small synthetic trace: busy union, idle share,
time per executable and per kernel, idle gaps by host span."""

import pytest

from benchmarks.chip import tracefile


def make_trace():
    # device ops (ns): two overlapping, one alone, one outside the window
    ops = [("fusion.1", 0, 10), ("_decode_kernel", 5, 15),
           ("_prefill_kernel", 20, 30), ("fusion.2", 50, 60)]
    modules = [("jit__step_fn(3)", 0, 15), ("jit__prefill_fn(4)", 18, 31),
               ("jit__step_fn(3)", 45, 60)]
    host = [("Server.step", 0, 18), ("Executor.decode", 1, 16),
            ("Server.step", 18, 32), ("Executor.prefill", 18, 31),
            ("wait_arrival", 32, 40)]
    return tracefile.Trace(ops, modules, host, (0, 40))


def test_busy_is_the_union_of_device_operations_in_the_window():
    tr = make_trace()
    assert tracefile.busy_ns(tr) == 25          # [0,15] + [20,30]
    assert tr.window_s == pytest.approx(40e-9)
    idle = 1 - tracefile.busy_ns(tr) / 40
    assert idle == pytest.approx(0.375)


def test_busy_is_averaged_over_the_devices():
    tr = make_trace()
    tr.n_devices = 2
    assert tracefile.busy_ns(tr) == 12.5


def test_idle_gaps_and_their_host_spans():
    tr = make_trace()
    gaps = tracefile.idle_gaps(tr)
    assert gaps == [(15, 20), (30, 40)]
    labels = tracefile.label_gaps(gaps, tr.host)
    # (15, 20): midpoint 17 lies in Server.step only (decode ended at 16);
    # (30, 40): midpoint 35 in wait_arrival
    assert dict(labels) == {"Server.step": 5, "wait_arrival": 10}
    assert tracefile.top(labels, scale=1) == [["wait_arrival", 10],
                                              ["Server.step", 5]]


def test_innermost_span_takes_the_gap():
    host = [("window_like", 0, 100), ("Server.step", 10, 90),
            ("Executor.prefill", 40, 60)]
    labels = tracefile.label_gaps([(45, 55), (20, 30), (95, 99)], host)
    assert dict(labels) == {"Executor.prefill": 10, "Server.step": 10,
                            "window_like": 4}
    assert dict(tracefile.label_gaps([(5, 6)], [])) == {"none": 1}


def test_time_per_executable_and_per_kernel():
    tr = make_trace()
    assert tracefile.time_of(tr.modules, lambda n: "_step_fn" in n,
                             tr.window) == (15, 1)
    assert tracefile.time_of(tr.modules, lambda n: "_prefill_fn" in n,
                             tr.window) == (13, 1)
    assert tracefile.time_of(tr.ops, lambda n: "_decode_kernel" in n,
                             tr.window) == (10, 1)
    assert tracefile.time_of(tr.ops, lambda n: "nothing" in n,
                             tr.window) == (0, 0)
    totals = tracefile.op_totals(tr)
    assert totals["_prefill_kernel"] == 10 and "fusion.2" not in totals


def test_events_are_clipped_to_the_window():
    assert tracefile.clip([("a", -5, 5), ("b", 35, 50), ("c", 41, 50)],
                          0, 40) == [("a", 0, 5), ("b", 35, 40)]
    assert tracefile.merge([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4),
                                                                 (5, 9)]


def test_loops_count_once_and_names_shorten():
    ops = [("%while.4 = (s32[], bf16[2,8]{1,0}) while((s32[], bf16[2,8]) "
            "%t), body=%b", 0, 100),
           ("%copy.7 = bf16[16,20,2048]{2,1,0:T(8,128)(2,1)} copy(bf16[16,"
            "20,2048]{2,1,0} %p)", 10, 40),
           ("%closed_call.10 = bf16[20,16,1,128]{3,2,1,0:T(2,128)(2,1)S(1)} "
            "custom-call(s32[20]{0} %a), custom_call_target=\"tpu_custom_call\"",
            50, 60),
           ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %x)", 120, 130)]
    assert [e[0][:8] for e in tracefile.leaves(ops)] == [
        "%copy.7 ", "%closed_", "%fusion."]
    tr = tracefile.Trace(ops, [], [], (0, 200))
    assert dict(tracefile.op_totals(tr)) == {
        "copy.7 copy bf16[16,20,2048]": 30,
        "closed_call.10 custom-call bf16[20,16,1,128]": 10,
        "fusion.1 fusion f32[4]": 10,
    }
    assert tracefile.short_name("no equals sign") == "no equals sign"


def test_events_inside_the_runs_of_an_executable():
    runs = [(0, 10), (20, 30)]
    ops = [("a", 1, 2), ("b", 12, 13), ("c", 29, 31), ("d", 30, 32)]
    assert [e[0] for e in tracefile.inside(ops, runs)] == ["a", "c"]
