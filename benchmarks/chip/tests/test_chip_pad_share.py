"""``prefill_pad_share``'s reader: token positions the window's prefill
runs dispatched against the prompt tokens admitted, on synthetic runs,
and on a tiny served window where it must agree with the program's own
counters."""

import pytest

from benchmarks.chip import counts, harness, tracefile, traffic
from benchmarks.chip import program_trace as pt
from benchmarks.chip.cell import metric_reader

SEED = 2**31 + 77
read = metric_reader("prefill_pad_share.offline")


def make_run(cell, prefills, modules, n_devices=1):
    win = harness.Window(prefills=prefills)
    tr = tracefile.Trace([("fusion.1", 0, 1)], modules, [], (0, 100),
                         n_devices)
    return harness.Run(cell, counts.Model.from_config(cell.model), win, tr,
                       None)


@pytest.fixture
def cell(tiny_cell):
    c = tiny_cell("olmo-1b")
    c.config["serve"].update(batch_slots=2, prefill_chunk=128)
    return c


def test_two_chunks_for_prompts_of_5_and_200(cell):
    # prompts of 5 and 200 tokens write 4 + 199; the longest takes two
    # runs of 2 slots x 128 positions; a run outside the window and the
    # decode step count for nothing
    modules = [("jit__prefill_fn(4)", 10, 20), ("jit__prefill_fn(4)", 21, 30),
               ("jit__step_fn(3)", 31, 40), ("jit__prefill_fn(4)", 120, 130)]
    run = make_run(cell, [(0, 4), (1, 199)], modules)
    assert read(run) == pytest.approx(100 * (1 - 203 / 512))


def test_runs_are_counted_once_over_the_devices(cell):
    modules = [("jit__prefill_fn(4)", 10, 20)] * 4
    run = make_run(cell, [(0, 128), (1, 64)], modules, n_devices=2)
    assert read(run) == pytest.approx(100 * (1 - 192 / 512))


def test_no_prefill_run_reads_none(cell):
    assert read(make_run(cell, [], [("jit__step_fn(3)", 0, 9)])) is None
    assert read(make_run(cell, [(0, 9)], [])) is None


def test_a_served_window_agrees_with_the_programs_counters(tiny_cell):
    cell = tiny_cell("olmo-1b")
    params, server = harness.build(cell, SEED)
    planned = traffic.generate(cell.traffic, 2.0, SEED,
                               cell.model["vocab_size"])
    before = server.stats()
    win = harness.serve(server, planned, 2.0)
    got = pt.counter_delta(before, server.stats())
    assert got["prefill_dispatches"] > 0
    assert got["prefill_tokens"] == sum(k for _, k in win.prefills)
    # the chip's trace holds one prefill executable run per dispatch
    modules = [("jit__prefill_fn(4)", 2 * i, 2 * i + 1)
               for i in range(got["prefill_dispatches"])]
    run = make_run(cell, win.prefills, modules)
    assert read(run) == pytest.approx(pt.prefill_pad_share(got))
