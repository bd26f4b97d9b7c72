"""The program's spans and counters read from a trace: the host's
turnaround between decode steps, the padding of prefill dispatches, the
``serve.`` filter over a real trace, and idle gaps given to a program
span inside a harness span."""

import jax
import pytest

from benchmarks.chip import program_trace as pt
from benchmarks.chip import tracefile

D, F, P = pt.DISPATCH, pt.FETCH, pt.PREFILL


def decode_spans():
    # three steps: dispatch (enqueue) then fetch (wait for the tokens);
    # a prefill between the second and third
    return [
        ("Executor.decode", 0, 30), (D, 1, 3), (F, 4, 28),
        ("serve.scheduler.deliver", 29, 31),
        ("Executor.decode", 32, 60), (D, 33, 35), (F, 36, 58),
        (P, 62, 80),
        ("Executor.decode", 82, 99), (D, 83, 86), (F, 87, 98),
    ]


def test_turnaround_runs_from_a_fetch_to_the_next_dispatch():
    spans = decode_spans()
    # 28 -> 35 is a pair; 58 -> 86 holds a prefill and is left out
    assert pt.host_turnaround(spans, (0, 100)) == [7]
    assert pt.host_turnaround_ms(spans, (0, 100)) == pytest.approx(7e-6)


def test_turnaround_counts_only_steps_inside_the_window():
    spans = [(D, 1, 3), (F, 4, 10), (D, 12, 14), (F, 15, 20),
             (D, 22, 24), (F, 25, 40)]
    assert pt.host_turnaround(spans, (0, 100)) == [4, 4]
    # the last dispatch ends past the close: its pair is not in the window
    assert pt.host_turnaround(spans, (0, 23)) == [4]
    # the window opens after the second fetch began: no pair is whole
    assert pt.host_turnaround(spans, (16, 100)) == []


def test_no_program_spans_read_none():
    host = [("Executor.decode", 0, 30), ("Server.step", 0, 31)]
    assert pt.host_turnaround_ms(host, (0, 100)) is None
    assert pt.host_turnaround_ms([], (0, 100)) is None


def test_pad_share_of_the_counters_over_the_window():
    before = {"prefill_tokens": 10, "prefill_slot_tokens": 256,
              "decode_steps": 4, "queued": 3}
    after = {"prefill_tokens": 213, "prefill_slot_tokens": 768,
             "decode_steps": 9, "queued": 0}
    window = pt.counter_delta(before, after)
    assert window == {"prefill_tokens": 203, "prefill_slot_tokens": 512,
                      "decode_steps": 5, "queued": -3}
    assert pt.prefill_pad_share(window) == pytest.approx(
        100 * (1 - 203 / 512))


def test_no_counters_or_no_prefill_read_none():
    # a program without the counters: stats() lacks the positions
    assert pt.prefill_pad_share({"prefill_tokens": 40}) is None
    assert pt.prefill_pad_share({}) is None
    assert pt.prefill_pad_share(
        {"prefill_tokens": 0, "prefill_slot_tokens": 0}) is None


def test_load_keeps_only_the_programs_spans(tmp_path):
    from jax.profiler import TraceAnnotation

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("Executor.decode"):
            with TraceAnnotation(D) as sp:
                sp.set_metadata(step=0)
                jax.block_until_ready(jax.numpy.ones(4) + 1)
            with TraceAnnotation(F):
                pass
        with TraceAnnotation("serve_not_the_program"):
            pass
    spans = pt.load_spans(tracefile.find_xplane(str(tmp_path)))
    assert sorted(n for n, _, _ in spans) == sorted([D, F])
    (d,) = [x for x in spans if x[0] == D]
    (f,) = [x for x in spans if x[0] == F]
    assert d[1] <= d[2] <= f[1] <= f[2]


def test_a_program_span_inside_a_harness_span_takes_the_gap():
    spans = decode_spans()
    harness_spans = [x for x in spans if not x[0].startswith("serve.")]
    program = [x for x in spans if x[0].startswith("serve.")]
    gaps = [(5, 27), (29, 31), (60, 62)]
    # the harness alone puts the fetch's gap on Executor.decode
    assert dict(tracefile.label_gaps(gaps, harness_spans)) == {
        "Executor.decode": 24, "none": 2}
    assert dict(tracefile.label_gaps(gaps, harness_spans + program)) == {
        F: 22, "serve.scheduler.deliver": 2, "none": 2}
