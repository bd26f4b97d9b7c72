"""The serve loop's profiler spans and counters.

A tiny server runs under ``jax.profiler`` on the CPU; the host plane of
the trace it writes holds every ``serve.`` span of the loop, each decode
dispatch before its fetch inside one scheduler step, and no span nested
in one of its own name.  The counters equal what the run dispatched.
"""

import glob

import jax
import numpy as np
import pytest

from repro.models import get_smoke_bundle
from repro.serve import Request, ServeConfig, Server

SPANS = (
    "serve.scheduler.step", "serve.scheduler.reap",
    "serve.scheduler.preempt", "serve.scheduler.admit",
    "serve.scheduler.replan", "serve.scheduler.deliver",
    "serve.scheduler.sync", "serve.executor.prefill",
    "serve.executor.prefill.chunk", "serve.executor.prefill.wait",
    "serve.executor.decode.dispatch", "serve.executor.decode.tap",
    "serve.executor.decode.fetch", "serve.executor.decode.observe",
)
#: prompts of 5 and 200 tokens, written 4 and 199 (the last is decoded),
#: in chunks of 128 over 2 slots: 2 dispatches of 2 x 128 positions
PROMPTS = (5, 200)
NEW_TOKENS = 3


@pytest.fixture(scope="module")
def bundle():
    return get_smoke_bundle("olmo-1b")


@pytest.fixture(scope="module")
def params(bundle):
    return bundle.init_params(jax.random.PRNGKey(0), "float32")


def _server(bundle, params):
    srv = Server(
        bundle, ServeConfig(batch_slots=2, max_len=256, prefill_chunk=128),
        params,
    )
    srv.add_requests([
        Request(rid=k, prompt=(np.arange(n, dtype=np.int32) % 500) + 1,
                max_new_tokens=NEW_TOKENS)
        for k, n in enumerate(PROMPTS)
    ])
    return srv


@pytest.fixture(scope="module")
def traced(bundle, params, tmp_path_factory):
    """(spans, server) of one run to the end under the profiler, with a
    logits tap set so that the tap's span is there too."""
    from jax.profiler import ProfileData

    srv = _server(bundle, params)
    srv.engine.logits_tap = lambda step, logits, new_lens: None
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        srv.run_until_done(50)
    (path,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    spans = [
        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
         dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("serve.")
    ]
    return spans, srv


def _of(spans, name):
    return sorted((s, e, meta) for n, s, e, meta in spans if n == name)


def test_every_span_of_the_loop_is_in_the_trace(traced):
    spans, _ = traced
    assert {n for n, *_ in spans} == set(SPANS)
    steps = _of(spans, "serve.scheduler.step")
    assert [m["tick"] for *_, m in steps] == list(range(1, len(steps) + 1))
    (admit,) = [m for *_, m in _of(spans, "serve.scheduler.admit")
                if m["rids"] != "[]"]
    assert admit["rids"] == "[0, 1]"
    assert [m["rows"] for *_, m in _of(spans, "serve.executor.prefill")] \
        == [2]
    assert [m["chunk"] for *_, m in
            _of(spans, "serve.executor.prefill.chunk")] == [0, 1]


def test_each_dispatch_precedes_its_fetch_inside_one_step(traced):
    spans, _ = traced
    steps = _of(spans, "serve.scheduler.step")
    dispatches = _of(spans, "serve.executor.decode.dispatch")
    fetches = _of(spans, "serve.executor.decode.fetch")
    assert len(dispatches) == len(fetches) == NEW_TOKENS
    assert [m["step"] for *_, m in dispatches] == list(range(NEW_TOKENS))
    for (ds, de, _), (fs, fe, _) in zip(dispatches, fetches):
        assert de <= fs
        (step,) = [(s, e) for s, e, _ in steps if s <= ds and fe <= e]
        assert step


def test_no_span_nests_in_its_own_name(traced):
    spans, _ = traced
    for name in SPANS:
        ev = _of(spans, name)
        for (_, e0, _), (s1, _, _) in zip(ev, ev[1:]):
            assert e0 <= s1, name


def test_counters_equal_what_the_run_dispatched(traced):
    _, srv = traced
    st = srv.stats()
    assert st["prefill_dispatches"] == 2
    assert st["prefill_slot_tokens"] == 2 * 2 * 128
    assert st["prefill_tokens"] == sum(n - 1 for n in PROMPTS) == 203
    assert st["decode_steps"] == NEW_TOKENS
    assert st["decode_tokens"] == len(PROMPTS) * NEW_TOKENS


def test_counters_count_without_a_trace(bundle, params):
    srv = _server(bundle, params)
    srv.run_until_done(50)
    st = srv.stats()
    assert (st["prefill_dispatches"], st["prefill_slot_tokens"],
            st["prefill_tokens"], st["decode_steps"]) == (2, 512, 203, 3)
