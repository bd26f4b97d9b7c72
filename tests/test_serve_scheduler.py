"""The scheduler layer: oversubscription, backpressure, planner-priced
preemption/promotion, streaming, and the asyncio front end.

The load-bearing invariant throughout: greedy tokens are **bit-identical
under any scheduling history** — admission order, queueing, preemption to
an off-cache tier and promotion back never change a single token.  That
is what makes oversubscription a first-class serving regime instead of a
correctness hazard.
"""

import asyncio

import jax
import numpy as np
import pytest

from repro.models import get_smoke_bundle
from repro.serve import (
    QueueFullError,
    Request,
    SamplingParams,
    Scheduler,
    ServeConfig,
    Server,
)


@pytest.fixture(scope="module")
def bundle():
    return get_smoke_bundle("olmo-1b")


@pytest.fixture(scope="module")
def params(bundle):
    return bundle.init_params(jax.random.PRNGKey(0), "float32")


def _req(i, *, n=6, extra=0, sampling=None):
    return Request(
        rid=i, prompt=np.arange(1, 6 + extra, dtype=np.int32),
        max_new_tokens=n,
        **({"sampling": sampling} if sampling else {}),
    )


def _solo_tokens(bundle, params, req_proto):
    """Reference: the same request served alone on a fresh server."""
    srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
    req = Request(rid=0, prompt=req_proto.prompt,
                  max_new_tokens=req_proto.max_new_tokens,
                  sampling=req_proto.sampling)
    srv.add_request(req)
    srv.run_until_done(200)
    return req.out_tokens


class TestOversubscription:
    def test_excess_requests_queue_and_drain(self, bundle, params):
        """More requests than slots: the overflow waits in the queue (no
        error) and every request completes in admission order."""
        srv = Server(bundle, ServeConfig(batch_slots=2, max_len=32), params)
        reqs = [_req(i, extra=i) for i in range(6)]
        srv.add_requests(reqs)
        assert srv.queue_depth == 6     # nothing admitted before a step
        srv.run_until_done(500)
        assert all(r.done and len(r.out_tokens) == 6 for r in reqs)
        assert not srv.has_work()
        assert srv.stats()["peak_queue"] == 6

    def test_bounded_queue_backpressure(self, bundle, params):
        """cfg.max_queue bounds *waiting* requests: the add that would
        exceed it raises QueueFullError, and draining reopens intake."""
        srv = Server(
            bundle,
            ServeConfig(batch_slots=1, max_len=32, max_queue=2),
            params,
        )
        srv.add_request(_req(0))
        srv.add_request(_req(1))
        with pytest.raises(QueueFullError, match="wait queue is full"):
            srv.add_request(_req(2))
        # the rejected request left no trace
        assert 2 not in srv.live_rids
        srv.run_until_done(200)
        srv.add_request(_req(2))        # intake reopened
        srv.run_until_done(200)
        assert not srv.has_work()

    def test_queued_tokens_match_solo_runs(self, bundle, params):
        """Queueing through a 1-slot server never changes greedy
        tokens."""
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        reqs = [_req(i, extra=i) for i in range(3)]
        srv.add_requests(reqs)
        srv.run_until_done(300)
        for r in reqs:
            assert r.out_tokens == _solo_tokens(bundle, params, r)


class TestPreemption:
    def test_preempt_promote_keeps_greedy_tokens(self, bundle, params):
        """The acceptance criterion: a preemption-heavy oversubscribed
        run produces exactly the solo-run tokens for every greedy
        request, with >= 1 spill and >= 1 promotion actually exercised."""
        srv = Server(
            bundle,
            ServeConfig(batch_slots=2, max_len=32, preempt=True,
                        preempt_wait=2),
            params,
        )
        reqs = [_req(i, n=8 + 4 * i, extra=i) for i in range(4)]
        srv.add_requests(reqs)
        srv.run_until_done(500)
        stats = srv.stats()
        assert stats["preemptions"] >= 1, stats
        assert stats["promotions"] >= 1, stats
        assert stats["preemptions"] == stats["promotions"]  # all came back
        assert stats["spill_s"] > 0 and stats["restore_s"] > 0
        for r in reqs:
            assert r.done
            assert r.out_tokens == _solo_tokens(bundle, params, r), r.rid
        preempted = [r for r in reqs if r.preemptions]
        assert preempted, "no request recorded a preemption"

    def test_sampled_requests_survive_preemption(self, bundle, params):
        """Seeded sampling is (seed, position)-deterministic, so spills
        and promotions cannot move a sampled request's tokens either."""
        mk = lambda i: Request(
            rid=i, prompt=np.arange(1, 6 + i, dtype=np.int32),
            max_new_tokens=8 + 4 * i,
            sampling=SamplingParams(temperature=0.8, top_k=12, seed=i),
        )
        srv = Server(
            bundle,
            ServeConfig(batch_slots=2, max_len=32, preempt=True,
                        preempt_wait=2),
            params,
        )
        reqs = [mk(i) for i in range(4)]
        srv.add_requests(reqs)
        srv.run_until_done(500)
        assert srv.stats()["preemptions"] >= 1
        for i, r in enumerate(reqs):
            assert r.out_tokens == _solo_tokens(bundle, params, mk(i)), i

    def test_no_preemption_when_disabled(self, bundle, params):
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        srv.add_requests([_req(i, n=10) for i in range(3)])
        srv.run_until_done(300)
        assert srv.stats()["preemptions"] == 0

    def test_thrash_guard_respects_preempt_wait(self, bundle, params):
        """A slot (re)occupied within preempt_wait ticks is not a
        victim: with a long window and short requests, natural drain
        wins and nothing spills."""
        srv = Server(
            bundle,
            ServeConfig(batch_slots=1, max_len=32, preempt=True,
                        preempt_wait=64),
            params,
        )
        srv.add_requests([_req(i, n=4) for i in range(3)])
        srv.run_until_done(300)
        assert srv.stats()["preemptions"] == 0

    def test_runtime_prices_the_spill(self, bundle, params):
        """The pricing hook surface: a placement plus a positive
        round-trip time, consistent with the datapath copy bounds."""
        srv = Server(bundle, ServeConfig(batch_slots=2, max_len=32), params)
        nbytes = srv.engine.slot_bytes()
        assert nbytes > 0
        place, price = srv.rt.preemption_price(nbytes)
        assert price >= 0.0
        assert place.tier is not None
        step_s = srv.rt.decode_step_seconds(2, 32)
        assert step_s > 0.0


class TestStreaming:
    def test_on_token_streams_in_decode_order(self, bundle, params):
        got = []
        req = Request(
            rid=0, prompt=np.arange(1, 7, dtype=np.int32),
            max_new_tokens=5,
            on_token=lambda r, t: got.append((t, r.done)),
        )
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        srv.add_request(req)
        srv.run_until_done(100)
        assert [t for t, _ in got] == req.out_tokens
        # done flag visible exactly on the final token's callback
        assert [d for _, d in got] == [False] * 4 + [True]

    def test_latency_stamps_monotonic(self, bundle, params):
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        reqs = [_req(i) for i in range(2)]
        srv.add_requests(reqs)
        srv.run_until_done(200)
        for r in reqs:
            assert r.submitted_s <= r.first_token_s <= r.finished_s


class _NoChunkBundle:
    """Proxy bundle whose ``prefill_at`` is genuinely unimplemented —
    the only kind of bundle left on the decode-replay fallback now that
    encoder-decoder bundles chunk-prefill."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def prefill_at(self, *args, **kwargs):
        raise NotImplementedError


class TestReplayFallback:
    def test_encdec_bundle_chunk_prefills(self, caplog):
        """Encoder-decoder bundles chunk-prefill like everything else
        (their cross KV is read-only during generation) — no replay
        fallback, no warning."""
        enc = get_smoke_bundle("seamless-m4t-medium")
        eparams = enc.init_params(jax.random.PRNGKey(0), "float32")
        srv = Server(enc, ServeConfig(batch_slots=2, max_len=32), eparams)
        assert srv.engine.supports_chunked_prefill
        with caplog.at_level("WARNING", logger="repro.serve.engine"):
            reqs = [_req(i, n=3, extra=i) for i in range(3)]
            srv.add_requests(reqs)
            srv.run_until_done(300)
        assert all(r.done for r in reqs)
        assert srv.stats()["decode_replay_prefills"] == 0
        assert not [r for r in caplog.records
                    if "decode-step replay" in r.getMessage()]

    def test_unchunkable_admission_warns_once_and_counts(self, bundle,
                                                         params, caplog):
        """The O(B*L) decode-replay prefill fallback (bundles without
        ``prefill_at``) is visible: one warning ever, a counter per
        admission."""
        srv = Server(_NoChunkBundle(bundle),
                     ServeConfig(batch_slots=2, max_len=32), params)
        assert not srv.engine.supports_chunked_prefill
        with caplog.at_level("WARNING", logger="repro.serve.engine"):
            reqs = [_req(i, n=3, extra=i) for i in range(3)]
            srv.add_requests(reqs)
            srv.run_until_done(300)
        assert all(r.done for r in reqs)
        assert srv.stats()["decode_replay_prefills"] == 3
        warns = [r for r in caplog.records
                 if "decode-step replay" in r.getMessage()]
        assert len(warns) == 1, "replay warning must fire exactly once"

    def test_chunked_bundle_never_counts_replay(self, bundle, params):
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        assert srv.engine.supports_chunked_prefill
        srv.add_request(_req(0))
        srv.run_until_done(100)
        assert srv.stats()["decode_replay_prefills"] == 0


class TestStatsSurface:
    def test_stats_is_a_method_with_all_layers(self, bundle, params):
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        srv.add_request(_req(0))
        srv.run_until_done(100)
        stats = srv.stats()
        for key in ("prefill_tokens", "decode_tokens", "replans",
                    "migrations", "decode_replay_prefills", "preemptions",
                    "promotions", "peak_queue", "queued", "spilled",
                    "spill_s", "restore_s"):
            assert key in stats, key
        assert stats["decode_tokens"] == 6
        tp = srv.throughput()
        assert tp["decode_tps"] > 0


class TestCalibrationObservations:
    """The Executor's decode-step timings as calibration observations:
    the EWMA lives on the Runtime (keyed by batch/len/policy), the first
    step after every executor (re)build is warm-up and never observed,
    and pricing falls back to the analytic prediction until a real
    measurement lands."""

    def test_analytic_fallback_before_any_observation(self, bundle, params):
        srv = Server(bundle, ServeConfig(batch_slots=2, max_len=32), params)
        assert srv.rt.measured_step_s(2, 32) is None
        assert srv.engine.measured_step_s is None
        step_s = srv.rt.decode_step_seconds(2, 32)
        assert step_s > 0.0
        assert step_s == srv.rt._analytic_step_seconds(2, 32)

    def test_first_step_after_build_is_warmup(self, bundle, params):
        """The compile-laden first decode step never pollutes the EWMA:
        no observation lands until the executor's second step."""
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        srv.add_request(_req(0, n=6))
        while srv.has_work() and srv.engine._steps_since_build < 1:
            srv.step()
        assert srv.engine._steps_since_build == 1
        assert srv.rt.measured_step_s(1, 32) is None
        while srv.has_work() and srv.engine._steps_since_build < 2:
            srv.step()
        assert srv.rt.measured_step_s(1, 32) is not None
        assert srv.engine.measured_step_s == srv.rt.measured_step_s(1, 32)

    def test_ewma_converges_and_prices_preemption(self, bundle, params):
        """Feeding a constant measured step time converges the EWMA to
        it, and decode_step_seconds — the scheduler's preemption-ledger
        wait price — returns the measured value, not the analytic one."""
        srv = Server(bundle, ServeConfig(batch_slots=2, max_len=32), params)
        analytic = srv.rt.decode_step_seconds(2, 32)
        first = srv.rt.observe_decode_step(2, 32, 0.025)
        assert first == pytest.approx(0.025)    # first observation seeds
        for _ in range(60):
            srv.rt.observe_decode_step(2, 32, 0.025)
        assert srv.rt.decode_step_seconds(2, 32) == pytest.approx(
            0.025, rel=1e-6)
        assert srv.rt.decode_step_seconds(2, 32) != analytic
        # observations feed the EWMA alone: no dispatch ran, so the
        # executor counted no decode step
        assert srv.stats()["decode_steps"] == 0
        # other shapes still fall back to the analytic prediction
        assert srv.rt.measured_step_s(1, 16) is None

    def test_nonpositive_observation_is_ignored(self, bundle, params):
        srv = Server(bundle, ServeConfig(batch_slots=2, max_len=32), params)
        srv.rt.observe_decode_step(2, 32, 0.0)
        srv.rt.observe_decode_step(2, 32, -1.0)
        assert srv.rt.measured_step_s(2, 32) is None

    def test_serve_run_feeds_the_runtime(self, bundle, params):
        """End to end: a real serve run leaves a measured EWMA on the
        runtime, one compiled decode dispatch per token."""
        srv = Server(bundle, ServeConfig(batch_slots=1, max_len=32), params)
        srv.add_request(_req(0, n=8))
        srv.run_until_done(200)
        measured = srv.rt.measured_step_s(1, 32)
        assert measured is not None and measured > 0
        assert srv.stats()["decode_steps"] == 8

    def test_tokens_bit_identical_under_calibration(self, bundle, params):
        """The acceptance criterion: activating a measurement-calibrated
        system re-prices scheduling but cannot move a single greedy
        token, even through a preemption-heavy oversubscribed run."""
        from repro.core.hardware import get_active_system, set_active_system

        cfg = lambda: ServeConfig(batch_slots=2, max_len=32, preempt=True,
                                  preempt_wait=2)
        reqs = lambda: [_req(i, n=8 + 4 * i, extra=i) for i in range(4)]

        baseline = Server(bundle, cfg(), params)
        base_reqs = reqs()
        baseline.add_requests(base_reqs)
        baseline.run_until_done(500)

        spec = get_active_system()
        calibrated = spec.with_measurements(
            hbm_bandwidth=8e9, ici_link_bandwidth=1e9, pcie_bandwidth=2e9)
        prev = set_active_system(calibrated)
        try:
            srv = Server(bundle, cfg(), params)
            assert srv.rt.system is calibrated   # runtime adopted it
            assert srv.rt.system.provenance_of("hbm_bandwidth") == "measured"
            cal_reqs = reqs()
            srv.add_requests(cal_reqs)
            srv.run_until_done(500)
        finally:
            set_active_system(prev)
        for b, c in zip(base_reqs, cal_reqs):
            assert b.done and c.done
            assert c.out_tokens == b.out_tokens, c.rid


class TestAsyncScheduler:
    def test_submit_stream_drain(self, bundle, params):
        """The asyncio front end: concurrent clients submit (absorbing
        backpressure), stream their tokens, and the driver drains —
        tokens identical to the sync path."""
        server = Server(
            bundle,
            ServeConfig(batch_slots=2, max_len=32, max_queue=2),
            params,
        )
        sched = Scheduler(server)
        prompts = [np.arange(1, 6 + i, dtype=np.int32) for i in range(5)]

        async def client(i):
            req = await sched.submit(prompts[i], max_new_tokens=4)
            return [tok async for tok in sched.stream(req)]

        async def main():
            async def clients():
                outs = await asyncio.gather(
                    *(client(i) for i in range(5)))
                sched.close()
                return outs
            _, outs = await asyncio.gather(sched.run(), clients())
            return outs

        outs = asyncio.run(main())
        assert all(len(o) == 4 for o in outs)
        assert not server.has_work()
        # async scheduling is still just the sync engine underneath
        for prompt, out in zip(prompts, outs):
            proto = Request(rid=0, prompt=prompt, max_new_tokens=4)
            assert out == _solo_tokens(bundle, params, proto)

    def test_backpressure_never_raises_through_submit(self, bundle, params):
        """max_queue=1 with many clients: submissions wait rather than
        surface QueueFullError."""
        server = Server(
            bundle,
            ServeConfig(batch_slots=1, max_len=32, max_queue=1),
            params,
        )
        sched = Scheduler(server)

        async def main():
            async def client(i):
                req = await sched.submit(
                    np.arange(1, 5, dtype=np.int32), max_new_tokens=2)
                async for _ in sched.stream(req):
                    pass
                return req

            async def clients():
                reqs = await asyncio.gather(*(client(i) for i in range(4)))
                sched.close()
                return reqs
            _, reqs = await asyncio.gather(sched.run(), clients())
            return reqs

        reqs = asyncio.run(main())
        assert all(r.done for r in reqs)
        assert server.stats()["peak_queue"] <= 1
