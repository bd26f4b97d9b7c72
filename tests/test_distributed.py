"""Multi-device distribution tests.

These need >1 XLA host device, and the device count must NOT be forced
globally (smoke tests/benches see 1 device) — so each test runs a small
script in a subprocess with ``--xla_force_host_platform_device_count=8``.
The scripts assert internally; the test checks the exit code.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n: int = 8, timeout: int = 600):
    script = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"\n'
        + textwrap.dedent(body)
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


class TestPallasPerShard:
    """On a multi-device mesh the Pallas kernels run per device under
    ``shard_map`` (XLA cannot partition a Mosaic kernel), split over the
    batch axes and over the head axes: the rule table's ``model`` axis and
    the donor axis a peer-tier KV cache is sharded over.  The compiled
    kernel calls move no cache bytes between devices."""

    @pytest.mark.parametrize("shape,axes,kv_spec,donor", [
        ((2,), ("data",), "P('data')", ()),
        ((4, 2), ("donor", "data"), "P('data', 'donor')", ("donor",)),
        ((2, 4), ("data", "model"), "P('data', 'model')", ()),
        ((4, 1), ("donor", "data"), "P('data', 'donor')", ("donor",)),
    ], ids=["pool-mesh", "donor-mesh", "tp-mesh", "kv-peer-mesh"])
    def test_kernels_match_oracle_under_mesh(self, shape, axes, kv_spec,
                                             donor):
        run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import ops
        from repro.launch.mesh import make_mesh_for
        from repro.models.sharding import use_sharding
        mesh = make_mesh_for({shape!r}, {axes!r})
        kv = NamedSharding(mesh, {kv_spec})
        rep = NamedSharding(mesh, P())
        ks = jax.random.split(jax.random.PRNGKey(0), 8)
        # GQA: 8 query heads over 4 KV heads
        q = jax.random.normal(ks[0], (4, 8, 32))
        kc = jax.device_put(jax.random.normal(ks[1], (4, 4, 256, 32)), kv)
        vc = jax.device_put(jax.random.normal(ks[2], (4, 4, 256, 32)), kv)
        lens = jnp.asarray([1, 100, 256, 37], jnp.int32)
        qc = jax.random.normal(ks[3], (4, 8, 8, 32))
        qpos = 40 + jnp.arange(8)[None].repeat(4, 0)
        kpos = jnp.where(jnp.arange(256) < 40, jnp.arange(256), -1)
        kpos = kpos[None].repeat(4, 0)
        x = jax.random.normal(ks[4], (4, 64, 4, 16))
        dt = jax.nn.softplus(jax.random.normal(ks[5], (4, 64, 4)))
        a = -jnp.exp(jax.random.normal(ks[6], (4,)) * 0.5)
        bm = jax.random.normal(ks[7], (4, 64, 16))
        cm = jax.random.normal(ks[0], (4, 64, 16))
        # the decode step's layer-stacked caches: layer 1 holds kc / vc
        kv5 = NamedSharding(mesh, P(None, *{kv_spec}))
        ks5 = jax.device_put(jnp.stack([vc, kc]), kv5)
        vs5 = jax.device_put(jnp.stack([kc, vc]), kv5)
        layer = jnp.int32(1)
        krow = jax.random.normal(ks[5], (4, 4, 32))
        vrow = jax.random.normal(ks[6], (4, 4, 32))
        slots = jnp.asarray([0, 99, 255, 36], jnp.int32)

        def dec(backend):
            return jax.jit(lambda q, k, v, l: ops.decode_attention(
                q, k, v, l, layer, backend=backend))

        def wr(backend):
            return jax.jit(lambda k, v, kr, vr, s: ops.kv_row_write(
                k, v, kr, vr, s, layer, backend=backend))

        def pre(backend):
            return jax.jit(lambda q, k, v, a, b: ops.prefill_attention(
                q, k, v, a, b, backend=backend))

        def ssd(backend):
            return jax.jit(lambda *xs: ops.ssd_scan(
                *xs, chunk=32, backend=backend))

        out = {{}}
        for backend in ("pallas", "ref"):
            with use_sharding(mesh, kv_donor_axes={donor!r}):
                out[backend] = (
                    dec(backend)(q, ks5, vs5, lens),
                    pre(backend)(qc, kc, vc, qpos, kpos),
                    ssd(backend)(x, dt, a, bm, cm),
                    wr(backend)(ks5, vs5, krow, vrow, slots),
                )
                if backend == "pallas":
                    hlo = [
                        dec(backend).lower(
                            jax.device_put(q, rep), ks5, vs5,
                            jax.device_put(lens, rep),
                        ).compile().as_text(),
                        wr(backend).lower(
                            ks5, vs5, jax.device_put(krow, rep),
                            jax.device_put(vrow, rep),
                            jax.device_put(slots, rep),
                        ).compile().as_text(),
                        pre(backend).lower(
                            jax.device_put(qc, rep), kc, vc,
                            jax.device_put(qpos, rep),
                            jax.device_put(kpos, rep),
                        ).compile().as_text(),
                    ]
        for got, want in zip(out["pallas"], out["ref"]):
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
        for text in hlo:
            for op in ("all-gather", "all-to-all", "collective-permute"):
                assert op not in text, op
        print("OK")
        """)


    def test_kv_peer_server_splits_the_cache_where_it_lives(self):
        run_with_devices("""
        import re, jax, numpy as np
        from repro.kernels import ops
        from repro.launch.mesh import make_donor_mesh
        from repro.models import get_smoke_bundle
        from repro.serve import Request, ServeConfig, Server

        # kv_peer_hbm's layout: one compute slice, the KV heads sharded
        # over 4 donor slices
        mesh = make_donor_mesh((1,), ("data",), 4)
        b = get_smoke_bundle("olmo-1b")
        params = b.init_params(jax.random.PRNGKey(0), "float32")

        def serve():
            srv = Server(b, ServeConfig(batch_slots=4, max_len=32,
                                        policy="kv_peer_hbm"),
                         params, mesh=mesh)
            reqs = [Request(rid=i, max_new_tokens=4, prompt=np.arange(
                        1 + i, 7 + 2 * i, dtype=np.int32)) for i in range(4)]
            for r in reqs:
                srv.add_request(r)
            srv.run_until_done(200)
            return srv, [list(r.out_tokens) for r in reqs]

        _, want = serve()                    # jnp oracles, XLA-partitioned
        ops._resolve = lambda backend: "pallas"  # per-device kernels
        srv, got = serve()
        assert got == want, (got, want)
        # a per-layer cache slice holds 4*4*32*16 elements: no collective
        # in the compiled steps gathers one
        layer = 4 * 4 * 32 * 16
        for step in ("decode", "prefill"):
            hlo = srv.engine.hlo_text(step)
            for m in re.finditer(r"=(.*?) all-gather(?:-start)?\\(", hlo):
                for dims in re.findall(r"\\w+\\[([\\d,]*)\\]", m.group(1)):
                    n = int(np.prod([int(d) for d in dims.split(",") if d]))
                    assert n < layer, (step, m.group(0))
        print("OK")
        """)


class TestQuantizedAllReduce:
    def test_matches_mean_within_quantization(self):
        run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import quantized_all_reduce
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for((8,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        f = shard_map(lambda v: quantized_all_reduce(v[0], "pod")[None],
                      mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                      check_rep=False)
        out = f(x)
        want = jnp.mean(x, axis=0)
        for row in np.asarray(out):
            np.testing.assert_allclose(row, np.asarray(want), atol=3e-2)
        print("OK")
        """)

    def test_error_feedback_reduces_bias(self):
        run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.optim.compression import quantize, dequantize
        # error feedback: accumulated quantization error is re-injected; the
        # RUNNING SUM of compressed values tracks the running sum of true
        # values much better than independent quantization.
        rng = np.random.default_rng(0)
        g = rng.normal(size=(100, 64)).astype(np.float32) * 0.01
        g[:, 0] += 5.0  # large coordinate dominates the scale
        ef = np.zeros(64, np.float32)
        sum_q_ef, sum_q_naive, sum_true = 0.0, 0.0, 0.0
        for t in range(100):
            q, s = quantize(jnp.asarray(g[t] + ef))
            deq = np.asarray(dequantize(q, s))
            ef = g[t] + ef - deq
            sum_q_ef += deq
            qn, sn = quantize(jnp.asarray(g[t]))
            sum_q_naive += np.asarray(dequantize(qn, sn))
            sum_true += g[t]
        err_ef = np.abs(sum_q_ef - sum_true).max()
        err_naive = np.abs(sum_q_naive - sum_true).max()
        assert err_ef <= err_naive + 1e-6, (err_ef, err_naive)
        assert err_ef < 0.1
        print("OK", err_ef, err_naive)
        """)


class TestPipelineParallel:
    def test_matches_sequential(self):
        run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.train.pipeline_parallel import pipelined_forward
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for((4,), ("pod",))
        n_stages, n_micro, B, D = 4, 8, 2, 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, D, D)) * 0.3
        xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, B, D))
        def stage_fn(w, x):
            return jnp.tanh(x @ w)
        # sequential reference
        def seq(x):
            for i in range(n_stages):
                x = stage_fn(ws[i], x)
            return x
        want = jax.vmap(seq)(xs)
        got = pipelined_forward(mesh, stage_fn, ws, xs, axis_name="pod")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        print("OK")
        """)

    def test_differentiable(self):
        run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.train.pipeline_parallel import pipelined_forward
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for((2,), ("pod",))
        ws = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8)) * 0.3
        xs = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 8))
        def stage_fn(w, x):
            return jnp.tanh(x @ w)
        def loss_pipe(ws):
            return jnp.sum(pipelined_forward(mesh, stage_fn, ws, xs, "pod") ** 2)
        def loss_seq(ws):
            def seq(x):
                for i in range(2):
                    x = jnp.tanh(x @ ws[i])
                return x
            return jnp.sum(jax.vmap(seq)(xs) ** 2)
        g1 = jax.grad(loss_pipe)(ws)
        g2 = jax.grad(loss_seq)(ws)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)
        print("OK")
        """)


class TestParallelConsistency:
    def test_sharded_train_matches_single_device(self):
        """The same train step on a (2,2,2) mesh and on a 1-device mesh
        produces the same loss trajectory — the distribution layer is
        numerically transparent."""
        run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import get_smoke_bundle
        from repro.train import TrainConfig, init_train_state, make_train_step
        from repro.optim import AdamWConfig
        from repro.data import DataConfig, SyntheticLM

        def run(mesh_dims, axes):
            from repro.launch.mesh import make_mesh_for
            mesh = make_mesh_for(mesh_dims, axes)
            b = get_smoke_bundle("granite-8b")
            tcfg = TrainConfig(remat="none",
                optimizer=AdamWConfig(lr=1e-3, warmup_steps=1))
            params, opt, ef = init_train_state(b, mesh, jax.random.PRNGKey(0), tcfg)
            step = jax.jit(make_train_step(b, mesh, tcfg))
            data = SyntheticLM(DataConfig(vocab=b.cfg.vocab, seq_len=32,
                                          global_batch=8))
            losses = []
            for i, batch in zip(range(4), data):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt, ef, m = step(params, opt, ef, batch)
                losses.append(float(m["loss"]))
            return losses
        l_multi = run((2, 2, 2), ("pod", "data", "model"))
        l_single = run((1,), ("data",))
        np.testing.assert_allclose(l_multi, l_single, rtol=2e-3, atol=2e-3)
        print("OK", l_multi, l_single)
        """)

    def test_compressed_pod_grads_still_learns(self):
        run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.models import get_smoke_bundle
        from repro.train import TrainConfig, init_train_state, make_train_step
        from repro.optim import AdamWConfig
        from repro.data import DataConfig, SyntheticLM
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for((2, 2, 2), ("pod", "data", "model"))
        b = get_smoke_bundle("olmo-1b")
        tcfg = TrainConfig(remat="none", compress_pod_grads=True,
            optimizer=AdamWConfig(lr=3e-3, warmup_steps=5, weight_decay=0.0))
        params, opt, ef = init_train_state(b, mesh, jax.random.PRNGKey(0), tcfg)
        step = jax.jit(make_train_step(b, mesh, tcfg))
        data = SyntheticLM(DataConfig(vocab=b.cfg.vocab, seq_len=32,
                                      global_batch=8, structure=1.0))
        losses = []
        for i, batch in zip(range(30), data):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, ef, m = step(params, opt, ef, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.2, (losses[0], losses[-1])
        print("OK", losses[0], losses[-1])
        """, timeout=900)


class TestDonorMeshRealization:
    """Peer/remote placement policies executed on a donor mesh axis: the
    bytes must land sharded across the donor slices (sharding + memory
    kind asserted), survive decode steps, and the planner's pick under a
    donor mesh must be the policy the engine then realizes."""

    def test_kv_peer_hbm_realized_on_donor_slice(self):
        run_with_devices("""
        import jax, numpy as np
        from repro.core.placement import resolve_memory_kind
        from repro.launch.mesh import make_donor_mesh
        from repro.models import get_smoke_bundle
        from repro.serve import Request, ServeConfig, Server

        mesh = make_donor_mesh((2,), ("data",), 2)   # (donor=2, data=2)
        b = get_smoke_bundle("olmo-1b")
        params = b.init_params(jax.random.PRNGKey(0), "float32")
        srv = Server(
            b,
            ServeConfig(batch_slots=4, max_len=32, policy="kv_peer_hbm"),
            params, mesh=mesh,
        )
        donor_devs = set(mesh.devices[1].ravel())  # donor slice 1
        want_kind = resolve_memory_kind("device") or \\
            jax.devices()[0].default_memory().kind
        from repro.models.sharding import spec_axes

        for leaf in jax.tree.leaves(srv.engine.caches):
            assert "donor" in spec_axes(leaf.sharding.spec), leaf.sharding
            assert leaf.sharding.memory_kind == want_kind, leaf.sharding
            devs = {s.device for s in leaf.addressable_shards}
            assert devs & donor_devs, (devs, donor_devs)
        # params stay local under kv_peer_hbm
        for leaf in jax.tree.leaves(srv.params):
            assert "donor" not in spec_axes(leaf.sharding.spec)
        # serving works and the placement survives the decode steps
        req = Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                      max_new_tokens=3)
        srv.add_request(req)
        srv.run_until_done(200)
        assert req.done
        for leaf in jax.tree.leaves(srv.engine.caches):
            assert "donor" in spec_axes(leaf.sharding.spec), leaf.sharding
        print("OK")
        """)

    def test_weights_peer_hbm_and_donor_stream(self):
        run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.placement import DonorStream
        from repro.launch.mesh import make_donor_mesh
        from repro.models import get_smoke_bundle
        from repro.serve import Request, ServeConfig, Server

        mesh = make_donor_mesh((2,), ("data",), 2)
        b = get_smoke_bundle("olmo-1b")
        params = b.init_params(jax.random.PRNGKey(0), "float32")
        srv = Server(
            b,
            ServeConfig(batch_slots=4, max_len=32,
                        policy="weights_peer_hbm"),
            params, mesh=mesh,
        )
        from repro.models.sharding import spec_axes
        donor_devs = set(mesh.devices[1].ravel())
        sharded = 0
        for leaf in jax.tree.leaves(srv.params):
            if "donor" in spec_axes(leaf.sharding.spec):
                sharded += 1
                assert {s.device for s in leaf.addressable_shards} & donor_devs
        assert sharded > 0, "no param leaf landed on the donor axis"
        req = Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                      max_new_tokens=2)
        srv.add_request(req)
        srv.run_until_done(200)
        assert req.done

        # Runtime.realize (the array-level realizer): a def-less stacked
        # tree under a STREAM peer placement lands donor-sharded on its
        # stack dim
        from repro.api import Runtime
        from repro.core.placement import Role
        from repro.models.sharding import spec_axes
        n, m = 6, 128
        stacked = jnp.arange(n * m, dtype=jnp.float32).reshape(n, m)
        placed = Runtime(b, mesh, "weights_peer_hbm").realize(
            {"w": stacked}, Role.PARAMS, specs=P()
        )
        assert spec_axes(placed["w"].sharding.spec) == {"donor"}
        assert {s.device for s in placed["w"].addressable_shards} & donor_devs

        # DonorStream: windows arrive locally, match the source, and the
        # staging buffer never holds more than the double buffer
        stack = jax.device_put(
            jnp.arange(n * m, dtype=jnp.float32).reshape(n, m),
            NamedSharding(mesh, P("donor")),
        )
        stream = DonorStream(stack, mesh, P(), n)
        for i in range(n):
            w = stream.window(i)
            np.testing.assert_array_equal(
                np.asarray(w), np.asarray(stack[i]))
            assert "donor" not in spec_axes(w.sharding.spec)  # staged locally
            assert len(stream._buf) <= 2           # double-buffered
        print("OK")
        """)

    def test_planner_pick_under_donor_mesh_is_realized(self):
        run_with_devices("""
        import jax, numpy as np
        from repro.core.placement import donor_allow_flags
        from repro.core.planner import plan
        from repro.launch.mesh import make_donor_mesh
        from repro.models import get_smoke_bundle
        from repro.serve import Request, ServeConfig, Server

        mesh = make_donor_mesh((2,), ("data",), 2)
        # an oversized-KV decode profile: only a peer tier both fits and
        # is realizable (host tiers don't exist on the CPU backend)
        from repro.core.planner import decode_profile, pool_capacities
        caps = pool_capacities()
        prof = decode_profile(
            name="big", param_bytes=2e9,
            kv_bytes=caps["hbm"], step_flops=1e12)
        flags = donor_allow_flags(mesh)
        flags["allow_host"] = False
        best, _ = plan(prof, **flags)
        assert best.policy in ("kv_peer_hbm", "weights_peer_hbm"), best
        # the engine realizes exactly that policy on the donor slice
        b = get_smoke_bundle("olmo-1b")
        params = b.init_params(jax.random.PRNGKey(0), "float32")
        srv = Server(
            b, ServeConfig(batch_slots=4, max_len=32, policy=best.policy),
            params, mesh=mesh)
        from repro.models.sharding import spec_axes
        donor_devs = set(mesh.devices[1].ravel())
        role_tree = (srv.engine.caches if best.policy == "kv_peer_hbm"
                     else srv.params)
        hit = 0
        for leaf in jax.tree.leaves(role_tree):
            if "donor" in spec_axes(leaf.sharding.spec):
                hit += 1
                assert {s.device for s in leaf.addressable_shards} & donor_devs
        assert hit > 0
        print("OK")
        """)


class TestPlacementPolicies:
    def test_opt_host_offload_runs_and_matches(self):
        run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import get_smoke_bundle
        from repro.core.placement import (
            OPT_HOST, HBM_RESIDENT, default_memory_kind, resolve_memory_kind)
        from repro.train import TrainConfig, init_train_state, make_train_step
        from repro.optim import AdamWConfig
        from repro.data import DataConfig, SyntheticLM
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for((2, 2), ("data", "model"))
        b = get_smoke_bundle("yi-6b")
        from repro.train.train_step import make_state_specs, repin_opt_state

        def run(policy):
            tcfg = TrainConfig(remat="none",
                optimizer=AdamWConfig(lr=1e-3, warmup_steps=1))
            params, opt, ef = init_train_state(
                b, mesh, jax.random.PRNGKey(0), tcfg, policy)
            _, opt_specs = make_state_specs(b, mesh, policy, tcfg.rules,
                                            tcfg.fsdp_axes)
            # the host kind the backend actually exposes (pinned_host on
            # TPU; the default kind on CPU where host DRAM == device mem)
            host_kind = resolve_memory_kind("pinned_host") or default_memory_kind()
            if policy.name == "opt_host":
                kinds = {x.sharding.memory_kind
                         for x in jax.tree.leaves(opt["master"])}
                assert kinds == {host_kind}, (kinds, host_kind)
            step = jax.jit(make_train_step(b, mesh, tcfg, policy))
            data = SyntheticLM(DataConfig(vocab=b.cfg.vocab, seq_len=16,
                                          global_batch=4))
            out = []
            for i, batch in zip(range(3), data):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt, ef, m = step(params, opt, ef, batch)
                # CPU backend: host re-pin happens outside jit
                opt = repin_opt_state(opt, opt_specs)
                out.append(float(m["loss"]))
            if policy.name == "opt_host":
                kinds = {x.sharding.memory_kind
                         for x in jax.tree.leaves(opt["master"])}
                assert kinds == {host_kind}, (kinds, host_kind)
            return out
        np.testing.assert_allclose(run(HBM_RESIDENT), run(OPT_HOST),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
        """)
