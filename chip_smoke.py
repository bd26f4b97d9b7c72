#!/usr/bin/env python3
"""Bring-up check: olmo-1b at full published width, served on a TPU.

One chip (the default) drives the path ``python -m repro.launch.serve``
takes — ``ModelBundle``, ``Server``, ``ServeConfig`` and the placement
planner (``--policy auto``) — at olmo-1b's full width and depth (16
layers, d_model 2048, 16 heads x 128, d_ff 8192, vocab 50304) with
random weights from ``--seed``:

1. serve 8 seeded requests (prompts of 200-399 tokens, 16-32 new tokens,
   greedy) in 8 slots with a 2048-position cache;
2. check that the compiled decode and prefill steps run the Mosaic
   (Pallas) kernels, and that each kernel matches its jnp oracle;
3. check the logits the server's own compiled steps produced while
   serving — chunked prefill and cached decode, bfloat16, Pallas kernels,
   all 8 slots live — for two of the requests against a float32 full
   forward of the same tokens (``repro.models.reference``).

``--four-chips`` runs only the cross-chip serving paths — the
disaggregated 2+2 ``Cluster`` and ``kv_peer_hbm`` on a 4-slice ICI donor
mesh — against the one-chip server on the same requests.

Earlier lines report each phase; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  Any failure, or a platform other
than TPU, exits non-zero without it.  Nothing here is a performance
number: times are printed to show where a bring-up spends its minutes.
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import decode_attention, flash_attention, kv_write, ref, ssd_scan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_donor_mesh  # noqa: E402
from repro.models.model_zoo import ModelBundle  # noqa: E402
from repro.models.reference import forward_logits  # noqa: E402
from repro.serve import Cluster, DisaggConfig, Request, ServeConfig, Server  # noqa: E402

ARCH = "olmo-1b"
SLOTS = 8
MAX_LEN = 2048
N_REQUESTS = 8
CHECKED_ROWS = (0, 1)     # requests whose logits are compared

#: Logits tolerance, as a fraction of the reference logits' RMS, on the
#: RMS of the error (``rel_rms``) and on its largest entry (``rel_max``).
#: The served path keeps weights, activations and the KV cache in
#: bfloat16 (relative rounding 2**-9) and accumulates in float32; at
#: olmo-1b's width on a CPU host, with 2 and with 4 of its 16 layers, it
#: measured rel_rms ~0.005 and rel_max ~0.03 against this reference,
#: nearly independent of depth.  The bounds sit at 3x that.  Attention
#: whose q/k/v are rounded to float8 (relative rounding 2**-4) measured
#: rel_rms ~0.033 and rel_max ~0.17-0.21 there: twice the bounds.  The
#: check computes that variant too and fails unless the bounds reject it.
TOL_REL_RMS = 0.015
TOL_REL_MAX = 0.08


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {d.platform!r}")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    return info


def make_requests(cfg, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=rid,
            prompt=rng.integers(
                0, cfg.vocab, size=int(rng.integers(200, 400))
            ).astype(np.int32),
            max_new_tokens=int(rng.integers(16, 33)),
        )
        for rid in range(N_REQUESTS)
    ]


def serve(server, requests) -> dict[int, list[int]]:
    """Serve fresh copies of ``requests``; rid -> generated tokens."""
    reqs = [
        Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        for r in requests
    ]
    for r in reqs:
        server.add_request(r)
    server.run_until_done()
    return {r.rid: list(r.out_tokens) for r in reqs}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def tap_logits(server, rids) -> dict:
    """Record, for the requests ``rids``, the logits of every compiled
    prefill and decode dispatch the server makes: rid -> (positions,
    logits, slots live at that dispatch).  A prefill chunk's logits are
    those of its last token; a decode step's those of the token fed."""
    got = {rid: ([], [], []) for rid in rids}
    table = server.table

    def tap(step, logits, new_lens):
        live = len(table.active_slots())
        for i, rid in enumerate(table.slots):
            if rid not in got:
                continue
            if step == "prefill":
                if not new_lens[i]:
                    continue
                pos = int(table.lengths[i] + new_lens[i] - 1)
            elif table.active[i]:
                pos = int(table.lengths[i])
            else:
                continue
            got[rid][0].append(pos)
            got[rid][1].append(np.asarray(logits[i], np.float32))
            got[rid][2].append(live)

    server.engine.logits_tap = tap
    return got


def phase_serve(bundle, params, requests):
    t0 = time.perf_counter()
    server = Server(
        bundle,
        ServeConfig(batch_slots=SLOTS, max_len=MAX_LEN, policy=None),
        params,
    )
    compile_s = time.perf_counter() - t0
    log(f"planner policy: {server.policy.name}")
    log(f"server build (decode + prefill compile): {compile_s:.1f} s")
    served_logits = tap_logits(server, CHECKED_ROWS)
    t0 = time.perf_counter()
    tokens = serve(server, requests)
    served = sum(len(t) for t in tokens.values())
    want = sum(r.max_new_tokens for r in requests)
    log(f"served: {len(tokens)} requests, {served} tokens in "
        f"{time.perf_counter() - t0:.1f} s (first-call compiles included)")
    if served != want:
        raise AssertionError(f"served {served} tokens, requested {want}")
    for step in ("decode", "prefill"):
        if "tpu_custom_call" not in server.engine.hlo_text(step):
            raise AssertionError(
                f"compiled {step} step holds no Mosaic kernel "
                "(tpu_custom_call)"
            )
    log("compiled decode and prefill steps hold tpu_custom_call")
    copies = server.stats()["decode_cache_copies"]
    log(f"compiled decode step: {copies} cache-sized copies")
    if copies:
        raise AssertionError("the decode step copies the KV cache")
    return server, tokens, served_logits


def phase_kernels(seed: int) -> None:
    """Each compiled Pallas kernel, at the default matmul precision the
    server compiles at, against its jnp oracle at the highest precision:
    at olmo-1b widths (ssd_scan at mamba2-780m's), plus cache lengths
    that leave flash_decode (600, 1500) and kv_row_write (1500) a partial
    last tile and a prompt length that leaves flash_attention a padded
    last block."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16

    def rnd(i, shape, dtype=bf):
        return jax.random.normal(ks[i], shape, jnp.float32).astype(dtype)

    def agree(name, got, oracle, atol):
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32)
        )))
        log(f"kernel {name}: max |pallas - oracle| = {err:.3g} "
            f"(tolerance {atol})")
        if not err <= atol:
            raise AssertionError(f"kernel {name} disagrees with its oracle")

    # bf16 outputs: the kernel and the oracle may round an output to
    # neighbouring bf16 values, one ulp apart: 2**-6 below magnitude 4
    # the decode kernels read and write layer 1 of a 2-layer stacked cache
    layer = jnp.int32(1)
    for smax in (2048, 600, 1500):
        q = rnd(0, (8, 16, 128))
        k, v = rnd(1, (2, 8, 16, smax, 128)), rnd(2, (2, 8, 16, smax, 128))
        lens = jnp.asarray(
            np.random.default_rng(seed).integers(1, smax + 1, 8), jnp.int32
        )
        agree(f"flash_decode[cache {smax}]",
              jax.jit(decode_attention.flash_decode)(q, k, v, lens, layer),
              lambda: ref.decode_attention(q, k[1], v[1], lens), 2e-2)
        rows = rnd(3, (2, 8, 16, 128))
        slot = lens % smax
        got = jax.jit(kv_write.kv_row_write)(k, v, rows[0], rows[1], slot, layer)
        bidx = jnp.arange(8)
        agree(f"kv_row_write[cache {smax}]",
              jnp.stack(got),
              lambda: jnp.stack([k.at[1, bidx, :, slot].set(rows[0]),
                                 v.at[1, bidx, :, slot].set(rows[1])]), 0.0)
    q = rnd(3, (8, 16, 32, 128))
    k, v = rnd(4, (8, 16, 2080, 128)), rnd(5, (8, 16, 2080, 128))
    rng = np.random.default_rng(seed)
    off = rng.integers(0, 2048, 8)
    qpos = jnp.asarray(off[:, None] + np.arange(32), jnp.int32)
    kpos = jnp.asarray(np.concatenate([
        np.where(np.arange(2048) < off[:, None], np.arange(2048), -1),
        off[:, None] + np.arange(32),
    ], axis=1), jnp.int32)
    agree("flash_prefill[batch 8]",
          jax.jit(flash_attention.flash_prefill)(q, k, v, qpos, kpos),
          lambda: ref.prefill_attention(q, k, v, qpos, kpos), 2e-2)
    for seq in (2048, 2000):
        q, k, v = (rnd(i, (1, 16, seq, 128)) for i in (5, 6, 7))
        agree(f"flash_attention[causal {seq}]",
              jax.jit(flash_attention.flash_attention)(q, k, v),
              lambda: ref.attention(q, k, v, kind="causal"), 2e-2)
    x = rnd(0, (1, 2048, 48, 64), jnp.float32)
    dt = jax.nn.softplus(rnd(1, (1, 2048, 48), jnp.float32))
    a = -jnp.exp(rnd(2, (48,), jnp.float32) * 0.5)
    bm, cm = rnd(3, (1, 2048, 128), jnp.float32), rnd(4, (1, 2048, 128),
                                                      jnp.float32)
    got = jax.jit(ssd_scan.ssd_scan)(x, dt, a, bm, cm)
    with jax.default_matmul_precision("highest"):
        want = ref.ssd_scan(x, dt, a, bm, cm, chunk=64)
    # float32 in, float32 out; relative to the output's scale
    scale = jnp.max(jnp.abs(want))
    agree("ssd_scan[mamba2-780m widths] (relative)",
          got / scale, lambda: want / scale, 2e-2)


def phase_check(bundle, params, requests, tokens, served):
    cfg = bundle.cfg
    seqs = [
        np.concatenate([requests[i].prompt, np.asarray(tokens[i], np.int32)])
        for i in CHECKED_ROWS
    ]
    T = max(len(s) for s in seqs) - 1
    batch = np.zeros((len(seqs), T), np.int32)   # causal: the pad is unseen
    for j, s in enumerate(seqs):
        batch[j, : len(s) - 1] = s[:-1]
    t0 = time.perf_counter()
    ref_fn = jax.jit(forward_logits, static_argnums=(2,),
                     static_argnames=("attn_dtype",))
    want = np.asarray(ref_fn(params, jnp.asarray(batch), cfg))
    low = np.asarray(ref_fn(params, jnp.asarray(batch), cfg,
                            attn_dtype=jnp.float8_e4m3fn))
    log(f"float32 reference forward (+ float8-attention control): "
        f"{time.perf_counter() - t0:.1f} s")

    def rel(err, scale):
        return (float(np.sqrt(np.mean(err ** 2))) / scale,
                float(np.max(np.abs(err))) / scale)

    failed = False
    for j, i in enumerate(CHECKED_ROWS):
        pos, lg, live = (np.asarray(a) for a in served[i])
        L = len(requests[i].prompt)
        n_pre = int(np.sum(pos < L - 1))   # prefill chunks, then decode
        n_dec = len(tokens[i])
        if len(pos) != n_pre + n_dec or n_pre == 0:
            raise AssertionError(
                f"request {i}: tapped {len(pos)} dispatches, expected "
                f"{n_dec} decode steps after the prefill chunks"
            )
        if live[:n_pre].min() != SLOTS or live[n_pre] != SLOTS:
            raise AssertionError(
                f"request {i}: compared dispatches ran with "
                f"{sorted(set(live.tolist()))} live slots, not {SLOTS}"
            )
        w = want[j, pos]
        scale = float(np.sqrt(np.mean(w ** 2)))
        for name, sl in (("prefill", slice(0, n_pre)),
                         ("decode", slice(n_pre, None))):
            r_rms, r_max = rel(lg[sl] - w[sl], scale)
            ok = r_rms <= TOL_REL_RMS and r_max <= TOL_REL_MAX
            failed |= not ok
            log(f"logits request {i} {name} ({len(pos[sl])} dispatches, "
                f"{live[sl].min()}-{live[sl].max()} slots live): "
                f"rel_rms {r_rms:.4g} (tol {TOL_REL_RMS}), rel_max "
                f"{r_max:.4g} (tol {TOL_REL_MAX}), logit rms {scale:.4g}"
                f" -> {'ok' if ok else 'FAIL'}")
        c_rms, c_max = rel(low[j, pos] - w, scale)
        tight = c_rms > TOL_REL_RMS or c_max > TOL_REL_MAX
        failed |= not tight
        log(f"control request {i} float8 attention: rel_rms {c_rms:.4g}, "
            f"rel_max {c_max:.4g} -> "
            f"{'rejected (tolerance is tight)' if tight else 'ACCEPTED'}")
        # the served greedy tokens: each is the argmax of the served
        # logits, and the reference's argmax up to the tolerance (a top-2
        # margin below it may flip either way)
        if not np.array_equal(lg[n_pre:].argmax(-1), tokens[i]):
            raise AssertionError(
                f"request {i}: served tokens are not the argmax of the "
                "served logits"
            )
        ref_steps = want[j, L - 1: L - 1 + len(tokens[i])]
        gap = ref_steps.max(-1) - ref_steps[np.arange(len(tokens[i])),
                                            tokens[i]]
        worst = float(gap.max() / scale)
        ok = worst <= TOL_REL_MAX
        failed |= not ok
        log(f"served tokens request {i}: {len(tokens[i])} greedy tokens, "
            f"largest reference-logit shortfall {worst:.4g} x rms "
            f"(tol {TOL_REL_MAX}) -> {'ok' if ok else 'FAIL'}")
    if failed:
        raise AssertionError("served logits disagree with the reference")


def one_chip(seed: int) -> dict:
    info = require_tpu(1)
    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    bundle = ModelBundle(cfg)
    params = bundle.init_params(jax.random.PRNGKey(seed))
    log(f"model: {cfg.name} ({cfg.num_params() / 1e9:.3f} B params, "
        f"{cfg.n_layers} layers, d_model {cfg.d_model}, {cfg.dtype})")
    requests = make_requests(cfg, seed)
    server, tokens, served = phase_serve(bundle, params, requests)
    del server
    gc.collect()   # the server holds reference cycles; free its KV cache
    phase_kernels(seed)
    phase_check(bundle, params, requests, tokens, served)
    return info


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def largest_all_gather(hlo: str) -> int:
    """Elements in the largest array an all-gather of ``hlo`` produces
    (synchronous or ``all-gather-start``, whose result is a tuple)."""
    sizes = [0]
    for m in re.finditer(r"=(.*?) all-gather(?:-start)?\(", hlo):
        sizes += [
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))
        ]
    return max(sizes)


def devices_of(tree) -> set:
    return set().union(*(x.sharding.device_set for x in jax.tree.leaves(tree)))


def four_chips(seed: int) -> dict:
    info = require_tpu(4)
    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    bundle = ModelBundle(cfg)
    # float32 weights at highest precision: greedy tokens then agree
    # across device layouts unless a top-2 margin sits at rounding level
    params = bundle.init_params(jax.random.PRNGKey(seed), "float32")
    requests = make_requests(cfg, seed)
    scfg = dict(batch_slots=SLOTS, max_len=MAX_LEN)
    results = {}
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        server = Server(bundle, ServeConfig(policy="hbm_resident", **scfg),
                        params)
        results["one chip"] = serve(server, requests)
        log(f"one-chip server ({server.policy.name}): "
            f"{time.perf_counter() - t0:.1f} s")
        del server
        gc.collect()   # free its device buffers before the next layout

        t0 = time.perf_counter()
        cluster = Cluster(
            bundle, DisaggConfig(split="prefill:2,decode:2", **scfg), params
        )
        pre = devices_of(cluster.prefill.engine.caches)
        dec = devices_of(cluster.decode.engine.caches)
        log(f"disagg 2+2: prefill cache on {len(pre)} devices, decode "
            f"cache on {len(dec)} devices, {len(pre | dec)} distinct")
        if len(pre) != 2 or len(dec) != 2 or len(pre | dec) != 4:
            raise AssertionError("the 2+2 pools do not span four chips")
        results["disagg 2+2"] = serve(cluster, requests)
        led = cluster.stats()["handoff"]
        log(f"disagg 2+2: {led['published']} handoffs published, "
            f"{led['adopted']} adopted, {led['lost']} lost; "
            f"{time.perf_counter() - t0:.1f} s")
        del cluster
        gc.collect()   # the cluster holds reference cycles

        t0 = time.perf_counter()
        server = Server(
            bundle, ServeConfig(policy="kv_peer_hbm", **scfg), params,
            mesh=make_donor_mesh((1,), ("data",), 4),
        )
        kv = devices_of(server.engine.caches)
        shard_bytes = {
            x.addressable_shards[0].data.nbytes / x.nbytes
            for x in jax.tree.leaves(server.engine.caches)
        }
        log(f"kv_peer_hbm: KV cache on {len(kv)} devices, each holding "
            f"{sorted(shard_bytes)} of every leaf")
        if len(kv) != 4 or max(shard_bytes) > 0.25:
            raise AssertionError("kv_peer_hbm did not shard the KV cache "
                                 "over four chips")
        # the per-device kernels read each chip's quarter of the cache
        # where it lives: no step may gather a layer's whole cache
        layer = SLOTS * cfg.attention.n_kv_heads * MAX_LEN \
            * cfg.attention.d_head
        for step in ("decode", "prefill"):
            hlo = server.engine.hlo_text(step)
            biggest = largest_all_gather(hlo)
            log(f"kv_peer_hbm {step} step: tpu_custom_call "
                f"{'present' if 'tpu_custom_call' in hlo else 'ABSENT'}, "
                f"largest all-gather {biggest} elements (a layer's cache "
                f"is {layer})")
            if "tpu_custom_call" not in hlo or biggest >= layer:
                raise AssertionError(
                    f"kv_peer_hbm {step} step gathers the KV cache or "
                    "runs no Mosaic kernel"
                )
        results["kv_peer_hbm"] = serve(server, requests)
        log(f"kv_peer_hbm: {time.perf_counter() - t0:.1f} s")
        del server
        gc.collect()   # free its device buffers before the next layout

    want = results["one chip"]
    failed = False
    for name in ("disagg 2+2", "kv_peer_hbm"):
        got = results[name]
        same = sum(got[r.rid] == want[r.rid] for r in requests)
        n_tok = sum(len(want[r.rid]) for r in requests)
        log(f"{name} vs one chip: {same}/{len(requests)} requests with "
            f"identical greedy tokens ({n_tok} tokens; tolerance: all)")
        failed |= same != len(requests)
    if failed:
        raise AssertionError("cross-chip serving disagrees with one chip")
    return info


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip paths, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    info = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
