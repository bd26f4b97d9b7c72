"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Continuous-batching server over the model zoo with a placement policy for
the KV cache (the paper's Fig. 17 knob).  Feeds a synthetic request stream
and reports tokens/s + per-phase latency.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.placement import (
    PoolSplit,
    extract_pool_split,
    registered_policies,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models.model_zoo import ModelBundle
from repro.serve import (
    Cluster,
    DisaggConfig,
    Request,
    SamplingParams,
    ServeConfig,
    Server,
)

log = logging.getLogger("repro.serve")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--donor", type=int, default=1,
                    help="prepend an ICI donor axis of this size (>=2 "
                         "unlocks kv_peer_hbm / weights_peer_hbm)")
    ap.add_argument("--remote-donor", type=int, default=1,
                    help="prepend a DCN donor axis of this size (>=2 "
                         "unlocks kv_remote_hbm)")
    ap.add_argument(
        "--policy", default="auto",
        help="'auto' consults the placement planner (datapath-bound "
             "model); otherwise a registered name "
             f"({', '.join(registered_policies())}), the compact "
             "role=tier[:strategy][,...] grammar (e.g. "
             "'kv=host:stream,params=peer_hbm'), or policy JSON",
    )
    ap.add_argument(
        "--pools", default=None, metavar="prefill:N,decode:M",
        help="serve disaggregated (repro.serve.disagg): split the "
             "device set into a prefill pool and a decode pool joined "
             "by the DCN handoff.  'auto' lets plan_pool_split pick the "
             "split; the directive may equivalently ride inside "
             "--policy as pools=prefill:N,decode:M.  Ignores --mesh/"
             "--donor (the cluster owns its device partition).",
    )
    ap.add_argument(
        "--auto-replan", action="store_true",
        help="re-run the planner as cache occupancy crosses band "
             "boundaries and migrate the live KV cache/params when the "
             "pick changes (planner-owned policies only)",
    )
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest-probability tokens "
                         "(0 = no top-k filter)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = no top-p filter)")
    ap.add_argument("--seed", type=int, default=0,
                    help="per-request sampling seed base (request rid is "
                         "added so rows draw independently)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound on waiting requests (backpressure); "
                         "default unbounded")
    ap.add_argument("--preempt", action="store_true",
                    help="enable planner-priced KV preemption: starved "
                         "waiters may evict a victim slot to the cheapest "
                         "realizable far tier")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="price placements from a measurement-calibrated "
                         "hardware model: load this calibration.json, or "
                         "run the calibration microbenchmarks and save it "
                         "there when the file does not exist (spec-sheet "
                         "constants otherwise)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()
    if args.calibration:
        from repro.core.calibration import load_or_calibrate

        cal = load_or_calibrate(args.calibration, activate=True)
        log.info("calibrated hardware model active:\n%s", cal.summary())
    policy = None if args.policy == "auto" else args.policy
    # the pools= directive rides inside --policy (its value has commas,
    # so it is carved out before the role grammar parses) or arrives as
    # the explicit --pools flag; either selects the disaggregated path
    pool_split, policy = extract_pool_split(policy)
    if args.pools and args.pools != "auto":
        pool_split = PoolSplit.parse(args.pools)
    disaggregated = bool(args.pools) or pool_split is not None

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    bundle = ModelBundle(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))

    if disaggregated:
        if args.mesh != "1x1" or args.donor > 1 or args.remote_donor > 1:
            log.warning(
                "--pools ignores --mesh/--donor/--remote-donor: the "
                "cluster partitions the device set itself"
            )
        server = Cluster(
            bundle,
            DisaggConfig(
                batch_slots=args.slots,
                max_len=args.max_len,
                split=pool_split,
                policy=policy,
                max_queue=args.max_queue,
                preempt=args.preempt,
            ),
            params,
        )
        log.info(
            "serving disaggregated (%s) with placement policy %s",
            server.split.to_str(), server.decode.policy.name,
        )
    else:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[-len(dims):]
        if args.remote_donor > 1:
            dims, axes = (args.remote_donor, *dims), ("donor_pod", *axes)
        if args.donor > 1:
            dims, axes = (args.donor, *dims), ("donor", *axes)
        mesh = make_mesh_for(dims, axes) if np.prod(dims) > 1 else None
        server = Server(
            bundle,
            ServeConfig(
                batch_slots=args.slots,
                max_len=args.max_len,
                policy=policy,
                auto_replan=args.auto_replan,
                max_queue=args.max_queue,
                preempt=args.preempt,
            ),
            params,
            mesh=mesh,
        )
        log.info("serving with placement policy %s", server.policy.name)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        server.add_request(
            Request(
                rid=rid,
                prompt=rng.integers(
                    0, cfg.vocab, size=args.prompt_len
                ).astype(np.int32),
                max_new_tokens=args.max_new,
                sampling=SamplingParams(
                    temperature=args.temperature,
                    top_k=args.top_k,
                    top_p=args.top_p,
                    seed=args.seed + rid,
                ),
            )
        )
    t0 = time.perf_counter()
    server.run_until_done()
    dt = time.perf_counter() - t0
    total_tokens = args.requests * args.max_new
    tp = server.throughput()
    stats = server.stats()
    if disaggregated:
        led = stats["handoff"]
        log.info(
            "served %d requests, %d tokens in %.2fs -> %.1f tok/s "
            "(%s, policy %s) | prefill %.1f tok/s | decode %.1f tok/s "
            "| handoff: %d published / %d adopted / %d lost "
            "(%d bytes crossed donor_pod, %d replays)",
            args.requests, total_tokens, dt, total_tokens / dt,
            server.split.to_str(), server.decode.policy.name,
            tp["prefill_tps"], tp["decode_tps"],
            led["published"], led["adopted"], led["lost"],
            led["bytes_published"], stats["handoff_replays"],
        )
    else:
        log.info(
            "served %d requests, %d tokens in %.2fs -> %.1f tok/s "
            "(policy %s, %d replans / %d migrations, %d preemptions / "
            "%d promotions) | prefill %.1f tok/s | decode %.1f tok/s",
            args.requests, total_tokens, dt, total_tokens / dt,
            server.policy.name, stats["replans"], stats["migrations"],
            stats["preemptions"], stats["promotions"],
            tp["prefill_tps"], tp["decode_tps"],
        )


if __name__ == "__main__":
    main()
