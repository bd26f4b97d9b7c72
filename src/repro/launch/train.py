"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Wires together the whole stack: mesh, model bundle, placement policy (from
the planner unless forced), data pipeline with prefetch, fault-tolerant
supervisor with async checkpoints and straggler monitoring.  On this CPU
container it runs the smoke-scale configs end-to-end; on a TPU fleet the
same file is the per-process entry point (jax.distributed handles the
process group; the mesh helper sizes itself from jax.device_count()).
"""

from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Runtime
from repro.checkpoint import Checkpointer
from repro.configs import get_config, smoke_config
from repro.core.placement import registered_policies
from repro.data import DataConfig, Prefetcher, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models.model_zoo import ModelBundle
from repro.optim.adamw import AdamWConfig
from repro.runtime import Supervisor, SupervisorConfig
from repro.train import TrainConfig, init_train_state, make_train_step

log = logging.getLogger("repro.train")


def make_runtime(
    bundle: ModelBundle,
    mesh,
    policy_arg: str | None,
    *,
    batch: int = 8,
    seq: int = 128,
    remat: str = "full",
) -> Runtime:
    """The run's placement runtime: forced policy or planner-selected.

    A forced ``--policy`` accepts any :func:`repro.core.placement.
    parse_policy` spelling (registered name, ``role=tier:strategy``
    grammar, JSON) and is validated against the mesh up front.  The auto
    path runs the planner on the real run shape — including the gradient
    all-reduce terms for the mesh's data/pod axes — restricted to the
    tiers this runtime realizes, and logs the top-candidate table
    (:meth:`Runtime.explain`).
    """
    if policy_arg:
        rt = Runtime(bundle, mesh, policy_arg)
        log.info("placement policy forced: %s", rt.policy.name)
        return rt
    rt = Runtime.auto(
        bundle, mesh, phase="train",
        batch=batch, seq=seq, remat=remat != "none",
    )
    best = rt.plans["train"]
    if best.picked not in best.feasible:
        for name, p in best.predictions.items():
            log.warning("planner OOM: %s overflows pools %s",
                        name, ", ".join(p.overflow_pools) or "none")
    log.info("planner picked %s", rt.policy.name)
    return rt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1x1",
                    help="e.g. 2x2x2 -> (pod,data,model); 4x2 -> (data,model)")
    ap.add_argument("--donor", type=int, default=1,
                    help="prepend an ICI donor axis of this size (>=2 "
                         "unlocks the peer placement tiers)")
    ap.add_argument("--remote-donor", type=int, default=1,
                    help="prepend a DCN donor axis of this size (>=2 "
                         "unlocks kv_remote_hbm)")
    ap.add_argument(
        "--policy", default=None,
        help="force a placement policy: a registered name "
             f"({', '.join(registered_policies())}), the compact "
             "role=tier[:strategy][,...] grammar (e.g. "
             "'opt=host:stream'), or policy JSON; default: planner",
    )
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
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

    dims = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("pod", "data", "model")[-len(dims):] if len(dims) > 1 else ("data",)
    if args.remote_donor > 1:
        dims, axes = (args.remote_donor, *dims), ("donor_pod", *axes)
    if args.donor > 1:
        dims, axes = (args.donor, *dims), ("donor", *axes)
    mesh = make_mesh_for(dims, axes)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    bundle = ModelBundle(cfg)
    rt = make_runtime(
        bundle, mesh, args.policy,
        batch=args.batch, seq=args.seq, remat=args.remat,
    )
    policy = rt.policy

    tcfg = TrainConfig(
        remat=args.remat,
        n_microbatches=args.microbatches,
        compress_pod_grads=args.compress_pod_grads,
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=min(50, args.steps // 5 + 1)),
    )
    params, opt_state, ef = init_train_state(
        bundle, mesh, jax.random.PRNGKey(0), tcfg, policy
    )
    step_fn = jax.jit(
        # sharding is re-constrained inside the step: output placement is
        # the input placement
        make_train_step(bundle, mesh, tcfg, policy),
        donate_argnums=(0, 1),  # repro: lint-disable=donate-without-out-shardings
    )

    data = SyntheticLM(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch),
        process_index=jax.process_index(),
        process_count=jax.process_count(),
    )
    it = Prefetcher(data)

    ckpt = Checkpointer(args.ckpt_dir)
    sup = Supervisor(ckpt, SupervisorConfig(checkpoint_every=args.ckpt_every))

    state = {"params": params, "opt": opt_state, "ef": ef}
    losses = []

    def one_step(state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        p, o, e, metrics = step_fn(
            state["params"], state["opt"], state["ef"], batch
        )
        losses.append(float(metrics["loss"]))
        if len(losses) % args.log_every == 0:
            log.info(
                "step %d loss %.4f grad_norm %.3f",
                len(losses), losses[-1], float(metrics["grad_norm"]),
            )
        return {"params": p, "opt": o, "ef": e}, metrics

    state, step = sup.run(
        state, one_step, it, args.steps, extra_state=lambda: {"data": data.state()}
    )
    it.close()
    log.info(
        "done: %d steps, loss %.4f -> %.4f, straggler stats %s",
        step, losses[0] if losses else float("nan"),
        losses[-1] if losses else float("nan"), sup.monitor.summary(),
    )


if __name__ == "__main__":
    main()
