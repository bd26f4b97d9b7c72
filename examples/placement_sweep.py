"""Placement sweep: the paper's §IV study as a runnable decision procedure.

    PYTHONPATH=src python examples/placement_sweep.py [--arch gemma3-27b]

Two parts, mirroring the paper's predicted-vs-measured method:

1. **Predicted** (Figs. 15-17 table, generated): for the full-size
   architecture at ``--chips`` chips, the datapath planner's step-time
   prediction + memory-pool fit for *every* placement policy, in both the
   training and decode regimes, and which policy the launcher would pick.

2. **Predicted vs measured**: the same-family smoke config is actually run
   on this host — one jitted decode step per policy, with params/KV placed
   under the policy's (backend-resolved) memory kinds and, for peer/remote
   policies, sharded across a **donor mesh axis** — next to the planner's
   prediction for *this* machine's workload shape.  The final column is
   the paper's headline metric, measured/predicted.  On a CPU container
   every tier resolves to the same physical memory, so measured times
   coincide by construction; a TPU backend separates the *host* tiers for
   real and puts peer/remote bytes an ICI/DCN hop away.  Peer/remote rows
   need >= 2 devices (run under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` on CPU to
   exercise them); with a single device they are starred: no donor mesh
   axis exists, the engine would refuse to realize them, and only the
   prediction is reported.

``--analytic`` prints the predicted tables only (the CI smoke mode).
``--calibration calibration.json`` activates a measurement-calibrated
hardware model: every prediction is then made under the calibrated
constants and the measured column reports its achieved-over-bound ratio
against **both** the spec-sheet and calibrated predictions — how much
calibration moved each policy's number.
"""

import argparse
import time

from repro.api import SPEC_SYSTEM
from repro.configs import SHAPES, ShapeSpec, get_config, list_archs, smoke_config
from repro.core.hardware import get_active_system
from repro.core.placement import (
    Role,
    TIER_DONOR_AXIS,
    host_available,
    registered_policies,
)
from repro.core.planner import plan, predict
from repro.models.model_zoo import ModelBundle


def _calibrated() -> bool:
    return get_active_system() is not SPEC_SYSTEM


def _mesh_axes(chips: int, data_axis: int, pod_axis: int) -> tuple[int, int]:
    """Clamp the requested axis sizes to what ``chips`` can host."""
    if data_axis * pod_axis > chips:
        pod_axis = 1
        data_axis = min(data_axis, chips)
    return data_axis, pod_axis


def predicted_tables(arch: str, chips: int, data_axis: int,
                     pod_axis: int) -> None:
    bundle = ModelBundle(get_config(arch))
    cfg = bundle.cfg
    data_axis, pod_axis = _mesh_axes(chips, data_axis, pod_axis)

    print(f"=== {cfg.name}: {cfg.num_params()/1e9:.1f}B params, "
          f"{chips} chips (data axis {data_axis}, pod axis {pod_axis}) ===\n")

    def _table(prof):
        # plan() prices under the active system; with a calibration
        # active, each row also shows the spec-sheet step time so the
        # table says how much calibration moved every prediction.
        best, preds = plan(prof)
        spec = {}
        if _calibrated():
            _, sp = plan(prof, system=SPEC_SYSTEM)
            spec = {p.policy: p for p in sp}
        for p in preds:
            mark = " <== planner pick" if p.policy == best.policy else ""
            extra = (f" [spec: {spec[p.policy].step_s*1e3:.3f}ms]"
                     if p.policy in spec else "")
            print("  " + p.explain() + extra + mark)

    print("-- training (train_4k) --")
    _table(bundle.train_workload(
        SHAPES["train_4k"],
        num_chips=chips,
        data_axis_size=data_axis,
        pod_axis_size=pod_axis,
    ))

    print("\n-- decoding (decode_32k) --")
    _table(bundle.decode_workload(SHAPES["decode_32k"], num_chips=chips))


def _mesh_for_policy(policy):
    """Mesh that realizes ``policy``: a plain 1-device mesh for local
    tiers, a 2-slice donor mesh (ICI or DCN axis per the tier) for
    peer/remote tiers — or None when this host lacks the devices."""
    import jax

    from repro.launch.mesh import make_donor_mesh, make_mesh_for

    donor_axes = {
        TIER_DONOR_AXIS[t] for t in policy.tiers() if t in TIER_DONOR_AXIS
    }
    if not donor_axes:
        return make_mesh_for((1,), ("data",))
    if jax.device_count() < 2 or len(donor_axes) > 1:
        return None
    return make_donor_mesh(
        (1,), ("data",), 2, remote=donor_axes == {"donor_pod"}
    )


def _measure_decode_ms(bundle, policy, slots: int, max_len: int,
                       iters: int) -> float | None:
    """Wall-clock of one jitted decode step under ``policy`` placements,
    realized on a donor mesh for peer/remote tiers (None when this host
    cannot realize the policy)."""
    import jax
    import jax.numpy as jnp

    from repro.api import Runtime

    mesh = _mesh_for_policy(policy)
    if mesh is None:
        return None
    rt = Runtime(bundle, mesh, policy)
    params = bundle.init_params(jax.random.PRNGKey(0), "float32")
    params = rt.realize(params, Role.PARAMS)
    cache_defs = bundle.cache_defs(slots, max_len)
    caches = rt.realize(bundle.init_cache(slots, max_len),
                        Role.KV_CACHE, cache_defs)
    cache_specs = rt.specs(Role.KV_CACHE, cache_defs)

    step = jax.jit(
        lambda p, b, c: bundle.decode_step(p, b, c),
        out_shardings=(None, cache_specs),
    )
    batch = {
        "tokens": jnp.ones((slots, 1), jnp.int32),
        "lengths": jnp.full((slots,), 4, jnp.int32),
    }
    logits, caches = step(params, batch, caches)  # compile
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for _ in range(iters):
        logits, caches = step(params, batch, caches)
    jax.block_until_ready(logits)
    return (time.perf_counter() - t0) / iters * 1e3


def predicted_vs_measured(arch: str, slots: int, max_len: int,
                          iters: int) -> None:
    import jax

    bundle = ModelBundle(smoke_config(arch))
    cfg = bundle.cfg

    prof = bundle.decode_workload(
        ShapeSpec("local", max_len, slots, "decode"), num_chips=1
    )
    cal = _calibrated()
    print(f"\n=== predicted vs measured: {cfg.name} decode on this host "
          f"({slots} slots x {max_len} ctx, host_available="
          f"{host_available()}, devices={jax.device_count()}, "
          f"calibration={'active' if cal else 'none (spec sheet)'}) ===")
    if cal:
        print(f"{'policy':<20} {'fits':<5} {'pred spec ms':>12} "
              f"{'pred cal ms':>12} {'measured ms':>12} "
              f"{'meas/spec':>10} {'meas/cal':>9}")
    else:
        print(f"{'policy':<20} {'fits':<5} {'predicted ms':>12} "
              f"{'measured ms':>12} {'meas/pred':>10}")
    starred = False

    def _ratio(meas_ms, pred_s):
        return meas_ms / (pred_s * 1e3) if pred_s else float("inf")

    # the registry, not a hand-written list: custom register_policy()'d
    # policies show up in the sweep automatically
    for policy in registered_policies().values():
        pred = predict(prof, policy)   # under the active (cal'd) system
        spec_pred = predict(prof, policy, SPEC_SYSTEM) if cal else pred
        meas = _measure_decode_ms(bundle, policy, slots, max_len, iters)
        if meas is None:
            starred = True
            if cal:
                print(f"{policy.name + '*':<20} {str(pred.fits):<5} "
                      f"{spec_pred.step_s*1e3:>12.4f} "
                      f"{pred.step_s*1e3:>12.4f} {'-':>12} {'-':>10} "
                      f"{'-':>9}")
            else:
                print(f"{policy.name + '*':<20} {str(pred.fits):<5} "
                      f"{pred.step_s*1e3:>12.4f} {'-':>12} {'-':>10}")
            continue
        if cal:
            print(f"{policy.name:<20} {str(pred.fits):<5} "
                  f"{spec_pred.step_s*1e3:>12.4f} {pred.step_s*1e3:>12.4f} "
                  f"{meas:>12.4f} {_ratio(meas, spec_pred.step_s):>10.1f} "
                  f"{_ratio(meas, pred.step_s):>9.1f}")
        else:
            print(f"{policy.name:<20} {str(pred.fits):<5} "
                  f"{pred.step_s*1e3:>12.4f} {meas:>12.4f} "
                  f"{_ratio(meas, pred.step_s):>10.1f}")
    if starred:
        print("* not measurable here: needs a donor mesh axis (>=2 devices; "
              "set XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-27b", choices=list_archs())
    ap.add_argument("--chips", type=int, default=256)
    ap.add_argument("--data-axis", type=int, default=16,
                    help="data-parallel (ICI) axis size for the train table")
    ap.add_argument("--pod-axis", type=int, default=2,
                    help="pod (DCN) axis size for the train table")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--no-measure", "--analytic", dest="no_measure",
                    action="store_true",
                    help="predicted tables only (pure analysis; the CI "
                         "smoke mode)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="activate a calibration.json (tools/calibrate.py) "
                         "so predictions use measured constants and the "
                         "table reports meas/spec AND meas/cal ratios")
    args = ap.parse_args()

    cal_path = args.calibration
    if cal_path:
        from repro.core.calibration import load_or_calibrate

        load_or_calibrate(cal_path, activate=True)
        print(f"(calibration active: {cal_path})\n")

    predicted_tables(args.arch, args.chips, args.data_axis, args.pod_axis)
    if not args.no_measure:
        predicted_vs_measured(args.arch, args.slots, args.max_len, args.iters)


if __name__ == "__main__":
    main()
