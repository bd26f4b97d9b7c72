"""``repro.api`` — the one placement-aware runtime facade.

The paper's §IV studies (and Schieffer et al.'s follow-up on the unified
GH200 address space) show that *per-role, per-phase* physical placement
decides performance, and that the best placement changes as the workload
changes: prefill vs decode, a KV cache growing toward the HBM ceiling, a
train→serve handover.  Acting on that requires three things the scattered
pre-facade wiring could not express:

1. **Placements as values** — :func:`repro.core.placement.policy`,
   :class:`~repro.core.placement.PolicyBuilder` and the policy string/JSON
   grammar build arbitrary :class:`~repro.core.placement.PlacementPolicy`
   objects; the registry makes them nameable.
2. **One facade** — a :class:`Runtime` owns mesh + policy + planner.
   :meth:`Runtime.auto` runs the planner restricted to the tiers this
   runtime realizes; :meth:`Runtime.realize` / :meth:`Runtime.specs`
   subsume the per-call-site ``policy_specs``/``put_like`` wiring;
   :meth:`Runtime.explain` surfaces the planner's prediction table.
3. **Re-placement as a runtime primitive** — :meth:`Runtime.migrate`
   moves *live* tensors between tiers mid-run: donation-aware
   ``device_put`` onto the new (donor-extended) shardings, validated
   against the mesh (:class:`~repro.core.placement.DonorAxisError`, never
   a silent local landing), with registered ``Strategy.STREAM`` staging
   buffers rebuilt around the moved tree.  ``Server.replan()`` in
   :mod:`repro.serve.scheduler` uses it to re-place the KV cache and params
   when occupancy crosses planner-priced thresholds — the first point in
   the repo where the paper's placement tradeoffs are acted on *during*
   execution instead of only at startup.
"""

from __future__ import annotations

import dataclasses
import json
import logging
from typing import Iterable, Mapping, Sequence

import jax
from jax.sharding import PartitionSpec as P

from repro.core.datapath import copy_bound
from repro.core.hardware import (
    DEFAULT_SYSTEM,
    MemoryTier,
    SystemSpec,
    get_active_system,
    set_active_system,
)
from repro.core.faults import NO_FAULTS, FaultPlan
from repro.core.replay import ReplayLog
from repro.core.placement import (
    HOST_TIERS,
    PEER_TIERS,
    REMOTE_TIERS,
    DonorStream,
    Placement,
    PlacementPolicy,
    Role,
    _put_like,
    donor_allow_flags,
    get_policy,
    parse_policy,
    parse_role,
    parse_tier,
    put_donating,
    registered_policies,
    validate_policy_for_mesh,
)
from repro.core.planner import PolicyPrediction, plan, predict
from repro.models.sharding import _policy_specs, donation_compatible

log = logging.getLogger("repro.api")

__all__ = ["Runtime", "PhasePlan", "SPEC_SYSTEM"]

#: The spec-sheet baseline, re-exported so spec-vs-calibrated comparisons
#: (benchmarks, placement sweeps) never re-import the hardware singleton:
#: this facade is the one sanctioned consumer of the raw constant.
SPEC_SYSTEM = DEFAULT_SYSTEM

#: decode-step EWMA weights (old, new) — matches the serve Executor's
#: historical smoothing so pricing behavior is unchanged, just owned here.
_EWMA_OLD, _EWMA_NEW = 0.8, 0.2


@dataclasses.dataclass
class PhasePlan:
    """One planner pass: the pick plus everything it was compared against.

    ``predictions`` maps policy name to the phase's (possibly combined)
    :class:`~repro.core.planner.PolicyPrediction`; ``score`` is the
    quantity the pick minimized (plain ``step_s`` for single-profile
    phases, the combined per-token time for ``serve``).
    """

    phase: str
    picked: str
    predictions: dict[str, PolicyPrediction]
    score: dict[str, float]
    feasible: frozenset[str]

    def table(self, top: int = 3) -> str:
        """Human-readable top-``top`` candidate table (the pick always
        included), feasible candidates first, fastest first."""
        ranked = sorted(
            self.predictions,
            key=lambda n: (n not in self.feasible, self.score[n]),
        )
        show = ranked[:top]
        if self.picked in self.predictions and self.picked not in show:
            show.append(self.picked)
        lines = [f"phase={self.phase} picked={self.picked}"]
        for name in show:
            mark = "=> " if name == self.picked else "   "
            lines.append(f"{mark}{self.predictions[name].explain()}")
        return "\n".join(lines)


def _resolve_candidates(
    candidates: Iterable[PlacementPolicy | str] | None,
) -> list[PlacementPolicy] | None:
    if candidates is None:
        return None
    return [parse_policy(c) for c in candidates]


def _candidate_index(
    cand: list[PlacementPolicy] | None,
) -> dict[str, PlacementPolicy]:
    """Name -> policy over the candidate set the planner enumerated
    (the registry when no explicit candidates were given)."""
    return {
        p.name: p
        for p in (registered_policies().values() if cand is None else cand)
    }


class Runtime:
    """Mesh + placement policy + planner behind one object.

    Construct directly to force a policy (any spelling
    :func:`~repro.core.placement.parse_policy` accepts — a registered
    name, the compact grammar, JSON, or a
    :class:`~repro.core.placement.PlacementPolicy` value), or via
    :meth:`auto` to let the planner pick for a phase.  Either way the
    policy is validated against the mesh up front: a peer/remote
    placement on a donor-less mesh raises
    :class:`~repro.core.placement.DonorAxisError` at construction, never
    a silent local landing at realize time.
    """

    def __init__(
        self,
        bundle,
        mesh=None,
        policy: PlacementPolicy | str | Mapping | None = None,
        *,
        rules: Mapping | None = None,
        system: SystemSpec | None = None,
    ):
        self.bundle = bundle
        self.mesh = mesh
        self.rules = rules
        # the runtime owns the (possibly calibrated) system every pricing
        # path consumes; None adopts the process-wide active system.
        self.system = system if system is not None else get_active_system()
        self.policy = (
            get_policy("hbm_resident") if policy is None
            else parse_policy(policy)
        )
        validate_policy_for_mesh(self.policy, mesh)
        #: planner passes run by auto()/plan_phase(), newest last per phase
        self.plans: dict[str, PhasePlan] = {}
        self._streams: dict[Role, tuple[DonorStream, tuple]] = {}
        self._step_estimates: dict[tuple, float] = {}
        #: measured decode-step EWMA per (batch_slots, max_len, policy)
        self._step_observed: dict[tuple, float] = {}
        #: the last Calibration adopted by calibrate() (None = spec)
        self.calibration = None
        #: predicted-vs-measured log fed by observe_decode_step()
        self.replay = ReplayLog()
        #: injected-fault schedule; the falsy NO_FAULTS default means
        #: production paths pay one truthiness test (see core/faults.py)
        self.faults: FaultPlan = NO_FAULTS
        #: tiers declared unusable by mark_tier_lost()/evacuate();
        #: _allow_flags() masks them out of every subsequent planner
        #: pass, spill-placement pick and migration target
        self.lost_tiers: set[MemoryTier] = set()

    # -- construction ------------------------------------------------------
    @classmethod
    def auto(
        cls,
        bundle,
        mesh=None,
        *,
        phase: str = "decode",
        rules: Mapping | None = None,
        system: SystemSpec | None = None,
        candidates: Iterable[PlacementPolicy | str] | None = None,
        require_fit: bool = False,
        **phase_kw,
    ) -> "Runtime":
        """Planner-selected Runtime for ``phase``.

        ``phase`` is ``"train"``, ``"decode"``, ``"prefill"`` or
        ``"serve"`` (decode + chunked prefill priced together, the serve
        engine's combined per-token objective).  ``phase_kw`` are the
        workload knobs of :meth:`plan_phase` (``batch``/``seq``/``remat``
        for train; ``batch_slots``/``max_len``/``prefill_chunk`` for the
        serve-side phases).  The candidate set defaults to the policy
        registry restricted to the tiers this mesh/backend realizes
        (:func:`~repro.core.placement.donor_allow_flags`), so the pick is
        always realizable.
        """
        rt = cls(bundle, mesh, None, rules=rules, system=system)
        rt.plan_phase(
            phase, candidates=candidates, require_fit=require_fit,
            **phase_kw,
        )
        return rt

    @property
    def num_chips(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 1

    # -- degraded-tier bookkeeping -----------------------------------------
    def mark_tier_lost(self, tier: "MemoryTier | str") -> MemoryTier:
        """Declare ``tier`` unusable for the rest of this runtime's life.

        Tier loss happens at donor-axis granularity — losing the peer
        link takes peer HBM *and* peer DRAM with it (same ``donor``
        axis), so the sibling tier on the same axis is marked too.
        Planner passes, :meth:`spill_placement` and :meth:`evacuate`
        all consult :attr:`lost_tiers` via :meth:`_allow_flags`.
        """
        tier = parse_tier(tier)
        self.lost_tiers.add(tier)
        if tier in PEER_TIERS:
            self.lost_tiers |= PEER_TIERS
        if tier in REMOTE_TIERS:
            self.lost_tiers |= REMOTE_TIERS
        log.warning(
            "tier %s marked lost (now excluded: %s)",
            tier.value, sorted(t.value for t in self.lost_tiers),
        )
        return tier

    def _allow_flags(self) -> dict:
        """``donor_allow_flags(mesh)`` masked by :attr:`lost_tiers` — the
        one place every planning/spill/migration path gets its tier
        eligibility, so a lost tier disappears from all of them at once."""
        allow = donor_allow_flags(self.mesh)
        if not self.lost_tiers:
            return allow
        allow = dict(allow)
        if MemoryTier.HOST in self.lost_tiers:
            allow["allow_host"] = False
        if self.lost_tiers & PEER_TIERS:
            allow["allow_peer"] = False
        if self.lost_tiers & REMOTE_TIERS:
            allow["allow_remote"] = False
        return allow

    # -- planning ----------------------------------------------------------
    def plan_phase(
        self,
        phase: str = "decode",
        *,
        batch: int = 8,
        seq: int = 128,
        remat: bool = True,
        batch_slots: int = 8,
        max_len: int = 512,
        prefill_chunk: int = 32,
        kv_utilization: float = 1.0,
        candidates: Iterable[PlacementPolicy | str] | None = None,
        require_fit: bool = False,
        log_table: bool = True,
    ) -> PolicyPrediction:
        """Run the planner for ``phase`` and adopt its pick.

        Restricted to tiers this runtime realizes; ``kv_utilization``
        scales the KV-cache bytes of the serve-side profiles to the
        *current* cache occupancy — what :meth:`repro.serve.scheduler.
        Server.replan` feeds so spill/promote thresholds are priced on
        live
        state, not the worst case.  Returns the winning (decode-side for
        ``serve``) prediction; the full comparison lands in
        :attr:`plans` and :meth:`explain`.
        """
        from repro.configs import ShapeSpec

        cand = _resolve_candidates(candidates)
        if cand is None and self.mesh is None:
            # With no mesh the runtime realizes no placements (realize()
            # is a no-op), whatever the backend's memory kinds — restrict
            # the auto pick to the default placement so the planner never
            # adopts a policy this runtime would silently fail to realize.
            cand = [get_policy("hbm_resident")]
        allow = self._allow_flags()
        num_chips = self.num_chips

        if phase == "train":
            axes = dict(self.mesh.shape) if self.mesh is not None else {}
            prof = self.bundle.train_workload(
                ShapeSpec("auto", seq, batch, "train"),
                num_chips=num_chips,
                data_axis_size=axes.get("data", 1),
                pod_axis_size=axes.get("pod", 1),
                remat=remat,
            )
            best, preds = plan(
                prof, cand, self.system, require_fit=require_fit, **allow
            )
            score = {p.policy: p.step_s for p in preds}
            combined = {p.policy: p for p in preds}
        elif phase in ("decode", "prefill"):
            shape = ShapeSpec("auto", max_len, batch_slots, "decode")
            if phase == "decode":
                prof = self.bundle.decode_workload(shape, num_chips=num_chips)
            else:
                prof = self.bundle.prefill_workload(
                    shape, chunk_tokens=prefill_chunk, num_chips=num_chips
                )
            prof = _scale_kv(prof, kv_utilization)
            best, preds = plan(
                prof, cand, self.system, require_fit=require_fit, **allow
            )
            score = {p.policy: p.step_s for p in preds}
            combined = {p.policy: p for p in preds}
        elif phase == "serve":
            best, score, combined = self._plan_serve(
                cand, batch_slots=batch_slots, max_len=max_len,
                prefill_chunk=prefill_chunk, kv_utilization=kv_utilization,
                require_fit=require_fit,
            )
        else:
            raise ValueError(
                f"unknown phase {phase!r}; one of train/decode/prefill/serve"
            )

        self.policy = _candidate_index(cand)[best.policy]
        self.plans[phase] = PhasePlan(
            phase=phase,
            picked=best.policy,
            predictions=combined,
            score=score,
            feasible=frozenset(n for n, p in combined.items() if p.fits),
        )
        if log_table:
            log.info("planner\n%s", self.explain(phase))
        return best

    def _plan_serve(
        self,
        cand,
        *,
        batch_slots: int,
        max_len: int,
        prefill_chunk: int,
        kv_utilization: float,
        require_fit: bool,
    ):
        """Price decode AND chunked prefill; minimize combined per-token
        time over policies that fit both phases (one decode step yields
        ``batch_slots`` tokens; one prefill dispatch ingests
        ``batch_slots * prefill_chunk`` prompt tokens — amortized 1:1).
        When nothing fits, fall back to the least-HBM decode prediction
        (a slower placement that runs beats an OOM), unless
        ``require_fit``."""
        from repro.configs import ShapeSpec
        from repro.core.planner import PlacementOOMError

        shape = ShapeSpec("serve", max_len, batch_slots, "decode")
        dec_prof = _scale_kv(
            self.bundle.decode_workload(shape, num_chips=self.num_chips),
            kv_utilization,
        )
        pre_prof = _scale_kv(
            self.bundle.prefill_workload(
                shape, chunk_tokens=prefill_chunk, num_chips=self.num_chips
            ),
            kv_utilization,
        )
        allow = self._allow_flags()
        _, dec_preds = plan(dec_prof, cand, self.system, **allow)
        by_name = _candidate_index(cand)
        pre_preds = {
            d.policy: predict(pre_prof, by_name[d.policy], self.system)
            for d in dec_preds
        }

        def per_token(d: PolicyPrediction) -> float:
            return d.step_s + pre_preds[d.policy].step_s / max(
                prefill_chunk, 1
            )

        score = {d.policy: per_token(d) for d in dec_preds}
        combined = {d.policy: d for d in dec_preds}
        feasible = [
            d for d in dec_preds if d.fits and pre_preds[d.policy].fits
        ]
        if feasible:
            best = min(feasible, key=per_token)
        elif require_fit:
            raise PlacementOOMError(dec_preds, self.system)
        else:
            best = min(dec_preds, key=lambda d: d.hbm_bytes)
            for d in dec_preds:
                log.warning(
                    "planner OOM: %s overflows pools %s (decode) / %s "
                    "(prefill)",
                    d.policy,
                    ", ".join(d.overflow_pools) or "none",
                    ", ".join(pre_preds[d.policy].overflow_pools) or "none",
                )
        # mark serve feasibility as BOTH-phase fit for the PhasePlan
        combined = {
            n: dataclasses.replace(d, fits=d.fits and pre_preds[n].fits)
            for n, d in combined.items()
        }
        return best, score, combined

    def explain(self, phase: str | None = None, top: int = 3) -> str:
        """The planner's prediction table for ``phase`` (default: every
        phase planned so far): the top-``top`` candidates with their
        per-term datapath seconds, pool residency and fit, the pick
        marked.  Empty string when nothing was planned (forced policy)."""
        plans = (
            list(self.plans.values()) if phase is None
            else [self.plans[phase]] if phase in self.plans else []
        )
        return "\n".join(pl.table(top) for pl in plans)

    def describe(self) -> dict:
        """JSON-serializable record of what this runtime runs under —
        benchmark artifacts embed it so the numbers name their placement."""
        return {
            "policy": json.loads(self.policy.to_json()),
            "mesh_axes": dict(self.mesh.shape) if self.mesh is not None else None,
            "phases": {
                name: {
                    "picked": pl.picked,
                    "top3": pl.table(3),
                }
                for name, pl in self.plans.items()
            },
        }

    # -- realization -------------------------------------------------------
    def specs(
        self,
        role: Role | str,
        defs=None,
        *,
        fsdp_axes: Sequence[str] = (),
        policy: PlacementPolicy | None = None,
    ):
        """NamedShardings realizing the policy's placement of ``role``.

        ``defs`` is a Param-def pytree (defaults to the bundle's param
        defs for ``Role.PARAMS``).  Returns ``None`` with no mesh — the
        single-device path where placement is a no-op.
        """
        if self.mesh is None:
            return None
        role = parse_role(role)
        if defs is None:
            if role is not Role.PARAMS:
                raise ValueError(
                    f"specs({role}): a def pytree is required for every "
                    "role but PARAMS (params default to bundle.param_defs())"
                )
            defs = self.bundle.param_defs()
        return _policy_specs(
            defs, self.mesh, self.rules, role, policy or self.policy,
            fsdp_axes=fsdp_axes,
        )

    def realize(
        self,
        tree,
        role: Role | str,
        defs=None,
        *,
        specs=None,
        fsdp_axes: Sequence[str] = (),
        policy: PlacementPolicy | None = None,
    ):
        """device_put ``tree`` under the policy's placement for ``role``.

        With ``defs`` (or for ``Role.PARAMS``, where the bundle's defs
        are implied) the placement is realized through the logical-axis
        rule table; a def-less tree is placed leaf-wise with ``specs``
        (a PartitionSpec or matching pytree, default replicated) extended
        over the tier's donor axes.  No mesh -> returns ``tree``
        unchanged (nothing to realize).
        """
        if self.mesh is None:
            return tree
        if self.faults:
            self.faults.check("realize")
        role = parse_role(role)
        pol = policy or self.policy
        if defs is None and specs is None and role is Role.PARAMS:
            defs = self.bundle.param_defs()
        if defs is not None:
            shardings = self.specs(role, defs, fsdp_axes=fsdp_axes,
                                   policy=pol)
            return jax.tree.map(jax.device_put, tree, shardings)
        return _put_like(
            tree, self.mesh, P() if specs is None else specs, role, pol
        )

    def donate_ok(self, role: Role | str) -> bool:
        """May a jitted step donate ``role``'s buffers under the current
        policy?  (STREAM placements must keep their resident buffer.)"""
        return donation_compatible(self.policy, parse_role(role))

    # -- static data-movement audit ----------------------------------------
    def audit(
        self,
        target,
        arg_roles: Mapping[str, "Role | str"],
        *,
        donated: Iterable[str] = (),
        host_bytes_allowed: float = 0.0,
        workload=None,
        tolerance: float = 0.5,
        label: str = "",
    ):
        """Diff a compiled executable's data movement against this policy.

        ``target`` is a jax ``Compiled`` (anything with ``as_text()``) or
        raw HLO text.  ``arg_roles`` maps jit argument names (the roots of
        the ``op_name`` arg paths in the entry parameters, e.g.
        ``{"caches": Role.KV_CACHE, "p": Role.PARAMS}``) to planner roles;
        ``donated`` names the arguments the call actually donates.  A
        donation-compatible donated argument must appear in the module's
        ``input_output_alias`` header (else ``missed-donation``); an
        argument the policy forbids donating (STREAM) must not
        (``forbidden-donation``).  Host↔device traffic beyond
        ``host_bytes_allowed`` is ``stray-host-transfer`` — serve decode's
        allowance is the one (B,) token vector each way of Fig. 17.  With
        a planner ``workload`` (:class:`~repro.core.planner.
        WorkloadProfile`), each role's observed parameter bytes are also
        checked against ``bytes_per_role`` within ``tolerance``
        (warning-severity: padding and sharding legitimately skew these).

        Returns a :class:`repro.analysis.hlo_audit.AuditReport`.
        """
        from repro.analysis.hlo_audit import (
            ExpectedMovement,
            RoleExpectation,
            audit_hlo_text,
        )

        donated = set(donated)
        plan_bytes = dict(getattr(workload, "bytes_per_role", None) or {})
        term_by_tier = {
            MemoryTier.HBM: "hbm",
            MemoryTier.HOST: "pcie",
            MemoryTier.PEER_HBM: "ici",
            MemoryTier.PEER_HOST: "ici",
            MemoryTier.REMOTE_HBM: "dcn",
        }
        roles = []
        for root, role in arg_roles.items():
            role = parse_role(role)
            roles.append(RoleExpectation(
                role=role.value,
                arg_root=root,
                donate=root in donated and self.donate_ok(role),
                planner_term=term_by_tier.get(
                    self.policy.placement(role).tier, "hbm"
                ),
                plan_bytes=(
                    float(plan_bytes[role]) if role in plan_bytes else None
                ),
                tolerance=tolerance,
            ))
        expected = ExpectedMovement(
            roles=tuple(roles),
            host_bytes_allowed=float(host_bytes_allowed),
            label=label or f"{self.bundle.cfg.name}:{self.policy.name}",
        )
        text = target if isinstance(target, str) else target.as_text()
        mesh_axes = (
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            if self.mesh is not None else None
        )
        return audit_hlo_text(text, expected, mesh_axes)

    # -- eviction pricing --------------------------------------------------
    def price_copy(
        self,
        nbytes: float,
        dst: "Placement | MemoryTier | str",
        src: "Placement | MemoryTier | str | None" = None,
    ) -> float:
        """Planner-priced seconds to move ``nbytes`` between tiers.

        The datapath ``copy_bound`` (twice-traversed-link halving rule +
        per-segment latencies) between ``src`` (default: the current
        policy's KV-cache tier) and ``dst`` — the cost model behind
        preemption decisions: what does parking these cache rows off-HBM
        actually cost on this machine?
        """
        if src is None:
            src = self.policy.placement(Role.KV_CACHE)
        src_t = src.tier if isinstance(src, Placement) else parse_tier(src)
        dst_t = dst.tier if isinstance(dst, Placement) else parse_tier(dst)
        return copy_bound(src_t, dst_t, self.system).time(nbytes)

    def spill_placement(self, allow: dict | None = None) -> Placement:
        """The cheapest *realizable* far-tier parking spot for evicted KV
        rows: host DRAM when the backend exposes it, the peer/remote
        donor pools when the mesh has the donor axis — whichever round
        trip the datapath model prices lowest.  Falls back to local HBM
        (a placement-neutral parking copy: the slot is still freed, just
        without relieving HBM capacity) when no far tier is realizable.

        ``allow`` lets a caller pin one ``_allow_flags()`` snapshot
        across the pick *and* whatever pricing it derives from the pick
        (:meth:`preemption_price` does) — :meth:`mark_tier_lost` racing a
        concurrent evacuation must not let the two disagree.
        """
        if allow is None:
            allow = self._allow_flags()
        tiers: list[MemoryTier] = []
        if allow["allow_host"]:
            tiers.append(MemoryTier.HOST)
        if allow["allow_peer"]:
            tiers += [MemoryTier.PEER_HOST, MemoryTier.PEER_HBM]
        if allow["allow_remote"]:
            tiers.append(MemoryTier.REMOTE_HBM)
        if not tiers:
            return Placement(MemoryTier.HBM)
        one_mb = 1 << 20   # round trip at a representative row size
        best = min(
            tiers,
            key=lambda t: self.price_copy(one_mb, t)
            + self.price_copy(one_mb, self.policy.placement(Role.KV_CACHE),
                              src=t),
        )
        return Placement(best)

    def preemption_price(self, nbytes: float) -> tuple[Placement, float]:
        """(spill placement, round-trip seconds) for parking ``nbytes``
        of KV rows off-cache and bringing them back — what the scheduler
        weighs against the planner-predicted natural slot-free time
        before evicting a victim.

        The spill-target pick and the price read the *same*
        ``_allow_flags()`` snapshot: a ``mark_tier_lost`` landing between
        them (tier-loss recovery runs concurrently with the scheduler's
        preemption scan) must not price a tier the pick no longer
        considers realizable, or vice versa.
        """
        allow = self._allow_flags()
        spill = self.spill_placement(allow=allow)
        kv = self.policy.placement(Role.KV_CACHE)
        return spill, (
            self.price_copy(nbytes, spill)
            + self.price_copy(nbytes, kv, src=spill)
        )

    def decode_step_seconds(
        self, batch_slots: int, max_len: int
    ) -> float:
        """Decode-step seconds under the current policy — the other side
        of the preemption ledger (how long until a slot frees naturally).

        Measurement-backed: once :meth:`observe_decode_step` has fed real
        Executor step timings for this (batch, len, policy) shape, the
        observed EWMA is returned; before any observation the planner's
        analytic prediction is the fallback.
        """
        observed = self.measured_step_s(batch_slots, max_len)
        if observed is not None:
            return observed
        return self._analytic_step_seconds(batch_slots, max_len)

    def _analytic_step_seconds(self, batch_slots: int, max_len: int
                               ) -> float:
        from repro.configs import ShapeSpec

        key = (batch_slots, max_len, self.policy.name)
        cached = self._step_estimates.get(key)
        if cached is not None:
            return cached
        prof = self.bundle.decode_workload(
            ShapeSpec("serve", max_len, batch_slots, "decode"),
            num_chips=self.num_chips,
        )
        est = predict(prof, self.policy, self.system).step_s
        self._step_estimates[key] = est
        return est

    def measured_step_s(self, batch_slots: int, max_len: int
                        ) -> float | None:
        """The observed decode-step EWMA for this shape under the current
        policy, or None before any observation."""
        return self._step_observed.get(
            (batch_slots, max_len, self.policy.name)
        )

    def observe_decode_step(
        self, batch_slots: int, max_len: int, seconds: float
    ) -> float:
        """Feed one measured decode-step time into the runtime.

        This is the serve Executor's per-step timing becoming a
        calibration observation: it updates the EWMA that
        :meth:`decode_step_seconds` (and through it
        :meth:`preemption_price` users like the scheduler's preemption
        ledger) returns.  Returns the updated EWMA.
        """
        seconds = float(seconds)
        if seconds <= 0.0:
            return self.decode_step_seconds(batch_slots, max_len)
        key = (batch_slots, max_len, self.policy.name)
        prev = self._step_observed.get(key)
        ewma = (seconds if prev is None
                else _EWMA_OLD * prev + _EWMA_NEW * seconds)
        self._step_observed[key] = ewma
        return ewma

    # -- calibration -------------------------------------------------------
    def calibrate(
        self,
        path=None,
        *,
        activate: bool = True,
        **kwargs,
    ):
        """Adopt a measurement-calibrated system for every pricing path.

        Runs :func:`repro.core.calibration.calibrate` (or loads the
        persisted ``calibration.json`` at ``path`` — which is also where
        a fresh run is saved), derives ``self.system`` via
        :meth:`SystemSpec.with_measurements`, and drops cached analytic
        step estimates so planner passes, ``price_copy``,
        ``preemption_price`` and ``decode_step_seconds`` all re-price
        under measured constants.  ``activate=True`` (default) also
        installs the calibrated system process-wide
        (:func:`repro.core.hardware.set_active_system`) so module-level
        helpers price consistently with this runtime.

        Calibration changes *pricing only* — realized placements and
        computed values are untouched (greedy serve tokens are
        bit-identical before/after; asserted in tests).  Returns the
        :class:`repro.core.calibration.Calibration`.
        """
        from repro.core.calibration import load_or_calibrate

        cal = load_or_calibrate(path, system=self.system, **kwargs)
        self.calibration = cal
        self.system = cal.apply(self.system)
        if activate:
            set_active_system(self.system)
        self._step_estimates.clear()
        self.replay.extend(cal.replay.records())
        log.info("calibrated hardware model:\n%s", cal.summary())
        return cal

    # -- live migration ----------------------------------------------------
    def migrate(
        self,
        tree,
        role: Role | str,
        to_policy: "PlacementPolicy | str | Mapping | Placement",
        defs=None,
        *,
        specs=None,
        fsdp_axes: Sequence[str] = (),
        donate: bool | None = None,
    ):
        """Re-place ``role``'s *live* tensors under ``to_policy`` mid-run.

        The runtime primitive behind phase-boundary re-placement (spill
        KV to host as occupancy grows, promote back as slots free, move
        params at a train→serve handover):

        * ``to_policy`` may be a full policy (any
          :func:`~repro.core.placement.parse_policy` spelling) or a bare
          :class:`~repro.core.placement.Placement` applied to ``role``
          on top of the current policy.
        * The target is validated against the mesh first — migrating to
          a peer/remote tier on a donor-less mesh raises
          :class:`~repro.core.placement.DonorAxisError`; a live buffer
          never silently lands in local memory.
        * The move is one ``device_put`` per leaf onto the new
          (donor-extended) shardings, **donation-aware**: when the
          *source* placement is donation-compatible (RESIDENT — nothing
          streams from the old buffer), the old tier's bytes are donated
          to the transfer and freed as the copy lands; a STREAM source
          keeps its resident buffer undonated until the new tree is up
          (in-flight staged windows still read it).
        * Registered ``Strategy.STREAM`` staging buffers for ``role``
          (see :meth:`open_stream`) are rebuilt around the migrated tree.

        Adopts the resulting policy as the runtime's current policy and
        returns the migrated tree; values are bit-identical (it is a
        copy, not a recompute).  Requires a mesh — with no mesh there is
        no second tier to move to.
        """
        if self.mesh is None:
            raise ValueError(
                "Runtime.migrate needs a mesh: with no mesh the runtime "
                "realizes no placements, so there is nothing to move "
                "between"
            )
        # pre-dispatch injection: before validation and before any
        # device_put, so a faulted migrate adopts nothing and donates
        # nothing — a retry sees the exact pre-call state.
        if self.faults:
            self.faults.check("migrate")
        role = parse_role(role)
        if isinstance(to_policy, Placement):
            new_policy = self.policy.with_placement(role, to_policy)
            new_policy = new_policy.renamed(
                f"{self.policy.name}+{role.value}={to_policy.to_str()}"
            )
        else:
            new_policy = parse_policy(to_policy)
        validate_policy_for_mesh(new_policy, self.mesh)

        if donate is None:
            # old STREAM buffers may still be feeding staged windows
            donate = donation_compatible(self.policy, role)
        if defs is None and specs is None and role is Role.PARAMS:
            defs = self.bundle.param_defs()
        if defs is not None:
            new_specs = _policy_specs(
                defs, self.mesh, self.rules, role, new_policy,
                fsdp_axes=fsdp_axes,
            )
            moved = jax.tree.map(
                lambda x, s: put_donating(x, s, donate),
                tree, new_specs,
            )
        else:
            # def-less path: the same realizer realize() uses, donating
            moved = _put_like(
                tree, self.mesh, P() if specs is None else specs, role,
                new_policy, donate=donate,
            )

        old = self.policy.placement(role)
        self.policy = new_policy
        self._rebuild_stream(role, moved)
        log.info(
            "migrated %s: %s -> %s under policy %s",
            role.value, old.to_str(),
            new_policy.placement(role).to_str(), new_policy.name,
        )
        return moved

    def migrate_roles(
        self,
        trees: dict,
        target: "PlacementPolicy | str | Mapping",
        defs: Mapping | None = None,
        *,
        force: bool = False,
    ) -> list[Role]:
        """Migrate several roles' live trees to ``target`` in one pass.

        ``trees`` maps :class:`Role` to its live pytree and is mutated
        **in place** as each role lands — deliberately: a migrated role's
        old buffers may have been donated (freed), so the moved tree must
        survive a later role's failure.  Roles whose placement is
        unchanged are skipped unless ``force``.  ``defs`` maps roles to
        def pytrees (PARAMS defaults to the bundle's).

        On partial failure the adopted policy is the *old* policy with
        the already-moved placements swapped in — it always describes
        what the live buffers actually are — and the error re-raises.
        On success adopts ``target``.  Returns the roles moved.
        """
        if self.mesh is None:
            return []
        target = parse_policy(target)
        validate_policy_for_mesh(target, self.mesh)
        old = self.policy
        defs = defs or {}
        moved: list[Role] = []
        try:
            for role in list(trees):
                role = parse_role(role)
                if not force and target.placement(role) == old.placement(role):
                    continue
                trees[role] = self.migrate(
                    trees[role], role, target, defs.get(role),
                    donate=donation_compatible(old, role),
                )
                # migrate() adopted target; hold the handover until every
                # role lands so a failure can report the true partial state
                self.policy = old
                moved.append(role)
        except BaseException:
            partial = old
            for r in moved:
                partial = partial.with_placement(r, target.placement(r))
            if moved:
                partial = partial.renamed(
                    old.name + "+" + ",".join(
                        f"{r.value}={target.placement(r).to_str()}"
                        for r in moved
                    )
                )
            self.policy = partial
            raise
        self.policy = target
        return moved

    def evacuate(
        self,
        tier: "MemoryTier | str",
        trees: dict,
        defs: Mapping | None = None,
        *,
        phase: str | None = None,
        **phase_kw,
    ) -> tuple[PlacementPolicy, list[Role]]:
        """Abandon ``tier`` and re-place every affected role off it.

        The graceful-degradation primitive: :meth:`mark_tier_lost`
        excludes the tier (and its donor-axis siblings) from every
        future planner pass and spill pick, then the roles in ``trees``
        whose current placement sits on a lost tier are migrated to a
        realizable target — the planner's re-pick for ``phase`` when
        given (priced by the same ``migrate`` cost model as any replan),
        else the current policy with each lost placement swapped to
        local HBM (the placement that always exists).  Reuses
        :meth:`migrate_roles`' adopt-nothing-on-failure semantics.

        Tier loss is a *degradation notice*, not a crash: the lost
        tier's buffers are assumed still readable (the GH200 failure
        mode is an order-of-magnitude slowdown, not data loss), so the
        evacuation copy itself may read from them one last time.
        Returns ``(adopted policy, roles moved)``.
        """
        tier = self.mark_tier_lost(tier)
        old = self.policy
        affected = [
            r for r in trees if old.placement(parse_role(r)).tier
            in self.lost_tiers
        ]
        if self.mesh is None or not affected:
            return old, []
        if phase is not None:
            try:
                self.plan_phase(phase, log_table=False, **phase_kw)
                target = self.policy
            finally:
                self.policy = old
            # the planner minimizes step time, not realizability of the
            # degraded set: guard against a pick that still touches a
            # lost tier (possible only with explicit candidates)
            if any(
                target.placement(parse_role(r)).tier in self.lost_tiers
                for r in trees
            ):
                target = None
        else:
            target = None
        if target is None:
            target = old
            for r, p in old.placements.items():
                if p.tier in self.lost_tiers:
                    target = target.with_placement(
                        r, Placement(MemoryTier.HBM)
                    )
            target = target.renamed(f"{old.name}-evac-{tier.value}")
        moved = self.migrate_roles(trees, target, defs)
        log.warning(
            "evacuated %s off %s: policy %s -> %s",
            ",".join(r.value for r in moved) or "nothing",
            tier.value, old.name, self.policy.name,
        )
        return self.policy, moved

    # -- streaming ---------------------------------------------------------
    def open_stream(
        self,
        tree,
        role: Role | str,
        n_windows: int,
        *,
        specs=P(),
        depth: int = 2,
    ) -> DonorStream:
        """Double-buffered window streamer over ``role``'s donor-resident
        stack, registered with the runtime so :meth:`migrate` rebuilds
        its staging buffers around the migrated tree (stale staged
        windows from the old tier are dropped)."""
        role = parse_role(role)
        stream = DonorStream(tree, self.mesh, specs, n_windows, depth=depth)
        self._streams[role] = (stream, (specs, n_windows, depth))
        return stream

    def stream(self, role: Role | str) -> DonorStream | None:
        """The registered stream for ``role`` (None when none is open)."""
        entry = self._streams.get(parse_role(role))
        return entry[0] if entry else None

    def _rebuild_stream(self, role: Role, tree) -> None:
        entry = self._streams.get(role)
        if entry is None:
            return
        _, (specs, n_windows, depth) = entry
        self._streams[role] = (
            DonorStream(tree, self.mesh, specs, n_windows, depth=depth),
            (specs, n_windows, depth),
        )


def _scale_kv(profile, utilization: float):
    """Scale a profile's KV-cache bytes to the live cache occupancy
    (replan pricing); clamped to [1/16, 1] so an empty server still
    prices a nonzero cache."""
    u = min(max(float(utilization), 1.0 / 16.0), 1.0)
    if u >= 1.0 or Role.KV_CACHE not in profile.bytes_per_role:
        return profile
    scaled = dict(profile.bytes_per_role)
    scaled[Role.KV_CACHE] = scaled[Role.KV_CACHE] * u
    return dataclasses.replace(profile, bytes_per_role=scaled)
