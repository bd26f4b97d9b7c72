"""Layer 1: diff compiled-HLO data movement against the planner's plan.

The paper's core claim is that *where bytes move* decides performance on
tightly coupled systems; Schieffer et al. (PAPERS.md) show the failure
mode — transparent access makes unintended transfers silent.  This module
makes them loud, statically: given ``compiled.as_text()`` (post-SPMD, so
every quantity is per chip) and an :class:`ExpectedMovement` derived from
the placement policy, it checks

* **donation coverage** — every donation-compatible buffer (placement
  strategy is not STREAM) must appear in the module's
  ``input_output_alias`` header; a donated-but-unaliased buffer is a
  silent full-size copy per dispatch (``missed-donation``);
* **donation prohibition** — STREAM placements must *not* be aliased:
  the streaming window still reads the source after dispatch
  (``forbidden-donation``, the PR 3 rule);
* **host↔device budget** — total bytes crossing the host memory space
  (``S(5)`` layouts on ``copy``/``copy-start``) must stay within the
  policy's allowance — for serve decode, exactly one ``(B,)`` token
  vector per step (Fig. 17's once-per-token datapath)
  (``stray-host-transfer``);
* **byte plan** — per-role parameter bytes vs the planner's
  ``bytes_per_role`` within tolerance (``byte-plan-mismatch``, warning:
  planner estimates legitimately diverge from padded/sharded reality);
* **cache-sized copies** — a count, not a violation: instructions whose
  result is a KV-cache leaf's shape or one layer of it and that copy or
  slice it (``cache_sized_copies``).  Each moves a whole layer or stack
  of the cache per dispatch; the decode step should hold none.  In-place
  row updates are not counted.

Violations carry the op, bytes, tier edge, and the planner term they
break, so a CI failure reads like a planner line item, not a grep hit.
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Any, Iterable, Iterator, Mapping

from repro.core.hlo_analysis import (
    AliasPair,
    Computation,
    HloAnalyzer,
    Instruction,
    TransferStat,
    entry_parameters,
    parse_input_output_alias,
)
from repro.core.placement import Role


class DonationAliasError(RuntimeError):
    """A donation the policy requires did not materialize (or one it
    forbids did).  Raised at Executor build time so the cost is a clear
    error, not a silent extra copy on every dispatch."""


# ---------------------------------------------------------------------------
# Expectations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RoleExpectation:
    """What the policy says about one jit argument (planner role)."""

    role: str                     # planner role name, e.g. "kv_cache"
    arg_root: str                 # jax arg-path root in HLO metadata, e.g. "caches"
    donate: bool                  # donation-compatible => must alias
    planner_term: str = "hbm"     # predict() term pricing this movement
    plan_bytes: float | None = None   # planner's per-step byte plan, if priced
    tolerance: float = 0.5        # relative tolerance for plan_bytes


@dataclasses.dataclass(frozen=True)
class ExpectedMovement:
    """The policy-derived movement contract for one compiled executable."""

    roles: tuple[RoleExpectation, ...] = ()
    #: host↔device byte allowance per dispatch (serve decode: one (B,)
    #: token vector; 0 for fully device-resident steps)
    host_bytes_allowed: float = 0.0
    label: str = ""

    def role_for_root(self, root: str) -> RoleExpectation | None:
        for r in self.roles:
            if r.arg_root == root:
                return r
        return None


# ---------------------------------------------------------------------------
# Violations / report
# ---------------------------------------------------------------------------

#: gate-failing violation kinds (severity "error")
ERROR_KINDS = ("missed-donation", "forbidden-donation", "stray-host-transfer")


@dataclasses.dataclass(frozen=True)
class AuditViolation:
    kind: str          # one of ERROR_KINDS or "byte-plan-mismatch"
    severity: str      # "error" | "warning"
    op: str            # HLO instruction / parameter the violation anchors to
    nbytes: float      # bytes at stake, per dispatch
    tier_edge: str     # the datapath edge being (mis)used, e.g. "host<->hbm"
    planner_term: str  # which predict() term the movement breaks
    detail: str

    def to_json(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class AuditReport:
    """Everything the audit observed, plus the diff against expectations."""

    label: str
    violations: list[AuditViolation]
    transfers: list[TransferStat]
    aliases: list[AliasPair]
    #: observed entry-parameter bytes per planner role
    role_bytes: dict[str, float]
    host_transfer_bytes: float
    donation_expected: int
    donation_materialized: int
    #: copies, slices and update-slices of a whole cache leaf or layer
    cache_sized_copies: int = 0

    @property
    def ok(self) -> bool:
        return not any(v.severity == "error" for v in self.violations)

    @property
    def donation_coverage(self) -> float:
        """Fraction of donation-required buffers that actually aliased."""
        if self.donation_expected == 0:
            return 1.0
        return self.donation_materialized / self.donation_expected

    def to_json(self) -> dict[str, Any]:
        return {
            "label": self.label,
            "ok": self.ok,
            "violations": [v.to_json() for v in self.violations],
            "host_transfer_bytes": self.host_transfer_bytes,
            "donation_expected": self.donation_expected,
            "donation_materialized": self.donation_materialized,
            "donation_coverage": self.donation_coverage,
            "cache_sized_copies": self.cache_sized_copies,
            "role_bytes": dict(self.role_bytes),
            "n_transfers": len(self.transfers),
            "n_aliases": len(self.aliases),
        }

    def raise_on_donation_errors(self) -> None:
        bad = [
            v for v in self.violations
            if v.kind in ("missed-donation", "forbidden-donation")
        ]
        if bad:
            lines = "\n".join(f"  [{v.kind}] {v.op}: {v.detail}" for v in bad)
            raise DonationAliasError(
                f"{self.label or 'executable'}: donation contract not "
                f"honored by the compiled module "
                f"({self.donation_materialized}/{self.donation_expected} "
                f"aliased):\n{lines}"
            )


# ---------------------------------------------------------------------------
# Cache-sized copies
# ---------------------------------------------------------------------------

#: opcodes that materialise a second copy of what they read
COPY_OPCODES = frozenset({"copy", "dynamic-slice"})

#: instructions that name a buffer without producing one
PLUMBING = frozenset({"parameter", "get-tuple-element", "tuple"})

_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def _fused(ins) -> str | None:
    """The computation a fusion instruction calls (None for others)."""
    if ins.opcode != "fusion":
        return None
    m = _CALLS_RE.search(ins.attrs)
    return m.group(1) if m else None


def cache_sized_instructions(
    comps: Mapping[str, Computation],
    cache_shapes: Iterable[tuple[str, tuple[int, ...]]],
) -> Iterator[tuple[Instruction, int]]:
    """Every buffer the module materialises whose shape is one of the
    cache leaf shapes ``(dtype, dims)`` or one layer of one (``dims[1:]``,
    or with a leading 1), with the element count of that leaf's layer.

    Buffers are the results of instructions outside fused computations
    (a fusion's own result stands for its body), apart from parameters
    and tuple plumbing.  A kernel returning the caches it aliases returns
    a tuple, so it is not one."""
    layer = {}
    for dtype, dims in cache_shapes:
        n = math.prod(dims[1:])
        for d in (dims, dims[1:], (1, *dims[1:])):
            layer[(dtype, d)] = min(n, layer.get((dtype, d), n))
    fused = {
        f for c in comps.values() for i in c.instructions.values()
        if (f := _fused(i))
    }
    for name, comp in comps.items():
        if name in fused:
            continue
        for ins in comp.instructions.values():
            if ins.opcode in PLUMBING or ins.type_str.startswith("("):
                continue
            out = ins.shapes
            if len(out) == 1 and (out[0].dtype, out[0].dims) in layer:
                yield ins, layer[(out[0].dtype, out[0].dims)]


def _copies(comps: Mapping[str, Computation], ins, floor: int) -> bool:
    """Whether ``ins`` copies or slices out at least ``floor`` elements,
    itself or in its fused body."""
    if ins.opcode in COPY_OPCODES:
        out = ins.shapes
        return len(out) == 1 and out[0].numel >= floor
    f = _fused(ins)
    return f in comps and any(
        _copies(comps, i, floor) for i in comps[f].instructions.values()
    )


def count_cache_sized_copies(
    comps: Mapping[str, Computation],
    cache_shapes: Iterable[tuple[str, tuple[int, ...]]],
) -> int:
    """The :func:`cache_sized_instructions` that copy or dynamic-slice a
    cache leaf or a layer of one, themselves or fused: each moves a whole
    layer or stack of the cache per dispatch.  An update-slice or a
    scatter is not counted: XLA writes it into the operand's buffer in
    place, moving only the update.  A fusion around one counts only where
    its body also copies a layer or more (XLA's copy-then-update when the
    buffer cannot be reused)."""
    return sum(
        _copies(comps, ins, floor)
        for ins, floor in cache_sized_instructions(comps, cache_shapes)
    )


# ---------------------------------------------------------------------------
# The audit
# ---------------------------------------------------------------------------

def audit_hlo_text(
    text: str,
    expected: ExpectedMovement,
    mesh_axes: Mapping[str, int] | None = None,
) -> AuditReport:
    """Audit one compiled module's text against ``expected``."""
    analyzer = HloAnalyzer(text, mesh_axes)
    cost = analyzer.analyze()
    params = entry_parameters(text, analyzer.comps)
    aliases = parse_input_output_alias(text)
    aliased_params = {a.param_number for a in aliases}
    violations: list[AuditViolation] = []

    by_root: dict[str, list] = defaultdict(list)
    for p in params:
        by_root[p.arg_root].append(p)

    role_bytes: dict[str, float] = {}
    donation_expected = donation_materialized = 0
    for exp in expected.roles:
        leaves = by_root.get(exp.arg_root, [])
        observed = float(sum(p.nbytes for p in leaves))
        role_bytes[exp.role] = observed
        for p in leaves:
            label = f"parameter({p.number}) {p.op_name}".strip()
            if exp.donate:
                donation_expected += 1
                if p.number in aliased_params:
                    donation_materialized += 1
                else:
                    violations.append(AuditViolation(
                        kind="missed-donation",
                        severity="error",
                        op=label,
                        nbytes=float(p.nbytes),
                        tier_edge="hbm",
                        planner_term=exp.planner_term,
                        detail=(
                            f"role {exp.role!r} is donation-compatible but "
                            f"has no input_output_alias entry: every "
                            f"dispatch pays a silent {p.nbytes}-byte copy "
                            f"the planner never priced"
                        ),
                    ))
            elif p.number in aliased_params:
                violations.append(AuditViolation(
                    kind="forbidden-donation",
                    severity="error",
                    op=label,
                    nbytes=float(p.nbytes),
                    tier_edge="hbm",
                    planner_term=exp.planner_term,
                    detail=(
                        f"role {exp.role!r} has a STREAM placement — the "
                        f"window still reads the source after dispatch, so "
                        f"aliasing its buffer is a use-after-donate race"
                    ),
                ))
        if exp.plan_bytes is not None and exp.plan_bytes > 0:
            rel = abs(observed - exp.plan_bytes) / exp.plan_bytes
            if rel > exp.tolerance:
                violations.append(AuditViolation(
                    kind="byte-plan-mismatch",
                    severity="warning",
                    op=f"role:{exp.role}",
                    nbytes=observed,
                    tier_edge=exp.planner_term,
                    planner_term=exp.planner_term,
                    detail=(
                        f"planner prices {exp.plan_bytes:.0f} B/step for "
                        f"role {exp.role!r} but the compiled module holds "
                        f"{observed:.0f} B ({rel:.0%} off, tolerance "
                        f"{exp.tolerance:.0%})"
                    ),
                ))

    host_bytes = cost.host_transfer_bytes
    if host_bytes > expected.host_bytes_allowed:
        for t in cost.transfers:
            if not t.crosses_host:
                continue
            violations.append(AuditViolation(
                kind="stray-host-transfer",
                severity="error",
                op=f"{t.opcode} %{t.name}" + (f" ({t.op_name})" if t.op_name else ""),
                nbytes=t.nbytes,
                tier_edge="host<->hbm",
                planner_term="pcie",
                detail=(
                    f"host↔device traffic is {host_bytes:.0f} B/dispatch, "
                    f"over the policy allowance of "
                    f"{expected.host_bytes_allowed:.0f} B (Fig. 17: decode "
                    f"moves exactly one (B,) token vector per step)"
                ),
            ))

    cache_roots = {
        r.arg_root for r in expected.roles if r.role == Role.KV_CACHE.value
    }
    cache_shapes = {
        (sh.dtype, sh.dims)
        for p in params if p.arg_root in cache_roots for sh in p.shapes
    }
    return AuditReport(
        label=expected.label,
        violations=violations,
        transfers=cost.transfers,
        aliases=aliases,
        role_bytes=role_bytes,
        host_transfer_bytes=host_bytes,
        donation_expected=donation_expected,
        donation_materialized=donation_materialized,
        cache_sized_copies=count_cache_sized_copies(
            analyzer.comps, cache_shapes
        ),
    )


def audit_compiled(
    compiled: Any,
    expected: ExpectedMovement,
    mesh_axes: Mapping[str, int] | None = None,
) -> AuditReport:
    """Audit a jax ``Compiled`` (or anything with ``as_text()``)."""
    return audit_hlo_text(compiled.as_text(), expected, mesh_axes)
