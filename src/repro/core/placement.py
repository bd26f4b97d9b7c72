"""Placement policies: where every tensor role physically lives.

The paper's application studies (§IV) show that the *physical placement* of
each buffer — not just its sharding — decides performance, and that the
decision is per-role: GEMM source matrices care (reads dominate), the
destination does not; KV-type read-mostly buffers benefit from the big slow
pool only when the fast pool is full.

A :class:`PlacementPolicy` maps tensor roles to placements over the **full**
:class:`repro.core.hardware.MemoryTier` axis — local HBM, local host DRAM,
a peer chip's HBM / host DRAM over ICI, and a remote pod's HBM over DCN —
mirroring the paper's {HBM, DDR, HBM-p, DDR-p} columns (Figs. 5/7/9 and the
§IV application tables).  The planner (:mod:`repro.core.planner`) predicts
each policy's step time from the datapath bounds and picks the best that
fits every memory pool; the train/serve steps consume the chosen policy.

Physical realization on the runtime: JAX exposes ``NamedSharding(mesh,
spec, memory_kind=...)`` with kinds ``device`` (HBM), ``pinned_host`` and
``unpinned_host`` — the TPU analogue of the paper's Table II allocation
APIs (``numa_alloc_onnode`` ≈ explicit memory_kind; first-touch ≈ default
``device``).  Every kind the policy requests is passed through
:func:`resolve_memory_kind`, which raises for a kind the backend lacks
rather than placing the tensor somewhere else.

Peer and remote tiers are **executable**, not analysis-only: they are
realized on a *donor mesh axis* (see :mod:`repro.launch.mesh`).  A mesh
axis named :data:`DONOR_AXIS` (``"donor"``, an ICI axis) marks a group of
chips whose memory is donated to the computation — far-tier tensors are
sharded across that axis (each donor slice holds ``1/axis_size`` of the
bytes in its own pool, a hop away over the link, exactly the paper's HBM-p
placement), while every local-tier tensor ignores the axis and is
replicated over it.  :data:`REMOTE_DONOR_AXIS` (``"donor_pod"``) is the
same convention one interconnect further out: a donor group reached over
DCN, realizing :attr:`MemoryTier.REMOTE_HBM`.  ``PEER_HBM``/``REMOTE_HBM``
keep memory kind ``device`` (the bytes live in a peer's HBM);
``PEER_HOST`` pins to the donor's host DRAM.  :func:`put_like` and
:func:`repro.models.sharding.policy_specs` emit donor-extended specs;
:func:`validate_policy_for_mesh` refuses to realize a peer/remote policy
on a mesh without the required axis — a placement must never silently
degrade to ``hbm_resident`` (and then OOM where the planner predicted a
fit).  :class:`DonorStream` is the ``Strategy.STREAM`` datapath: per-layer
windows fetched from the donor slices into a double-buffered local staging
slot, overlapping the fetch of window ``i+1`` with the use of ``i``.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import re
from typing import Mapping

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.hardware import MemoryTier


class Role(str, enum.Enum):
    PARAMS = "params"            # model weights (read every step)
    MASTER = "master"            # f32 master copy of params (optimizer)
    OPT_STATE = "opt_state"      # Adam moments
    GRADS = "grads"              # gradient buffers
    ACTIVATIONS = "activations"  # step-local
    KV_CACHE = "kv_cache"        # decode-state, read-mostly, grows with seq
    INPUTS = "inputs"            # token batches


class Strategy(str, enum.Enum):
    RESIDENT = "resident"   # lives in its tier; computed on in place
    STREAM = "stream"       # lives in a far tier; bulk-moved each use
                            # (paper: "managed"-like — pay the migration,
                            #  then access at HBM speed)


#: memory_kind strings understood by jax shardings, per tier.  Peer and
#: remote HBM are device memory reached over ICI/DCN (donor-axis sharding);
#: peer host DRAM is pinned host memory on the donor's host.
_TIER_TO_KIND = {
    MemoryTier.HBM: "device",
    MemoryTier.HOST: "pinned_host",
    MemoryTier.PEER_HBM: "device",
    MemoryTier.PEER_HOST: "pinned_host",
    MemoryTier.REMOTE_HBM: "device",
}

#: canonical tier spellings for the placement string grammar (the names
#: configs/CLI use: ``--policy kv=host:stream,params=peer_hbm``), plus the
#: aliases accepted on input (the MemoryTier enum values and a few
#: paper-flavored spellings).
TIER_NAMES: dict[MemoryTier, str] = {
    MemoryTier.HBM: "hbm",
    MemoryTier.HOST: "host",
    MemoryTier.PEER_HBM: "peer_hbm",
    MemoryTier.PEER_HOST: "peer_host",
    MemoryTier.REMOTE_HBM: "remote_hbm",
}
_TIER_ALIASES: dict[str, MemoryTier] = {
    **{v: k for k, v in TIER_NAMES.items()},
    **{t.value: t for t in TIER_NAMES},   # enum values: hbm_p, host_p, ...
    "device": MemoryTier.HBM,
    "ddr": MemoryTier.HOST,
    "ddr_p": MemoryTier.PEER_HOST,
}

#: role spellings for the grammar: enum values plus short aliases.
_ROLE_ALIASES: dict[str, Role] = {
    **{r.value: r for r in Role},
    "kv": Role.KV_CACHE,
    "weights": Role.PARAMS,
    "opt": Role.OPT_STATE,
    "act": Role.ACTIVATIONS,
}


def parse_role(name: str | Role) -> Role:
    """Role from a grammar spelling (``kv``/``kv_cache``/``params``/...)."""
    if isinstance(name, Role):
        return name
    try:
        return _ROLE_ALIASES[name.strip().lower()]
    except KeyError:
        raise ValueError(
            f"unknown tensor role {name!r}; one of "
            f"{sorted(_ROLE_ALIASES)}"
        ) from None


def parse_tier(name: str | MemoryTier) -> MemoryTier:
    """MemoryTier from a grammar spelling (``hbm``/``peer_hbm``/...)."""
    if isinstance(name, MemoryTier):
        return name
    try:
        return _TIER_ALIASES[name.strip().lower()]
    except KeyError:
        raise ValueError(
            f"unknown memory tier {name!r}; one of "
            f"{sorted(set(_TIER_ALIASES))}"
        ) from None

#: tiers whose bytes live in a host DRAM pool (vs an HBM pool).
HOST_TIERS = frozenset({MemoryTier.HOST, MemoryTier.PEER_HOST})

#: tiers that live on another chip/host and need a donor mesh axis.
PEER_TIERS = frozenset({MemoryTier.PEER_HBM, MemoryTier.PEER_HOST})
REMOTE_TIERS = frozenset({MemoryTier.REMOTE_HBM})

#: donor mesh-axis convention (see module docstring + repro.launch.mesh):
#: an axis with this name groups the local slice with the memory-donor
#: slices; peer/remote-tier tensors are sharded across it.
DONOR_AXIS = "donor"
REMOTE_DONOR_AXIS = "donor_pod"

#: which donor axis realizes each far tier (ICI donors vs DCN donors).
TIER_DONOR_AXIS: dict[MemoryTier, str] = {
    MemoryTier.PEER_HBM: DONOR_AXIS,
    MemoryTier.PEER_HOST: DONOR_AXIS,
    MemoryTier.REMOTE_HBM: REMOTE_DONOR_AXIS,
}


class DonorAxisError(ValueError):
    """A placement needs a donor mesh axis the active mesh does not have."""


def _mesh_axes(mesh) -> dict[str, int]:
    return dict(mesh.shape) if mesh is not None else {}


def donor_axes_for(mesh, tier: MemoryTier) -> tuple[str, ...]:
    """Mesh axes that realize ``tier``'s donor placement (empty for local
    tiers).  Raises :class:`DonorAxisError` when ``tier`` needs a donor
    axis and ``mesh`` has none of (usable) size >= 2."""
    axis = TIER_DONOR_AXIS.get(tier)
    if axis is None:
        return ()
    if _mesh_axes(mesh).get(axis, 1) < 2:
        raise DonorAxisError(
            f"tier {tier} needs a {axis!r} mesh axis of size >= 2 to be "
            f"realized; mesh axes are {_mesh_axes(mesh) or None} (see "
            "repro.launch.mesh.make_donor_mesh)"
        )
    return (axis,)


def donor_allow_flags(mesh) -> dict[str, bool]:
    """``allow_*`` kwargs for :func:`repro.core.planner.plan`, derived
    from what this runtime can realize: host tiers need a distinct host
    memory space, peer tiers a :data:`DONOR_AXIS`, remote tiers a
    :data:`REMOTE_DONOR_AXIS`.  With ``mesh=None`` nothing non-local is
    realizable."""
    axes = _mesh_axes(mesh)
    return {
        "allow_host": host_available(),
        "allow_peer": axes.get(DONOR_AXIS, 1) > 1,
        "allow_remote": axes.get(REMOTE_DONOR_AXIS, 1) > 1,
    }


def validate_policy_for_mesh(policy: "PlacementPolicy", mesh) -> None:
    """Raise :class:`DonorAxisError` if ``policy`` places any role in a
    peer/remote tier the mesh cannot realize.  Realizers call this before
    ``device_put`` so a donor placement never silently lands in local
    memory."""
    for role, pl in policy.placements.items():
        try:
            donor_axes_for(mesh, pl.tier)
        except DonorAxisError as e:
            raise DonorAxisError(
                f"policy {policy.name!r} places {role.value} in {pl.tier}: {e}"
            ) from None


# ---------------------------------------------------------------------------
# Backend memory-kind capability
# ---------------------------------------------------------------------------

_KINDS_CACHE: frozenset[str] | None = None
_DEFAULT_KIND_CACHE: str | None = None


def available_memory_kinds() -> frozenset[str]:
    """Memory kinds the default backend's device 0 can address."""
    global _KINDS_CACHE
    if _KINDS_CACHE is None:
        _KINDS_CACHE = frozenset(
            m.kind for m in jax.devices()[0].addressable_memories()
        )
    return _KINDS_CACHE


def default_memory_kind() -> str:
    """The backend's default memory kind (``device`` on TPU)."""
    global _DEFAULT_KIND_CACHE
    if _DEFAULT_KIND_CACHE is None:
        _DEFAULT_KIND_CACHE = jax.devices()[0].default_memory().kind
    return _DEFAULT_KIND_CACHE


def resolve_memory_kind(kind: str | None) -> str | None:
    """Check a requested memory kind against what the backend exposes.

    ``None`` means "backend default" and always works.  A kind the
    backend lacks raises ``ValueError``: a host tier never lands silently
    in device memory.
    """
    if kind is None or kind in available_memory_kinds():
        return kind
    raise ValueError(
        f"memory kind {kind!r} is not exposed by this backend "
        f"({jax.devices()[0].platform}: {sorted(available_memory_kinds())})"
    )


def host_available() -> bool:
    """Does this backend expose a host memory space distinct from device
    memory?  False on CPU backends (host DRAM *is* the default memory), in
    which case offload policies are placement no-ops and the planner should
    not prefer them."""
    kinds = available_memory_kinds()
    default = default_memory_kind()
    return any(
        k.endswith("host") and k != default for k in kinds
    ) and default is not None and not default.endswith("host")


@dataclasses.dataclass(frozen=True)
class Placement:
    tier: MemoryTier = MemoryTier.HBM
    strategy: Strategy = Strategy.RESIDENT

    @property
    def raw_memory_kind(self) -> str:
        """The memory kind this tier wants, ignoring backend capability."""
        return _TIER_TO_KIND.get(self.tier, "device")

    @property
    def memory_kind(self) -> str | None:
        """The memory kind to actually hand to jax on this backend."""
        return resolve_memory_kind(self.raw_memory_kind)

    @property
    def on_host(self) -> bool:
        return self.tier in HOST_TIERS

    def to_str(self) -> str:
        """Grammar form: ``tier[:strategy]`` (``:resident`` is implied)."""
        tier = TIER_NAMES[self.tier]
        if self.strategy is Strategy.RESIDENT:
            return tier
        return f"{tier}:{self.strategy.value}"

    @classmethod
    def parse(cls, text: "str | Placement") -> "Placement":
        """Placement from ``tier[:strategy]`` (``host:stream``, ``peer_hbm``)."""
        if isinstance(text, Placement):
            return text
        tier_s, _, strat_s = text.partition(":")
        tier = parse_tier(tier_s)
        if not strat_s:
            return cls(tier, Strategy.RESIDENT)
        try:
            strategy = Strategy(strat_s.strip().lower())
        except ValueError:
            raise ValueError(
                f"unknown placement strategy {strat_s!r} in {text!r}; one "
                f"of {[s.value for s in Strategy]}"
            ) from None
        return cls(tier, strategy)


@dataclasses.dataclass(frozen=True)
class PlacementPolicy:
    """Named per-role placement map (the paper's 'allocation policy')."""

    name: str
    placements: Mapping[Role, Placement]
    description: str = ""

    def placement(self, role: Role) -> Placement:
        return self.placements.get(role, Placement())

    def memory_kind(self, role: Role) -> str | None:
        return self.placement(role).memory_kind

    def raw_memory_kind(self, role: Role) -> str:
        return self.placement(role).raw_memory_kind

    def tiers(self) -> frozenset[MemoryTier]:
        """Every tier this policy places at least one role in."""
        return frozenset(
            {MemoryTier.HBM} | {p.tier for p in self.placements.values()}
        )

    @property
    def uses_host(self) -> bool:
        return any(p.on_host for p in self.placements.values())

    def sharding(
        self, mesh: Mesh, spec: PartitionSpec, role: Role
    ) -> NamedSharding:
        return NamedSharding(mesh, spec, memory_kind=self.memory_kind(role))

    def with_placement(self, role: Role, placement: Placement) -> "PlacementPolicy":
        p = dict(self.placements)
        p[role] = placement
        return PlacementPolicy(self.name, p, self.description)

    def renamed(self, name: str, description: str | None = None) -> "PlacementPolicy":
        return PlacementPolicy(
            name, dict(self.placements),
            self.description if description is None else description,
        )

    # -- serialization ----------------------------------------------------
    def to_spec(self) -> str:
        """Compact grammar form: ``role=tier[:strategy],...`` (sorted,
        ``hbm``-resident roles omitted — they are the default)."""
        return ",".join(
            f"{role.value}={pl.to_str()}"
            for role, pl in sorted(
                self.placements.items(), key=lambda kv: kv[0].value
            )
            if pl != Placement()
        )

    def to_json(self, *, indent: int | None = None) -> str:
        """JSON form; :meth:`from_json` round-trips it exactly."""
        return json.dumps(
            {
                "name": self.name,
                "description": self.description,
                "placements": {
                    role.value: pl.to_str()
                    for role, pl in sorted(
                        self.placements.items(), key=lambda kv: kv[0].value
                    )
                },
            },
            indent=indent,
        )

    @classmethod
    def from_json(cls, data: "str | Mapping") -> "PlacementPolicy":
        """Inverse of :meth:`to_json`; also accepts the already-parsed
        dict form (configs embed it without re-stringifying)."""
        if isinstance(data, (str, bytes)):
            data = json.loads(data)
        if not isinstance(data, Mapping):
            raise ValueError(
                f"policy JSON must decode to an object, got {type(data)}"
            )
        placements = {
            parse_role(role): Placement.parse(pl)
            for role, pl in dict(data.get("placements", {})).items()
        }
        name = data.get("name") or _spec_name(placements)
        return cls(name, placements, data.get("description", ""))


def _spec_name(placements: Mapping[Role, Placement]) -> str:
    """Canonical derived name for an anonymous policy (stable across
    round-trips: sorted compact-grammar body)."""
    body = ",".join(
        f"{role.value}={pl.to_str()}"
        for role, pl in sorted(placements.items(), key=lambda kv: kv[0].value)
    )
    return f"custom({body or 'hbm_resident'})"


def policy(
    name: str | None = None,
    description: str = "",
    **role_placements: "str | Placement",
) -> PlacementPolicy:
    """Compositional policy constructor: placements as values, not names.

    Keyword names are role spellings (``kv``/``kv_cache``, ``params``,
    ``opt``/``opt_state``, ...), values are :class:`Placement` objects or
    grammar strings (``"host:stream"``, ``"peer_hbm"``)::

        policy(kv="host:stream", params="peer_hbm")

    Unnamed policies get a stable derived name so they serialize, log and
    register cleanly.
    """
    placements = {
        parse_role(role): Placement.parse(pl)
        for role, pl in role_placements.items()
    }
    return PlacementPolicy(name or _spec_name(placements), placements,
                           description)


class PolicyBuilder:
    """Incremental form of :func:`policy` for programmatic construction::

        p = (PolicyBuilder("serve_spill")
             .place("kv_cache", "host:stream")
             .place(Role.PARAMS, Placement(MemoryTier.PEER_HBM))
             .describe("KV spilled to host, params on the donor")
             .build())

    ``build(register=True)`` also publishes it to the registry.
    """

    def __init__(self, name: str | None = None):
        self._name = name
        self._description = ""
        self._placements: dict[Role, Placement] = {}

    def place(self, role: "str | Role", placement: "str | Placement") -> "PolicyBuilder":
        self._placements[parse_role(role)] = Placement.parse(placement)
        return self

    def describe(self, description: str) -> "PolicyBuilder":
        self._description = description
        return self

    def build(self, *, register: bool = False) -> PlacementPolicy:
        out = PlacementPolicy(
            self._name or _spec_name(self._placements),
            dict(self._placements),
            self._description,
        )
        if register:
            register_policy(out)
        return out


def parse_policy(text: "str | Mapping | PlacementPolicy") -> PlacementPolicy:
    """One entry point for every external policy spelling.

    Accepts, in order: a :class:`PlacementPolicy` (pass-through), a
    registered policy name (``"kv_host"``), a JSON object/string
    (:meth:`PlacementPolicy.from_json`), or the compact grammar
    (``"kv=host:stream,params=peer_hbm"``).  This is what ``--policy``
    flags and config files feed.
    """
    if isinstance(text, PlacementPolicy):
        return text
    if isinstance(text, Mapping):
        return PlacementPolicy.from_json(text)
    text = text.strip()
    if text in _REGISTRY:
        return _REGISTRY[text]
    if text.startswith("{"):
        return PlacementPolicy.from_json(text)
    if "=" not in text:
        raise ValueError(
            f"unknown policy {text!r}: not a registered name "
            f"({sorted(_REGISTRY)}), not JSON, and not the "
            "role=tier[:strategy][,...] grammar"
        )
    placements: dict[Role, Placement] = {}
    for part in text.split(","):
        if not part.strip():
            continue
        role_s, eq, pl_s = part.partition("=")
        if not eq:
            raise ValueError(
                f"bad policy fragment {part!r} in {text!r} "
                "(expected role=tier[:strategy])"
            )
        if role_s.strip().lower() == "pools":
            raise ValueError(
                f"policy spec {text!r} carries a 'pools=' directive; "
                "strip it with extract_pool_split() before parse_policy "
                "(only the disaggregated-serve entry points accept it)"
            )
        placements[parse_role(role_s)] = Placement.parse(pl_s)
    return PlacementPolicy(_spec_name(placements), placements,
                           "parsed from policy spec string")


# ---------------------------------------------------------------------------
# Pool-split grammar (disaggregated prefill/decode serving)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PoolSplit:
    """An explicit prefill/decode device split for a disaggregated
    cluster (``repro.serve.disagg``): ``prefill`` devices fill KV and
    publish handoff tickets, ``decode`` devices generate.  Parsed from
    the ``pools=prefill:N,decode:M`` grammar extension; ``None`` (no
    directive) means the planner's :func:`repro.core.planner.
    plan_pool_split` chooses the split."""

    prefill: int
    decode: int

    def __post_init__(self):
        if self.prefill < 1 or self.decode < 1:
            raise ValueError(
                f"pool split needs >= 1 device per pool, got "
                f"prefill:{self.prefill},decode:{self.decode}"
            )

    @property
    def total(self) -> int:
        return self.prefill + self.decode

    def to_str(self) -> str:
        return f"pools=prefill:{self.prefill},decode:{self.decode}"

    @classmethod
    def parse(cls, text: "str | PoolSplit") -> "PoolSplit":
        """PoolSplit from ``prefill:N,decode:M`` (either order; the
        ``pools=`` prefix is accepted and stripped)."""
        if isinstance(text, PoolSplit):
            return text
        body = text.strip()
        if body.lower().startswith("pools="):
            body = body[len("pools="):]
        counts: dict[str, int] = {}
        for frag in body.split(","):
            m = _POOL_FRAGMENT.match(frag)
            if not m:
                raise ValueError(
                    f"bad pool fragment {frag!r} in {text!r} "
                    "(expected pools=prefill:N,decode:M)"
                )
            pool, n = m.group(1), int(m.group(2))
            if pool in counts:
                raise ValueError(f"duplicate pool {pool!r} in {text!r}")
            counts[pool] = n
        if set(counts) != {"prefill", "decode"}:
            raise ValueError(
                f"pool split {text!r} must name both pools "
                "(pools=prefill:N,decode:M)"
            )
        return cls(counts["prefill"], counts["decode"])


_POOL_FRAGMENT = re.compile(r"^\s*(prefill|decode)\s*:\s*(\d+)\s*$")


def extract_pool_split(
    text: "str | Mapping | PlacementPolicy | None",
) -> "tuple[PoolSplit | None, str | Mapping | PlacementPolicy | None]":
    """Split a ``pools=prefill:N,decode:M`` directive out of a policy spec.

    The pools directive rides inside the same ``--policy`` string as the
    role grammar (``"kv=remote_hbm,pools=prefill:1,decode:3"``) but its
    *value* contains commas, so it must be carved out before
    :func:`parse_policy` splits on them.  Returns ``(split, remainder)``
    where ``remainder`` is the spec with the directive removed (``None``
    if nothing else remains) — non-string specs pass through untouched
    with ``split=None``.
    """
    if not isinstance(text, str) or "pools" not in text:
        return None, text
    parts = [p for p in text.split(",") if p.strip()]
    for i, part in enumerate(parts):
        role_s, eq, val = part.partition("=")
        if not (eq and role_s.strip().lower() == "pools"):
            continue
        frags = [val]
        j = i + 1
        while j < len(parts) and _POOL_FRAGMENT.match(parts[j]):
            frags.append(parts[j])
            j += 1
        split = PoolSplit.parse(",".join(frags))
        rest = ",".join(parts[:i] + parts[j:])
        return split, (rest if rest else None)
    return None, text


# ---------------------------------------------------------------------------
# Policy registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, PlacementPolicy] = {}


def register_policy(
    policy: PlacementPolicy, *, overwrite: bool = False
) -> PlacementPolicy:
    """Publish ``policy`` under its name.

    Registered policies show up everywhere the registry is enumerated:
    planner candidate sets, the placement sweep, the benchmark policy
    table, and every ``--policy <name>`` flag.  Re-registering a name is
    an error unless ``overwrite=True`` (a silent replacement would change
    what existing configs mean).
    """
    if not policy.name:
        raise ValueError("cannot register an unnamed policy")
    if policy.name in _REGISTRY and not overwrite:
        raise ValueError(
            f"policy {policy.name!r} is already registered; pass "
            "overwrite=True to replace it"
        )
    _REGISTRY[policy.name] = policy
    return policy


def get_policy(name: str) -> PlacementPolicy:
    """Registered policy by exact name (KeyError lists what exists)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no registered placement policy {name!r}; registered: "
            f"{sorted(_REGISTRY)}"
        ) from None


def registered_policies() -> dict[str, PlacementPolicy]:
    """Snapshot of the registry (insertion-ordered name -> policy)."""
    return dict(_REGISTRY)


def _policy(name: str, desc: str, **roles: Placement) -> PlacementPolicy:
    return register_policy(PlacementPolicy(
        name,
        {Role[k.upper()]: v for k, v in roles.items()},
        desc,
    ))


HBM = Placement(MemoryTier.HBM, Strategy.RESIDENT)
HOST = Placement(MemoryTier.HOST, Strategy.RESIDENT)
HOST_STREAM = Placement(MemoryTier.HOST, Strategy.STREAM)
PEER_HBM = Placement(MemoryTier.PEER_HBM, Strategy.RESIDENT)
PEER_HBM_STREAM = Placement(MemoryTier.PEER_HBM, Strategy.STREAM)
PEER_HOST_STREAM = Placement(MemoryTier.PEER_HOST, Strategy.STREAM)
REMOTE_HBM = Placement(MemoryTier.REMOTE_HBM, Strategy.RESIDENT)


#: Paper-faithful default: everything in fast memory ("local HBM" column of
#: every paper figure — the best-performing placement when it fits).
HBM_RESIDENT = _policy(
    "hbm_resident",
    "all tensors in device HBM (paper's local-HBM baseline)",
)

#: Optimizer-state offload: master weights + moments live in host DRAM and
#: are streamed through once per step (ZeRO-Offload-style).  Trades PCIe
#: bandwidth for ~12 bytes/param of HBM.
OPT_HOST = _policy(
    "opt_host",
    "Adam moments + f32 master in host DRAM, streamed once per step",
    master=HOST_STREAM,
    opt_state=HOST_STREAM,
)

#: KV cache on host, streamed per decode step (long-context serving when the
#: cache exceeds HBM; paper Fig. 17's DDR rows).
KV_HOST = _policy(
    "kv_host",
    "KV cache in host DRAM, streamed per decode step",
    kv_cache=HOST_STREAM,
)

#: Layer-wise weight streaming (serving models bigger than aggregate HBM;
#: paper Fig. 17 'weights on DDR').
WEIGHTS_STREAM = _policy(
    "weights_stream",
    "weights resident in host DRAM, streamed layer-by-layer",
    params=HOST_STREAM,
)

#: KV cache in a peer chip's HBM, read in place over ICI — the paper's
#: HBM-p column (peer HBM beats local DDR whenever the chip-to-chip link
#: outruns the host link, which it does on both GH200 and TPU).
KV_PEER_HBM = _policy(
    "kv_peer_hbm",
    "KV cache resident in a peer chip's HBM, read in place over ICI",
    kv_cache=PEER_HBM,
)

#: Weights streamed from a peer chip's HBM (Figs. 15-16: GEMM sources in
#: HBM-p) — the serving regime where a memory-donor chip holds the cold
#: layers and ships them over ICI ahead of use.
WEIGHTS_PEER_HBM = _policy(
    "weights_peer_hbm",
    "weights resident in peer HBM, streamed layer-by-layer over ICI",
    params=PEER_HBM_STREAM,
)

#: Optimizer state spilled to a *peer's* host DRAM (DDR-p column): the
#: escape hatch when local host DRAM is full — pays ICI+PCIe per step.
OPT_PEER_HOST = _policy(
    "opt_peer_host",
    "Adam moments + f32 master in a peer's host DRAM (spill-to-peer-host)",
    master=PEER_HOST_STREAM,
    opt_state=PEER_HOST_STREAM,
)

#: KV cache in a remote pod's HBM over DCN — the inter-node tier the paper
#: reaches once a node's four-superchip pool is exhausted.
KV_REMOTE_HBM = _policy(
    "kv_remote_hbm",
    "KV cache resident in a remote pod's HBM, read in place over DCN",
    kv_cache=REMOTE_HBM,
)

class _PoliciesView(Mapping):
    """Deprecated read-only live view of the policy registry.

    The old closed ``POLICIES`` dict, kept importable: reads forward to
    the registry (so policies registered later appear), writes raise.
    Every access path warns once per process, pointing at the
    replacement surface.
    """

    def _reg(self):
        _warn_deprecated(
            "POLICIES",
            "repro.core.placement.POLICIES is a deprecated read-only "
            "view; use registered_policies()/get_policy()/parse_policy() "
            "or the repro.api.Runtime facade",
        )
        return _REGISTRY

    def __getitem__(self, name):
        return self._reg()[name]

    def __iter__(self):
        return iter(self._reg())

    def __len__(self):
        return len(self._reg())

    def __contains__(self, name):
        return name in self._reg()

    def __setitem__(self, name, value):  # pragma: no cover - guard rail
        raise TypeError(
            "POLICIES is a read-only view; use register_policy() instead"
        )

    def __repr__(self):
        return f"POLICIES(deprecated view of {sorted(_REGISTRY)})"


_POLICIES_VIEW = _PoliciesView()


def _warn_deprecated(key: str, message: str) -> None:
    from repro.analysis.warnings_registry import warn_once

    warn_once(f"deprecated:{key}", message, DeprecationWarning, stacklevel=4)


def __getattr__(name: str):
    # PEP 562 deprecation shims: the names still resolve (external code
    # keeps working) but emit a single DeprecationWarning per process.
    if name == "POLICIES":
        return _POLICIES_VIEW  # the view warns on first *use*
    if name == "put_like":
        _warn_deprecated(
            "put_like",
            "repro.core.placement.put_like is deprecated; use "
            "repro.api.Runtime.realize (or Runtime.specs) instead",
        )
        return _put_like
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def put_donating(x, sharding, donate: bool):
    """``device_put`` that donates ``x`` only within one memory kind.

    The runtime refuses donation across memory kinds (a host <-> device
    move is a copy between two pools, never an alias), so a move that
    changes kind keeps its source buffer alive until it is dropped.
    """
    src = getattr(x, "sharding", None)
    same_kind = src is not None and (
        src.memory_kind or default_memory_kind()
    ) == (sharding.memory_kind or default_memory_kind())
    return jax.device_put(x, sharding, donate=donate and same_kind)


def _put_like(tree, mesh: Mesh, specs, role: Role, policy: PlacementPolicy,
              *, donate: bool = False):
    """device_put a pytree under the policy's placement for ``role``.

    ``specs`` is a matching pytree of PartitionSpecs (or a single spec).
    For peer/remote placements the spec of every leaf is extended over the
    tier's donor axis (validated first — a missing donor axis raises
    :class:`DonorAxisError` rather than silently landing locally).
    ``donate=True`` hands each source leaf to the transfer (the
    migration path: the old tier's buffer is freed as the copy lands).

    This is the array-level twin of the def-based realizer
    (``repro.models.sharding``) for trees without Param defs.  Lacking
    logical axis names, a STREAM placement targets the first divisible
    free dim — dim 0 of a stacked tree, i.e. the stack dim — where the
    def-based form targets the dim *labelled* ``layers``.
    """
    pl = policy.placement(role)
    donor = donor_axes_for(mesh, pl.tier)

    def _put(x, spec):
        if donor:
            from repro.models.sharding import donor_extend

            spec = donor_extend(
                spec, x.shape, mesh, donor,
                prefer_stack=pl.strategy is Strategy.STREAM,
            )
        return put_donating(
            x,
            NamedSharding(mesh, spec, memory_kind=policy.memory_kind(role)),
            donate,
        )

    if isinstance(specs, PartitionSpec):
        return jax.tree.map(lambda x: _put(x, specs), tree)
    return jax.tree.map(_put, tree, specs)


def to_device(tree, mesh: Mesh, specs):
    """Move a (possibly host-placed) pytree into HBM inside a jit region.

    This is the 'migration' step of a STREAM placement: under jit, XLA turns
    it into a host->device DMA that the latency-hiding scheduler can overlap
    with compute (the TPU analogue of managed-memory prefetch).
    """
    kind = resolve_memory_kind("device")

    def _mv(x, spec):
        return jax.device_put(
            x, NamedSharding(mesh, spec, memory_kind=kind)
        )

    if isinstance(specs, PartitionSpec):
        return jax.tree.map(lambda x: _mv(x, specs), tree)
    return jax.tree.map(_mv, tree, specs)


def to_host(tree, mesh: Mesh, specs):
    """Move a pytree to (pinned) host memory inside a jit region."""
    kind = resolve_memory_kind("pinned_host")

    def _mv(x, spec):
        return jax.device_put(
            x, NamedSharding(mesh, spec, memory_kind=kind)
        )

    if isinstance(specs, PartitionSpec):
        return jax.tree.map(lambda x: _mv(x, specs), tree)
    return jax.tree.map(_mv, tree, specs)


class DonorStream:
    """Double-buffered per-window streaming from a donor-resident stack.

    The executable form of ``Strategy.STREAM`` over a donor axis (the
    planner's ``copy_bound(PEER_*/REMOTE_*, HBM)`` datapath): ``tree``'s
    leaves are stacked along dim 0 into ``n_windows`` windows (layer-wise
    weight streaming stacks per-layer params) and live sharded across the
    donor slices; :meth:`window` returns window ``i`` device_put into the
    **local** sharding and immediately issues the (asynchronous) fetch of
    window ``i+1`` into the second staging slot, so the next fetch crosses
    the ICI/DCN path while the caller computes on window ``i``.  At most
    ``depth`` windows are held locally — the double-buffered staging
    footprint the planner charges against local HBM (``2 * bytes /
    stream_chunks``).
    """

    def __init__(self, tree, mesh: Mesh, specs, n_windows: int,
                 depth: int = 2):
        self._tree = tree
        self._mesh = mesh
        self._specs = specs
        self.n_windows = int(n_windows)
        self.depth = max(int(depth), 2)
        self._buf: dict[int, object] = {}
        self._kind = resolve_memory_kind("device")

    def _fetch(self, i: int):
        def mv(x, spec):
            return jax.device_put(
                x[i], NamedSharding(self._mesh, spec, memory_kind=self._kind)
            )

        if isinstance(self._specs, PartitionSpec):
            return jax.tree.map(lambda x: mv(x, self._specs), self._tree)
        return jax.tree.map(mv, self._tree, self._specs)

    def window(self, i: int):
        """Window ``i`` in local memory; prefetches the next ``depth - 1``
        windows behind it (``depth=2`` = classic double buffering)."""
        if not 0 <= i < self.n_windows:
            raise IndexError(f"window {i} of {self.n_windows}")
        keep = range(i, min(i + self.depth, self.n_windows))
        for j in keep:           # j == i first: the caller's window, then
            if j not in self._buf:     # the async prefetches behind it
                self._buf[j] = self._fetch(j)
        for k in [k for k in self._buf if k not in keep]:
            del self._buf[k]  # bound staging residency to `depth` windows
        return self._buf[i]

    def __iter__(self):
        for i in range(self.n_windows):
            yield self.window(i)
