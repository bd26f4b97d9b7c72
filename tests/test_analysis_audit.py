"""HLO data-movement audit tests: synthetic fixtures + compiled modules.

The synthetic HLO strings exist because CPU CI cannot *generate* ``S(5)``
host-memory-space layouts (the CPU backend only has ``unpinned_host``);
the parser and the audit are exercised on hand-written post-SPMD text,
while donation/aliasing — which CPU does materialize — is audited on real
compiled modules, for every registered placement policy (the donor-mesh
policies run on the forced-4-device CI leg).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_audit import (
    AuditViolation,
    DonationAliasError,
    ERROR_KINDS,
    ExpectedMovement,
    RoleExpectation,
    audit_compiled,
    audit_hlo_text,
    cache_sized_instructions,
    count_cache_sized_copies,
)
from repro.core.hlo_analysis import parse_hlo
from repro.core.placement import Role, registered_policies

# -- synthetic post-SPMD modules -------------------------------------------

CLEAN_DONATED = """\
HloModule clean, input_output_alias={ {0}: (1, {}, may-alias) }

ENTRY %main (p0: f32[16], p1: f32[64]) -> (f32[64]) {
  %p0 = f32[16]{0} parameter(0), metadata={op_name="p[\\'w\\']"}
  %p1 = f32[64]{0} parameter(1), metadata={op_name="caches[0]"}
  ROOT %t = (f32[64]{0}) tuple(%p1)
}
"""

NO_ALIAS = """\
HloModule no_alias

ENTRY %main (p0: f32[16], p1: f32[64]) -> (f32[64]) {
  %p0 = f32[16]{0} parameter(0), metadata={op_name="p[\\'w\\']"}
  %p1 = f32[64]{0} parameter(1), metadata={op_name="caches[0]"}
  ROOT %t = (f32[64]{0}) tuple(%p1)
}
"""

HOST_TRAFFIC = """\
HloModule host_traffic

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0), metadata={op_name="caches[0]"}
  %cs = (f32[1024]{0:S(5)}, f32[1024]{0}, u32[]) copy-start(%p0)
  ROOT %cd = f32[1024]{0:S(5)} copy-done(%cs)
}
"""

# a copy and a fused layer slice of the stacked cache count; the
# parameter, the in-place row write, the writer's custom call and the
# tuple do not
CACHE_COPIES = """\
HloModule cache_copies, input_output_alias={ {0}: (0, {}, may-alias) }

%fused_slice (param_0: bf16[4,2,8,16], param_1: s32[]) -> bf16[1,2,8,16] {
  %param_0 = bf16[4,2,8,16]{3,2,1,0} parameter(0)
  %param_1 = s32[] parameter(1)
  %zero = s32[] constant(0)
  ROOT %ds = bf16[1,2,8,16]{3,2,1,0} dynamic-slice(%param_0, %param_1, %zero, %zero, %zero), dynamic_slice_sizes={1,2,8,16}
}

%fused_row (param_0: bf16[4,2,8,16], param_1: bf16[1,1,1,16], param_2: s32[]) -> bf16[4,2,8,16] {
  %param_0 = bf16[4,2,8,16]{3,2,1,0} parameter(0)
  %param_1 = bf16[1,1,1,16]{3,2,1,0} parameter(1)
  %param_2 = s32[] parameter(2)
  %zero = s32[] constant(0)
  ROOT %dus = bf16[4,2,8,16]{3,2,1,0} dynamic-update-slice(%param_0, %param_1, %param_2, %zero, %zero, %zero)
}

ENTRY %main (p0: bf16[4,2,8,16], p1: s32[], p2: bf16[1,1,1,16]) -> (bf16[4,2,8,16], bf16[1,2,8,16], bf16[4,2,8,16], bf16[4,2,8,16]) {
  %p0 = bf16[4,2,8,16]{3,2,1,0} parameter(0), metadata={op_name="caches[0]"}
  %p1 = s32[] parameter(1), metadata={op_name="i"}
  %p2 = bf16[1,1,1,16]{3,2,1,0} parameter(2), metadata={op_name="row"}
  %relayout = bf16[4,2,8,16]{2,3,1,0} copy(%p0)
  %layer = bf16[1,2,8,16]{3,2,1,0} fusion(%p0, %p1), kind=kLoop, calls=%fused_slice
  %row_write = bf16[4,2,8,16]{3,2,1,0} fusion(%p0, %p2, %p1), kind=kLoop, calls=%fused_row
  %writer = bf16[4,2,8,16]{3,2,1,0} custom-call(%row_write, %p1), custom_call_target="tpu_custom_call"
  ROOT %t = (bf16[4,2,8,16]{3,2,1,0}, bf16[1,2,8,16]{3,2,1,0}, bf16[4,2,8,16]{2,3,1,0}, bf16[4,2,8,16]{3,2,1,0}) tuple(%writer, %layer, %relayout, %row_write)
}
"""


def _kv(donate, **kw):
    return ExpectedMovement(
        roles=(RoleExpectation("kv_cache", "caches", donate=donate),),
        label="test",
        **kw,
    )


class TestAuditHloText:
    def test_clean_module_passes(self):
        rep = audit_hlo_text(CLEAN_DONATED, _kv(donate=True))
        assert rep.ok and rep.violations == []
        assert rep.donation_expected == rep.donation_materialized == 1
        assert rep.donation_coverage == 1.0
        assert rep.role_bytes == {"kv_cache": 64 * 4}

    def test_missed_donation(self):
        rep = audit_hlo_text(NO_ALIAS, _kv(donate=True))
        assert not rep.ok
        (v,) = rep.violations
        assert v.kind == "missed-donation" and v.severity == "error"
        assert v.nbytes == 64 * 4 and "caches[0]" in v.op
        assert rep.donation_coverage == 0.0
        with pytest.raises(DonationAliasError, match="missed-donation"):
            rep.raise_on_donation_errors()

    def test_forbidden_donation(self):
        rep = audit_hlo_text(CLEAN_DONATED, _kv(donate=False))
        assert not rep.ok
        (v,) = rep.violations
        assert v.kind == "forbidden-donation"
        with pytest.raises(DonationAliasError, match="forbidden-donation"):
            rep.raise_on_donation_errors()

    def test_stray_host_transfer(self):
        rep = audit_hlo_text(HOST_TRAFFIC, _kv(donate=False))
        assert not rep.ok
        (v,) = rep.violations
        assert v.kind == "stray-host-transfer"
        assert v.tier_edge == "host<->hbm" and v.planner_term == "pcie"
        assert rep.host_transfer_bytes == 1024 * 4
        # stray transfers are not donation violations: this raise is about
        # aliasing only
        rep.raise_on_donation_errors()

    def test_host_allowance_admits_budgeted_traffic(self):
        rep = audit_hlo_text(
            HOST_TRAFFIC, _kv(donate=False, host_bytes_allowed=1024 * 4)
        )
        assert rep.ok and rep.host_transfer_bytes == 1024 * 4

    def test_byte_plan_mismatch_is_warning(self):
        exp = ExpectedMovement(
            roles=(RoleExpectation(
                "kv_cache", "caches", donate=True,
                plan_bytes=64 * 4 * 10, tolerance=0.5,
            ),),
            label="test",
        )
        rep = audit_hlo_text(CLEAN_DONATED, exp)
        (v,) = rep.violations
        assert v.kind == "byte-plan-mismatch" and v.severity == "warning"
        assert rep.ok  # warnings never fail the gate

    def test_byte_plan_within_tolerance_is_silent(self):
        exp = ExpectedMovement(
            roles=(RoleExpectation(
                "kv_cache", "caches", donate=True,
                plan_bytes=64 * 4 * 1.2, tolerance=0.5,
            ),),
            label="test",
        )
        assert audit_hlo_text(CLEAN_DONATED, exp).violations == []

    def test_unmentioned_roles_ignored(self):
        # p (params) has no expectation: its missing alias is not an error
        exp = ExpectedMovement(roles=(), label="test")
        rep = audit_hlo_text(NO_ALIAS, exp)
        assert rep.ok and rep.donation_expected == 0
        assert rep.donation_coverage == 1.0

    def test_cache_sized_copies_counted(self):
        rep = audit_hlo_text(CACHE_COPIES, _kv(donate=True))
        assert rep.cache_sized_copies == 2
        # every cache-sized buffer is seen; only the copy and the slice count
        shapes = [("bf16", (4, 2, 8, 16))]
        comps = parse_hlo(CACHE_COPIES)
        assert sorted(
            ins.name for ins, _ in cache_sized_instructions(comps, shapes)
        ) == ["layer", "relayout", "row_write", "writer"]
        assert count_cache_sized_copies(comps, shapes) == 2
        assert rep.to_json()["cache_sized_copies"] == 2
        # a report, never a violation
        assert rep.ok and rep.violations == []
        # only cache shapes count: without a cache role nothing does
        assert audit_hlo_text(
            CACHE_COPIES, ExpectedMovement(label="test")
        ).cache_sized_copies == 0

    def test_to_json_round_trips(self):
        import json

        rep = audit_hlo_text(NO_ALIAS, _kv(donate=True))
        blob = json.loads(json.dumps(rep.to_json()))
        assert blob["ok"] is False and blob["donation_coverage"] == 0.0
        assert blob["violations"][0]["kind"] == "missed-donation"
        assert set(ERROR_KINDS) == {
            "missed-donation", "forbidden-donation", "stray-host-transfer"
        }
        assert isinstance(
            AuditViolation(**blob["violations"][0]).to_json(), dict
        )


class TestAuditCompiled:
    def test_real_donated_jit(self):
        step = jax.jit(
            lambda caches, x: (caches + x, x),
            donate_argnums=(0,),  # repro: lint-disable=donate-without-out-shardings
        )
        compiled = step.lower(
            jax.ShapeDtypeStruct((128,), jnp.float32),
            jax.ShapeDtypeStruct((128,), jnp.float32),
        ).compile()
        exp = ExpectedMovement(
            roles=(RoleExpectation("kv_cache", "caches", donate=True),),
            label="real",
        )
        rep = audit_compiled(compiled, exp)
        assert rep.ok and rep.donation_coverage == 1.0
        assert rep.role_bytes["kv_cache"] == 128 * 4

    def test_real_undonated_jit_trips(self):
        step = jax.jit(lambda caches, x: (caches + x, x))
        compiled = step.lower(
            jax.ShapeDtypeStruct((128,), jnp.float32),
            jax.ShapeDtypeStruct((128,), jnp.float32),
        ).compile()
        exp = ExpectedMovement(
            roles=(RoleExpectation("kv_cache", "caches", donate=True),),
            label="real",
        )
        rep = audit_compiled(compiled, exp)
        assert not rep.ok
        assert rep.violations[0].kind == "missed-donation"


# ---------------------------------------------------------------------------
# Runtime.audit + the full Executor, for EVERY registered policy
# ---------------------------------------------------------------------------

def _policy_tiers(policy):
    return {p.tier.value for p in policy.placements.values()}


def _needs_donor(policy) -> bool:
    return bool(_policy_tiers(policy) & {"hbm_p", "host_p", "hbm_r"})


def _mesh_for(policy):
    """A mesh this policy validates on, or pytest.skip."""
    from repro.launch.mesh import make_donor_mesh

    if not _needs_donor(policy):
        return None  # single-device semantics; no realization needed
    if len(jax.devices()) < 4:
        pytest.skip("donor-tier policy needs the forced-4-device leg")
    remote = "hbm_r" in _policy_tiers(policy)
    return make_donor_mesh((2,), ("data",), donor_size=2, remote=remote)


@pytest.fixture(scope="module")
def smoke():
    from repro.models import get_smoke_bundle

    bundle = get_smoke_bundle("olmo-1b")
    params = bundle.init_params(jax.random.PRNGKey(0), "float32")
    return bundle, params


def test_smoke_server_decode_holds_no_cache_copies(smoke):
    """The decode step writes each K/V row in place: its compiled module
    holds no copy or slice of a cache layer, where the prefill step's
    xs/ys layer scan still slices each layer out and back."""
    from repro.serve import ServeConfig, Server

    bundle, params = smoke
    srv = Server(
        bundle, ServeConfig(batch_slots=2, max_len=32, prefill_chunk=4),
        params,
    )
    assert srv.stats()["decode_cache_copies"] == 0
    assert srv.engine.audit_reports["prefill"].cache_sized_copies > 0
    # the insert step's row write updates the cache in place: no copy
    assert srv.engine.audit_reports["insert"].cache_sized_copies == 0


@pytest.mark.parametrize("policy_name", sorted(registered_policies()))
class TestEveryRegisteredPolicy:
    def test_decode_step_movement_matches_plan(self, smoke, policy_name):
        """The acceptance sweep: build the serve Executor under each
        policy and diff the compiled decode step against the planner.

        * donation contract honored (coverage 1.0; STREAM never aliased);
        * zero host<->device bytes beyond the (B,) token-vector allowance;
        * observed KV bytes match ``decode_workload``'s byte plan within
          tolerance (exactly, on the 1-device mesh);
        * f32 test params are 2x the planner's bf16 pricing — flagged as
          a byte-plan-mismatch *warning*, never a gate failure.
        """
        from repro.models.model_zoo import ShapeSpec
        from repro.serve import Executor, ServeConfig

        bundle, params = smoke
        policy = registered_policies()[policy_name]
        mesh = _mesh_for(policy)
        cfg = ServeConfig(
            batch_slots=2, max_len=32, prefill_chunk=4, policy=policy_name
        )
        ex = Executor(bundle, cfg, params, mesh)

        # build-time audit ran (satellite: donation asserted at build,
        # not first dispatch) and found no movement-contract violations
        assert set(ex.audit_reports) >= {"decode", "prefill"}
        for name, rep in ex.audit_reports.items():
            assert rep.ok, (policy_name, name, rep.violations)
            assert rep.donation_coverage == 1.0

        donate = {"caches"} if ex.donates_cache else set()
        num_chips = 1 if mesh is None else mesh.devices.size
        wl = bundle.decode_workload(
            ShapeSpec(bundle.cfg.name, cfg.max_len, cfg.batch_slots,
                      "decode"),
            num_chips=num_chips,
        )
        rep = ex.rt.audit(
            ex._decode,
            {"p": Role.PARAMS, "caches": Role.KV_CACHE},
            donated=donate,
            host_bytes_allowed=3 * cfg.batch_slots * 4,
            workload=None if mesh is not None else wl,
        )
        assert rep.ok, (policy_name, rep.violations)
        assert rep.donation_coverage == 1.0
        assert rep.role_bytes["kv_cache"] > 0

        if mesh is None:
            # byte plan: KV exact; params 2x (f32 vs the planner's bf16
            # pricing) -> exactly one warning, for the params role
            plan = {r.value: v for r, v in wl.bytes_per_role.items()}
            assert rep.role_bytes["kv_cache"] == pytest.approx(
                plan["kv_cache"], rel=0.5
            )
            assert rep.role_bytes["params"] == pytest.approx(
                2 * plan["params"], rel=0.01
            )
            warns = [v for v in rep.violations
                     if v.kind == "byte-plan-mismatch"]
            assert [v.op for v in warns] == ["role:params"]

    def test_stream_policies_never_alias(self, smoke, policy_name):
        """STREAM placements must not donate — the compiled module's
        alias header must not cover the streamed role's parameters."""
        from repro.serve import Executor, ServeConfig

        bundle, params = smoke
        policy = registered_policies()[policy_name]
        if policy.placement(Role.KV_CACHE).strategy.value != "stream":
            pytest.skip("policy keeps KV resident")
        mesh = _mesh_for(policy)
        cfg = ServeConfig(
            batch_slots=2, max_len=32, prefill_chunk=4, policy=policy_name
        )
        ex = Executor(bundle, cfg, params, mesh)
        assert not ex.donates_cache
        rep = ex.audit_reports["decode"]
        # no alias entry may touch a caches[...] parameter
        from repro.core.hlo_analysis import entry_parameters

        text = ex._decode.as_text()
        aliased = {a.param_number for a in rep.aliases}
        cache_nums = {
            p.number for p in entry_parameters(text)
            if p.arg_root == "caches"
        }
        assert not (aliased & cache_nums)
