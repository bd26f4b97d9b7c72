"""Executor: the jitted device half of the serve stack.

The serve package is layered (see ``docs/serving.md``):

* :mod:`repro.serve.state` — slot/sequence host mirrors + device state,
  upload discipline;
* :mod:`repro.serve.sampling` — per-request temperature/top-k/top-p/
  seeds/stop tokens, computed in-jit;
* :mod:`repro.serve.scheduler` — the continuous-batching front end
  (request queue, admission ordering, streaming callbacks, planner-priced
  KV preemption) and the public :class:`~repro.serve.scheduler.Server`;
* this module — the **executor**: it owns the params, the KV cache, the
  :class:`repro.api.Runtime` (mesh + policy + planner), and every jitted
  dispatch.  Nothing here knows about requests or queues; it moves
  batches of tokens and cache rows.

The hot path keeps the zero-copy discipline of the Fig. 17 rework —
per decode step every parameter byte is read once, each cache layer is
read once by the attention kernel and only the new K/V rows are written
(the model's decode scan writes them in place; prefill still slices
each layer's cache out of its scan and back):

* **Donated caches** — decode/prefill jits donate the cache pytree
  (gated per policy by ``donation_compatible``; ``Strategy.STREAM``
  placements keep their far-tier resident buffer undonated), with
  ``Runtime.specs``-pinned ``out_shardings`` so donor/host placements
  survive the aliasing across steps.
* **Chunked batched prefill** — admission writes whole prompt chunks for
  all newly claimed slots per :meth:`ModelBundle.prefill_at` dispatch, so
  a batch of length-L prompts costs O(L / prefill_chunk) dispatches.
  Encoder-decoder bundles now take this path too
  (:func:`~repro.models.encdec.encdec_prefill_at`); only a bundle whose
  ``prefill_at`` raises ``NotImplementedError`` falls back to the O(B·L)
  decode-step replay — warned once and counted
  (``decode_replay_prefills``) instead of silent.
* **On-device serve state** — lengths/last-token/active *and the
  per-slot sampling parameters* live in a device state dict carried
  through the jitted step; sampling + stop detection happen in-jit, and
  the only per-step host↔device traffic is one packed ``(2, B)``
  next-token/stopped vector fetched back.
* **Slot extract/insert** — preemption's device half: one jitted
  ``dynamic_slice`` pulls a victim's cache rows out (then parked on the
  planner-priced spill tier), one jitted ``dynamic_update_slice`` puts
  them back on promotion.  Both preserve the pinned cache placement.

:meth:`Executor.replan` re-places the live cache/params mid-serve via
:meth:`repro.api.Runtime.migrate` and rebuilds the jits (donation flags
and pinned out_shardings are placement-dependent); decode output is
bit-identical across the move.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation as span
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import Runtime
from repro.core.faults import TransientFault
from repro.core.placement import (
    Placement,
    PlacementPolicy,
    Role,
    default_memory_kind,
    donor_axes_for,
    parse_policy,
)
from repro.models.sharding import use_sharding
from repro.runtime.retry import MIGRATION_RETRY, retry_call
from repro.serve import sampling as sampling_mod
from repro.serve.state import idle_device_state, upload

log = logging.getLogger("repro.serve.engine")


def _device_twin(tree):
    """Shardings of ``tree``'s leaves with host memory kinds swapped for
    device memory, or None when every leaf already lives on the device."""
    device = default_memory_kind()
    shardings = jax.tree.map(lambda x: x.sharding, tree)
    if all(
        s.memory_kind in (None, device) for s in jax.tree.leaves(shardings)
    ):
        return None
    return jax.tree.map(lambda s: s.with_memory_kind(device), shardings)


def _mover(shardings):
    """``device_put`` onto ``shardings`` (identity for None)."""
    if shardings is None:
        return lambda tree: tree
    return lambda tree: jax.device_put(tree, shardings)


class Executor:
    """Jitted decode/prefill/extract/insert steps over one model bundle.

    ``cfg`` is the scheduler's ``ServeConfig`` (duck-typed: only the
    shape/policy fields are read here).  The executor owns ``params``,
    ``caches`` and the :class:`repro.api.Runtime`; the scheduler owns
    requests, slots, and the device state dict it threads through
    :meth:`decode`.
    """

    def __init__(self, bundle, cfg, params, mesh=None):
        self.bundle = bundle
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        # The Runtime facade owns mesh + policy + planner.  A forced
        # peer/remote policy on a donor-less mesh raises DonorAxisError
        # here, up front, rather than serving from local HBM.
        if cfg.policy is not None:
            self.rt = Runtime(bundle, mesh, cfg.policy, rules=cfg.rules)
        else:
            self.rt = Runtime.auto(
                bundle, mesh, phase="serve", rules=cfg.rules,
                batch_slots=cfg.batch_slots, max_len=cfg.max_len,
                prefill_chunk=cfg.prefill_chunk,
            )
            log.info(
                "planner picked %s for %s (%d slots x %d ctx, prefill "
                "chunk %d)", self.rt.policy.name, bundle.cfg.name,
                cfg.batch_slots, cfg.max_len, cfg.prefill_chunk,
            )
        # injected-fault schedule (ServeConfig.faults): lives on the
        # Runtime so migrate()/realize() sites and the executor's
        # dispatch sites consult one plan; NO_FAULTS default costs one
        # truthiness test per site
        faults = getattr(cfg, "faults", None)
        if faults:
            self.rt.faults = faults
        self.caches = bundle.init_cache(cfg.batch_slots, cfg.max_len)
        if mesh is not None:
            # realize the policy for every role the executor owns: the KV
            # cache AND the params (weights_stream keeps params host-side;
            # kv_peer_hbm/weights_peer_hbm shard across the donor slices)
            self.caches = self.rt.realize(
                self.caches, Role.KV_CACHE, self._cache_defs()
            )
            self.params = self.rt.realize(self.params, Role.PARAMS)
        # slot extract/insert slice the batch axis; every cache family
        # stacks layers first, batch second — verify rather than assume
        for leaf in jax.tree.leaves(self.caches):
            if leaf.ndim < 2 or leaf.shape[1] != cfg.batch_slots:
                raise ValueError(
                    "cache leaf does not carry the batch on axis 1: "
                    f"shape {leaf.shape} with batch_slots="
                    f"{cfg.batch_slots}"
                )
        #: optional observer of each compiled step's (B, vocab) logits,
        #: called as ``tap(step, logits, new_lens)`` after every
        #: ``"decode"`` dispatch (``new_lens`` None) and every chunked
        #: ``"prefill"`` dispatch (``new_lens`` (B,): the tokens each row
        #: wrote; its logits are those of the last one), before the
        #: scheduler's slot table advances.  Checks read served logits
        #: through it; serving never sets it.
        self.logits_tap = None
        #: phase counters (tokens and wall seconds) + lifecycle events.
        #: ``decode_steps`` counts compiled decode dispatches (with the
        #: scheduler's ``decode_tokens``, live slot-steps: occupancy);
        #: ``prefill_dispatches`` chunked prefill dispatches, each
        #: ``batch_slots x prefill_chunk`` token positions wide
        #: (``prefill_slot_tokens``; with ``prefill_tokens``, the prompt
        #: tokens written: padding)
        self.counters = {
            "prefill_tokens": 0, "prefill_s": 0.0,
            "prefill_dispatches": 0, "prefill_slot_tokens": 0,
            "decode_tokens": 0, "decode_s": 0.0, "decode_steps": 0,
            "replans": 0, "migrations": 0,
            "decode_replay_prefills": 0,
            "spill_s": 0.0, "restore_s": 0.0,
            "migration_retries": 0, "evacuations": 0,
        }
        self._build_steps()

    @property
    def policy(self) -> PlacementPolicy:
        """The placement policy currently in force (may change across
        :meth:`replan` migrations)."""
        return self.rt.policy

    @property
    def donates_cache(self) -> bool:
        """Whether the decode/prefill jits donate the cache pytree under
        the current policy (RESIDENT yes, STREAM no)."""
        return self._donate_cache

    @property
    def supports_chunked_prefill(self) -> bool:
        return self._prefill is not None

    def hlo_text(self, step: str) -> str:
        """Compiled HLO of the ``"decode"`` or ``"prefill"`` step — the
        program each dispatch runs (e.g. whether it holds the Mosaic
        kernels)."""
        return {"decode": self._decode, "prefill": self._prefill}[
            step
        ].as_text()

    def _cache_defs(self):
        return self.bundle.cache_defs(self.cfg.batch_slots, self.cfg.max_len)

    def slot_bytes(self) -> int:
        """Resident bytes of one cache slot row — what a preemption spill
        moves (each way)."""
        return sum(
            leaf.nbytes // self.cfg.batch_slots
            for leaf in jax.tree.leaves(self.caches)
        )

    # -- jit construction --------------------------------------------------
    def _build_steps(self) -> None:
        """(Re)build the jitted steps for the current policy: donation
        flags and pinned cache out_shardings are placement-dependent, so
        :meth:`replan` calls this after a migration."""
        bundle, cfg = self.bundle, self.cfg
        cache_specs = (
            None if self.mesh is None
            else self.rt.specs(Role.KV_CACHE, self._cache_defs())
        )
        self._state_sharding = (
            None if self.mesh is None
            else NamedSharding(self.mesh, P())
        )
        # warm-up counter restarts with each jit build: the first step
        # after a (re)build is compile-dominated and must not feed the
        # runtime's measured-step calibration.  The EWMA itself lives on
        # the Runtime (keyed by shape + policy), so a replan migration
        # starts a fresh observation under the new policy's key while the
        # old policy's measurements survive a later flip back.
        self._steps_since_build = 0

        # STREAM placements (kv_host & co.) keep the resident cache buffer
        # undonated — it is the source of truth the next step's staged
        # migration reads.  Everything RESIDENT donates: the decode step
        # then updates KV in place, no per-token cache-sized allocation.
        self._donate_cache = self.rt.donate_ok(Role.KV_CACHE)
        log.info(
            "decode step %s the KV cache under policy %s",
            "donates" if self._donate_cache else "does NOT donate",
            self.policy.name,
        )

        # Host-placed roles (kv_host, weights_stream) are computed on in
        # device memory: the executor stages them in around each dispatch
        # and writes the returned cache back to its tier.
        params_dev = _device_twin(self.params)
        caches_dev = _device_twin(self.caches)
        self._params_in = _mover(params_dev)
        self._caches_in = _mover(caches_dev)
        self._repin = _mover(None if caches_dev is None else cache_specs)
        cache_out = cache_specs if caches_dev is None else caches_dev

        # the model traces under the executor's mesh and rules, so its
        # sharding constraints and the per-device kernel calls
        # (repro.kernels.ops) see the mesh the arrays live on, and split
        # a donor-sharded KV cache where it lives
        mesh, rules = self.mesh, cfg.rules
        kv_donor = () if mesh is None else donor_axes_for(
            mesh, self.policy.placement(Role.KV_CACHE).tier
        )

        def _step_fn(p, state, caches):
            with use_sharding(mesh, rules, kv_donor):
                logits, new_caches = bundle.decode_step(
                    p,
                    {"tokens": state["tokens"], "lengths": state["lengths"]},
                    caches,
                )
            # the sampler layer, in-jit: greedy rows (temp == 0) take the
            # plain argmax — bit-identical to the pre-sampler engine
            next_tok = sampling_mod.sample_tokens(logits, state)    # (B,)
            stopped = sampling_mod.hit_stop(next_tok, state["stop"])
            active = state["active"]
            new_state = dict(
                state,
                # inactive rows keep their token/length so idle slots and
                # freshly prefilled slots ride through untouched
                tokens=jnp.where(
                    active[:, None], next_tok[:, None], state["tokens"]
                ),
                lengths=state["lengths"] + active.astype(jnp.int32),
            )
            # one packed (2, B) vector back per step: next token + stop hit
            out = jnp.stack(
                [next_tok, (stopped & active).astype(jnp.int32)]
            )
            # the logits stay on the device unless a logits_tap reads them
            return out, new_state, new_caches, logits

        donate = (1, 2) if self._donate_cache else (1,)
        decode_jit = jax.jit(
            _step_fn,
            donate_argnums=donate,
            # pin the returned cache to its realized placement so a donor
            # or host placement survives across steps (and donation keeps
            # aliasing the same tier) instead of drifting to whatever
            # layout XLA prefers for the first output.  The state dict is
            # pinned replicated: several of its arrays (sampling params,
            # stop table) pass through unchanged, and a donated
            # pass-through must come back with the sharding it arrived
            # with (place_state) or aliasing fails.
            out_shardings=(
                None if cache_specs is None
                else (None, self._state_sharding, cache_out, None)
            ),
        )
        # Ahead-of-time: lower + compile against the live params/caches
        # and the canonical idle state NOW, so the donation contract is
        # checked at build time (not first dispatch), and reuse the
        # Compiled object AS the dispatch path — one compile, not two.
        # (.lower().compile() does not warm the jit dispatch cache, so
        # dispatching through the jit wrapper would recompile.)
        self._proto_state = self.place_state(
            idle_device_state(cfg.batch_slots)
        )
        self._decode = decode_jit.lower(
            self._params_in(self.params), self._proto_state,
            self._caches_in(self.caches),
        ).compile()

        # offset-chunk prefill, probed by capability rather than family:
        # encoder-decoder bundles chunk-prefill too now (their cross KV
        # is read-only during generation); only a bundle whose
        # prefill_at raises NotImplementedError falls back to the
        # decode-step replay admission.
        def _prefill_fn(p, batch, caches, offsets):
            with use_sharding(mesh, rules, kv_donor):
                return bundle.prefill_at(p, batch, caches, offsets)

        prefill_jit = jax.jit(
            _prefill_fn,
            donate_argnums=(2,) if self._donate_cache else (),
            out_shardings=(
                None if cache_specs is None else (None, cache_out)
            ),
        )
        chunk = max(int(cfg.prefill_chunk), 1)
        B = cfg.batch_slots
        proto_batch = self.place_state({
            "tokens": jnp.zeros((B, chunk), jnp.int32),
            "new_lens": jnp.zeros((B,), jnp.int32),
        })
        proto_offsets = self.place_state(jnp.zeros((B,), jnp.int32))
        try:
            self._prefill = prefill_jit.lower(
                self._params_in(self.params), proto_batch,
                self._caches_in(self.caches), proto_offsets,
            ).compile()
        except NotImplementedError:
            self._prefill = None

        # preemption's device half: one slot row out / back in.  Extract
        # must NOT donate (the cache lives on); insert donates like the
        # decode step and keeps the pinned placement.  Both stay lazy
        # jits: promoted rows arrive from whatever spill tier preemption
        # parked them on, so insert's input shardings vary per call and
        # an AOT executable would be too strict.
        self._extract = jax.jit(
            lambda caches, i: jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, i, 1, axis=1), caches
            ),
        )
        self._insert = jax.jit(
            lambda caches, rows, i: jax.tree.map(
                lambda x, r: lax.dynamic_update_slice_in_dim(
                    x, r, i, axis=1
                ),
                caches, rows,
            ),
            donate_argnums=(0,) if self._donate_cache else (),
            out_shardings=cache_out,
        )
        self._audit_builds()

    # -- build-time movement audit ----------------------------------------
    def _audit_builds(self) -> None:
        """Audit every donation path's compiled module at build time.

        The compiled text's ``input_output_alias`` header is the ground
        truth for whether ``donate_argnums`` materialized; a donation the
        policy requires that did NOT alias is a silent cache-sized copy
        per dispatch — raised here as
        :class:`repro.analysis.hlo_audit.DonationAliasError` (gated by
        ``cfg.verify_donation``).  Reports land in ``self.audit_reports``
        for ``tools/audit.py`` and the tests.
        """
        cfg = self.cfg
        arg_roles = {"p": Role.PARAMS, "caches": Role.KV_CACHE}
        donated = {"caches"} if self._donate_cache else set()
        # disaggregated clusters run one Executor per pool; the pool tag
        # keeps each pool's donation audit separately attributable
        pool = getattr(cfg, "pool", "")
        tag = f"{pool}:" if pool else ""
        # Fig. 17 allowance: one (B,1) token upload + one packed (2,B)
        # readback per step — nothing else may cross host<->device
        host_allow = 3 * cfg.batch_slots * 4
        self.audit_reports = {
            "decode": self.rt.audit(
                self._decode, arg_roles, donated=donated,
                host_bytes_allowed=host_allow,
                label=f"{tag}decode:{self.bundle.cfg.name}:{self.policy.name}",
            ),
        }
        if self._prefill is not None:
            self.audit_reports["prefill"] = self.rt.audit(
                self._prefill, arg_roles, donated=donated,
                host_bytes_allowed=host_allow,
                label=f"{tag}prefill:{self.bundle.cfg.name}:{self.policy.name}",
            )
        verify = getattr(cfg, "verify_donation", True)
        if verify and self._donate_cache:
            # the insert jit stays lazy (spill-tier inputs vary), so
            # verify its donation on a one-off compile against the
            # resident placement
            proto_rows = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    (x.shape[0], 1) + x.shape[2:], x.dtype
                ),
                self.caches,
            )
            insert_compiled = self._insert.lower(
                self._caches_in(self.caches), proto_rows, jnp.int32(0)
            ).compile()
            self.audit_reports["insert"] = self.rt.audit(
                insert_compiled, {"caches": Role.KV_CACHE},
                donated=donated, host_bytes_allowed=0.0,
                label=f"{tag}insert:{self.bundle.cfg.name}:{self.policy.name}",
            )
        if verify:
            for report in self.audit_reports.values():
                report.raise_on_donation_errors()

    def place_state(self, state: dict) -> dict:
        """Replicate a freshly uploaded state dict onto the mesh so the
        decode step's donated pass-through arrays alias cleanly (their
        pinned output sharding must match the input's)."""
        if self._state_sharding is None:
            return state
        return jax.device_put(state, self._state_sharding)

    # -- decode ------------------------------------------------------------
    def decode(self, state: dict) -> tuple[np.ndarray, np.ndarray, dict]:
        """One jitted decode step over every slot.

        Returns ``(next_tokens (B,), stopped (B,) bool, new_state)`` with
        the packed result fetched through a single async transfer — the
        only per-step host↔device traffic.
        """
        # pre-dispatch injection: the decode jit donates state + caches,
        # so a fault must fire before the call consumes the buffers — a
        # recovery path then sees intact pre-step state
        if self.rt.faults:
            self.rt.faults.check("decode")
        t0 = time.perf_counter()
        # profiler spans (no-ops unless a trace is recording): the
        # enqueue of the compiled step, the tap, the blocking fetch, and
        # the bookkeeping before the scheduler's next tick
        with span("serve.executor.decode.dispatch") as sp:
            if sp.is_enabled():
                sp.set_metadata(step=self.counters["decode_steps"])
            out, new_state, caches, logits = self._decode(
                self._params_in(self.params), state,
                self._caches_in(self.caches),
            )
            self.caches = self._repin(caches)
        if self.logits_tap is not None:
            with span("serve.executor.decode.tap"):
                self.logits_tap("decode", logits, None)
        with span("serve.executor.decode.fetch"):
            copy_async = getattr(out, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
            # the sanctioned once-per-step fetch: the packed (2, B) vector
            out_host = np.asarray(out)  # repro: lint-disable=blocking-transfer-in-hot-path
        dt = time.perf_counter() - t0
        with span("serve.executor.decode.observe"):
            self.counters["decode_s"] += dt
            self.counters["decode_steps"] += 1
            # each warm step updates the measured EWMA on the Runtime
            # behind rt.decode_step_seconds (the preemption ledger's wait
            # side).  The first step after a (re)build is
            # compile-dominated and skipped.
            self._steps_since_build += 1
            if self._steps_since_build > 1:
                self.rt.observe_decode_step(
                    self.cfg.batch_slots, self.cfg.max_len, dt
                )
        return out_host[0], out_host[1].astype(bool), new_state

    @property
    def measured_step_s(self) -> float | None:
        """EWMA of observed decode-step wall time under the current
        policy (None until the second step after a jit build feeds the
        runtime) — the wait-side price preemption uses via
        ``rt.decode_step_seconds``."""
        return self.rt.measured_step_s(
            self.cfg.batch_slots, self.cfg.max_len
        )

    # -- prefill (admission) ----------------------------------------------
    def prefill(self, new, table) -> None:
        """Write the newly claimed rows' prompts into the cache.

        ``new`` is ``[(slot, prompt ndarray), ...]``; ``table`` is the
        scheduler's :class:`~repro.serve.state.SlotTable`, whose
        ``lengths`` mirror advances as chunks land.  The last prompt
        token is withheld: the first decode step feeds it so its logits
        produce the first generated token.  Blocks on the dispatches so
        the prefill/decode split in the counters is honest.
        """
        if self.rt.faults:
            self.rt.faults.check("prefill")
        with span("serve.executor.prefill") as sp:
            if sp.is_enabled():
                sp.set_metadata(rows=len(new))
            t0 = time.perf_counter()
            if self._prefill is None:
                self._replay_prefill(new, table)
            else:
                self._chunked_prefill(new, table)
            with span("serve.executor.prefill.wait"):
                jax.block_until_ready(self.caches)
            self.counters["prefill_tokens"] += sum(
                len(prompt) - 1 for _, prompt in new
            )
            self.counters["prefill_s"] += time.perf_counter() - t0

    def _chunked_prefill(self, new, table) -> None:
        chunk = max(int(self.cfg.prefill_chunk), 1)
        lens = {i: len(prompt) - 1 for i, prompt in new}
        # at least one dispatch even when every prompt has length 1
        # (lens all 0): recurrent (SSM) state is cumulative and a freed
        # slot keeps integrating garbage while idle, so admission must
        # run prefill_at once for its offsets==0 zero-state reset even
        # with nothing to write.
        max_len = max(max(lens.values()), 1)
        B = self.cfg.batch_slots
        for k, lo in enumerate(range(0, max_len, chunk)):
            with span("serve.executor.prefill.chunk") as sp:
                if sp.is_enabled():
                    sp.set_metadata(chunk=k)
                toks = np.zeros((B, chunk), np.int32)
                new_lens = np.zeros(B, np.int32)
                for i, prompt in new:
                    n = int(np.clip(lens[i] - lo, 0, chunk))
                    if n > 0:
                        toks[i, :n] = prompt[lo : lo + n]
                        new_lens[i] = n
                logits, caches = self._prefill(
                    self._params_in(self.params),
                    # toks/new_lens are freshly built per chunk and never
                    # mutated after the handoff; lengths is a live mirror
                    # and goes through the race-safe upload copy.
                    # place_state commits them to the replicated sharding
                    # the AOT executable was lowered against.
                    self.place_state({
                        "tokens": jnp.asarray(toks),
                        "new_lens": jnp.asarray(new_lens),
                    }),
                    self._caches_in(self.caches),
                    self.place_state(upload(table.lengths, np.int32)),
                )
                self.caches = self._repin(caches)
                self.counters["prefill_dispatches"] += 1
                self.counters["prefill_slot_tokens"] += B * chunk
                if self.logits_tap is not None:
                    self.logits_tap("prefill", logits, new_lens)
                for i, _ in new:
                    table.lengths[i] += int(new_lens[i])

    def _replay_prefill(self, new, table) -> None:
        """Fallback admission for bundles whose ``prefill_at`` raises
        ``NotImplementedError``: replay each prompt token-by-token
        through the full-batch decode step — O(B·L) dispatches,
        correctness-only.  Warned once and counted so the slow path is
        visible."""
        from repro.analysis.warnings_registry import mark

        if mark(f"decode_replay:{self.bundle.cfg.name}"):
            log.warning(
                "%s has no chunked prefill (prefill_at raised "
                "NotImplementedError): admission falls back to O(B*L) "
                "decode-step replay — correctness-only; counted in "
                "stats()['decode_replay_prefills']",
                self.bundle.cfg.name,
            )
        self.counters["decode_replay_prefills"] += len(new)
        B = self.cfg.batch_slots

        def idle_state(toks):
            # rebuilt per dispatch from the canonical schema: the decode
            # jit donates the state, so these buffers are consumed by
            # each call
            return dict(
                idle_device_state(B),
                tokens=jnp.asarray(toks),
                lengths=upload(table.lengths, np.int32),
            )

        for i, prompt in new:
            for t in range(len(prompt) - 1):
                toks = np.zeros((B, 1), np.int32)
                toks[i, 0] = prompt[t]
                _, _, caches, _ = self._decode(
                    self._params_in(self.params),
                    self.place_state(idle_state(toks)),
                    self._caches_in(self.caches),
                )
                self.caches = self._repin(caches)
                table.lengths[i] += 1

    # -- preemption: slot spill / restore ---------------------------------
    def extract_slot(self, i: int, spill_to: Placement):
        """Pull slot ``i``'s cache rows out and park them on
        ``spill_to`` (the planner-priced spill tier).  Blocking — the
        rows are consistent when this returns.  Counted in ``spill_s``."""
        if self.rt.faults:
            self.rt.faults.check("extract")
        t0 = time.perf_counter()
        rows = self._extract(self._caches_in(self.caches), jnp.int32(i))
        if self.mesh is not None:
            park = self.rt.policy.with_placement(Role.KV_CACHE, spill_to)
            rows = self.rt.realize(
                rows, Role.KV_CACHE, specs=None, policy=park
            )
        jax.block_until_ready(rows)
        self.counters["spill_s"] += time.perf_counter() - t0
        return rows

    def insert_slot(self, i: int, rows) -> None:
        """Scatter parked rows back into slot ``i`` (promotion).  The
        insert jit donates the cache like the decode step and keeps the
        pinned placement, so the move is bit-preserving and in place.
        Rows parked in host DRAM are first brought to device memory: the
        insert is one on-device update."""
        t0 = time.perf_counter()
        device = default_memory_kind()
        rows = jax.tree.map(
            lambda r: jax.device_put(r, r.sharding.with_memory_kind(device)),
            rows,
        )
        self.caches = self._repin(
            self._insert(self._caches_in(self.caches), rows, jnp.int32(i))
        )
        jax.block_until_ready(self.caches)
        self.counters["restore_s"] += time.perf_counter() - t0

    # -- live re-placement -------------------------------------------------
    def replan(
        self, policy=None, *, force: bool = False, occupancy: float = 1.0,
        inflight=None,
    ) -> bool:
        """Re-place the live KV cache (and params) mid-serve.

        With ``policy=None``, re-runs the planner's combined serve
        pricing against the *current* cache occupancy (``occupancy``
        scales the KV bytes, so a near-empty cache prices like a
        near-empty cache); with an explicit ``policy`` (any
        ``parse_policy`` spelling), adopts it directly.  When the target
        differs from the policy in force, the KV cache and — if its
        placement changed — the params are migrated between tiers via
        :meth:`repro.api.Runtime.migrate` (donation-aware ``device_put``
        onto the new shardings; decode output is bit-identical across
        the move), and the jitted steps are rebuilt for the new donation
        flags and pinned out_shardings.  ``inflight`` is blocked on
        before the buffers move (the scheduler passes its device state).
        Returns True iff a migration happened.  No mesh -> nothing is
        realizable, always False.
        """
        if self.mesh is None:
            return False
        old = self.rt.policy
        self.counters["replans"] += 1
        if policy is None:
            self.rt.plan_phase(
                "serve",
                batch_slots=self.cfg.batch_slots,
                max_len=self.cfg.max_len,
                prefill_chunk=self.cfg.prefill_chunk,
                kv_utilization=occupancy,
                log_table=False,
            )
            target = self.rt.policy
        else:
            target = parse_policy(policy)
        # structural comparison, not names: a custom 'kv=host:stream' is
        # the same placement as the registered kv_host (no-op), while a
        # JSON policy reusing a registered name may carry new placements
        same = all(
            target.placement(r) == old.placement(r) for r in Role
        )
        if same and not force:
            self.rt.policy = old
            return False
        # drain in-flight dispatches against the old placement before the
        # buffers move out from under them
        jax.block_until_ready(
            (self.caches,) if inflight is None else (self.caches, inflight)
        )
        # plan_phase may have already adopted the target into rt.policy;
        # migrate_roles() owns the handover: it mutates the trees dict in
        # place as each role lands, and on partial failure sets rt.policy
        # to what the live buffers actually are.  Transient faults (link
        # hiccups, injected MigrationFault) are retried under the
        # migration budget — the retry re-reads the partial policy, so
        # only the unfinished roles move again.
        self.rt.policy = old
        trees = {Role.KV_CACHE: self.caches, Role.PARAMS: self.params}
        defs = {Role.KV_CACHE: self._cache_defs()}

        def _on_retry(attempt, err, delay):
            self.counters["migration_retries"] += 1

        try:
            moved = retry_call(
                lambda: self.rt.migrate_roles(
                    trees, target, defs, force=force
                ),
                retry_on=(TransientFault,),
                policy=MIGRATION_RETRY,
                label=f"replan {old.name}->{target.name}",
                seed=self.counters["replans"],
                on_retry=_on_retry,
            )
        except BaseException:
            # migrated roles' old buffers were donated (freed): adopt
            # whatever landed before re-raising, or the executor would
            # dispatch against dead buffers.  Rebuild the jits only if
            # something actually moved — a clean adopt-nothing failure
            # leaves the compiled steps valid as-is.
            self.caches = trees[Role.KV_CACHE]
            self.params = trees[Role.PARAMS]
            if self.rt.policy is not old:
                self._build_steps()
            raise
        self.caches = trees[Role.KV_CACHE]
        self.params = trees[Role.PARAMS]
        self._build_steps()
        self.counters["migrations"] += 1
        log.info(
            "replan: migrated %s -> %s (%s) at occupancy %.0f%%",
            old.name, target.name,
            ",".join(r.value for r in moved) or "forced no-op",
            100 * occupancy,
        )
        return True

    def evacuate(
        self, tier, *, occupancy: float = 1.0, inflight=None
    ) -> list[Role]:
        """Serve-side tier loss: drain in-flight work, delegate to
        :meth:`repro.api.Runtime.evacuate` (planner re-pick with the
        lost tier excluded, transient faults retried under the
        migration budget), adopt the moved trees and rebuild the jits.
        Returns the roles that moved."""
        if self.mesh is None:
            self.rt.mark_tier_lost(tier)
            return []
        old = self.rt.policy
        jax.block_until_ready(
            (self.caches,) if inflight is None else (self.caches, inflight)
        )
        trees = {Role.KV_CACHE: self.caches, Role.PARAMS: self.params}
        defs = {Role.KV_CACHE: self._cache_defs()}

        def _on_retry(attempt, err, delay):
            self.counters["migration_retries"] += 1

        try:
            _, moved = retry_call(
                lambda: self.rt.evacuate(
                    tier, trees, defs, phase="serve",
                    batch_slots=self.cfg.batch_slots,
                    max_len=self.cfg.max_len,
                    prefill_chunk=self.cfg.prefill_chunk,
                    kv_utilization=occupancy,
                ),
                retry_on=(TransientFault,),
                policy=MIGRATION_RETRY,
                label=f"evacuate {tier}",
                seed=self.counters["evacuations"],
                on_retry=_on_retry,
            )
        except BaseException:
            self.caches = trees[Role.KV_CACHE]
            self.params = trees[Role.PARAMS]
            if self.rt.policy is not old:
                self._build_steps()
            raise
        self.caches = trees[Role.KV_CACHE]
        self.params = trees[Role.PARAMS]
        self.counters["evacuations"] += 1
        if moved:
            self._build_steps()
            self.counters["migrations"] += 1
        return moved
