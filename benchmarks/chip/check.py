"""Whether the timed path served the right logits and tokens.

Before the window, a handful of requests is chosen from the seed among
the first the window admits (they fill every slot together), with the
longest of them.  A tap on the executor (``Executor.logits_tap``)
keeps, for these requests only, the logits that every compiled prefill
and decode dispatch of the timed path produced for them.  Once the
window has closed, they are finished and the server is freed, each is
run through the float32 reference over prompt + served tokens, and
these numbers are compared:

- ``prefill_rel_rms`` / ``prefill_rel_max`` and ``decode_rel_rms`` /
  ``decode_rel_max``: the RMS and the largest magnitude of the served
  logits' error against the reference's at the same positions, in units
  of the reference logits' RMS (the arithmetic of ``chip_smoke.py``'s
  served-logits check), the worst request's;
- ``token_gap``: the widest gap, over every served token, between the
  reference's best logit and its logit of the served token, in the same
  units;
- ``dispatch_mismatch``: positions at which a watched request's
  dispatches should have produced logits and did not, or did twice.

The control (calibration only) is the reference computed in a lower
precision, read at the same positions in the same units.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

NUMBERS = ("prefill_rel_rms", "prefill_rel_max", "decode_rel_rms",
           "decode_rel_max", "token_gap")


def watch_set(planned, seed: int, slots: int, rows: int) -> list[int]:
    """Indices (the window's rids) of the requests the tap records: of
    the first ``slots`` due, the longest and others drawn from the
    seed, ``rows`` in all."""
    first = range(min(slots, len(planned)))
    longest = max(first, key=lambda k: (len(planned[k].prompt)
                                        + planned[k].max_new_tokens, -k))
    rest = [k for k in first if k != longest]
    rng = np.random.default_rng([seed, 1])
    return sorted([longest] + [rest[i] for i in
                               rng.permutation(len(rest))[: rows - 1]])


@jax.jit
def _gather(logits, idx):
    return jnp.take(logits, idx, axis=0).astype(jnp.float32)


class Tap:
    """``Executor.logits_tap`` that keeps the watched requests' rows.

    Each dispatch that serves a watched request adds one fixed-shape
    gather of ``rows`` logits rows on the device, copied to the host
    asynchronously and read at the next such dispatch, by when it has
    long arrived.  ``got[rid]`` lists ``(step, position, live slots,
    logits)``; a prefill chunk's logits are those of its last token, a
    decode step's those of the token fed."""

    def __init__(self, table, rids, rows: int):
        self.table, self.rids, self.rows = table, set(rids), rows
        self.got = {rid: [] for rid in rids}
        self._pending = []

    def __call__(self, step, logits, new_lens):
        table = self.table
        live = sum(s is not None for s in table.slots)
        picked = []
        for i, rid in enumerate(table.slots):
            if rid not in self.rids:
                continue
            if step == "prefill":
                if not new_lens[i]:
                    continue
                pos = int(table.lengths[i] + new_lens[i] - 1)
            elif table.active[i]:
                pos = int(table.lengths[i])
            else:
                continue
            picked.append((i, rid, step, pos, live))
        if not picked:
            return
        self.flush()
        idx = np.zeros(self.rows, np.int32)
        idx[: len(picked)] = [p[0] for p in picked]
        got = _gather(logits, idx)
        got.copy_to_host_async()
        self._pending.append((got, picked))

    def flush(self) -> None:
        for got, picked in self._pending:
            host = np.asarray(got)
            for j, (_, rid, step, pos, live) in enumerate(picked):
                self.got[rid].append((step, pos, live, host[j]))
        self._pending = []


def expected_positions(prompt_len: int, n_out: int, chunk: int):
    """(prefill, decode) positions at which a request's dispatches give
    logits: each chunk's last token, then every token fed."""
    n = prompt_len - 1
    pre = [min(lo + chunk, n) - 1 for lo in range(0, n, chunk)]
    return pre, list(range(n, n + n_out))


def _rel(err_sumsq, err_max, scale, vocab):
    """RMS and largest magnitude of the error, over ``scale``."""
    if not len(err_sumsq):
        return 0.0, 0.0
    rms = float(np.sqrt(err_sumsq.sum() / (len(err_sumsq) * vocab)))
    return rms / scale, float(err_max.max()) / scale


def compare(params, model: dict, requests, got: dict, length: int,
            chunk: int, low=None, scope: str = "all") -> dict:
    """The numbers of the module's docstring over ``requests`` (each
    with ``prompt``, ``out_tokens`` and ``rid``), their logits as
    ``Tap.got`` holds them; with ``low``, the control's as ``low_<name>``.
    Also ``tokens`` (served tokens compared), ``dispatches`` and
    ``live_min`` (the fewest slots live at a compared dispatch)."""
    worst = dict.fromkeys(NUMBERS, None)
    if low is not None:
        worst.update({f"low_{k}": None for k in NUMBERS})
    mismatch, n_tok, n_disp, live_min = 0, 0, 0, None

    def keep(name, value):
        if worst[name] is None or value > worst[name]:
            worst[name] = value

    for r in requests:
        out = np.asarray(r.out_tokens, np.int32)
        seq = np.concatenate([r.prompt, out])
        taps = got.get(r.rid, [])
        pre, dec = expected_positions(len(r.prompt), len(out), chunk)
        seen = sorted(p for _, p, _, _ in taps)
        mismatch += len(set(pre + dec) ^ set(seen)) + len(seen) - len(
            set(seen))
        if not taps or not len(out):
            continue
        pos = np.array([p for _, p, _, _ in taps], np.int32)
        served = np.stack([lg for _, _, _, lg in taps])
        is_pre = np.array([s == "prefill" for s, _, _, _ in taps])
        st = reference.position_stats(params, model, seq, length, pos,
                                      served, low=low, scope=scope)
        n_tok += len(out)
        n_disp += len(taps)
        lm = min(lv for _, _, lv, _ in taps)
        live_min = lm if live_min is None else min(live_min, lm)
        scale = float(np.sqrt(st["ref_sumsq"].mean() / served.shape[1]))
        at = slice(len(r.prompt) - 1, len(seq) - 1)
        tok_scale = float(np.sqrt(st["sumsq"][at].mean() / served.shape[1]))
        keep("token_gap", float((st["best"][at] - st["at"][at]).max())
             / tok_scale)
        prefixes = [("", "err")] + ([("low_", "low_err")] if low is not None
                                     else [])
        for name, key in prefixes:
            for phase, sel in (("prefill", is_pre), ("decode", ~is_pre)):
                rms, mx = _rel(st[f"{key}_sumsq"][sel], st[f"{key}_max"][sel],
                               scale, served.shape[1])
                keep(f"{name}{phase}_rel_rms", rms)
                keep(f"{name}{phase}_rel_max", mx)
        if low is not None:
            keep("low_token_gap", float((st["best"][at]
                                         - st["at_low"][at]).max())
                 / tok_scale)
    return dict(worst, dispatch_mismatch=mismatch, tokens=n_tok,
                dispatches=n_disp, live_min=live_min)
