"""The benchmark's float32 reference against the program's plain
reference at smoke size, and its control against the comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import check, reference, weights
from benchmarks.chip.cell import load_family

dense = load_family("dense")
forward = dense.forward


def make_model(tiny_config, arch, dtype="float32"):
    config = tiny_config(arch)
    config["model"]["dtype"] = dtype
    cfg = dense.arch_config(config)
    from repro.models.model_zoo import ModelBundle

    bundle = ModelBundle(cfg)
    shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    return (config["model"], cfg,
            weights.make_params(shapes, 2**33 + 5, dense.fan_in))


@pytest.mark.parametrize("arch", ["olmo-1b", "yi-6b"])
def test_reference_equals_the_programs_reference(tiny_config, arch):
    from repro.models.reference import forward_logits

    model, cfg, params = make_model(tiny_config, arch)
    assert ("unembed" in params["head"]) == (arch == "yi-6b")
    seq = np.random.default_rng(0).integers(0, cfg.vocab, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward_logits(params, jnp.asarray(seq[None]),
                                         cfg))[0, :-1]
    pos = np.array([3, 17, 38], np.int32)
    st = reference.position_stats(params, forward, model, seq, 64, pos,
                                  want[pos])
    np.testing.assert_allclose(st["best"], want.max(-1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        st["at"], want[np.arange(39), seq[1:]], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st["sumsq"], (want ** 2).sum(-1), rtol=1e-4)
    assert np.mean(st["first"] == want.argmax(-1)) > 0.9
    np.testing.assert_allclose(st["ref_sumsq"], (want[pos] ** 2).sum(-1),
                               rtol=1e-4)
    assert st["err_max"].max() < 1e-3 * np.sqrt(st["ref_sumsq"].max())


class Served:
    def __init__(self, rid, prompt, out):
        self.rid, self.prompt, self.out_tokens = rid, prompt, list(out)


def serve_exactly(params, model, prompt, n, length, chunk):
    """A request served with the reference's own greedy continuation and
    logits, tapped where the program's dispatches are: (tokens, taps)."""
    seq = list(prompt)
    for _ in range(n):
        first = reference.position_stats(
            params, forward, model, np.asarray(seq + [0], np.int32),
            length)["first"]
        seq.append(int(first[len(seq) - 1]))
    toks = np.zeros(length, np.int32)
    toks[: len(seq)] = seq
    logits = np.asarray(forward(params, model, toks))
    pre, dec = check.expected_positions(len(prompt), n, chunk)
    taps = [("prefill", p, 4, logits[p]) for p in pre]
    taps += [("decode", p, 4, logits[p]) for p in dec]
    return seq[len(prompt):], taps


@pytest.mark.parametrize("arch", ["olmo-1b", "yi-6b"])
def test_exact_tokens_pass_and_the_float8_control_fails(tiny_config, arch):
    model, cfg, params = make_model(tiny_config, arch, "bfloat16")
    limits = tiny_config(arch)["correct"]
    rng = np.random.default_rng(1)
    rows, got = [], {}
    for rid in range(4):
        prompt = rng.integers(0, cfg.vocab, 20).astype(np.int32)
        out, got[rid] = serve_exactly(params, model, prompt, 24, 64, 8)
        rows.append(Served(rid, prompt, out))
    for scope in ("qkv", "all"):
        res = check.compare(params, forward, model, rows, got, 64, 8,
                            low=jnp.float8_e4m3fn, scope=scope)
        assert res["tokens"] == 96 and res["dispatches"] == 4 * (3 + 24)
        assert res["dispatch_mismatch"] == 0
        for name in check.NUMBERS:
            assert res[name] < 1e-3 < limits[name], name
        # the control fails at least one number
        assert any(res[f"low_{n}"] > limits[n] for n in check.NUMBERS), res
    # an altered token reads beyond the token limit
    rows[0].out_tokens[3] = (rows[0].out_tokens[3] + 1) % cfg.vocab
    res = check.compare(params, forward, model, rows, got, 64, 8)
    assert res["token_gap"] > 2 * limits["token_gap"]
    # a dispatch missing, or tapped twice, is a mismatch
    got[1] = got[1][:-1] + got[1][:1]
    assert check.compare(params, forward, model, rows, got, 64, 8)[
        "dispatch_mismatch"] == 2


def test_watch_set_takes_the_longest_of_the_first_wave():
    from benchmarks.chip.traffic import Planned

    planned = [Planned(np.zeros(n, np.int32), 4, 0.0)
               for n in (5, 9, 30, 7, 8, 6, 100)]
    a = check.watch_set(planned, 2**31 + 3, 5, 3)
    assert len(a) == 3 and 2 in a and max(a) < 5
    assert a == check.watch_set(planned, 2**31 + 3, 5, 3)
    assert check.watch_set(planned, 1, 2, 8) == [0, 1]
