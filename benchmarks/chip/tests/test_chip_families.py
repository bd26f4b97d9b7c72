"""What depends on the kind of model comes from ``families/<family>.py``:
a family written into a fresh home is loaded, built, counted and
referenced with no harness file changed; a missing one is named; the
dense family gives what the harness computed before; and a served run
hands its readers the program's counters and spans."""

import dataclasses
import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import counts, harness, reference, tracefile, weights
from benchmarks.chip.cell import load_cell, load_family

ROOT = Path(__file__).resolve().parents[3]
CONFIGS = ROOT / "benchmarks" / "chip" / "configs"
SEED = 2**31 + 1616
dense = load_family("dense")

#: a family the harness has never seen: a decoder whose every layer
#: attends to the last ``sliding_window`` positions only
WINDOWED = '''
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts as _counts
from benchmarks.chip import reference as ref
from benchmarks.chip.cell import load_family

fan_in = load_family("dense").fan_in


def arch_config(config):
    from repro.configs import ArchConfig, AttentionSpec

    m = config["model"]
    return ArchConfig(
        name="windowed", family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], layer_pattern="L", norm=m["norm"],
        tie_embeddings=m["tie_word_embeddings"], dtype=m["dtype"],
        attention=AttentionSpec(
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], d_head=m["head_dim"],
            rope_theta=float(m["rope_theta"]), window=m["sliding_window"]))


@dataclasses.dataclass(frozen=True)
class Counts(_counts.Model):
    window: int = 0

    def attn_flops(self, first, n):
        keys = sum(min(t + 1, self.window) for t in range(first, first + n))
        return 4 * self.n_layers * self.n_heads * self.d_head * keys


def counts(model):
    return Counts(**dataclasses.asdict(_counts.Model.from_config(model)),
                  window=model["sliding_window"])


def _layer(model, low, scope, lp, x):
    kind, eps, at = model["norm"], model["norm_eps"], lp["attn"]
    mm_low = low if scope == "all" else None
    h = ref.norm(x, lp["attn_norm"], kind, eps)
    q, k, v = (ref.mm("td,dhk->htk", h, at[w], mm_low)
               for w in ("w_q", "w_k", "w_v"))
    q, k = ref.rope(q, model["rope_theta"]), ref.rope(k, model["rope_theta"])
    if low is not None:
        q, k, v = (t.astype(low).astype(ref.F32) for t in (q, k, v))
    hkv, T, dh = k.shape
    s = jnp.einsum("hgqd,hkd->hgqk", q.reshape(hkv, -1, T, dh), k,
                   precision=ref.HIGHEST) / np.sqrt(dh)
    t = jnp.arange(T)
    back = t[:, None] - t[None, :]
    seen = (back >= 0) & (back < model["sliding_window"])
    s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("hgqk,hkd->hgqd", jax.nn.softmax(s, -1), v,
                   precision=ref.HIGHEST)
    x = x + ref.mm("htk,hkd->td", o.reshape(q.shape), at["w_o"], mm_low)
    h = ref.norm(x, lp["mlp_norm"], kind, eps)
    m = lp["mlp"]
    g = jax.nn.silu(ref.mm("td,df->tf", h, m["w_gate"], mm_low))
    u = ref.mm("td,df->tf", h, m["w_up"], mm_low)
    return x + ref.mm("tf,fd->td", g * u, m["w_down"], mm_low)


def forward(params, model, toks, low=None, scope="all"):
    x = params["embed"]["embedding"][jnp.asarray(toks)].astype(ref.F32)
    for stage in params["stages"]:
        for sub in stage.values():
            for r in range(jax.tree.leaves(sub)[0].shape[0]):
                lp = jax.tree.map(lambda w: w[r], sub)
                x = _layer(model, low, scope, lp, x)
    h = ref.norm(x, params["final_norm"], model["norm"], model["norm_eps"])
    return ref.mm("td,dv->tv", h, ref.head_weight(params),
                  low if scope == "all" else None)
'''


def write_home(home: Path, config: dict, mix: dict) -> dict:
    """A home holding one family, config and mix, and the BENCHMARK.json
    that names them."""
    for sub, name, text in (("families", "windowed.py", WINDOWED),
                            ("configs", "toy.json", json.dumps(config)),
                            ("traffic", "toy-mix.json", json.dumps(mix))):
        (home / sub).mkdir(exist_ok=True)
        (home / sub / name).write_text(text)
    return {"workloads": [{"name": "toy.mix", "config": "toy",
                           "traffic": "toy-mix", "chips": 1}],
            "end_to_end": [], "per_layer": []}


@pytest.fixture
def windowed_cell(tmp_path, tiny_config, tiny_cell):
    config = tiny_config("yi-6b")
    config["family"] = "windowed"
    config["model"]["sliding_window"] = 12
    bench = write_home(tmp_path, config, tiny_cell().traffic)
    return load_cell(bench, "toy.mix", home=tmp_path)


def test_a_family_from_new_files_alone(windowed_cell):
    from repro.models.reference import forward_logits

    cell, model = windowed_cell, windowed_cell.model
    cfg = cell.family.arch_config(cell.config)
    assert cfg.layer_codes() == "LL" and cfg.attention.window == 12
    # built through the program
    params, server = harness.build(cell, SEED)
    assert server.table.slots == [None] * cell.serve["batch_slots"]
    # counted: the window caps the keys a query attends to
    m, full = cell.family.counts(model), dense.counts(model)
    assert m.prefill_flops(12) == full.prefill_flops(12)
    assert m.prefill_flops(40) < full.prefill_flops(40)
    assert m.decode_flops(30) == full.decode_flops(11)
    assert m.decode_step_bytes([5, 30]) == full.decode_step_bytes([5, 30])
    # referenced: the family's reference is the program's windowed one
    seq = np.random.default_rng(3).integers(0, cfg.vocab, 40).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward_logits(params, jnp.asarray(seq[None]),
                                         cfg))[0, :-1]
    pos = np.array([5, 20, 38], np.int32)
    st = reference.position_stats(params, cell.family.forward, model, seq,
                                  64, pos, want[pos])
    np.testing.assert_allclose(st["best"], want.max(-1), rtol=1e-4,
                               atol=1e-4)
    assert st["err_max"].max() < 1e-3 * np.sqrt(st["ref_sumsq"].max())
    # the dense arithmetic, which sees every position, reads otherwise
    full_st = reference.position_stats(params, reference.forward, model,
                                       seq, 64, pos, want[pos])
    assert full_st["err_max"].max() > 1e-2 * np.sqrt(st["ref_sumsq"].max())


@pytest.mark.usefixtures("no_compile_cache")
def test_a_family_from_new_files_alone_serves_correctly(windowed_cell):
    # prompts of up to 20 tokens and replies of up to 8 pass the window
    line = harness.run(windowed_cell, SEED, 2.0, False,
                       started=time.perf_counter(), require_chip=False)
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["decode_rel_rms"]["value"] < 1e-3


def test_a_missing_family_is_named(tmp_path, tiny_config, tiny_cell):
    config = tiny_config("olmo-1b")
    config["family"] = "latent"
    bench = write_home(tmp_path, config, tiny_cell().traffic)
    want = re.escape(str(tmp_path / "families" / "latent.py"))
    with pytest.raises(FileNotFoundError, match=want):
        load_cell(bench, "toy.mix", home=tmp_path)


@pytest.mark.parametrize("arch", ["olmo-1b", "yi-6b"])
def test_the_dense_family_gives_the_configs_as_before(arch):
    from repro.configs import get_config

    config = json.loads((CONFIGS / f"{arch}.json").read_text())
    family = load_family(config["family"])
    assert family is dense
    m = config["model"]
    # the program's own config, but for the depth and head the file states
    assert family.arch_config(config) == dataclasses.replace(
        get_config(arch), n_layers=m["num_hidden_layers"],
        tie_embeddings=m["tie_word_embeddings"])
    assert family.counts(m) == counts.Model.from_config(m)
    assert family.fan_in("w_o", (16, 32, 128, 4096), True) == 32 * 128
    assert family.fan_in("w_q", (4096, 32, 128), False) == 4096


@pytest.mark.parametrize("arch", ["olmo-1b", "yi-6b"])
def test_the_dense_reference_through_the_family_is_the_same(tiny_config,
                                                            arch):
    config = tiny_config(arch)
    from repro.models.model_zoo import ModelBundle

    bundle = ModelBundle(dense.arch_config(config))
    shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, SEED, dense.fan_in)
    toks = np.random.default_rng(4).integers(
        0, bundle.cfg.vocab, 64).astype(np.int32)
    model = config["model"]
    for low, scope in ((None, "all"), (jnp.float8_e4m3fn, "qkv"),
                       (jnp.float8_e4m3fn, "all")):
        got = dense.forward(params, model, toks, low, scope)
        want = reference.forward(params, model, toks, low, scope)
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_dense_family_refuses_other_layers(tiny_config):
    config = tiny_config("olmo-1b")
    config["arch"] = "gemma3-27b"      # local and global layers
    with pytest.raises(NotImplementedError, match="full-attention"):
        dense.arch_config(config)
    params = {"stages": [{"0L": {}}]}
    with pytest.raises(NotImplementedError, match="full-attention"):
        dense.forward(params, config["model"], np.zeros(8, np.int32))


@pytest.mark.usefixtures("no_compile_cache")
def test_a_served_run_hands_readers_counters_and_spans(tiny_cell,
                                                       monkeypatch):
    """A traced run on the CPU: the readers get the counters' change over
    the window and the program's spans.  The CPU trace has no TPU plane,
    so one device operation spanning the trace stands in for the chip's."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = tiny_cell("olmo-1b")
    cell.per_layer = [m for m in bench["per_layer"]
                      if m["name"] == "host_turnaround_ms.offline"]
    monkeypatch.setattr(tracefile, "device_events", lambda data, n: (
        [("fusion.0", 0, 2**62)], [], ["stand-in"]))
    runs, read = [], harness.metric_reader

    def keep(name):
        def reader(run):
            runs.append(run)
            return read(name)(run)
        return reader

    monkeypatch.setattr(harness, "metric_reader", keep)
    line = harness.run(cell, SEED, 2.0, True, started=time.perf_counter(),
                       require_chip=False)
    assert line["correct"] is True, line["compared"]
    (run,) = runs
    win, got = run.window, run.window.counters
    assert got["prefill_dispatches"] > 0
    assert got["prefill_tokens"] == sum(k for _, k in win.prefills)
    s = cell.serve
    assert got["prefill_slot_tokens"] == (got["prefill_dispatches"]
                                          * s["batch_slots"]
                                          * s["prefill_chunk"])
    assert got["decode_steps"] == len(win.steps)
    assert got["decode_tokens"] == sum(len(f) for f in win.steps)
    names = {n for n, _, _ in run.trace.spans}
    assert {"serve.executor.decode.fetch",
            "serve.executor.decode.dispatch"} <= names
    assert all(n.startswith("serve.") for n in names)
    assert line["metrics"]["host_turnaround_ms.offline"]["value"] > 0
