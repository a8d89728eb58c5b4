"""The latent-attention, expert-share cell (``serve.kimik2-s9e8.longout``)
at a size the CPU runs: its path serves through the engine and agrees with
``refs/mla_moe.py`` while the float8 control does not; the engine's expert
counters reach the run; the cost of its decode step; the scope reader on a
recorded trace; and its per-layer readers on a run whose scope times are
given."""

import json
import pathlib

import numpy as np
import pytest

import cost
import cost_mla
import harness
import scopes
from conftest import cell_from_files

HERE = pathlib.Path(__file__).resolve().parent
CELL = "serve.kimik2-s9e8.longout"


def tiny_kimi_cell():
    """The cell with every width cut small: 16 experts of which this share
    holds 4 from the third on, top-4, one dense layer and two MoE layers;
    the same engine, path, traffic generator and reference."""
    cell = cell_from_files("kimik2-s9e8", "longout", CELL)
    c = cell.config
    c.update({"hidden_size": 64, "intermediate_size": 128,
              "moe_intermediate_size": 32, "num_attention_heads": 4,
              "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16,
              "num_hidden_layers": 3, "vocab_size": 256,
              "n_routed_experts": 4, "expert_offset": 2,
              "num_experts_per_tok": 4})
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["program"]["overrides"] = {
        "n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
        "d_ff": 128, "moe_d_ff": 32, "vocab": 256, "q_lora_rank": 32,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_experts": 16, "n_experts_held": 4,
        "expert_offset": 2, "top_k": 4, "q_block": 32, "kv_block": 32}
    cell.traffic.update({
        "prompt": {"ladder": [8, 16, 24], "median": 12, "sigma": 0.5},
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "cache_len": 48, "max_batch": 4, "rate_per_s": 6.0})
    return cell


@pytest.fixture(scope="module")
def served():
    import jax
    cell = tiny_kimi_cell()
    cell.limits = {"logit_gap_mean": 0.01}
    ctx = harness.Ctx(jax, cell, 2 ** 31 + 11, 2.0, False, jax.devices(),
                      control=True, log=lambda m: None)
    return harness.load_module("paths", cell.path).run(ctx)


def test_the_path_agrees_with_the_reference_and_the_control_does_not(
        served):
    """The check judges the mean per-token gap over at least four
    requests; the widest gap is kept, not judged."""
    prog = served.checks["logit_gap_mean"]["value"]
    ctrl = served.control["checks"]["logit_gap_mean"]["value"]
    assert list(served.checks) == ["logit_gap_mean"]
    assert served.correct and prog <= 0.01, served.checks
    assert served.attempted == 12 and served.failed == 0
    assert served.control["correct"] is False
    assert ctrl >= 3 * prog
    assert served.counters["check_tokens"] >= 4 * 2
    assert served.counters["logit_gap_max"] >= prog


def test_the_check_samples_several_requests():
    """At least four requests, even when the longest alone has more
    served tokens than ``serve.SAMPLE_TOKENS``; the longest first."""
    import types
    serve = harness.load_module("paths", "serve")
    sample = harness.load_module("paths", "serve_mla")._sample_of(serve)
    from repro.serving import Request
    reqs = [types.SimpleNamespace(tokens=[0] * n)
            for n in (40, 2048, 300, 7, 90, 12)]
    outs = [Request(prompt=np.zeros(1, np.int32)) for _ in reqs]
    got = sample(reqs, outs, 2 ** 31 + 1)
    assert len(got) == 4 and got[0] is reqs[1]
    assert len({id(r) for r in got}) == 4
    assert sample(reqs[:2], outs[:2], 5) == [reqs[1], reqs[0]]


def test_the_engine_counts_what_the_held_experts_took(served):
    moe = served.counters["moe"]
    for phase in ("decode", "prefill"):
        d = moe[phase]
        assert d["steps"] > 0
        assert 0 < d["moe_local_assignments"] <= d["moe_assignments"]
        assert d["moe_experts_touched"] <= d["moe_local_assignments"]
        assert d["moe_experts_touched"] <= d["steps"] * 2 * 4
    d = moe["decode"]
    # every decode token routes 4 slots in each of the 2 expert layers
    assert d["moe_assignments"] == 4 * 2 * served.counters["decode_tokens"]
    # 4 of 16 experts held: about a quarter of the routed slots
    assert 0.1 < d["moe_local_assignments"] / d["moe_assignments"] < 0.45


def test_the_parent_program_config_check_refuses_gqa():
    """The registry's GQA reading of Kimi-K2 (what the program had before
    latent attention) fails the width check at once."""
    path = harness.load_module("paths", "serve_mla")
    c = harness.load_cell(CELL).config
    assert path.program_config(c).attn_kind == "mla"
    over = dict(c["program"]["overrides"], attn_kind="full")
    with pytest.raises(ValueError):
        path.program_config(dict(c, program=dict(c["program"],
                                                 overrides=over)))


def test_decode_step_cost_matches_the_parameter_count():
    c = harness.load_cell(CELL).config
    m = cost_mla.dims(c)
    # the file's per-layer counts less the two latent norms' scales
    assert m["mla"] == c["parameters"]["mla_per_layer"] - 1536 - 512
    assert m["expert"] * 8 == c["parameters"]["held_experts_per_layer"]
    assert cost_mla.latent_bytes_per_token(c) == \
        c["parameters"]["latent_cache_bytes_per_token"] == 10368
    f_all, b_all = cost_mla.decode_step(c, 64, 64 * 1000, 64 * 8 * 8 / 48,
                                        64.0)
    f_a, b_a = cost_mla.mla_decode(c, 64, 64 * 1000)
    f_m, b_m = cost_mla.moe_decode(c, 64, 64 * 8 * 8 / 48, 64.0)
    # every held expert touched: the step reads every weight but the
    # embedding table once, the cache at the live context, and writes its
    # new rows
    weights = (c["parameters"]["total"] - 9 * (2 * 7168 + 2048) - 7168
               - 8 * 384 - 7168 * 20480) * 2
    assert b_all == pytest.approx(weights + (64000 + 64) * 10368
                                  + 64 * 7168 * 2, rel=1e-6)
    assert b_all > b_a + b_m and f_all > f_a + f_m
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert cost.roofline_s(f_all, b_all, peak) == pytest.approx(
        b_all / 819e9)


def test_scopes_reads_op_names_from_a_recorded_trace():
    xp = str(HERE / "fixtures" / "decode_prefill.xplane.pb")
    pre = scopes.scope_ns(xp, "jit__prefill")
    dec = scopes.scope_ns(xp, "jit__decode")
    assert pre["dot_general"] > 0 and pre["jit(_prefill)"] > 0
    assert "jit(_prefill)" not in dec and dec["jit(_decode)"] > 0
    assert scopes.scope_ns(xp, "jit__no_such_program") == {}


def test_the_readers_divide_least_time_by_scope_time(monkeypatch):
    import types
    cell = harness.load_cell(CELL)
    runs = [types.SimpleNamespace(start=i * 30e6, dur=20e6, fingerprint="1")
            for i in range(10)]
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(programs={"jit__decode": runs}),
        counters={"decode_tokens": 320, "kv_positions": 320 * 900,
                  "moe": {"decode": {"moe_assignments": 320 * 64,
                                     "moe_local_assignments": 427,
                                     "moe_experts_touched": 300,
                                     "steps": 10}}})
    monkeypatch.setattr(scopes, "of_run", lambda r, c, p: {
        "mla": 10 * 4e6, "moe": 10 * 9e6})
    peaks = json.loads((HERE.parent / "peaks.json").read_text())["TPU v5 lite"]
    got = {n: harness.load_module("metrics", n).read(run, cell, peaks)
           for n in ("mla_roofline.kimik2", "moe_roofline.kimik2",
                     "decode_mfu.kimik2")}
    c = cell.config
    want_mla = cost.roofline_s(*cost_mla.mla_decode(c, 32, 32 * 900),
                               peaks) / 4e-3
    want_moe = cost.roofline_s(*cost_mla.moe_decode(c, 32, 42.7, 30),
                               peaks) / 9e-3
    want_step = cost.roofline_s(*cost_mla.decode_step(c, 32, 32 * 900,
                                                      42.7, 30),
                                peaks) / 20e-3
    assert got["mla_roofline.kimik2"] == pytest.approx(100 * want_mla)
    assert got["moe_roofline.kimik2"] == pytest.approx(100 * want_moe)
    assert got["decode_mfu.kimik2"] == pytest.approx(100 * want_step)
    assert all(0 < v < 100 for v in got.values())
    untraced = types.SimpleNamespace(trace=None, counters=run.counters)
    for n in got:
        assert harness.load_module("metrics", n).read(
            untraced, cell, peaks) is None


def test_the_mix_sends_long_answers_and_fits_its_slots():
    import traffic as T
    m = T.load("longout")
    rs = T.serve_requests(m, 2 ** 31 + 3, 50.0, 20480)
    out = np.array([r["max_new"] for r in rs])
    assert np.median(out) == pytest.approx(640, rel=0.1)
    assert T.max_context(m) <= m["cache_len"]
    assert all(0 <= r["prompt"].max() < 20480 for r in rs)


def test_ties_finds_the_program_parting_from_the_reference_at_near_ties():
    """``ties.py`` on the small cell: it serves the seed's longest answers
    greedily with their held experts and lines them up with the
    reference's; where the two pick different held experts, a held
    expert's biased score lies within a tenth of the median distance from
    the k-th largest."""
    import ties
    cell = tiny_kimi_cell()
    out = ties.examine(cell, 2 ** 31 + 11, 3, 2.0, lambda m: None)
    assert [o["tokens"] for o in out] == [12, 11, 9]
    flipped = [o for o in out if o["tie_at_flips_max"] is not None]
    assert flipped
    for o in out:
        assert o["positions"] == o["prompt"] + o["tokens"] - 1
        assert 0 <= o["gap_mean"] <= o["gap_max"] < 0.06
        assert len(o["flips_per_layer"]) == 2
    for o in flipped:
        assert o["tie_at_flips_max"] < 0.1 * o["tie_median"], o
