"""The serving path end to end at a size the CPU runs: the engine's timed
path, checked against the plain reference; the control (the reference in
float8) and a token altered where the engine produces it both come out as
not correct."""

import numpy as np

import harness
from conftest import tiny_serve_cell


def _run(cell, seed, control=False, hook=None):
    import jax
    ctx = harness.Ctx(jax, cell, seed, 2.0, False, jax.devices(),
                      control=control, hook=hook, log=lambda m: None)
    return harness.load_module("paths", cell.path).run(ctx)


def _small(cell):
    """Wide enough that float8 moves the greedy choice."""
    c = cell.config
    c.update({"hidden_size": 128, "intermediate_size": 256,
              "vocab_size": 2048})
    c["program"]["overrides"].update({"d_model": 128, "d_ff": 256,
                                      "vocab": 2048, "head_dim": 32})
    cell.traffic.update({
        "prompt": {"ladder": [16, 32], "median": 20, "sigma": 0.5},
        "output": {"median": 24, "sigma": 0.3, "min": 16, "max": 32},
        "cache_len": 64})
    return cell


def test_reference_matches_the_program_prefill():
    import jax
    import jax.numpy as jnp
    from repro.core.plan import single_device_plan
    from repro.models.lm import LM
    cell = _small(tiny_serve_cell())
    c = cell.config
    serve = harness.load_module("paths", "serve")
    ref = harness.load_module("refs", "dense_gqa")
    cfg = serve.program_config(c)
    import weights as W
    params = W.init_on_device(7, ref.layout(c), jnp.bfloat16)
    prompt = np.random.default_rng(0).integers(0, 2048, 40, dtype=np.int32)
    logits, _ = LM(cfg).prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                                single_device_plan(), cache_len=64)
    want = ref.logits_at(7, c, [prompt], [[39]])[0][0]
    got = np.asarray(logits[0, -1], np.float32)
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()
    assert np.argmax(got) == np.argmax(want)


def test_served_tokens_agree_with_the_reference():
    cell = tiny_serve_cell()
    cell.limits = {"logit_gap": 0.05}
    run = _run(cell, 2 ** 31 + 11)
    assert run.correct, run.checks
    assert run.checks["logit_gap"]["value"] <= 0.05
    assert run.attempted == 12 and run.failed == 0
    assert set(run.end_to_end) == {"ttft_p95_ms", "itl_p95_ms",
                                   "tokens_per_s"}


def test_the_float8_control_fails_the_check():
    cell = _small(tiny_serve_cell())
    cell.limits = {"logit_gap": 0.06}
    run = _run(cell, 5, control=True)
    prog = run.checks["logit_gap"]["value"]
    ctrl = run.control["checks"]["logit_gap"]["value"]
    assert run.correct and prog <= 0.06
    assert run.control["correct"] is False
    assert ctrl > 0.06 and ctrl >= 3 * prog


def test_a_token_altered_where_it_is_produced_is_caught():
    import jax.numpy as jnp

    def alter(eng):
        decode = eng._decode_node._decode

        def broken(p, caches, batch):
            nt, conf, caches = decode(p, caches, batch)
            return (nt + 1) % 256, conf, caches
        eng._decode_node._decode = broken

    cell = tiny_serve_cell()
    cell.limits = {"logit_gap": 0.05}
    run = _run(cell, 2 ** 31 + 11, hook=alter)
    assert not run.correct
    assert run.checks["logit_gap"]["value"] > 0.05

