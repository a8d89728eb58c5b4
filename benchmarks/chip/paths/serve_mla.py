"""The serving path for a DeepSeek-V3-style configuration (latent attention,
one chip's share of the experts): ``paths/serve.py`` as it is, with the
width check of such a configuration file in place of its dense one, and
its own check of the served tokens.

The check compares at least ``SAMPLE_REQUESTS`` finished requests (the one
with the most served tokens, then others in an order drawn from the seed,
until ``serve.SAMPLE_TOKENS`` tokens are reached) with the reference, as
``serve.py`` does.  It judges ``logit_gap_mean``: the mean, over every
served token of the sample, of how far its reference logit lies below the
reference's best at that position.  The widest such gap is logged and
kept in the counters (``logit_gap_max``), not judged: a router's top-k is
a discontinuous function, and where bfloat16 activations move a score
across the k-th largest a held expert's part comes or goes, at a single
token, in either precision.  The mean weighs how often the served token
is not the reference's best and by how much, which the precision sets.

The run's counters also get ``moe``: the engine's expert counters
(``InferenceEngine.stats()["moe"]``, per phase ``moe_assignments``,
``moe_local_assignments``, ``moe_experts_touched`` and ``steps``) over the
window, read as it opens and as it closes.
"""

from __future__ import annotations

import dataclasses
import weakref

import numpy as np

import harness

SAMPLE_REQUESTS = 4     # requests the check compares, at least


def program_config(c: dict):
    """The program's config for ``c``, with every width, the routing and
    the rope scaling checked against the configuration file."""
    from repro.configs import get
    p = c["program"]
    cfg = dataclasses.replace(get(p["registry"]), **p["overrides"])
    rs = c["rope_scaling"]
    if (rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]
            or not c["norm_topk_prob"] or c["n_group"] != 1
            or c["topk_group"] != 1 or c["moe_layer_freq"] != 1):
        raise ValueError("the program computes YaRN with mscale equal to "
                         "mscale_all_dim, normalized top-k weights and one "
                         "expert group, every layer after the dense ones "
                         "an expert layer")
    want = {"attn_kind": "mla", "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "q_lora_rank": c["q_lora_rank"],
            "kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_head_dim": c["qk_nope_head_dim"],
            "qk_rope_head_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"], "d_ff": c["intermediate_size"],
            "moe_d_ff": c["moe_intermediate_size"],
            "n_experts": c["published"]["n_routed_experts"],
            "n_experts_held": c["n_routed_experts"],
            "expert_offset": c["expert_offset"],
            "top_k": c["num_experts_per_tok"],
            "n_shared_experts": c["n_shared_experts"],
            "first_k_dense": c["first_k_dense_replace"],
            "router_score": c["scoring_func"],
            "router_bias": c["topk_method"] == "noaux_tc",
            "routed_scale": c["routed_scaling_factor"],
            "vocab": c["vocab_size"], "n_layers": c["num_hidden_layers"],
            "rope_theta": c["rope_theta"], "rope_factor": rs["factor"],
            "rope_original_len": rs["original_max_position_embeddings"],
            "rope_beta_fast": rs["beta_fast"],
            "rope_beta_slow": rs["beta_slow"],
            "rope_mscale_all_dim": rs["mscale_all_dim"],
            "act": c["hidden_act"],
            "tie_embeddings": c["tie_word_embeddings"]}
    got = {k: getattr(cfg, k, None) for k in want}
    if got != want:
        raise ValueError(f"program config {p['registry']} differs from the "
                         f"configuration file: {got} != {want}")
    return cfg


def _sample_of(serve):
    """``serve._sample`` with at least ``SAMPLE_REQUESTS`` requests: its
    seeded order, cut where both its token count and that are reached."""
    tokens, ordered = serve.SAMPLE_TOKENS, serve._sample
    serve.SAMPLE_TOKENS = float("inf")      # the order, up to SAMPLE_MAX

    def sample(reqs, outcomes, seed: int):
        out, n = [], 0
        for r in ordered(reqs, outcomes, seed):
            if n >= tokens and len(out) >= SAMPLE_REQUESTS:
                break
            out.append(r)
            n += len(r.tokens)
        return out
    return sample


def _judge(run, gaps: list, limits: dict, log) -> None:
    """Replace the run's checks by ``logit_gap_mean`` of the program's
    gaps (the first list ``serve.run`` computed) and its control's (the
    second, where the run has a control)."""
    def checks(g):
        return harness.judge({"logit_gap_mean": float(np.mean(g))
                              if len(g) else float("inf")}, limits)
    if gaps:
        run.counters["logit_gap_max"] = float(gaps[0].max())
        log(f"per-token logit gaps of {len(gaps[0])} tokens: mean "
            f"{gaps[0].mean():.6g}, widest {gaps[0].max():.6g}, "
            f"{int((gaps[0] > 0).sum())} above 0")
    run.checks = checks(gaps[0] if gaps else np.zeros(0))
    run.correct = (harness.checks_ok(run.checks)
                   and run.counters.get("failed", 1) == 0
                   and run.counters.get("sent", 0) > 0)
    if run.control is not None and len(gaps) > 1:
        cchecks = checks(gaps[1])
        run.control = {"checks": cchecks,
                       "correct": harness.checks_ok(cchecks)}
        log(f"control's per-token gaps: mean {gaps[1].mean():.6g}, widest "
            f"{gaps[1].max():.6g}, {int((gaps[1] > 0).sum())} above 0")


def _delta(a: dict, b: dict) -> dict:
    return {phase: {k: b[phase][k] - a[phase][k] for k in a[phase]}
            for phase in a}


def run(ctx) -> "harness.Run":
    serve = harness.load_module("paths", "serve", ctx.cell.root)
    serve.program_config = program_config
    serve._sample = _sample_of(serve)
    gaps, logit_gaps = [], serve.logit_gaps

    def kept_gaps(ref_logits, served):
        gaps.append(logit_gaps(ref_logits, served))
        return gaps[-1]

    serve.logit_gaps = kept_gaps
    engine, edges = [], []
    outer_hook = ctx.hook

    def hook(eng):
        engine.append(weakref.ref(eng))     # the run frees it; keep no ref
        if outer_hook is not None:
            outer_hook(eng)

    def at_edge(edge):
        def call():
            eng = engine[0]() if engine else None
            if eng is not None and len(edges) < 2:
                edges.append(eng.stats().get("moe"))
            return edge()
        return call

    ctx.hook = hook
    ctx.open_window = at_edge(ctx.open_window)
    ctx.close_window = at_edge(ctx.close_window)
    run = serve.run(ctx)
    _judge(run, gaps, ctx.cell.limits, ctx.log)
    if len(edges) == 2 and None not in edges:
        moe = _delta(*edges)
        run.counters["moe"] = moe
        d = moe["decode"]
        ctx.log(f"expert counters in the window: {moe}; held share of the "
                f"decode's routed slots "
                f"{d['moe_local_assignments'] / max(d['moe_assignments'], 1):.5f}")
    return run
