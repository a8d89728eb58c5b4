"""Latent attention and the expert layer that holds a share of the experts
(Kimi-K2's block, DeepSeek-V3's architecture) at a small Kimi-shaped size
on the CPU, against the plain float32 reference the chip benchmark uses
(``benchmarks/chip/refs/mla_moe.py``), on seeded random weights; and the
dense and Mixtral-shaped models' logits pinned to what they were before
either existed."""

import dataclasses
import hashlib
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core.plan import single_device_plan
from repro.models.lm import LM
from repro.runtime.steps import make_decode_step, make_prefill_step

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import weights as W  # noqa: E402

# the benchmark's configuration file, every width cut small; 16 experts of
# which this share holds 4 from the third on, top-4, one dense layer
SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
         "vocab_size": 256, "n_routed_experts": 4, "expert_offset": 2,
         "num_experts_per_tok": 4}
OVERRIDES = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
             "d_ff": 128, "moe_d_ff": 32, "vocab": 256, "q_lora_rank": 32,
             "kv_lora_rank": 32, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16, "n_experts": 16,
             "n_experts_held": 4, "expert_offset": 2, "top_k": 4,
             "q_block": 16, "kv_block": 16}
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def small():
    """(configuration file, program config, reference module, program
    weights made from the seed as the benchmark makes them)."""
    import json
    c = json.loads((BENCH / "configs" / "kimik2-s9e8.json").read_text())
    c.update(SMALL)
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["program"] = dict(c["program"], overrides=OVERRIDES)
    ref = harness.load_module("refs", "mla_moe", BENCH)
    cfg = harness.load_module("paths", "serve_mla", BENCH).program_config(c)
    W.check_layout(ref.layout(c), LM(cfg).param_defs())
    params = W.init_on_device(SEED, ref.layout(c), jnp.bfloat16)
    return c, cfg, ref, params


def test_prefill_then_decode_through_the_latent_cache_matches_reference(
        small):
    """Two prompts of different lengths prefilled, then 8 greedy decode
    steps each through the latent cache at per-slot positions: every
    step's logits against the reference's full forward over the same
    tokens.  Tolerance: the program holds activations and the cache in
    bfloat16 (about 3 significant digits) through 3 layers, the reference
    computes in float32.  A position where the logits part by more is one
    where the program's held experts differ from the reference's in an
    expert layer, at a near-tie of the router's biased scores: a held
    expert lies closer to the k-th largest there than a tenth of the
    median distance."""
    c, cfg, ref, params = small
    plan = single_device_plan()
    CL, steps, lens = 32, 8, [9, 13]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in lens]
    prefill = jax.jit(make_prefill_step(cfg, plan, CL, stats=True))
    pre = [prefill(params, {"tokens": jnp.asarray(p)[None]}) for p in prompts]
    caches = jax.tree.map(lambda *cs: jnp.concatenate(cs, axis=1),
                          *[cc for _, cc, _ in pre])
    tok = jnp.stack([jnp.argmax(lg[0, -1]) for lg, _, _ in pre])[:, None]
    got = [[np.asarray(lg[0, -1], np.float32)] for lg, _, _ in pre]
    # the program's held experts at every position: (expert layers, 1, held)
    hits = [[np.asarray(h["moe_hits"])] for _, _, h in pre]
    seqs = [list(p) for p in prompts]
    decode = jax.jit(make_decode_step(cfg, plan, CL, stats=True))
    for i in range(steps):
        for b in range(2):
            seqs[b].append(int(tok[b, 0]))
        pos = jnp.asarray([n + i for n in lens], jnp.int32)
        tok, lg, caches, st = decode(params, caches, {"token": tok.astype(
            jnp.int32), "pos": pos})
        for b in range(2):
            got[b].append(np.asarray(lg[b, -1], np.float32))
            hits[b].append(np.asarray(st["moe_hits"])[:, b:b + 1])
    want, routes = ref.logits_at(
        SEED, c, [np.asarray(s, np.int32) for s in seqs],
        [np.arange(n - 1, n + steps) for n in lens], routes=True)
    for g, w, h, (picked, tie), n in zip(got, want, hits, routes, lens):
        g = np.stack(g)
        err = np.abs(g - w).max(-1) / np.abs(w).max(-1)
        flips = (np.concatenate(h, 1).astype(bool) != picked).any(-1)
        near = tie < 0.1 * np.median(tie)
        assert not (flips & ~near).any(), (tie[flips], np.median(tie))
        # rows n-1 ... n+steps-1 of the sequence
        at_flip = flips[:, n - 1:].any(0)
        assert (err[~at_flip] < 0.03).all(), (err, at_flip)
        assert (err < 0.25).all(), err
        # and the served (greedy) token is the reference's best everywhere
        assert np.array_equal(g.argmax(-1), w.argmax(-1))


def test_absorbed_decode_equals_decompressed_attention(small):
    """In float32, attention of the last position computed from the latent
    cache with W_UK and W_UV absorbed equals the prefill's decompressed,
    per-head attention at that position."""
    from repro.models.mla import mla_attention
    _, cfg, _, params = small
    plan = single_device_plan()
    p = jax.tree.map(lambda t: t[0].astype(jnp.float32),
                     params["stacks"]["moe"]["attn"])
    S = 11
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    cfg = dataclasses.replace(cfg, cache_len=16)
    full, _ = mla_attention(x, p, cfg, plan, positions=pos, q_block=4,
                            kv_block=4)
    _, init = mla_attention(x[:, :S - 1], p, cfg, plan,
                            positions=pos[:, :S - 1], cache="init")
    stack = {"ckv": init["ckv"][None]}
    last, new = mla_attention(x[:, S - 1:], p, cfg, plan,
                              positions=pos[:, S - 1:], cache=stack,
                              cache_pos=jnp.asarray([S - 1, S - 1]),
                              layer=0)
    np.testing.assert_allclose(np.asarray(last[:, 0], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               rtol=2e-3, atol=2e-3)
    # the decode wrote exactly its own rows
    np.testing.assert_array_equal(np.asarray(new["ckv"][0, :, :S - 1]),
                                  np.asarray(init["ckv"][:, :S - 1]))


def test_yarn_frequencies_and_scale_at_the_published_widths(small):
    """Kimi-K2's rope (64 lanes, theta 50,000, YaRN factor 32 from 4,096,
    beta 1/1): the 20 fastest of the 32 pairs keep their frequency, the
    rest are divided by 32, and the softmax scale is 192^-1/2 times
    (0.1 ln 32 + 1)^2; the reference computes the same on its own."""
    from repro.models.mla import softmax_scale, yarn_freqs
    c, _, ref, _ = small
    cfg = get("kimi-k2-1t-a32b")
    plain = 1.0 / 50000.0 ** (np.arange(0, 64, 2) / 64)
    want = np.where(np.arange(32) < 20, plain, plain / 32)
    np.testing.assert_allclose(yarn_freqs(cfg), want, rtol=1e-6)
    assert softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    m = ref.dims(dict(c, qk_rope_head_dim=64, qk_nope_head_dim=128))
    freqs, scale = ref._yarn(m)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert scale == pytest.approx(softmax_scale(cfg))


def test_correction_bias_picks_experts_but_does_not_weigh_them():
    from repro.models.moe import _route
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (64, 16))
    wr = jax.random.normal(jax.random.fold_in(key, 1), (16, 12)) * 0.25
    bias = 0.2 * jax.random.normal(jax.random.fold_in(key, 2), (12,))
    _, w0, i0, _ = _route(x, wr, 4, "sigmoid", None, 2.5)
    _, w1, i1, _ = _route(x, wr, 4, "sigmoid", bias, 2.5)
    assert not np.array_equal(np.asarray(i0), np.asarray(i1))
    scores = jax.nn.sigmoid(x @ wr)
    for idx, w in ((i0, w0), (i1, w1)):
        picked = jnp.take_along_axis(scores, idx, -1)
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(2.5 * picked / picked.sum(-1,
                                                                 keepdims=True)),
            rtol=1e-6)
    # the picks are the top-4 of score + bias
    want = np.sort(np.asarray(jax.lax.top_k(scores + bias, 4)[1]), -1)
    np.testing.assert_array_equal(np.sort(np.asarray(i1), -1), want)


def _moe_params(small, layer=0):
    _, _, _, params = small
    return jax.tree.map(lambda t: t[layer].astype(jnp.float32),
                        params["stacks"]["moe"]["moe"])


def _reference_experts(small, x, p, offset, held):
    c, _, ref, _ = small
    m = dict(ref.dims(c), offset=offset, held=held)
    q = {k: p[k] for k in ("router", "router_bias", "wi", "wg", "wo")}
    q.update(s_wi=p["shared"]["wi"], s_wg=p["shared"]["wg"],
             s_wo=p["shared"]["wo"])
    with jax.default_matmul_precision("highest"):
        return ref._experts(x, q, m, "f32")[0]


def test_skewed_routing_drops_no_token(small):
    """With a bias that sends every token to the held experts, each held
    expert takes all 48 tokens, and the layer still equals the reference
    mixture token by token: nothing is dropped for capacity."""
    from repro.models.moe import moe_block
    _, cfg, _, _ = small
    p = _moe_params(small)
    off, held = cfg.expert_offset, cfg.n_experts_held
    p["router_bias"] = jnp.zeros((cfg.n_experts,)).at[off:off + held].set(
        10.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, aux = moe_block(x, p, cfg, single_device_plan())
    np.testing.assert_array_equal(np.asarray(aux["moe_hits"]),
                                  np.ones((48, held), np.int32))
    want = _reference_experts(small, x.reshape(48, -1), p, off, held)
    np.testing.assert_allclose(np.asarray(out).reshape(48, -1),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def _every_held_expert(x2, p, cfg, live):
    """The held layer as it was first written: every held expert on every
    row, weighted by the router's gate; rows not ``live`` get nothing.
    Returns (out, hits)."""
    from repro.models.moe import _route
    held, off = cfg.n_experts_held, cfg.expert_offset
    _, tw, ti, _ = _route(x2, p["router"], cfg.top_k, cfg.router_score,
                          p.get("router_bias"), cfg.routed_scale)
    pick = (ti[:, :, None] == off + jnp.arange(held)) & live[:, None, None]
    gate = jnp.einsum("tk,tke->te", tw, pick.astype(jnp.float32))
    a = jnp.einsum("td,edf->etf", x2, p["wi"])
    g = jax.nn.silu(jnp.einsum("td,edf->etf", x2, p["wg"]))
    y = jnp.einsum("etf,efd->etd", a * g, p["wo"])
    return (jnp.einsum("etd,te->td", y, gate),
            pick.any(1).astype(jnp.int32))


# (router, batch x length, live rows, correction bias added to the held
# experts): prompts and decode steps, with dead slots, with no live row on
# a held expert, and with every row on all of them (top-4, 4 held)
GROUPED = {
    "sigmoid-prefill": ("sigmoid", (1, 40), None, 0.0),
    "softmax-prefill": ("softmax", (2, 20), None, 0.0),
    "sigmoid-decode-dead-rows": ("sigmoid", (16, 1), 11, 0.0),
    "softmax-decode-dead-rows": ("softmax", (16, 1), 5, 0.0),
    "decode-no-held-expert": ("sigmoid", (16, 1), 16, -10.0),
    "decode-all-held-experts": ("sigmoid", (16, 1), 16, 10.0),
    "prefill-all-held-experts": ("sigmoid", (1, 40), None, 10.0),
}


@pytest.mark.parametrize("case", sorted(GROUPED))
def test_grouped_held_layer_equals_every_held_expert(small, case):
    """The held layer computes only the (token, held expert) pairs the
    router picked, as one grouped matmul per matrix, yet equals the gate
    times every held expert on every row, row by row; dead rows get the
    shared expert alone, and the group sizes it returns are the pairs of
    live rows, so no routed pair is dropped."""
    from repro.models.moe import moe_block
    score, (B, S), live, push = GROUPED[case]
    _, cfg, _, _ = small
    cfg = dataclasses.replace(cfg, router_score=score,
                              router_bias=score == "sigmoid")
    p = _moe_params(small)
    off, held = cfg.expert_offset, cfg.n_experts_held
    if score == "sigmoid":
        p["router_bias"] = p["router_bias"].at[off:off + held].add(push)
    else:
        p.pop("router_bias")
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, cfg.d_model))
    active = None if live is None else (
        jnp.arange(B) < live).astype(jnp.int32)
    rows = jnp.ones((B * S,), bool) if active is None else active > 0
    plan = single_device_plan()
    with jax.default_matmul_precision("highest"):
        out, aux = moe_block(x, p, cfg, plan, active)
        shared = moe_block(x, p, dataclasses.replace(
            cfg, expert_offset=cfg.n_experts), plan)[0]   # holds no expert
        want, hits = _every_held_expert(x.reshape(B * S, -1), p, cfg, rows)
    np.testing.assert_array_equal(np.asarray(aux["moe_hits"]),
                                  np.asarray(hits))
    sizes = np.asarray(aux["moe_group_sizes"])
    np.testing.assert_array_equal(sizes, np.asarray(hits).sum(0))
    if push < 0:
        assert sizes.sum() == 0
    if push > 0:
        assert (sizes == int(rows.sum())).all()
    got = np.asarray(out - shared).reshape(B * S, -1)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=1e-4)
    assert not got[~np.asarray(rows)].any()


def test_the_engine_counts_the_experts_each_step_read(small):
    """A decode step with dead slots returns, per expert layer, the group
    sizes of its live rows alone (the same rows' picks as in a step where
    every slot is live), and the engine's counter adds as read the
    layer-experts whose group was not empty."""
    from repro.configs import cache_specs
    from repro.serving.engine import _ExpertCounts
    _, cfg, _, params = small
    plan = single_device_plan()
    B, CL = 6, 16
    live = np.array([1, 0, 1, 1, 0, 1], np.int32)
    caches = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype),
                          cache_specs(cfg, B, CL, None))
    batch = {"token": jax.random.randint(jax.random.PRNGKey(8), (B, 1), 0,
                                         cfg.vocab),
             "pos": jnp.zeros((B,), jnp.int32)}
    decode = jax.jit(make_decode_step(cfg, plan, CL, stats=True))
    st = decode(params, caches, dict(batch, active=jnp.asarray(live)))[-1]
    every = decode(params, caches, batch)[-1]["moe_hits"]
    want = (np.asarray(every) * live[None, :, None]).sum(1)
    sizes = np.asarray(st["moe_group_sizes"])            # (layers, held)
    np.testing.assert_array_equal(sizes, want)
    counts = _ExpertCounts(cfg)
    counts.add("decode", sizes.reshape(-1), int(live.sum()))
    d = counts.snapshot()["decode"]
    assert d["moe_experts_read"] == int((want > 0).sum()) > 0
    assert d["moe_local_assignments"] == int(want.sum())
    assert d["moe_experts_read"] == d["moe_experts_touched"]


def test_the_shares_sum_to_the_uncut_layer(small):
    """The 4 shares of 4 experts each, the shared expert counted once,
    add up to the reference layer that holds all 16 experts, within
    float32 rounding."""
    from repro.models.moe import moe_block
    _, cfg, _, _ = small
    d, f, n, held = cfg.d_model, cfg.moe_d_ff, cfg.n_experts, \
        cfg.n_experts_held
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    full = {"router": jax.random.normal(ks[0], (d, n)) / d ** 0.5,
            "router_bias": 0.05 * jax.random.normal(ks[1], (n,)),
            "wi": jax.random.normal(ks[2], (n, d, f)) / d ** 0.5,
            "wg": jax.random.normal(ks[3], (n, d, f)) / d ** 0.5,
            "wo": jax.random.normal(ks[4], (n, f, d)) / f ** 0.5,
            "shared": {"wi": jax.random.normal(ks[5], (d, f)) / d ** 0.5,
                       "wg": jax.random.normal(ks[6], (d, f)) / d ** 0.5,
                       "wo": jax.random.normal(ks[7], (f, d)) / f ** 0.5}}
    x = jax.random.normal(ks[8], (2, 24, d))
    plan = single_device_plan()
    with jax.default_matmul_precision("highest"):
        shares = [np.asarray(moe_block(
            x, dict(full, **{k: full[k][off:off + held]
                             for k in ("wi", "wg", "wo")}),
            dataclasses.replace(cfg, expert_offset=off), plan)[0])
            for off in range(0, n, held)]
        s = full["shared"]
        shared = np.asarray(jnp.einsum(
            "bsf,fd->bsd", jnp.einsum("bsd,df->bsf", x, s["wi"])
            * jax.nn.silu(jnp.einsum("bsd,df->bsf", x, s["wg"])), s["wo"]))
    total = sum(shares) - (len(shares) - 1) * shared
    want = _reference_experts(small, x.reshape(48, -1), full, 0, n)
    np.testing.assert_allclose(total.reshape(48, -1), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# sha256 of the prefill and three decode steps' logits (float32 bytes) of
# the reduced configs, from the tree before latent attention and the held
# expert layer were added: their paths must not move a bit
PINNED = {
    "yi-34b": ("a5836c05c357f7e79f4448b2f2c32f4c"
               "89356f94f2353a9fbce3f520598874cf", {}),
    "mixtral-8x7b": ("dc755a653a29756c4657aa25834ba5b0"
                     "a098676d1511ca4f887e601bbe86b56a",
                     {"capacity_factor": 8.0}),
}


@pytest.mark.parametrize("arch", sorted(PINNED))
def test_dense_and_mixtral_logits_are_bit_identical(arch):
    digest, over = PINNED[arch]
    plan = single_device_plan()
    cfg = dataclasses.replace(get(arch).reduced(), **over)
    params = LM(cfg).init(jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, cfg.vocab)
    lg, caches = jax.jit(make_prefill_step(cfg, plan, 24))(
        params, {"tokens": toks})
    h = hashlib.sha256(np.asarray(lg, np.float32).tobytes())
    dec = jax.jit(make_decode_step(cfg, plan, 24))
    tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
    for i in range(3):
        tok, lg, caches = dec(params, caches, {
            "token": tok, "pos": jnp.asarray([12 + i, 12 + i], jnp.int32)})
        h.update(np.asarray(lg, np.float32).tobytes())
    assert h.hexdigest() == digest
