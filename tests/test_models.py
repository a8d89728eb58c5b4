"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward/train step on CPU, asserting output shapes + no NaNs;
plus prefill/decode consistency against the parallel forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get
from repro.models.lm import LM
from repro.optim.schedules import cosine_warmup
from repro.runtime.steps import (init_state, make_decode_step,
                                 make_prefill_step, make_train_step)


def _batch_for(cfg, key, B=2, S=32):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["embeds"] = 0.1 * jax.random.normal(key, (B, S, cfg.d_model),
                                                  jnp.bfloat16)
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)).astype(jnp.int32)
    if cfg.family == "encdec":
        batch = {"frames": 0.1 * jax.random.normal(
                     key, (B, S, cfg.d_model), jnp.bfloat16),
                 "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_arch_smoke_train_step(arch, plan, rng):
    cfg = get(arch).reduced()
    state = init_state(cfg, plan, rng)
    batch = _batch_for(cfg, rng)
    step = jax.jit(make_train_step(cfg, plan, cosine_warmup(1e-3, 5, 50)))
    state2, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), (arch, loss)
    assert int(state2["step"]) == 1
    # params updated, shapes preserved, finite
    for p, p2 in zip(jax.tree.leaves(state["params"]),
                     jax.tree.leaves(state2["params"])):
        assert p.shape == p2.shape and p.dtype == p2.dtype
        assert np.all(np.isfinite(np.asarray(p2, np.float32)))


@pytest.mark.parametrize("arch", ["gemma-7b", "mixtral-8x7b", "xlstm-125m",
                                  "zamba2-1.2b", "qwen2-vl-2b"])
def test_arch_smoke_serve(arch, plan, rng):
    cfg = get(arch).reduced()
    params = init_state(cfg, plan, rng)["params"]
    B, S, CL = 2, 16, 32
    batch = _batch_for(cfg, rng, B, S)
    logits, caches = jax.jit(make_prefill_step(cfg, plan, CL))(params, batch)
    assert logits.shape == (B, 1, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    decode = jax.jit(make_decode_step(cfg, plan, CL))
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    db = {"token": tok, "pos": jnp.asarray(S, jnp.int32)}
    if cfg.family == "vlm":
        db["embeds"] = 0.1 * jax.random.normal(rng, (B, 1, cfg.d_model),
                                               jnp.bfloat16)
        db["mrope_positions"] = jnp.full((3, B, 1), S, jnp.int32)
    nt, lg, caches = decode(params, caches, db)
    assert nt.shape == (B, 1)
    assert np.all(np.isfinite(np.asarray(lg, np.float32)))


def test_prefill_decode_matches_parallel_forward(plan, rng):
    """decode(prefill(t[:S]), t[S]) logits == prefill(t[:S+1]) logits —
    the KV cache path agrees with the parallel path."""
    cfg = get("ff-tiny").reduced()
    params = init_state(cfg, plan, rng)["params"]
    B, S = 2, 12
    toks = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab)
    CL = 24
    prefill = jax.jit(make_prefill_step(cfg, plan, CL))
    lg_full, _ = prefill(params, {"tokens": toks})
    lg_pre, caches = prefill(params, {"tokens": toks[:, :S]})
    decode = jax.jit(make_decode_step(cfg, plan, CL))
    _, lg_dec, _ = decode(params, caches,
                          {"token": toks[:, S:S + 1],
                           "pos": jnp.asarray(S, jnp.int32)})
    a = np.asarray(lg_full[:, -1], np.float32)
    b = np.asarray(lg_dec[:, -1], np.float32)
    np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)
    # and the argmax (the actual served token) agrees
    assert np.array_equal(a.argmax(-1), b.argmax(-1))


def test_swa_ring_cache_matches_full_window(plan, rng):
    """SWA ring cache decode == full-cache decode with window mask."""
    import dataclasses
    cfg = get("mixtral-8x7b").reduced()
    cfg = dataclasses.replace(cfg, window=8, attn_kind="swa")
    params = init_state(cfg, plan, rng)["params"]
    B, S = 1, 12
    toks = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab)
    # ring cache (cache_len == window)
    lg_pre, caches = jax.jit(make_prefill_step(cfg, plan, S))(
        params, {"tokens": toks[:, :S]})
    decode = jax.jit(make_decode_step(cfg, plan, S))
    _, lg_ring, _ = decode(params, caches,
                           {"token": toks[:, S:S + 1],
                            "pos": jnp.asarray(S, jnp.int32)})
    # oracle: parallel forward over the full prompt
    cfg2 = dataclasses.replace(cfg)
    lg_full, _ = jax.jit(make_prefill_step(cfg2, plan, S + 1))(
        params, {"tokens": toks})
    a = np.asarray(lg_full[:, -1], np.float32)
    b = np.asarray(lg_ring[:, -1], np.float32)
    assert np.array_equal(a.argmax(-1), b.argmax(-1))
    # bf16 cache + different softmax path (streaming vs full): loose bound
    np.testing.assert_allclose(a, b, rtol=0.2, atol=0.2)


# case -> (arch, overrides): every form of decode cache the tiny archs have
WRITE_CASES = {
    "dense": ("ff-tiny", {}),
    "moe": ("mixtral-8x7b", {"capacity_factor": 8.0}),
    "swa_ring": ("mixtral-8x7b", {"capacity_factor": 8.0, "window": 8}),
    "shared_attn": ("zamba2-1.2b", {}),
    "dec_cross": ("whisper-medium", {}),
    "xlstm_state": ("xlstm-125m", {}),
}
RECURRENT = ("mamba2", "mlstm", "slstm")


def _bits(x):
    a = np.asarray(x)
    return a.view(np.dtype(f"u{a.itemsize}"))


@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_decode_writes_only_its_rows(case, plan, rng):
    """One decode step over slots at different positions leaves every
    cache entry outside the written ``(layer, b, :, pos[b])`` K/V rows bit
    for bit as it was, and writes the rows a prefill of the one-longer
    prompt holds there; a cross cache is not touched, a recurrent state is
    replaced whole in its own shape."""
    import dataclasses
    arch, over = WRITE_CASES[case]
    cfg = dataclasses.replace(get(arch).reduced(), **over)
    params = init_state(cfg, plan, rng)["params"]
    CL = 16
    ring = cfg.attn_kind == "swa" and cfg.window < CL
    lens = [10, 11] if ring else [5, 6]       # slot b's prompt length
    toks = jax.random.randint(rng, (2, max(lens) + 1), 0, cfg.vocab)
    frames = 0.1 * jax.random.normal(rng, (1, 8, cfg.d_model), jnp.bfloat16)
    prefill = jax.jit(make_prefill_step(cfg, plan, CL))

    def slot_cache(b, n):
        batch = {"tokens": toks[b:b + 1, :n]}
        if cfg.family == "encdec":
            batch["frames"] = frames
        return prefill(params, batch)[1]

    caches = jax.tree.map(lambda *cs: jnp.concatenate(cs, axis=1),
                          *[slot_cache(b, n) for b, n in enumerate(lens)])
    refs = [slot_cache(b, n + 1) for b, n in enumerate(lens)]
    wpos = [n % cfg.window if ring else n for n in lens]
    noise = np.random.default_rng(0)

    def kv_of(c, kind):
        return c["self"] if kind == "dec" else c

    if not ring:      # unused rows (masked at decode) hold noise, not zeros
        for kind, c in caches.items():
            if kind in RECURRENT:
                continue
            kv = dict(kv_of(c, kind))
            for name, t in kv.items():
                a = np.array(t, np.float32)
                for b, n in enumerate(lens):
                    a[:, b, :, n:] = noise.normal(size=a[:, b, :, n:].shape)
                kv[name] = jnp.asarray(a, t.dtype)
            caches[kind] = dict(c, self=kv) if kind == "dec" else kv

    decode = jax.jit(make_decode_step(cfg, plan, CL))
    _, _, new = decode(params, caches, {
        "token": toks[jnp.arange(2), jnp.asarray(lens)][:, None],
        "pos": jnp.asarray(lens, jnp.int32)})

    assert jax.tree.structure(new) == jax.tree.structure(caches)
    for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(new)):
        assert a.shape == b.shape and a.dtype == b.dtype
    for kind, c in caches.items():
        if kind in RECURRENT:
            for a, b in zip(jax.tree.leaves(c), jax.tree.leaves(new[kind])):
                for layer in range(a.shape[0]):
                    assert not np.array_equal(_bits(a[layer]),
                                              _bits(b[layer]))
            continue
        if kind == "dec":
            for a, b in zip(jax.tree.leaves(c["cross"]),
                            jax.tree.leaves(new[kind]["cross"])):
                assert np.array_equal(_bits(a), _bits(b))
        for name, old in kv_of(c, kind).items():
            got = kv_of(new[kind], kind)[name]
            written = np.zeros(old.shape, bool)       # (L, B, kv, S, hd)
            for b, p in enumerate(wpos):
                written[:, b, :, p] = True
            assert np.array_equal(_bits(old)[~written], _bits(got)[~written])
            for b, p in enumerate(wpos):
                want = kv_of(refs[b][kind], kind)[name][:, 0, :, p]
                np.testing.assert_allclose(
                    np.asarray(got[:, b, :, p], np.float32),
                    np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)


def test_moe_block_matches_dense_mixture(plan, rng):
    """With ample capacity, the scatter/dispatch MoE == explicit per-token
    mixture of expert FFNs (the farm's collector is exact)."""
    from repro.models.moe import moe_block, moe_defs, _route
    from repro.models.params import init_params
    import dataclasses
    cfg = get("mixtral-8x7b").reduced()
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    defs = moe_defs(cfg, None)
    p = init_params(defs, rng)
    B, S = 2, 16
    x = 0.5 * jax.random.normal(rng, (B, S, cfg.d_model), jnp.float32) \
        .astype(jnp.bfloat16)
    out, aux = jax.jit(lambda x, p: moe_block(x, p, cfg, plan))(x, p)

    # oracle
    x2 = x.reshape(-1, cfg.d_model)
    probs, tw, ti, _ = _route(x2, p["router"], cfg.top_k)
    def ffn(e, t):
        a = t @ p["wi"][e]
        g = jax.nn.silu(t @ p["wg"][e])
        return (a * g) @ p["wo"][e]
    ref = jnp.zeros((x2.shape[0], cfg.d_model), jnp.float32)
    for k in range(cfg.top_k):
        for e in range(cfg.n_experts):
            m = (ti[:, k] == e)[:, None]
            ref = ref + jnp.where(
                m, tw[:, k:k + 1] * ffn(e, x2).astype(jnp.float32), 0.0)
    ref = ref.reshape(B, S, cfg.d_model)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_ssm_chunked_equals_sequential(rng):
    """chunked_gla == step-by-step recurrence."""
    from repro.models.ssm import chunked_gla, gla_step
    B, S, H, N, P = 2, 64, 3, 8, 16
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    q = jax.random.normal(k1, (B, S, H, N)) * 0.5
    k = jax.random.normal(k2, (B, S, H, N)) * 0.5
    v = jax.random.normal(k3, (B, S, H, P))
    la = -jnp.abs(jax.random.normal(k4, (B, S, H))) * 0.1
    y, s_fin = chunked_gla(q, k, v, la, chunk=16)
    state = jnp.zeros((B, H, N, P))
    ys = []
    for t in range(S):
        state, yt = gla_step(state, q[:, t:t + 1], k[:, t:t + 1],
                             v[:, t:t + 1], la[:, t:t + 1])
        ys.append(yt)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(state),
                               rtol=1e-3, atol=1e-3)


def test_vocab_parallel_ce_matches_naive(plan, rng):
    from repro.models.lm import vocab_parallel_ce
    B, S, d, V = 2, 8, 16, 64
    x = jax.random.normal(rng, (B, S, d), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(rng, 1), (d, V)) * 0.1
    w = w.astype(jnp.bfloat16)
    labels = jax.random.randint(rng, (B, S), 0, V)
    mask = jnp.ones((B, S), jnp.float32)
    loss = vocab_parallel_ce(x, w, labels, mask, plan, chunks=2)
    logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    ref = jnp.mean(lse - ll)
    np.testing.assert_allclose(float(loss), float(ref), rtol=2e-3)
