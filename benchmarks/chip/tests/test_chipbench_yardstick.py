"""The yardstick's pieces on their own: seeded weights, traffic, the cost
of a decode step."""

import math

import numpy as np
import pytest

import cost
import harness
import traffic as T
import weights as W


def test_a_layer_made_alone_equals_that_layer_of_the_whole_tree():
    import jax
    import jax.numpy as jnp
    key = W.seed_key(2 ** 31 + 99)
    layout = {"a/w": ((4, 3), 3, 0.5), "b": ((5,), 0, 1.0)}
    tree = jax.jit(lambda k: W.make_tree(k, layout, jnp.bfloat16))(key)
    assert tree["a"]["w"].shape == (3, 4, 3)
    for layer in range(3):
        alone = W.layer_leaf(key, "a/w", layer, (4, 3), 0.5, jnp.bfloat16)
        assert np.array_equal(np.asarray(alone),
                              np.asarray(tree["a"]["w"][layer]))
    assert np.array_equal(np.asarray(W.flat_leaf(key, "b", (5,), 1.0,
                                                 jnp.bfloat16)),
                          np.asarray(tree["b"]))
    other = jax.jit(lambda k: W.make_tree(k, layout, jnp.bfloat16))(
        W.seed_key(2 ** 31 + 98))
    assert not np.array_equal(np.asarray(other["b"]), np.asarray(tree["b"]))


def test_seeds_above_32_bits_give_distinct_keys():
    import jax
    a = jax.random.key_data(W.seed_key(2 ** 31 + 1))
    b = jax.random.key_data(W.seed_key(2 ** 32 + 2 ** 31 + 1))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mix", ["chat"])
def test_every_seed_gets_the_same_work_in_another_order(mix):
    m = T.load(mix)
    a = T.serve_requests(m, 11, 30.0, 64000)
    b = T.serve_requests(m, 2 ** 31 + 12, 30.0, 64000)

    def sizes(rs):
        return sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert sizes(a) == sizes(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert all(len(r["prompt"]) in m["prompt"]["ladder"] for r in a)
    assert all(m["output"]["min"] <= r["max_new"] <= m["output"]["max"]
               for r in a)
    assert T.max_context(m) <= m["cache_len"]
    due = [r["due_s"] for r in a]
    assert len(due) == round(m["rate_per_s"] * 30.0)
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0
    gaps = sorted(np.diff([0.0] + due))
    assert gaps == pytest.approx(sorted(np.diff(
        [0.0] + [r["due_s"] for r in b])))


def test_chat_prompt_lengths_follow_the_lognormal_on_the_ladder():
    m = T.load("chat")
    lens = T.prompt_lengths(m["prompt"], 2000)
    assert np.median(lens) in (376, 528)
    assert set(lens) == set(m["prompt"]["ladder"])


def test_decode_step_bytes_match_the_weight_read_reckoning():
    c = harness.load_cell("serve.yi34b-s8.chat").config
    # 8 layers of 557.8 M parameters and the 459 M head, in bfloat16
    assert cost.dense_weight_bytes(c) == pytest.approx(9.85e9, rel=2e-3)
    # K and V of 8 heads of 128 in 8 layers: 32 KiB a token
    assert cost.kv_bytes_per_token(c) == 32 * 1024
    flops, byts = cost.dense_decode_step(c, 32, 32 * 1000)
    assert byts == pytest.approx(cost.dense_weight_bytes(c) + 32000 * 32768
                                 + 32 * 32768 + 32 * 7168 * 2)
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # decode is bound by the bytes: about 12 ms a step at the least
    assert cost.roofline_s(flops, byts, peak) == pytest.approx(
        byts / 819e9)
    assert 0.0120 < cost.roofline_s(flops, byts, peak) < 0.0135
    assert math.isclose(flops, 2 * 32 * (cost.dense_weight_bytes(c) / 2)
                        + 4 * 8 * 56 * 128 * 32000)
