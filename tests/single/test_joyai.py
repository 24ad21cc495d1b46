"""``horovod_tpu.models.joyai``: the model against the plain reference
(``benchmark/references/joyai.py``, parameters in the published layout) with
every leaf stirred off its initial value and the balancing bias off zero, the
bias in the choice and not in the weights, the stored column orders against
the published ones, the shares of a layer adding up to the uncut layer, the
published share's parameter count, and what an ``axis_name`` does and
refuses."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import joyai as family
from benchmark.references import joyai as reference
from horovod_tpu.models import joyai as jy
from horovod_tpu.models import losses
from horovod_tpu.models.sdar import rotary as half_split_rotary

TINY = jy.JOYAI_TINY
# One chip's share of the tiny model: two of four heads, half the dense
# columns, experts 4 to 11 of 16, a quarter of the vocabulary.
SHARE = dataclasses.replace(
    TINY, num_heads_held=2, dense_columns_held=48, num_experts_held=8,
    first_expert=4, vocab_size_held=128)


def _stirred(model, ids, seed=5):
    """The model's variables with every leaf moved off its initial value (a
    one, a zero or a draw): norms' scales, routers and **the balancing
    biases** too (a tenth of the spread of the scores they are added to)."""
    v = model.init(jax.random.key(0), ids)
    leaves, tree = jax.tree_util.tree_flatten(v)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + (0.3 * jnp.std(leaf) + 0.05) * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _reference_loss(v, ids, cfg):
    rcfg, tree = family.reference_config(cfg), family.published(v, cfg)
    total = 0.0
    for row in ids:
        x, _ = reference.hidden(tree, row, rcfg)
        total += reference.loss_sum(tree, x, row)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def test_model_agrees_with_the_reference_on_stirred_weights(cfg=SHARE):
    """Loss, logits and every leaf's gradient of one chip's share, float32
    on both sides, the reference in the published layout and its gradient
    taken through ``published`` (so in the stored orders): what is left is
    the order of the sums (1e-4).  The biases are off zero and get no
    gradient on either side."""
    model = jy.JoyAI(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 40), 0, cfg.rows_held)
    v = _stirred(model, ids)
    biases = jax.tree_util.tree_leaves(v["balancing"])
    assert len(biases) == 1 and all(float(jnp.min(jnp.abs(b))) > 0
                                    for b in biases)
    rcfg = family.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda v: jy.lm_loss(model, v, ids)))(v)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda v: _reference_loss(v, ids, cfg)))(v)
        logits = jax.jit(model.apply)(v, ids)
        want_logits = jax.jit(lambda v: (lambda p: reference.head(
            p, reference.hidden(p, ids[0], rcfg)[0]))(
                family.published(v, cfg)))(v)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits[0] - want_logits))) < 1e-4
    got, want = (dict(jax.tree_util.tree_flatten_with_path(g["params"])[0])
                 for g in (grads, want))
    assert len(got) == len(want) == 31
    for path, b in want.items():
        err = float(jnp.linalg.norm(got[path] - b) / jnp.linalg.norm(b))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)
    for g in jax.tree_util.tree_leaves(grads["balancing"]):
        assert not np.any(np.asarray(g))


def test_the_bias_moves_the_choice_and_not_the_weights():
    """One token whose eighth expert the bias flips: its choice changes and
    its weights are still the unbiased scores of the chosen over their sum;
    a token the bias does not flip keeps its experts and their weights."""
    cfg = dataclasses.replace(TINY, num_experts_per_tok=8)
    router = jy.JoyAIRouter(cfg)
    x = jax.random.normal(jax.random.key(3), (6, 64))
    v = router.init(jax.random.key(0), x)
    scores, chosen, weights = router.apply(v, x)
    order = np.argsort(-np.asarray(scores[0]))
    eighth, ninth = int(order[7]), int(order[8])
    gap = float(scores[0, eighth] - scores[0, ninth])
    bias = np.zeros(16, np.float32)
    bias[ninth] = 1.5 * gap
    biased = {**v, "balancing": {"bias": jnp.asarray(bias)}}
    scores2, chosen2, weights2 = router.apply(biased, x)
    np.testing.assert_array_equal(scores2, scores)
    assert ninth in np.asarray(chosen2[0]) and eighth not in np.asarray(
        chosen2[0])
    picked = np.asarray(scores)[0, np.asarray(chosen2[0])]
    np.testing.assert_allclose(weights2[0], picked / picked.sum(), rtol=1e-6)
    assert abs(float(jnp.sum(weights2[0])) - 1.0) < 1e-6
    same = [t for t in range(1, 6)
            if set(np.asarray(chosen2[t])) == set(np.asarray(chosen[t]))]
    assert same
    for t in same:       # the same experts (in the biased order) as weighty
        by_expert = lambda c, w: np.asarray(w)[np.argsort(np.asarray(c))]  # noqa: E731
        np.testing.assert_allclose(by_expert(chosen2[t], weights2[t]),
                                   by_expert(chosen[t], weights[t]),
                                   rtol=1e-6)  # the divisor's sum, reordered
    # A mutable collection has the bias set from the tokens' loads, not zero.
    _, settled = router.apply(v, x, mutable=["balancing"])
    assert np.any(np.asarray(settled["balancing"]["bias"]))


def test_stored_column_orders_against_the_published_ones():
    """``rotary_lanes`` on the stored order (even lanes then odd) is the
    published interleaved turn lane for lane, and is ``models/sdar.py``'s
    half-split turn head by head; ``published`` puts a head's ``[nope |
    rope]`` and ``[k_nope | v]`` side by side."""
    order = jy.stored_rope_order(8)
    np.testing.assert_array_equal(order, [0, 2, 4, 6, 1, 3, 5, 7])
    x = jax.random.normal(jax.random.key(2), (1, 12, 3 * 8))      # published
    stored = x.reshape(1, 12, 3, 8)[..., order].reshape(1, 12, 24)
    got = jy.rotary_lanes(stored, jnp.arange(12), 1e4, 8)
    want = reference.rotary(x.reshape(12, 3, 8), 1e4)             # published
    np.testing.assert_allclose(got.reshape(12, 3, 8), want[..., order],
                               atol=1e-6)
    np.testing.assert_allclose(
        got.reshape(12, 3, 8),
        half_split_rotary(stored.reshape(12, 3, 8), jnp.arange(12), 1e4),
        atol=1e-6)
    cfg = SHARE
    v = jy.JoyAI(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    a = v["params"]["layer_0"]["attn"]
    p = family.published(v, cfg)["layer_0"]["attn"]
    assert p["q_b"].shape == (48, 2 * 24) and p["kv_b"].shape == (32, 2 * 32)
    assert p["kv_a"].shape == (64, 32 + 8)
    q_b = np.asarray(p["q_b"]).reshape(48, 2, 24)
    np.testing.assert_array_equal(
        q_b[..., :16], np.asarray(a["q_b_nope"]["kernel"]).reshape(48, 2, 16))
    np.testing.assert_array_equal(
        q_b[..., 16:][..., order],
        np.asarray(a["q_b_rope"]["kernel"]).reshape(48, 2, 8))
    np.testing.assert_array_equal(
        np.asarray(p["kv_a"])[:, 32:][:, order],
        np.asarray(a["kv_a"]["kernel"])[:, 32:])
    kv_b = np.asarray(p["kv_b"]).reshape(32, 2, 32)
    np.testing.assert_array_equal(
        kv_b[..., 16:].reshape(32, -1), np.asarray(a["kv_b"]["kernel"])[:, 32:])


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """An expert layer of the tiny model, uncut, through the reference; then
    every share of it (2 head shares x 2 expert shares) through the program:
    the attention parts of the head shares (``W_o``'s partial sums, the
    latent projections and norms whole on each) sum to the layer's attention,
    and on that sum the routed parts of the expert shares plus the shared
    expert **counted once** sum to the layer's mixture, under a bias off
    zero."""
    model = jy.JoyAI(TINY)
    ids = jax.random.randint(jax.random.key(7), (1, 24), 0, 512)
    v = _stirred(model, ids)
    p, bias = v["params"]["layer_1"], v["balancing"]["layer_1"]
    rcfg = family.reference_config(TINY)
    want_tree = family.published(v, TINY)["layer_1"]
    x = jax.random.normal(jax.random.key(8), (24, 64))

    def attn_share(g):
        """Heads ``2g, 2g + 1``: their columns of W_qb and W_kvb, rows of
        W_o."""
        cfg = dataclasses.replace(TINY, num_heads_held=2)
        a = p["attn"]
        cols = lambda w: slice(g * 2 * w, (g + 1) * 2 * w)  # noqa: E731
        kv_b = a["kv_b"]["kernel"]
        cut = {**a,
               "q_b_nope": {"kernel": a["q_b_nope"]["kernel"][:, cols(16)]},
               "q_b_rope": {"kernel": a["q_b_rope"]["kernel"][:, cols(8)]},
               "kv_b": {"kernel": jnp.concatenate(
                   [kv_b[:, :64][:, cols(16)], kv_b[:, 64:][:, cols(16)]], 1)},
               "o_proj": {"kernel": a["o_proj"]["kernel"][cols(16)]}}
        return jy.JoyAIAttention(cfg).apply({"params": cut}, x[None])[0]

    def moe_share(first, h):
        cfg = dataclasses.replace(TINY, num_experts_held=8,
                                  first_expert=first)
        m = p["moe"]
        cut = {**m, **{k: m[k][first:first + 8]
                       for k in ("w_gate", "w_up", "w_down")},
               "shared_down": {"kernel": jnp.zeros_like(
                   m["shared_down"]["kernel"])}}
        return jy.JoyAIMoE(cfg).apply(
            {"params": cut, "balancing": bias["moe"]}, h[None])[0]

    with jax.default_matmul_precision("highest"):
        want_attn = jax.jit(lambda: reference.attn(want_tree["attn"], x,
                                                   rcfg))()
        got_attn = jax.jit(lambda: attn_share(0) + attn_share(1))()
        np.testing.assert_allclose(got_attn, want_attn, atol=2e-5)
        want_moe, shared = jax.jit(lambda: (
            reference.moe(want_tree["moe"], x, rcfg)[0],
            reference.shared(want_tree["moe"], x)))()
        got_moe = jax.jit(lambda: moe_share(0, x) + moe_share(8, x))()
        np.testing.assert_allclose(got_moe + shared, want_moe, atol=2e-5)
        # The shared expert is no small part of it: counted twice, it shows.
        assert float(jnp.max(jnp.abs(shared))) > 100 * 2e-5


def test_parameter_count_of_the_published_share():
    """430,080,000, by the arithmetic of ISSUE 54 and of the configuration's
    ``assumed.parameters``; the biases are no parameters."""
    from benchmark import run

    cfg = run.load_json("configs", "joyai-llm-flash-ep16.json")
    jcfg = family._joyai_config(cfg, rehearse=False)
    shapes = jax.eval_shape(
        lambda k: jy.JoyAI(jcfg).init(k, jnp.zeros((1, 16), jnp.int32)),
        jax.random.key(0))
    count = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    params = shapes["params"]
    attention = (2048 * 1536 + 1536 + 1536 * 768 + 2048 * 576 + 512
                 + 512 * 1024 + 512 * 2048)
    assert attention == count(params["layer_0"]["attn"]) == 7_079_936
    assert count(params["layer_0"]) == 12_589_056
    assert count(params["layer_1"]) == count(params["layer_4"]) == 87_824_384
    assert count(params) == 430_080_000
    assert "430,080,000" in cfg["assumed"]["parameters"]
    assert count(shapes["balancing"]) == 4 * 256
    attn = params["layer_1"]["attn"]
    assert attn["q_b_nope"]["kernel"].shape == (1536, 4 * 128)
    assert attn["q_b_rope"]["kernel"].shape == (1536, 4 * 64)
    assert attn["kv_b"]["kernel"].shape == (512, 2 * 4 * 128)
    assert attn["kv_a"]["kernel"].shape == (2048, 512 + 64)
    assert params["layer_1"]["moe"]["w_gate"].shape == (16, 2048, 768)


def test_an_axis_sums_attention_and_the_dense_layer_and_refuses_the_rest():
    """Under a mesh axis the row-parallel points sum (two chips that hold the
    same share give twice the one-chip result); the experts' exchange, the
    head and the loss raise by name."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    dense_only = dataclasses.replace(TINY, num_layers=1)
    ids = jax.random.randint(jax.random.key(1), (1, 16), 0, 512)
    v = jy.JoyAI(dense_only).init(jax.random.key(0), ids)
    block = v["params"]["layer_0"]
    x = jax.random.normal(jax.random.key(2), (1, 16, 64))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))

    def summed(name, module):
        alone = jax.jit(lambda p, x: module(None).apply({"params": p}, x))(
            block[name], x)
        both = jax.jit(shard_map(
            lambda p, x: module("tp").apply({"params": p}, x), mesh=mesh,
            in_specs=(P(), P()), out_specs=P()))(block[name], x)
        np.testing.assert_allclose(both, 2 * alone, rtol=1e-5, atol=1e-6)

    summed("attn", lambda axis: jy.JoyAIAttention(dense_only, axis))
    summed("mlp", lambda axis: jy.JoyAIMLP(dense_only, axis))
    for method, match in (("head", "JoyAI.head over a vocabulary split"),
                          ("loss", "JoyAI.loss over a vocabulary split")):
        with pytest.raises(NotImplementedError, match=match):
            shard_map(lambda v, a: jy.JoyAI(dense_only, "tp").apply(
                v, a, method=method), mesh=mesh, in_specs=(P(), P()),
                out_specs=P())(v, x if method == "head" else ids)
    sparse = jy.JoyAI(TINY).init(jax.random.key(0), ids)
    with pytest.raises(NotImplementedError, match="experts' exchange"):
        shard_map(lambda p, x: jy.JoyAIMoE(TINY, "tp").apply(
            {"params": p}, x), mesh=mesh, in_specs=(P(), P()),
            out_specs=P())(sparse["params"]["layer_1"]["moe"], x)


@pytest.mark.parametrize("blocks", [1, 3], ids=["one-block", "three-blocks"])
def test_the_loss_through_the_blocked_head_against_whole_logits(blocks,
                                                                monkeypatch):
    """``JoyAI.loss`` (``losses.head_cross_entropy`` on ``hidden``) against
    the form it had, ``head`` and ``softmax_cross_entropy`` over the whole
    float32 logits: the value and every parameter's gradient, at the family's
    rehearsal sizes (2 x 48 tokens, 512 rows held), as one block and as
    three."""
    from benchmark import run

    cfg = family._joyai_config(
        run.load_json("configs", "joyai-llm-flash-ep16.json"), True)
    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES",
                        4 * cfg.rows_held * 96 // blocks)
    assert losses._head_blocks(96, cfg.rows_held) == blocks
    model = jy.JoyAI(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg.rows_held)
    v = _stirred(model, ids)

    def whole(v):
        nll = losses.softmax_cross_entropy(model.apply(v, ids),
                                           jnp.roll(ids, -1, axis=1))
        predicts = jnp.arange(48) < 47
        return jnp.sum(nll * predicts / (2 * 47.0))

    got, got_grads = jax.jit(jax.value_and_grad(
        lambda v: jy.lm_loss(model, v, ids)))(v)
    want, want_grads = jax.jit(jax.value_and_grad(whole))(v)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert got_grads["params"]["lm_head"].shape == (cfg.hidden_size,
                                                    cfg.rows_held)
    flat, _ = jax.tree_util.tree_flatten_with_path(want_grads)
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(got_grads)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-6 * float(jnp.max(jnp.abs(b))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
