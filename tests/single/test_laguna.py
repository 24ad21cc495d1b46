"""``horovod_tpu.models.laguna``: the model against the plain reference
(``benchmark/references/laguna.py``) with every leaf stirred off its initial
value, the layer kinds, head counts and rotary rules by index, YaRN's
frequencies against numbers worked by hand, the shares of a layer adding up to
the uncut layer, the published share's parameter count, and what an
``axis_name`` does and refuses."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import laguna as family
from benchmark.references import laguna as reference
from horovod_tpu.models import laguna as lg
from horovod_tpu.models import losses

TINY = lg.LAGUNA_TINY
# One chip's share of the tiny model: one of two key/value heads with its
# query heads, half the dense columns, experts 2 to 5 of 8, a quarter of the
# vocabulary.
SHARE = dataclasses.replace(
    TINY, num_kv_heads_held=1, num_heads_per_layer_held=(2, 3, 3, 3, 2),
    dense_columns_held=48, num_experts_held=4, first_expert=2,
    vocab_size_held=128)


def _stirred(model, ids, seed=5):
    """The model's variables with every leaf moved off its initial value (a
    one, a zero or a draw): norms' scales, gates and routers too."""
    v = model.init(jax.random.key(0), ids)
    leaves, tree = jax.tree_util.tree_flatten(v)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + (0.3 * jnp.std(leaf) + 0.05) * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _reference_loss(v, ids, rcfg):
    total = 0.0
    for row in ids:
        x, _ = reference.hidden(v["params"], row, rcfg)
        total += reference.loss_sum(v["params"], x, row)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def test_model_agrees_with_the_reference_on_stirred_weights(cfg=SHARE):
    """Loss, logits and every leaf's gradient of one chip's share, float32
    on both sides: what is left is the order of the sums (1e-4: the largest
    leaf reads 6e-6).  (The uncut layers are held by the test of the shares
    below.)"""
    model = lg.Laguna(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 40), 0, cfg.rows_held)
    v = _stirred(model, ids)
    rcfg = family.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda v: lg.lm_loss(model, v, ids)))(v)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda v: _reference_loss(v, ids, rcfg)))(v)
        logits = jax.jit(model.apply)(v, ids)
        want_logits = jax.jit(lambda p: reference.head(
            p, reference.hidden(p, ids[0], rcfg)[0]))(v["params"])
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits[0] - want_logits))) < 1e-4
    got, want = (dict(jax.tree_util.tree_flatten_with_path(g)[0])
                 for g in (grads, want))
    assert len(got) == len(want) == 64
    for path, b in want.items():
        err = float(jnp.linalg.norm(got[path] - b) / jnp.linalg.norm(b))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_layer_kinds_heads_and_rotary_rules_follow_the_published_lists():
    cfg = lg.LAGUNA_S_2_1
    assert cfg.layer_types == tuple(
        lg.FULL if i % 4 == 0 else lg.SLIDING for i in range(48))
    assert cfg.num_heads_per_layer == tuple(
        48 if i % 4 == 0 else 72 for i in range(48))
    assert cfg.mlp_layer_types == (lg.DENSE,) + (lg.SPARSE,) * 47
    for i in range(48):
        full = i % 4 == 0
        assert cfg.window(i) == (None if full else 512)
        rope = cfg.rope(i)
        assert (rope.rope_type, rope.rope_theta, rope.partial_rotary_factor,
                rope.attention_factor) == (
                    ("yarn", 5e5, 0.5, 1.4852030263919618) if full
                    else ("default", 1e4, 1.0, 1.0))
        assert cfg.heads_held(i) % cfg.kv_heads_held == 0
    assert math.isclose(cfg.rope_full.attention_factor,
                        0.1 * math.log(128) + 1)
    # The model's parameters are each layer's own kind's.
    shapes = jax.eval_shape(
        lambda k: lg.Laguna(SHARE).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"]
    for i, (kind, heads) in enumerate(zip(SHARE.layer_types,
                                          SHARE.num_heads_per_layer_held)):
        attn = shapes[f"layer_{i}"]["attn"]
        assert heads == (2 if kind == lg.FULL else 3)
        assert attn["q_proj"]["kernel"].shape == (64, heads * 16)
        assert attn["k_proj"]["kernel"].shape == (64, 16)
        assert attn["gate_proj"].shape == (64, heads)
        assert attn["o_proj"]["kernel"].shape == (heads * 16, 64)
        assert ("mlp" in shapes[f"layer_{i}"]) == (i == 0)
    with pytest.raises(ValueError, match="names 3 layers of 5"):
        dataclasses.replace(TINY, layer_types=(lg.FULL,) * 3)
    with pytest.raises(ValueError, match="3 query heads held on 2"):
        dataclasses.replace(TINY, num_heads_per_layer_held=(3,) * 5)


def test_yarn_frequencies_by_hand():
    """The published full layer: 64 lanes turn, 32 pairs.  ``low`` = floor(64
    ln(8192 / (32 x 2 pi)) / (2 ln 5e5)) = floor(9.04) = 9, ``high`` =
    ceil(64 ln(8192 / (2 pi)) / (2 ln 5e5)) = ceil(17.49) = 18: pairs 0 to 9
    keep 5e5^(-i / 32), pairs 18 to 31 are divided by 128, pair 12 is a
    third of the way: its frequency (1 - 1/3) + 1/3 / 128 of its own."""
    rope = lg.LAGUNA_S_2_1.rope_full
    freq = lg.yarn_inv_freq(64, rope)
    plain = 5e5 ** (-np.arange(32) / 32.0)
    assert freq.shape == (32,)
    np.testing.assert_allclose(freq[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(freq[18:], plain[18:] / 128, rtol=1e-12)
    np.testing.assert_allclose(freq[12], plain[12] * (2 / 3 + 1 / 3 / 128),
                               rtol=1e-12)
    assert np.all(np.diff(freq) < 0)
    np.testing.assert_allclose(
        lg.yarn_inv_freq(128, lg.LAGUNA_S_2_1.rope_sliding),
        1e4 ** (-np.arange(64) / 64.0), rtol=1e-12)
    # The reference's own, written apart, agrees.
    np.testing.assert_allclose(
        reference.inv_freq(64, dataclasses.asdict(rope)), freq, rtol=1e-5)


def test_rotary_flat_is_the_half_split_turn_head_by_head():
    """``rotary_flat`` on [B, S, H x D] against the pairing written out on
    [S, H, D]: lane i with lane i + width / 2, the rest passing, cos and sin
    scaled."""
    rope = dataclasses.replace(TINY.rope_full, attention_factor=1.5)
    x = jax.random.normal(jax.random.key(2), (1, 12, 3 * 16))
    cos, sin, half = lg.rope_tables(jnp.arange(12), 16, rope)
    assert half == 4 and cos.shape == (12, 16)
    got = lg.rotary_flat(x, cos, sin, half).reshape(12, 3, 16)
    want = reference.rotary(x.reshape(12, 3, 16), dataclasses.asdict(rope))
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x.reshape(12, 3, 16)[..., 8:])


def test_gate_heads_spreads_a_heads_gate_over_its_lanes():
    ctx = jax.random.normal(jax.random.key(3), (2, 5, 3 * 8))
    gate = jax.random.uniform(jax.random.key(4), (2, 5, 3))
    with jax.default_matmul_precision("highest"):
        got = lg.gate_heads(ctx, gate, 8)
    np.testing.assert_allclose(
        got, (ctx.reshape(2, 5, 3, 8) * gate[..., None]).reshape(2, 5, 24),
        rtol=1e-6)


@pytest.mark.parametrize("layer", [1, 4], ids=["sliding", "full"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(layer):
    """A sparse layer of the tiny model, uncut, through the reference; then
    every share of it (2 key/value heads x 2 groups of 4 experts: a chip
    holds one head with its query heads and gate columns, and one group of
    experts) through the program: the attention parts of the two heads sum to
    the layer's attention, and on that sum the routed parts of the two expert
    groups plus the shared expert **counted once** sum to the layer's
    mixture."""
    model = lg.Laguna(TINY)
    ids = jax.random.randint(jax.random.key(7), (1, 24), 0, 512)
    p = _stirred(model, ids)["params"][f"layer_{layer}"]
    rcfg = family.reference_config(TINY)
    x = jax.random.normal(jax.random.key(8), (24, 64))
    heads = TINY.num_heads_per_layer[layer]
    group, d = heads // 2, TINY.head_dim

    def attn_share(g):
        """Key/value head ``g`` with its query heads: their columns of q,
        the gate and the rows of o."""
        cfg = dataclasses.replace(
            TINY, num_kv_heads_held=1,
            num_heads_per_layer_held=tuple(
                h // 2 for h in TINY.num_heads_per_layer))
        a = p["attn"]
        qs = slice(g * group * d, (g + 1) * group * d)
        cut = {"q_proj": {"kernel": a["q_proj"]["kernel"][:, qs]},
               "k_proj": {"kernel": a["k_proj"]["kernel"][
                   :, g * d:(g + 1) * d]},
               "v_proj": {"kernel": a["v_proj"]["kernel"][
                   :, g * d:(g + 1) * d]},
               "gate_proj": a["gate_proj"][:, g * group:(g + 1) * group],
               "o_proj": {"kernel": a["o_proj"]["kernel"][qs]}}
        return lg.LagunaAttention(cfg, layer).apply({"params": cut},
                                                    x[None])[0]

    def moe_share(first, h):
        cfg = dataclasses.replace(TINY, num_experts_held=4,
                                  first_expert=first)
        m = p["moe"]
        cut = {**m, **{k: m[k][first:first + 4]
                       for k in ("w_gate", "w_up", "w_down")}}
        routed_only = {**cut, "shared_down": {"kernel": jnp.zeros_like(
            m["shared_down"]["kernel"])}}
        return lg.LagunaMoE(cfg).apply({"params": routed_only}, h[None])[0]

    with jax.default_matmul_precision("highest"):
        kind = TINY.layer_types[layer]
        want_attn = reference.attn(p["attn"], x, kind, rcfg)
        got_attn = attn_share(0) + attn_share(1)
        np.testing.assert_allclose(got_attn, want_attn, atol=2e-5)
        want_moe, _, _ = reference.moe(p["moe"], x, rcfg)
        shared = reference.shared(p["moe"], x)
        got_moe = moe_share(0, x) + moe_share(4, x) + shared
        np.testing.assert_allclose(got_moe, want_moe, atol=2e-5)
        # The shared expert is no small part of it: counted twice, it shows.
        assert float(jnp.max(jnp.abs(shared))) > 100 * 2e-5


def test_parameter_count_of_the_published_share():
    """468,867,072, by the arithmetic of ISSUE 51 and of the configuration's
    ``assumed.parameters``."""
    from benchmark import run

    cfg = run.load_json("configs", "laguna-s-2.1-ep32.json")
    lcfg = family._laguna_config(cfg, rehearse=False)
    shapes = jax.eval_shape(
        lambda k: lg.Laguna(lcfg).init(k, jnp.zeros((1, 16), jnp.int32)),
        jax.random.key(0))["params"]
    count = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    full = 3072 * (768 + 128 + 128) + 768 * 3072 + 3072 * 6
    sliding = 3072 * (1152 + 128 + 128) + 1152 * 3072 + 3072 * 9
    sparse = 786_432 + 9_437_184 + 75_497_472 + 6_144
    assert (full, sliding) == (5_523_456, 7_891_968)
    assert count(shapes["layer_0"]) == full + 14_155_776 + 6_144 == 19_685_376
    assert count(shapes["layer_1"]) == sliding + sparse == 93_619_200
    assert count(shapes["layer_4"]) == full + sparse == 91_250_688
    assert count(shapes) == 468_867_072
    assert "468,867,072" in cfg["assumed"]["parameters"]
    # The paired kernels are flat leaves, [3072, 2 x held].
    assert shapes["layer_0"]["mlp"]["gate_up"]["kernel"].shape == (3072, 3072)
    assert shapes["layer_1"]["moe"]["shared_gate_up"]["kernel"].shape == (
        3072, 2048)


def test_an_axis_sums_attention_and_the_dense_layer_and_refuses_the_rest():
    """Under a mesh axis the row-parallel points sum (two chips that hold the
    same share give twice the one-chip result); the experts' exchange, the
    head and the loss raise by name."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    dense_only = dataclasses.replace(
        TINY, num_layers=1, num_heads_per_layer=(4,),
        layer_types=(lg.FULL,), mlp_layer_types=(lg.DENSE,))
    ids = jax.random.randint(jax.random.key(1), (1, 16), 0, 512)
    v = lg.Laguna(dense_only).init(jax.random.key(0), ids)
    block = v["params"]["layer_0"]
    x = jax.random.normal(jax.random.key(2), (1, 16, 64))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))

    def summed(name, module):
        alone = module(None).apply({"params": block[name]}, x)
        both = shard_map(
            lambda p, x: module("tp").apply({"params": p}, x), mesh=mesh,
            in_specs=(P(), P()), out_specs=P())(block[name], x)
        np.testing.assert_allclose(both, 2 * alone, rtol=1e-5, atol=1e-6)

    summed("attn", lambda axis: lg.LagunaAttention(dense_only, 0, axis))
    summed("mlp", lambda axis: lg.LagunaMLP(dense_only, axis))
    for method, match in (("head", "Laguna.head over a vocabulary split"),
                          ("loss", "Laguna.loss over a vocabulary split")):
        with pytest.raises(NotImplementedError, match=match):
            shard_map(lambda v, a: lg.Laguna(dense_only, "tp").apply(
                v, a, method=method), mesh=mesh, in_specs=(P(), P()),
                out_specs=P())(v, x if method == "head" else ids)
    sparse = lg.Laguna(TINY).init(jax.random.key(0), ids)["params"]["layer_1"]
    with pytest.raises(NotImplementedError, match="experts' exchange"):
        shard_map(lambda p, x: lg.LagunaMoE(TINY, "tp").apply(
            {"params": p}, x), mesh=mesh, in_specs=(P(), P()),
            out_specs=P())(sparse["moe"], x)


@pytest.mark.parametrize("blocks", [1, 3], ids=["one-block", "three-blocks"])
def test_the_loss_through_the_blocked_head_against_whole_logits(blocks,
                                                                monkeypatch):
    """``Laguna.loss`` (``losses.head_cross_entropy`` on ``hidden``) against
    the form it had, ``head`` and ``softmax_cross_entropy`` over the whole
    float32 logits: the value and every parameter's gradient, at the family's
    rehearsal sizes (2 x 48 tokens, 512 rows held), as one block and as
    three."""
    from benchmark import run

    cfg = family._laguna_config(
        run.load_json("configs", "laguna-s-2.1-ep32.json"), True)
    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES",
                        4 * cfg.rows_held * 96 // blocks)
    assert losses._head_blocks(96, cfg.rows_held) == blocks
    model = lg.Laguna(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg.rows_held)
    v = _stirred(model, ids)

    def whole(v):
        nll = losses.softmax_cross_entropy(model.apply(v, ids),
                                           jnp.roll(ids, -1, axis=1))
        predicts = jnp.arange(48) < 47
        return jnp.sum(nll * predicts / (2 * 47.0))

    got, got_grads = jax.jit(jax.value_and_grad(
        lambda v: lg.lm_loss(model, v, ids)))(v)
    want, want_grads = jax.jit(jax.value_and_grad(whole))(v)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert got_grads["params"]["lm_head"].shape == (cfg.hidden_size,
                                                    cfg.rows_held)
    flat, _ = jax.tree_util.tree_flatten_with_path(want_grads)
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(got_grads)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-6 * float(jnp.max(jnp.abs(b))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
