"""``horovod_tpu.models.sala``: the model against the plain reference
(``benchmark/references/sala.py``) with every leaf stirred off its initial
value, both mixers, loss, logits and every gradient; the reference's own
choice against the program's; ``dense_len`` switching the sparse layers' form
by the sequence's length and nothing else; the four shares of a layer adding
up to the uncut reference's layer with the residual stream counted once; the
published share's parameter count and slopes; what an ``axis_name`` does and
refuses; a block under the model's own checkpoint."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import sala as family
from benchmark.references import sala as reference
from horovod_tpu.models import sala
from horovod_tpu.ops import flash_select

TINY = sala.SALA_TINY
# One chip's share of the tiny model: heads 2 and 3 of four lightning heads,
# query heads 2 and 3 of a sparse layer on the key/value head they read, a
# quarter of the columns and of the vocabulary.
SHARE = dataclasses.replace(
    TINY, lightning_heads_held=2, first_lightning_head=2, num_heads_held=2,
    num_kv_heads_held=1, intermediate_size_held=24, vocab_size_held=128)
SEQ = 64           # twice the tiny dense_len: the sparse layers choose


def _stirred(model, ids, seed=5):
    """The model's variables with every leaf moved off its initial value (a
    one or a draw): the norms' scales too."""
    v = model.init(jax.random.key(0), ids)
    leaves, tree = jax.tree_util.tree_flatten(v)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + (0.3 * jnp.std(leaf) + 0.05) * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _reference_loss(v, ids, cfg, chosen=None):
    rcfg, tree = family.reference_config(cfg), family.published(v)
    total = 0.0
    for n, row in enumerate(ids):
        x, _ = reference.hidden(tree, row, rcfg,
                                None if chosen is None else chosen[n])
        total += reference.loss_sum(tree, x, row)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


@pytest.fixture(scope="module")
def stirred_share():
    model = sala.Sala(SHARE)
    ids = jax.random.randint(jax.random.key(1), (2, SEQ), 0, SHARE.rows_held)
    return model, ids, _stirred(model, ids)


def test_model_agrees_with_the_reference_on_stirred_weights(stirred_share):
    """Loss, logits, the sparse layers' choices and every leaf's gradient of
    one chip's share, float32 on both sides, each side under its own
    selection (in float32 they choose alike): what is left is the order of
    the sums (1e-4)."""
    model, ids, v = stirred_share
    rcfg = family.reference_config(SHARE)
    with jax.default_matmul_precision("highest"):
        (loss, seen), grads = jax.jit(jax.value_and_grad(
            lambda v: model.apply(v, ids, method="loss",
                                  mutable=["intermediates"]),
            has_aux=True))(v)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda v: _reference_loss(v, ids, SHARE)))(v)
        logits = jax.jit(model.apply)(v, ids)
        want_logits, want_seen = jax.jit(lambda v: (lambda p, out: (
            reference.head(p, out[0]), out[1]))(
                family.published(v), reference.hidden(
                    family.published(v), ids[0], rcfg)))(v)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits[0] - want_logits))) < 1e-4
    for layer in (0, 3):
        bits = seen["intermediates"][f"layer_{layer}"]["attn"]["chosen"][0]
        np.testing.assert_array_equal(
            np.asarray(flash_select.unpack_bits(bits[0], SEQ // 8)),
            np.asarray(want_seen[layer]["chosen"]))
        per_query = np.asarray(want_seen[layer]["chosen"]).sum(-1)
        assert per_query.max() == 4 and per_query[0, -1] == 4
    assert want_seen[1] is None and want_seen[2] is None
    got, want = (dict(jax.tree_util.tree_flatten_with_path(g["params"])[0])
                 for g in (grads, want))
    assert len(got) == len(want) == 49
    for path, b in want.items():
        err = float(jnp.linalg.norm(got[path] - b) / jnp.linalg.norm(b))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_given_choices_stand_in_for_the_layers_own(stirred_share):
    """The program and the reference on **given** choices (only the forced
    blocks: not what either would choose) agree, and differ from their own:
    the path the benchmark's comparison takes."""
    model, ids, v = stirred_share
    own = (np.arange(SEQ) // 8)[:, None]
    blk = np.arange(SEQ // 8)[None, :]
    forced = jnp.asarray((blk < 1) | ((blk > own - 2) & (blk <= own)))
    given = jnp.broadcast_to(forced, (2, 1, SEQ, SEQ // 8))
    bits = flash_select.pack_bits(given)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda v: model.apply(
            v, ids, {0: bits, 3: bits}, method="loss"))(v)
        want = jax.jit(lambda v: _reference_loss(
            v, ids, SHARE, [{0: g, 3: g} for g in given]))(v)
        own_loss = jax.jit(lambda v: model.apply(v, ids, method="loss"))(v)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert abs(float(got) - float(own_loss)) > 1e-4 * float(want)


@pytest.mark.parametrize("seq,selects", [(32, False), (40, True)])
def test_dense_len_switches_the_form_by_the_length_alone(seq, selects):
    """Up to ``dense_len`` tokens a sparse layer is plain causal attention
    and sows no choice; one block longer it chooses."""
    cfg = dataclasses.replace(SHARE, num_layers=1)
    layer = sala.SparseAttention(cfg)
    u = jax.random.normal(jax.random.key(2), (1, seq, 64))
    v = layer.init(jax.random.key(0), u)
    y, seen = layer.apply(v, u, mutable=["intermediates"])
    assert ("chosen" in seen["intermediates"]) == selects
    kept = seen["intermediates"]["attention"][0]
    by_head = lambda t, n: t.reshape(1, seq, n, 16)  # noqa: E731
    from horovod_tpu.ops.flash_attention import dense_attention
    causal = dense_attention(by_head(kept["q"], 2), by_head(kept["k"], 1),
                             by_head(kept["v"], 1), causal=True,
                             scale=0.25).reshape(1, seq, -1)
    same = float(jnp.max(jnp.abs(causal - kept["ctx"]))) < 1e-6
    assert same != selects
    assert cfg.selects(seq) == selects
    assert reference.selects(seq, family.reference_config(cfg)) == selects


def _share_of(attn: dict, names, cols, rows):
    """A share's cut of an attention module's parameters: ``cols`` of the
    column-parallel kernels in ``names``, ``rows`` of ``o_proj``."""
    return {**attn, **{n: {"kernel": attn[n]["kernel"][:, cols[n]]}
                       for n in names},
            "o_proj": {"kernel": attn["o_proj"]["kernel"][rows]}}


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference_s_layer():
    """A sparse block and a lightning block of the tiny model, uncut, through
    the reference; then the four shares of each through the program (one
    head each: its columns of q, gate and, on a lightning layer, k and v; the
    key/value head it reads on a sparse one; its rows of W_o; a quarter of
    the SwiGLU's columns).  A share's result is ``x + s (its part)``, so the
    parts, **the residual stream counted once**, add up to the uncut block's
    mixer and then, on that sum, to its SwiGLU.  The sparse layer's shares
    take the uncut reference's choice for their key/value head (a group's
    heads lie on two chips, whose selection's exchange is not built)."""
    model = sala.Sala(TINY)
    ids = jax.random.randint(jax.random.key(7), (1, SEQ), 0, 512)
    v = _stirred(model, ids)
    rcfg = family.reference_config(TINY)
    tree = family.published(v)
    x = 0.5 * jax.random.normal(jax.random.key(8), (SEQ, 64))
    s, eps = TINY.residual_scale, TINY.rms_norm_eps
    head = lambda g: slice(16 * g, 16 * (g + 1))  # noqa: E731
    wide = ("q_proj", "gate_proj")

    def mixer_share(layer, g, u, chosen):
        a = v["params"][f"layer_{layer}"]["attn"]
        if TINY.mixer_types[layer] == sala.SPARSE:
            cfg = dataclasses.replace(TINY, num_heads_held=1,
                                      num_kv_heads_held=1)
            kv = head(g // 2)
            cut = _share_of(a, wide + ("k_proj", "v_proj"), {
                "q_proj": head(g), "gate_proj": head(g), "k_proj": kv,
                "v_proj": kv}, head(g))
            bits = flash_select.pack_bits(chosen[None, g // 2:g // 2 + 1])
            return sala.SparseAttention(cfg).apply({"params": cut}, u[None],
                                                   bits)[0]
        cfg = dataclasses.replace(TINY, lightning_heads_held=1,
                                  first_lightning_head=g)
        names = wide + ("k_proj", "v_proj")
        cut = _share_of(a, names, dict.fromkeys(names, head(g)), head(g))
        return sala.LightningAttention(cfg, layer).apply({"params": cut},
                                                         u[None])[0]

    def mlp_share(layer, g, u):
        m = v["params"][f"layer_{layer}"]["mlp"]
        cfg = dataclasses.replace(TINY, intermediate_size_held=24)
        cols = slice(24 * g, 24 * (g + 1))
        gate_up = m["gate_up"]["kernel"]
        cut = {"gate_up": {"kernel": jnp.concatenate(
            [gate_up[:, :96][:, cols], gate_up[:, 96:][:, cols]], 1)},
            "down": {"kernel": m["down"]["kernel"][cols]}}
        return sala.SalaMLP(cfg).apply({"params": cut}, u[None])[0]

    with jax.default_matmul_precision("highest"):
        for layer in (0, 1):
            p = tree[f"layer_{layer}"]
            want, seen = jax.jit(lambda: reference.block(p, x, rcfg, layer))()
            chosen = None if seen is None else seen["chosen"]
            u = reference.rms_norm(x, p["input_norm"], eps)
            mixed = x + s * sum(mixer_share(layer, g, u, chosen)
                                for g in range(4))
            u = reference.rms_norm(mixed, p["post_attn_norm"], eps)
            got = mixed + s * sum(mlp_share(layer, g, u) for g in range(4))
            np.testing.assert_allclose(got, want, atol=2e-5)
            # The parts are no small part of it: one share's alone shows.
            assert float(jnp.max(jnp.abs(mixed - x))) > 100 * 2e-5


def test_parameter_count_and_slopes_of_the_published_share():
    """``configs/minicpm-sala-tp4.json``'s share at the published widths:
    its leaves' sizes by ``jax.eval_shape``, and the slopes of the held heads
    of published layer 1."""
    import json
    import os

    path = os.path.join(os.path.dirname(family.__file__), "..", "configs",
                        "minicpm-sala-tp4.json")
    with open(path) as f:
        cfg = json.load(f)
    scfg = family._sala_config(cfg, False)
    shapes = jax.eval_shape(
        lambda k: sala.Sala(scfg).init(k, jnp.zeros((1, 16), jnp.int32)),
        jax.random.key(0))
    count = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
    assert count == 428_332_416
    assert f"{count:,}" in cfg["assumed"]["parameters"]
    assert list(scfg.mixer_types[:scfg.num_layers]) == [
        sala.SPARSE] + [sala.LIGHTNING] * 3
    assert scfg.mixer_types == sala.MIXER_TYPES == tuple(cfg["mixer_types"])
    a = np.asarray(sala.lightning_slopes(scfg, 1))
    np.testing.assert_allclose(
        a, [2.0 ** (-8 * (h + 1) / 32) * (1 - 1 / 31 + 1e-5)
            for h in range(24, 32)], rtol=1e-6)
    assert 0.03 < math.exp(-256 * a[0]) < 0.05 < 0.35 < math.exp(
        -256 * a[-1]) < 0.4
    assert abs(scfg.residual_scale - 1.4 / math.sqrt(32)) < 1e-12
    assert scfg.logit_divisor == 16 and scfg.local_blocks == 32


def test_an_axis_sums_the_row_parallel_products_and_refuses_the_rest():
    """With ``axis_name`` over four shares stacked by ``vmap`` a lightning
    layer's and the SwiGLU's row-parallel products are summed (every share
    returns the sum of the four parts); a sparse layer whose group lies on
    several chips, the head and the loss raise by name."""
    cfg = dataclasses.replace(TINY, lightning_heads_held=1,
                              intermediate_size_held=24, num_heads_held=1,
                              num_kv_heads_held=1)
    u = jax.random.normal(jax.random.key(4), (1, SEQ, 64))

    def summed(make, name):
        module, alone = make("tp"), make(None)
        v = alone.init(jax.random.key(0), u)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.stack([x * (1 + 0.1 * g) for g in range(4)]), v)
        got = jax.jit(jax.vmap(lambda v: module.apply(v, u),
                               axis_name="tp"))(stacked)
        parts = jax.jit(jax.vmap(lambda v: alone.apply(v, u)))(stacked)
        np.testing.assert_allclose(got[0], parts.sum(0), atol=1e-5,
                                   err_msg=name)
        np.testing.assert_allclose(got[3], got[0], atol=1e-6)

    summed(lambda axis: sala.LightningAttention(cfg, 1, axis), "lightning")
    summed(lambda axis: sala.SalaMLP(cfg, axis), "mlp")
    sparse = sala.SparseAttention(cfg, "tp")
    v = sala.SparseAttention(cfg).init(jax.random.key(0), u)
    with pytest.raises(NotImplementedError, match="selection's sum"):
        jax.vmap(lambda v: sparse.apply(v, u), axis_name="tp")(
            jax.tree_util.tree_map(lambda x: jnp.stack([x] * 4), v))
    model = sala.Sala(cfg, axis_name="tp")
    ids = jnp.zeros((1, 8), jnp.int32)
    v = sala.Sala(cfg).init(jax.random.key(0), ids)
    for method, arg in (("head", jnp.zeros((8, 64))), ("loss", ids)):
        with pytest.raises(NotImplementedError, match="vocabulary split"):
            model.apply(v, arg, method=method)
