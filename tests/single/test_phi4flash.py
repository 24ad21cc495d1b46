"""``horovod_tpu.models.Phi4Flash`` against ``benchmark/references/
phi4flash.py`` at a small size on stirred weights, layers on both sides of
the memory layer and the layer whose k and v are handed down, so that every
block kind, the carry and its summed gradients are held; the band at a window
shorter than the sequence; the two shares of a layer against the uncut
reference's layer for each of the five kinds; ``lambda_init`` by the
published index; Jamba's mixer unchanged by the switch that takes its norms
off."""

import dataclasses
import math
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from benchmark import common
from benchmark.references import phi4flash as reference
from horovod_tpu import models
from horovod_tpu.models import jamba, phi4flash

# Published layers 1 to 7 of 8: banded, Mamba, banded, Mamba + memory, full +
# k/v, gate, cross.
CFG = dataclasses.replace(models.PHI4FLASH_TINY, first_layer=1, num_layers=7)
RCFG = {"layer_norm_eps": CFG.layer_norm_eps,
        "mamba_dt_rank": CFG.mamba_dt_rank,
        "mamba_d_state": CFG.mamba_d_state,
        "sliding_window": CFG.sliding_window,
        "published_num_hidden_layers": CFG.published_layers}
BATCH, SEQ = 2, 24
SHARES = 2


@pytest.fixture(autouse=True)
def whole_products():
    with jax.default_matmul_precision("highest"):
        yield


def _stirred(variables, seed=5):
    """Every leaf that a one, a zero or a constant would hide a fault behind
    (all the one-dimensional leaves, the biases by head and ``A_log``) moved
    off what it starts at."""
    def stir(path, leaf):
        name = jax.tree_util.keystr(path)
        if leaf.ndim != 1 and not name.endswith(("['A_log']", "['bias']")):
            return leaf
        key = jax.random.fold_in(jax.random.key(seed),
                                 zlib.crc32(name.encode()) % (1 << 30))
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)

    return jax.tree_util.tree_map_with_path(stir, variables)


@pytest.fixture(scope="module")
def tiny():
    model = models.Phi4Flash(CFG)
    ids = jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0,
                             CFG.vocab_size)
    return model, _stirred(jax.jit(model.init)(jax.random.key(0), ids)), ids


@jax.jit
def _reference_loss(params, ids, cfg=None):
    cfg = cfg or RCFG
    total = 0.0
    for row in ids:
        x = reference.hidden(params, row, cfg)
        total = total + reference.loss_sum(params, x, row)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def test_the_layer_order_comes_from_the_published_index():
    assert CFG.sliding_window < SEQ
    assert CFG.layer_kinds == ("banded", "mamba", "banded", "mamba+memory",
                               "full+kv", "gmu", "cross")
    whole = models.PHI4_MINI_FLASH.layer_kinds
    assert [whole.count(k) for k in ("mamba", "mamba+memory", "banded",
                                     "full+kv", "gmu", "cross")] == [
                                         8, 1, 8, 1, 7, 7]
    assert (whole[16], whole[17], whole[18], whole[19]) == (
        "mamba+memory", "full+kv", "gmu", "cross")
    assert [reference.kind_of(i, RCFG) for i in CFG.layers] == list(
        CFG.layer_kinds)
    cut = dataclasses.replace(models.PHI4_MINI_FLASH, first_layer=14,
                              num_layers=6)
    assert cut.layer_kinds == ("mamba", "banded", "mamba+memory", "full+kv",
                               "gmu", "cross")


@pytest.mark.parametrize("layers", [(18, 2), (19, 1)], ids=["gate", "cross"])
def test_a_cut_that_reads_a_layer_it_does_not_hold_is_refused(layers):
    with pytest.raises(ValueError, match="layer it reads"):
        dataclasses.replace(models.PHI4_MINI_FLASH, first_layer=layers[0],
                            num_layers=layers[1])


def test_pairs_must_be_whole():
    with pytest.raises(ValueError, match="pairs"):
        dataclasses.replace(models.PHI4_MINI_FLASH, num_heads_held=10,
                            num_kv_heads_held=5)


@pytest.mark.parametrize("layer", [0, 13, 17, 31])
def test_lambda_init_takes_the_published_index(layer):
    want = 0.8 - 0.6 * math.exp(-0.3 * layer)
    assert phi4flash.lambda_init(layer) == pytest.approx(want)
    assert reference.lambda_init(layer, RCFG) == pytest.approx(want)


def test_loss_and_logits_against_the_plain_reference(tiny):
    model, variables, ids = tiny
    got = jax.jit(lambda v: phi4flash.lm_loss(model, v, ids))(variables)
    want = _reference_loss(variables["params"], ids)
    assert common.rel_err(got, want) < 1e-5
    logits = jax.jit(model.apply)(variables, ids)
    assert logits.dtype == jnp.float32
    want = jax.jit(lambda p: jnp.stack([reference.head(p, reference.hidden(
        p, row, RCFG)) for row in ids]))(variables["params"])
    assert common.rel_err(logits, want) < 2e-5


@pytest.fixture(scope="module")
def gradients(tiny):
    model, variables, ids = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(
            lambda v: phi4flash.lm_loss(model, v, ids)))(variables)
        want = jax.jit(jax.grad(
            lambda p: _reference_loss(p, ids)))(variables["params"])
    return common.leaf_paths(got["params"]), common.leaf_paths(want)


# A leaf of every kind, by the end of its path; the layers that hold one are
# all compared.  A key's bias moves every score of a row alike, so its
# gradient is zero: held apart, below.
LEAF_KINDS = (
    "['embedding']", "['q_proj']['kernel']", "['q_proj']['bias']",
    "['k_proj']['kernel']", "['v_proj']['kernel']", "['v_proj']['bias']",
    "['o_proj']['kernel']", "['o_proj_bias']", "['lambda_q1']",
    "['lambda_k1']", "['lambda_q2']", "['lambda_k2']", "['pair_norm']",
    "['mamba']['in_proj']['kernel']", "['x_proj']['kernel']", "['dt_proj']",
    "['dt_bias']", "['A_log']", "['D']", "['conv']", "['conv_bias']",
    "['mamba']['out_proj']['kernel']", "['gmu']['in_proj']['kernel']",
    "['gmu']['out_proj']['kernel']", "['input_norm']['scale']",
    "['input_norm']['bias']", "['post_mixer_norm']['scale']",
    "['gate_up']['kernel']", "['down']['kernel']", "['final_norm']['bias']")


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_gradients_of_every_leaf_kind_against_the_plain_reference(kind,
                                                                  gradients):
    """Layer 4's Mamba leaves carry the gate layer's gradient through the
    memory, layer 5's k and v the cross layer's: a sum over the readers."""
    got, want = gradients
    paths = [p for p in want if p.endswith(kind)]
    assert paths, kind
    for path in paths:
        assert common.l2_rel_err(got[path], want[path]) < 2e-4, path


def test_a_key_s_bias_has_no_gradient(gradients):
    got, want = gradients
    paths = [p for p in want if p.endswith("['k_proj']['bias']")]
    assert len(paths) == 3
    for path in paths:
        scale = float(jnp.max(jnp.abs(want[path.replace("k_proj", "v_proj")])))
        assert float(jnp.max(jnp.abs(got[path]))) < 1e-5 * scale
        assert float(jnp.max(jnp.abs(want[path]))) < 1e-5 * scale


def test_every_leaf_is_compared(gradients):
    _, want = gradients
    missed = [p for p in want if not p.endswith(LEAF_KINDS)
              and not p.endswith("['k_proj']['bias']")]
    assert all(p.endswith(("['scale']", "['bias']")) for p in missed), missed


def test_checkpointed_blocks_give_the_same_loss_and_gradients(tiny,
                                                              gradients):
    """``nn.remat`` a block carries ``(x, M, (k, v))`` across its boundary,
    None before the two sources."""
    _, variables, ids = tiny
    model = models.Phi4Flash(dataclasses.replace(CFG,
                                                 checkpoint_blocks=True))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda v: phi4flash.lm_loss(model, v, ids)))(variables)
    plain = jax.jit(lambda v: phi4flash.lm_loss(
        models.Phi4Flash(CFG), v, ids))(variables)
    assert common.rel_err(loss, plain) < 1e-6
    for path, leaf in common.leaf_paths(grads["params"]).items():
        if not path.endswith("['k_proj']['bias']"):
            assert common.l2_rel_err(leaf, gradients[0][path]) < 1e-5, path


def test_the_carry_is_read_and_its_gradient_reaches_its_source(tiny):
    """Without the readers the sources' gradients change: layer 4's Mamba
    leaves and layer 5's k and v carry layers 6's and 7's."""
    _, variables, ids = tiny
    short = dataclasses.replace(CFG, num_layers=5)
    held = {k: v for k, v in variables["params"].items()
            if k not in ("layer_6", "layer_7")}

    def grads(cfg, params):
        model = models.Phi4Flash(cfg)
        return jax.jit(jax.grad(lambda p: phi4flash.lm_loss(
            model, {"params": p}, ids)))(params)

    whole, cut = grads(CFG, variables["params"]), grads(short, held)
    for layer, mixer, leaf in (("layer_4", "mamba", "A_log"),
                               ("layer_5", "attn", "k_proj"),
                               ("layer_5", "attn", "v_proj")):
        a, b = whole[layer][mixer][leaf], cut[layer][mixer][leaf]
        a, b = (x["kernel"] if isinstance(x, dict) else x for x in (a, b))
        assert common.l2_rel_err(a, b) > 1e-2, (layer, leaf)


# Where each leaf is cut between the two chips that share a layer: the axis
# that holds the channels, heads, columns or rows; the others are whole on
# every chip.  The paired kernels are cut through their [hidden, 2, width]
# view (tests/single/test_jamba.py).
CUT_AXIS = {("embed", "embedding"): 0, ("mamba", "in_proj"): 2,
            ("mamba", "conv"): 1, ("mamba", "conv_bias"): 0,
            ("mamba", "A_log"): 0, ("mamba", "D"): 0,
            ("mamba", "dt_proj"): 1, ("mamba", "dt_bias"): 0,
            ("mamba", "x_proj"): 0, ("mamba", "out_proj"): 0,
            ("gmu", "in_proj"): 1, ("gmu", "out_proj"): 0,
            ("attn", "q_proj"): {"kernel": 1, "bias": 0},
            ("attn", "k_proj"): {"kernel": 1, "bias": 0},
            ("attn", "v_proj"): {"kernel": 1, "bias": 0},
            ("attn", "o_proj"): 0, ("mlp", "gate_up"): 2, ("mlp", "down"): 0}
PAIRED = (("mamba", "in_proj"), ("mlp", "gate_up"))


def _names(path):
    return [k.key for k in path]


def _cut_axis(path):
    names = _names(path)
    for (module, leaf), axis in CUT_AXIS.items():
        if module in names and leaf in names:
            return axis[names[-1]] if isinstance(axis, dict) else axis
    return None


def _paired(params, *middle):
    def view(path, leaf):
        names = _names(path)
        if any(m in names and n in names for m, n in PAIRED):
            return leaf.reshape(leaf.shape[0], *middle, -1)
        return leaf

    return jax.tree_util.tree_map_with_path(view, params)


# (first layer, layers): a cut that holds the kind and what it reads.
KIND_CUTS = {"banded": (1, 1), "mamba": (2, 1), "full+kv": (5, 1),
             "mamba+memory and gmu": (4, 3), "cross": (4, 4)}


@pytest.mark.parametrize("kind", KIND_CUTS)
def test_the_two_shares_under_shard_map_are_the_uncut_layers(kind):
    """With ``axis_name`` each chip holds half of every layer (whole pairs of
    heads, the memory's channels the gate layers' own) and the row-parallel
    points sum over the axis (``x_proj``'s inside the mixer; ``b_o`` added
    once, after the sum): every chip's result is the plain reference's on
    the uncut weights, the residual stream and what both compute alike
    counted once."""
    first, count = KIND_CUTS[kind]
    whole_cfg = dataclasses.replace(CFG, first_layer=first, num_layers=count)
    share_cfg = dataclasses.replace(
        whole_cfg, mamba_d_inner_held=CFG.d_inner // SHARES,
        num_heads_held=CFG.num_heads // SHARES,
        num_kv_heads_held=CFG.num_kv_heads // SHARES,
        intermediate_size_held=CFG.intermediate_size // SHARES,
        vocab_size_held=CFG.vocab_size // SHARES)
    ids = jax.random.randint(jax.random.key(2), (BATCH, SEQ), 0,
                             CFG.vocab_size)
    whole = _stirred(jax.jit(models.Phi4Flash(whole_cfg).init)(
        jax.random.key(3), ids))["params"]
    want = jax.jit(lambda p: jnp.stack(
        [reference.hidden(p, row, RCFG) for row in ids]))(whole)
    model = models.Phi4Flash(share_cfg, axis_name="tp")
    mesh = Mesh(np.asarray(jax.devices()[:SHARES]), ("tp",))
    whole = _paired(whole, 2)
    specs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: P() if _cut_axis(path) is None else P(
            *([None] * _cut_axis(path) + ["tp"])), whole)

    def run(params, ids):
        return model.apply({"params": _paired(params)}, ids,
                           method="hidden")[None]

    got = jax.jit(shard_map(run, mesh=mesh, in_specs=(specs, P()),
                            out_specs=P("tp")))(whole, ids)
    assert got.shape == (SHARES, *want.shape)
    for share in range(SHARES):
        assert common.rel_err(got[share], want) < 5e-5, share


def test_jamba_s_mixer_keeps_its_three_norms_and_this_family_s_has_none(tiny):
    _, variables, _ = tiny
    ids = jnp.zeros((1, 8), jnp.int32)
    tree = jax.jit(models.Jamba(models.JAMBA_TINY).init)(jax.random.key(0),
                                                         ids)
    assert {"dt_norm", "b_norm", "c_norm"} <= set(
        tree["params"]["layer_0"]["mamba"])
    assert models.JAMBA_TINY.mamba_norms and not CFG.mamba_norms
    assert not {"dt_norm", "b_norm", "c_norm"} & set(
        variables["params"]["layer_2"]["mamba"])
    mixer = jamba.MambaMixer(CFG, memory=True)
    u = jax.random.normal(jax.random.key(4), (1, SEQ, CFG.hidden_size))
    held = variables["params"]["layer_4"]["mamba"]
    out, memory = jax.jit(mixer.apply)({"params": held}, u)
    want_out, want_memory = jax.jit(
        lambda p, h: reference.mamba(p, h, RCFG))(held, u[0])
    assert common.rel_err(out[0], want_out) < 2e-5
    assert common.rel_err(memory[0], want_memory) < 2e-5


@pytest.mark.parametrize("t", [9])
def test_nothing_sees_to_the_right_and_the_band_ends(tiny, t):
    """Row t's hidden state does not move with a later token; under the band
    a banded layer alone does not move with a token ``window`` or more
    before either."""
    model, variables, ids = tiny
    moved = ids.at[:, t + 1:].set((ids[:, t + 1:] + 7) % CFG.vocab_size)
    hidden = jax.jit(lambda v, i: model.apply(v, i, method="hidden"))
    a, b = hidden(variables, ids), hidden(variables, moved)
    assert common.rel_err(a[:, :t + 1], b[:, :t + 1]) < 1e-6
    if t >= CFG.sliding_window:
        banded = models.Phi4Flash(dataclasses.replace(CFG, num_layers=1))
        held = {"params": {k: variables["params"][k] for k in (
            "embed", "layer_1", "final_norm")}}
        early = ids.at[:, :t - CFG.sliding_window + 1].set(
            (ids[:, :t - CFG.sliding_window + 1] + 7) % CFG.vocab_size)
        a = banded.apply(held, ids, method="hidden")
        b = banded.apply(held, early, method="hidden")
        assert common.rel_err(a[:, t], b[:, t]) < 1e-6
        assert common.rel_err(a[:, t - 1], b[:, t - 1]) > 1e-4


def test_the_model_with_an_axis_refuses_the_split_head():
    model = models.Phi4Flash(CFG, axis_name="tp")
    with pytest.raises(NotImplementedError, match="Reach B9"):
        model.apply({"params": {}}, jnp.zeros((1, 4), jnp.int32),
                    method="loss")
