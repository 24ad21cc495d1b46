"""``models/jamba.py`` at ``JAMBA_TINY`` on the CPU: the model against the
plain reference (``benchmark/references/jamba.py``, which imports nothing of
it) on seeded weights with every leaf that starts at a one or a zero (norm
scales, ``D``, the convolution's bias) or at a constant (``A_log``) moved;
both layer kinds, the layer order from period and offset, loss and
gradients; the share tied to the model: under ``shard_map`` with the axis set
the four shares' block equals the uncut reference's, and with no axis one
share equals the reference given that share; nothing in the mixer sees to
the right."""

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from _flash_helpers import equations, kernel_calls
from benchmark import common
from benchmark.references import jamba as reference
from horovod_tpu import models
from horovod_tpu.models import jamba
from horovod_tpu.models.flat_dense import FlatDenseGeneral
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.selective_scan import selective_scan

CFG = models.JAMBA_TINY
RCFG = {"rms_norm_eps": CFG.rms_norm_eps, "mamba_dt_rank": CFG.mamba_dt_rank,
        "mamba_d_state": CFG.mamba_d_state}
BATCH, SEQ = 2, 24
SHARES = 4


@pytest.fixture(autouse=True)
def whole_products():
    with jax.default_matmul_precision("highest"):
        yield


def _stirred(variables, seed=5):
    """Every leaf that a one, a zero or a constant would hide a fault behind
    (all the one-dimensional leaves, and ``A_log``) moved off what it starts
    at."""
    def stir(path, leaf):
        name = jax.tree_util.keystr(path)
        if leaf.ndim != 1 and not name.endswith("['A_log']"):
            return leaf
        # (crc32, not ``hash``: a str's hash is drawn anew every process.)
        key = jax.random.fold_in(jax.random.key(seed),
                                 zlib.crc32(name.encode()) % (1 << 30))
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)

    return jax.tree_util.tree_map_with_path(stir, variables)


@pytest.fixture(scope="module")
def tiny():
    model = models.Jamba(CFG)
    ids = jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0,
                             CFG.vocab_size)
    return model, _stirred(model.init(jax.random.key(0), ids)), ids


def _reference_loss(params, ids):
    total = 0.0
    for row in ids:
        x = reference.hidden(params, row, RCFG)
        total = total + reference.loss_sum(params, x, row)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def test_the_layer_order_comes_from_period_and_offset(tiny):
    assert CFG.layer_kinds == ("mamba", "attention", "mamba", "attention")
    published = models.JAMBA2_3B.layer_kinds
    assert [i for i, k in enumerate(published) if k == "attention"] == [7, 21]
    assert len(published) == 28
    params = tiny[1]["params"]
    assert [("attn" in params[f"layer_{i}"], "mamba" in params[f"layer_{i}"])
            for i in range(4)] == [(False, True), (True, False)] * 2


def test_loss_and_logits_against_the_plain_reference(tiny):
    model, variables, ids = tiny
    params = variables["params"]
    logits = model.apply(variables, ids)
    assert logits.dtype == jnp.float32
    want = jnp.stack([reference.head(params, reference.hidden(
        params, row, RCFG)) for row in ids])
    assert common.rel_err(logits, want) < 2e-5
    loss = jamba.lm_loss(model, variables, ids)
    assert common.rel_err(loss, _reference_loss(params, ids)) < 1e-5


@pytest.fixture(scope="module")
def gradients(tiny):
    model, variables, ids = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda v: jamba.lm_loss(model, v, ids))(variables)
        want = jax.grad(lambda p: _reference_loss(p, ids))(
            variables["params"])
    return common.leaf_paths(got["params"]), common.leaf_paths(want)


# Every leaf kind of both layer kinds, the embedding and the final norm.
LEAF_KINDS = ("A_log", "D", "conv", "conv_bias", "dt_bias", "dt_proj",
              "dt_norm", "b_norm", "c_norm", "in_proj", "x_proj", "out_proj",
              "q_proj", "kv_proj", "o_proj", "gate_up", "down", "input_norm",
              "pre_ff_norm", "embedding", "final_norm")


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_gradients_of_every_leaf_kind_against_the_plain_reference(kind,
                                                                  gradients):
    got, want = gradients
    assert set(got) == set(want)
    paths = [p for p in want if f"['{kind}']" in p]
    assert paths, kind
    for path in paths:
        assert common.l2_rel_err(got[path], want[path]) < 1e-4, path


def test_checkpointed_blocks_give_the_same_loss_and_gradients(tiny):
    model, variables, ids = tiny
    again = models.Jamba(dataclasses.replace(CFG, checkpoint_blocks=True))
    plain = jax.value_and_grad(lambda v: jamba.lm_loss(model, v, ids))(
        variables)
    held = jax.value_and_grad(lambda v: jamba.lm_loss(again, v, ids))(
        variables)
    np.testing.assert_allclose(plain[0], held[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(plain[1]),
                    jax.tree_util.tree_leaves(held[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


# A checkpointed block, its paired projections and its kernels.  ``policy``:
# the block as the model builds it; ``plain``: ``nn.remat(JambaBlock)``
# keeping nothing (a policy over no name is ``jax.checkpoint``'s own);
# ``none``: no checkpoint.
CHECKPOINTS = {"policy": (True, jamba.CHECKPOINT_NAMES), "plain": (True, ()),
               "none": (False, jamba.CHECKPOINT_NAMES)}
MAMBA_LAYERS = CFG.layer_kinds.count("mamba")
ATTENTION_LAYERS = CFG.layer_kinds.count("attention")


@pytest.fixture(scope="module")
def checkpointed(tiny):
    """``(traced, params)``: the tiny model's loss and gradient traced under
    ``jax.jit`` for each kind of ``CHECKPOINTS``, its attention and its scan
    the Pallas kernels in the interpreter."""
    _, variables, ids = tiny
    interpreted = {
        "flash_attention": functools.partial(fa.flash_attention,
                                             interpret=True),
        "selective_scan": functools.partial(selective_scan, interpret=True)}

    def loss_of(kind):
        blocks, names = CHECKPOINTS[kind]
        model = models.Jamba(dataclasses.replace(
            CFG, use_flash=True, checkpoint_blocks=blocks))

        def loss(params):
            with pytest.MonkeyPatch.context() as patch:
                for name, kernel in interpreted.items():
                    patch.setattr(jamba, name, kernel)
                patch.setattr(jamba, "CHECKPOINT_NAMES", names)
                return jamba.lm_loss(model, {"params": params}, ids)
        return loss

    with jax.default_matmul_precision("highest"):
        return {kind: jax.jit(jax.value_and_grad(loss_of(kind))).trace(
            variables["params"]) for kind in CHECKPOINTS}, variables["params"]


def _forward_products(jaxpr, held: int) -> int:
    """The ``dot_general``s that make a half of a paired projection ``held``
    wide: ``[B, S, hidden] x [hidden, held] -> [B, S, held]``."""
    want = ((BATCH, SEQ, CFG.hidden_size), (CFG.hidden_size, held),
            (BATCH, SEQ, held))
    return sum(
        eqn.primitive.name == "dot_general"
        and tuple(v.aval.shape for v in (*eqn.invars, *eqn.outvars)) == want
        for eqn in equations(jaxpr))


# What a step runs of each, a layer that holds it: the halves of ``in_proj``
# a Mamba layer and of ``gate_up`` a feed-forward, the flash forward an
# attention layer, the scan's forward a Mamba layer (a checkpointed block
# runs it twice under any policy: ``benchmark/families/jamba.py:least_calls``
# holds the cell to that).
@pytest.mark.parametrize("kind,in_proj,gate_up,flash_fwd,scan_fwd", [
    ("policy", 2, 2, 1, 2), ("plain", 4, 4, 2, 2), ("none", 2, 2, 1, 1)])
def test_a_checkpointed_block_runs_its_paired_products_and_flash_forward_once(
        kind, in_proj, gate_up, flash_fwd, scan_fwd, checkpointed):
    """A checkpoint that keeps nothing runs both halves of ``in_proj`` and
    ``gate_up`` and the flash forward a second time in the backward; the
    model's keeps what they made.  Counted in the gradient's jaxpr: nothing
    runs."""
    traced, _ = checkpointed
    jaxpr = traced[kind].jaxpr.jaxpr
    assert _forward_products(jaxpr, CFG.d_inner) == in_proj * MAMBA_LAYERS
    assert _forward_products(jaxpr, CFG.intermediate_size) == (
        gate_up * CFG.num_layers)
    assert {name: kernel_calls(jaxpr, name) for name in (
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv",
        "hvd_ssm_scan_fwd", "hvd_ssm_scan_bwd")} == {
            "hvd_flash_fwd": flash_fwd * ATTENTION_LAYERS,
            "hvd_flash_dq": ATTENTION_LAYERS,
            "hvd_flash_dkv": ATTENTION_LAYERS,
            "hvd_ssm_scan_fwd": scan_fwd * MAMBA_LAYERS,
            "hvd_ssm_scan_bwd": MAMBA_LAYERS}


@pytest.fixture(scope="module")
def checkpointed_gradients(checkpointed):
    traced, params = checkpointed
    return {kind: traced[kind].lower().compile()(params)
            for kind in CHECKPOINTS}


@pytest.mark.parametrize("other", ["plain", "none"])
def test_what_a_checkpoint_keeps_changes_no_gradient(
        other, checkpointed_gradients, gradients):
    """The kept arrays are what the second run would make again from the same
    operands: the loss is the same number and every gradient leaf the same to
    float32's rounding (the CPU's compiler fuses the three programs'
    elementwise passes differently: 1.5e-7 to 4.1e-6 over the 48 leaves)."""
    (loss, grads), (other_loss, other_grads) = (
        checkpointed_gradients[kind] for kind in ("policy", other))
    assert np.asarray(loss) == np.asarray(other_loss)
    got, want = common.leaf_paths(grads), common.leaf_paths(other_grads)
    assert set(got) == set(want) and len(got) == 48
    for path in want:
        assert float(np.linalg.norm(want[path])) > 0, path
        assert common.l2_rel_err(got[path], want[path]) < 1e-5, path
    # And they are the model's gradients: the dense oracle's and XLA's own
    # scan's, to rounding.
    dense, _ = gradients
    for path in dense:
        assert common.l2_rel_err(got[path], dense[path]) < 1e-4, path


# ---------------------------------------------------------------------------
# The share: tensor parallelism, not a slice
# ---------------------------------------------------------------------------

# Where each leaf of a block is cut among the chips that share it: the axis
# of the leaf that holds the channels, heads or columns; the others are
# whole on every chip.  ``in_proj`` and ``gate_up`` are stored flat,
# ``[hidden, 2 * width]`` with columns ``[u | z]`` / ``[gate | up]``: their
# axis 2 is that of the ``[hidden, 2, width]`` view (``_paired(tree, 2)``),
# so that a share holds its own channels' columns of both halves.
CUT_AXIS = {"in_proj": 2, "conv": 1, "conv_bias": 0, "A_log": 0, "D": 0,
            "dt_proj": 1, "dt_bias": 0, "x_proj": 0, "out_proj": 0,
            "q_proj": 1, "o_proj": 0, "gate_up": 2, "down": 0}
PAIRED = ("in_proj", "gate_up")
WHOLE = ("dt_norm", "b_norm", "c_norm", "kv_proj", "input_norm",
         "pre_ff_norm")


def _cut_axis(path) -> int:
    names = [k.key for k in path]
    for name in names:
        if name in CUT_AXIS:
            return CUT_AXIS[name]
    assert any(name in WHOLE for name in names), names
    return None


def _paired(block_params, *middle):
    """The paired kernels reshaped ``[hidden, *middle, -1]``, every other
    leaf as it is: ``_paired(tree, 2)`` is the ``[hidden, 2, width]`` view,
    ``_paired(tree)`` the flat kernels the model stores."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf.reshape(leaf.shape[0], *middle, -1)
        if any(k.key in PAIRED for k in path) else leaf, block_params)


def _share(block_params, share: int):
    """Share ``share`` of ``SHARES`` of a whole block's parameters."""
    def cut(path, leaf):
        axis = _cut_axis(path)
        if axis is None:
            return leaf
        size = leaf.shape[axis] // SHARES
        return jax.lax.slice_in_dim(leaf, share * size, (share + 1) * size,
                                    axis=axis)

    return _paired(jax.tree_util.tree_map_with_path(
        cut, _paired(block_params, 2)))


def _share_config():
    return dataclasses.replace(
        CFG, mamba_d_inner_held=CFG.d_inner // SHARES,
        num_heads_held=CFG.num_heads // SHARES,
        intermediate_size_held=CFG.intermediate_size // SHARES)


def _whole_block(attention: bool, seed=3):
    """A whole block's stirred parameters and an input, float32."""
    block = jamba.JambaBlock(CFG, attention=attention)
    x = jax.random.normal(jax.random.key(seed), (BATCH, SEQ, CFG.hidden_size))
    return _stirred(block.init(jax.random.key(seed + 1), x))["params"], x


@pytest.mark.parametrize("attention", [False, True],
                         ids=["mamba", "attention"])
def test_the_four_shares_under_shard_map_are_the_uncut_block(attention):
    """With ``axis_name`` set each chip holds a quarter of the block and the
    row-parallel points sum over the axis (``x_proj``'s inside the mixer,
    before the three norms): every chip's result is the plain reference's
    on the uncut weights."""
    whole, x = _whole_block(attention)
    want = jnp.stack([reference.block(whole, row, RCFG) for row in x])
    block = jamba.JambaBlock(_share_config(), attention=attention,
                             axis_name="tp")
    mesh = Mesh(np.asarray(jax.devices()[:SHARES]), ("tp",))
    # The mesh cuts the paired kernels through their [hidden, 2, width]
    # view; a chip flattens its cut into what the model stores.
    whole = _paired(whole, 2)
    specs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: P() if _cut_axis(path) is None else P(
            *([None] * _cut_axis(path) + ["tp"])), whole)

    def run(params, x):
        return block.apply({"params": _paired(params)}, x)[None]

    got = jax.jit(shard_map(run, mesh=mesh, in_specs=(specs, P()),
                            out_specs=P("tp")))(whole, x)
    assert got.shape == (SHARES, *x.shape)
    for share in range(SHARES):
        assert common.rel_err(got[share], want) < 2e-5, share
    # The gradient of a leaf that is whole on every chip (the key/value
    # projection, the three norms' scales) is the sum of the chips' parts.
    def loss(params, x):
        return jax.lax.pmean(
            jnp.sum(block.apply({"params": _paired(params)}, x) ** 2), "tp")

    grads = jax.jit(shard_map(jax.grad(loss), mesh=mesh,
                              in_specs=(specs, P()), out_specs=specs))(
                                  whole, x)
    want_grads = jax.grad(lambda p: sum(
        jnp.sum(reference.block(p, row, RCFG) ** 2) for row in x))(whole)
    for path, leaf in common.leaf_paths(want_grads).items():
        assert common.l2_rel_err(common.leaf_paths(grads)[path],
                                 leaf) < 1e-4, path


@pytest.mark.parametrize("attention", [False, True],
                         ids=["mamba", "attention"])
def test_one_share_alone_is_the_reference_given_that_share(attention):
    """With no axis a share computes its own part of every sum (``dt``,
    ``B`` and ``C`` from its own channels alone), in the program and in the
    reference alike, and the parts do NOT add up to the uncut block's."""
    whole, x = _whole_block(attention)
    block = jamba.JambaBlock(_share_config(), attention=attention)
    uncut = jnp.stack([reference.block(whole, row, RCFG) for row in x])
    for share in (0, SHARES - 1):
        mine = _share(whole, share)
        got = block.apply({"params": mine}, x)
        want = jnp.stack([reference.block(mine, row, RCFG) for row in x])
        assert common.rel_err(got, want) < 2e-5, share
        assert common.rel_err(got, uncut) > 1e-2


@pytest.mark.parametrize("name, layer, formula", [
    ("in_proj", "mamba", lambda p, h: reference.mamba(p, h[0], RCFG)[None]),
    ("gate_up", "mlp", lambda p, h: reference.mlp(p, h[0])[None])])
def test_a_paired_kernel_is_the_3d_one_reshaped_from_the_same_key(
        name, layer, formula):
    """``in_proj`` and ``gate_up`` are stored ``[hidden, 2 * width]``: the
    kernel a ``(2, width)`` declaration draws from the same key, reshaped
    value for value, and the layer's output and gradients are those of the
    formula on the ``[hidden, 2, width]`` kernel (the reference's, which
    reads either shape)."""
    whole, x = _whole_block(False)
    params, h = whole[layer], x[:1]
    stored = params[name]["kernel"]
    width = stored.shape[1] // 2
    assert stored.shape == (CFG.hidden_size, 2 * width)
    old = FlatDenseGeneral((2, width), use_bias=False)
    new = jamba.PairedDense(width, jnp.float32)
    key = jax.random.key(11)
    old_v, new_v = old.init(key, h), new.init(key, h)
    assert old_v["params"]["kernel"].shape == (CFG.hidden_size, 2, width)
    np.testing.assert_array_equal(
        old_v["params"]["kernel"].reshape(stored.shape),
        new_v["params"]["kernel"])
    np.testing.assert_allclose(
        old.apply(old_v, h), jnp.concatenate(new.apply(new_v, h), axis=-1),
        rtol=1e-6, atol=1e-6)

    module = (jamba.MambaMixer if layer == "mamba" else jamba.JambaMLP)(CFG)
    viewed = _paired({layer: params}, 2)[layer]
    assert viewed[name]["kernel"].shape == (CFG.hidden_size, 2, width)
    got, got_grads = jax.value_and_grad(lambda p: jnp.sum(
        module.apply({"params": p}, h) ** 2))(params)
    want, want_grads = jax.value_and_grad(lambda p: jnp.sum(
        formula(p, h) ** 2))(viewed)
    assert common.rel_err(got, want) < 2e-5
    want_grads = common.leaf_paths(_paired({layer: want_grads}))
    for path, leaf in common.leaf_paths({layer: got_grads}).items():
        assert leaf.shape == want_grads[path].shape, path
        assert common.l2_rel_err(leaf, want_grads[path]) < 1e-4, path


def test_a_paired_kernel_s_gradient_is_a_float32_product():
    """In bfloat16 the paired product's weight gradient is the float32
    accumulator of ``x^T dy`` itself, half by half, as the configuration's
    "float32 gradients" says: equal to that product to float32's rounding,
    where one rounded to bfloat16 on its way out differs by 2e-3."""
    keys = jax.random.split(jax.random.key(5), 4)
    x = jax.random.normal(keys[0], (2, 64, 32), jnp.bfloat16)
    kernel = jax.random.normal(keys[1], (32, 2 * 48), jnp.float32)
    cts = tuple(jax.random.normal(k, (2, 64, 48), jnp.bfloat16)
                for k in keys[2:])
    _, pull = jax.vjp(lambda k: jamba.paired_dot(x, k, jnp.bfloat16), kernel)
    (got,) = pull(cts)
    assert got.dtype == jnp.float32 and got.shape == kernel.shape
    want = jnp.einsum("bsd,bsc->dc", x, jnp.concatenate(cts, axis=-1),
                      preferred_element_type=jnp.float32)
    assert common.l2_rel_err(got, want) < 1e-6
    assert common.l2_rel_err(
        want.astype(jnp.bfloat16).astype(jnp.float32), want) > 1e-3


def test_the_model_with_an_axis_runs_its_blocks_and_refuses_the_split_head():
    cfg = dataclasses.replace(_share_config(),
                              vocab_size_held=CFG.vocab_size // SHARES)
    model = models.Jamba(cfg, axis_name="tp")
    ids = jax.random.randint(jax.random.key(1), (1, 8), 0, CFG.vocab_size)
    with pytest.raises(NotImplementedError, match="split across 'tp'"):
        model.init(jax.random.key(0), ids, method="loss")


def test_a_bias_on_the_mixers_projections_is_refused_by_name():
    """``mamba_proj_bias`` is a published key (False); nothing carries the
    other value, so a configuration that asks for it is refused."""
    assert models.JAMBA2_3B.mamba_proj_bias is False
    with pytest.raises(ValueError, match="mamba_proj_bias = True"):
        dataclasses.replace(CFG, mamba_proj_bias=True)


def test_the_mixer_keeps_its_scan_s_operands_for_whoever_asks(tiny):
    """Applied with ``mutable=["intermediates"]`` a mixer hands back what
    its ``x_proj`` made, the six operands its scan took and the scan's
    ``y``; applied as a step applies it, nothing."""
    model, variables, ids = tiny[0], tiny[1], tiny[2]
    _, kept = model.apply(variables, ids, method="hidden",
                          mutable=["intermediates"])
    assert set(kept["intermediates"]) == {"layer_0", "layer_2"}
    (scan,) = kept["intermediates"]["layer_0"]["mamba"]["scan"]
    u, dt, a, b, c, d = scan["operands"]
    held, n = CFG.channels_held, CFG.mamba_d_state
    assert scan["x_proj"].shape == (*ids.shape, CFG.mamba_dt_rank + 2 * n)
    assert u.shape == dt.shape == scan["y"].shape == (*ids.shape, held)
    assert (a.shape, b.shape, c.shape, d.shape) == (
        (held, n), (*ids.shape, n), (*ids.shape, n), (held,))
    assert dt.dtype == a.dtype == b.dtype == c.dtype == jnp.float32
    np.testing.assert_array_equal(
        scan["y"], selective_scan(u, dt, a, b, c, d))


# ---------------------------------------------------------------------------
# Causality and the kinds' own arithmetic
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t", [1, 7, 23])
@pytest.mark.parametrize("attention", [False, True],
                         ids=["mamba", "attention"])
def test_nothing_in_a_block_sees_to_the_right(attention, t):
    params, x = _whole_block(attention)
    block = jamba.JambaBlock(CFG, attention=attention)
    moved = x.at[:, t:].add(1.0)
    a = block.apply({"params": params}, x)
    b = block.apply({"params": params}, moved)
    np.testing.assert_allclose(a[:, :t], b[:, :t], rtol=0, atol=1e-6)
    assert float(jnp.max(jnp.abs(a[:, t:] - b[:, t:]))) > 1e-3


def test_attention_carries_no_positions():
    """Attention alone is blind to order: with the first ``t`` rows put in
    another order, row ``t`` reads the same."""
    params, x = _whole_block(True)
    layer = jamba.JambaAttention(CFG)
    p = params["attn"]
    t = 9
    turned = jnp.concatenate([x[:, :t][:, ::-1], x[:, t:]], axis=1)
    a = layer.apply({"params": p}, x)
    b = layer.apply({"params": p}, turned)
    np.testing.assert_allclose(a[:, t:], b[:, t:], rtol=0, atol=1e-5)


def test_the_initial_values_are_the_ones_the_configuration_states():
    model = models.Jamba(CFG)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    mixer = params["layer_0"]["mamba"]
    np.testing.assert_allclose(
        -np.exp(mixer["A_log"]),
        -np.broadcast_to(np.arange(1, CFG.mamba_d_state + 1),
                         mixer["A_log"].shape), rtol=1e-6)
    np.testing.assert_array_equal(mixer["D"], 1.0)
    np.testing.assert_array_equal(mixer["conv_bias"], 0.0)
    step = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert jamba.DT_INIT_MIN * 0.99 <= step.min()
    assert step.max() <= jamba.DT_INIT_MAX * 1.01
    bound = CFG.mamba_dt_rank ** -0.5
    assert np.abs(mixer["dt_proj"]).max() <= bound
    assert "bias" not in params["layer_0"]["mamba"]["in_proj"]
    assert "bias" not in params["layer_1"]["attn"]["q_proj"]
