"""``models/zaya.py`` at ``ZAYA_TINY`` on the CPU: the model against the plain
reference (``benchmark/references/zaya.py``, which imports nothing of it) on
seeded weights whose scales, temperatures, ``gamma`` and balancing biases are
not what they start at; the shares of the expert layer add up to the uncut
layer; compressed convolutional attention against a loop over the positions,
and nothing in it sees to the right; the balancing bias evens the loads; the
blocked tied head and loss against ``softmax_cross_entropy`` on whole logits;
the dispatch alone against ``routed_experts``; the grouped products in
interpret mode at a matrix wider than the kernels' VMEM budget; a
checkpointed block keeps what its flash kernel made (the kernel calls of the
gradient's jaxpr counted, the gradients bit for bit)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _flash_helpers import kernel_calls
from benchmark import common
from benchmark.references import zaya as reference
from horovod_tpu import models
from horovod_tpu.models import losses, zaya
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.parallel import moe

CFG = models.ZAYA_TINY
RCFG = {"num_attention_heads": CFG.num_heads,
        "num_key_value_heads": CFG.num_kv_heads, "head_dim": CFG.head_dim,
        "partial_rotary_factor": CFG.partial_rotary_factor,
        "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.rms_norm_eps,
        "num_experts_per_tok": CFG.num_experts_per_tok, "first_expert": 0}
BATCH, SEQ = 2, 32


@pytest.fixture(autouse=True)
def whole_products():
    with jax.default_matmul_precision("highest"):
        yield


def _stirred(variables, seed=5):
    """The variables with every one-dimensional leaf (norm scales, residual
    scales and biases, ``gamma``, the temperatures, the router's biases)
    moved off what it starts at, and balancing biases that are not zero: a
    fault in how one of them enters is not hidden by a one or a zero."""
    def stir(path, leaf):
        if leaf.ndim != 1:
            return leaf
        key = jax.random.fold_in(jax.random.key(seed), hash(
            jax.tree_util.keystr(path)) % (1 << 30))
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)

    return {"params": jax.tree_util.tree_map_with_path(stir,
                                                       variables["params"]),
            "balancing": jax.tree_util.tree_map_with_path(
                lambda p, b: 0.05 * jax.random.normal(jax.random.fold_in(
                    jax.random.key(seed + 1), len(jax.tree_util.keystr(p))),
                    b.shape), variables["balancing"])}


@pytest.fixture(scope="module")
def tiny():
    model = models.Zaya(CFG)
    ids = jax.random.randint(jax.random.key(0), (BATCH, SEQ), 0,
                             CFG.vocab_size)
    return model, _stirred(model.init(jax.random.key(1), ids)), ids


def _reference_loss(params, balancing, ids):
    total = 0.0
    for row in ids:
        x, _ = reference.hidden(params, balancing, row, RCFG)
        total = total + reference.loss_sum(params, x, row)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def test_loss_and_logits_against_the_plain_reference(tiny):
    model, variables, ids = tiny
    got = model.apply(variables, ids)
    assert got.dtype == jnp.float32 and got.shape == (BATCH, SEQ,
                                                      CFG.vocab_size)
    for i, row in enumerate(ids):
        x, _ = reference.hidden(variables["params"], variables["balancing"],
                                row, RCFG)
        np.testing.assert_allclose(
            got[i], reference.head(variables["params"], x), rtol=2e-4,
            atol=2e-4)
    assert float(zaya.lm_loss(model, variables, ids)) == pytest.approx(
        float(_reference_loss(variables["params"], variables["balancing"],
                              ids)), rel=1e-5)


LEAF_KINDS = ["embedding", "conv0", "conv1", "temp", "q_proj", "k_proj",
              "v_proj", "v_shift_proj", "o_proj", "down", "gamma", "mlp_0",
              "mlp_1", "mlp_2", "norm", "w_gate", "w_up", "w_down",
              "input_norm", "post_attn_norm", "res_attn", "res_moe",
              "res_final", "final_norm"]


@pytest.fixture(scope="module")
def gradients(tiny):
    model, variables, ids = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: zaya.lm_loss(
            model, {**variables, "params": p}, ids))(variables["params"])
        want = jax.grad(_reference_loss)(variables["params"],
                                         variables["balancing"], ids)
    return common.leaf_paths(got), common.leaf_paths(want)


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_gradients_of_every_leaf_kind_against_the_plain_reference(kind,
                                                                  gradients):
    got, want = gradients
    assert set(got) == set(want) and len(got) == 61
    mine = [path for path in want if f"['{kind}']" in path]
    assert mine, kind
    for path in mine:
        assert float(np.linalg.norm(want[path])) > 0, path
        assert common.l2_rel_err(got[path], want[path]) < 1e-4, path


def test_the_balancing_bias_gets_no_gradient_and_is_no_parameter(tiny):
    model, variables, ids = tiny
    assert set(variables) == {"params", "balancing"}
    grads = jax.grad(lambda b: zaya.lm_loss(
        model, {**variables, "balancing": b}, ids))(variables["balancing"])
    assert all(not np.any(g) for g in jax.tree_util.tree_leaves(grads))


# ---------------------------------------------------------------------------
# The shares add up
# ---------------------------------------------------------------------------


def _expert_layer(first, held):
    return zaya.ZayaExperts(dataclasses.replace(
        CFG, first_expert=first, num_experts_held=held))


@pytest.mark.parametrize("with_state", [False, True],
                         ids=["first-block", "a-block-with-gamma"])
def test_the_shares_add_up_to_the_uncut_layer(with_state):
    """Experts 0-3 and 4-7 of the tiny layer's 8, under a balancing bias
    that is not zero and a ``gamma`` that is not one, each computing its own
    part of every token's sum: added up they are the uncut layer.  The
    router is whole on both and counted once: both hand on the same state
    and make the same choices, and every token's expert is held by exactly
    one of them."""
    keys = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(keys[0], (BATCH, SEQ, CFG.hidden_size))
    s = (jax.random.normal(keys[1], (BATCH, SEQ, CFG.router_hidden_size))
         if with_state else None)
    whole = _expert_layer(0, 8)
    variables = whole.init(keys[2], x, s)
    params = dict(variables["params"])
    if with_state:
        params["router"] = {**params["router"], "gamma": 1.0 + 0.3 *
                            jax.random.normal(keys[3],
                                              (CFG.router_hidden_size,))}
    bias = {"bias": 0.05 * jax.random.normal(keys[3], (CFG.num_experts,))}

    def share(first, held):
        mine = {**params, **{k: params[k][first:first + held]
                             for k in ("w_gate", "w_up", "w_down")}}
        (y, state), seen = _expert_layer(first, held).apply(
            {"params": mine, "balancing": bias}, x, s,
            mutable=["intermediates"])
        return y, state, seen["intermediates"]

    y, state, seen = share(0, 8)
    low, state_low, seen_low = share(0, 4)
    high, state_high, seen_high = share(4, 4)
    np.testing.assert_allclose(low + high, y, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(state_low, state)
    np.testing.assert_array_equal(state_high, state)
    np.testing.assert_array_equal(seen_low["chosen_experts"][0],
                                  seen["chosen_experts"][0])
    load = np.asarray(seen["expert_load"][0])
    np.testing.assert_array_equal(np.concatenate([
        seen_low["expert_load"][0], seen_high["expert_load"][0]]), load)
    assert load.sum() == BATCH * SEQ and np.count_nonzero(load) > 2
    # the bias moved some choices, and the gate is the unbiased probability
    unbiased, _, _ = [a for a in _expert_layer(0, 8).apply(
        {"params": params, "balancing": {"bias": jnp.zeros(8)}}, x, s,
        mutable=["intermediates"])][0] + (None,)
    assert not np.allclose(unbiased, y)
    # a token whose expert is absent gets zero from a share
    absent = np.asarray(seen["chosen_experts"][0])[:, 0] >= 4
    assert absent.any() and not np.any(
        np.asarray(low).reshape(-1, CFG.hidden_size)[absent])


def test_the_gate_is_the_probability_itself_not_renormalised():
    """Renormalised, a top-1 gate is 1 and the router gets no gradient."""
    x = jax.random.normal(jax.random.key(0), (1, SEQ, CFG.hidden_size))
    layer = _expert_layer(0, 8)
    variables = layer.init(jax.random.key(1), x, None)
    grads = jax.grad(lambda p: jnp.sum(layer.apply(
        {**variables, "params": p}, x, None)[0] ** 2))(variables["params"])
    assert float(jnp.linalg.norm(grads["router"]["mlp_2"]["kernel"])) > 0


def test_the_balancing_bias_evens_the_loads():
    """Set on some tokens (the collection mutable), the bias sends every
    expert the same rows of them, where the untouched router sends the
    busiest several times the mean; it is in the choice only."""
    x = jax.random.normal(jax.random.key(0), (1, 1024, CFG.hidden_size))
    layer = _expert_layer(0, 8)
    variables = layer.init(jax.random.key(1), x, None)
    assert not np.any(variables["balancing"]["bias"])

    def load(v):
        _, seen = layer.apply(v, x, None, mutable=["intermediates"])
        return np.asarray(seen["intermediates"]["expert_load"][0])

    assert load(variables).max() > 1.5 * 128
    _, settled = layer.apply(variables, x, None, mutable=["balancing"])
    assert np.any(settled["balancing"]["bias"])
    even = load({**variables, **settled})
    assert even.sum() == 1024 and np.abs(even - 128).max() <= 3, even
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(2), (512, 8)))
    bias = zaya.balancing_bias(probs, 1)
    chosen = jnp.argmax(probs + bias, axis=-1)
    assert np.abs(np.bincount(np.asarray(chosen), minlength=8)
                  - 64).max() <= 2


# ---------------------------------------------------------------------------
# Compressed convolutional attention
# ---------------------------------------------------------------------------


def _cca(seed=0, seq=24):
    layer = zaya.CCA(CFG)
    x = jax.random.normal(jax.random.key(seed), (1, seq, CFG.hidden_size))
    params = layer.init(jax.random.key(seed + 1), x)["params"]
    params = {**params, "temp": jnp.asarray([1.3, 0.7])}
    return layer, params, x


def _cca_by_positions(p, x):
    """The layer a position at a time, in numpy: position t reads rows
    t - 1 and t of the latents, row t - 1 of the input for its second value
    head, and keys and values 0 .. t."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    seq, (h, g, d) = len(x), (CFG.num_heads, CFG.num_kv_heads, CFG.head_dim)
    q0 = (x @ p["q_proj"]["kernel"]).reshape(seq, h, d)
    k0 = (x @ p["k_proj"]["kernel"]).reshape(seq, g, d)
    z = np.concatenate([q0, k0], axis=1)
    zero = np.zeros_like(z[0])
    taps0 = p["conv0"].reshape(2, h + g, d)

    def conv0(t):
        return taps0[0] * (z[t - 1] if t else zero) + taps0[1] * z[t]

    def conv1(t):
        before = conv0(t - 1) if t else zero
        return (np.einsum("gc,gcd->gd", before, p["conv1"][0])
                + np.einsum("gc,gcd->gd", conv0(t), p["conv1"][1]))

    half, freq = d // 4, CFG.rope_theta ** (-np.arange(d // 4) / (d // 4))

    def rope(v, t):
        out = v.copy()
        cos, sin = np.cos(t * freq), np.sin(t * freq)
        a, b = v[:, :half], v[:, half:2 * half]
        out[:, :half], out[:, half:2 * half] = a * cos - b * sin, \
            b * cos + a * sin
        return out

    def unit(v):
        return v * np.sqrt(d) / np.linalg.norm(v, axis=-1, keepdims=True)

    qs, ks, vs = [], [], []
    for t in range(seq):
        u = conv1(t)
        q = u[:h] + (q0[t] + np.repeat(k0[t], h // g, axis=0)) / 2
        k = u[h:] + (q0[t].reshape(g, h // g, d).mean(1) + k0[t]) / 2
        qs.append(rope(unit(q), t))
        ks.append(rope(unit(k) * p["temp"][:, None], t))
        vs.append(np.stack([x[t] @ p["v_proj"]["kernel"],
                            (x[t - 1] if t else 0 * x[t])
                            @ p["v_shift_proj"]["kernel"]]))
    out = np.zeros((seq, h, d))
    for t in range(seq):
        for i in range(h):
            scores = np.array([qs[t][i] @ ks[j][i // (h // g)]
                               for j in range(t + 1)]) / np.sqrt(d)
            w = np.exp(scores - scores.max())
            out[t, i] = (w / w.sum()) @ np.array(
                [vs[j][i // (h // g)] for j in range(t + 1)])
    return out.reshape(seq, h * d) @ p["o_proj"]["kernel"]


def test_cca_against_a_loop_over_the_positions():
    layer, params, x = _cca()
    np.testing.assert_allclose(layer.apply({"params": params}, x)[0],
                               _cca_by_positions(params, x[0]), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("t", [1, 7, 23])
def test_nothing_in_cca_sees_to_the_right(t):
    """Perturb token t: the rows before it do not move (the shift, both
    convolutions and the attention read the left only); row t and, through
    the shift and the convolutions, the rows after it do."""
    layer, params, x = _cca()
    moved = x.at[0, t].add(1.0)
    a = np.asarray(layer.apply({"params": params}, x)[0])
    b = np.asarray(layer.apply({"params": params}, moved)[0])
    np.testing.assert_array_equal(a[:t], b[:t])
    assert np.abs(a[t] - b[t]).max() > 1e-3
    if t + 1 < len(a):
        assert np.abs(a[t + 1] - b[t + 1]).max() > 1e-4


def test_rotary_turns_the_first_part_of_a_head_and_leaves_the_rest():
    from horovod_tpu.models.sdar import rotary

    x = jax.random.normal(jax.random.key(0), (5, 2, 16))
    pos = jnp.arange(5)
    half = rotary(x, pos, 1e4, 8)
    np.testing.assert_array_equal(half[..., 8:], x[..., 8:])
    np.testing.assert_allclose(half[..., :8], rotary(x[..., :8], pos, 1e4))
    np.testing.assert_array_equal(rotary(x, pos, 1e4, 16),
                                  rotary(x, pos, 1e4))
    np.testing.assert_allclose(
        half, reference.rotary(x, 1e4, 8), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# The blocked tied head and loss
# ---------------------------------------------------------------------------


def _whole_logits_loss(x, table, ids, labels, weights):
    """The same loss with every logit alive: the gather, the head as the
    embedding transposed, ``softmax_cross_entropy``."""
    logits = (x + 0.0 * table[ids]) @ table.T
    return jnp.sum(weights * losses.softmax_cross_entropy(logits, labels))


@pytest.mark.parametrize("tokens,block", [(96, 32), (96, 96), (60, 16)],
                         ids=["three-blocks", "one-block",
                              "a-block-that-does-not-divide"])
def test_the_blocked_head_against_whole_logits(tokens, block, monkeypatch):
    """Values, d x and both gradients of the tied matrix (the gather's and
    the head's blocks' products, which autodiff adds up)."""
    # A block is as many tokens as its float32 logits may take bytes.
    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES", 4 * 200 * block)
    assert losses._head_blocks(tokens, 200) == {(96, 32): 3, (96, 96): 1,
                                                (60, 16): 4}[tokens, block]
    keys = jax.random.split(jax.random.key(tokens + block), 5)
    table = jax.random.normal(keys[0], (200, 24))
    ids = jax.random.randint(keys[1], (tokens,), 0, 200)
    labels = jax.random.randint(keys[2], (tokens,), 0, 200)
    weights = jax.random.uniform(keys[3], (tokens,)) / tokens
    mix = jax.random.normal(keys[4], (24, 24)) / 5

    def blocked(table, mix):
        x = table[ids] @ mix            # the embedding is gathered and tied
        return 3.0 * losses.tied_head_cross_entropy(x, table, labels,
                                                    weights)

    def whole(table, mix):
        x = table[ids] @ mix
        return 3.0 * jnp.sum(weights * losses.softmax_cross_entropy(
            x @ table.T, labels))

    got, got_grads = jax.value_and_grad(blocked, argnums=(0, 1))(table, mix)
    want, want_grads = jax.value_and_grad(whole, argnums=(0, 1))(table, mix)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(blocked(table, mix)) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # the head's part alone, without the gather's
    head_only = jax.grad(lambda t: losses.tied_head_cross_entropy(
        jax.lax.stop_gradient(table[ids] @ mix), t, labels, weights))(table)
    want_head = jax.grad(lambda t: jnp.sum(
        weights * losses.softmax_cross_entropy(
            jax.lax.stop_gradient(table[ids] @ mix) @ t.T, labels)))(table)
    np.testing.assert_allclose(head_only, want_head, rtol=1e-4, atol=1e-6)


def test_the_blocked_head_keeps_no_logits_of_every_token(monkeypatch):
    """No array of ``[tokens, rows]`` in the jaxpr of its value and
    gradient: a block's logits are the largest."""
    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES", 4 * 200 * 16)
    tokens, rows, d = 64, 200, 24
    x, table = jnp.ones((tokens, d)), jnp.ones((rows, d))
    labels, weights = jnp.zeros((tokens,), jnp.int32), jnp.ones((tokens,))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, t: losses.tied_head_cross_entropy(x, t, labels, weights),
        argnums=(0, 1)))(x, table)

    def shapes(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                yield tuple(getattr(v.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert (16, rows) in seen
    assert not any(tokens in s and rows in s for s in seen), seen


def test_the_blocked_head_inside_a_jitted_shard_map_step():
    """Under ``shard_map`` with ``check_vma``: the scan's carries and the
    tied matrix's cotangent vary as the tokens do."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("hvd",))
    table = jax.random.normal(jax.random.key(0), (50, 8))
    x = jax.random.normal(jax.random.key(1), (2 * 32, 8))
    labels = jax.random.randint(jax.random.key(2), (2 * 32,), 0, 50)
    weights = jnp.full((2 * 32,), 1 / 64)

    def step(x, table, labels, weights):
        loss, grads = jax.value_and_grad(
            losses.tied_head_cross_entropy, argnums=(0, 1))(
                x, table, labels, weights)
        # The tied matrix came in replicated: its cotangent is already the
        # sum over the chips (the cast's transpose).
        return jax.lax.psum(loss, "hvd"), grads[0], grads[1]

    loss, dx, dtable = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("hvd"), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"), P())))(x, table, labels, weights)
    want, (want_dx, want_dtable) = jax.value_and_grad(
        lambda x, t: jnp.sum(weights * losses.softmax_cross_entropy(
            x @ t.T, labels)), argnums=(0, 1))(x, table)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(dx, want_dx, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(dtable, want_dtable, rtol=1e-4, atol=1e-7)


# ---------------------------------------------------------------------------
# The dispatch alone, and the products of a wide expert
# ---------------------------------------------------------------------------


def _routed_layer(seed=0, tokens=256, d=32, f=48, experts=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (tokens, d))
    router = jax.random.normal(ks[1], (d, experts)) * d ** -0.5
    w_gate, w_up = (jax.random.normal(k, (experts, d, f)) * d ** -0.5
                    for k in ks[2:4])
    w_down = jax.random.normal(ks[4], (experts, f, d)) * f ** -0.5
    return x, router, w_gate, w_up, w_down


def _routed_experts_before_the_split(x, router_kernel, w_gate, w_up, w_down,
                                     *, top_k, capacity_factor,
                                     first_expert=0, renormalize=True):
    """``routed_experts`` as PR 35 left it, word for word but the names of
    the module's own functions."""
    tokens, held, experts = (x.shape[0], w_gate.shape[0],
                             router_kernel.shape[1])
    with jax.named_scope("hvd_moe_route"):
        routing = moe.route(x, router_kernel, top_k, first_expert, held,
                            renormalize)
        local = moe._local(routing.experts, first_expert, held)
    rows = moe.row_buffer(tokens, top_k, held, experts, capacity_factor)
    y = moe._dropless(rows, x, local, routing.weights.astype(jnp.float32),
                      w_gate, w_up, w_down)
    return y, routing


@pytest.mark.parametrize("first,held,capacity_factor,renormalize", [
    (0, 4, 2.0, True), (4, 8, 0.5, True), (0, 16, 1.0, False)],
    ids=["fits", "in-parts", "every-expert-not-renormalised"])
def test_routed_experts_is_bit_equal_before_and_after_the_split(
        first, held, capacity_factor, renormalize):
    """SDAR's path through ``routed_experts``, now ``route`` followed by
    ``dispatch_experts``: the same bits, values and gradients."""
    x, router, *kernels = _routed_layer()
    mine = [k[first:first + held] for k in kernels]
    kwargs = dict(top_k=4, capacity_factor=capacity_factor,
                  first_expert=first, renormalize=renormalize)

    def both(fn):
        (y, routing), vjp = jax.vjp(
            lambda *a: fn(*a, **kwargs), x, router, *mine)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, routing)
        return y, routing, vjp((jnp.ones_like(y), zeros))

    got = jax.jit(lambda: both(moe.routed_experts))()
    want = jax.jit(lambda: both(_routed_experts_before_the_split))()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_the_dispatch_takes_a_callers_choice_and_weights():
    """Top-1 under a bias, gated by the unbiased probability: the held
    experts' part against a loop over them, values and gradients (tokens,
    weights, the three kernels)."""
    x, router, *kernels = _routed_layer(tokens=128)
    first, held = 8, 8
    mine = [k[first:first + held] for k in kernels]
    probs = jax.nn.softmax(x @ router, axis=-1)
    bias = 0.1 * jax.random.normal(jax.random.key(7), (16,))
    chosen = jnp.argmax(probs + bias, axis=-1)[:, None]
    assert np.any(np.asarray(chosen[:, 0]) != np.asarray(jnp.argmax(probs,
                                                                    -1)))

    def layer(x, weights, *k):
        return moe.dispatch_experts(
            x, chosen, weights, *k, first_expert=first, experts_total=16,
            capacity_factor=2.0)

    def loop(x, weights, w_gate, w_up, w_down):
        y = jnp.zeros_like(x)
        for i in range(held):
            gate = jnp.where(chosen[:, 0] == first + i, weights[:, 0], 0.0)
            h = jax.nn.silu(x @ w_gate[i]) * (x @ w_up[i])
            y = y + gate[:, None] * (h @ w_down[i])
        return y

    weights = jnp.take_along_axis(probs, chosen, axis=-1)
    np.testing.assert_allclose(layer(x, weights, *mine),
                               loop(x, weights, *mine), rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(layer(*a) ** 2),
                   argnums=(0, 1, 2, 3, 4))(x, weights, *mine)
    want = jax.grad(lambda *a: jnp.sum(loop(*a) ** 2),
                    argnums=(0, 1, 2, 3, 4))(x, weights, *mine)
    for name, a, b in zip(("x", "weights", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)
    assert moe.row_buffer(128, 1, 8, 16, 2.0) == 128       # every row fits


@pytest.mark.parametrize("w_dtype", ["float32", "bfloat16"])
def test_grouped_products_of_a_matrix_wider_than_the_vmem_budget(
        w_dtype, monkeypatch):
    """A budget under which a whole [256, 256] matrix does not fit: the
    product falls to column blocks of 128 and dW to (128, 128) blocks, a
    2 x 2 grid over the visits, as an expert of [2048, 2048] does under the
    real one.  Values and both gradients against a loop over the groups,
    NaN in the rows past the groups' sum."""
    k = n = 256
    tile = gm.TILE_ROWS
    budget = gm._gmm_bytes(tile, k, 128, 2, jnp.dtype(w_dtype).itemsize)
    monkeypatch.setattr(gm, "_VMEM_BUDGET", budget)
    assert gm._gmm_bytes(tile, k, n, 2, jnp.dtype(w_dtype).itemsize) > budget
    fits = [(bk, bn) for bk in gm._divisors(k) for bn in gm._divisors(n)
            if gm._tgmm_bytes(tile, bk, bn, 2, jnp.dtype(
                w_dtype).itemsize) <= budget]
    assert max(fits, key=lambda b: (b[0] * b[1], b[1])) == (128, 128)
    sizes = (300, 0, 212, 400)
    rows_n = 2 * tile
    ks = jax.random.split(jax.random.key(11), 3)
    past = np.arange(rows_n)[:, None] >= sum(sizes)
    rows = jnp.where(past, jnp.nan, jax.random.normal(
        ks[0], (rows_n, k), jnp.bfloat16))
    w = (jax.random.normal(ks[1], (4, k, n)) * k ** -0.5).astype(w_dtype)
    ct = jnp.where(past, jnp.nan, jax.random.normal(ks[2], (rows_n, n),
                                                    jnp.bfloat16))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    out, vjp = jax.vjp(lambda r, w: gm.grouped_dot(
        r, w, group_sizes, interpret=True), rows, w)
    drows, dw = vjp(ct)
    assert dw.dtype == w.dtype and out.dtype == jnp.bfloat16
    want_out = np.zeros((rows_n, n), np.float32)
    want_drows = np.zeros((rows_n, k), np.float32)
    want_dw = np.zeros((4, k, n), np.float32)
    r32, c32 = np.asarray(rows, np.float32), np.asarray(ct, np.float32)
    w32 = np.asarray(w.astype(jnp.bfloat16), np.float32)
    start = 0
    for g, size in enumerate(sizes):
        mine = slice(start, start + size)
        want_out[mine] = r32[mine] @ w32[g]
        want_drows[mine] = c32[mine] @ w32[g].T
        want_dw[g] = r32[mine].T @ c32[mine]
        start += size
    for got, want in ((out, want_out), (drows, want_drows), (dw, want_dw)):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=2e-2,
                                   atol=2e-2 * np.abs(want).max())


# A checkpointed block and its flash kernel.  ``policy``: the block as the
# model builds it; ``plain``: ``nn.remat(ZayaBlock)`` keeping nothing (a
# policy over no name is ``jax.checkpoint``'s own); ``none``: no checkpoint.
CHECKPOINTS = {"policy": (True, fa.CHECKPOINT_NAMES), "plain": (True, ()),
               "none": (False, fa.CHECKPOINT_NAMES)}


@pytest.fixture(scope="module")
def checkpointed(tiny):
    """``(traced, params)``: the tiny model's loss and gradient traced under
    ``jax.jit`` for each kind of ``CHECKPOINTS``, its attention the Pallas
    kernels in the interpreter."""
    _, variables, ids = tiny
    interpreted = functools.partial(fa.flash_attention, interpret=True)

    def loss_of(kind):
        blocks, names = CHECKPOINTS[kind]
        model = models.Zaya(dataclasses.replace(
            CFG, use_flash=True, checkpoint_blocks=blocks))

        def loss(params):
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(zaya, "flash_attention", interpreted)
                patch.setattr(zaya, "CHECKPOINT_NAMES", names)
                return zaya.lm_loss(model, {**variables, "params": params},
                                    ids)
        return loss

    with jax.default_matmul_precision("highest"):
        return {kind: jax.jit(jax.value_and_grad(loss_of(kind))).trace(
            variables["params"]) for kind in CHECKPOINTS}, variables["params"]


@pytest.mark.parametrize("kind,calls_a_layer", [("policy", 3), ("plain", 4),
                                                ("none", 3)])
def test_a_checkpointed_block_runs_its_flash_forward_once(
        kind, calls_a_layer, checkpointed):
    """Forward, dq and dkv a layer; a checkpoint that keeps nothing runs the
    forward kernel a second time to get its output and row statistics back,
    the model's does not.  Counted in the gradient's jaxpr: nothing runs."""
    traced, _ = checkpointed
    assert kernel_calls(
        traced[kind].jaxpr.jaxpr) == calls_a_layer * CFG.num_layers


@pytest.fixture(scope="module")
def checkpointed_gradients(checkpointed):
    traced, params = checkpointed
    return {kind: traced[kind].lower().compile()(params)
            for kind in CHECKPOINTS}


@pytest.mark.parametrize("other", ["plain", "none"])
def test_what_a_checkpoint_keeps_changes_no_bit_of_a_gradient(
        other, checkpointed_gradients, gradients):
    """The kept arrays are what the second run would make again from the same
    operands: the loss and every gradient leaf are the same numbers."""
    (loss, grads), (other_loss, other_grads) = (
        checkpointed_gradients[kind] for kind in ("policy", other))
    assert np.asarray(loss) == np.asarray(other_loss)
    got, want = common.leaf_paths(grads), common.leaf_paths(other_grads)
    assert set(got) == set(want) and len(got) == 61
    for path in want:
        assert float(np.linalg.norm(want[path])) > 0, path
        np.testing.assert_array_equal(got[path], want[path], err_msg=path)
    # And they are the model's gradients: the dense oracle's, to rounding.
    dense, _ = gradients
    for path in dense:
        assert common.l2_rel_err(got[path], dense[path]) < 1e-4, path
