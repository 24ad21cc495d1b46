"""Compile the main path's Pallas kernels for the TPU v5e with the chip's
own compiler, on a described (not attached) ``v5e:2x2`` topology.

Interpret mode cannot show what Mosaic refuses: a block not aligned to the
tiling, more VMEM than a kernel may use, a kernel that cannot be
partitioned.  Nothing runs here, so these say nothing about results or
times; chip_smoke.py and the benchmark do.  This is the only file that
describes the chip: the topology is described inside a fixture (never at
import, not autouse, not in conftest.py) and every compile happens in the
test's own process, because one process at a time may load the TPU's library.
"""

import dataclasses
import functools
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from horovod_tpu.ops import quantize as qz
from horovod_tpu.ops.flash_attention import (
    flash_attention, flash_attention_with_lse)
from horovod_tpu.parallel.ring_attention import ring_attention

# [batch, seq, heads, head_dim]: a long-context shape with 128-wide heads,
# GPT-2-small's attention at batch 8, and GPT-2-medium's at the benchmark's
# GPT cells' batch (the default plan there).
SHAPES = {"4x2048x8x128": (4, 2048, 8, 128), "8x1024x12x64": (8, 1024, 12, 64),
          "8x1024x16x64": (8, 1024, 16, 64)}
CODEC_ELEMS = 1 << 22


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _shapes_on(sharding, tree):
    """``tree``'s leaves as shapes placed on the described chip."""
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=sharding), tree)


def _qkv(shape, sharding):
    return (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding),) * 3


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_flash_forward_compiles(one_chip, shape):
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        *_qkv(shape, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_flash_backward_compiles(one_chip, shape):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False).astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_qkv(shape, one_chip))
    # forward (recomputed for the residuals), dq, and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_flash_out_lse_pair_compiles(one_chip, shape):
    """The variant the ring hop differentiates through: a cotangent on lse."""
    def loss(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, causal=True,
                                            interpret=False)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_qkv(shape, one_chip))
    assert text.count("tpu_custom_call") >= 3


def test_flash_with_a_key_length_per_sequence_compiles(one_chip):
    """BERT-Large's attention at the benchmark's cell (32 x 512 x 16 x 64,
    non-causal, ``kv_lens``): the lengths reach the three kernels as a
    scalar-prefetch operand and the walks end at a length read in the
    kernel, which only Mosaic can refuse."""
    def loss(q, k, v, lens):
        return jnp.sum(flash_attention(q, k, v, interpret=False,
                                       kv_lens=lens).astype(jnp.float32))

    lens = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_qkv((32, 512, 16, 64), one_chip), lens)
    assert text.count("tpu_custom_call") >= 3


# One transformer block of each model family at its benchmark cell's shape:
# (model family, batch, seq, heads, head_dim, the attention module's scope).
BLOCKS = {"gpt2-medium": ("gpt", 8, 1024, 16, 64, "attn"),
          "bert-large": ("bert", 32, 512, 16, 64, "attention"),
          "head_dim-80": ("gpt", 8, 1024, 16, 80, "attn")}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (\(?\w+\[[\d,]*\].*?) ([\w\-]+)\(")


def _block_grad_text(family, batch, seq, heads, head_dim, one_chip):
    """The compiled forward + backward of one block (the model's own
    module, bf16, kernels on) for the described chip."""
    from horovod_tpu import models
    from horovod_tpu.models import bert, gpt

    hidden = heads * head_dim
    x = jax.ShapeDtypeStruct((batch, seq, hidden), jnp.bfloat16,
                             sharding=one_chip)
    if family == "gpt":
        block = gpt.GPTBlock(models.GPTConfig(
            hidden_size=hidden, num_heads=heads, max_seq_len=seq,
            use_flash=True, dtype=jnp.bfloat16))
        args = (x,)

        def call(method, variables_or_key, x):
            return method(variables_or_key, x)
    else:
        block = bert.TransformerLayer(dataclasses.replace(
            models.BERT_LARGE, hidden_size=hidden, num_heads=heads,
            use_flash=True))
        args = (x, jax.ShapeDtypeStruct((batch,), jnp.int32,
                                        sharding=one_chip))

        def call(method, variables_or_key, x, lens):
            return method(variables_or_key, x, lengths=lens)

    params = _shapes_on(one_chip, jax.eval_shape(functools.partial(
        call, block.init, jax.random.PRNGKey(0)), *args))

    def loss(params, *args):
        return jnp.sum(call(block.apply, params, *args)
                       .astype(jnp.float32) ** 2)

    return _compiled_text(jax.grad(loss, argnums=(0, 1)), params, *args)


def _entry_instructions(text, scope):
    """(opcode, result type, op_name) of the entry computation's
    instructions whose scope holds ``/<scope>/``."""
    entry = text[text.index("\nENTRY"):]
    for line in entry.splitlines():
        found = _INSTRUCTION.match(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if found and name and f"/{scope}/" in name.group(1):
            yield found.group(2), found.group(1), name.group(1)


@pytest.mark.parametrize("block", BLOCKS.values(), ids=BLOCKS.keys())
def test_block_takes_the_kernels_without_relayout(one_chip, monkeypatch,
                                                  block):
    """What PR 28 bought, held without a chip: where whole heads fill 128
    lanes the three kernels read and write the model's own [B, S, H * D]
    tensors, so no activation is copied or transposed under the attention
    scope (the parent compiled eight such copies a layer).  At head_dim 80
    the fallback's relayouts are there and say who they are."""
    # The models leave interpret=None, and the dispatch then asks which
    # backend is attached; here that is the CPU, so steer it in the test.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    family, batch, seq, heads, head_dim, scope = block
    text = _block_grad_text(family, batch, seq, heads, head_dim, one_chip)
    under = list(_entry_instructions(text, scope))
    calls = [line for line in text[text.index("\nENTRY"):].splitlines()
             if "tpu_custom_call" in line]
    # forward (out, lse), dq one array, dkv a tuple of two: what the
    # benchmark's flash_dq_ms / flash_dkv_ms part the backward's calls by.
    returns = sorted(len(re.findall(r"\w+\[[\d,]+\]", _INSTRUCTION.match(
        line).group(1))) for line in calls)
    assert returns == [1, 2, 2], calls
    # Each call's instruction is named for its innermost scope, the attention
    # module's (``hvd_attn`` lies outside it, ``hvd_attn_proj`` round the
    # products alone): what the benchmark's flash metrics select by.
    assert all(re.match(rf"\s*%{scope}[\w.]* = ", line) for line in calls), \
        calls
    activation = batch * seq * heads * head_dim
    relayouts = [
        (op, result, name) for op, result, name in under
        if op in ("copy", "transpose") and np.prod(
            [int(n) for n in re.search(r"\[([\d,]+)\]", result)
             .group(1).split(",")]) >= activation]
    if head_dim == 64:
        assert not relayouts, relayouts
    else:
        assert len(relayouts) >= 8
        marked = [r for r in relayouts if "hvd_flash_relayout" in r[2]]
        assert len(marked) >= 8, relayouts


def _elements(result: str) -> int:
    """Elements of the first array of an instruction's result type."""
    return int(np.prod([int(n) for n in re.search(
        r"\[([\d,]*)\]", result).group(1).split(",") if n]))


def test_sdar_norms_and_turns_q_and_k_on_the_kernels_layout(one_chip,
                                                            monkeypatch):
    """``SDARAttention`` at ``sdar-moe-ep8-s4096``'s shape (2 x 8,192
    positions, 32 query heads on 4 key/value heads of 128), forward +
    backward: the per-head norm and the rotary of q and k are the two
    kernels of ``ops/qk_norm_rope.py`` on the projections' own
    [B, S, H * D], and the block-diffusion mask is one call each of the
    three flash kernels on the same arrays (the noised copy's own blocks
    inside them, the two copies as views), so nothing of q's size is copied,
    transposed or reshaped anywhere in the block and no float32 array of
    q's size exists.  (Before ``ops/qk_norm_rope.py`` there was a relayout
    on either side of ``RMSNorm(...)(q, rope)``, the normed q in float32
    and the rotated halves padded to a lane tile; while the own blocks were
    products outside the kernels, under ``hvd_flash_block_diag``, head-major
    copies of the noised half of q going in and of q's cotangent coming
    out.)"""
    from horovod_tpu import models
    from horovod_tpu.models import sdar

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(models.SDAR_30B_A3B, use_flash=True)
    attn = sdar.SDARAttention(cfg)
    x = jax.ShapeDtypeStruct((2, 8192, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    params = _shapes_on(one_chip, jax.eval_shape(
        attn.init, jax.random.PRNGKey(0), x))

    def loss(params, x):
        return jnp.sum(attn.apply(params, x).astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), params, x)
    for kernel, calls in (("hvd_qk_norm_rope_fwd", 2),       # q's and k's
                          ("hvd_qk_norm_rope_bwd", 2), ("hvd_flash_fwd", 1),
                          ("hvd_flash_dq", 1), ("hvd_flash_dkv", 1)):
        assert len(re.findall(rf"%{kernel}[\w.]* = ", text)) == calls, kernel
    assert "hvd_flash_block_diag" not in text
    q_elements = 2 * 8192 * cfg.num_heads * cfg.head_dim
    entry = [(found.group(2), found.group(1), line) for found, line in (
        (_INSTRUCTION.match(line), line)
        for line in text[text.index("\nENTRY"):].splitlines()) if found]
    moved = [(op, "".join(re.findall(r'op_name="([^"]*)"', line)))
             for op, result, line in entry
             if op in ("copy", "transpose", "reshape")
             and _elements(result) >= q_elements]
    assert not moved, [(op, name[-40:] or "no scope") for op, name in moved]
    wide = [result for _, result, _ in entry if result.startswith("f32[")
            and _elements(result) >= q_elements]
    assert not wide, wide


def test_head_and_loss_write_the_logits_once_in_float32(one_chip):
    """GPT-2-medium's float32 head and ``lm_loss`` at the benchmark's GPT
    cells' shape, value and gradient: the head's matmul writes the logits
    (1.65 GB in float32), the loss's hand-written backward writes dlogits
    (XLA narrows it to bf16 for the two matmuls that read it), and nothing
    else writes an array of that size.  ``log_softmax`` + ``take_along_axis``
    wrote a float32 log-probability for every class besides, for the loss
    to pick 8,184 of 412 M."""
    import flax.linen as nn
    from horovod_tpu import models

    head = nn.Dense(50304, use_bias=False, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
    params = _shapes_on(one_chip, jax.eval_shape(
        head.init, jax.random.PRNGKey(0), x))

    def loss(params, x, ids):
        return models.lm_loss(head.apply(params, x), ids)

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)),
                          params, x, ids)
    entry = text[text.index("\nENTRY"):]
    writers = [
        found.group(1) for found in map(_INSTRUCTION.match,
                                        entry.splitlines())
        if found and found.group(2) in ("fusion", "custom-call", "copy")
        and "[8,1023,50304]" in found.group(1)]
    assert 1 <= len(writers) <= 2, writers
    assert sum("f32[8,1023,50304]" in w for w in writers) == 1, writers
    assert "log_softmax" not in text


@pytest.mark.parametrize("w_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)],
                         ids=["gate-up", "down"])
def test_grouped_products_compile_at_an_expert_layer_s_sizes(one_chip, k, n,
                                                             w_dtype):
    """``ops/grouped_matmul.py`` at ``sdar-moe-ep8-s4096``'s sizes (a
    36,864-row buffer, 16 held experts of 2048 x 768, whole matrices in
    VMEM): the product, d rows and dW are three kernel calls, and no
    ``ragged-dot`` is left."""
    from horovod_tpu.ops.grouped_matmul import grouped_dot

    def fwd_bwd(rows, w, sizes, ct):
        out, vjp = jax.vjp(lambda r, w: grouped_dot(r, w, sizes,
                                                    interpret=False), rows, w)
        return (out, *vjp(ct))

    text = _compiled_text(fwd_bwd, *_shapes_on(one_chip, (
        jax.ShapeDtypeStruct((36864, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((16, k, n), jnp.dtype(w_dtype)),
        jax.ShapeDtypeStruct((16,), jnp.int32),
        jax.ShapeDtypeStruct((36864, n), jnp.bfloat16))))
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert len(re.findall(r"hvd_moe_gmm[\w.]* = ", text)) == 2
    assert len(re.findall(r"hvd_moe_tgmm[\w.]* = ", text)) == 1
    assert "ragged-dot" not in text


@pytest.mark.parametrize("w_dtype", ["float32", "bfloat16"])
def test_grouped_products_compile_at_an_expert_wider_than_the_budget(
        one_chip, w_dtype):
    """``ops/grouped_matmul.py`` at ``zaya1-moe-ep2-s16384``'s sizes (a
    16,384-row buffer, 8 held experts of 2048 x 2048): a whole float32
    matrix does not fit the kernels' VMEM budget, the product runs in column
    blocks and dW in ``(bk, bn)`` blocks, and the chip's compiler takes all
    three."""
    from horovod_tpu.ops import grouped_matmul as gm

    w_itemsize = jnp.dtype(w_dtype).itemsize
    whole = gm._gmm_bytes(gm.TILE_ROWS, 2048, 2048, 2, w_itemsize)
    assert (whole > gm._VMEM_BUDGET) == (w_dtype == "float32")

    def fwd_bwd(rows, w, sizes, ct):
        out, vjp = jax.vjp(lambda r, w: gm.grouped_dot(
            r, w, sizes, interpret=False), rows, w)
        return (out, *vjp(ct))

    text = _compiled_text(fwd_bwd, *_shapes_on(one_chip, (
        jax.ShapeDtypeStruct((16384, 2048), jnp.bfloat16),
        jax.ShapeDtypeStruct((8, 2048, 2048), jnp.dtype(w_dtype)),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((16384, 2048), jnp.bfloat16))))
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert len(re.findall(r"hvd_moe_gmm[\w.]* = ", text)) == 2
    assert len(re.findall(r"hvd_moe_tgmm[\w.]* = ", text)) == 1


@pytest.fixture
def expert_layer_as_on_a_tpu(monkeypatch):
    """``parallel/moe.py`` traced as a TPU traces it (the kernels, not what a
    CPU runs in their place), and no such trace left behind: the passes' jit
    caches are keyed by shapes, not by the backend they were traced for."""
    from horovod_tpu.parallel import moe

    def traced_anew():
        moe._forward.clear_cache()
        moe._backward.clear_cache()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    traced_anew()
    yield moe
    traced_anew()


def test_a_fitting_expert_layer_runs_no_product_of_its_forward_again(
        one_chip, expert_layer_as_on_a_tpu):
    """``parallel/moe.py:routed_experts`` at ``sdar-moe-ep8-s4096``'s layer
    (16,384 tokens, top-8 of 128, 16 held, a 36,864-row buffer), value and
    gradients: where the rows fit, three products forward and, from what
    the forward kept, three ``d rows`` and three ``dW`` backward; in parts
    as before, each part's forward made again."""
    moe = expert_layer_as_on_a_tpu

    def step(x, router, *kernels):
        return jax.value_and_grad(lambda *a: jnp.sum(moe.routed_experts(
            *a, top_k=8, capacity_factor=2.25)[0].astype(jnp.float32) ** 2),
            argnums=(0, 1, 2, 3, 4))(x, router, *kernels)

    text = _compiled_text(step, *_shapes_on(one_chip, (
        jax.ShapeDtypeStruct((16384, 2048), jnp.bfloat16),
        jax.ShapeDtypeStruct((2048, 128), jnp.float32),
        jax.ShapeDtypeStruct((16, 2048, 768), jnp.float32),
        jax.ShapeDtypeStruct((16, 2048, 768), jnp.float32),
        jax.ShapeDtypeStruct((16, 768, 2048), jnp.float32))))

    def calls(kernel, side):
        return len(re.findall(
            rf"{kernel}[\w.]* = [^\n]*cond/branch_{side}_fun", text))

    assert (calls("hvd_moe_gmm", 1), calls("hvd_moe_tgmm", 1)) == (6, 3)
    assert (calls("hvd_moe_gmm", 0), calls("hvd_moe_tgmm", 0)) == (9, 3)
    assert "ragged-dot" not in text


# (tokens, d, f, experts, held, top_k, capacity_factor) of a chip's expert
# layer in the four cells that have one.
EXPERT_LAYERS = {
    "sdar-moe-ep8-s4096": (16384, 2048, 768, 128, 16, 8, 2.25),
    "laguna-swa-ep32-s16384": (16384, 3072, 1024, 256, 8, 10, 2.0),
    "joyai-mla-ep16-s16384": (16384, 2048, 768, 256, 16, 8, 2.0),
    "zaya1-moe-ep2-s16384": (16384, 2048, 2048, 16, 8, 1, 2.0)}


def _loops_scattering_into(text: str, shape: str) -> list:
    """The ``while`` bodies of a compiled module (and what they call) that
    hold a ``scatter`` whose result is ``shape``."""
    bodies = dict(re.findall(r"\n(?:ENTRY )?%?([\w.-]+) [^\n]*\{\n(.*?)\n\}",
                             text, flags=re.S))

    def scatters(name, seen):
        if name in seen or name not in bodies:
            return False
        seen.add(name)
        body = bodies[name]
        if re.search(rf"= {re.escape(shape)}[^\n]* scatter\(", body):
            return True
        return any(scatters(callee, seen) for callee in re.findall(
            r"(?:calls|to_apply|body|condition|branch_computations=\{)"
            r"=?%?([\w.-]+)", body))

    return [body for body in re.findall(r"body=%?([\w.-]+)", text)
            if scatters(body, set())]


@pytest.mark.parametrize("rows,tokens,d,dtype", [
    (36864, 16384, 2048, "bfloat16"), (10240, 16384, 3072, "bfloat16"),
    (8192, 4096, 2048, "float32"), (1100, 300, 256, "bfloat16"),
    (1100, 300, 256, "float32"), (96, 40, 128, "bfloat16")],
    ids=["sdar-and-joyai-s-width", "laguna-s-width", "float32-rows",
         "no-whole-number-of-either-tile", "the-same-in-float32",
         "smaller-than-either-tile"])
def test_the_sum_by_token_compiles(one_chip, rows, tokens, d, dtype):
    """``ops/grouped_matmul.py:sum_by_token`` alone: a gather into token
    order (one for each prefix of the buffer it may cover) and one
    ``hvd_moe_sum_rows``, at the cells' widths, for float32 rows (summed at
    the highest precision) and where the buffer's last row tile and the
    tokens' last tile are partial."""
    from horovod_tpu.ops import grouped_matmul as gm

    order = gm.TokenOrder(*_shapes_on(one_chip, (
        jax.ShapeDtypeStruct((rows,), jnp.int32),) * 2))
    text = _compiled_text(
        lambda r, o: gm.sum_by_token(r, o, tokens, interpret=False),
        jax.ShapeDtypeStruct((rows, d), jnp.dtype(dtype), sharding=one_chip),
        order)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"hvd_moe_sum_rows[\w.]* = ", text)) == 1
    # (the schedule's ``jnp.repeat`` is a scatter of a few int32)
    assert not re.search(rf"= \w+\[\d+,{d}\][^\n]* scatter\(", text)


# (rows of the table held, d, ids a step): the claimed cell's, the widest
# rows (and a table of no whole number of sublanes), the largest table.
EMBEDDINGS = {"phi4flash": (100032, 2560, 16384),
              "sala": (18362, 4096, 16384), "zaya": (131136, 2048, 16384)}


@pytest.mark.parametrize("rows,d,ids", EMBEDDINGS.values(),
                         ids=EMBEDDINGS.keys())
def test_the_embedding_s_gradient_is_one_kernel_under_hvd_embed(one_chip,
                                                                rows, d, ids):
    """``ops/embedding.py:embed_lookup``'s table gradient as a model makes it
    (a float32 table, bfloat16 rows, inside ``hvd_embed``), compiled for the
    chip: one ``embed_grad_sum_rows`` and no ``scatter`` into an array of the
    table's width (the schedule's ``jnp.repeat`` is one of a few int32).  The
    kernel's ``op_name`` lies under ``hvd_embed`` and ``benchmark/
    scope_ledger.py`` counts it there: that is why its name does not begin
    with ``hvd_``, which would make it a layer of its own that no metric
    reads (``phi_embed_ms`` and ``sala_embed_ms`` select ``^hvd_embed$``)."""
    from benchmark.scope_ledger import layer_of_scope
    from horovod_tpu.ops.embedding import KERNEL_NAME, embed_lookup

    def d_table(table, tokens, g):
        def loss(table):
            with jax.named_scope("hvd_embed"):
                x = embed_lookup(table, tokens, jnp.bfloat16, interpret=False)
            return jnp.sum(x.astype(jnp.float32) * g)
        return jax.grad(loss)(table)

    text = _compiled_text(d_table, *_shapes_on(one_chip, (
        jax.ShapeDtypeStruct((rows, d), jnp.float32),
        jax.ShapeDtypeStruct((1, ids), jnp.int32),
        jax.ShapeDtypeStruct((1, ids, d), jnp.float32))))
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]+)"', text)
    assert len(kernels) == 1 and f"/{KERNEL_NAME}/" in kernels[0], kernels
    assert "transpose(" in kernels[0]
    assert layer_of_scope(kernels[0]) == "hvd_embed"
    assert not re.search(rf"= \w+\[\d+,{d}\][^\n]* scatter\(", text)


@pytest.mark.parametrize("cell", EXPERT_LAYERS)
def test_the_expert_layer_sums_its_rows_back_by_one_kernel_a_pass(
        one_chip, expert_layer_as_on_a_tpu, cell):
    """``parallel/moe.py:dispatch_experts`` at each cell's layer, value and
    gradients, compiled for the chip: the fitting side holds two
    ``hvd_moe_sum_rows`` (the forward's sum of the weighted rows, the
    backward's of ``d rows``), the grouped products' counts are what they
    were, and no ``scatter`` into a ``[tokens, d]`` array is left inside a
    ``while``.  ZAYA's top-1 layer gathers its sum and holds neither."""
    moe = expert_layer_as_on_a_tpu
    tokens, d, f, experts, held, top_k, factor = EXPERT_LAYERS[cell]

    def step(x, chosen, weights, *kernels):
        return jax.value_and_grad(lambda x, weights, *k: jnp.sum(
            moe.dispatch_experts(
                x, chosen, weights, *k, first_expert=0, experts_total=experts,
                capacity_factor=factor).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2, 3, 4))(x, weights, *kernels)

    text = _compiled_text(step, *_shapes_on(one_chip, (
        jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((tokens, top_k), jnp.int32),
        jax.ShapeDtypeStruct((tokens, top_k), jnp.float32),
        jax.ShapeDtypeStruct((held, d, f), jnp.float32),
        jax.ShapeDtypeStruct((held, d, f), jnp.float32),
        jax.ShapeDtypeStruct((held, f, d), jnp.float32))))

    def calls(kernel, *sides):
        """The calls of ``kernel`` whose ``op_name`` goes through these sides
        of the conds it lies in, outermost first."""
        found = re.findall(rf"{kernel}[\w.]* = [^\n]*", text)
        return sum(tuple(map(int, re.findall(r"cond/branch_(\d+)_fun", line))
                         )[:len(sides)] == sides for line in found)

    assert _loops_scattering_into(text, f"bf16[{tokens},{d}]") == []
    if top_k == 1:          # no cond: the buffer holds every row there is
        assert "cond/branch" not in text
        assert calls("hvd_moe_sum_rows") == 0
        assert (calls("hvd_moe_gmm"), calls("hvd_moe_tgmm")) == (6, 3)
        return
    fits, in_parts = 1, 0
    assert calls("hvd_moe_sum_rows", fits) == 2
    assert (calls("hvd_moe_gmm", fits), calls("hvd_moe_tgmm", fits)) == (6, 3)
    assert (calls("hvd_moe_gmm", in_parts),
            calls("hvd_moe_tgmm", in_parts)) == (9, 3)


HEADS = {"zaya": (2048, 131136), "jamba": (2560, 16384)}


@pytest.mark.parametrize("d,rows", HEADS.values(), ids=HEADS.keys())
def test_the_tied_head_s_kernel_compiles_at_both_cells_shapes(one_chip, d,
                                                              rows):
    """``ops/tied_head.py`` at a block of ``zaya1-moe-ep2-s16384`` (``[2048,
    2048] x [131136, 2048]``, a last tile of 64 rows) and of
    ``jamba2-ssm-tp4-s16384`` (``[2048, 2560] x [16384, 2560]``), bfloat16:
    the chip's compiler takes it within the VMEM it asks for, the whole block
    of tokens standing, and nothing but the kernel is in the program: the
    logits leave as the kernel wrote them."""
    from horovod_tpu.ops import tied_head as th

    tiles = th.plan(2048, d, rows, 2)
    assert tiles.tokens == 2048
    assert th._vmem_bytes(*tiles, d, 2) <= th._VMEM_BUDGET < th._VMEM_LIMIT
    compiled = jax.jit(lambda x, table: th._head_logits(
        x, table, tiles, False)).lower(*_shapes_on(one_chip, (
            jax.ShapeDtypeStruct((2048, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((rows, d), jnp.bfloat16)))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"hvd_head_logits[\w.]* = ", text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def _blocked_head_texts(one_chip, monkeypatch, loss, matrix_shape, d):
    """The compiled value and gradients of a blocked head's ``loss`` over
    16,384 tokens of width ``d``, with the kernel and with ``_block_nll``'s
    ``jax.numpy``."""
    from horovod_tpu.models import losses
    from horovod_tpu.ops import tied_head as th

    def compiled(kernel: bool):
        monkeypatch.setattr(losses, "head_logits", functools.partial(
            th.head_logits, interpret=False) if kernel
            else lambda x, table: None)
        # A function of its own a side: jit's cache is keyed on it.
        return _compiled_text(
            lambda *a: jax.value_and_grad(loss, argnums=(0, 1))(*a),
            *_shapes_on(one_chip, (
                jax.ShapeDtypeStruct((16384, d), jnp.bfloat16),
                jax.ShapeDtypeStruct(matrix_shape, jnp.float32),
                jax.ShapeDtypeStruct((16384,), jnp.int32),
                jax.ShapeDtypeStruct((16384,), jnp.float32))))

    return compiled(True), compiled(False)


def _logits_census(text, rows, block):
    """The instructions outside fusions' bodies that make an array of a
    block's logits' size, by opcode, and the products' result types."""
    made, products = [], []
    for name, lines in _computations(text).items():
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
            if not m:
                continue
            result, op = m.groups()
            if op == "convolution":
                products.append(result.split("{")[0])
            elif ("fused" not in name and op not in (
                    "parameter", "get-tuple-element", "bitcast", "tuple")
                  and re.match(rf"\w+\[({rows},{block}|{block},{rows})\]",
                               result)):
                made.append(op)
    return sorted(made), sorted(products)


def _head_products_in_the_forward(text):
    """The products a compiled step holds under ``hvd_lm_head`` (the
    kernel's call and XLA's own), every one of them in the forward pass: the
    blocked head's reverse mode is by hand, ``dx`` and the matrix's gradient
    are made beside the loss and the backward scales them."""
    names = [m.group(1) for line in text.splitlines()
             if " convolution(" in line or "tpu_custom_call" in line
             for m in [re.search(r'op_name="([^"]*/hvd_lm_head/[^"]*)"', line)]
             if m]
    assert not [n for n in names if "transpose(" in n], names
    return names


def _row_reductions(text, block):
    """XLA's own reductions to one float32 a token of a block."""
    return [line for line in text.splitlines()
            if re.search(rf"= f32\[{block}\]\S* reduce\(", line)]


@pytest.mark.parametrize("d,rows", HEADS.values(), ids=HEADS.keys())
def test_the_tied_head_s_step_reads_the_kernel_s_logits_as_they_are(
        one_chip, monkeypatch, d, rows):
    """Value and gradients of ``tied_head_cross_entropy`` over 16,384 tokens
    at both cells' widths with the kernel on, a block as many tokens as
    ``HEAD_BLOCK_BYTES`` of float32 logits hold (2,048 of ZAYA's 131,136
    rows in a scan of eight, all 16,384 of Jamba's 16,384 rows and no scan):
    the program holds one ``hvd_head_logits``, no pass of XLA's own over a
    block's logits for the row statistics (no reduction to one float32 a
    token), no ``copy`` or ``transpose`` of a block's logits or ``d logits``
    (the kernel writes ``[V, T]``, the layout the two backward products
    read), and those two products as they were: ``dx`` and the accumulation
    into the float32 ``[V, d]``."""
    from horovod_tpu.models import losses

    blocks = losses._head_blocks(16384, rows)
    assert blocks == {131136: 8, 16384: 1}[rows]
    block = 16384 // blocks
    kernel, plain = _blocked_head_texts(
        one_chip, monkeypatch, losses.tied_head_cross_entropy, (rows, d), d)
    assert len(re.findall(r"hvd_head_logits[\w.]* = ", kernel)) == 1
    assert ("while(" in kernel) == (blocks > 1)
    assert "hvd_head_logits" not in plain
    made, products = _logits_census(kernel, rows, block)
    plain_made, plain_products = _logits_census(plain, rows, block)
    assert "copy" not in made and "transpose" not in made, made
    # XLA's own logits' product is gone and the two backward ones stand.
    logits = f"f32[{block},{rows}]"
    assert plain_products.count(logits) == 1 and logits not in products
    plain_products.remove(logits)
    assert products == plain_products
    assert f"f32[{rows},{d}]" in products and f"f32[{block},{d}]" in products
    # No reduction over a block's logits is XLA's any more.
    assert _row_reductions(kernel, block) == []
    assert _row_reductions(plain, block)


UNTIED_HEADS = {"laguna": (3072, 12544), "joyai": (2048, 16160)}


@pytest.mark.parametrize("d,rows", UNTIED_HEADS.values(),
                         ids=UNTIED_HEADS.keys())
def test_a_head_of_its_own_takes_the_blocked_head_s_path(one_chip,
                                                         monkeypatch, d, rows):
    """Value and gradients of ``head_cross_entropy`` over 16,384 tokens at
    ``laguna-swa-ep32-s16384``'s and ``joyai-mla-ep16-s16384``'s heads (a
    float32 kernel ``[d, V]``, ``x`` in bfloat16) with the kernel on: all the
    tokens are one block by their logits' bytes, so the program holds no loop
    and ``hvd_head_logits`` once; **nothing else makes an array of the
    logits' size**, in any dtype (no ``d logits`` array: it is made inside
    the two backward products' fusions; no ``copy`` or ``transpose`` of the
    logits); no reduction of XLA's own to one float32 a token (no pass for
    the row maxima or the sums of exponentials); the kernel's gradient leaves
    the product float32 in the parameter's own ``[d, V]``, and the kernel is
    turned to the ``[V, d]`` rows the blocks read once, by the cast."""
    from horovod_tpu.models import losses

    blocks = losses._head_blocks(16384, rows)
    block = 16384 // blocks
    kernel, plain = _blocked_head_texts(
        one_chip, monkeypatch, losses.head_cross_entropy, (d, rows), d)
    assert len(re.findall(r"hvd_head_logits[\w.]* = ", kernel)) == 1
    assert ("while(" in kernel) == (blocks > 1)
    assert "hvd_head_logits" not in plain
    made, products = _logits_census(kernel, rows, block)
    assert made == [], made
    assert f"f32[{block},{rows}]" not in products
    assert f"f32[{block},{rows}]" in _logits_census(plain, rows, block)[1]
    assert f"f32[{d},{rows}]" in products and f"f32[{block},{d}]" in products
    assert _row_reductions(kernel, block) == []
    assert _row_reductions(plain, block)
    # The one pass over the kernel that is no product: float32 [d, V] in,
    # bfloat16 rows of d out.
    turned = [m.group(1) for name, lines in _computations(kernel).items()
              if "fused" not in name for line in lines
              for m in [re.search(
                  rf"= bf16\[(?:{d},{rows}|{rows},{d})\]\S* (\w+)\(", line)]
              if m and m.group(1) not in ("parameter", "bitcast")]
    assert len(turned) == 1, turned


def test_selective_scan_compiles_at_the_jamba_cell_s_shapes_in_shard_map(
        topo):
    """``ops/selective_scan.py`` at ``jamba2-ssm-tp4-s16384``'s shapes (one
    sequence of 16,384 steps, 1,280 channels, 16 states, ``u`` in bfloat16 and
    ``dt``, ``B``, ``C`` in float32), forward and backward, inside a
    ``shard_map`` over one described chip with ``A`` and ``D`` held whole:
    one call of each kernel by name, and ``shard_map``'s types accept what
    the kernels declare (``vma`` on every output; no loop in a kernel
    carries a value read from an operand's ref beside the kernel's own
    arithmetic, which the interpreter cannot show)."""
    from jax import shard_map

    from horovod_tpu.ops.selective_scan import selective_scan

    mesh = Mesh(np.asarray(topo.devices[:1]), ("hvd",))
    rows, whole = P("hvd"), P()
    specs = (rows, rows, whole, rows, rows, whole)

    def total(*operands):
        y = selective_scan(*operands, interpret=False)
        return jax.lax.psum(jnp.sum(y.astype(jnp.float32)), "hvd")

    shapes = [((1, 16384, 1280), jnp.bfloat16), ((1, 16384, 1280),
                                                 jnp.float32),
              ((1280, 16), jnp.float32), ((1, 16384, 16), jnp.float32),
              ((1, 16384, 16), jnp.float32), ((1280,), jnp.float32)]
    text = jax.jit(shard_map(
        jax.value_and_grad(total, argnums=tuple(range(6))), mesh=mesh,
        in_specs=specs, out_specs=(whole, specs))).lower(*(
            jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for (shape, dtype), spec in zip(shapes, specs))
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert len(re.findall(r"hvd_ssm_scan_fwd[\w.]* = ", text)) == 1
    assert len(re.findall(r"hvd_ssm_scan_bwd[\w.]* = ", text)) == 1


def test_a_jamba_step_relays_none_of_its_paired_kernels(topo, monkeypatch):
    """One Mamba block with its feed-forward at ``jamba2-ssm-tp4-s16384``'s
    widths (2,560 wide; 1,280 channels and 2,048 columns held) on a short
    sequence, a whole step: gradients, ``optax.adamw`` through
    ``DistributedOptimizer``, donated state, ``shard_map`` over one described
    chip.  ``in_proj`` and ``gate_up`` cross the step's boundary 2-D,
    ``[2560, 2 * held]``, and the step holds no ``copy`` of a kernel's size:
    declared ``(2, held)`` the parameter, both moments and the gradient were
    ``[2560, 2, held]``, the middle 2 tiled ``T(2,128)``, and every step
    relaid each of them on the way in and out again (PERF.md section 6, PR
    49: 10 GB a step in the cell).  Their weight gradients are float32
    products in the leaves' own layout: left to itself the compiler lays a
    float32 one out ``{0,1}`` and relays kernel and moments to match."""
    import optax
    from jax import shard_map

    import horovod_tpu as hvd
    from horovod_tpu.models import jamba

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        jamba.JAMBA2_3B, mamba_d_inner_held=1280, num_heads_held=5,
        intermediate_size_held=2048)
    block = jamba.JambaBlock(cfg, attention=False)
    tx = hvd.DistributedOptimizer(optax.adamw(2e-7), axis_name="hvd")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("hvd",))

    def train_step(variables, opt_state, x):
        loss, grads = jax.value_and_grad(lambda v: jnp.sum(
            block.apply(v, x).astype(jnp.float32) ** 2))(variables)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return (optax.apply_updates(variables, updates), opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    x = jax.ShapeDtypeStruct((1, 1024, cfg.hidden_size), jnp.bfloat16)
    variables = jax.eval_shape(block.init, jax.random.key(0), x)
    state = (variables, jax.eval_shape(tx.init, variables))
    paired = {"in_proj": (2560, 2 * 1280), "gate_up": (2560, 2 * 2048)}
    seen = [leaf.shape for path, leaf in
            jax.tree_util.tree_leaves_with_path(state)
            if any(getattr(k, "key", None) in paired for k in path)]
    # The parameter, the optimizer's accumulator and AdamW's two moments.
    assert sorted(seen) == sorted(list(paired.values()) * 4)
    text = jax.jit(
        shard_map(train_step, mesh=mesh, in_specs=(P(), P(), P("hvd")),
                  out_specs=(P(), P(), P())),
        donate_argnums=(0, 1)).lower(
            *_shapes_on(NamedSharding(mesh, P()), state),
            _shapes_on(NamedSharding(mesh, P("hvd")), x)).compile().as_text()
    assert len(re.findall(r"hvd_ssm_scan_fwd[\w.]* = ", text)) == 1
    sizes = {math.prod(shape) for shape in paired.values()}
    copies = [
        line.strip()[:160] for line in text.splitlines()
        for m in [re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                           r"copy\(", line)]
        if m and math.prod(map(int, m.group(1).split(","))) in sizes]
    assert not copies, copies
    # The weight gradients: two products a kernel, each float32 and row-major
    # as the leaf it updates (``paired_dot``'s backward), none in bfloat16.
    products = re.findall(
        r" = (\w+)\[2560,(?:1280|2048)\](\{[\d,]+)\S* convolution\(", text)
    assert sorted(products) == [("f32", "{1,0")] * 4, products


def _computations(text: str) -> dict:
    """A compiled module's computations by name, each as its lines."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif line == "}":
            name = None
        elif name:
            out[name].append(line)
    return out


def test_a_checkpointed_jamba_block_keeps_its_paired_products(one_chip,
                                                              monkeypatch):
    """A Mamba block and an attention block at ``jamba2-ssm-tp4-s16384``'s
    widths on a short sequence under the model's own checkpoint
    (``checkpoint_blocks``: ``save_only_these_names(*CHECKPOINT_NAMES)``),
    forward and backward.  The backward's second forward holds no product of
    ``in_proj`` or ``gate_up`` and no flash forward (the scan's it runs
    again: ``benchmark/families/jamba.py:least_calls``), and ``down``'s
    forward product reads ``silu(gate) * up`` as an array: with both halves
    kept for the backward the compiler made that product an operand computed
    inside ``down``'s, which took 22.6 ms a step of the cell where reading
    the array takes 13.4 (PERF.md section 5, PR 52)."""
    from benchmark.families.jamba import kernel_calls
    from horovod_tpu.models import jamba

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        jamba.JAMBA2_3B, num_layers=2, attn_layer_period=2,
        attn_layer_offset=1, mamba_d_inner_held=1280, num_heads_held=5,
        intermediate_size_held=2048, vocab_size_held=1024,
        checkpoint_blocks=True)
    model = jamba.Jamba(cfg)
    ids = jax.ShapeDtypeStruct((1, 1024), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    text = _compiled_text(
        jax.grad(lambda v, ids: jnp.sum(model.apply(
            v, ids, method="hidden").astype(jnp.float32) ** 2)),
        *_shapes_on(one_chip, (variables, ids)))
    assert kernel_calls(text) == {
        "hvd_ssm_scan_fwd": 2, "hvd_ssm_scan_bwd": 1, "hvd_flash_fwd": 1,
        "hvd_flash_dq": 1, "hvd_flash_dkv": 1}
    products = [line for line in text.splitlines()
                if " convolution(" in line
                and re.search(r"(in_proj|gate_up)/dot_general", line)]
    again = [line.strip()[:200] for line in products
             if "rematted_computation" in line]
    assert len(products) >= 6 and not again, again
    # ``down``'s forward: the computation that holds its product holds no
    # instruction of the feed-forward's ``silu(gate) * up``.
    forward = re.compile(
        r'op_name="jit\([^"]*\)/jvp\([^"]*\)/[^"]*/mlp/down/[^"]*dot_general"')
    holders = [(name, lines) for name, lines in _computations(text).items()
               if any(" convolution(" in line and forward.search(line)
                      and "transpose(" not in line for line in lines)]
    assert len(holders) == 2, [name for name, _ in holders]
    for name, lines in holders:
        inside = [line.strip()[:160] for line in lines
                  if re.search(r'/mlp/(mul|silu|logistic)[/"]', line)]
        assert not inside, (name, inside)


def test_a_laguna_step_copies_nothing_of_q_s_size_round_its_kernels(
        topo, monkeypatch):
    """The first two layers of ``laguna-swa-ep32-s16384`` at its widths (a
    full layer of 6 query heads with the dense feed-forward, a sliding layer
    of 9 with the mixture and the shared expert; one key/value head, 1,536
    dense columns, 8 experts and 12,544 rows held) on a short sequence, a
    whole step: gradients, ``optax.adamw`` through ``DistributedOptimizer``,
    donated state, ``shard_map`` over one described chip.  The banded kernels
    are on the sliding layer and the un-banded ones on the full layer, one
    call each by name; under the attention scope the step holds **no copy
    and no transpose as large as q** (the rotary turn and the gate work on
    ``[B, S, heads x 128]`` as it lies: tiled over the heads, the cos / sin
    tables were two float32 copies of q's size a layer kind); the two paired
    kernels cross the step's boundary 2-D and nothing of their size is
    copied."""
    import optax
    from jax import shard_map

    import horovod_tpu as hvd
    from horovod_tpu.models import laguna

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        laguna.LAGUNA_S_2_1, num_layers=2, num_kv_heads_held=1,
        num_heads_per_layer_held=(6, 9), dense_columns_held=1536,
        num_experts_held=8, vocab_size_held=12544)
    model, seq = laguna.Laguna(cfg), 1024
    tx = hvd.DistributedOptimizer(optax.adamw(2e-7), axis_name="hvd")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("hvd",))

    def train_step(variables, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda v: laguna.lm_loss(model, v, ids))(variables)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return (optax.apply_updates(variables, updates), opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    state = (variables, jax.eval_shape(tx.init, variables))
    paired = {"gate_up": (3072, 2 * 1536), "shared_gate_up": (3072, 2 * 1024)}
    seen = [leaf.shape for path, leaf in
            jax.tree_util.tree_leaves_with_path(state)
            if any(getattr(k, "key", None) in paired for k in path)]
    assert sorted(seen) == sorted(list(paired.values()) * 4)
    text = jax.jit(
        shard_map(train_step, mesh=mesh, in_specs=(P(), P(), P("hvd")),
                  out_specs=(P(), P(), P())),
        donate_argnums=(0, 1)).lower(
            *_shapes_on(NamedSharding(mesh, P()), state),
            _shapes_on(NamedSharding(mesh, P("hvd")), ids)).compile().as_text()
    for kernel in ("fwd", "dq", "dkv"):
        assert len(re.findall(rf"hvd_flash_swa_{kernel}[\w.]* = ", text)) == 1
        assert len(re.findall(rf"hvd_flash_{kernel}[\w.]* = ", text)) == 1
    # The loss goes through the blocked head: its kernel once, one block.
    assert len(re.findall(r"hvd_head_logits[\w.]* = ", text)) == 1
    products = _head_products_in_the_forward(text)
    assert len(products) == 3, products
    moved = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                       r"(copy|transpose)\(")
    least = seq * 6 * 128           # the full layer's q, the smaller of two
    round_the_kernels = [
        line.strip()[:200] for line in text.splitlines()
        for m in [moved.match(line)]
        if m and math.prod(map(int, m.group(1).split(","))) >= least
        and "/attn/" in line]
    assert not round_the_kernels, round_the_kernels
    sizes = {math.prod(shape) for shape in paired.values()}
    copies = [line.strip()[:160] for line in text.splitlines()
              for m in [moved.match(line)]
              if m and math.prod(map(int, m.group(1).split(","))) in sizes]
    assert not copies, copies


def test_a_joyai_step_copies_nothing_round_its_paired_kernels(topo,
                                                              monkeypatch):
    """The first two layers of ``joyai-mla-ep16-s16384`` at its widths (the
    dense layer and an expert layer; 4 heads of 128 + 64 / 128, 896 dense
    columns, 16 experts and 16,160 rows held) on a short sequence, a whole
    step as ``families/joyai.py`` builds it: gradients, ``optax.adamw``
    through ``DistributedOptimizer``, donated state, ``shard_map`` over one
    described chip.  Each layer holds one call of each paired kernel by name
    and no ``hvd_flash_relayout``; under the attention scope the step holds
    **no copy and no transpose as large as q**, and outside the rotary turn
    and the projections nothing as large as q_rope is broadcast, padded or
    concatenated (``k_rope`` reaches the kernels as its one [B, S, 64] array,
    never repeated a head); the paired kernels (``kv_b``, ``gate_up``,
    ``shared_gate_up``) cross the step's boundary 2-D and nothing of their
    size is copied."""
    import optax
    from jax import shard_map

    import horovod_tpu as hvd
    from benchmark.families.joyai import kernel_calls
    from horovod_tpu.models import joyai

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        joyai.JOYAI_LLM_FLASH, num_layers=2, num_heads_held=4,
        dense_columns_held=896, num_experts_held=16, vocab_size_held=16160)
    model, seq = joyai.JoyAI(cfg), 1024
    tx = hvd.DistributedOptimizer(optax.adamw(2e-7), axis_name="hvd")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("hvd",))

    def train_step(variables, opt_state, ids):
        rest = {k: v for k, v in variables.items() if k != "params"}
        params = {"params": variables["params"]}
        loss, grads = jax.value_and_grad(
            lambda p: joyai.lm_loss(model, {**rest, **p}, ids))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return ({**rest, **optax.apply_updates(params, updates)}, opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    state = (variables, jax.eval_shape(
        lambda v: tx.init({"params": v["params"]}), variables))
    paired = {"kv_b": (512, 2 * 512), "gate_up": (2048, 2 * 896),
              "shared_gate_up": (2048, 2 * 768)}
    seen = [leaf.shape for path, leaf in
            jax.tree_util.tree_leaves_with_path(state)
            if any(getattr(k, "key", None) in paired for k in path)]
    assert sorted(seen) == sorted(
        [paired["kv_b"]] * 8 + [paired["gate_up"]] * 4
        + [paired["shared_gate_up"]] * 4)
    text = jax.jit(
        shard_map(train_step, mesh=mesh, in_specs=(P(), P(), P("hvd")),
                  out_specs=(P(), P(), P())),
        donate_argnums=(0, 1)).lower(
            *_shapes_on(NamedSharding(mesh, P()), state),
            _shapes_on(NamedSharding(mesh, P("hvd")), ids)).compile().as_text()
    assert kernel_calls(text) == dict.fromkeys(
        ("hvd_flash_mla_fwd", "hvd_flash_mla_dq", "hvd_flash_mla_dkv"), 2)
    assert "hvd_flash_relayout" not in text
    # The loss goes through the blocked head: its kernel once, one block.
    assert len(re.findall(r"hvd_head_logits[\w.]* = ", text)) == 1
    products = _head_products_in_the_forward(text)
    assert len(products) == 3, products
    made = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                      r"(copy|transpose|broadcast|pad|concatenate)\(")
    q, q_rope = seq * 4 * 128, seq * 4 * 64
    round_the_kernels = [
        line.strip()[:200] for line in text.splitlines()
        for m in [made.match(line)]
        if m and "/attn/" in line and (
            math.prod(map(int, m.group(1).split(","))) >= q
            if m.group(2) in ("copy", "transpose") else
            math.prod(map(int, m.group(1).split(","))) >= q_rope
            and not re.search(r"hvd_(rope|attn_proj|mla_latent)", line))]
    assert not round_the_kernels, round_the_kernels
    sizes = {math.prod(shape) for shape in paired.values()}
    copies = [line.strip()[:160] for line in text.splitlines()
              for m in [made.match(line)]
              if m and m.group(2) == "copy"
              and math.prod(map(int, m.group(1).split(","))) in sizes]
    assert not copies, copies


def test_lightning_and_the_selected_walk_compile_at_the_sala_cell_s_shapes(
        one_chip):
    """``ops/lightning_attention.py`` and ``ops/flash_select.py`` at
    ``sala-sparse-linear-tp4-s16384``'s shapes (8 heads of 128 over 16,384
    rows; 8 query heads on 1 key/value head whose k and v lie whole in VMEM,
    the walk's lists scalar-prefetched, a branch on a listed step's kind
    inside the walk's loop): value and gradients, which only Mosaic can
    refuse."""
    from horovod_tpu.ops import flash_select as fs
    from horovod_tpu.ops import lightning_attention as la

    q = jax.ShapeDtypeStruct((1, 16384, 8, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 1, 128), jnp.bfloat16,
                              sharding=one_chip)
    slopes = jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one_chip)
    bits = jax.ShapeDtypeStruct((1, 1, 8, 16384), jnp.int32,
                                sharding=one_chip)

    def lightning(q, k, v, a):
        return jnp.sum(la.lightning_attention(
            q, k, v, a, interpret=False).astype(jnp.float32) ** 2)

    def selected(q, k, v, bits):
        out, lse = fs.flash_select(q, k, v, fs.Selection(bits, 64),
                                   interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(lse)

    text = _compiled_text(jax.grad(lightning, argnums=(0, 1, 2)), q, q, q,
                          slopes)
    for name in ("hvd_lightning_fwd", "hvd_lightning_dq", "hvd_lightning_dkv"):
        assert len(re.findall(rf"{name}[\w.]* = ", text)) == 1, name
    text = _compiled_text(jax.grad(selected, argnums=(0, 1, 2)), q, kv, kv,
                          bits)
    for name in ("hvd_flash_sel_fwd", "hvd_flash_sel_dq", "hvd_flash_sel_dkv"):
        assert len(re.findall(rf"{name}[\w.]* = ", text)) == 1, name


def test_a_sala_step_copies_nothing_of_q_s_size_round_its_kernels(
        topo, monkeypatch):
    """The first two layers of ``sala-sparse-linear-tp4-s16384`` at its
    widths (the sparse layer and a lightning layer; 8 heads of each held,
    4,096 SwiGLU columns, 18,362 rows) at its 16,384 tokens, which choose, a
    whole step as ``families/sala.py`` builds it: gradients, ``optax.adamw``
    through ``DistributedOptimizer``, donated state, the chosen bits handed
    out, ``shard_map`` over one described chip.  The step holds one call of
    each of the six kernels by name, the head norms' op four times each way,
    no ``hvd_flash_relayout``, and under the attention scope **no copy and no
    transpose as large as q**; the gate/up pair crosses the step's boundary
    2-D and nothing of its size is copied."""
    import optax
    from jax import shard_map

    import horovod_tpu as hvd
    from benchmark.families.sala import _loss_and_choices, kernel_calls
    from horovod_tpu.models import sala

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        sala.MINICPM_SALA, num_layers=2, lightning_heads_held=8,
        first_lightning_head=24, num_heads_held=8, num_kv_heads_held=1,
        intermediate_size_held=4096, vocab_size_held=18362)
    model, seq = sala.Sala(cfg), 16384
    tx = hvd.DistributedOptimizer(optax.adamw(2e-7), axis_name="hvd")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("hvd",))

    def train_step(variables, opt_state, chosen, ids):
        del chosen
        (loss, chosen), grads = jax.value_and_grad(
            lambda p: _loss_and_choices(model, [0], p, ids),
            has_aux=True)(variables)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return (optax.apply_updates(variables, updates), opt_state, chosen,
                hvd.allreduce(loss, axis_name="hvd"))

    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    state = (variables, jax.eval_shape(tx.init, variables))
    chosen = jax.ShapeDtypeStruct((1, 1, 1, 8, seq), jnp.int32)
    by_sequence = NamedSharding(mesh, P(None, "hvd"))
    text = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(None, "hvd"), P("hvd")),
                  out_specs=(P(), P(), P(None, "hvd"), P())),
        donate_argnums=(0, 1, 2)).lower(
            *_shapes_on(NamedSharding(mesh, P()), state),
            _shapes_on(by_sequence, chosen),
            _shapes_on(NamedSharding(mesh, P("hvd")), ids)).compile().as_text()
    assert kernel_calls(text) == dict.fromkeys(
        ("hvd_lightning_fwd", "hvd_lightning_dq", "hvd_lightning_dkv",
         "hvd_flash_sel_fwd", "hvd_flash_sel_dq", "hvd_flash_sel_dkv"), 1)
    for name in ("hvd_qk_norm_rope_fwd", "hvd_qk_norm_rope_bwd"):
        assert len(re.findall(rf"{name}[\w.]* = ", text)) == 4, name
    assert "hvd_flash_relayout" not in text
    assert len(re.findall(r"hvd_head_logits[\w.]* = ", text)) == 1
    made = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                      r"(copy|transpose)\(")
    q = seq * 8 * 128
    round_the_kernels = [
        line.strip()[:200] for line in text.splitlines()
        for m in [made.match(line)]
        if m and "/attn/" in line
        and math.prod(map(int, m.group(1).split(","))) >= q]
    assert not round_the_kernels, round_the_kernels
    pair = 4096 * 2 * 4096
    copies = [line.strip()[:160] for line in text.splitlines()
              for m in [made.match(line)]
              if m and m.group(2) == "copy"
              and math.prod(map(int, m.group(1).split(","))) == pair]
    assert not copies, copies


@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_codec_encode_decode_compiles(one_chip, codec):
    def roundtrip(flat):
        codes, scales = qz.quantize(flat, codec, interpret=False)
        return qz.dequantize(codes, scales, CODEC_ELEMS, codec,
                             interpret=False)

    text = _compiled_text(roundtrip, jax.ShapeDtypeStruct(
        (CODEC_ELEMS,), jnp.float32, sharding=one_chip))
    assert text.count("tpu_custom_call") >= 2     # encode and decode


def test_flash_ring_attention_compiles_on_four_chips(topo, monkeypatch):
    """ring_attention(use_flash=True) under shard_map over the four
    described chips: Pallas inside lax.switch inside fori_loop, with the
    K/V rotation between hops."""
    # ring_attention leaves interpret=None, and the dispatch then asks which
    # backend is attached; here that is the CPU, so steer it in the test.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("sp",))
    spec = P(None, "sp")
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True,
                          use_flash=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    text = _compiled_text(fn, *_qkv((2, 4 * 1024, 12, 64),
                                    NamedSharding(mesh, spec)))
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_the_two_maps_compile_at_the_phi4flash_cell_s_shapes(one_chip,
                                                             monkeypatch):
    """``models/phi4flash.py:two_maps`` at ``phi4flash-sambay-tp2-s16384``'s
    shapes (10 query pairs on 5 values of 128 lanes, q and k padded from 64,
    16,384 rows), causal and under the band of 512, value and gradients: two
    calls of each kernel a mask, by name, and no ``hvd_flash_relayout``."""
    from horovod_tpu.models import phi4flash

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(phi4flash.PHI4_MINI_FLASH, num_heads_held=20,
                              num_kv_heads_held=10)
    q = jax.ShapeDtypeStruct((1, 16384, 1280), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 640), jnp.bfloat16,
                              sharding=one_chip)
    for window, band in ((None, ""), (512, "swa_")):
        def loss(q, k, v, window=window):
            a1, a2 = phi4flash.two_maps(cfg, q, k, v, window)
            return jnp.sum((a1.astype(jnp.float32)
                            - 0.8 * a2.astype(jnp.float32)) ** 2)

        text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
        for kernel in ("fwd", "dq", "dkv"):
            name = f"hvd_flash_{band}{kernel}"
            assert len(re.findall(rf"{name}[\w.]* = ", text)) == 2, name
        assert "hvd_flash_relayout" not in text
