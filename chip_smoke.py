"""Bring-up smoke: what only a run on the TPU can show, once.

    python chip_smoke.py             one chip, one process, every phase below
    python chip_smoke.py --chips 4   four chips: launch_np4, then device(4)
    python chip_smoke.py --grouped-products
                                     one chip: the expert layer of
                                     ``parallel/moe.py`` against a loop over
                                     its experts, and what its grouped
                                     products cost by ``ragged_dot``, by
                                     megablox ``gmm`` and by the repo's own
                                     kernels; the layer's sum of its rows back
                                     into the tokens alone, at the three
                                     cells' sizes, beside the scatter-add it
                                     replaced; the same kernels at an expert
                                     of [2048, 2048], wider than their VMEM
                                     budget, each call timed; nothing else
    python chip_smoke.py --qk-norm-rope
                                     one chip: ``ops/qk_norm_rope.py`` at
                                     ``sdar-moe-ep8-s4096``'s q and k against
                                     its ``jax.numpy`` form, forward and
                                     backward timed; nothing else
    python chip_smoke.py --flash-window
                                     one chip: the banded flash kernels at
                                     ``laguna-swa-ep32-s16384``'s two shapes
                                     (9 on 1 and 6 on 1 heads, 16,384 x 128,
                                     window 512 and none) against
                                     ``dense_attention``, values and the three
                                     gradients, forward and backward timed;
                                     nothing else
    python chip_smoke.py --flash-mla
                                     one chip: the flash kernels with a second
                                     score operand at ``joyai-mla-ep16-s16384``'s
                                     shape (4 heads, 16,384 x 128 + 64 on one
                                     shared rotary key) against
                                     ``dense_attention``, values and the five
                                     gradients, and its values against the
                                     kernels without the pair (q and k
                                     concatenated to 192, v padded), forward
                                     and backward timed; nothing else
    python chip_smoke.py --flash-diff
                                     one chip: differential attention's two
                                     maps at ``phi4flash-sambay-tp2-s16384``'s
                                     shape (10 query pairs on 5 values of 128,
                                     q and k 64 wide, 16,384 rows), causal
                                     and under the band of 512,
                                     as one padded 128-lane call a map and as
                                     four 64-wide calls, against
                                     ``dense_attention``, values and the three
                                     gradients, forward and backward timed,
                                     and ``A1 - lambda A2``; nothing else
    python chip_smoke.py --lightning
                                     one chip: ``ops/lightning_attention.py``
                                     at ``sala-sparse-linear-tp4-s16384``'s
                                     shape (heads 24 to 31 of 32 with their
                                     published slopes, 16,384 x 128) against
                                     the quadratic form and the scan form,
                                     values and the three gradients, forward
                                     and backward timed by chunk size;
                                     nothing else
    python chip_smoke.py --flash-select
                                     one chip: ``ops/flash_select.py`` at that
                                     cell's sparse layer (8 query heads on 1
                                     key/value head, 16,384 x 128, MiniCPM4's
                                     selection constants): the selection
                                     timed, the selected walk's kernels
                                     against the masked dense softmax, values
                                     and the three gradients, the walk's
                                     counters, forward and backward timed by
                                     tile sizes beside the plain causal
                                     kernels; nothing else
    python chip_smoke.py --tied-head
                                     one chip: ``ops/tied_head.py`` at the
                                     four blocked heads of the benchmark
                                     (ZAYA's and Jamba's tied, Laguna's and
                                     JoyAI's of their own) by the block of
                                     tokens, against the ``jax.numpy`` product
                                     and statistics, alone and inside the
                                     whole head's value and gradients, both
                                     timed; nothing else
    python chip_smoke.py --embed-grad
                                     one chip: ``ops/embedding.py`` at the
                                     seven cells' held tables and ids a step:
                                     the table's gradient by the segment sum
                                     in id order against a float32
                                     scatter-add, timed beside XLA's scatter
                                     for the same lookup, and the forward's
                                     two orders (cast the table then take,
                                     take then cast); nothing else

One chip: the device JAX found, a clean build of the C++ core and
``hvd.init()`` on it, the Pallas kernels alone against their references (at
layouts no benchmark cell runs), and the eager spine (C++ core -> device
plane) on device-resident arrays.  Four chips: one process a chip through
``runner/launch.py --jax-distributed``.  Each phase prints one JSON line; the
last line of stdout is ``{"ok": true, "device": {"platform", "kind",
"count"}}``.  Any phase that fails raises, and the script exits non-zero.
There is no CPU mode: without a TPU it exits before the first phase.

The training steps it once drove are the benchmark's cells now
(``python benchmark/run.py --workload <cell>``), at published widths and held
to stricter checks on every PR: ``resnet50_train`` and ``sync`` went to
``resnet50-1chip``, ``gpt_flash_train`` to ``gpt2m-1chip``, ``spmd_dp4`` to
``gpt2m-dp4``.

The phases are plain functions with size arguments, so
tests/single/test_chip_smoke.py calls them tiny on the CPU mesh.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import subprocess
import sys
import time
from unittest import mock

REPO = os.path.dirname(os.path.abspath(__file__))

# Stated tolerances (max |a-b| / max |b|).  f32 inputs still go through the
# MXU's default bf16 passes in kernel and reference alike; bf16 carries 8
# bits of mantissa per operation.
TOL_F32 = 6e-3
TOL_BF16_FWD = 2e-2
TOL_BF16_BWD = 4e-2


def emit(phase: str, **kv) -> dict:
    line = {"phase": phase, **kv}
    print(json.dumps(line), flush=True)
    return line


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(1e-12, float(np.max(np.abs(b)))))


def _check(checks: list, name: str, a, b, tol: float) -> None:
    err = rel_err(a, b)
    checks.append({"name": name, "rel": err, "tol": tol, "ok": err < tol})


def _raise_on_failed(phase: str, checks: list) -> None:
    bad = [c for c in checks if not c["ok"]]
    if bad:
        raise AssertionError(f"{phase}: out of tolerance: {bad}")


# ---------------------------------------------------------------------------
# Phases (one chip)
# ---------------------------------------------------------------------------


def device(expect_count: int = 1, platform: str = "tpu") -> dict:
    """The accelerator JAX found, or exit: nothing below has a CPU mode."""
    from importlib import metadata

    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != platform:
        print(f"chip_smoke: JAX found no {platform} (platform="
              f"{devs[0].platform!r}); nothing to smoke", file=sys.stderr)
        sys.exit(1)
    if len(devs) != expect_count:
        raise AssertionError(f"expected {expect_count} device(s), JAX reports "
                             f"{len(devs)}: {devs}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    emit("device", **info, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=metadata.version("libtpu"),
         compile_cache=jax.config.jax_compilation_cache_dir)
    return info


def native_core(clean: bool = True) -> dict:
    """Rebuild the C++ core from the sources in this copy and init on it."""
    cpp = os.path.join(REPO, "horovod_tpu", "cpp")
    if clean:
        subprocess.run(["make", "-s", "clean"], cwd=cpp, check=True)
    t0 = time.perf_counter()
    import horovod_tpu as hvd
    from horovod_tpu import _core
    from horovod_tpu.context import HorovodContext

    _core._load_library()
    build_s = time.perf_counter() - t0
    hvd.init()
    core = HorovodContext.instance().core
    assert isinstance(core, _core.NativeCore), type(core)
    return emit("native_core", core=type(core).__name__, clean_build=clean,
                build_s=round(build_s, 2), size=hvd.size())


def _qkv(b, s, h, d, dtype, key):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


def kernels(interpret: bool = False, seqs=(512, 777), heads: int = 4,
            head_dim: int = 64, codec_elems: int = 1 << 22,
            dtypes=("float32", "bfloat16"), causals=(False, True),
            grouped=((8, 2, 128, 1024, 4), (8, 2, 128, 608, 32),
                     (4, 1, 64, 512, 4))) -> dict:
    """The Pallas kernels alone, compiled for the device (``interpret`` off):
    flash forward, dq/dk/dv, the (out, lse) pair the ring hop differentiates
    through, grouped-query heads under the causal and the block-diffusion
    mask (``grouped``: query heads, key/value heads, head width, L, block
    length), one ring hop under shard_map, and the two device codecs
    bit-exact against their jnp mirror."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops import quantize as qz
    from horovod_tpu.ops.flash_attention import (
        dense_attention, dense_attention_with_lse, flash_attention,
        flash_attention_with_lse)
    from horovod_tpu.parallel.ring_attention import ring_attention

    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda x: x.astype(jnp.float32))
    flash = functools.partial(flash_attention, interpret=interpret)
    flash_lse = functools.partial(flash_attention_with_lse,
                                  interpret=interpret)
    checks = []
    s0 = seqs[0]
    tols = {"float32": (TOL_F32, TOL_F32),
            "bfloat16": (TOL_BF16_FWD, TOL_BF16_BWD)}
    for name in dtypes:
        dtype, (tol_f, tol_b) = jnp.dtype(name), tols[name]
        for causal in causals:
            for s in seqs:  # the second length exercises the padding path
                q, k, v = _qkv(2, s, heads, head_dim, dtype, key=0)
                got, ref = jax.jit(lambda q, k, v: (
                    flash(q, k, v, causal),
                    dense_attention(*f32((q, k, v)), causal)))(q, k, v)
                _check(checks, f"fwd/{name}/causal={causal}/s={s}", got, ref,
                       tol_f)
            q, k, v = _qkv(2, s0, heads, head_dim, dtype, key=1)
            w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

            def loss(fn, q, k, v):
                return jnp.sum(fn(q, k, v, causal).astype(jnp.float32) * w)

            got = jax.jit(jax.grad(functools.partial(loss, flash),
                                   argnums=(0, 1, 2)))(q, k, v)
            ref = jax.jit(jax.grad(functools.partial(loss, dense_attention),
                                   argnums=(0, 1, 2)))(*f32((q, k, v)))
            for g, a, b in zip(("dq", "dk", "dv"), got, ref):
                _check(checks, f"bwd/{name}/causal={causal}/{g}", a, b, tol_b)

    # The (out, lse) pair: the lse cotangent folds into delta.
    q, k, v = _qkv(2, s0 // 2, heads, head_dim, jnp.float32, key=2)
    wo = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)
    wl = jax.random.normal(jax.random.PRNGKey(4), (2, heads, s0 // 2),
                           jnp.float32)

    def pair_loss(fn, q, k, v):
        out, lse = fn(q, k, v, True)
        return jnp.sum(out.astype(jnp.float32) * wo) + jnp.sum(lse * wl)

    got = jax.jit(jax.grad(functools.partial(pair_loss, flash_lse),
                           argnums=(0, 1, 2)))(q, k, v)
    ref = jax.jit(jax.grad(
        functools.partial(pair_loss, dense_attention_with_lse),
        argnums=(0, 1, 2)))(q, k, v)
    for g, a, b in zip(("dq", "dk", "dv"), got, ref):
        _check(checks, f"lse_vjp/{g}", a, b, TOL_F32)

    # Fewer key/value heads than query heads, under the causal mask over L
    # positions and under the block-diffusion mask over [clean ; noised].
    for hq, hkv, d, length, block in grouped:
        for name, mask in (("causal", {"causal": True}),
                           ("block_diffusion",
                            {"block_diffusion": (length, block)})):
            s = length * (2 if name == "block_diffusion" else 1)
            ks = jax.random.split(jax.random.PRNGKey(7), 4)
            q = jax.random.normal(ks[0], (2, s, hq, d), jnp.bfloat16)
            k, v = (jax.random.normal(x, (2, s, hkv, d), jnp.bfloat16)
                    for x in ks[1:3])
            w = jax.random.normal(ks[3], q.shape, jnp.float32)

            def out_and_grads(fn, q, k, v):
                def loss(*a):
                    out = fn(*a, **mask).astype(jnp.float32)
                    return jnp.sum(out * w), out

                (_, out), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
                return out, grads

            got = jax.jit(functools.partial(out_and_grads, flash))(q, k, v)
            ref = jax.jit(functools.partial(out_and_grads, dense_attention))(
                *f32((q, k, v)))
            tag = f"gqa/{name}/h={hq}:{hkv}/d={d}/L={length}/B={block}"
            _check(checks, f"{tag}/out", got[0], ref[0], TOL_BF16_FWD)
            for g, a, b in zip(("dq", "dk", "dv"), got[1], ref[1]):
                _check(checks, f"{tag}/{g}", a, b, TOL_BF16_BWD)

    # pallas inside lax.switch inside fori_loop inside shard_map: the
    # composition ring_attention(use_flash=True) builds, on a 1-device mesh.
    q, k, v = _qkv(2, s0, heads, head_dim, jnp.bfloat16, key=5)
    ring = jax.jit(shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True,
                          use_flash=True, block_size=128,
                          interpret=interpret),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("sp",)),
        in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    _check(checks, "ring_hop/causal", ring(q, k, v),
           jax.jit(lambda q, k, v: dense_attention(*f32((q, k, v)), True))(
               q, k, v), TOL_BF16_FWD)

    # Codecs: Pallas encode+decode against the jnp path quantize() takes off
    # the TPU, bit for bit.
    flat = jax.random.normal(jax.random.PRNGKey(6), (codec_elems,),
                             jnp.float32) * 3.0
    codecs = []
    def roundtrip(flat, codec, interpret):
        q = qz.quantize(flat, codec, interpret)
        return q, qz.dequantize(*q, codec_elems, codec, interpret)

    for codec in ("int8", "int4"):
        got_q, got_x = jax.jit(functools.partial(
            roundtrip, codec=codec, interpret=interpret))(flat)
        with mock.patch.object(qz, "_dispatch", lambda _interpret: None):
            ref_q, ref_x = jax.jit(functools.partial(
                roundtrip, codec=codec, interpret=None))(flat)
        same = all(
            bool(jnp.array_equal(a, b)) for a, b in zip(
                jax.tree_util.tree_leaves((got_q, got_x)),
                jax.tree_util.tree_leaves((ref_q, ref_x))))
        codecs.append({"codec": codec, "bit_exact": same,
                       "roundtrip_rel": rel_err(got_x, flat)})
    report = emit("kernels", interpret=interpret, checks=checks,
                  codecs=codecs)
    _raise_on_failed("kernels", checks)
    assert all(c["bit_exact"] for c in codecs), codecs
    return report


def _expert_layer(tokens, d, f, held, experts, dtype, key, busy=0):
    """Seeded rows, a router over ``experts`` and ``held`` SwiGLU experts,
    the first ``busy`` of them in nearly every token's top-k."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    x = jax.random.normal(ks[0], (tokens, d), dtype)
    router = jax.random.normal(ks[1], (d, experts), jnp.float32) * d ** -0.5
    if busy:
        x = x.at[:, 0].set(1.0)
        router = router.at[0, :busy].add(6.0)
    w_gate, w_up = (jax.random.normal(k, (held, d, f), jnp.float32)
                    * d ** -0.5 for k in ks[2:4])
    w_down = jax.random.normal(ks[4], (held, f, d), jnp.float32) * f ** -0.5
    return x, router, w_gate, w_up, w_down


def _choices(tokens, top_k, held, experts, routed):
    """[tokens, top_k] int32 choices of which ``routed``, seeded and spread
    over the tokens, are held experts in turn and the others absent ones."""
    import jax
    import jax.numpy as jnp

    pair = jax.random.permutation(jax.random.PRNGKey(2), tokens * top_k)
    absent = held + pair % max(1, experts - held)
    return jnp.where(pair < routed, pair % held, absent).reshape(
        tokens, top_k).astype(jnp.int32)


def _best_ms(repeats: int, fn, *args) -> float:
    """Milliseconds of ``fn(*args)`` to completion, best of ``repeats``
    after one call that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return round(1e3 * min(times), 3)


# (rows of the buffer, tokens, d, top_k, held, experts) of a chip's expert
# layer in the three cells whose tokens have more than one row.
SUM_SHAPES = {"sdar-moe-ep8-s4096": (36864, 16384, 2048, 8, 16, 128),
              "joyai-mla-ep16-s16384": (16384, 16384, 2048, 8, 16, 256),
              "laguna-swa-ep32-s16384": (10240, 16384, 3072, 10, 8, 256)}


def _chained_ms(repeats: int, chain: int, body, rows, *args,
                whole: bool = False) -> float:
    """Milliseconds of one ``body(rows, *args)`` on the device: ``chain`` of
    them in one compiled program, each fed one element of the one before,
    less a chain of one (the dispatch, some 0.6 ms, is in neither).
    ``whole``: the chain carries a body's whole result beside it, for a body
    XLA could narrow to the element read (a gather)."""
    import jax

    def chained(trips):
        def fn(rows, *args):
            def trip(_, carry):
                out = body(carry[0], *args)
                fed = carry[0].at[0, 0].add(out[0, 0].astype(rows.dtype))
                return (fed, out) if whole else (fed,)
            kept = (jax.numpy.zeros_like(jax.eval_shape(body, rows, *args)),
                    ) if whole else ()
            return jax.lax.fori_loop(0, trips, trip, (rows, *kept))
        return jax.jit(fn)

    return round((_best_ms(repeats, chained(chain), rows, *args)
                  - _best_ms(repeats, chained(1), rows, *args))
                 / (chain - 1), 3)


def sum_rows(shapes=None, repeats: int = 5, chain: int = 16,
             interpret: bool = False) -> dict:
    """The expert layer's sum of its rows back into the tokens, alone
    (``parallel/moe.py:add_rows``), at each cell's (buffer, tokens, d) with a
    quarter, a half and all of the buffer routed, rows and tokens as a layer
    makes them (sorted by expert, a token's rows on ``top_k`` experts):
    milliseconds on the device, one of ``chain`` in one program, best of
    ``repeats``.  ``sum_rows_ms`` is what a TPU runs, from the order the layer
    keeps: the gather into token order and ``hvd_moe_sum_rows``;
    ``token_order_ms`` is that order's sort, made once a layer for both
    passes; ``scatter_add_ms`` is the scatter-add in trips that it replaced
    (130 ns a row).  The two sums are held against each other."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import grouped_matmul as gm
    from horovod_tpu.parallel import moe

    chained_ms = functools.partial(_chained_ms, repeats, chain)
    report, checks = {}, []
    for cell, (buffer, tokens, d, top_k, held, experts) in (
            shapes or SUM_SHAPES).items():
        rows = jax.random.normal(jax.random.PRNGKey(3), (buffer, d),
                                 jnp.bfloat16)

        def order_of(token, n):
            return gm.token_order(token, n, interpret=interpret or None)

        def by_kernel(rows, order):
            return gm.sum_by_token(rows, order, tokens,
                                   interpret=interpret or None)

        def in_trips(rows, token, n):
            return moe._scatter_add_rows(rows, token, n, tokens)

        for share, routed in (("quarter", buffer // 4),
                              ("half", buffer // 2), ("all", buffer)):
            chosen = _choices(tokens, top_k, held, experts, routed)
            token = (jnp.argsort(chosen.reshape(-1), stable=True)[:buffer]
                     // top_k).astype(jnp.int32)
            n = jnp.int32(routed)
            order = jax.jit(order_of)(token, n)
            case = f"{cell}/routed={share}"
            report[f"token_order_ms/{case}"] = chained_ms(
                # (the sort's keys hang on the chain's carry)
                lambda rows, token, n: order_of(
                    token + (rows[0, 0] != rows[0, 0]).astype(jnp.int32), n
                ).rows[:, None], rows, token, n)
            report[f"sum_rows_ms/{case}"] = chained_ms(by_kernel, rows, order)
            report[f"scatter_add_ms/{case}"] = chained_ms(in_trips, rows,
                                                          token, n)
            _check(checks, f"sum_rows/{case}",
                   jax.jit(by_kernel)(rows, order),
                   jax.jit(in_trips)(rows, token, n), TOL_BF16_FWD)
    report = emit("sum_rows", checks=checks, **report)
    _raise_on_failed("sum_rows", checks)
    return report


# (rows of the table held, d, ids a step) of the seven cells whose embedding
# is a held share of a vocabulary.
EMBED_SHAPES = {"phi4flash-sambay-tp2-s16384": (100032, 2560, 16384),
                "zaya1-moe-ep2-s16384": (131136, 2048, 16384),
                "jamba2-ssm-tp4-s16384": (16384, 2560, 16384),
                "sala-sparse-linear-tp4-s16384": (18362, 4096, 16384),
                "laguna-swa-ep32-s16384": (12544, 3072, 16384),
                "sdar-moe-ep8-s4096": (18992, 2048, 16384),
                "joyai-mla-ep16-s16384": (16160, 2048, 16384)}
# One rounding of a float32 sum to bfloat16 is 2 ** -9 of the value.
TOL_ONE_ROUNDING = 2.0 ** -8


def embed_grad(shapes=None, repeats: int = 5, chain: int = 8,
               interpret: bool = False) -> dict:
    """``ops/embedding.py:embed_lookup`` alone at each cell's (table rows, d,
    ids a step), ids uniform over the table: its table gradient (a float32
    table under bfloat16 cotangent rows, as the models hold them) against a
    float32 scatter-add, its value against ``jnp.take`` of the table cast,
    and milliseconds on the device, one of ``chain`` in one program, best of
    ``repeats``.  ``embed_grad_ms`` is the sum as a TPU runs it (the sort of
    the ids, the gather into id order, ``embed_grad_sum_rows``) and
    ``sorted_scatter_ms`` XLA's own for the same ``jnp.take`` (what
    ``nn.Embed`` compiles to: the sorted scatter where the ids are more than
    an eighth of the table's rows, the plain one under it), both into a
    bfloat16 gradient: the cast to the table's float32 fuses into the sum
    with a tied head's gradient on either side.  ``cast_then_take_ms`` /
    ``take_then_cast_ms`` are the forward's two orders over the float32
    table, between which ``embed_lookup`` chooses by the table's bytes."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.embedding import embed_lookup

    chained_ms = functools.partial(_chained_ms, repeats, chain)

    def lookup(table, ids):
        return embed_lookup(table, ids, jnp.bfloat16,
                            interpret=interpret or None)

    def cast_first(table, ids):
        return jnp.take(table.astype(jnp.bfloat16), ids, axis=0)

    def take_first(table, ids):
        return jnp.take(table, ids, axis=0).astype(jnp.bfloat16)

    def d_table(fn):
        return lambda g, table, ids: jax.vjp(
            lambda t: fn(t, ids), table)[1](g)[0]

    report, checks = {}, []
    for cell, (rows, d, m) in (shapes or EMBED_SHAPES).items():
        ks = jax.random.split(jax.random.PRNGKey(rows), 3)
        table = jax.random.normal(ks[0], (rows, d), jnp.float32) / 50
        ids = jax.random.randint(ks[1], (m,), 0, rows)
        g = jax.random.normal(ks[2], (m, d), jnp.bfloat16)
        _check(checks, f"embed_grad/{cell}/value",
               jax.jit(lookup)(table, ids), jax.jit(cast_first)(table, ids),
               1e-9)
        _check(checks, f"embed_grad/{cell}/d_table",
               jax.jit(d_table(lookup))(g, table, ids),
               jnp.zeros((rows, d), jnp.float32).at[ids].add(
                   g.astype(jnp.float32)), TOL_ONE_ROUNDING)
        held = table.astype(jnp.bfloat16)
        report[f"embed_grad_ms/{cell}"] = chained_ms(
            d_table(lookup), g, held, ids)
        report[f"sorted_scatter_ms/{cell}"] = chained_ms(
            d_table(cast_first), g, held, ids)
        report[f"cast_then_take_ms/{cell}"] = chained_ms(
            cast_first, table, ids, whole=True)
        report[f"take_then_cast_ms/{cell}"] = chained_ms(
            take_first, table, ids, whole=True)
    report = emit("embed_grad", checks=checks, chain=chain, **report)
    _raise_on_failed("embed_grad", checks)
    return report


def grouped_products(tokens: int = 16384, d: int = 2048, f: int = 768,
                     held: int = 16, experts: int = 128, top_k: int = 8,
                     capacity_factor: float = 2.25, repeats: int = 5,
                     megablox: bool = True, interpret: bool = False) -> dict:
    """What one chip's share of a top-k expert layer costs, forward and
    backward, under an even router (the rows fit the layer's buffer) and
    under one with three busy experts (they do not: every row a router can
    send, in parts), what it costs on a caller's choices that route a
    quarter, a half and all of the buffer's rows here (the gather, the sum
    back and the products follow the rows routed, not the buffer:
    ``parallel/moe.py:rows_walked``), and what its grouped products alone
    cost three ways,
    over that buffer and over ``tokens x top_k`` rows: milliseconds, best of
    ``repeats``.  The three: ``jax.lax.ragged_dot`` (what ``parallel/moe.py``
    uses off the TPU), the megablox ``gmm`` that ships with jax (declares no
    ``vma``: of no use under ``shard_map``), and the repo's own
    ``ops/grouped_matmul.py:grouped_dot``, **which is what ``parallel/
    moe.py`` uses on a TPU**: as the layer calls it (float32 kernels, cast a
    group at a time in VMEM) and with the kernels cast before the call as
    the other two take them.  ``interpret``: the repo's kernels through the
    Pallas interpreter (the CPU rehearsal)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.grouped_matmul import grouped_dot
    from horovod_tpu.parallel import moe

    # The layer against a loop over its experts, values and gradients, at a
    # size where a third of the row buffer lies past the rows routed (what a
    # grouped product leaves there must reach neither) and, with three busy
    # experts, where the rows outgrow the buffer and are walked in parts.
    checks = []

    def by_loop(x, router, w_gate, w_up, w_down):
        probs = jax.nn.softmax(x @ router, axis=-1)
        weights, chosen = jax.lax.top_k(probs, 4)
        weights = weights / weights.sum(-1, keepdims=True)
        y = jnp.zeros_like(x)
        for i in range(w_gate.shape[0]):
            mine = jnp.sum(jnp.where(chosen == i, weights, 0.0), axis=-1)
            y = y + mine[:, None] * ((jax.nn.silu(x @ w_gate[i])
                                      * (x @ w_up[i])) @ w_down[i])
        return y

    def value_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out, *vjp(jnp.cos(out)))

    for case, busy in (("routed_experts", 0), ("routed_experts_in_parts", 3)):
        small = _expert_layer(1024, 256, 128, 4, 16, jnp.float32, key=1,
                              busy=busy)
        with jax.default_matmul_precision("highest"):
            got = jax.jit(functools.partial(value_and_grads, lambda *a: (
                moe.routed_experts(*a, top_k=4, capacity_factor=1.5)[0])))(
                    *small)
            ref = jax.jit(functools.partial(value_and_grads, by_loop))(*small)
        for name, a, b in zip(("y", "dx", "drouter", "dgate", "dup", "ddown"),
                              got, ref):
            _check(checks, f"{case}/{name}", a, b, TOL_F32)

    x, router, w_gate, w_up, w_down = _expert_layer(
        tokens, d, f, held, experts, jnp.bfloat16, key=0)

    best_ms = functools.partial(_best_ms, repeats)

    def layer(x, router, *kernels):
        y, routing = moe.routed_experts(x, router, *kernels, top_k=top_k,
                                        capacity_factor=capacity_factor)
        return jnp.sum(y.astype(jnp.float32) ** 2), routing.load

    step = jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True))
    (_, load), _ = step(x, router, w_gate, w_up, w_down)
    # The layer's buffer (the defaults are SDAR-30B-A3B's share of eight at
    # 16,384 positions), and every row a router can send.
    buffer = moe.row_buffer(tokens, top_k, held, experts, capacity_factor)
    worst = tokens * min(top_k, held)
    busy = _expert_layer(tokens, d, f, held, experts, jnp.bfloat16, key=0,
                         busy=3)
    (_, busy_load), _ = step(*busy)
    report = {"layer_fwd_bwd_ms": best_ms(step, x, router, w_gate, w_up,
                                          w_down),
              "layer_fwd_bwd_ms/in_parts": best_ms(step, *busy),
              "load": [int(n) for n in load],
              "load/in_parts": [int(n) for n in busy_load],
              "buffer": buffer, "worst": worst}
    # The same layer on a caller's choices that route a quarter, a half and
    # all of the buffer's rows here: what is paid by the row (gather, sum
    # back, products) follows the rows routed, not the buffer.
    dispatch = jax.jit(jax.grad(
        lambda x, chosen, weights, *kernels: jnp.sum(moe.dispatch_experts(
            x, chosen, weights, *kernels, first_expert=0,
            experts_total=experts, capacity_factor=capacity_factor).astype(
                jnp.float32) ** 2), argnums=(0, 2, 3, 4, 5)))
    even = jnp.full((tokens, top_k), 1.0 / top_k, jnp.float32)
    for share, routed in (("quarter", buffer // 4), ("half", buffer // 2),
                          ("all", buffer)):
        report[f"layer_fwd_bwd_ms/routed={share}"] = best_ms(
            dispatch, x, _choices(tokens, top_k, held, experts, routed), even,
            w_gate, w_up, w_down)
    # The layer's backward starts from what its forward kept (and takes
    # d weights from g . W_down^T): against plain reverse mode through the
    # same buffer, at the timed size, half of it routed.
    half = _choices(tokens, top_k, held, experts, buffer // 2)
    plain = jax.jit(jax.grad(
        lambda x, weights, *kernels: jnp.sum(moe._held_part(
            buffer, x, moe._local(half, 0, held), weights, *kernels)[0].astype(
                jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4)))
    for name, a, b in zip(("dx", "dweights", "dgate", "dup", "ddown"),
                          dispatch(x, half, even, w_gate, w_up, w_down),
                          plain(x, even, w_gate, w_up, w_down)):
        _check(checks, f"kept_forward/{name}", a, b, TOL_BF16_BWD)

    def products(dot):
        """Gradients of the three products' sum with respect to the rows
        and the float32 kernels, as the layer's backward asks for them."""
        def fn(rows, sizes, *kernels):
            def loss(rows, *kernels):
                h = jax.nn.silu(dot(rows, kernels[0], sizes)) * dot(
                    rows, kernels[1], sizes)
                return jnp.sum(dot(h, kernels[2], sizes).astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2, 3))(rows, *kernels)
        return jax.jit(fn)

    def cast_first(dot):
        return lambda rows, w, sizes: dot(rows, w.astype(rows.dtype), sizes)

    own = functools.partial(grouped_dot, interpret=interpret or None)
    dots = {"ragged_dot": cast_first(jax.lax.ragged_dot),
            "hvd_grouped_dot": own,
            "hvd_grouped_dot_cast_first": cast_first(own)}
    if megablox:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        dots["megablox_gmm"] = cast_first(lambda a, b, sizes: gmm(
            a, b, sizes, a.dtype,
            lambda m, k, n: (512, min(k, 1024), min(n, 1024))))
    # The buffer and the worst case holding the rows of this router; the
    # buffer half empty; the worst case with every row routed: tokens x
    # top_k rows of work.  (No case fills the buffer: since PR 35 the layer
    # leaves its tail to no group.)
    routed = jnp.asarray(load, jnp.int32)
    cases = {f"rows={buffer}": (buffer, routed),
             f"rows={worst}": (worst, routed),
             f"rows={buffer}/half": (buffer, routed // 2),
             f"rows={worst}/all_routed": (
                 worst, jnp.full((held,), worst // held))}
    grads = {}
    for name, dot in dots.items():
        fn = products(dot)
        for case, (rows, sizes) in cases.items():
            some = jnp.zeros((rows, d), jnp.bfloat16).at[:tokens].set(x)
            report[f"{name}_fwd_bwd_ms/{case}"] = best_ms(
                fn, some, sizes, w_gate, w_up, w_down)
            if rows == buffer and sizes is routed:
                grads[name] = fn(some, sizes, w_gate, w_up, w_down)
    # The repo's kernels against ragged_dot at the timed size, over the rows
    # routed (past them a ragged_dot's d rows are undefined on a TPU, the
    # kernels' are zeros).
    n_routed = int(routed.sum())
    for name in ("hvd_grouped_dot", "hvd_grouped_dot_cast_first"):
        for what, a, b in zip(("drows", "dgate", "dup", "ddown"),
                              grads[name], grads["ragged_dot"]):
            if what == "drows":
                checks.append({"name": f"{name}/drows_tail_is_zero",
                               "ok": not bool(jnp.any(a[n_routed:]))})
                a, b = a[:n_routed], b[:n_routed]
            _check(checks, f"{name}/{what}", a, b, TOL_BF16_BWD)
    report = emit("grouped_products", checks=checks, **report)
    _raise_on_failed("grouped_products", checks)
    return report


def eager(n: int = 1 << 20, steps: int = 3, batch: int = 512) -> dict:
    """The un-jitted spine on device-resident arrays: mpi_ops -> C++ core ->
    device plane.  Needs hvd.init() (the native_core phase)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext
    from horovod_tpu.models import MLP, xent_loss

    plane = HorovodContext.instance().device_plane
    before = dict(plane.stats)
    size, rank = hvd.size(), hvd.rank()
    x = jnp.arange(n, dtype=jnp.float32) + rank
    want_sum = np.arange(n, dtype=np.float32) * size + sum(range(size))
    got = hvd.allreduce(x, op=hvd.Sum, name="smoke.sum")
    assert isinstance(got, jax.Array), type(got)
    np.testing.assert_allclose(np.asarray(got), want_sum, rtol=1e-6)
    got = hvd.allreduce(x, op=hvd.Average, name="smoke.avg")
    np.testing.assert_allclose(np.asarray(got), want_sum / size, rtol=1e-6)
    outs = hvd.grouped_allreduce([x, 2 * x], op=hvd.Sum, name="smoke.group")
    np.testing.assert_allclose(np.asarray(outs[1]),
                               2 * want_sum, rtol=1e-6)

    rng = np.random.RandomState(rank)
    xs = jnp.asarray(rng.rand(batch, 28, 28, 1).astype(np.float32))
    ys = jnp.asarray(rng.randint(0, 10, size=batch).astype(np.int32))
    model = MLP()
    params = model.init(jax.random.PRNGKey(rank), xs[:1])
    params = hvd.broadcast_parameters(params, root_rank=0)
    tx = hvd.DistributedOptimizer(optax.sgd(0.05), op=hvd.Average)
    opt_state = tx.init(params)
    loss_and_grad = jax.value_and_grad(
        lambda p: xent_loss(model.apply(p, xs), ys))
    losses = []
    for _ in range(steps):  # no jit around the step
        loss, grads = loss_and_grad(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0], losses

    moved = {k: plane.stats[k] - before[k] for k in plane.stats
             if plane.stats[k] != before[k]}
    done = "identity" if size == 1 else "allreduce"
    assert moved.get(done, 0) > 0, (done, moved)
    assert plane.stats["host_fallback"] == 0, plane.stats
    return emit("eager", size=size, losses=losses, device_plane_moved=moved,
                host_fallback=plane.stats["host_fallback"])


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def launch_np4_worker(platform: str = "tpu") -> None:
    """One of four launcher-spawned processes, one chip each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.context import HorovodContext

    hvd.init()
    dev = jax.local_devices()[0]
    assert dev.platform == platform, dev
    assert jax.local_device_count() == 1, jax.local_devices()
    assert jax.device_count() == 4, jax.devices()
    assert hvd.size() == 4
    n = 1 << 20
    x = jnp.full((n,), float(hvd.rank() + 1), jnp.float32)
    got = hvd.allreduce(x, op=hvd.Sum, name="np4.sum")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.full((n,), 10.0, np.float32))
    # Rank order survives the rank -> chip mapping (a jax process's index
    # follows its chip, not its rank).
    order = hvd.allgather(jnp.full((1,), hvd.rank(), jnp.int32),
                          name="np4.order")
    np.testing.assert_array_equal(np.asarray(order), np.arange(4))
    stats = HorovodContext.instance().device_plane.stats
    assert stats["allreduce"] > 0 and stats["allgather"] > 0, stats
    assert stats["host_fallback"] == 0, stats
    emit("launch_np4_worker", rank=hvd.rank(),
         jax_process_index=jax.process_index(), device_id=dev.id,
         coords=list(getattr(dev, "coords", ())), kind=dev.device_kind,
         device_count=jax.device_count(), allreduce_sum=float(got[0]),
         device_plane_allreduce=stats["allreduce"],
         host_fallback=stats["host_fallback"])
    hvd.shutdown()


def launch_np4(np_workers: int = 4, timeout: float = 600.0) -> dict:
    """One process per chip through the launcher.  Runs while this process
    has not initialised a JAX backend: a parent that held the chips would
    leave none for the workers."""
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
           str(np_workers), "--jax-distributed", sys.executable,
           os.path.join(REPO, "chip_smoke.py"), "--worker"]
    # Its own process group: on a timeout the launcher AND its workers go.
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        sys.stderr.write(out[-8000:])
        raise AssertionError(f"launch_np4: no result within {timeout:.0f}s")
    workers = []
    for line in out.splitlines():
        at = line.find('{"phase": "launch_np4_worker"')
        if at >= 0:
            workers.append(json.loads(line[at:]))
    if proc.returncode != 0 or len(workers) != np_workers:
        sys.stderr.write(out[:6000] + "\n[...]\n" + out[-6000:])
        raise AssertionError(
            f"launch_np4: launcher rc={proc.returncode}, "
            f"{len(workers)}/{np_workers} workers reported")
    workers.sort(key=lambda w: w["rank"])
    assert len({w["device_id"] for w in workers}) == np_workers, workers
    assert all(w["device_count"] == np_workers and w["host_fallback"] == 0
               for w in workers), workers
    return emit("launch_np4", workers=workers)


def wide_expert_products(rows: int = 16384, routed: int = 8192,
                         d: int = 2048, f: int = 2048, held: int = 8,
                         repeats: int = 5, interpret: bool = False) -> dict:
    """The grouped products at an expert wider than the kernels' VMEM
    budget (``ops/grouped_matmul.py``; the defaults are ZAYA1-8B's share of
    two at 16,384 positions: 8 experts of [2048, 2048] float32, half of the
    buffer routed): a whole matrix does not fit, so the product runs in
    column blocks and dW in ``(bk, bn)`` blocks.  The product, its d rows
    and dW against a loop over the experts, then each call's time alone
    (``gmm``: the product; ``gmm_t``: d rows, the matrices read transposed;
    ``tgmm``: dW) and the SwiGLU's three products forward and backward, as
    :func:`grouped_products` times a layer's: milliseconds, best of
    ``repeats``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import grouped_matmul as gm

    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (rows, d), jnp.bfloat16)
    ct = jax.random.normal(ks[1], (rows, f), jnp.bfloat16)
    w_gate, w_up = (jax.random.normal(k, (held, d, f), jnp.float32)
                    * d ** -0.5 for k in ks[2:4])
    w_down = jax.random.normal(ks[4], (held, f, d), jnp.float32) * f ** -0.5
    # Uneven groups whose edges fall inside tiles; the tail is in no group.
    sizes = jnp.asarray([routed // held + (37 if i % 2 else -37)
                         for i in range(held)], jnp.int32)
    dot = functools.partial(gm.grouped_dot, interpret=interpret or None)

    def own(x, w, ct):
        out, vjp = jax.vjp(lambda x, w: dot(x, w, sizes), x, w)
        return (out, *vjp(ct))

    def by_loop(x, w, ct):
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        x, ct = jnp.where(live, x, 0), jnp.where(live, ct, 0)
        group = jnp.repeat(jnp.arange(held), sizes, total_repeat_length=rows)
        out, vjp = jax.vjp(lambda x, w: sum(
            jnp.where((group == g)[:, None] & live,
                      x @ w[g].astype(x.dtype), 0) for g in range(held)),
            x, w)
        return (out, *vjp(ct))

    checks = []
    for name, a, b in zip(("out", "drows", "dw"),
                          jax.jit(own)(x, w_gate, ct),
                          jax.jit(by_loop)(x, w_gate, ct)):
        _check(checks, f"wide/{name}", a, b, TOL_BF16_BWD)

    best_ms = functools.partial(_best_ms, repeats)

    def swiglu(x, *kernels):
        def loss(x, *kernels):
            h = jax.nn.silu(dot(x, kernels[0], sizes)) * dot(x, kernels[1],
                                                             sizes)
            return jnp.sum(dot(h, kernels[2], sizes).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, *kernels)

    w_itemsize = w_gate.dtype.itemsize
    report = {
        "gmm_block": next(b for b in gm._divisors(f) if gm._gmm_bytes(
            gm._tile_rows(rows), d, b, 2, w_itemsize) <= gm._VMEM_BUDGET),
        "gmm_ms": best_ms(jax.jit(lambda x, w: dot(x, w, sizes)), x, w_gate),
        "gmm_t_ms": best_ms(jax.jit(jax.grad(lambda x, w: jnp.sum(
            dot(x, w, sizes).astype(jnp.float32)))), x, w_gate),
        "tgmm_ms": best_ms(jax.jit(jax.grad(lambda w, x: jnp.sum(
            dot(x, w, sizes).astype(jnp.float32)))), w_gate, x),
        f"hvd_grouped_dot_fwd_bwd_ms/rows={rows}": best_ms(
            jax.jit(swiglu), x, w_gate, w_up, w_down),
        "rows": rows, "routed": int(jnp.sum(sizes)), "d": d, "f": f,
        "held": held}
    report = emit("wide_expert_products", checks=checks, **report)
    _raise_on_failed("wide_expert_products", checks)
    return report


def qk_norm_rope(batch: int = 2, length: int = 4096, heads=(32, 4),
                 head_dim: int = 128, repeats: int = 5, chain: int = 8,
                 interpret: bool = False) -> dict:
    """``ops/qk_norm_rope.py`` alone (the defaults are ``sdar-moe-ep8-s4096``'s
    q and k: 2 x 8,192 rows of 32 and of 4 heads of 128 in bfloat16, the
    clean and the noised copy at the same positions): value, ``dx`` and
    ``dscale`` of the kernels against the ``jax.numpy`` form, then each
    pass's time, best of ``repeats``, and the bytes it has to move (x and
    the result; x, the cotangent and dx) over that time, for the kernels and
    for the ``jax.numpy`` form as XLA compiles it.  A pass is timed as one of
    ``chain`` in one compiled program, each fed by the one before, as a step
    runs them: the cos / sin tables are made once for all of them and one
    dispatch is spread over the chain (written out and not a ``fori_loop``,
    whose carry costs a copy of x a pass: 0.47 ms of 0.93 at q's size)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import qk_norm_rope as op

    positions = jnp.tile(jnp.arange(length), 2)
    forms = {"kernels": functools.partial(op.qk_norm_rope,
                                          interpret=interpret or None),
             "dense": op.dense_qk_norm_rope}
    checks, report = [], {}
    for h in heads:
        ks = jax.random.split(jax.random.PRNGKey(h), 3)
        x, g = (jax.random.normal(k, (batch, 2 * length, h * head_dim),
                                  jnp.bfloat16) for k in ks[:2])
        scale = 1.0 + 0.1 * jax.random.normal(ks[2], (head_dim,))
        passes, chains = {}, {}
        for form, fn in forms.items():
            fn = functools.partial(fn, positions=positions, heads=h,
                                   head_dim=head_dim, eps=1e-6, theta=1e6)

            def fwd(x, s, fn=fn):
                return fn(x, s)

            # The backward alone: its forward's result is not asked for.
            def bwd(x, s, g, fn=fn):
                return jax.vjp(fn, x, s)[1](g)

            passes[form] = jax.jit(fwd), jax.jit(bwd)
            # Each pass's x is the result of the one before (the backward's:
            # its dx, and its cotangent the x before), so nothing but the
            # tables is the same from pass to pass.
            def fwd_chain(x, s, fwd=fwd):
                for _ in range(chain):
                    x = fwd(x, s)
                return x

            def bwd_chain(x, s, g, bwd=bwd):
                for _ in range(chain):
                    x, g = bwd(x, s, g)[0], x
                return x, g

            chains[form] = (("fwd", 2, jax.jit(fwd_chain), (x, scale)),
                            ("bwd", 3, jax.jit(bwd_chain), (x, scale, g)))
        for name, a, b in zip(
                ("out", "dx", "dscale"),
                (passes["kernels"][0](x, scale),
                 *passes["kernels"][1](x, scale, g)),
                (passes["dense"][0](x, scale),
                 *passes["dense"][1](x, scale, g))):
            _check(checks, f"heads={h}/{name}", a, b, TOL_BF16_FWD)
        for form, timed in chains.items():
            for name, arrays, loop, args in timed:
                ms = round(_best_ms(repeats, loop, *args) / chain, 3)
                report[f"{form}_{name}_ms/heads={h}"] = ms
                report[f"{form}_{name}_gb_s/heads={h}"] = round(
                    arrays * x.nbytes / max(ms, 1e-3) / 1e6, 1)
    report = emit("qk_norm_rope", checks=checks, batch=batch, length=length,
                  head_dim=head_dim, chain=chain, **report)
    _raise_on_failed("qk_norm_rope", checks)
    return report


def flash_window(length: int = 16384, heads=(9, 6), head_dim: int = 128,
                 windows=(512, None), repeats: int = 5, chain: int = 4,
                 interpret: bool = False) -> dict:
    """The flash kernels under a band (the defaults are
    ``laguna-swa-ep32-s16384``'s two attention shapes: 9 and 6 query heads on
    one key/value head of 128 over 16,384 rows in bfloat16, window 512 and
    none): value, dq, dk and dv of the kernels against ``dense_attention``
    with the same mask, a query head at a time (the dense scores of one head
    are a gigabyte; dk and dv are the heads' sum), then the forward's time
    and the forward and backward's together, best of ``repeats``.  A pass is
    timed as one of ``chain`` in one compiled program, each fed by the one
    before, as ``qk_norm_rope`` does.  Beside a banded call's times, its
    schedule from the plan: the forward's grid steps a head and the keys a
    row is computed against (``schedule/window=...``)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (band_of, dense_attention,
                                                 flash_attention, tile_plan)

    checks, report = [], {}
    for window in windows:
        if window is not None and window < length:
            plan = tile_plan(length, head_dim, 2, True, heads=1,
                             window=window)
            band = band_of(plan.block_q, plan.step_k, plan.tile_q, window)
            report[f"schedule/window={window}"] = {
                "grid_steps_a_head": plan.seq_pad // band.block,
                "keys_a_row": band.rows_visited, "block": band.block,
                "step": band.step, "chains": band.chains,
                "rows_beside": band.n_beside * band.beside}
    for h in heads:
        ks = jax.random.split(jax.random.PRNGKey(h), 4)
        q, g = (jax.random.normal(k, (1, length, h, head_dim), jnp.bfloat16)
                for k in ks[:2])
        k, v = (jax.random.normal(key, (1, length, 1, head_dim),
                                  jnp.bfloat16) for key in ks[2:])
        for window in windows:
            tag = f"heads={h}/window={window}"
            kernel = functools.partial(flash_attention, causal=True,
                                       window=window,
                                       interpret=interpret or None)
            dense = functools.partial(dense_attention, causal=True,
                                      window=window)

            def both(fn, q, k, v, g):
                out, vjp = jax.vjp(fn, q, k, v)
                return (out, *vjp(g))

            got = jax.jit(functools.partial(both, kernel))(q, k, v, g)
            one_head = jax.jit(functools.partial(both, dense))
            per_head = [one_head(q[:, :, i:i + 1], k, v, g[:, :, i:i + 1])
                        for i in range(h)]
            want = (*(jnp.concatenate([p[j] for p in per_head], axis=2)
                      for j in (0, 1)),
                    *(sum(p[j].astype(jnp.float32) for p in per_head)
                      for j in (2, 3)))
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
                _check(checks, f"{tag}/{name}", a, b,
                       TOL_BF16_FWD if name == "out" else TOL_BF16_BWD)

            def fwd_chain(q, k, v, kernel=kernel):
                for _ in range(chain):
                    q = kernel(q, k, v)
                return q

            def bwd_chain(q, k, v, g, kernel=kernel):
                for _ in range(chain):
                    q, k, v = jax.vjp(kernel, q, k, v)[1](g)
                return q, k, v

            fwd_ms = _best_ms(repeats, jax.jit(fwd_chain), q, k, v) / chain
            both_ms = _best_ms(repeats, jax.jit(bwd_chain), q, k, v,
                               g) / chain
            report[f"fwd_ms/{tag}"] = round(fwd_ms, 3)
            report[f"fwd_bwd_ms/{tag}"] = round(both_ms, 3)
    report = emit("flash_window", checks=checks, length=length,
                  head_dim=head_dim, chain=chain, **report)
    _raise_on_failed("flash_window", checks)
    return report


def flash_mla(length: int = 16384, heads: int = 4, head_dim: int = 128,
              rope: int = 64, repeats: int = 5, chain: int = 4,
              interpret: bool = False) -> dict:
    """The flash kernels with a second score operand (the defaults are
    ``joyai-mla-ep16-s16384``'s attention: 4 heads of 128 + 64 on one shared
    rotary key over 16,384 rows in bfloat16, scores scaled by 192^-1/2):
    value, dq, dk, dv, dq_rope and dk_rope against ``dense_attention`` with
    the pair, a head at a time (dk_rope is the heads' sum), and the value
    against the kernels without the pair (``concat192``: q and k concatenated
    to 192 a head, the shared key copied a head, v padded to 192, which is
    the ``hvd_flash_relayout`` path); then the forward's time and the forward
    and backward's, each pass one of ``chain`` in one compiled program as
    ``flash_window`` does.  What the other forms read (``concat192``, the
    rotary parts padded to 128 lanes, other blocks): PERF.md, PR 54."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (dense_attention,
                                                 flash_attention)

    scale = (head_dim + rope) ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(heads), 6)
    q, k, v, g = (jax.random.normal(key, (1, length, heads, head_dim),
                                    jnp.bfloat16) for key in ks[:4])
    qr = jax.random.normal(ks[4], (1, length, heads, rope), jnp.bfloat16)
    kr = jax.random.normal(ks[5], (1, length, 1, rope), jnp.bfloat16)

    def both(fn, g, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out, *vjp(g))

    def paired(attend, **kw):
        return lambda q, k, v, qr, kr: attend(
            q, k, v, causal=True, scale=scale, q_rope=qr, k_rope=kr, **kw)

    def concatenated(q, k, v, qr, kr):
        # The kernels without the pair: one 192-wide product a head.
        wide = [(0, 0)] * 3 + [(0, rope)]
        return flash_attention(
            jnp.concatenate([q, qr], -1), jnp.concatenate(
                [k, jnp.broadcast_to(kr, qr.shape)], -1), jnp.pad(v, wide),
            causal=True, scale=scale,
            interpret=interpret or None)[..., :head_dim]

    kernel = paired(flash_attention, interpret=interpret or None)
    checks = []
    got = jax.jit(functools.partial(both, kernel))(g, q, k, v, qr, kr)
    one_head = jax.jit(functools.partial(both, paired(dense_attention)))
    per_head = [one_head(*(x[:, :, i:i + 1] for x in (g, q, k, v, qr)), kr)
                for i in range(heads)]
    want = (*(jnp.concatenate([p[j] for p in per_head], axis=2)
              for j in range(5)),
            sum(p[5].astype(jnp.float32) for p in per_head))
    for name, a, b in zip(("out", "dq", "dk", "dv", "dq_rope", "dk_rope"),
                          got, want):
        _check(checks, name, a, b,
               TOL_BF16_FWD if name == "out" else TOL_BF16_BWD)
    _check(checks, "concat192/out", jax.jit(concatenated)(q, k, v, qr, kr),
           got[0], TOL_BF16_FWD)

    def fwd_chain(q, *rest):
        for _ in range(chain):
            q = kernel(q, *rest)
        return q

    def bwd_chain(g, *args):
        for _ in range(chain):
            args = jax.vjp(kernel, *args)[1](g)
        return args

    report = emit(
        "flash_mla", checks=checks, length=length, heads=heads,
        head_dim=head_dim, rope=rope, chain=chain,
        fwd_ms=round(_best_ms(repeats, jax.jit(fwd_chain), q, k, v, qr, kr)
                     / chain, 3),
        fwd_bwd_ms=round(_best_ms(repeats, jax.jit(bwd_chain), g, q, k, v,
                                  qr, kr) / chain, 3))
    _raise_on_failed("flash_mla", checks)
    return report


def flash_diff(length: int = 16384, heads: int = 20, kv_heads: int = 10,
               head_dim: int = 64, window: int = 512, layer: int = 17,
               repeats: int = 5, chain: int = 4,
               interpret: bool = False) -> dict:
    """Differential attention's two softmax maps on the flash kernels (the
    defaults are ``phi4flash-sambay-tp2-s16384``'s: 20 query heads on 10
    key/value heads of 64 paired by neighbours, so 10 query pairs on 5 values
    of 128, over 16,384 rows in bfloat16), causal and under the band, in two
    forms: ``padded`` (what ``models/
    phi4flash.py:two_maps`` runs: a map is one grouped call at the value's
    128 lanes, q and k zero-padded) and ``four_calls`` (the kernels as they
    stood: each map against the value's even and odd head, 64 wide, every
    score made twice).  A value operand wider than q and k inside the
    kernels is not built (PERF.md section 6, PR 60 says why).  Each form's
    ``(A1, A2)`` and its dq, dk, dv of one drawn pair of cotangents against
    ``dense_attention`` a pair at a time; the forward's time and the forward
    and backward's, each pass one of ``chain`` in one compiled program; and
    ``A1 - lambda A2`` at published layer ``layer``'s ``lambda_init``, the
    subtraction in float32 of the maps as the kernels hand them out, against
    the dense form's (``difference_error``)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import phi4flash
    from horovod_tpu.ops.flash_attention import (dense_attention,
                                                 flash_attention)

    cfg = phi4flash.Phi4FlashConfig(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        sliding_window=window, use_flash=True)
    pairs, groups, d = heads // 2, kv_heads // 2, head_dim
    ks = jax.random.split(jax.random.PRNGKey(heads), 5)
    q = jax.random.normal(ks[0], (1, length, heads * d), jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, length, kv_heads * d), jnp.bfloat16)
            for key in ks[1:3])
    g = tuple(jax.random.normal(key, (1, length, pairs, 2 * d), jnp.bfloat16)
              for key in ks[3:5])
    kernel = functools.partial(flash_attention, causal=True, scale=d ** -0.5,
                               interpret=interpret or None)

    def padded(band, q, k, v):
        return phi4flash.two_maps(cfg, q, k, v, band,
                                  interpret=interpret or None)

    def by_pair(x, n):
        x = x.reshape(1, length, n, 2, d)
        return x[:, :, :, 0], x[:, :, :, 1]

    def four_calls(band, q, k, v):
        v_even, v_odd = by_pair(v, groups)
        return tuple(jnp.concatenate(
            [kernel(q_m, k_m, half, window=band) for half in (v_even, v_odd)],
            axis=-1) for q_m, k_m in zip(by_pair(q, pairs),
                                         by_pair(k, groups)))

    def dense(band, q, k, v):
        """A pair at a time: float32 operands at full precision, the dense
        softmax (the kernels beside it keep the precision they run at)."""
        wide = lambda x: x.astype(jnp.float32)  # noqa: E731
        values = wide(v).reshape(1, length, groups, 2 * d)
        zeros = [(0, 0)] * 3 + [(0, d)]
        with jax.default_matmul_precision("highest"):
            return tuple(jnp.concatenate([dense_attention(
                jnp.pad(q_m[:, :, p:p + 1], zeros),
                jnp.pad(k_m[:, :, r:r + 1], zeros), values[:, :, r:r + 1],
                causal=True, window=band, scale=d ** -0.5)
                for p in range(pairs) for r in [p // (pairs // groups)]],
                axis=2) for q_m, k_m in zip(by_pair(wide(q), pairs),
                                            by_pair(wide(k), groups)))

    def both(fn, g, *args):
        out, vjp = jax.vjp(fn, *args)
        return (*out, *vjp(tuple(x.astype(o.dtype) for x, o in zip(g, out))))

    start = phi4flash.lambda_init(layer)

    def difference_error(maps, want) -> float:
        got, want = (a1.astype(jnp.float32) - start * a2.astype(jnp.float32)
                     for a1, a2 in (maps, want))
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    checks, times, errors = [], {}, {}
    for mask, band in (("causal", None), ("band", window)):
        want = jax.jit(functools.partial(both, functools.partial(
            dense, band)))(g, q, k, v)
        for form, fn in (("padded", padded), ("four_calls", four_calls)):
            fn = functools.partial(fn, band)
            got = jax.jit(functools.partial(both, fn))(g, q, k, v)
            for name, a, b in zip(("a1", "a2", "dq", "dk", "dv"), got, want):
                _check(checks, f"{mask}/{form}/{name}", a, b,
                       TOL_BF16_FWD if name[0] == "a" else TOL_BF16_BWD)
            errors[f"{mask}/{form}"] = difference_error(got[:2], want[:2])
            if not interpret or form == "padded":
                times[f"{mask}/{form}"] = _two_maps_ms(fn, g, (q, k, v),
                                                       chain, repeats)
    report = emit("flash_diff", checks=checks, length=length, heads=heads,
                  kv_heads=kv_heads, head_dim=head_dim, window=window,
                  chain=chain, lambda_init=start, times=times,
                  difference_error=errors)
    _raise_on_failed("flash_diff", checks)
    return report


def _two_maps_ms(fn, g, operands, chain: int, repeats: int) -> dict:
    """``fwd_ms`` and ``fwd_bwd_ms`` of ``fn(q, k, v) -> (A1, A2)``: each pass
    one of ``chain`` in one compiled program, a pass fed one element of each
    result of the one before so that no kernel can be dropped or
    overlapped."""
    import jax

    def fed(x, like):
        return x.at[0, 0, 0].add(like.ravel()[0].astype(x.dtype))

    def fwd_chain(q, k, v):
        for _ in range(chain):
            a1, a2 = fn(q, k, v)       # both read: neither map is dropped
            q = fed(fed(q, a1), a2)
        return q

    def bwd_chain(g, q, k, v):
        for _ in range(chain):
            dq, dk, dv = jax.vjp(fn, q, k, v)[1](g)
            q, k, v = fed(q, dq), fed(k, dk), fed(v, dv)
        return q, k, v

    return {"fwd_ms": round(_best_ms(repeats, jax.jit(fwd_chain), *operands)
                            / chain, 3),
            "fwd_bwd_ms": round(_best_ms(repeats, jax.jit(bwd_chain), g,
                                         *operands) / chain, 3)}


def _chain_ms(fn, g, operands, chain: int, repeats: int) -> dict:
    """``fwd_ms`` and ``fwd_bwd_ms`` of ``fn(*operands)`` (the result shaped
    like its first operand): each pass one of ``chain`` in one compiled
    program, the forward's result fed back as the first operand, the
    backward's cotangents as the next operands."""
    import jax

    def fwd_chain(first, *rest):
        for _ in range(chain):
            first = fn(first, *rest)
        return first

    def bwd_chain(g, *args):
        for _ in range(chain):
            args = jax.vjp(fn, *args)[1](g)
        return args

    return {"fwd_ms": round(_best_ms(repeats, jax.jit(fwd_chain), *operands)
                            / chain, 3),
            "fwd_bwd_ms": round(_best_ms(repeats, jax.jit(bwd_chain), g,
                                         *operands) / chain, 3)}


def published_slopes(heads: int = 32, first: int = 24, held: int = 8,
                     layer: int = 1, layers: int = 32):
    """The slopes of MiniCPM-SALA's held lightning heads (``configs/
    minicpm-sala-tp4.json``: ``assumed.slopes``): ``2^(-8 (h + 1) / heads) x
    (1 - layer / (layers - 1) + 1e-5)``."""
    import jax.numpy as jnp

    h = jnp.arange(first, first + held, dtype=jnp.float32)
    return 2.0 ** (-8.0 * (h + 1) / heads) * (1 - layer / (layers - 1) + 1e-5)


def lightning(length: int = 16384, heads: int = 8, head_dim: int = 128,
              chunks=(256, 128), repeats: int = 5, chain: int = 4,
              check_rows: int = 2048, interpret: bool = False) -> dict:
    """The lightning-attention kernels (the defaults are
    ``sala-sparse-linear-tp4-s16384``'s: heads 24 to 31 of 32 with the
    published slopes of layer 1, 16,384 rows of 128 in bfloat16): value, dq,
    dk, dv of the first ``check_rows`` rows against the quadratic form
    ``((Q K^T) * D) V`` in float32, and of the whole length against the
    ``lax.scan`` form (the carried state crosses every block); then the
    forward's time and the forward and backward's for each chunk size of
    ``chunks``, each pass one of ``chain`` in one compiled program."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import lightning_attention as la

    slopes = published_slopes(held=heads)
    scale = head_dim ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    q, k, v, g = (jax.random.normal(key, (1, length, heads, head_dim),
                                    jnp.bfloat16) for key in ks)

    def both(fn, g, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out, *vjp(g))

    def kernel(chunk):
        return lambda q, k, v: la.lightning_attention(
            q, k, v, slopes, scale, chunk=min(chunk, length),
            interpret=interpret or None)

    checks, times = [], {}
    rows = min(check_rows, length)
    head = [x[:, :rows] for x in (g, q, k, v)]
    quadratic = jax.jit(functools.partial(both, lambda q, k, v: (
        la.lightning_attention_quadratic(q, k, v, slopes, scale))))(
            *(x.astype(jnp.float32) for x in head))
    scan = jax.jit(functools.partial(both, lambda q, k, v: (
        la.lightning_attention_scan(q, k, v, slopes, scale))))(g, q, k, v)
    for chunk in chunks:
        fn = kernel(chunk)
        got_head = jax.jit(functools.partial(both, fn))(*head)
        got = jax.jit(functools.partial(both, fn))(g, q, k, v)
        for name, a, b, c, d in zip(("out", "dq", "dk", "dv"), got_head,
                                    quadratic, got, scan):
            tol = TOL_BF16_FWD if name == "out" else TOL_BF16_BWD
            _check(checks, f"chunk{chunk}/quadratic/{name}", a, b, tol)
            _check(checks, f"chunk{chunk}/scan/{name}", c, d, tol)
        times[f"chunk{chunk}"] = _chain_ms(fn, g, (q, k, v), chain, repeats)
    report = emit("lightning", checks=checks, length=length, heads=heads,
                  head_dim=head_dim, chain=chain, times=times)
    _raise_on_failed("lightning", checks)
    return report


# MiniCPM4's sparse_config (``configs/minicpm-sala-tp4.json``: ``assumed``).
SPARSE = {"kernel_size": 32, "stride": 16, "block": 64, "topk": 64,
          "init_blocks": 1, "local_blocks": 32}


def flash_select(length: int = 16384, heads: int = 8, head_dim: int = 128,
                 sparse=None, forms=((256, 256, 256, 256), (512, 256, 512, 256),
                                     (512, 512, 512, 512),
                                     (1024, 256, 1024, 256),
                                     (1024, 512, 1024, 512),
                                     (512, 256, 1024, 256),
                                     (1024, 256, 512, 256)),
                 repeats: int = 5, chain: int = 4, check_rows: int = 4096,
                 interpret: bool = False) -> dict:
    """The selected walk (the defaults are ``sala-sparse-linear-tp4-s16384``'s
    sparse layer: 8 query heads on 1 key/value head, 16,384 x 128 in
    bfloat16, MiniCPM4's selection constants): the selection's time alone;
    value, dq, dk, dv of the kernels on the selection of drawn q and k
    against ``dense_select`` (the masked dense softmax) over the first
    ``check_rows`` rows, a head at a time; the walk's counters; then the
    forward's time and the forward and backward's in each of ``forms``
    (query tile, key step, key tile, query step), one of ``chain`` in one
    compiled program, beside the plain causal kernels on the same operands."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_select as fs
    from horovod_tpu.ops.flash_attention import flash_attention

    sparse = dict(sparse or SPARSE)
    scale = head_dim ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    q, g = (jax.random.normal(key, (1, length, heads, head_dim),
                              jnp.bfloat16) for key in ks[:2])
    k, v = (jax.random.normal(key, (1, length, 1, head_dim), jnp.bfloat16)
            for key in ks[2:])
    choose = jax.jit(lambda q, k: fs.sparse_select(q, k, scale=scale,
                                                   **sparse).bits)
    bits = choose(q, k)
    select = fs.Selection(bits, sparse["block"])

    def both(fn, g, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out, *vjp(g))

    def walk(form, rows=length):
        sizes = dict(zip(("tile_q", "step_k", "tile_k", "step_q"),
                         (min(f, rows) for f in form)))
        chosen = fs.Selection(bits[..., :rows], sparse["block"])
        return lambda q, k, v: fs.flash_select(
            q, k, v, chosen, scale, interpret=interpret or None, **sizes)[0]

    checks, times = [], {}
    rows = min(check_rows, length)
    head = [x[:, :rows] for x in (g, q, k, v)]
    dense = jax.jit(functools.partial(both, lambda q, k, v: fs.dense_select(
        q, k, v, fs.Selection(bits[..., :rows], sparse["block"]), scale)[0]))
    per_head = [dense(head[0][:, :, i:i + 1], head[1][:, :, i:i + 1],
                      head[2], head[3]) for i in range(heads)]
    want = (jnp.concatenate([p[0] for p in per_head], 2),
            jnp.concatenate([p[1] for p in per_head], 2),
            sum(p[2].astype(jnp.float32) for p in per_head),
            sum(p[3].astype(jnp.float32) for p in per_head))
    for form in forms:
        name = "x".join(map(str, form))
        got = jax.jit(functools.partial(both, walk(form, rows)))(*head)
        for what, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            _check(checks, f"{name}/{what}", a, b,
                   TOL_BF16_FWD if what == "out" else TOL_BF16_BWD)
        times[name] = {
            **_chain_ms(walk(form), g, (q, k, v), chain, repeats),
            "counters": {n: float(c) for n, c in jax.jit(
                lambda b, form=form: fs.walk_counters(
                    fs.Selection(b, sparse["block"]), length,
                    min(form[0], length), min(form[1], length)))(
                        bits).items()}}
    causal = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, scale=scale, interpret=interpret or None)
    report = emit(
        "flash_select", checks=checks, length=length, heads=heads,
        head_dim=head_dim, chain=chain, times=times,
        select_ms=_best_ms(repeats, choose, q, k),
        plain_causal_fwd_bwd_ms=_chain_ms(causal, g, (q, k, v), chain,
                                          repeats)["fwd_bwd_ms"])
    _raise_on_failed("flash_select", checks)
    return report


def _whole_logits_loss(tied: bool, x, matrix, labels, weights):
    """The blocked head's loss with the float32 logits whole: the product in
    ``x.dtype`` and ``softmax_cross_entropy``, the form ``laguna.Laguna.loss``
    and ``joyai.JoyAI.loss`` had before ``losses.head_cross_entropy``."""
    import jax.numpy as jnp

    from horovod_tpu.models import losses

    table = matrix.astype(x.dtype)
    logits = jnp.dot(x, table.T if tied else table,
                     preferred_element_type=jnp.float32)
    return jnp.sum(weights * losses.softmax_cross_entropy(logits, labels))


# The four blocked heads of the benchmark, ``(d, rows, tied)``: the tied
# tables of ``zaya1-moe-ep2-s16384`` and ``jamba2-ssm-tp4-s16384`` (``[V, d]``)
# and the heads of their own of ``laguna-swa-ep32-s16384`` and
# ``joyai-mla-ep16-s16384`` (``[d, V]``).
HEADS = ((2048, 131136, True), (2560, 16384, True),
         (3072, 12544, False), (2048, 16160, False))


def tied_head(heads=HEADS, tokens: int = 16384,
              blocks=(2048, 4096, 8192, 16384), repeats: int = 5,
              chain: int = 4, interpret: bool = False) -> dict:
    """``ops/tied_head.py`` alone and in its place, by the head and by the
    block of tokens (the defaults: the four heads above, 16,384 tokens,
    bfloat16).  A block's logits and log-sum-exp by ``hvd_head_logits``
    against the ``jax.numpy`` product and row statistics as XLA compiles
    them alone, each timed as one of ``chain`` in one compiled program; then
    value, ``dx`` and the matrix's gradient of ``tied_head_cross_entropy`` /
    ``head_cross_entropy`` over all the tokens with the kernel and with
    ``_block_nll``'s ``jax.numpy``, and both times: the second pair is the
    one that says what a step gains, since XLA schedules its own product
    differently beside the two backward ones.  Where all the tokens'
    float32 logits take less than 2 GiB, the head with every logit alive
    (:func:`_whole_logits_loss`) is compared and timed too.  ``blocks``: the
    blocks of tokens a head is timed at, those left out whose float32 logits
    would take 2 GiB or more (ZAYA's past 2,048); ``models/losses.py`` itself
    takes the largest whose logits fit ``HEAD_BLOCK_BYTES``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import losses
    from horovod_tpu.ops import tied_head as op

    kernel = functools.partial(op.head_logits, interpret=interpret or None)

    def dense(x, table):
        # ``_block_nll``'s own ``jax.numpy``: its logits and log-sum-exp.
        with mock.patch.object(losses, "head_logits", lambda *_: None):
            return losses._block_nll(
                x, table, jnp.zeros(x.shape[:1], jnp.int32))[1:]

    def chained(fn):
        def run(x, table):
            kept = []
            for _ in range(chain):
                logits, lse = fn(x, table)
                kept.append(logits)
                x = x + (0 * lse).astype(x.dtype)
            return kept, x
        return jax.jit(run)

    def whole(head, loss, rows, block):
        # A function of its own a side (jit's cache is keyed on it), traced
        # while ``_block_nll`` finds ``head`` in the kernel's place.
        def run(*args):
            with mock.patch.object(losses, "head_logits", head), \
                    mock.patch.object(losses, "HEAD_BLOCK_BYTES",
                                      4 * rows * block):
                return jax.value_and_grad(loss, argnums=(0, 1))(*args)
        return jax.jit(run)

    checks, report = [], {}

    def compare(tag, got, want, tol):
        # (loss, (dx, d matrix)) of two sides.
        _check(checks, f"{tag}/loss", got[0], want[0], 1e-6)
        _check(checks, f"{tag}/dx", got[1][0], want[1][0], TOL_BF16_FWD)
        _check(checks, f"{tag}/dmatrix", got[1][1], want[1][1], tol)

    for d, rows, tied in heads:
        ks = jax.random.split(jax.random.PRNGKey(rows), 4)
        x = jax.random.normal(ks[0], (tokens, d)).astype(jnp.bfloat16)
        table = jax.random.normal(ks[1], (rows, d)) / 20
        labels = jax.random.randint(ks[2], (tokens,), 0, rows)
        weights = jax.random.uniform(ks[3], (tokens,)) / tokens
        matrix, loss = (table, losses.tied_head_cross_entropy) if tied else (
            table.T, losses.head_cross_entropy)
        tb = table.astype(jnp.bfloat16)
        for block in (b for b in blocks if 4 * rows * b < 2 ** 31):
            tag = f"d={d}/rows={rows}/block={block}"
            xb = x[:block]
            for name, a, b in zip(("logits", "lse"), jax.jit(kernel)(xb, tb),
                                  jax.jit(dense)(xb, tb)):
                _check(checks, f"{tag}/{name}", a, b, 2e-6)
            for form, fn in (("kernel", kernel), ("dense", dense)):
                ms = _best_ms(repeats, chained(fn), xb, tb) / chain
                report[f"{form}_ms/{tag}"] = round(ms, 3)
                report[f"{form}_tflop_s/{tag}"] = round(
                    2 * block * d * rows / max(ms, 1e-3) / 1e9, 1)
            sides = {"kernel": whole(kernel, loss, rows, block),
                     "dense": whole(lambda *_: None, loss, rows, block)}
            compare(tag, *(sides[form](x, matrix, labels, weights)
                           for form in ("kernel", "dense")), 1e-3)
            for form, fn in sides.items():
                report[f"head_{form}_ms/{tag}"] = _best_ms(
                    repeats, fn, x, matrix, labels, weights)
        if 4 * rows * tokens < 2 ** 31:
            # Every logit alive, as a model without the blocks makes them.
            side = jax.jit(jax.value_and_grad(functools.partial(
                _whole_logits_loss, tied), argnums=(0, 1)))
            # (Its cast's transpose rounds ``d matrix`` to bfloat16.)
            compare(f"d={d}/rows={rows}/whole",
                    *(fn(x, matrix, labels, weights)
                      for fn in (sides["kernel"], side)), TOL_BF16_FWD)
            report[f"head_whole_ms/d={d}/rows={rows}"] = _best_ms(
                repeats, side, x, matrix, labels, weights)
    report = emit("tied_head", checks=checks, tokens=tokens, chain=chain,
                  **report)
    _raise_on_failed("tied_head", checks)
    return report


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run launch_np4 and nothing else")
    ap.add_argument("--grouped-products", action="store_true",
                    help="time the expert layer's grouped products, and "
                         "nothing else")
    ap.add_argument("--qk-norm-rope", action="store_true",
                    help="check and time the q/k norm + rotary op, and "
                         "nothing else")
    ap.add_argument("--flash-window", action="store_true",
                    help="check and time the banded flash kernels, and "
                         "nothing else")
    ap.add_argument("--flash-mla", action="store_true",
                    help="check and time the flash kernels with a second "
                         "score operand, and nothing else")
    ap.add_argument("--flash-diff", action="store_true",
                    help="check and time differential attention's two maps "
                         "on the flash kernels, and nothing else")
    ap.add_argument("--lightning", action="store_true",
                    help="check and time the lightning-attention kernels, "
                         "and nothing else")
    ap.add_argument("--flash-select", action="store_true",
                    help="check and time the selection and the selected "
                         "walk's flash kernels, and nothing else")
    ap.add_argument("--tied-head", action="store_true",
                    help="check and time the blocked heads' logits kernel, "
                         "and nothing else")
    ap.add_argument("--embed-grad", action="store_true",
                    help="check and time the embedding lookup's gradient, "
                         "and nothing else")
    ap.add_argument("--worker", action="store_true",
                    help="internal: one launch_np4 worker")
    args = ap.parse_args(argv)
    if args.worker:
        launch_np4_worker()
        return 0

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # exported: the launcher's workers share it
    if args.chips == 4:
        launch_np4()
        info = device(expect_count=4)
    elif args.grouped_products:
        info = device()
        grouped_products()
        sum_rows()
        wide_expert_products()
    elif args.qk_norm_rope:
        info = device()
        qk_norm_rope()
    elif args.flash_window:
        info = device()
        flash_window()
    elif args.flash_mla:
        info = device()
        flash_mla()
    elif args.flash_diff:
        info = device()
        flash_diff()
    elif args.lightning:
        info = device()
        lightning()
    elif args.flash_select:
        info = device()
        flash_select()
    elif args.tied_head:
        info = device()
        tied_head()
    elif args.embed_grad:
        info = device()
        embed_grad()
    else:
        info = device()
        native_core()
        kernels()
        eager()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
