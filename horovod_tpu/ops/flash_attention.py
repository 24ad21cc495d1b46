"""Flash attention as a Pallas TPU kernel.

The reference's only custom kernels are CUDA memcpy/scale helpers
(horovod/common/ops/cuda/cuda_kernels.cu; SURVEY.md §2.2) — its models come
from torch/TF.  This framework owns its model zoo, so the hot op worth a
hand kernel on TPU is attention: this kernel keeps the [S, S] score matrix
out of HBM entirely (VMEM-blocked online softmax), the classic
flash-attention trade.

Layout: inputs [batch, seq, heads, head_dim]; the kernels run on
[batch*heads, seq, head_dim] with streaming (BH, n_q, n_kv)-style grids:
K/V (forward) or Q/dO (dK/dV backward) blocks flow through VMEM while the
online-softmax state (acc/m/l, or the dq/dk/dv partials) persists in f32
scratch across the innermost grid steps — so no operand is ever VMEM-whole
and sequence length is HBM-bound, not VMEM-bound.  Causal mode requires
block_q == block_k; tiles above the diagonal (and tiles entirely in tail
padding) are predicated off with pl.when, so every processed row has at
least one valid key (keeps the online-softmax max finite with a -1e30 mask
value, no NaN guards needed).

Off-TPU (CPU tests) the public wrapper falls back to an identical-math
dense implementation; the kernels are unit-tested in interpret mode and
validated on hardware by chip_smoke.py's ``kernels`` phase.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Per-row statistics (lse, delta) cross the pallas_call boundary broadcast
# across a trailing 128-lane dimension: the TPU lowering requires the last
# two block dims to be (sublane-multiple, lane-multiple-or-whole), so a
# [rows] vector must ride as [rows, 128] (the same layout the reference
# jax TPU kernel uses for its l/m outputs, MIN_BLOCK_SIZE lanes).  Inside
# kernels the [:, :1] column is the value; wrappers squeeze lane 0.
LANES = 128


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct for a pallas_call output, carrying the varying-
    manual-axes type of ``like`` so the kernel can run inside shard_map
    (check_vma requires outputs to declare their mesh-axis variance)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _block_live(iq, jk, causal: bool, block_q: int, block_k: int,
                valid_len: int, seq_len: int):
    """Whether the (q-block iq, k-block jk) tile can contribute: on the TPU
    the grid is sequential and can't be shortened per-row, so dead tiles
    (above the causal diagonal, or entirely in tail padding) are skipped by
    predication — the dots never issue, only the pipelined DMA runs."""
    live = jk * block_k < valid_len
    if causal:
        live = jnp.logical_and(live,
                               (iq + 1) * block_q - 1 >= jk * block_k)
    return live


def _mha_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale: float, causal: bool, block_q: int, block_k: int,
                valid_len: int):
    """Streaming forward: grid (BH, n_q, n_kv), K/V blocks flow through
    VMEM while acc/m/l persist in scratch across the innermost kv steps
    (the o/lse output blocks are revisited and written on the last step)."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    n_kv = pl.num_programs(2)
    seq_len = n_kv * block_k
    padded = valid_len < seq_len

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_block_live(iq, jk, causal, block_q, block_k, valid_len,
                         seq_len))
    def _compute():
        # Dots run on the MXU in the input dtype (bf16 native rate, 2x the
        # f32 path) with f32 accumulation; softmax math stays f32.  The
        # sm_scale folds in after the QK dot so it happens in f32.
        q = q_ref[:]                                      # [Bq, D]
        k = k_ref[:]                                      # [Bk, D]
        v = v_ref[:]
        s = jax.lax.dot_general(                          # [Bq, Bk] on MXU
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal or padded:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if causal:
                # Padding lives at the tail, so kpos > any real qpos —
                # the causal mask already excludes padded keys.
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            else:
                s = jnp.where(kpos < valid_len, s, NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(jk == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        # Log-sum-exp per query row, the residual the backward pass needs
        # to re-materialize P = exp(S - lse) blockwise without storing
        # [S, S].  Written lane-broadcast ([Bq, LANES]) per the TPU
        # block-shape rule.
        lse_ref[:] = jnp.broadcast_to(m_ref[:] + jnp.log(l),
                                      (block_q, LANES))


def _flash_fwd_bhsd(qb, kb, vb, sm_scale, causal, block_q, block_k,
                    interpret, valid_len):
    """Forward kernel over [BH, S, D] (S already padded): out + row lse."""
    bh, s, d = qb.shape
    grid = (bh, s // block_q, s // block_k)
    kernel = functools.partial(_mha_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               valid_len=valid_len)
    out, lse_lanes = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((bh, s, d), qb.dtype, qb),
            _out_struct((bh, s, LANES), jnp.float32, qb),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb)
    return out, lse_lanes[:, :, 0]


def _mha_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, acc_ref, *, sm_scale: float, causal: bool,
                       block_q: int, block_k: int, valid_len: int):
    """dQ, streaming: grid (BH, n_q, n_kv); K/V blocks flow past a fixed
    query block while dq accumulates in f32 scratch (the dq output block is
    revisited and written on the last kv step).  P is re-materialized from
    the lse residual — the [S, S] score matrix never exists."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    n_kv = pl.num_programs(2)
    seq_len = n_kv * block_k
    padded = valid_len < seq_len

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(iq, jk, causal, block_q, block_k, valid_len,
                         seq_len))
    def _compute():
        q = q_ref[:]                                       # [Bq, D]
        k = k_ref[:]                                       # [Bk, D]
        v = v_ref[:]
        do = do_ref[:].astype(jnp.float32)                 # [Bq, D]
        lse = lse_ref[:][:, :1]                            # [Bq, 1] f32
        delta = delta_ref[:][:, :1]                        # [Bq, 1] f32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal or padded:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if causal:
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            else:
                s = jnp.where(kpos < valid_len, s, NEG_INF)
        p = jnp.exp(s - lse)                               # [Bq, Bk]
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale                   # [Bq, Bk]
        acc_ref[:] = acc_ref[:] + jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(jk == n_kv - 1)
    def _flush():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _mha_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale: float,
                        causal: bool, block_q: int, block_k: int,
                        valid_len: int):
    """dK/dV, streaming: grid (BH, n_kv, n_q); Q/dO/stat blocks flow past a
    fixed key block while dk/dv accumulate in f32 scratch."""
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    n_q = pl.num_programs(2)
    seq_len = n_q * block_q
    padded = valid_len < seq_len

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(iq, jk, causal, block_q, block_k, valid_len,
                         seq_len))
    def _compute():
        q = q_ref[:]                                       # [Bq, D]
        k = k_ref[:]                                       # [Bk, D]
        v = v_ref[:]
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:][:, :1]
        delta = delta_ref[:][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal or padded:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if causal:
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            else:
                s = jnp.where(kpos < valid_len, s, NEG_INF)
        p = jnp.exp(s - lse)                               # [Bq, Bk]
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(       # P^T @ dO
            p.astype(do_ref.dtype), do.astype(do_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(       # dS^T @ Q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == n_q - 1)
    def _flush():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_bhsd(qb, kb, vb, ob, lse, dob, sm_scale, causal, block_q,
                    block_k, interpret, valid_len, dlse=None):
    bh, s, d = qb.shape
    # delta_i = rowsum(dO_i * O_i) — the standard backward residual.  An
    # lse cotangent (pair-valued VJP) folds in as delta - dlse.
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)                               # [BH, S]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    # Per-row stats enter the kernels lane-broadcast (see LANES).
    lse_l = jnp.broadcast_to(lse.astype(jnp.float32)[..., None],
                             (bh, s, LANES))
    delta_l = jnp.broadcast_to(delta[..., None], (bh, s, LANES))
    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, valid_len=valid_len)
    # dq: q-block fixed per outer step, k/v stream on the inner grid dim.
    q_by_i = pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0))
    kv_by_j = pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0))
    row_by_i = pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_mha_bwd_dq_kernel, **common),
        grid=(bh, s // block_q, s // block_k),
        in_specs=[q_by_i, kv_by_j, kv_by_j, q_by_i, row_by_i, row_by_i],
        out_specs=q_by_i,
        out_shape=_out_struct((bh, s, d), qb.dtype, qb),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, dob, lse_l, delta_l)
    # dk/dv: k-block fixed per outer step, q/do/stats stream inside.
    q_by_j = pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, j, 0))
    kv_by_i = pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, i, 0))
    row_by_j = pl.BlockSpec((None, block_q, LANES), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_mha_bwd_dkv_kernel, **common),
        grid=(bh, s // block_k, s // block_q),
        in_specs=[q_by_j, kv_by_i, kv_by_i, q_by_j, row_by_j, row_by_j],
        out_specs=[kv_by_i, kv_by_i],
        out_shape=[_out_struct((bh, s, d), kb.dtype, kb),
                   _out_struct((bh, s, d), vb.dtype, vb)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, dob, lse_l, delta_l)
    return dq, dk, dv




@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhsd_lse(qb, kb, vb, sm_scale, causal, block_q, block_k,
                    interpret, valid_len):
    """Differentiable kernel entry over [BH, S, D] (S already padded),
    returning ``(out, lse)`` — the pair ring attention merges across hops
    (the public ``flash_attention`` wrapper simply discards the lse).

    The backward for the pair is the standard flash backward with one
    twist: dL/dS_ij gains a ``+ dlse_i * p_ij`` term, which folds into the
    existing kernels as ``delta_i -> delta_i - dlse_i`` (both enter as
    ``ds = p * (dp - delta)``) — no separate kernels needed.
    """
    return _flash_fwd_bhsd(qb, kb, vb, sm_scale, causal, block_q, block_k,
                           interpret, valid_len)


def _flash_bhsd_lse_fwd(qb, kb, vb, sm_scale, causal, block_q, block_k,
                        interpret, valid_len):
    out, lse = _flash_fwd_bhsd(qb, kb, vb, sm_scale, causal, block_q,
                               block_k, interpret, valid_len)
    return (out, lse), (qb, kb, vb, out, lse)


def _flash_bhsd_lse_bwd(sm_scale, causal, block_q, block_k, interpret,
                        valid_len, res, cotangents):
    qb, kb, vb, ob, lse = res
    dob, dlse = cotangents
    dq, dk, dv = _flash_bwd_bhsd(qb, kb, vb, ob, lse, dob, sm_scale, causal,
                                 block_q, block_k, interpret, valid_len,
                                 dlse=dlse)
    return dq, dk, dv


_flash_bhsd_lse.defvjp(_flash_bhsd_lse_fwd, _flash_bhsd_lse_bwd)


def dense_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """Reference-math dense attention over [B, S, H, D] (fp32 softmax)."""
    out, _ = dense_attention_with_lse(q, k, v, causal, scale)
    return out


def dense_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None):
    """Dense attention that also returns log-sum-exp [B, H, S] (the chunk
    statistic ring attention merges across hops)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)      # [B, H, S]
    probs = jnp.exp(logits - lse[..., None]).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out, lse


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: int = 128, block_k: int = 128,
                             interpret: Optional[bool] = None):
    """Pallas attention over [B, S, H, D] returning ``(out, lse)`` with
    lse shaped [B, H, S].  Same dispatch rules as :func:`flash_attention`;
    off-TPU it falls back to :func:`dense_attention_with_lse`."""
    b, s, h, d = q.shape
    if interpret is None:
        if jax.default_backend() != "tpu":
            return dense_attention_with_lse(q, k, v, causal, scale)
        interpret = False
    sm_scale = d ** -0.5 if scale is None else scale
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if causal and block_q != block_k:
        block_q = block_k = min(block_q, block_k)
    import math

    block = math.lcm(block_q, block_k)
    s_pad = -(-s // block) * block
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s_pad, d)

    out, lse = _flash_bhsd_lse(to_bhsd(q), to_bhsd(k), to_bhsd(v), sm_scale,
                               causal, block_q, block_k, bool(interpret), s)
    out = out.reshape(b, h, s_pad, d).transpose(0, 2, 1, 3)[:, :s]
    lse = lse.reshape(b, h, s_pad)[:, :, :s]
    return out, lse


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Attention over [batch, seq, heads, head_dim].

    On TPU this is the Pallas kernel; elsewhere it falls back to the dense
    implementation (identical math) unless ``interpret=True`` forces the
    kernel through the Pallas interpreter (tests).
    """
    out, _ = flash_attention_with_lse(q, k, v, causal, scale, block_q,
                                      block_k, interpret)
    return out
