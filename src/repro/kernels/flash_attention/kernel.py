"""Fused FlashAttention forward as a Pallas TPU kernel — the TPU-native
realization of the paper's SystolicAttention schedule (DESIGN.md §2).

The paper fuses QKᵀ → online softmax → PV inside one systolic array so no
intermediate ever leaves the array.  On TPU the equivalent is one Pallas
kernel whose S/P tiles never leave VMEM:

  * grid = (batch·heads, num_q_blocks, num_k_blocks); the KV dimension is
    innermost, so the fp32 running statistics (m, l) and the output
    accumulator live in VMEM scratch across KV steps — the analogue of the
    CMP-row registers and the accumulation SRAM.  m and l are stored
    lane-broadcast, ``[block_q, 128]``, the layout of a reduced row;
  * bf16 q, k and v reach the MXU as bf16 with fp32 accumulation, and the
    fp32 P as three bf16 slices that sum to it exactly; other dtypes run
    both products as fp32 ``HIGHEST`` dots;
  * Br = Bc = 128 blocks match the paper's §3.5 tiling (= MXU tile);
  * softmax uses exp2 with the 1/sqrt(d) scale folded into the exp2
    argument — *exactly* Algorithm 1's operation order (rowmax on unscaled
    scores), preserving the paper's numerics claims;
  * optionally the 8-segment PWL exp2 (paper §3.3) computed with the same
    slope/intercept MAC formulation, on the VPU;
  * GQA without materializing repeated KV heads (index_map arithmetic);
  * causal grid steps whose (q block, KV block) pair lies wholly above the
    diagonal do no work and fetch nothing: the body sits under
    ``pl.when(live)`` and the KV index_map clamps a dead step to the row's
    last live block, which is already in VMEM.  The skip is exact: such a
    block would add ``exp2(-huge) = 0`` to ``l``, ``0 @ V`` to the
    accumulator and leave ``m`` as it was;
  * with a sliding ``window`` the KV axis of the grid covers only the
    blocks a window can reach: step ``j`` of q block ``i`` reads KV block
    ``first_window_kv_block(i) + j``, so the blocks below the band never
    iterate, and the lower edge is masked per element.

The backward pass has its own Pallas kernels (kernel_bwd.py): the forward
optionally emits base-2 log-sum-exp rows, and FlashAttention-2-style dq /
dkv grids recompute P per VMEM tile from the LSE — S/P are never stored.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pwl_exp2 import LOG2_E, pwl_coeffs

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
LANES = 128  # TPU vector lane width: row statistics are stored lane-broadcast


def last_live_kv_block(i, block_q, block_k, q_offset):
    """Last KV block that q block ``i`` sees under the causal mask."""
    return (i * block_q + q_offset + block_q - 1) // block_k


def first_live_q_block(j, block_q, block_k, q_offset):
    """First q block that sees KV block ``j`` under the causal mask."""
    return (j * block_k - q_offset) // block_q


def first_window_kv_block(i, block_q, block_k, q_offset, window):
    """First KV block that q block ``i`` sees under a window of ``window``
    keys (row ``r`` sees columns ``r - window < c <= r``)."""
    return jnp.maximum(i * block_q + q_offset - window + 1, 0) // block_k


def window_kv_steps(window, block_q, block_k, num_k):
    """KV steps of a windowed grid: the most KV blocks one q block's band,
    ``window + block_q - 1`` columns wide, can touch."""
    return min(num_k, -(-(window + block_q - 1) // block_k) + 1)


def causal_grid_steps(sq, sk, block_q, block_k, q_offset, causal):
    """(live, total) grid steps per head of a flash grid over (q, KV) blocks.

    Block ``(i, j)`` is live iff some row ``i*block_q + q_offset + r`` may
    attend some column ``j*block_k + c``; non-causal calls are all live.
    The forward, dq and dkv grids skip the same steps.
    """
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    num_q, num_k = -(-sq // block_q), -(-sk // block_k)
    total = num_q * num_k
    if not causal:
        return total, total
    live = sum(
        min(last_live_kv_block(i, block_q, block_k, q_offset) + 1, num_k)
        for i in range(num_q)
    )
    return live, total


def _exp2_inline(x: jax.Array, exp2_impl: str, num_segments: int) -> jax.Array:
    """exp2 on a VMEM-resident fp32 tile; 'pwl' follows §3.3 bit-for-bit."""
    if exp2_impl == "exact":
        return jnp.exp2(x)
    x_i = jnp.ceil(x)
    x_f = x - x_i
    idx = jnp.clip(
        jnp.floor((x_f + 1.0) * num_segments).astype(jnp.int32), 0, num_segments - 1
    )
    # Segment select on the VPU; mirrors the hardware streaming
    # slope/intercept into the PE rows.
    slope, intercept = pwl_coeffs(idx, num_segments)
    frac = slope * x_f + intercept  # the PE-MAC step
    e = jnp.clip(x_i, -150.0, 127.0).astype(jnp.int32)
    out = jnp.ldexp(frac, e)
    return jnp.where(x_i < -148, 0.0, out)


def _lane_rows(x: jax.Array, width: int) -> jax.Array:
    """A lane-broadcast ``[rows, LANES]`` statistic against a ``[rows,
    width]`` tile: as it is where the widths match, else its first column."""
    return x if width == LANES else x[:, :1]


def _fwd_kernel(
    q_ref,  # [1, block_q, d]
    k_ref,  # [1, block_k, d]
    v_ref,  # [1, block_k, d]
    *refs,  # o_ref, [lse_ref], m_scr, l_scr, acc_scr
    num_k_blocks: int,  # KV steps of the grid
    block_q: int,
    block_k: int,
    causal: bool,
    sm_scale: float,
    q_offset: int,
    exp2_impl: str,
    num_segments: int,
    seq_k: int,
    with_lse: bool,
    window: Optional[int] = None,
    num_kv: int = 0,  # KV blocks of the input (windowed grids)
):
    if with_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        lse_ref = None
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    c = sm_scale * LOG2_E  # folded scale (Algorithm 1 lines 10/12)

    # Causal: KV blocks wholly above the diagonal add exact zeros, so their
    # steps do nothing; they come after every live block of the row.
    if window is None:
        kb = j
        live = j <= last_live_kv_block(i, block_q, block_k, q_offset) if causal else True
    else:
        # Windowed: step j reads the j-th block of the row's band.
        kb = first_window_kv_block(i, block_q, block_k, q_offset, window) + j
        last = jnp.minimum(last_live_kv_block(i, block_q, block_k, q_offset), num_kv - 1)
        live = kb <= last

    @pl.when(live)
    def _step():
        # bf16 operands go to the MXU as they are stored: a bf16 x bf16
        # product is exact in fp32, so one pass forms what a fp32 HIGHEST dot
        # (six passes) forms from their upcast.  Other dtypes upcast.
        bf16 = all(r.dtype == jnp.bfloat16 for r in (q_ref, k_ref, v_ref))
        if bf16:
            q, k, precision = q_ref[0], k_ref[0], None
        else:
            q, k = q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32)
            precision = jax.lax.Precision.HIGHEST
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=precision,
        )  # [bq, bk] — unscaled S, as in Algorithm 1 line 6

        cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if seq_k % block_k != 0:
            s = jnp.where(cols < seq_k, s, NEG_INF)
        if causal:
            rows = (
                i * block_q
                + q_offset
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
            if window is not None:
                s = jnp.where(rows - cols < window, s, NEG_INF)

        # m and l are [bq, LANES], each row's value in every lane: a row
        # statistic then needs no relayout between its row and lane forms.
        old_m = m_scr[...]
        new_m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), old_m)      # line 8
        b = _exp2_inline(c * (old_m - new_m), exp2_impl, num_segments)        # line 10
        p = _exp2_inline(c * (s - _lane_rows(new_m, block_k)), exp2_impl,
                         num_segments)                                        # line 12
        l_scr[...] = l_scr[...] * b + jnp.sum(p, axis=-1, keepdims=True)    # lines 13-14
        # P stays fp32, as in the decode einsum that must agree with this
        # kernel.  Against bf16 V it enters as three bf16 slices that sum to
        # it exactly, so three passes form every product that a fp32 HIGHEST
        # dot on upcast V forms.
        pv = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if bf16:
            v = v_ref[0]
            p1 = p.astype(jnp.bfloat16)
            rest = p - p1.astype(jnp.float32)
            p2 = rest.astype(jnp.bfloat16)
            p3 = (rest - p2.astype(jnp.float32)).astype(jnp.bfloat16)
            local_o = (pv(p3, v) + pv(p2, v)) + pv(p1, v)
        else:
            local_o = pv(p, v_ref[0].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        acc_scr[...] = acc_scr[...] * _lane_rows(b, acc_scr.shape[1]) + local_o  # line 16
        m_scr[...] = new_m

    @pl.when(j == num_k_blocks - 1)
    def _finalize():  # line 21: O_i = diag(l)^-1 O
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o = acc_scr[...] / _lane_rows(safe_l, acc_scr.shape[1])
        o_ref[0, :, :] = o.astype(o_ref.dtype)
        if with_lse:
            # Base-2 LSE with the scale folded in: P = exp2(c*S - LSE) is
            # the *normalized* probability the backward recomputes.  Stored
            # lane-broadcast, as m and l are: a [block_q] row block is not
            # (8, 128)-tiled.
            lse_ref[0] = c * m_scr[...] + jnp.log2(safe_l)


def kv_block_index(causal, block_q, block_k, q_offset):
    """KV block a (q block i, KV block j) step reads, KV innermost.

    A dead causal step names the row's last live block, the one the step
    before it read, so the pipeline issues no copy for it.
    """
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(j, last_live_kv_block(i, block_q, block_k, q_offset))


def window_kv_block_index(block_q, block_k, q_offset, window, num_k):
    """KV block a windowed (q block i, step j) step reads: the j-th block of
    the row's band, clamped to its last live block as ``kv_block_index``
    clamps a dead causal step."""
    def index(i, j):
        last = jnp.minimum(last_live_kv_block(i, block_q, block_k, q_offset), num_k - 1)
        return jnp.minimum(first_window_kv_block(i, block_q, block_k, q_offset, window) + j,
                           last)

    return index


def q_block_index(causal, block_q, block_k, q_offset, num_q):
    """q block a (KV block j, q block i) step reads, q innermost.

    A dead causal step names the column's first live q block, the one the
    step after it reads, so the pipeline issues no copy for it.
    """
    if not causal:
        return lambda j, i: i
    return lambda j, i: jnp.minimum(
        jnp.maximum(i, first_live_q_block(j, block_q, block_k, q_offset)), num_q - 1
    )


def flash_attention_fwd(
    q: jax.Array,  # [B, Sq, H, d]
    k: jax.Array,  # [B, Sk, Hkv, d]
    v: jax.Array,  # [B, Sk, Hkv, d]
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    exp2_impl: str = "exact",
    num_segments: int = 8,
    interpret: bool = False,
    return_lse: bool = False,
    window: Optional[int] = None,
):
    """``window``: each query row ``r`` (``q_offset`` included) sees keys
    ``r - window < c <= r``; needs ``causal``.  Windowed calls carry the
    tag ``flash_fwd_window``."""
    batch, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    assert h % hkv == 0
    rep = h // hkv
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    num_q = -(-sq // block_q)
    num_k = -(-sk // block_k)
    pad_q = num_q * block_q - sq
    pad_k = num_k * block_k - sk

    # [B,S,H,d] -> [B*H, S, d] head-major layout for clean 2D blocks.
    qh = q.transpose(0, 2, 1, 3).reshape(batch * h, sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(batch * hkv, sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(batch * hkv, sk, d)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kh = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))

    if window is None:
        steps, tag = num_k, "flash_fwd"
        kv_block = kv_block_index(causal, block_q, block_k, q_offset)
    else:
        if not causal:
            raise ValueError("a sliding window needs causal attention")
        steps, tag = window_kv_steps(window, block_q, block_k, num_k), "flash_fwd_window"
        kv_block = window_kv_block_index(block_q, block_k, q_offset, window, num_k)
    grid = (batch * h, num_q, steps)

    kernel = functools.partial(
        _fwd_kernel,
        with_lse=return_lse,
        num_k_blocks=steps,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        sm_scale=float(scale),
        q_offset=q_offset,
        exp2_impl=exp2_impl,
        num_segments=num_segments,
        seq_k=sk,
        window=window,
        num_kv=num_k,
    )

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        # GQA: map q-head bh -> kv-head bh // rep without materializing.
        pl.BlockSpec((1, block_k, d),
                     lambda bh, i, j, rep=rep: (bh // rep, kv_block(i, j), 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda bh, i, j, rep=rep: (bh // rep, kv_block(i, j), 0)),
    ]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            [
                pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, block_q, LANES), lambda bh, i, j: (bh, i, 0)),
            ]
            if return_lse
            else pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
        ),
        out_shape=(
            [
                jax.ShapeDtypeStruct((batch * h, num_q * block_q, d), q.dtype),
                jax.ShapeDtypeStruct(
                    (batch * h, num_q * block_q, LANES), jnp.float32
                ),
            ]
            if return_lse
            else jax.ShapeDtypeStruct((batch * h, num_q * block_q, d), q.dtype)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        # The tag lands in the custom call's frontend attributes
        # (``kernel_metadata``), which device traces print with each call.
        name=tag,
        metadata={"kernel": tag},
    )(qh, kh, vh)

    if return_lse:
        out, lse = out
        o = out[:, :sq, :].reshape(batch, h, sq, d).transpose(0, 2, 1, 3)
        return o, lse
    out = out[:, :sq, :].reshape(batch, h, sq, d).transpose(0, 2, 1, 3)
    return out
