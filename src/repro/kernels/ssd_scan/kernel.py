"""Pallas TPU kernel: Mamba2 SSD (state-space duality) chunked scan.

One (batch, head) pair per outer grid step; the chunk axis is innermost and
sequential, carrying the (N, P) inter-chunk SSD state in fp32 VMEM scratch —
the same carry-across-grid idiom as flash attention, but the carry is a
matrix recurrence instead of softmax stats.

Per chunk (Q = chunk length):
  intra:  scores = (C B^T) ⊙ exp(seg(dA_cs)) masked-causal  -> (Q, Q) @ xdt
  inter:  y += exp(dA_cs) * (C @ S)
  state:  S <- exp(sum dA) * S + B^T diag(dt*decay_end) x

VMEM working set at (Q=128, N=128, P=64): scores 128² f32 (64 KB) + state
128x64 f32 (32 KB) + x/B/C tiles — comfortably under 1 MB.  dt enters
twice, as a (Q, 1) column and a (1, Q) row tile, so both orientations of
its cumulative sum come from masked reductions; the per-head decay rates ``A``
sit whole in SMEM (a (1, 1) VMEM block of an (H, 1) array is not a
legal TPU tiling) and each grid step reads its head's scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref, s_out_ref,
            state_ref, *, nc: int, Q: int, H: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)      # (Q, 1)
    dt_row = dtr_ref[0].astype(jnp.float32)  # (1, Q), same values
    A = a_ref[pl.program_id(0) % H]         # scalar, from SMEM
    Bm = b_ref[0].astype(jnp.float32)       # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)       # (Q, N)

    dA = dt * A                             # (Q, 1), <= 0
    xdt = x * dt                            # (Q, P)

    # ---- inclusive cumsum of dA as a column and as a row: masked sums
    # over the (Q, Q) causal triangle (the TPU lowering has no cumsum and
    # no column->row relayout)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = ii >= jj
    dA_cs = jnp.sum(jnp.where(causal, dt_row * A, 0.0), axis=1,
                    keepdims=True)          # (Q, 1)
    dA_cs_row = jnp.sum(jnp.where(ii <= jj, dA, 0.0), axis=0,
                        keepdims=True)      # (1, Q)
    dA_tot = jnp.sum(dA, axis=0, keepdims=True)         # (1, 1)

    # ---- intra-chunk
    seg = dA_cs - dA_cs_row                 # (Q, Q): cs_i - cs_j
    L = jnp.where(causal, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(CB * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # ---- inter-chunk (uses incoming state)
    S = state_ref[...]                      # (N, P)
    y += jnp.exp(dA_cs) * jax.lax.dot_general(
        Cm, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # ---- state update
    decay_end = jnp.exp(dA_tot - dA_cs)                 # (Q, 1)
    wgt = xdt * decay_end                               # (Q, P)
    S_chunk = jax.lax.dot_general(Bm, wgt, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(dA_tot) * S + S_chunk

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit_state():
        s_out_ref[0] = state_ref[...]


def ssd_scan_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                    Bm: jax.Array, Cm: jax.Array, *, chunk: int,
                    interpret: bool = False):
    """x: (BH, T, P); dt: (BH, T); A: (H,); Bm/Cm: (B, T, N); BH = B*H.

    Returns (y (BH, T, P), final_state (BH, N, P)).
    """
    BH, T, P = x.shape
    B, _, N = Bm.shape
    H = BH // B
    Q = min(chunk, T)
    assert T % Q == 0, (T, Q)
    nc = T // Q
    kernel = functools.partial(_kernel, nc=nc, Q=Q, H=H)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, Q, 1), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda bh, c: (bh, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, N), lambda bh, c, _H=H: (bh // _H, c, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, c, _H=H: (bh // _H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, N, P), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt[..., None], dt[:, None, :], A.astype(jnp.float32), Bm, Cm)
    return y, s_out
