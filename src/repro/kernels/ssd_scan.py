"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of the paper's GPU SSD kernel (arXiv:2405.21060 §7): the
chunk-local quadratic term becomes (Q x Q) MXU matmuls, and the inter-chunk
recurrence rides the TPU grid's *sequential* innermost axis — the running
state (P x N per head) persists in VMEM scratch across chunk iterations, so
the whole scan is one kernel launch with no HBM round-trip for the state
(the GPU version materializes per-chunk states and runs a separate
state-passing kernel; the TPU grid makes that fusion natural).

Grid: (B, H, nc) — nc innermost/sequential. The wrapper makes x and dt
head-major ((B, H, S, P) and (B, H, 1, S)) and reads A from SMEM, so every
tile's last two dims are ones Mosaic can tile. The kernel still does not
compile for the TPU: Mosaic has no lowering for the chunk-local cumsum
(it runs in interpret mode only). Per-tile VMEM working set at
Q=128, P=64, N=128: x (Q,P), B/C (Q,N), dt/LA (Q,), state (P,N) f32,
G/M/W (Q,Q) f32 ≈ 0.3 MB.

Inputs are pre-activation SSD tensors (post conv/softplus), i.e. the kernel
computes exactly ssd_chunked() from repro.models.ssm = ref oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                state_ref, *, chunk, nc):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                # (Q, P)
    dt = dt_ref[0, 0, 0]                               # (Q,) f32
    A = a_ref[pl.program_id(1)]                        # scalar f32 (this head)
    Bm = b_ref[0, :, :].astype(jnp.float32)            # (Q, N)
    Cm = c_ref[0, :, :].astype(jnp.float32)            # (Q, N)

    dA = dt * A                                        # (Q,)
    LA = jnp.cumsum(dA)                                # (Q,)

    # intra-chunk: W[q,s] = (C_q . B_s) * exp(LA_q - LA_s) * dt_s   (s <= q)
    diff = LA[:, None] - LA[None, :]
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    M = jnp.exp(jnp.where(si <= qi, diff, -1e9))       # (Q, Q); mask exponent
                                                       # to avoid exp overflow
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    W = G * M * dt[None, :]
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: y += exp(LA_q) * C_q @ state^T  ; state (P, N)
    state = state_ref[...]
    y += jnp.exp(LA)[:, None] * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # state' = exp(sum dA) * state + sum_s exp(LA_Q - LA_s) dt_s x_s B_s^T
    tail = jnp.exp(LA[-1] - LA) * dt                   # (Q,)
    contrib = jax.lax.dot_general(
        x * tail[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # (P, N)
    state_ref[...] = jnp.exp(jnp.sum(dA)) * state + contrib

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _write_state():
        st_ref[0, 0, :, :] = state_ref[...]


def ssd_scan(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H) f32 post-softplus
    A: jax.Array,    # (H,) f32 negative
    Bm: jax.Array,   # (B, S, N)
    Cm: jax.Array,   # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N) f32)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    y, st = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ci: (b, h, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda b, h, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3),
      dt.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :],
      A.astype(jnp.float32), Bm, Cm)
    return y.transpose(0, 2, 1, 3), st
