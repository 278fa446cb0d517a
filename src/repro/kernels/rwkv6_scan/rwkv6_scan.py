"""Pallas TPU kernel for the RWKV-6 WKV recurrence, chunked over time.

Grid: (batch, heads, time_chunks) — time chunks are the innermost,
sequential grid dimension. The (N x N) f32 recurrent state lives in VMEM
scratch and is carried across chunks, so HBM sees each (r,k,v,w) element
exactly once and the state never round-trips to HBM (the CUDA kernel in the
RWKV repo achieves the same with shared memory; VMEM is the TPU analogue).

Within a chunk the recurrence is evaluated stepwise on the VPU
(data-dependent diagonal decay makes the per-step update elementwise); the
chunk size only amortizes grid and DMA overhead. A matmul (MXU) formulation
via log-space cumulative decays is the recorded hillclimb candidate —
see EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sout_ref,
                 s_scr, *, chunk: int, seq_len: int):
    c_idx = pl.program_id(2)
    n_chunks = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0, :, :].astype(jnp.float32)

    u = u_ref[0, :].astype(jnp.float32)              # (n,)

    def step(i, S):
        r_t = r_ref[0, i, 0, :].astype(jnp.float32)  # (n,)
        k_t = k_ref[0, i, 0, :].astype(jnp.float32)
        v_t = v_ref[0, i, 0, :].astype(jnp.float32)
        w_t = w_ref[0, i, 0, :].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]             # (n, n)
        out = ((S + u[:, None] * kv) * r_t[:, None]).sum(axis=0)
        o_ref[0, i, 0, :] = out.astype(o_ref.dtype)
        # positions past seq_len (padded final chunk) must not advance state
        valid = (c_idx * chunk + i) < seq_len
        S_new = jnp.where(valid, w_t[:, None] * S + kv, S)
        return S_new

    s_scr[...] = jax.lax.fori_loop(0, chunk, step, s_scr[...])

    @pl.when(c_idx == n_chunks - 1)
    def _finish():
        sout_ref[0, 0, :, :] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, state=None, *, chunk: int = 128,
         interpret: bool = False):
    """r,k,v,w: (b, t, h, n); u: (h, n); state: (b, h, n, n) f32 or None."""
    b, t, h, n = r.shape
    if state is None:
        state = jnp.zeros((b, h, n, n), jnp.float32)
    chunk = min(chunk, t)
    n_chunks = pl.cdiv(t, chunk)
    pad = n_chunks * chunk - t
    if pad:
        z = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v, w = z(r), z(k), z(v), z(w)

    grid = (b, h, n_chunks)
    kernel = functools.partial(_wkv6_kernel, chunk=chunk, seq_len=t)
    tspec = pl.BlockSpec((1, chunk, 1, n), lambda b_, h_, c: (b_, c, h_, 0))
    out, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            tspec, tspec, tspec, tspec,
            pl.BlockSpec((1, n), lambda b_, h_, c: (h_, 0)),
            pl.BlockSpec((1, 1, n, n), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_specs=[
            tspec,
            pl.BlockSpec((1, 1, n, n), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_chunks * chunk, h, n), r.dtype),
            jax.ShapeDtypeStruct((b, h, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret, name="wkv6",
    )(r, k, v, w, u, state)
    return out[:, :t], s_out
