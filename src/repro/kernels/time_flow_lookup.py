"""Time-flow table lookup Pallas TPU kernel — the paper's data-plane hot op.

The P4 dataplane's match-action lookup (arrival slice, dst) -> (egress,
departure slice) maps onto TPU as: the current slice's two tables resident
in VMEM (the match-action SRAM analogue), packets streamed through the grid
in blocks of ``bp``. Each block gathers its rows, counts the contiguous
valid multipath slots, and selects a slot by hash — the fused
lookup+hash+select the fabric simulator performs every slice.

Mosaic lowers no gather from a 3-D table, so the gather is two exact
selections. The wrapper lays both tables out as one ``[2*K*Dp, Np]`` f32
matrix (row ``(t*K + k)*Dp + d``, column ``node``; ``Dp``/``Np`` pad ``D``/
``N`` to the sublane/lane tiles). A one-hot ``[Np, bp]`` matrix of the
block's nodes picks each packet's table column on the MXU, then a sublane
mask at ``dst`` picks its row on the VPU. Each output of the matmul is one
table entry times 1.0 plus zeros, so at ``precision=HIGHEST`` it is exact for
every entry below 2^24 in magnitude (entries are node ids and slice
offsets). At 108 ToRs and K = 4 the stacked table is 458 KB of VMEM.

Packets travel in lanes: the packet vectors are ``[1, P]`` rows cut into
``(1, bp)`` blocks, with ``bp`` a multiple of 128.

Adaptation note (DESIGN.md §2): P4 does one packet per pipeline stage at
line rate; the TPU-native formulation is wide SIMD gather over a packet
vector, which is how the JAX fabric consumes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(tbl_ref, node_ref, dst_ref, hash_ref, nxt_ref, dep_ref, *,
            K: int, Dp: int):
    node = node_ref[...]                    # [1, bp]
    dst = dst_ref[...]
    hashv = hash_ref[...]
    Np, bp = tbl_ref.shape[1], node.shape[1]

    onehot = (jax.lax.broadcasted_iota(jnp.int32, (Np, bp), 0)
              == node).astype(jnp.float32)
    cols = jnp.dot(tbl_ref[...], onehot, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)     # [2*K*Dp, bp]
    hit = jax.lax.broadcasted_iota(jnp.int32, (Dp, bp), 0) == dst

    def entry(i):                           # slab i's value at row dst
        slab = cols[i * Dp:(i + 1) * Dp]
        return jnp.sum(jnp.where(hit, slab, 0.0), axis=0,
                       keepdims=True).astype(jnp.int32)    # [1, bp]

    rows_n = [entry(k) for k in range(K)]
    rows_d = [entry(K + k) for k in range(K)]
    nvalid = sum((r >= 0).astype(jnp.int32) for r in rows_n)
    slot = (hashv % jnp.maximum(nvalid, 1).astype(jnp.uint32)).astype(jnp.int32)
    nxt_ref[...] = sum(jnp.where(slot == k, r, 0) for k, r in enumerate(rows_n))
    dep_ref[...] = sum(jnp.where(slot == k, r, 0) for k, r in enumerate(rows_d))


def _stack_tables(tbl_next, tbl_dep, Dp: int, Np: int):
    """[N, D, K] x 2 -> [2*K*Dp, Np] f32, entry (t, n, d, k) at row
    (t*K + k)*Dp + d, column n; padding rows/columns are 0 and never hit."""
    N, D, K = tbl_next.shape
    t = jnp.transpose(jnp.stack([tbl_next, tbl_dep]), (0, 3, 2, 1))
    t = jnp.pad(t, ((0, 0), (0, 0), (0, Dp - D), (0, Np - N)))
    return t.reshape(2 * K * Dp, Np).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def time_flow_lookup(tbl_next, tbl_dep, node, dst, hashv, *, bp: int = 512,
                     interpret: bool = False):
    """tbl_*: [N, D, K] int32 (this slice's tables); node/dst: [P] int32;
    hashv: [P] uint32. Returns (next_hop [P], dep_offset [P]).

    Arbitrary packet counts are supported: the packet vector is padded to a
    multiple of the block size (``bp`` rounded up to a lane multiple;
    padding rows look up entry (0, 0), which always exists) and the outputs
    are sliced back to ``P``. ``interpret=True`` runs the kernel body on
    the CPU (validation only).
    """
    N, D, K = tbl_next.shape
    P = node.shape[0]
    Dp, Np = _round_up(D, SUBLANES), _round_up(N, LANES)
    bp = _round_up(min(bp, max(P, 1)), LANES)
    Ppad = _round_up(max(P, 1), bp)
    row = lambda a: jnp.pad(a, (0, Ppad - P))[None, :]
    nxt, dep = pl.pallas_call(
        functools.partial(_kernel, K=K, Dp=Dp),
        grid=(Ppad // bp,),
        in_specs=[
            pl.BlockSpec((2 * K * Dp, Np), lambda i: (0, 0)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Ppad), jnp.int32),
            jax.ShapeDtypeStruct((1, Ppad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="tf_lookup",
    )(_stack_tables(tbl_next, tbl_dep, Dp, Np), row(node), row(dst),
      row(hashv))
    return nxt[0, :P], dep[0, :P]
