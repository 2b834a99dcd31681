"""Queue-admission Pallas TPU kernel — the fabric's per-slice capacity cut.

The data plane admits packets to circuits FIFO per (node, egress) group
under per-group byte capacities (``repro.core.fabric._group_admit``). The
XLA CPU formulation sorts the packet vector by group key and runs a
segmented prefix-sum over the sorted order — the dominant remaining
per-slice cost at P = 2^15 (~2 ms per P-wide scatter/sort; ROADMAP
"next big dataplane win").

This kernel removes the sort entirely. FIFO admission only needs, for each
packet ``i``, the *in-index-order* segmented prefix

    prefix[i] = sum of sizes of wanted packets j < i with key[j] == key[i]

which the kernel computes tile-by-tile over a sequential grid:

* the packet vector is padded to a multiple of the ``bp`` tile size
  (padding rows carry the sentinel key, which is never admitted — the same
  padded-tile pattern as :mod:`repro.kernels.time_flow_lookup`); packets
  travel in lanes as ``(1, bp)`` blocks of a ``[1, P]`` row;
* a running per-key byte accumulator (``acc``, the carry between tiles)
  lives in a VMEM-resident output block revisited by every grid step
  (constant index map — the standard sequential-accumulation layout, so the
  grid must execute in order: ``dimension_semantics=("arbitrary",)``);
* within a tile, the segmented exclusive prefix is a dense
  ``[bp, bp]`` same-key-and-earlier masked row-sum — O(bp^2) work that maps
  onto the VPU instead of a data-dependent sort;
* the admission decision ``acc[key] + prefix + size <= cap[key]`` and the
  per-key admitted-byte totals (``used``) fall out of the same tile pass.

Mosaic lowers no gather or scatter from a key-indexed vector, so the
per-key state is a ``[128, R]`` matrix holding key ``k`` at
``[k % 128, k // 128]`` (``R`` lane-padded; pad keys have zero capacity).
A one-hot ``[R, bp]`` matrix of the tile's ``k // 128`` makes both moves
matmuls: the gather ``state @ onehot`` followed by a sublane mask at
``k % 128``, and the scatter-add ``(mask * bytes) @ onehot^T``. Every value
rides the MXU as two 16-bit halves in f32 at ``precision=HIGHEST``: a
gather output is one half times 1.0, and a scatter output sums at most
``bp <= 256`` halves below 2^16, so both stay below 2^24 and are exact;
the halves recombine in int32.

Outputs are bit-identical to the sort-based XLA path — enforced by
``tests/test_admission.py`` and the fabric golden suite at
``FabricConfig.admit_impl="pallas-interpret"``, and on the chip by
``chip_smoke.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_BP = 256     # bp * (2^16 - 1) < 2^24: a tile's byte halves sum exactly


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _halves(x):
    """int32 -> (low 16 bits, arithmetic high half), both exact in f32."""
    return [x & 0xFFFF, x >> 16]


def _join(lo, hi):
    return lo.astype(jnp.int32) + (hi.astype(jnp.int32) << 16)


def _column(row):
    """[1, n] -> [n, 1] by a diagonal mask and a lane reduction."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0), axis=1, keepdims=True)


def _kernel(cap_ref, key_ref, size_ref, adm_ref, used_ref, acc_ref, *,
            num_keys: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        used_ref[...] = jnp.zeros_like(used_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = key_ref[...]                        # [1, bp] group key (sentinel parked)
    s = size_ref[...]                       # [1, bp] bytes (0 when not wanted)
    bp, R = k.shape[1], acc_ref.shape[1]
    hp = jax.lax.Precision.HIGHEST

    # in-tile segmented exclusive prefix: same key, strictly earlier index
    rows = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 1)
    same_earlier = (_column(k) == k) & (rows < cols)
    pre = jnp.sum(jnp.where(same_earlier, _column(s), 0), axis=0,
                  keepdims=True)

    onehot = (jax.lax.broadcasted_iota(jnp.int32, (R, bp), 0)
              == (k >> 7)).astype(jnp.float32)            # [R, bp]
    lo_hit = jax.lax.broadcasted_iota(jnp.int32, (LANES, bp), 0) == (k & 127)

    # gather acc[k] and cap[k] (wanted bytes per key in prior tiles; budget)
    state = jnp.concatenate(_halves(acc_ref[...]) + _halves(cap_ref[...]))
    g = jnp.dot(state.astype(jnp.float32), onehot, precision=hp,
                preferred_element_type=jnp.float32)       # [4*128, bp]
    pick = [jnp.sum(jnp.where(lo_hit, g[p * LANES:(p + 1) * LANES], 0.0),
                    axis=0, keepdims=True) for p in range(4)]
    adm = (_join(*pick[:2]) + pre + s <= _join(*pick[2:])) & (k < num_keys)
    adm_ref[...] = adm.astype(jnp.int32)

    # scatter-add the tile's wanted and admitted bytes per key
    parts = _halves(s) + _halves(jnp.where(adm, s, 0))
    upd = jnp.concatenate([jnp.where(lo_hit, p, 0) for p in parts])
    d = jax.lax.dot_general(upd.astype(jnp.float32), onehot,
                            (((1,), (1,)), ((), ())), precision=hp,
                            preferred_element_type=jnp.float32)  # [4*128, R]
    d = [d[p * LANES:(p + 1) * LANES] for p in range(4)]
    acc_ref[...] += _join(d[0], d[1])
    used_ref[...] += _join(d[2], d[3])


@functools.partial(jax.jit,
                   static_argnames=("num_keys", "bp", "interpret"))
def admission_admit(key, size, want, cap_left, *, num_keys: int,
                    bp: int = MAX_BP, interpret: bool = False):
    """FIFO group admission under per-key byte capacity.

    key/size: [P] int32; want: [P] bool; cap_left: [num_keys] int32.
    Returns ``(admitted [P] bool, used [num_keys] int32)`` — packet ``i`` is
    admitted iff it is wanted and the wanted bytes of its key group at
    indices ``< i`` plus its own size still fit ``cap_left[key[i]]``;
    ``used`` is the admitted bytes per key. Bit-identical to
    :func:`repro.core.fabric._group_admit`.

    Arbitrary packet counts are supported (pad to a multiple of the tile
    with sentinel-key rows, slice back); the tile is ``bp`` rounded up to a
    lane multiple, at most ``MAX_BP``. ``interpret=True`` runs the kernel
    body on the CPU (validation only).
    """
    if bp > MAX_BP:
        raise ValueError(f"bp={bp} exceeds {MAX_BP}: a tile's 16-bit byte "
                         "halves must sum below 2^24 to stay exact in f32")
    P = key.shape[0]
    bp = _round_up(min(bp, max(P, 1)), LANES)
    Ppad = _round_up(max(P, 1), bp)
    key = jnp.where(want, key, num_keys).astype(jnp.int32)
    size = jnp.where(want, size, 0).astype(jnp.int32)
    key = jnp.pad(key, (0, Ppad - P), constant_values=num_keys)[None, :]
    size = jnp.pad(size, (0, Ppad - P))[None, :]
    R = _round_up(-(-(num_keys + 1) // LANES), LANES)
    cap = jnp.zeros((R * LANES,), jnp.int32).at[:num_keys].set(
        cap_left.astype(jnp.int32)).reshape(R, LANES).T

    adm, used, _acc = pl.pallas_call(
        functools.partial(_kernel, num_keys=num_keys),
        grid=(Ppad // bp,),
        in_specs=[
            pl.BlockSpec((LANES, R), lambda i: (0, 0)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((LANES, R), lambda i: (0, 0)),   # used: accumulated
            pl.BlockSpec((LANES, R), lambda i: (0, 0)),   # acc: tile carry
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Ppad), jnp.int32),
            jax.ShapeDtypeStruct((LANES, R), jnp.int32),
            jax.ShapeDtypeStruct((LANES, R), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="group_admit",
    )(cap, key, size)
    return adm[0, :P].astype(bool), used.T.reshape(-1)[:num_keys]
