"""Public jit'd wrappers over the Pallas kernels with jnp-oracle dispatch.

``impl="pallas"`` runs the TPU kernels; the fabric kernels
(``time_flow_lookup``, ``admission_admit``) run compiled unless the caller
passes ``interpret=True``, which executes the kernel body on the CPU (the
validation mode of the CPU test suite). ``impl="ref"`` runs the pure-jnp
oracles from :mod:`repro.kernels.ref`.
The model stack uses the oracles for SPMD dry-runs (Mosaic kernels cannot
lower on the CPU backend) and the kernels on real TPU deployments.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref as _ref
from .admission import admission_admit as _admit_pallas
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .grouped_matmul import grouped_matmul as _grouped_pallas
from .rg_lru import rg_lru as _rg_lru_pallas
from .time_flow_lookup import time_flow_lookup as _tfl_pallas

__all__ = ["flash_attention", "decode_attention", "grouped_matmul", "rg_lru",
           "time_flow_lookup", "admission_admit"]


def flash_attention(q, k, v, *, n_q_heads, n_kv_heads, causal=True, window=0,
                    softcap=0.0, scale=None, q_offset=0, impl="pallas",
                    **kw):
    if impl == "ref":
        return _ref.flash_attention_ref(
            q, k, v, n_q_heads=n_q_heads, n_kv_heads=n_kv_heads,
            causal=causal, window=window, softcap=softcap, scale=scale,
            q_offset=q_offset)
    return _flash_pallas(q, k, v, n_q_heads=n_q_heads, n_kv_heads=n_kv_heads,
                         causal=causal, window=window, softcap=softcap,
                         scale=scale, q_offset=q_offset, **kw)


def decode_attention(q, k_cache, v_cache, pos, cur_index, *, n_q_heads,
                     n_kv_heads, window=0, softcap=0.0, scale=None,
                     impl="pallas", **kw):
    if impl == "ref":
        return _ref.decode_attention_ref(
            q, k_cache, v_cache, pos, cur_index, n_q_heads=n_q_heads,
            n_kv_heads=n_kv_heads, window=window, softcap=softcap,
            scale=scale)
    return _decode_pallas(q, k_cache, v_cache, pos, cur_index,
                          n_q_heads=n_q_heads, n_kv_heads=n_kv_heads,
                          window=window, softcap=softcap, scale=scale, **kw)


def grouped_matmul(x, w, *, impl="pallas", **kw):
    if impl == "ref":
        return _ref.grouped_matmul_ref(x, w)
    return _grouped_pallas(x, w, **kw)


def rg_lru(a, b, *, impl="pallas", **kw):
    if impl == "ref":
        return _ref.rg_lru_ref(a, b)
    return _rg_lru_pallas(a, b, **kw)


def time_flow_lookup(tbl_next, tbl_dep, node, dst, hashv, *, impl="pallas",
                     **kw):
    if impl == "ref":
        return _ref.time_flow_lookup_ref(tbl_next, tbl_dep, node, dst, hashv)
    return _tfl_pallas(tbl_next, tbl_dep, node, dst, hashv, **kw)


def admission_admit(key, size, want, cap_left, *, num_keys, cap_offset=None,
                    impl="pallas", **kw):
    """FIFO group admission; ``cap_offset`` is the shard_map dispatch hook:
    under the sharded fabric each shard passes its earlier-shards per-key
    wanted-byte prefix (:func:`repro.distributed.collectives.shard_group_offsets`)
    and the kernel runs unchanged on the shifted capacities — local FIFO
    admission against ``cap_left - cap_offset`` is exactly global FIFO
    admission for contiguous-block packet sharding."""
    if cap_offset is not None:
        cap_left = jnp.asarray(cap_left) - cap_offset
    if impl == "ref":
        return _ref.admission_admit_ref(key, size, want, cap_left,
                                        num_keys=num_keys)
    return _admit_pallas(key, size, want, cap_left, num_keys=num_keys, **kw)
