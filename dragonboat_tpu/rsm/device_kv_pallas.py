"""Pallas apply kernel for DeviceKV — the rsm-apply hot loop as a real
TPU kernel.

Why pallas here: the XLA lowering of ``DeviceKV.apply_kernel`` is a
``lax.scan`` over the AB command lanes, and every iteration streams the
whole ``[G, T]`` table through HBM (AB x 2 full passes).  This kernel
keeps an 8-shard block of the table resident in VMEM across the entire
apply window — one HBM read + one write of the table per step instead of
AB of each — while the per-command work stays VPU-shaped ([8, T]
elementwise one-hot selects, no gathers/scatters, same discipline as the
raft kernel).

Semantics are bit-identical to the XLA path (same linear-probe order,
same last-write-wins within a window); ``tests/test_device_kv_pallas.py``
asserts exact state/result equality in interpret mode.  ``interpret``
defaults to True on the CPU backend only; on any other backend the kernel
compiles or raises.  The speedup has not been measured on a chip (PERF.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dragonboat_tpu.core.params import splitmix32
from dragonboat_tpu.rsm.device_kv import DeviceKV

I32 = jnp.int32
SHARD_BLOCK = 8   # sublane dimension: shards per grid program


def _apply_block_kernel(T: int, D: int, AB: int, hash_keys: bool,
                        ckey_ref, cval_ref, valid_ref,
                        _keys_in, _vals_in, _count_in,
                        keys_ref, vals_ref, count_ref,
                        results_ref, ok_ref):
    """One grid program: apply AB commands to an [8, T] table block held
    in VMEM.  keys/vals/count are input_output_aliased (in-place): the
    output refs start holding the input tables, so the kernel reads
    through them once, carries the block as values across the window and
    writes it back once, ignoring the shadow input refs.

    AB is static, so the command lanes are a Python-unrolled loop over
    STATIC columns of whole-block values (command keys and values arrive
    as two [8, AB] planes): Mosaic takes no dynamic lane index — a traced
    ``ref[:, j]`` is refused ("cannot statically prove that index in
    dimension 1 is a multiple of 128")."""
    pos = jax.lax.broadcasted_iota(I32, (SHARD_BLOCK, T), 1)
    lane = jax.lax.broadcasted_iota(I32, (SHARD_BLOCK, AB), 1)
    ckey, cval = ckey_ref[:, :], cval_ref[:, :]       # [8, AB]
    valid = valid_ref[:, :] != 0
    K, V = keys_ref[:, :], vals_ref[:, :]             # [8, T] tables
    count = count_ref[:, :]                           # [8, 1]
    results = jnp.zeros((SHARD_BLOCK, AB), I32)
    oks = jnp.zeros((SHARD_BLOCK, AB), I32)
    for j in range(AB):
        key = ckey[:, j:j + 1]                        # [8, 1]
        val = cval[:, j:j + 1]
        lane_ok = valid[:, j:j + 1]
        if hash_keys:
            # the SAME mixer as DeviceKV._probe_slots — probe order must
            # stay bit-identical between the pallas and XLA paths
            h = splitmix32(key.astype(jnp.uint32)).astype(I32) & (T - 1)
        else:
            h = key & (T - 1)
        rel = (pos - h) & (T - 1)                     # [8, T]
        in_window = rel < D
        hit = (K == key + 1) & in_window
        empty = (K == 0) & in_window
        # first (lowest probe offset) hit, else first empty — identical
        # pick order to the sequential XLA path
        min_hit = jnp.min(jnp.where(hit, rel, T), axis=1, keepdims=True)
        min_empty = jnp.min(jnp.where(empty, rel, T), axis=1,
                            keepdims=True)
        use_rel = jnp.where(min_hit < T, min_hit, min_empty)
        found = use_rel < T
        do = lane_ok & found & (key >= 0)             # [8, 1]
        is_new = do & ~(min_hit < T)
        target = (h + use_rel) & (T - 1)
        onehot = (pos == target) & do
        K = jnp.where(onehot, key + 1, K)
        V = jnp.where(onehot, val, V)
        count = count + is_new.astype(I32)
        results = jnp.where(lane == j, jnp.where(do, val, -1), results)
        oks = jnp.where(lane == j, do.astype(I32), oks)
    keys_ref[:, :] = K
    vals_ref[:, :] = V
    count_ref[:, :] = count
    results_ref[:, :] = results
    ok_ref[:, :] = oks


# keys/vals/count are donated: callers replace their state dict with the
# returned one, and without donation XLA must copy the whole table into
# the aliased output buffers — re-adding the HBM traffic the kernel
# exists to remove
@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3, 4))
def _apply_pallas(kv: DeviceKV, interpret: bool, keys, vals, count,
                  cmd_lanes, valid_mask):
    G = keys.shape[0]
    T, D = kv.table_cap, kv.probe_depth
    AB = cmd_lanes.shape[1]
    pad = (-G) % SHARD_BLOCK
    if pad:
        keys = jnp.pad(keys, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        count = jnp.pad(count, (0, pad))
        cmd_lanes = jnp.pad(cmd_lanes, ((0, pad), (0, 0), (0, 0)))
        valid_mask = jnp.pad(valid_mask, ((0, pad), (0, 0)))
    Gp = G + pad
    grid = (Gp // SHARD_BLOCK,)

    def block(i):  # shard-block index map
        return (i, 0)

    kernel = functools.partial(_apply_block_kernel, T, D, AB, kv.hash_keys)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((SHARD_BLOCK, AB), block),
            pl.BlockSpec((SHARD_BLOCK, AB), block),
            pl.BlockSpec((SHARD_BLOCK, AB), block),
            pl.BlockSpec((SHARD_BLOCK, T), block),
            pl.BlockSpec((SHARD_BLOCK, T), block),
            pl.BlockSpec((SHARD_BLOCK, 1), block),
        ],
        out_specs=[
            pl.BlockSpec((SHARD_BLOCK, T), block),
            pl.BlockSpec((SHARD_BLOCK, T), block),
            pl.BlockSpec((SHARD_BLOCK, 1), block),
            pl.BlockSpec((SHARD_BLOCK, AB), block),
            pl.BlockSpec((SHARD_BLOCK, AB), block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Gp, T), I32),       # keys
            jax.ShapeDtypeStruct((Gp, T), I32),       # vals
            jax.ShapeDtypeStruct((Gp, 1), I32),       # count
            jax.ShapeDtypeStruct((Gp, AB), I32),      # results
            jax.ShapeDtypeStruct((Gp, AB), I32),      # ok
        ],
        # tables update in place: alias inputs 3/4/5 onto outputs 0/1/2
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=interpret,
    )(cmd_lanes[:, :, 0], cmd_lanes[:, :, 1], valid_mask.astype(I32),
      keys, vals, count[:, None])
    nkeys, nvals, ncount, results, ok = out
    return (nkeys[:G], nvals[:G], ncount[:G, 0], results[:G],
            ok[:G].astype(bool))


def apply_kernel_pallas(kv: DeviceKV, sm_state: dict, cmd_lanes,
                        valid_mask, interpret: bool | None = None):
    """``DeviceKV.apply_kernel`` semantics backed by the pallas block
    kernel.  NOT drop-in on buffer lifetime: the input state arrays are
    DONATED (callers must replace their state dict with the returned one
    and never touch the old arrays — keeping a pre-apply copy requires
    an explicit ``jnp.copy`` first).  Donation is what lets the aliased
    tables update in place; with ``G`` not a multiple of SHARD_BLOCK the
    pad path copies anyway, so size ``G`` block-aligned for the zero-copy
    claim to hold.  ``interpret`` defaults to True on the CPU backend."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    keys, vals, count, results, ok = _apply_pallas(
        kv, interpret, sm_state["keys"], sm_state["vals"],
        sm_state["count"], cmd_lanes, valid_mask)
    return {"keys": keys, "vals": vals, "count": count}, (results, ok)
