"""Mesh collectives for curve-group values (SURVEY §2.3 "G1/G2 reduction
collectives" row).

G1 point addition is a group law, not a ring sum, so GSPMD's automatic
`psum` insertion cannot reduce it; the collective is spelled out with
shard_map: each device tree-reduces its local shard of points (all VPU
work, no communication), ONE `all_gather` moves the n_devices partial sums
over ICI (~100 bytes/device — the only wire traffic regardless of input
size), and every device finishes the log2(n_devices) tail reduce
replicated. This is the scale-out path for registry-wide pubkey
aggregation (sync-committee aggregate keys, deposit-sweep key checks):
single-chip `ops/bls12_jax.g1_sum_reduce` handles one device's worth, this
composes it across the mesh.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import bls12_jax as K
from .mesh import DATA_AXIS

# check_vma=False on every shard_map below: each per-device tail recomputes
# an identical replicated reduce from gathered partials, which the
# replication checker can't prove.


@lru_cache(maxsize=8)
def _mesh_reduce_fn(mesh):
    """One compiled reducer per mesh (jit then caches per input shape);
    rebuilding the shard_map closure per call would recompile every time."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def reduce_shards(X, Y, Z):
        px, py, pz = K.g1_sum_reduce((X, Y, Z))
        gx = jax.lax.all_gather(px[None], DATA_AXIS, axis=0, tiled=True)
        gy = jax.lax.all_gather(py[None], DATA_AXIS, axis=0, tiled=True)
        gz = jax.lax.all_gather(pz[None], DATA_AXIS, axis=0, tiled=True)
        return K.g1_sum_reduce((gx, gy, gz))

    return jax.jit(reduce_shards)


def g1_mesh_sum(pts, mesh):
    """Sum a mesh-sharded batch of Jacobian G1 points.

    `pts`: (X, Y, Z) arrays of shape (N, limbs), N divisible by the mesh
    size; sharded (or shardable) on the leading axis. Returns the single
    Jacobian sum, replicated on every device."""
    split = NamedSharding(mesh, P(DATA_AXIS))
    pts = tuple(jax.device_put(a, split) for a in pts)
    return _mesh_reduce_fn(mesh)(*pts)


def g1_small_multiples(n: int):
    """(X, Y, Z) Jacobian Montgomery arrays of [1]G .. [n]G plus their
    affine int pairs — the shared fixture for collective checks (the
    dryrun and tests/test_mesh_collectives.py must agree on encoding)."""
    import jax.numpy as jnp

    from ..crypto import bls12_381 as oracle

    enc = K.F.ints_to_mont_batch
    affs, acc = [], oracle.G1_GEN
    for _ in range(n):
        affs.append(oracle.pt_to_affine(oracle.FP_FIELD, acc))
        acc = oracle.pt_add(oracle.FP_FIELD, acc, oracle.G1_GEN)
    X = jnp.asarray(enc([a[0] for a in affs]))
    Y = jnp.asarray(enc([a[1] for a in affs]))
    Z = jnp.broadcast_to(jnp.asarray(K.F.ONE_MONT), X.shape)
    return (X, Y, Z), affs


@lru_cache(maxsize=8)
def _mesh_rlc_fn(mesh, p2_is_neg_g1: bool):
    """Mesh-sharded `pairing_check_rlc`: the flagship kernel's scale-out.

    Signature sets are sharded on the data axis; every device runs the
    z-scalar ladders and its shard's Miller loops, tree-folding local Fp12
    values (pure compute, no wire traffic). With `p2_is_neg_g1` the second
    pairing set collapses by bilinearity exactly as in the single-device
    kernel (ops/bls12_jax.py): each shard ladders and locally sums
    [z_i]·sig_i on G2, the per-device partial POINTS (~600 B each) ride
    the same all_gather round as the Fp12 partials, and the one extra
    Miller loop for e(−G1, Σ z_i·sig_i) runs replicated. Communication
    volume stays independent of batch size; the final exponentiation is
    paid once, not per shard.
    """
    import jax.numpy as jnp

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=tuple([P(DATA_AXIS)] * 9),
        out_specs=P(),
        check_vma=False,
    )
    def rlc_shards(qx, qy, px, py, q2x, q2y, p2x, p2y, zbits):
        a1x, a1y = K.rlc_randomize_g1(px, py, zbits)
        m1 = K.miller_loop_batch(qx, qy, a1x, a1y)
        if p2_is_neg_g1:
            one = jnp.broadcast_to(
                jnp.asarray(K.F.ONE_MONT), q2x[0].shape).astype(q2x[0].dtype)
            one2 = (one, jnp.zeros_like(one))
            zsig = K.g2_scalar_mul_batch((q2x, q2y, one2), zbits)
            local_pt = K.g2_sum_reduce(zsig)  # shard's Σ [z_i]·sig_i

            def gather_f2(c):
                return (
                    jax.lax.all_gather(c[0][None], DATA_AXIS, axis=0, tiled=True),
                    jax.lax.all_gather(c[1][None], DATA_AXIS, axis=0, tiled=True),
                )

            total_pt = K.g2_sum_reduce(tuple(gather_f2(c) for c in local_pt))
            aqx, aqy = K.g2_jacobian_to_affine(total_pt)
            ngx, ngy = K._neg_g1_affine_mont()
            m2_single = K.miller_loop_batch(aqx, aqy, ngx, ngy)
            local = K.f12_prod_reduce(m1)  # leading dim 1
            gathered = jax.tree.map(
                lambda c: jax.lax.all_gather(c, DATA_AXIS, axis=0, tiled=True), local)
            return K.rlc_tail(gathered, m2_single)
        one = jnp.broadcast_to(jnp.asarray(K.F.ONE_MONT), px.shape).astype(px.dtype)
        z2 = K.g1_scalar_mul_batch((p2x, p2y, one), zbits)
        a2x, a2y = K._g1_jacobian_to_affine_batch(z2)
        m2 = K.miller_loop_batch(q2x, q2y, a2x, a2y)
        local = K.f12_prod_reduce(K.f12_mul(m1, m2))  # leading dim 1
        gathered = jax.tree.map(
            lambda c: jax.lax.all_gather(c, DATA_AXIS, axis=0, tiled=True), local)
        prod = K.f12_prod_reduce(gathered)
        single = tuple((c[0][0], c[1][0]) for c in prod)
        return K.f12_is_one(K.final_exponentiation_batch(single))

    return jax.jit(rlc_shards)


def pairing_check_rlc_mesh(mesh, qx, qy, px, py, q2x, q2y, p2x, p2y, zbits,
                           p2_is_neg_g1: bool = False):
    """Randomized batch signature check sharded across `mesh`.

    Same contract as `ops.bls12_jax.pairing_check_rlc` (scalar bool,
    2^-64 soundness, caller supplies nonzero zbits); batch size must be
    divisible by the mesh's device count. Bit-equal to the single-device
    kernel: tests/test_mesh_collectives.py asserts agreement, and the
    driver's `dryrun_multichip` runs it over the hierarchical layout."""
    split = NamedSharding(mesh, P(DATA_AXIS))
    args = tuple(
        jax.device_put(a, split)
        for a in (qx, qy, px, py, q2x, q2y, p2x, p2y, zbits)
    )
    return _mesh_rlc_fn(mesh, p2_is_neg_g1)(*args)


@lru_cache(maxsize=8)
def _mesh_rlc_grouped_fn(mesh):
    """Mesh-sharded SEGMENTED `pairing_check_rlc`: the distinct-message
    collapse scaled across chips. Two axes ride the same mesh axis:

    - ITEMS (N): each device runs the [z_i]·pk_i and [z_i]·sig_i 64-bit
      ladders for its shard, then ONE all_gather moves the N randomized
      Jacobian G1 points (~600 B/item) so every device can segment-sum any
      group — membership is arbitrary, a group's items may live anywhere.
    - GROUPS (D): the D distinct-message Miller loops partition across
      devices; device k segment-sums and Miller-loops groups
      [k·D/n_dev, (k+1)·D/n_dev) only. This is where the wall-clock lives
      (the Fp12 squaring chain), so throughput scales with chip count.

    The tail is one psum-style Fp12 PRODUCT collective (all_gather of
    per-device Fp12 partials + replicated tree product — a group law, so
    GSPMD's additive psum cannot express it, same stance as g1_mesh_sum),
    the sig-side partial G2 points ride the gather round, and the single
    final exponentiation runs replicated. Exact equality with the
    single-device kernel: all reductions are modular group/field ops, so
    association order cannot change the value."""
    import jax.numpy as jnp

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=tuple([P(DATA_AXIS)] * 7) + (P(),),
        out_specs=P(),
        check_vma=False,
    )
    def grouped_shards(qx, qy, px, py, q2x, q2y, zbits, seg_ids):
        d_local = qx[0].shape[0]  # D / n_devices distinct messages per device
        base = jax.lax.axis_index(DATA_AXIS) * d_local
        one = jnp.broadcast_to(jnp.asarray(K.F.ONE_MONT), px.shape).astype(px.dtype)
        z1_local = K.g1_scalar_mul_batch((px, py, one), zbits)
        z1 = tuple(
            jax.lax.all_gather(c, DATA_AXIS, axis=0, tiled=True) for c in z1_local)
        segsum = K.g1_segment_sum(z1, seg_ids, d_local, first_segment=base)
        a1x, a1y = K._g1_jacobian_to_affine_batch(segsum)
        m1_local = K.miller_loop_batch(qx, qy, a1x, a1y)

        # sig-side bilinearity collapse, sharded: local ladders + local sum,
        # per-device partial G2 points gathered and folded replicated
        oneq = jnp.broadcast_to(
            jnp.asarray(K.F.ONE_MONT), q2x[0].shape).astype(q2x[0].dtype)
        one2 = (oneq, jnp.zeros_like(oneq))
        zsig = K.g2_scalar_mul_batch((q2x, q2y, one2), zbits)
        local_pt = K.g2_sum_reduce(zsig)

        def gather_f2(c):
            return (
                jax.lax.all_gather(c[0][None], DATA_AXIS, axis=0, tiled=True),
                jax.lax.all_gather(c[1][None], DATA_AXIS, axis=0, tiled=True),
            )

        total_pt = K.g2_sum_reduce(tuple(gather_f2(c) for c in local_pt))
        aqx, aqy = K.g2_jacobian_to_affine(total_pt)
        ngx, ngy = K._neg_g1_affine_mont()
        m2_single = K.miller_loop_batch(aqx, aqy, ngx, ngy)

        local = K.f12_prod_reduce(m1_local)  # leading dim 1
        gathered = jax.tree.map(
            lambda c: jax.lax.all_gather(c, DATA_AXIS, axis=0, tiled=True), local)
        return K.rlc_tail(gathered, m2_single)

    return jax.jit(grouped_shards)


def pairing_check_rlc_grouped_mesh(mesh, qx, qy, px, py, q2x, q2y, zbits,
                                   seg_ids):
    """Segmented randomized batch check sharded across `mesh`.

    Same contract as the single-device grouped fast path
    (`ops.bls12_jax.pairing_check_rlc(..., seg_ids=...)`): qx/qy carry the
    D distinct H(m) points, seg_ids (N,) maps items to groups, every group
    must be non-empty, and both N and D must divide by the mesh's device
    count. seg_ids stays replicated (it is the only global index table);
    item arrays shard on N, message arrays on D."""
    split = NamedSharding(mesh, P(DATA_AXIS))
    repl = NamedSharding(mesh, P())
    args = tuple(
        jax.device_put(a, split) for a in (qx, qy, px, py, q2x, q2y, zbits))
    seg = jax.device_put(seg_ids, repl)
    return _mesh_rlc_grouped_fn(mesh)(*args, seg)
