"""Shared Montgomery limb-field kernel factory (Fp and Fr specialize this).

Both BLS12-381 fields used by the framework — the 381-bit base field
(ops/fp_jax.py, 24×16-bit limbs) and the 255-bit scalar field
(ops/fr_jax.py, 16×16-bit limbs) — need the same deferred-carry SOS
Montgomery core: 16-bit little-endian limbs in uint32 lanes, uint64
accumulation columns, per-limb fori_loops (unrolling is fatal to XLA compile
times at this op count). One parameterized implementation generates both so
a carry-scheme or bound fix lands in exactly one place.

Magnitude analysis (worst case, nlimbs = 24): schoolbook columns accumulate
≤ 24·(2^16-1)^2 ≈ 2^36.6; each Montgomery round adds m·p (≤ 2^32 per
column) plus a folded carry (≤ 2^21) — far below the uint64 ceiling.

Perf notes (measured, TPU v5e, pairing_check_batch):
- this fori/dynamic-slice form: ~27ms/verify, compile ~750s (batch 64);
  throughput flat in batch size (59/s at 2048) => VPU-compute-bound.
- a fully parallel rewrite (broadcast poly-mul + pad-stack-sum columns,
  full-word Montgomery reduction, bounded magnitude passes +
  associative-scan carry-lookahead) was built and differentially validated:
  TPU runtime equivalent (32/s), compile ~20%% faster, but CPU (test-suite)
  10x SLOWER — XLA/CPU lowers the fori form to tight loops. Reverted.
- the real path to the 100k/s target is a representation change that puts
  limb products on the MXU (int8 limbs with int32 matmul accumulation, or
  RNS), likely as a Pallas kernel with explicit VMEM tiling — tracked for
  the next round.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)


class MontgomeryField:
    """Batched modular arithmetic over (..., nlimbs) u32 limb vectors.

    Elements are stored in the Montgomery domain (R = 2^(16·nlimbs)).
    Attributes `add`, `sub`, `neg`, `mont_mul`, `mont_sqr` are jitted; use
    `pow_const(x, e)` for static-exponent chains (inversion, sqrt)."""

    def __init__(self, modulus: int, nlimbs: int, limb_bits: int = 16):
        assert modulus < 1 << (nlimbs * limb_bits)
        self.modulus = modulus
        self.nlimbs = nlimbs
        self.limb_bits = limb_bits
        self.mask = (1 << limb_bits) - 1
        # host numpy, NOT jnp: creating device arrays here would initialize
        # the default backend at import time, making every pure-host
        # consumer (spec compiler via kzg -> fr_jax) take the chip. Under
        # jit these trace to constants either way.
        self.base = np.uint64(1 << limb_bits)
        self.R = 1 << (nlimbs * limb_bits)
        self.R_mod = self.R % modulus
        self.n0 = (-pow(modulus, -1, 1 << limb_bits)) % (1 << limb_bits)
        self.mod_limbs = self.int_to_limbs(modulus)
        self._mod64 = self.mod_limbs.astype(np.uint64)
        self.one_mont = self.int_to_limbs(self.R_mod)
        self.zero = np.zeros(nlimbs, dtype=np.uint32)

        self.add = jax.jit(self._add)
        self.sub = jax.jit(self._sub)
        self.neg = jax.jit(self._neg)
        self.mont_mul = jax.jit(self._mont_mul)
        self.mont_sqr = jax.jit(lambda a: self._mont_mul(a, a))
        self.pow_const = partial(jax.jit, static_argnums=(1,))(self._pow_const)

    # --- host codecs --------------------------------------------------------

    def int_to_limbs(self, x: int) -> np.ndarray:
        assert 0 <= x < self.R
        lb, m = self.limb_bits, self.mask
        return np.array([(x >> (lb * i)) & m for i in range(self.nlimbs)], dtype=np.uint32)

    def limbs_to_int(self, limbs) -> int:
        arr = np.asarray(limbs, dtype=np.uint64).reshape(-1)
        return sum(int(v) << (self.limb_bits * i) for i, v in enumerate(arr))

    def to_mont(self, x: int) -> np.ndarray:
        return self.int_to_limbs((x % self.modulus) * self.R % self.modulus)

    def from_mont_int(self, limbs) -> int:
        return (self.limbs_to_int(limbs) * pow(self.R, -1, self.modulus)) % self.modulus

    def ints_to_mont_batch(self, xs) -> np.ndarray:
        xs = list(xs)
        if not xs:
            return np.zeros((0, self.nlimbs), np.uint32)
        return np.stack([self.to_mont(int(x)) for x in xs])

    def mont_batch_to_ints(self, arr) -> list[int]:
        a = np.asarray(arr, dtype=np.uint32)
        return [self.from_mont_int(a[i]) for i in range(a.shape[0])]

    # --- carry / borrow / compare primitives --------------------------------

    def carry_pass(self, t):
        """(..., N) u64 deferred-carry columns -> per-limb < 2^16 except the
        last (which receives the final carry)."""
        n = t.shape[-1]
        mask64 = jnp.uint64(self.mask)
        lb = self.limb_bits

        def body(i, t):
            v = jax.lax.dynamic_index_in_dim(t, i, axis=-1, keepdims=False)
            t = jax.lax.dynamic_update_index_in_dim(t, v & mask64, i, axis=-1)
            nxt = jax.lax.dynamic_index_in_dim(t, i + 1, axis=-1, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(t, nxt + (v >> lb), i + 1, axis=-1)

        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n - 1), body, t)

    def sub_limbs(self, x, y):
        """x - y over canonical u64 limb vectors, assuming x >= y."""
        out = jnp.zeros(jnp.broadcast_shapes(x.shape, y.shape), dtype=jnp.uint64)
        borrow0 = jnp.zeros(out.shape[:-1], dtype=jnp.uint64)
        xb = jnp.broadcast_to(x, out.shape)
        yb = jnp.broadcast_to(y, out.shape)
        mask64 = jnp.uint64(self.mask)
        lb = self.limb_bits

        def body(i, st):
            borrow, out = st
            xi = jax.lax.dynamic_index_in_dim(xb, i, axis=-1, keepdims=False)
            yi = jax.lax.dynamic_index_in_dim(yb, i, axis=-1, keepdims=False)
            d = xi + self.base - yi - borrow
            out = jax.lax.dynamic_update_index_in_dim(out, d & mask64, i, axis=-1)
            borrow = jnp.uint64(1) - (d >> lb)
            return borrow, out

        _, res = jax.lax.fori_loop(jnp.int32(0), jnp.int32(self.nlimbs), body, (borrow0, out))
        return res

    def geq_vec(self, a64, vec):
        """Lexicographic a >= vec over canonical u64 limbs (vec a (nlimbs,) array)."""
        gt = jnp.zeros(a64.shape[:-1], dtype=bool)
        lt = jnp.zeros(a64.shape[:-1], dtype=bool)
        for i in range(self.nlimbs - 1, -1, -1):
            ai = a64[..., i]
            vi = vec[i]
            gt = gt | (~lt & (ai > vi))
            lt = lt | (~gt & (ai < vi))
        return ~lt

    def cond_sub_mod(self, a64):
        """Subtract the modulus where a >= modulus (a canonical, a < 2·mod)."""
        sub = self.sub_limbs(a64, self._mod64)
        return jnp.where(self.geq_vec(a64, self._mod64)[..., None], sub, a64)

    # --- field ops ----------------------------------------------------------

    def _add(self, a, b):
        t = self.carry_pass(a.astype(jnp.uint64) + b.astype(jnp.uint64))
        return self.cond_sub_mod(t).astype(jnp.uint32)

    def _sub(self, a, b):
        mod_minus_b = self.sub_limbs(self._mod64, b.astype(jnp.uint64))
        # b == 0 -> mod_minus_b == modulus; cond_sub_mod renormalizes.
        t = self.carry_pass(a.astype(jnp.uint64) + mod_minus_b)
        return self.cond_sub_mod(t).astype(jnp.uint32)

    def _neg(self, a):
        z = jnp.all(a == 0, axis=-1, keepdims=True)
        res = self.sub_limbs(self._mod64, a.astype(jnp.uint64))
        return jnp.where(z, jnp.zeros_like(res), res).astype(jnp.uint32)

    def poly_mul_acc(self, a64, b64):
        """Schoolbook product columns: (..., n) x (..., n) -> (..., 2n) u64."""
        shape = jnp.broadcast_shapes(a64.shape[:-1], b64.shape[:-1])
        t = jnp.zeros(shape + (2 * self.nlimbs,), dtype=jnp.uint64)
        a64 = jnp.broadcast_to(a64, shape + (self.nlimbs,))
        b64 = jnp.broadcast_to(b64, shape + (self.nlimbs,))

        def body(i, t):
            ai = jax.lax.dynamic_index_in_dim(a64, i, axis=-1, keepdims=True)
            window = jax.lax.dynamic_slice_in_dim(t, i, self.nlimbs, axis=-1)
            return jax.lax.dynamic_update_slice_in_dim(t, window + ai * b64, i, axis=-1)

        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(self.nlimbs), body, t)

    def _mont_mul(self, a, b):
        """Montgomery product (a·b·R^-1 mod modulus); SOS with deferred carries."""
        t = self.poly_mul_acc(a.astype(jnp.uint64), b.astype(jnp.uint64))
        t = jnp.concatenate([t, jnp.zeros(t.shape[:-1] + (1,), jnp.uint64)], axis=-1)
        n0 = jnp.uint64(self.n0)
        mask64 = jnp.uint64(self.mask)
        lb = self.limb_bits

        def body(i, t):
            ti = jax.lax.dynamic_index_in_dim(t, i, axis=-1, keepdims=False)
            m = ((ti & mask64) * n0) & mask64
            window = jax.lax.dynamic_slice_in_dim(t, i, self.nlimbs, axis=-1)
            window = window + m[..., None] * self._mod64
            # t[i] is now ≡ 0 mod 2^16; move its whole value up as carry
            carry = window[..., 0] >> lb
            window = window.at[..., 0].set(jnp.uint64(0))
            window = window.at[..., 1].add(carry)
            return jax.lax.dynamic_update_slice_in_dim(t, window, i, axis=-1)

        t = jax.lax.fori_loop(jnp.int32(0), jnp.int32(self.nlimbs), body, t)
        hi = self.carry_pass(t[..., self.nlimbs :])
        return self.cond_sub_mod(hi[..., : self.nlimbs]).astype(jnp.uint32)

    def _pow_const(self, a, exponent: int):
        """a^exponent, square-and-multiply over the static exponent bits."""
        bits = jnp.asarray(np.array([int(c) for c in bin(exponent)[2:]], dtype=np.int32))
        one = jnp.broadcast_to(jnp.asarray(self.one_mont), a.shape).astype(jnp.uint32)

        def body(i, acc):
            acc = self._mont_mul(acc, acc)
            mul = self._mont_mul(acc, a)
            return jnp.where(bits[i] == 1, mul, acc)

        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(bits.shape[0]), body, one)

    def inv(self, a):
        """Batched Fermat inversion a^(mod-2); zero maps to zero."""
        return self.pow_const(a, self.modulus - 2)
