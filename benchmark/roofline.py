"""The work a cell's kernels must do, counted from the configuration alone.

Each function counts what any correct implementation has to do, whatever
its program looks like, so a roofline share computed from it can neither
pass 100% nor go stale when a later PR rewrites the kernel.
"""
from __future__ import annotations

# Bytes of the per-validator columns that altair's process_epoch must read
# (R) or write (W) once per epoch, per validator. Reads: balance, effective
# balance, the four epochs (eligibility, activation, exit, withdrawable),
# the slashed flag, both participation bytes and the inactivity score.
# Writes: balance and inactivity score (both change for nearly every
# validator each epoch) and both participation bytes (previous takes the
# current flags, current is zeroed). Effective balances and epochs change
# only for a few validators and are not counted: a lower bound.
EPOCH_READ_BYTES_PER_VALIDATOR = 8 + 8 + 4 * 8 + 1 + 1 + 1 + 8
EPOCH_WRITE_BYTES_PER_VALIDATOR = 8 + 8 + 1 + 1


def epoch_min_bytes(validators: int) -> int:
    """HBM bytes one epoch transition must move at least: every column it
    depends on read once, every column it rewrites written once. Any
    implementation reads its inputs and writes its outputs at least once;
    one that rereads or spills moves more."""
    return validators * (EPOCH_READ_BYTES_PER_VALIDATOR + EPOCH_WRITE_BYTES_PER_VALIDATOR)


# Fp multiplications a BLS12-381 pairing check needs at least, counted on
# the best published formulas and rounded down:
# - each Miller loop (optimal ate, |x| has 64 bits, 6 of them set): 63
#   doubling steps and 5 addition steps, each a line evaluated at P
#   (>= 20 Fp multiplications with projective formulas) and a sparse
#   multiplication into the accumulator (>= 13 Fp2 multiplications, 39 Fp);
# - the accumulator's 63 squarings (>= 36 Fp each), shared by all the
#   Miller loops of one product;
# - one final exponentiation: its hard part alone is >= 5 exponentiations
#   by |x| of 63 cyclotomic squarings (>= 18 Fp each) = 5,670;
# - the random linear combination: one 64-bit G1 scalar multiplication per
#   set (>= 64 doublings of >= 5 Fp multiplications) and one 64-bit G2
#   scalar multiplication per set (>= 64 doublings of >= 5 Fp2
#   multiplications of 3 Fp each).
MILLER_STEPS = 68
FP_MULS_PER_MILLER_STEP = 20 + 39
FP_MULS_SHARED_SQUARINGS = 63 * 36
FP_MULS_FINAL_EXP = 5 * 63 * 18
FP_MULS_RLC_PER_SET = 64 * 5 + 64 * 5 * 3

# A product of two 381-bit numbers in int8 limbs is a product of two
# 48-term polynomials, whose bilinear complexity is 2*48 - 1 = 95
# multiplications: no algorithm does it in fewer int8 multiply-adds
# (the reduction modulo p and the carries are not counted).
INT8_MACS_PER_FP_MUL = 2 * 48 - 1


def pairing_min_fp_muls(sets: int, distinct: int) -> int:
    """Fp multiplications of one RLC pairing check of `sets` signature
    sets over `distinct` messages: distinct + 1 Miller loops into one
    product, one final exponentiation, and the per-set RLC scalars."""
    loops = distinct + 1
    return (loops * MILLER_STEPS * FP_MULS_PER_MILLER_STEP + FP_MULS_SHARED_SQUARINGS
            + FP_MULS_FINAL_EXP + sets * FP_MULS_RLC_PER_SET)


def pairing_min_int8_ops(sets: int, distinct: int) -> int:
    """int8 operations (a multiply-add is two) the check needs at least."""
    return 2 * INT8_MACS_PER_FP_MUL * pairing_min_fp_muls(sets, distinct)


def pairing_min_bytes(sets: int) -> int:
    """HBM bytes the check must read at least: per set an affine G1 key
    (96 bytes), an affine G2 message point and signature (192 each), and a
    64-bit RLC scalar."""
    return sets * (96 + 192 + 192 + 8)


def roofline_share(ops: float, bytes_: float, seconds: float, peaks: dict,
                   ops_peak: str) -> float:
    """The least time the chip could take (the larger of ops over the
    ops peak and bytes over the HBM peak) as a % of `seconds`."""
    least = max(ops / peaks[ops_peak], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
