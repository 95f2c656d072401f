"""The RLC pairing check's share of its roofline: the int8 MXU operations
and HBM bytes any implementation needs for the window's batch checks
(benchmark/roofline.py: a lower bound on Fp multiplications at the batch
size and distinct-message count, each costed at the least int8
multiply-adds of a 381-bit product), against the chip's int8 and HBM
peaks, as a % of the device time of `pairing_check_rlc`. The VPU, where
much of a field backend may run, has no published peak."""
from benchmark import roofline
from benchmark.trace_reduce import holds, program_seconds

PROGRAM = r"pairing_check_rlc"


def read(run):
    batches = run.work.get("batches")
    if not batches or not holds(run.trace, PROGRAM, batches):
        return None  # as in bls.pairing_ms
    seconds = program_seconds(run.trace, PROGRAM)
    per = run.work["blocks_per_batch"]  # one distinct message per block
    ops = batches * roofline.pairing_min_int8_ops(per, per)
    least_bytes = batches * roofline.pairing_min_bytes(per)
    return roofline.roofline_share(ops, least_bytes, seconds, run.peaks, "int8_ops_per_s")
