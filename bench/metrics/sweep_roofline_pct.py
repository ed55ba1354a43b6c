"""The sweep kernel's share of its roofline, in %.

Roofline time is the larger of the work's element operations over the
chip's VPU element-op peak and its bytes over the HBM peak
(``bench/peaks.json``); the share is that time over the summed device
time of the trace's ``metropolis_sweep*`` operations in the window.

The work counted is what the algorithm needs, whatever implements it
(layout, ``delta``/``full`` variant, runtime objective dispatch): per
chain-step, :func:`ops_per_step` float32/uint32 element operations, each
transcendental counted as one; per level of a job, the chain state read
and written once and the values written (:func:`bytes_per_level`).  The
kernel executes far more (padding, every objective's branch, a one-hot
over the row), so the share cannot pass 100%.
"""
from bench import tracereduce

KERNEL = "metropolis_sweep"

#: threefry2x32: 2 key injections, 20 rounds of (add, shift, shift, or,
#: xor), 5 schedule injections of 3 adds.
THREEFRY = 2 + 20 * 5 + 5 * 3
#: Per step: two threefry calls; three uniforms of (shift, convert, mul);
#: the coordinate (mod); the new value (mul, add); accept (sub, div,
#: neg, min, exp, compare); selects of x, f.
COMMON = 2 * THREEFRY + 3 * 3 + 1 + 2 + 6 + 2

#: The objective's own per-coordinate term (one evaluation), the update
#: of its accumulators from the old and the new term, their selects,
#: and the combine into a value.
OBJECTIVE = {
    # term: abs, sqrt, sin, mul; S += new - old; -S / d
    "schwefel": dict(term=4, update=2, select=1, combine=2),
    # term: mul, mul, cos, mul, sub; 10 d + S
    "rastrigin": dict(term=5, update=2, select=1, combine=1),
    # term: mul | mul, cos; two sums; -20 exp(-0.2 sqrt(S0/d)) - exp(S1/d)
    # + 20 + e
    "ackley": dict(term=3, update=4, select=2, combine=10),
    # term: mul, div | add, sqrt, div, cos; S0 and the product's log and
    # sign (abs, max, log, sub, add, 2 compares, 2 selects, mul)
    "griewank": dict(term=6, update=2 + 10, select=3, combine=4),
    # term: mul; -exp(-0.5 S)
    "exponential": dict(term=1, update=2, select=1, combine=3),
    # term: mul; 1 - cos(2 pi r) + 0.1 r, r = sqrt(S)
    "salomon": dict(term=1, update=2, select=1, combine=6),
}


def ops_per_step(objective: str) -> int:
    """Element operations the algorithm needs for one chain-step."""
    o = OBJECTIVE[objective]
    return COMMON + 2 * o["term"] + o["update"] + o["select"] + o["combine"]


def bytes_per_level(chains: int, dim: int) -> int:
    """HBM bytes of one level of a job: its float32 state read and
    written once, and one value per chain written."""
    return 4 * (2 * chains * dim + chains)


def roofline_seconds(job_levels, peaks) -> tuple:
    """(seconds, bound) of the work ``job_levels`` at the chip's peaks."""
    ops = sum(ops_per_step(o) * n * c * lv for o, _d, n, c, lv in job_levels)
    nbytes = sum(bytes_per_level(c, d) * lv for _o, d, _n, c, lv in job_levels)
    t_ops = ops / peaks["vpu_f32_ops_per_s"]["value"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]["value"]
    return (t_ops, "vpu") if t_ops >= t_mem else (t_mem, "hbm")


def read(run):
    if run.trace is None or run.peaks is None or not run.job_levels:
        return None
    kernel = tracereduce.kernel_seconds(run.trace, KERNEL)
    if not kernel:
        return None
    seconds, _bound = roofline_seconds(run.job_levels, run.peaks)
    return 100.0 * seconds / kernel
