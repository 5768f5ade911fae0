"""The least time the chip could take for a call, from its shape and the
peaks in ``bench/peaks.json``.  A share of a roofline is this over the
device time that the call took, so it cannot pass 100% as long as the
operations and bytes counted are ones that any implementation must do."""
from __future__ import annotations


def nearest_least_seconds(m: int, n: int, d: int, k: int,
                          peak: dict) -> float:
    """Exact k-nearest search of ``m`` queries over ``n`` base vectors of
    ``d`` float32 values: every query meets every base vector once
    (``2 m n d`` operations, at the bfloat16 peak, the chip's highest), and
    the least bytes are the base read once, the queries read once and the
    k (score, id) pairs written.  Scores written and read back, a base read
    once per block of queries: none of it is counted, so the bound holds
    whatever ``chunk_size`` or kernel the call runs with."""
    flops = 2.0 * m * n * d
    nbytes = 4.0 * n * d + 4.0 * m * d + 8.0 * m * k
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
