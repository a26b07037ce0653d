"""Operations and bytes a kernel call needs, counted from the work it
does for the problem, not from the padding of its implementation: a
later implementation is measured against the same work."""
from __future__ import annotations


def game_bestresponse(m: int, k: int) -> tuple:
    """(flops, bytes) of one best-response sweep over ``m`` live
    clusters and ``k`` partitions, in float32.  Per (cluster, partition)
    cost entry: the own-partition test, the load excluding itself
    (multiply, subtract), the balance term (add, two multiplies), the cut
    term (subtract, multiply), their sum, and the running minimum and its
    index (compare, two selects): 12 operations.  Bytes: the (m, k) cut
    mass read once; sizes, boundary totals and current choices read and
    the best choice and its cost written per cluster; the k loads read."""
    flops = 12 * m * k
    nbytes = 4 * m * k + 4 * 5 * m + 4 * k
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of compute over peak FLOP/s and
    bytes over peak bytes/s, and which of the two it is."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
