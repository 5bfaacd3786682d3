"""Row-count shape bucketing.

TPU-specific core design (no reference analog — cudf kernels are shape-dynamic,
XLA compiles per static shape, SURVEY.md section 7 "Hard parts" #1): every
columnar batch is padded up to the nearest bucket in a geometric ladder so a
compiled operator kernel is reused across all batches that land in the same
bucket. Padding rows carry validity=False so masked kernels ignore them; the
true row count travels as a dynamic scalar.
"""
from __future__ import annotations

from typing import List, Sequence

DEFAULT_BUCKETS: List[int] = [1024, 8192, 65536, 262144, 1048576, 4194304]

#: the ladder of a buffer that is made ONCE a query and then read whole by
#: every batch of the other side: a join's build side, and a join's
#: coalesced output (the next join's build side, or an aggregate's one
#: input). Every stream batch is sorted TOGETHER with the whole build side,
#: so a row of padding there costs a sort's time in each of them: the
#: steps above 262,144 rows are x2, and x1.5 / x1.33 from 1,048,576 on
#: (1.46M rows take 1,572,864 where DEFAULT_BUCKETS' x4 step takes
#: 4,194,304; PERF.md, PR 32, has the two readings). Ten shapes, and
#: multiples of the largest beyond it, as bucket_for rounds.
BUILD_BUCKETS: List[int] = DEFAULT_BUCKETS[:4] + [
    524288, 1048576, 1572864, 2097152, 3145728, 4194304]


def bucket_for(num_rows: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    """Smallest bucket >= num_rows; beyond the ladder, round up to the next
    multiple of the largest bucket (keeps compilation count bounded)."""
    if num_rows < 0:
        raise ValueError("negative row count")
    for b in buckets:
        if num_rows <= b:
            return b
    top = buckets[-1]
    return ((num_rows + top - 1) // top) * top


def padded_len(num_rows: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    return bucket_for(num_rows, buckets)
