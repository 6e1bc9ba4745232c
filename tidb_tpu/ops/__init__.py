"""Device kernels (Pallas) for the hot relational primitives.

The compute path is jax/XLA; this package holds the hand-written TPU
kernels for the few primitives XLA lowers poorly — today the segment
aggregation scatter-add (ref: SURVEY.md §7.4's "Pallas hash-table /
segment kernel as the optimized path"). Every kernel has an XLA
reference implementation; `pallas_enabled()` gates dispatch (TPU
backend only; `set_pallas_enabled` overrides it for the tier-1 tests).
What the kernels cost on the chip is the scan cell's ledger line
(ROADMAP B3).
"""

from tidb_tpu.ops.segment_sum import (
    pallas_enabled,
    segment_count,
    segment_sum_f32,
    segment_sum_i64,
    set_pallas_enabled,
)

__all__ = ["segment_count", "segment_sum_f32", "segment_sum_i64",
           "pallas_enabled", "set_pallas_enabled"]
