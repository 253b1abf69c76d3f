"""Bytes the algorithms need, from their shapes alone.

These counts are the benchmark's own and do not depend on how the program
implements the work: padding and layout do not count.
"""
from __future__ import annotations

# one frame through the epoch device pass: the wire scan reads its emission
# offset and serialization time and writes its arrival (int32 each), and
# the RSS gather, where a port has several queues, reads its flow id and
# writes its queue (int32 each)
SCAN_BYTES_PER_FRAME = 3 * 4
GATHER_BYTES_PER_FRAME = 2 * 4


def epoch_pass_bytes(frames: int, steered: bool) -> int:
    """Least HBM traffic of the epoch pass over ``frames`` unpadded frames."""
    per = SCAN_BYTES_PER_FRAME + (GATHER_BYTES_PER_FRAME if steered else 0)
    return per * frames
