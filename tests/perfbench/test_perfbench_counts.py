"""The benchmark's byte counter against hand counts."""
import pytest

from perfbench import counts


@pytest.mark.parametrize("frames,steered,want", [
    (7, True, 7 * (3 + 2) * 4),      # scan: offset, serialization, arrival
    (7, False, 7 * 3 * 4),           # gather: flow id, queue (int32 each)
    (33_728, False, 404_736),
    (148_000, True, 2_960_000),
])
def test_epoch_pass_bytes_count_unpadded_frames(frames, steered, want):
    assert counts.epoch_pass_bytes(frames, steered) == want
