"""The reader of attention's block counters: the share of the padded tables
read over the window, and nothing (not an error) from a program without
the counters."""
import pytest

from bench import harness

START = {"iterations": 10, "attn_kernel_steps": 10,
         "attn_blocks_read": 300, "attn_blocks_table": 2000}
END = {"iterations": 30, "attn_kernel_steps": 30,
       "attn_blocks_read": 1500, "attn_blocks_table": 6000}


def test_attn_read_share_reads_the_window():
    read = harness.reader("attn_read_share")
    assert read({"counters": {"start": START, "end": END}}) \
        == pytest.approx(30.0)


@pytest.mark.parametrize("start,end", [
    ({"iterations": 10}, {"iterations": 30}),   # a program without them
    (START, START),                             # an empty window
])
def test_attn_read_share_reads_nothing(start, end):
    read = harness.reader("attn_read_share")
    assert read({"counters": {"start": start, "end": end}}) is None
