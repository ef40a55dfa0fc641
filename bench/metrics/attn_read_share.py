"""Kernels (kernels/*): the share of the padded block tables that paged
attention read over the window: the engine's ``attn_blocks_read`` over
its ``attn_blocks_table`` counters (each row's live blocks where the
in-place kernel ran, the whole table where the reference gathered it)."""


def read(run):
    start, end = run["counters"]["start"], run["counters"]["end"]
    if "attn_blocks_table" not in end:
        return None
    table = end["attn_blocks_table"] - start["attn_blocks_table"]
    if not table:
        return None
    read = end["attn_blocks_read"] - start["attn_blocks_read"]
    return 100.0 * read / table
