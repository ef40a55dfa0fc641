"""KV pool: most blocks in use among the samples taken in the window,
over the pool's blocks."""


def read(run):
    kv = run["kv"]
    if not kv["samples"]:
        return None
    return 100.0 * max(kv["samples"]) / kv["num_blocks"]
