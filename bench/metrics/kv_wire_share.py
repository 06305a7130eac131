"""KV codec: wire bytes of the blocks pooled in the window over their
dense bytes, in % (the coding rate on served KV), from the ``kv``
counters of ``Engine.stats()`` read at the window's two ends. Nothing
is read where no block was pooled, or where the program keeps no such
counters."""


def read(run):
    k0, k1 = run["stats0"].get("kv", {}), run["stats1"].get("kv", {})
    if "dense_bytes" not in k1:
        return None
    dense = k1["dense_bytes"] - k0.get("dense_bytes", 0)
    if dense <= 0:
        return None
    return 100.0 * (k1["wire_bytes"] - k0.get("wire_bytes", 0)) / dense
