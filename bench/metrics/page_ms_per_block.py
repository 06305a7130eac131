"""Engine host path and KV paging: wall milliseconds per block paged
through the engine's host path, from the ``kv`` counters of
``Engine.stats()`` read at the window's two ends (``page_s`` over
``blocks_paged``, host clock). Nothing is read where no block was
paged, or where the program keeps no such counters."""


def read(run):
    k0, k1 = run["stats0"].get("kv", {}), run["stats1"].get("kv", {})
    if "blocks_paged" not in k1:
        return None
    blocks = k1["blocks_paged"] - k0.get("blocks_paged", 0)
    if blocks <= 0:
        return None
    return 1e3 * (k1["page_s"] - k0.get("page_s", 0.0)) / blocks
