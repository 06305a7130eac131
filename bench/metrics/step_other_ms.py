"""Engine host path, KV paging included: per engine step of the
window, the harness's wall time of ``step()`` less the prefill and
decode seconds the engine counted inside it; the mean, in ms."""


def read(run):
    steps = run["steps"]
    if not steps:
        return None
    other = [s["wall_s"] - s["prefill_s"] - s["decode_s"] for s in steps]
    return 1e3 * sum(other) / len(other)
