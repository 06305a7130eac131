"""Engine host path and KV paging: milliseconds a paged block leaves
the device idle, i.e. the time inside the program's ``engine.page``
spans in the traced window in which no operation ran on the device,
over the number of those spans (``bench/spans.py``). Nothing is read
where the program opened no such span."""
from bench import spans


def read(run):
    att = spans.attribution(run)
    page = (att or {}).get("spans", {}).get("engine.page")
    if not page:
        return None
    return 1e3 * page["idle_s"] / page["count"]
