"""KV codec: least time of the codec work of the blocks paged in the
traced window (each block's dense bytes read once by the encode and
written once by the decode, at the chip's HBM bandwidth) over the
device time spent inside the paging spans, in %. Nothing is read in a
cell that pages no block."""
from bench import roofline


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    blocks = run["counts"].get("page", 0)
    busy = (trace or {}).get("span_busy_s", {}).get("page", 0.0)
    if not blocks or not busy or not peaks:
        return None
    nbytes = blocks * roofline.kv_codec_bytes(run["config"], run["settings"]["kv_block"])
    return 100.0 * roofline.least_seconds(0, nbytes, peaks) / busy
