"""Whole step's share of the chip's peak: model FLOPs of every prompt
admitted and every token decoded in the window (from the shapes,
``bench/roofline.py``) over the window's seconds times the peak bf16
FLOP/s, in %."""
import functools

from bench import roofline, window


def read(run):
    peaks, c = run["peaks"], run["config"]
    if not peaks:
        return None
    flops = window.served_flops(run["records"], run["start"], run["end"],
                                functools.partial(roofline.prefill_flops, c),
                                functools.partial(roofline.token_flops, c))
    return 100.0 * flops / ((run["end"] - run["start"]) * peaks["bf16_flops_per_s"])
