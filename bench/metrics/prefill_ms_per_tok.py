"""Engine admission: prefill milliseconds per prompt token over the
window, from ``Engine.stats()`` read at the window's two ends (host
clock; reading the prefill's logits forces the device to finish)."""


def read(run):
    s0, s1 = run["stats0"], run["stats1"]
    tokens = s1["prefill_tokens"] - s0["prefill_tokens"]
    if tokens <= 0:
        return None
    secs = (s1["ms_per_token_prefill"] * s1["prefill_tokens"]
            - s0["ms_per_token_prefill"] * s0["prefill_tokens"]) / 1e3
    return 1e3 * secs / tokens
