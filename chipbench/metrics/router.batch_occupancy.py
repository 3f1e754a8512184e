"""Tokens decoded per batched decode round, as a share of the decode
batch (router counters over the window)."""


def read(obs):
    r = obs["router"]
    if not r["decode_rounds"]:
        return None
    return 100.0 * r["decoded_tokens"] / r["decode_rounds"] / obs[
        "decode_batch"]
