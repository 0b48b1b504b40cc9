"""Merged-tier share of decode tokens (%) inside the window, from the
engine's ``tier_stats`` counters: tokens decoded on one hot tenant's
merged weights, over all decode tokens.  Layer: registry / merged tier.
Moves ``tok_s``."""


def read(out):
    t = out.layer.tier_tokens
    total = t["bank_tokens"] + t["merged_tokens"]
    return 100.0 * t["merged_tokens"] / total if total else None
