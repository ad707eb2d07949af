"""The algorithm's work per boosting round, from a configuration's shapes
only. A tree booster has no FLOP count worth the name: its least work is
memory traffic. One level of a depthwise histogram tree has to read every
row's bin ids (one byte a feature at <= 256 bins) and its gradient pair
(2 x float32) at least once, so a round of ``max_depth`` levels moves at least

    max_depth * rows * (features * bin_bytes + 8)   bytes

over HBM. This is a lower bound on bytes (no partition traffic, no margin
update, no histogram write-back), so a share computed from it is a lower bound
on the share of the HBM peak actually used."""

from __future__ import annotations


def round_hbm_bytes(config: dict) -> float:
    bin_bytes = 1 if int(config["params"]["max_bin"]) <= 256 else 2
    return (int(config["params"]["max_depth"]) * int(config["rows"])
            * (int(config["features"]) * bin_bytes + 8))


def round_least_seconds(config: dict, hbm_bytes_per_s: float) -> float:
    return round_hbm_bytes(config) / hbm_bytes_per_s
