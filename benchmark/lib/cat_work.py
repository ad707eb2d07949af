"""The least bytes a boosting round has to read to build the categorical
columns' histograms, from a configuration's shapes alone.

Any implementation of a level's full per-category histograms has to read,
a level, each row's id in every categorical column once (two bytes where a
column passes 256 slots, one otherwise) and each row's gradient pair
(2 x float32), so a round of ``max_depth`` levels moves at least

    max_depth * rows * (sum of the columns' id bytes + 8)   bytes

over HBM. The count is the algorithm's, not a kernel's: the same whether
the columns ride the refine sweep or take a kernel of their own. The
configuration states its columns' categories (``categorical``)."""

from __future__ import annotations


def round_cat_bytes(config: dict) -> float:
    ids = sum(1 if n + 1 <= 256 else 2
              for n in config["categorical"].values())
    return int(config["params"]["max_depth"]) * int(config["rows"]) \
        * (ids + 8)


def round_least_seconds(config: dict, hbm_bytes_per_s: float) -> float:
    return round_cat_bytes(config) / hbm_bytes_per_s
