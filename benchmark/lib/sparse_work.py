"""The least bytes a boosting round has to read of a matrix that is mostly
missing, from a configuration's shapes alone.

``lib/work.py`` counts a dense matrix: one byte for every (row, feature). A
matrix with missing values need not be swept whole: ANY implementation has
to read, a level, each PRESENT value once (one byte at <= 256 bins) and each
row's gradient pair (2 x float32), so a round of ``max_depth`` levels moves
at least

    max_depth * (rows * features * (1 - missing_share) * 1 + rows * 8)  bytes

over HBM. The count is the algorithm's, not a kernel's: a kernel that
streams present values only cannot push a share computed from it past 100,
and a dense sweep of two-byte ids reads far under it. ``missing_share`` is
the configuration's top-level key (what the source publishes; a run's own
share is on its stderr)."""

from __future__ import annotations


def round_present_bytes(config: dict) -> float:
    rows, features = int(config["rows"]), int(config["features"])
    present = rows * features * (1.0 - float(config["missing_share"]))
    return int(config["params"]["max_depth"]) * (present * 1 + rows * 8)


def round_least_seconds(config: dict, hbm_bytes_per_s: float) -> float:
    return round_present_bytes(config) / hbm_bytes_per_s
