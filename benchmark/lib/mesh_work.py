"""The least time the chips' interconnect needs for a round's collectives,
from what the program's counter says it exchanged.

``xtpu_mesh_allreduce_bytes_total`` counts, a dispatch of the mesh grow
program, the operand bytes ONE shard hands to its collectives (traced
shapes, host arithmetic). A ring all-reduce of B bytes over P chips moves
2 (P - 1) / P x B bytes through each chip's links, so a round's least time
is that over the chip's interconnect bandwidth.

Peak: one TPU v5e chip's inter-chip interconnect, 1,600 Gbit/s = 200 GB/s
(Google Cloud documentation, "TPU v5e" system architecture page; the same
page ``peaks.py`` cites for HBM). Kept here because ``peaks.py`` may not be
edited; a device that is not in the table is an error, never a default."""

from __future__ import annotations

ICI_BYTES_PER_S = {"TPU v5 lite": 200e9, "TPU v5e": 200e9,
                   "rehearsal": 200e9}


def ici_peak(device_kind: str) -> float:
    if device_kind not in ICI_BYTES_PER_S:
        raise KeyError(f"no published interconnect bandwidth for device "
                       f"kind {device_kind!r}; add it to "
                       "benchmark/lib/mesh_work.py with its source")
    return ICI_BYTES_PER_S[device_kind]


def round_exchange_bytes(facts) -> float:
    """Operand bytes a shard handed to the collectives, a round of the
    window (the counter's growth over the window's rounds)."""
    mesh = facts.get("mesh") or {}
    rounds = facts.get("rounds") or 0
    return sum(mesh.get("bytes", {}).values()) / rounds if rounds else 0.0


def round_least_ici_seconds(facts) -> float:
    chips = int(facts.get("chips", 1))
    return (2.0 * (chips - 1) / max(chips, 1) * round_exchange_bytes(facts)
            / ici_peak(facts["device_kind"]))
