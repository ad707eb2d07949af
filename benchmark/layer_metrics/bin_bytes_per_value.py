"""Bytes a value takes in the resident bin matrix, from the program's gauge
``xtpu_binned_bin_bytes`` (set by the binning pass: 1 where every bin id
and the missing slot fit a byte, 2 at 257 slots). None where the program
has no such gauge."""


def read(facts):
    value = (facts.get('sparse') or {}).get('bin_bytes')
    return float(value) if value else None
