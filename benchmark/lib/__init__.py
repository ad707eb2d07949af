"""The yardstick: data, reference, comparison, trace reduction, peaks."""
