"""The benchmark: served-path cells on the chip, driven by data files.

``BENCHMARK.json`` at the repo's root names every configuration, cell
and metric; this package finds the files that belong to each by that
name (``configs/<config>.json``, ``traffic/<traffic>.json``,
``modes/<mode>.py``, ``topologies/<topology>.py``,
``layer_metrics/<metric>.py``, ``kernels/<kernel>.py``), so a later
cell, metric or deployment comes as new files and appended entries.
The yardstick (traffic, reference, comparison, trace reduction) lives
here; from the program it takes only the system under test and its
public counters and records.
"""
