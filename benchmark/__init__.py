"""The cells benchmark: one cell (a deployment under a traffic mix) served
through NodeHost on the chip and measured from the client's side.

``BENCHMARK.json`` at the root of the repo names the cells and the metrics;
``benchmark/run.py`` is the command.  Everything a later PR may want to add
is data found by name: ``configs/<config>.json``, ``traffic/<traffic>.json``
and ``layer_metrics/<metric>.py``.  The yardstick (load generation, metric
arithmetic, trace reduction, peaks, the reference and the comparison that
decides ``correct``) lives here and takes from the program only the system
under test, its spans, counters and kernel names.
"""
