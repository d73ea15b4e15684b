"""The chip benchmark: one harness (``run.py``) driven by data files.

A configuration is ``configs/<name>.json``, a traffic mix is
``traffic/<name>.json`` and a metric is ``metrics/<name>.py`` (a metric
``<quantity>.<cells>`` with no file of its own is read by
``metrics/<quantity>.py``); its unit, layer and the end-to-end metric it
moves are stated once, in ``BENCHMARK.json``.  The cells
that pair them are the ``workloads`` of ``BENCHMARK.json`` at the root of
the checkout.  Nothing here is imported by the program under test.
"""
