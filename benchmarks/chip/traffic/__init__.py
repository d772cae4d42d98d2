"""Traffic for the chip benchmark: one general generator over data files.

A cell's traffic mix is the ``traffic`` block of its file under
``workloads/``; ``generator.py`` turns it, with the run's seed, into the
scenario and the detection stream of each call.
"""
