"""The chip benchmark: ``python bench/run.py --workload <cell> ...``.

Everything that measures lives here and nowhere else: the graph
generator, the traffic runners, the trace reduction, the work counts, the
peak table and the plain references. The program under test (``src/``) is
imported only as the system being measured.
"""
