"""The chip benchmark of the NAM-DB path: one command (``run.py``), cells
named in ``BENCHMARK.json``, and everything each cell needs found by name
under this directory."""
