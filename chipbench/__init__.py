"""Chip benchmark of the power-schedule compiler (see ``BENCHMARK.json``
at the checkout's root and ``python -m chipbench --help``)."""
