"""Chip benchmark of IPA pipeline serving: open-loop traffic against the
program's stage servers, queues and planner, measured from the client's side.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration under ``bench/configs``, its traffic mix under
``bench/traffic``, one reader per metric under ``bench/metrics``, and per
model family a plain reference (``bench/reference``), operation counts
(``bench/counts``) and the mapping onto the program's config
(``bench/program``).
"""
