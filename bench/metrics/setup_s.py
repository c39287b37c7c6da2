"""Seconds from the start of the process to the window's start: init,
weights, profiling, warm-up and any compiles."""


def read(rec):
    return rec["setup_s"]
