"""Backend compiles from the window's start to the end of its drain."""


def read(rec):
    return rec["compiles_in_window"]
