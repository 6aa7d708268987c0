"""Backend compilations inside the measured window, as the program's
recorder counts them (each into the innermost span open as it compiled)."""


def read(run):
    return run.module("metrics", "_program_spans").compiles(run)
