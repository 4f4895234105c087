"""Seconds from the process's start to the window's opening: imports, the
Trainer, the weights, compiling (or reading the compile cache) and the
warm-up steps."""


def read(run):
    return run.setup_s
