"""Set-up: process start (before JAX is imported) to the window's start:
imports, building the cell from the seed, compiling or loading every
program, warm-up."""


def read(run):
    return run.c["setup_s"]
