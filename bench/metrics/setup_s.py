"""Seconds from process start to the first timed epoch or pass: building
the cell, partitioning, storage initialisation, compiling or loading the
layer programs, and one warm-up epoch or pass."""


def read(r):
    return r["setup_s"]
