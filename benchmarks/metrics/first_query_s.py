"""Harness clock around the first collect() of every query of the cell, in
set-up: compile or cache load, plus one run."""


def read(run):
    return run["phases"]["first_queries"]
