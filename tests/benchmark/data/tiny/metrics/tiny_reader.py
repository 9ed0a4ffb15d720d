"""Test data: a reader added by adding a file."""


def read(run, params):
    return float(run.values[params["key"]])
