"""Test data: a driver added by adding a file. It runs the training
driver and leaves one value of its own for a metric of its own."""

from benchmark.drivers import train


def run(run):
    outcome = train.run(run)
    run.values["tiny_extra"] = float(outcome["attempted"])
    return outcome
