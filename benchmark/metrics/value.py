"""Reader for a number the driver took on its own clock or read from a
counter: ``run.values[key]``, or a percentile of it where it is a list
of samples. A key the driver did not fill returns nothing."""

from benchmark.harness import stats


def read(run, params):
    v = run.values.get(params["key"])
    if v is None:
        return None
    if "stat" in params:
        if not v:
            return None
        return stats.percentile(v, params["stat"])[0]
    return float(v)
