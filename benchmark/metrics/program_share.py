"""Share of the traced window's device time that went to a family of
the program's own jitted programs, found by name on the trace's
``XLA Modules`` line (``run.reduced["modules"]``: whole calls inside
the window): device seconds of the programs whose name holds one of
``params["patterns"]`` over ``window_s``.

A trace without that line (the CPU rehearsal) gives nothing to read.
Nor does one that holds a program jax could not name (``jit__unknown``),
where a prefill cannot be told from anything else and a share of zero
would be a wrong number: the driver runs the traced cells of the parent
commit with these files laid over it, and that engine wrapped its
prefills in bare ``functools.partial``s. With every program named, none
matching is a share of zero."""

from benchmark.harness import core


def read(run, params):
    modules = run.reduced["modules"]
    if not modules or any("unknown" in k for k in modules):
        return None
    hits = {
        k: sc for k, sc in modules.items()
        if any(p in k for p in params["patterns"])
    }
    for name, (seconds, calls) in sorted(hits.items()):
        core.log(
            f"programs: {name}: {calls:g} calls, {seconds * 1e3 / calls:.2f} ms each"
        )
    return 100.0 * sum(s for s, _ in hits.values()) / run.reduced["window_s"]
